"""What every cell shares: finding its files by name (an architecture's
module among them), the device check, the compile cache and compile
counter, per-layer readers over the trace, and the result line."""
from __future__ import annotations

import dataclasses
import glob
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
from typing import Any, Dict, List, Optional

# host spans the trace is reduced with: the harness's own around its calls,
# and the serving engine's inside ``serve_wave`` (``serving/engine.py``), so
# that idle device time is split by what the engine was doing
ENGINE_SPANS = ("engine.wave", "engine.prefill", "engine.dispatch", "engine.token_sync",
                "engine.bookkeeping")
ANNOTATIONS = ("serve_wave", "wait_arrivals") + ENGINE_SPANS


class Refused(SystemExit):
    """The run cannot measure here; exits non-zero before any result."""

    def __init__(self, why: str):
        super().__init__(f"bench: {why}")


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _by_name(directory: str, name: str) -> str:
    p = os.path.join(directory, name + ".json")
    if not os.path.isfile(p):
        raise Refused(f"no file for {name!r} in {directory}")
    return p


def load_module(path: str):
    """A module from its file: per-layer readers and references are found
    by name, not imported by a list kept in code."""
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch(c: Dict[str, Any]):
    """The module of the architecture that the configuration ``c`` names
    under ``reference`` (``bench/reference/<name>.py``): everything the
    benchmark knows of it, its declaration ``COMPUTES`` (and ``FIELDS``),
    its weight table ``shapes``, its reference ``logits`` and its least
    work ``forward_flops`` and ``decode_least``."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "reference", c["reference"] + ".py")
    if not os.path.isfile(path):
        raise Refused(f"no architecture module {path} for {c['name']!r}")
    return load_module(path)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    c: Dict[str, Any]                 # configuration, as run
    t: Dict[str, Any]                 # traffic
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]  # the end-to-end metrics this cell reports
    per_layer: List[Dict[str, Any]]   # the per-layer metrics this cell reports


def _applies(metric: Dict, cell: str) -> bool:
    return cell in metric["workloads"] if "workloads" in metric else True


def load_cell(root: str, workload: str, rehearse: bool = False) -> Cell:
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[workload]
    conf = {x["name"]: x for x in spec["configs"]}[w["config"]]
    bench = os.path.join(root, "bench")
    c = _json(os.path.join(root, conf["file"]))
    t = _json(_by_name(os.path.join(bench, "traffic"), w["traffic"]))
    limits = _json(_by_name(os.path.join(bench, "limits"), workload))
    if rehearse:
        for d in (c, t, limits):
            d.update(d.get("rehearsal", {}))
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return Cell(workload, int(w["chips"]), c, t, limits, e2e, layer)


class Compiles:
    """Seconds and events of JAX's tracing, lowering and compiling, from its
    own monitoring events (as ``chip_smoke.py`` counts them)."""

    def __init__(self):
        import jax

        self.s = 0.0
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.s += secs
            self.n += 1


@dataclasses.dataclass
class Run:
    """What a kind's driver gets."""
    root: str
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    compiles: Compiles
    t_start: float
    rehearse: bool
    control: bool = False
    trace_dir: Optional[str] = None

    @property
    def c(self):
        return self.cell.c

    @property
    def t(self):
        return self.cell.t

    def note(self, msg: str) -> None:
        print(f"[{self.cell.name}] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Outcome:
    """What a kind's driver returns."""
    metrics: Dict[str, float]                 # end-to-end, by name
    attempted: int
    failed: int
    checks: Dict[str, List[float]]            # name -> [value, limit]
    record: Dict[str, Any]                    # host-side facts for the readers
    memory_peak_bytes: int
    traced: bool = False                      # the profiler wrote to run.trace_dir


@dataclasses.dataclass
class Reading:
    """What a per-layer reader gets: the reduced trace of the traced
    sub-window [lo, hi], the driver's record, the configuration, and the
    chip's peak."""
    trace: Any
    lo: float
    hi: float
    record: Dict[str, Any]
    c: Dict[str, Any]
    peak: Any


def _device_check(cell: Cell, rehearse: bool):
    import jax

    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < cell.chips:
        raise Refused(f"cell {cell.name} needs {cell.chips} chips, JAX found {len(devs)}")
    return devs[: cell.chips]


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, t_start: float = 0.0, control: bool = False) -> Dict:
    cell = load_cell(root, workload, rehearse)
    devs = _device_check(cell, rehearse)
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"the system under test is not at {src}/repro")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    if not rehearse:
        from repro.launch.compile_cache import use_compile_cache

        cache = use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        print(f"[{workload}] compile cache: {cache}", file=sys.stderr, flush=True)
    run = Run(root, cell, seed, seconds, trace, devs, Compiles(), t_start, rehearse, control)
    kind = importlib.import_module(f"bench.kinds.{cell.t['kind']}")
    tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    run.trace_dir = tmp
    try:
        out = kind.run(run)
        return _result(run, out)
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)


def _result(run: Run, out: Outcome) -> Dict:
    from bench import peaks, trace_reduce

    dev = run.devices[0]
    cell = run.cell
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(run.devices),
              "memory_peak_bytes": int(out.memory_peak_bytes)}
    result: Dict[str, Any] = {}
    metrics: Dict[str, Dict[str, Any]] = {}
    if run.trace:
        found = sorted(glob.glob(os.path.join(run.trace_dir, "**", "*.xplane.pb"), recursive=True))
        tr = trace_reduce.load(found[-1], ANNOTATIONS) if out.traced and found else None
        win = tr.window() if tr else None
        if tr is not None and win is not None and tr.devices:
            lo, hi = win
            peak = peaks.peak_for(dev.device_kind) if dev.platform == "tpu" else None
            reading = Reading(tr, lo, hi, out.record, run.c, peak)
            for m in cell.per_layer:
                reader = load_module(os.path.join(run.root, "bench", "metrics", m["name"] + ".py"))
                v = reader.read(reading)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
            busy = [trace_reduce.total(trace_reduce.busy(d, lo, hi)) for d in tr.devices.values()]
            device["busy_s"] = sum(busy) / len(busy)
            device["window_s"] = hi - lo
            result["breakdown"] = {"device_ops": trace_reduce.top_ops(tr, lo, hi),
                                   "idle_gaps": trace_reduce.idle_by_host(tr, lo, hi)}
    else:
        for m in cell.end_to_end:
            if m["name"] in out.metrics:
                metrics[m["name"]] = {"value": float(out.metrics[m["name"]]), "unit": m["unit"]}
    # a number with no value, or a cell whose limit is not yet set from
    # chip readings (null in its limits file), is never correct
    correct = out.failed == 0 and all(v is not None and lim is not None and v <= lim
                                      for v, lim in out.checks.values())
    result = {"correct": bool(correct), "attempted": int(out.attempted), "failed": int(out.failed),
              "metrics": metrics, "device": device, **result,
              "checks": {k: {"value": None if v is None else float(v), "limit": None if lim is None else float(lim)}
                         for k, (v, lim) in out.checks.items()}}
    return result


def print_result(result: Dict) -> None:
    for k, ch in result["checks"].items():
        print(f"check {k} {ch['value']!r} limit {ch['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
