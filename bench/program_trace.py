"""What the program marks in its own trace, for the per-layer readers.

Two kinds of mark, both in the profiler's ``.xplane.pb`` and so on the
trace's one clock:

- the serving engine's host spans (``TraceAnnotation``): ``engine.wave``
  around a wave and, inside it, ``engine.prefill`` and, per decode step,
  ``engine.dispatch``, ``engine.token_sync`` and ``engine.bookkeeping``;
- the model's named scopes (``jax.named_scope``), which reach each compiled
  instruction's ``op_name`` metadata.  The device's ``XLA Ops`` events name
  only the instruction; the profiler also stores each program's optimized
  HLO (an ``HloProto``, the stat ``Hlo Proto`` on the ``/host:metadata``
  plane), and that is read here for the scope of each instruction.

The harness reduces the trace with its annotations (the engine's spans
among them) and gives its readers no path to the file, so ``of(reading)``
finds the harness's trace file again (``bench_trace_*`` under the temporary
directory, where the harness writes it): the one whose annotations are the
reading's.  It reads the file once for all readers of a run.  A program
without these marks (no ``engine.*`` span, no model scope) gives nothing to
read.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import sys
import tempfile
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

from bench import harness
from bench import trace_reduce as tr

DECODE = "jit__decode"
ENGINE_SPANS = harness.ENGINE_SPANS
STEP_SPANS = ("engine.dispatch", "engine.token_sync", "engine.bookkeeping")
SCOPES = ("embed", "layers", "head", "norm", "attn", "kv_write", "mlp", "sample")
UNSCOPED = "unscoped"      # an instruction under no model scope
MISSING = "missing"        # an executed instruction the program's HLO does not name
PLUMBING = ("layers", UNSCOPED)   # the layer scan's own slicing and stacking, XLA's copies

_TEXT_NAME = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_TEXT_OP_NAME = re.compile(r'op_name="([^"]*)"')


def innermost(op_name: str) -> str:
    """The innermost model scope of an ``op_name`` such as
    ``jit(_decode)/layers/while/body/closed_call/attn/kv_write/dynamic_update_slice``;
    its last part is the primitive, never a scope."""
    for part in reversed(op_name.split("/")[:-1]):
        if part in SCOPES:
            return part
    return UNSCOPED


def scopes_from_text(text: str) -> Dict[str, str]:
    """Instruction name -> innermost scope, over every computation of a
    compiled program's text (``jax.stages.Compiled.as_text()``)."""
    out = {}
    for line in text.splitlines():
        m = _TEXT_NAME.match(line)
        if m:
            op = _TEXT_OP_NAME.search(line)
            out[m.group(1)] = innermost(op.group(1) if op else "")
    return out


# -- the profiler's HLO, by the protobuf wire format --------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int, or a memoryview of a
    length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif kind in (1, 5):
            step = 8 if kind == 1 else 4
            v, i = buf[i:i + step], i + step
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def _str(v) -> str:
    return bytes(v).decode()


def hlo_protos(path: str) -> Dict[str, memoryview]:
    """Program name as the trace has it (``jit__decode(<id>)``) -> its
    ``HloProto``, from the ``/host:metadata`` plane of an ``XSpace``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for k, plane in _fields(space):                     # XSpace.planes = 1
        if k != 1:
            continue
        fields = list(_fields(plane))
        if not any(f == 2 and _str(v) == "/host:metadata" for f, v in fields):  # XPlane.name
            continue
        names = {}
        for f, entry in fields:                          # XPlane.stat_metadata = 5 (map)
            if f == 5:
                md = dict(_fields(entry)).get(2)
                m = dict(_fields(md)) if md is not None else {}
                names[m.get(1, 0)] = _str(m.get(2, b""))
        for f, entry in fields:                          # XPlane.event_metadata = 4 (map)
            if f != 4:
                continue
            md = dict(_fields(entry)).get(2)
            if md is None:
                continue
            name = ""
            for g, v in _fields(md):                     # XEventMetadata: name = 2, stats = 5
                if g == 2:
                    name = _str(v)
                elif g == 5:
                    stat = dict(_fields(v))              # XStat: metadata_id = 1, bytes_value = 6
                    if names.get(stat.get(1)) == "Hlo Proto" and 6 in stat:
                        out[name] = stat[6]
    return out


def scopes_from_proto(hlo_proto) -> Dict[str, str]:
    """Instruction name -> innermost scope, over every computation of an
    ``HloProto``."""
    out = {}
    module = dict(_fields(hlo_proto)).get(1)             # HloProto.hlo_module = 1
    for k, comp in _fields(module if module is not None else b""):
        if k != 3:                                       # HloModuleProto.computations = 3
            continue
        for g, ins in _fields(comp):                     # HloComputationProto.instructions = 2
            if g != 2:
                continue
            name, op_name = "", ""
            for f, v in _fields(ins):                    # HloInstructionProto: name = 1, metadata = 7
                if f == 1:
                    name = _str(v)
                elif f == 7:
                    op_name = _str(dict(_fields(v)).get(2, b""))   # OpMetadata.op_name = 2
            out[name] = innermost(op_name)
    return out


def program_scopes(path: str, module: str, executed: Iterable[str] = ()) -> Optional[Dict[str, str]]:
    """The scope map of the program named ``module`` in the trace (the
    profiler stores every program the process holds): of several so named,
    the one that names most of the ``executed`` instructions."""
    maps = [scopes_from_proto(p) for name, p in hlo_protos(path).items()
            if name.split("(", 1)[0] == module]
    executed = set(executed)
    return max(maps, key=lambda m: len(executed & m.keys())) if maps else None


# -- device time by scope -----------------------------------------------------


def executed_ops(dev: tr.Device, module: str, lo: float, hi: float) -> Iterator[tr.Op]:
    """The leaf operations of the executions of ``module`` that start inside
    [lo, hi]; a loop counts through the operations of its body, not as one."""
    execs = tr.executions(dev, module, lo, hi)
    j = 0
    for o in dev.ops:
        if o.container:
            continue
        while j < len(execs) and execs[j][1] < o.start:
            j += 1
        if j == len(execs):
            return
        if o.start >= execs[j][0]:
            yield o


def scope_time(dev: tr.Device, module: str, lo: float, hi: float,
               scopes: Dict[str, str]) -> Dict[str, float]:
    """Device seconds per innermost scope over ``executed_ops``;
    instructions missing from ``scopes`` count under ``MISSING``."""
    acc: Dict[str, float] = {}
    for o in executed_ops(dev, module, lo, hi):
        key = scopes.get(o.name, MISSING)
        acc[key] = acc.get(key, 0.0) + (o.end - o.start)
    return acc


# -- the trace of a reading ---------------------------------------------------


@dataclasses.dataclass
class ProgramTrace:
    trace: tr.Trace                            # with the engine's spans
    decode_scopes: Optional[Dict[str, str]]    # the decode program's scope map

    def spans(self, names) -> List[tr.Interval]:
        return tr.union((s, e) for s, e, n in self.trace.spans if n in names)


_cache: List = []     # [the reading's trace, its ProgramTrace]


def _find(r) -> Optional[Tuple[str, tr.Trace]]:
    pattern = os.path.join(tempfile.gettempdir(), "bench_trace_*", "**", "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime, reverse=True):
        t = tr.load(path, harness.ANNOTATIONS)
        if t.spans == r.trace.spans:
            return path, t
    return None


def of(r) -> Optional[ProgramTrace]:
    """The program's marks in the trace behind the reading ``r``."""
    if _cache and _cache[0] is r.trace:
        return _cache[1]
    found = _find(r)
    pt = None
    if found:
        dev = r.trace.devices[min(r.trace.devices)]
        ran = (o.name for o in executed_ops(dev, DECODE, r.lo, r.hi))
        pt = ProgramTrace(found[1], program_scopes(found[0], DECODE, ran))
    _cache[:] = [r.trace, pt]
    if pt is not None:
        _note(r, pt)
    return pt


def decode_ms(r) -> Optional[Dict[str, float]]:
    """Device milliseconds per decode execution by innermost scope; None
    where the decode program carries no model scope."""
    pt = of(r)
    if pt is None or not pt.decode_scopes or set(pt.decode_scopes.values()) == {UNSCOPED}:
        return None
    dev = r.trace.devices[min(r.trace.devices)]
    n = len(tr.executions(dev, DECODE, r.lo, r.hi))
    if not n:
        return None
    return {k: 1e3 * v / n for k, v in scope_time(dev, DECODE, r.lo, r.hi, pt.decode_scopes).items()}


def _note(r, pt: ProgramTrace) -> None:
    """One line on standard error: what the readers of this module see."""
    dev = r.trace.devices[min(r.trace.devices)]
    execs = tr.executions(dev, DECODE, r.lo, r.hi)
    spans = sorted({n for _, _, n in pt.trace.spans if n in ENGINE_SPANS})
    idle = ", ".join(f"{k} {v:.4f}" for k, v in tr.idle_by_host(pt.trace, r.lo, r.hi))
    msg = f"engine spans {spans}; idle device seconds by innermost host span: {idle}"
    if execs and pt.decode_scopes:
        by = scope_time(dev, DECODE, r.lo, r.hi, pt.decode_scopes)
        ops = sum(by.values())
        step = sum(e - s for s, e in execs)
        plumbing: Dict[str, float] = {}
        for o in executed_ops(dev, DECODE, r.lo, r.hi):
            if pt.decode_scopes.get(o.name) in PLUMBING:
                plumbing[o.name] = plumbing.get(o.name, 0.0) + (o.end - o.start)
        per = 1e3 / len(execs)
        parts = ", ".join(f"{k} {per * v:.3f}" for k, v in sorted(by.items(), key=lambda kv: -kv[1]))
        top = ", ".join(f"{k} {per * v:.3f}" for k, v in sorted(plumbing.items(), key=lambda kv: -kv[1])[:8])
        msg += (f"; {DECODE}: {len(execs)} executions, {per * step:.3f} ms each, of which "
                f"operations {per * ops:.3f} ms ({100 * ops / step:.2f}%): {parts}; "
                f"not in the program's HLO {100 * by.get(MISSING, 0.0) / ops:.3f}% of operation time; "
                f"largest in {'/'.join(PLUMBING)}: {top}")
    print(f"[program_trace] {msg}", file=sys.stderr, flush=True)
