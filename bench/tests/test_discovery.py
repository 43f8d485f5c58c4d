"""A configuration, a traffic mix and a per-layer metric are added as new
files and entries only; the harness finds each by its name.  So is an
architecture other than a dense transformer: its module (declaration,
weight table, reference, least work) is one more file, which the
configuration names."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT

GRANITE = os.path.join(ROOT, "bench", "tests", "data", "granite")


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return out


def _copy(tmp):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(tmp, "src"))


def _add(tmp):
    b = os.path.join(tmp, "bench")
    c = json.load(open(os.path.join(b, "configs", "olmo-1b.json")))
    c["name"] = "olmo-1b-copy"
    json.dump(c, open(os.path.join(b, "configs", "olmo-1b-copy.json"), "w"))
    t = json.load(open(os.path.join(b, "traffic", "p512-sat.json")))
    t["rehearsal"]["rate_per_s"] = 2.0
    json.dump(t, open(os.path.join(b, "traffic", "p512-slow.json"), "w"))
    json.dump(json.load(open(os.path.join(b, "limits", "olmo1b-p512-sat.json"))),
              open(os.path.join(b, "limits", "copy-p512-slow.json"), "w"))
    with open(os.path.join(b, "metrics", "engine.waves_traced.py"), "w") as f:
        f.write("def read(r):\n    return float(len(r.record.get('waves', []))) or None\n")
    spec = json.load(open(os.path.join(tmp, "BENCHMARK.json")))
    spec["configs"].append({"name": "olmo-1b-copy", "source": "https://huggingface.co/allenai/OLMo-1B",
                            "file": "bench/configs/olmo-1b-copy.json", "reduced": [],
                            "why": "a copy"})
    spec["workloads"].append({"name": "copy-p512-slow", "config": "olmo-1b-copy",
                              "traffic": "p512-slow", "chips": 1, "why": "a copy at half the rate"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "olmo1b-p512-sat" in m["workloads"]:
            m["workloads"].append("copy-p512-slow")
    spec["per_layer"].append({"name": "engine.waves_traced", "unit": "waves", "better": "higher",
                              "source": "program_counter", "layer": "serving engine",
                              "moves": "serve_tokens_per_s", "workloads": ["copy-p512-slow"]})
    json.dump(spec, open(os.path.join(tmp, "BENCHMARK.json"), "w"), indent=1)


def _add_granite(tmp, reference="moe_transformer"):
    """The mixture-of-experts fixture as a model_config change would add it:
    a configuration, its architecture's module, traffic, limits, and entries
    in BENCHMARK.json; the new cell goes into every metric's list."""
    b = os.path.join(tmp, "bench")
    c = json.load(open(os.path.join(GRANITE, "granite-moe-1b-a400m.json")))
    c["reference"] = reference
    json.dump(c, open(os.path.join(b, "configs", "granite-moe-1b-a400m.json"), "w"))
    if reference == "moe_transformer":
        shutil.copy(os.path.join(GRANITE, "moe_transformer.py"), os.path.join(b, "reference"))
    shutil.copy(os.path.join(GRANITE, "p512-moe.json"), os.path.join(b, "traffic"))
    shutil.copy(os.path.join(GRANITE, "granite-p512.json"), os.path.join(b, "limits"))
    spec = json.load(open(os.path.join(tmp, "BENCHMARK.json")))
    spec["configs"].append({"name": "granite-moe-1b-a400m", "source": c["source"],
                            "file": "bench/configs/granite-moe-1b-a400m.json", "reduced": [],
                            "why": "grouped-query attention and 32 experts, top 8"})
    spec["workloads"].append({"name": "granite-p512", "config": "granite-moe-1b-a400m",
                              "traffic": "p512-moe", "chips": 1, "why": "the experts under chat traffic"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and "olmo1b-p512-sat" in m["workloads"]:
            m["workloads"].append("granite-p512")
    json.dump(spec, open(os.path.join(tmp, "BENCHMARK.json"), "w"), indent=1)


def _rehearse(tmp, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "bench/run.py", "--workload", workload, "--seed", "9",
                           "--seconds", "2", "--trace", "0", "--rehearse"], cwd=tmp, env=env,
                          capture_output=True, text=True, timeout=600)


def test_a_cell_added_as_files_only(tmp_path):
    tmp = str(tmp_path)
    _copy(tmp)
    before = _digest(os.path.join(tmp, "bench"))
    _add(tmp)
    after = _digest(os.path.join(tmp, "bench"))
    assert all(after[k] == v for k, v in before.items())      # nothing existing was edited
    assert len(after) == len(before) + 4

    sys.path.insert(0, tmp)
    try:
        from bench import harness

        cell = harness.load_cell(tmp, "copy-p512-slow", rehearse=True)
        assert cell.c["name"] == "olmo-1b-copy" and cell.t["rate_per_s"] == 2.0
        assert [m["name"] for m in cell.per_layer] == ["engine.waves_traced"]
        reader = harness.load_module(os.path.join(tmp, "bench", "metrics", "engine.waves_traced.py"))
        r = harness.Reading(None, 0.0, 1.0, {"waves": [{}, {}]}, cell.c, None)
        assert reader.read(r) == 2.0
        old = harness.load_cell(tmp, "olmo1b-p512-sat")
        assert "engine.waves_traced" not in [m["name"] for m in old.per_layer]
    finally:
        sys.path.remove(tmp)

    p = _rehearse(tmp, "copy-p512-slow")
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["attempted"] == 4


READ_GRANITE = """
import json, os, shutil, sys, tempfile
sys.path.insert(0, os.getcwd())
from bench import harness, peaks
from bench import trace_reduce as tr
tempfile.tempdir = tempfile.mkdtemp()
d = os.path.join(tempfile.tempdir, "bench_trace_recorded")
os.makedirs(d)
shutil.copy(sys.argv[1], d)
cell = harness.load_cell(os.getcwd(), "granite-p512", rehearse=True)
t = tr.load(os.path.join(d, os.path.basename(sys.argv[1])), harness.ANNOTATIONS)
lo, hi = t.window()
r = harness.Reading(t, lo, hi, json.loads(sys.argv[2]), cell.c, peaks.peak_for("TPU v5 lite"))
got = {m["name"]: harness.load_module(os.path.join("bench", "metrics", m["name"] + ".py")).read(r)
       for m in cell.per_layer}
shutil.rmtree(tempfile.tempdir)
print(json.dumps(got))
"""


def test_another_architecture_added_as_files_only(tmp_path):
    """A mixture of experts, which the dense reference refuses, rehearses
    through the same serve driver once its module is there, and the
    generic readers read its cell through that module."""
    tmp = str(tmp_path)
    _copy(tmp)
    before = _digest(os.path.join(tmp, "bench"))
    _add_granite(tmp)
    after = _digest(os.path.join(tmp, "bench"))
    assert all(after[k] == v for k, v in before.items())      # nothing existing was edited
    assert len(after) == len(before) + 4

    p = _rehearse(tmp, "granite-p512")
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, p.stderr[-3000:]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "compilations inside the window: 0" in p.stderr

    data = os.path.join(ROOT, "bench", "tests", "data")
    record = json.load(open(os.path.join(data, "serve_spans_tiny.json")))["record"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", READ_GRANITE, os.path.join(data, "serve_spans_tiny.xplane.pb"),
                        json.dumps(record)], cwd=tmp, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(got) == {m["name"] for m in spec["per_layer"]}
    assert all(v is not None and v > 0 for v in got.values()), got
    # the FLOPs behind serve.wave_mfu are the experts': the same trace under
    # the dense configuration it was recorded with reads 0.0010086213128851758
    from bench import costs, harness

    moe = harness.load_module(os.path.join(GRANITE, "moe_transformer.py"))
    c = json.load(open(os.path.join(GRANITE, "granite-moe-1b-a400m.json")))
    c.update(c.pop("rehearsal"))
    olmo = json.load(open(os.path.join(data, "serve_spans_tiny.json")))["c"]

    def flops(a, c):
        return sum(a.forward_flops(c, P, costs.causal_sum(0, P), 1)
                   + a.forward_flops(c, o - 1, costs.causal_sum(P, o - 1), o - 1)
                   for w in record["waves"] for P, o in zip(w["prompt"], w["out"]))

    assert got["serve.wave_mfu"] / 0.0010086213128851758 == \
        pytest.approx(flops(moe, c) / flops(harness.arch(olmo), olmo), rel=1e-12)


def test_an_architecture_its_reference_does_not_compute_is_refused(tmp_path):
    """The same mixture of experts pointed at the dense reference exits
    non-zero with no result, before it compiles anything."""
    tmp = str(tmp_path)
    _copy(tmp)
    _add_granite(tmp, reference="dense_transformer")
    p = _rehearse(tmp, "granite-p512")
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "'dense_transformer' does not" in p.stderr and "is_moe True" in p.stderr, p.stderr[-3000:]
