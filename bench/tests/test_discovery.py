"""A configuration, a traffic mix and a per-layer metric are added as new
files and entries only; the harness finds each by its name."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT


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

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "copy-p512-slow", "--seed", "9",
                        "--seconds", "2", "--trace", "0", "--rehearse"], cwd=tmp, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["attempted"] == 4
