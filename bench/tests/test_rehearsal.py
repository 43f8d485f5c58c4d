"""The CPU rehearsal of each cell: the same code as a chip run, at the tiny
sizes of each file's ``rehearsal`` section, in a process of its own."""
import json
import os
import subprocess
import sys

import pytest

from conftest import CELLS, ROOT


def run(*args, env=None, cwd=ROOT, timeout=600):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    e.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=e, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_and_is_correct(cell, trace):
    p = run(os.path.join("bench", "run.py"), "--workload", cell, "--seed", "3000000019", "--seconds", "2",
            "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, p.stderr[-3000:]
    assert out["device"]["platform"] == "cpu"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks" and out["checks"]
    last = p.stderr.strip().splitlines()[-len(out["checks"]):]
    assert all(line.startswith("check ") and " limit " in line for line in last)
    if trace == 0:
        spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
        want = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
        assert set(out["metrics"]) == want
    else:
        assert out["metrics"] == {}      # no device trace off the chip: nothing to read
    assert "compilations inside the window: 0" in p.stderr


def test_off_the_chip_it_refuses_before_compiling():
    p = run(os.path.join("bench", "run.py"), "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "compile" not in p.stderr.lower().replace("compile cache", "")
    assert not p.stdout.strip()


def test_without_the_program_it_exits_without_a_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    p = run(os.path.join("bench", "run.py"), "--workload", CELLS[0], "--seed", "1",
            "--seconds", "1", "--trace", "0", "--rehearse", cwd=str(tmp_path))
    assert p.returncode != 0
    assert not p.stdout.strip()
