"""Each fault the cell's timed path can have makes ``correct`` false, and
the control (the reference in float8, in the program's place) fails at
least one of the cell's numbers, while the program passes them."""
import json
import os
import subprocess
import sys

import pytest

from conftest import CELLS, ROOT


def _env():
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("XLA_FLAGS", None)
    return e


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", ["token_altered", "serve_half_batch"])
def test_a_broken_timed_path_is_not_correct(workload, fault):
    p = subprocess.run([sys.executable, os.path.join("bench", "tests", "fault_run.py"), workload, fault,
                        "3000000023"], cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is False, out


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_fails_and_the_program_passes(workload):
    p = subprocess.run([sys.executable, os.path.join("bench", "control.py"), "--workload", workload,
                        "--seeds", "3000000029", "--seconds", "2", "--rehearse"],
                       cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    checks = out["checks"]
    own = {k: v for k, v in checks.items() if not k.startswith("control_")}
    assert own and all(v["value"] <= v["limit"] for v in own.values()), checks
    control = {k: v for k, v in checks.items() if k.startswith("control_")}
    assert any(v["value"] > v["limit"] for v in control.values()), checks
    for k, v in control.items():
        base = checks[k[len("control_"):]]["value"]
        assert v["value"] >= 3 * base or v["value"] <= v["limit"], (k, v, base)
