"""Test config.  NOTE: no XLA_FLAGS here — tests must see the real (single)
CPU device; multi-device tests spawn subprocesses that set
--xla_force_host_platform_device_count themselves."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_subprocess(script: str, devices: int = 0, timeout: int = 300,
                   env: dict = None) -> str:
    """Run a python snippet in a fresh interpreter (optionally with N forced
    host devices and extra environment variables) and return stdout; raises
    on nonzero exit."""
    env = {**os.environ, **(env or {})}
    env["PYTHONPATH"] = SRC
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}\nstdout:\n{out.stdout[-2000:]}"
    return out.stdout


@pytest.fixture(scope="session")
def subproc():
    return run_subprocess
