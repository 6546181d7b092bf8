"""Shared fixtures.  NOTE: no XLA_FLAGS here — unit tests see 1 device;
multi-device coverage runs in subprocesses (tests/_mp.py)."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src"


def run_devices(code: str, ndev: int = 8, timeout: int = 900) -> str:
    """Run ``code`` in a fresh python with ``ndev`` virtual host (CPU)
    devices — never on an accelerator the parent may hold."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = str(SRC)
    proc = subprocess.run([sys.executable, "-c", code], env=env, timeout=timeout,
                          capture_output=True, text=True)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\n--- stdout ---\n"
            f"{proc.stdout[-4000:]}\n--- stderr ---\n{proc.stderr[-6000:]}")
    return proc.stdout


@pytest.fixture(scope="session")
def subproc():
    return run_devices
