"""CPU rehearsal of ``chip_smoke.py``'s control flow.

The script refuses anything but a TPU, so these tests steer its module
constants (the required platform, the kernel marker that interpret mode
cannot emit, and the problem sizes) and run its phases at a tiny size:
one device in-process, four virtual devices in a subprocess.  The
compile cache stays off: ``enable_compile_cache`` is replaced.
"""

import importlib.util
import json

import pytest

from conftest import REPO

TINY = {"N_C2C": 16, "N_DEALIAS": 8, "N_SERVE": 8, "SERVE_REQUESTS": 3}


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke(monkeypatch):
    cs = _load()
    for k, v in TINY.items():
        monkeypatch.setattr(cs, k, v)
    monkeypatch.setattr(cs, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(cs, "KERNEL_MARKER", "")
    monkeypatch.setattr(cs, "enable_compile_cache", lambda: "(off)")
    return cs


def test_refuses_without_tpu(capsys):
    """On a machine without a TPU the script exits non-zero before any
    phase and never prints the ok line."""
    cs = _load()
    assert cs.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok": true' not in out
    assert "no tpu found" in err


def test_one_device_phases(smoke, capsys):
    """Phases (a)-(d) pass on one device and the last line is exactly the
    result object."""
    assert smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    phases = [ln for ln in lines if ln.startswith("[")]
    assert [p.split()[0] for p in phases] == ["[a", "[b", "[c", "[d"]
    assert all("fwd_bwd_s=" in p or "serve_s=" in p for p in phases)
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_bounds_fail_loudly(smoke, capsys):
    """A phase over its bound makes the script exit non-zero with no ok
    line (a bf16-pass DFT would fail the lossless bound this way)."""
    smoke.LOSSLESS_TOL = 1e-12
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok": true' not in out and "exceeds the bound" in err


def test_four_device_phases(subproc):
    """``--chips 4``: only the 2x2-pencil and 4-slab phases (each payload)
    plus the one-chip comparison run, and the count is 4."""
    out = subproc(f"""
import importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", {str(REPO / "chip_smoke.py")!r})
cs = importlib.util.module_from_spec(spec); spec.loader.exec_module(cs)
for k, v in {TINY!r}.items():
    setattr(cs, k, v)
cs.REQUIRED_PLATFORM, cs.KERNEL_MARKER = "cpu", ""
cs.enable_compile_cache = lambda: "(off)"
sys.exit(cs.main(["--chips", "4"]))
""", ndev=4)
    lines = out.strip().splitlines()
    phases = [ln.split("]")[0] + "]" for ln in lines if ln.startswith("[") and "memory" not in ln]
    assert phases == ["[one-chip c2c jnp 16^3]"] + [
        f"[{m} {c} 16^3]" for m in ("pencil 2x2", "slab 4")
        for c in ("complex64", "bf16 pallas", "int8 pallas")]
    assert sum(ln.startswith("[memory]") for ln in lines) == 4
    assert json.loads(lines[-1])["device"]["count"] == 4
