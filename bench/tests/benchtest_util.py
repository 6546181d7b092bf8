"""Helpers of the benchmark's own tests: a copy of the benchmark with its
configurations cut to a size the CPU runs in seconds, and runs of a cell
in it that skip only the harness's look for a chip."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: the configurations at a CPU size: same transforms, dtypes and limits
TINY = {
    "tgv_dns_re1600": {"modes": 16, "points": 24,
                       "transforms": [{"kind": "c2c", "keep": 16}, {"kind": "c2c", "keep": 16},
                                      {"kind": "r2c", "keep": 9}],
                       "initial": {"perturbation": 0.01, "perturbation_k0": 4}},
    "paper_c2c_512": {"shape": [16, 16, 16]},
}


C2C_CELL = {"name": "c2c512.pencil2x2", "config": "paper_c2c_512",
            "traffic": "roundtrip.pencil2x2", "chips": 4, "why": "the paper's 512^3 c2c on a 2x2 pencil"}


def tiny_root(tmp: Path) -> Path:
    """A copy of ``BENCHMARK.json`` and ``bench/`` (tests left out) with
    the configurations cut to :data:`TINY`."""
    root = Path(tmp) / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    for name, changes in TINY.items():
        path = root / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(changes)
        path.write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    if not any(w["name"] == C2C_CELL["name"] for w in bench["workloads"]):
        bench["workloads"].append(C2C_CELL)  # its files are in bench/, its entry not yet
        (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_cell(root: Path, workload: str, *, seed: int = 2**31 + 11, seconds: float = 0.3,
             trace: bool = False) -> dict:
    """One run of a cell on the CPU, in this process."""
    from bench import harness

    spec = harness.find(Path(root), workload)
    enable = harness.enable_compile_cache
    harness.enable_compile_cache = lambda _root: "(off)"  # tests leave the cache off
    try:
        return harness.run(spec, seed=seed, seconds=seconds, trace=trace,
                           t0=time.perf_counter(), platform="cpu")
    finally:
        harness.enable_compile_cache = enable


def run_devices(code: str, ndev: int = 4, timeout: int = 600) -> str:
    """Run ``code`` in a fresh python on ``ndev`` virtual CPU devices."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.pathsep.join([str(REPO / "src"), str(REPO), str(Path(__file__).parent)])
    proc = subprocess.run([sys.executable, "-c", code], env=env, timeout=timeout,
                          capture_output=True, text=True, cwd=str(REPO))
    if proc.returncode != 0:
        raise AssertionError(f"subprocess failed (rc={proc.returncode})\n{proc.stdout[-3000:]}"
                             f"\n{proc.stderr[-5000:]}")
    return proc.stdout
