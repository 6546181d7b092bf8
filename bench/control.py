#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 7,8,9] [--steps N] [--out FILE]

In one process (the cell is compiled once per variant): for each seed, the
program's inputs are made, ``--steps`` steps run through the timed path
(default: the traffic's ``min_steps``), and the comparison of a run reads
its numbers (the lower readings).  Then the same with the configuration's
``control``: the program with its lower-precision path switched on (the
upper readings).  One JSON line per seed, on standard output and in
``--out``.  Runs only on a TPU; the benchmark's own runs never run it.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness, timing  # noqa: E402


def readings(spec, devices, seeds, *, steps: int, variant: str, overrides, emit):
    cell, ctx = harness.build_cell(spec, devices, overrides)
    for seed in seeds:
        t0 = time.perf_counter()
        cell.reset(seed)
        timing.closed_loop(cell.dispatch, steps=steps)
        t1 = time.perf_counter()
        checks = cell.check()
        emit({"variant": variant, "seed": seed,
              "checks": {c.name: {"value": c.value, "limit": c.limit} for c in checks},
              "correct": all(c.ok for c in checks),
              "steps_s": t1 - t0, "check_s": time.perf_counter() - t1,
              "plan_compile_s": ctx.spans_s.get("plan.compile")})


def main(argv=None, *, root: Path = ROOT, platform: str = harness.REQUIRED_PLATFORM) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    try:
        spec = harness.find(root, args.workload)
        devices = harness.require_devices(spec.workload["chips"], platform)
    except harness.RunError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(root)
    steps = args.steps or spec.traffic["min_steps"]
    readings(spec, devices, seeds, steps=steps, variant="program", overrides=None, emit=emit)
    if control_seeds:
        readings(spec, devices, control_seeds, steps=steps, variant="control",
                 overrides=spec.config["control"], emit=emit)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
