"""``python -m repro.serve`` — run the spectral serving engine on a
synthetic request load and print a JSON report.

Demo / smoke entrypoint, not a network server: it builds a device mesh,
starts a :class:`~repro.serve.engine.SpectralServer`, fires ``--requests``
forward transforms at it (mixing ``--shapes`` round-robin so the LRU
registry and the coalescer both get exercised), waits for every future,
and reports the outcome histogram plus engine stats.  ``--chaos`` arms a
recurring serve-level fault matrix (slow collectives, executor crashes,
cache corruption, request bursts) — the report then demonstrates the
resilience lifecycle: every request still terminates in a structured
outcome within its deadline.

Typical smoke run (8 virtual devices):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m repro.serve --shapes 32,32,32 --requests 12 --chaos
"""

from __future__ import annotations

import argparse
import json


def _parse_shapes(spec: str):
    shapes = []
    for part in spec.split(";"):
        shapes.append(tuple(int(s) for s in part.split(",")))
    return shapes


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m repro.serve",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="32,32,32",
                    help="semicolon-separated global shapes, e.g. "
                         "'32,32,32;16,16,16'")
    ap.add_argument("--grid", choices=["slab", "pencil"], default="slab")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--method", default="fused",
                    help="plan method (fused/traditional/pipelined/auto)")
    ap.add_argument("--deadline", type=float, default=60.0,
                    help="per-request deadline in seconds")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--tune-cache", default=None,
                    help="shared schedule DB path (method=auto)")
    ap.add_argument("--chaos", action="store_true",
                    help="arm the serve-level fault matrix")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from repro.core.compile_cache import enable_compile_cache
    from repro.core.meshutil import balanced_dims, make_mesh
    from repro.core.planconfig import PlanConfig
    from repro.robustness import faults
    from repro.serve import ServeConfig, SpectralServer

    enable_compile_cache()

    ndev = len(jax.devices())
    if args.grid == "slab":
        mesh, grid = make_mesh((ndev,), ("p0",)), ("p0",)
    else:
        mesh = make_mesh(balanced_dims(ndev), ("p0", "p1"))
        grid = ("p0", "p1")
    shapes = _parse_shapes(args.shapes)
    pc = PlanConfig(method=args.method, tuner_cache=args.tune_cache,
                    guard="degrade")
    sc = ServeConfig(deadline_s=args.deadline, max_batch=args.max_batch,
                     max_queue=args.max_queue)
    rng = np.random.default_rng(args.seed)

    fault_ctx = None
    if args.chaos:
        fault_ctx = (faults.FaultPlan()
                     .slow_collective(seconds=0.05, times=2)
                     .executor_crash(times=1)
                     .cache_corruption(mode="garbage", times=1)
                     .request_burst(factor=2, times=1))
        fault_ctx.__enter__()
    try:
        with SpectralServer(mesh, grid, plan_config=pc, config=sc) as srv:
            futures = []
            n = args.requests * faults.serve_burst()
            for i in range(n):
                shape = shapes[i % len(shapes)]
                x = rng.standard_normal(shape).astype(np.float32)
                futures.append(srv.submit(x, deadline_s=args.deadline))
            outcomes = [f.result(grace=sc.grace_s) for f in futures]
            stats = srv.stats()
    finally:
        if fault_ctx is not None:
            fault_ctx.__exit__(None, None, None)

    hist: dict[str, int] = {}
    for o in outcomes:
        hist[o.status] = hist.get(o.status, 0) + 1
    unresolved = [o for o in outcomes if o is None]
    report = {
        "requests": len(outcomes),
        "outcomes": hist,
        "unresolved": len(unresolved),
        "chaos": bool(args.chaos),
        "fired_faults": (fault_ctx.fired if fault_ctx is not None else []),
        "stats": stats,
        "sample": [o.summary() for o in outcomes[:4]],
    }
    print(json.dumps(report, indent=1, default=str))
    return 1 if unresolved else 0


if __name__ == "__main__":
    raise SystemExit(main())
