"""Time the c2r stage's two forms on one chip, to place the bound between
them (``fftcore._C2R_DFT_MAX_N``).

For each output length ``n`` and each kept-bin count ``m`` (``n//3+1``,
the 3/2-rule dealiased axis, and ``n//2+1``, the unpruned one), a block of
``--rows`` complex rows of ``m`` kept bins is transformed to ``n`` real
points along its last axis by the dense real DFT (``fftcore._c2r_dft``)
and by the Hermitian extension plus a complex inverse FFT, two rows to one
(``fftcore._c2r_packed``, the first spatial axis split).  The block's first
axis is a stack of fields, as in the plan's batched executor.  Each form is
compiled, run twice to warm up, then timed ``--repeats`` times on the host
clock around ``block_until_ready``; the median is reported, with the
relative L2 gap between the two forms' outputs.

    python benchmarks/c2r_crossover.py [--ns 128,256,384] [--rows 3,384,384]

One JSON line per case on stdout; with ``--out`` the list is also written
there.  Refuses to run on anything but a TPU: a CPU time says nothing of
the chip.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def _time(fn, x, repeats):
    for _ in range(2):
        fn(x).block_until_ready()
    times = []
    for _ in range(repeats):
        t = time.perf_counter()
        fn(x).block_until_ready()
        times.append(time.perf_counter() - t)
    return statistics.median(times)


def case(n: int, m: int, rows: tuple[int, ...], repeats: int, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import fftcore

    axis = len(rows)
    kr, ki = jax.random.split(jax.random.key(seed))
    shape = (*rows, m)
    x = jax.block_until_ready(jax.random.normal(kr, shape) + 1j * jax.random.normal(ki, shape))
    # the first row axis is a stack of fields, as in a batched plan
    dense = jax.jit(lambda b: fftcore._c2r_dft(b, axis, n, 1))
    packed = jax.jit(lambda b: fftcore._c2r_packed(b, axis, n, "jnp", 1))
    out = {"n": n, "m": m, "rows": list(rows),
           "dense_ms": _time(dense, x, repeats) * 1e3,
           "packed_ms": _time(packed, x, repeats) * 1e3}
    gap = jnp.linalg.norm(dense(x) - packed(x)) / jnp.linalg.norm(packed(x))
    out["rel_l2_gap"] = float(gap)
    out["dense_faster"] = out["dense_ms"] < out["packed_ms"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ns", default="128,256,384,480,512,768,1024")
    ap.add_argument("--rows", default="3,384,384",
                    help="the block's row axes: fields, then at least one spatial axis, "
                         "the first of them even (the packed form splits it)")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--out", default=None, help="also write the list of cases here")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"c2r_crossover: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    rows = tuple(int(r) for r in args.rows.split(","))
    results = []
    for n in (int(v) for v in args.ns.split(",")):
        for m in sorted({n // 3 + 1, n // 2 + 1}):
            r = case(n, m, rows, args.repeats)
            r["device"] = dev.device_kind
            print(json.dumps(r), flush=True)
            results.append(r)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(results, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
