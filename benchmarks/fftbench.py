"""Subprocess worker: time one distributed-FFT configuration.

Mirrors the paper's methodology (Sec. 4): an inner loop of ``--inner``
consecutive forward+backward transforms, repeated ``--outer`` times; we
report the fastest outer iteration divided by inner (their "fastest of 50
outers of 3").  ``--measure redistribution`` times an exchanges-only plan
(the paper's "global redistribution" split); fft time = total - redist.
``--compare`` times all four exchange engines {fused, traditional,
pipelined, auto} × every ``--comm-dtypes`` wire payload {complex64, bf16,
int8} on the same problem and reports one JSON table with a ``comm_dtype``
column per row (pass ``--tune-cache`` so the auto schedules round-trip to
disk).  ``--exchange-impls jnp,pallas`` adds fused-exchange-kernel rows
(``method@dtype@pallas``) for every lossy payload; lossless payloads get
no pallas row because the fused kernels don't apply there and the plan
would be identical.

``--fields N`` (N > 1) benchmarks the batched multi-field path: every
timed transform runs N stacked fields through one plan invocation, the
``--compare`` sweep grows a ``batch_fusion`` dimension ({stacked,
pipelined-across-fields, per-field} per method×payload row), and the
report gains an ``"exchange"`` section timing the exchanges-only plan
batched (one all-to-all per stage for all N fields) vs as a per-field
loop (N all-to-alls per stage) — the message-aggregation win in
isolation.

Run via benchmarks.paperfigs which sets XLA_FLAGS for the device count.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def build_plan(shape, gridspec, ndev, *, real, method, impl, chunks=4,
               comm_dtype=None, tuner_cache=None, transforms=None,
               batch_fusion="stacked", exchange_impl="jnp"):
    from repro.core.meshutil import make_mesh
    from repro.core.pfft import ParallelFFT
    from repro.core.planconfig import PlanConfig

    if gridspec == "slab":
        mesh = make_mesh((ndev,), ("p0",))
        grid = ("p0",)
    elif gridspec == "pencil":
        from repro.core.meshutil import balanced_dims

        mesh = make_mesh(balanced_dims(ndev), ("p0", "p1"))
        grid = ("p0", "p1")
    elif gridspec == "grid3":
        dims = []
        rem = ndev
        for _ in range(2):
            a = int(round(rem ** (1 / (3 - len(dims)))))
            while rem % a:
                a -= 1
            dims.append(a)
            rem //= a
        dims.append(rem)
        mesh = make_mesh(tuple(dims), ("p0", "p1", "p2"))
        grid = ("p0", "p1", "p2")
    else:
        raise ValueError(gridspec)
    if not transforms and real:
        # --real: r2c on the last axis, c2c elsewhere
        transforms = ("c2c",) * (len(shape) - 1) + ("r2c",)
    cfg = PlanConfig(method=method, impl=impl, exchange_impl=exchange_impl,
                     chunks=chunks, comm_dtype=comm_dtype,
                     batch_fusion=batch_fusion, tuner_cache=tuner_cache)
    return ParallelFFT(mesh, shape, grid, config=cfg,
                       transforms=transforms or None)


def exchanges_only(plan, *, nfields=1, batch_fusion="stacked"):
    """A jit'd function running only the plan's exchange stages (paper's
    'global redistribution' timing split).

    ``nfields > 1`` runs the stages on a stacked ``(nfields, …)`` block:
    ``batch_fusion="stacked"`` ships all fields in one all-to-all per
    stage, ``"per-field"`` issues the N per-field collectives a loop over
    single-field plans would — the pair isolates the message-aggregation
    win of the batched path."""
    from repro.core.meshutil import shard_map
    from repro.core.pfft import ExchangeStage
    from repro.core.redistribute import exchange_shard

    stages = [(s, b, a, dt) for s, b, a, dt in
              zip(plan.stages, plan.pencil_trace, plan.pencil_trace[1:],
                  plan.dtype_trace)
              if isinstance(s, ExchangeStage)]

    schedule = plan.schedule  # resolves "auto" to the tuned per-stage mix
    nbatch = 1 if nfields > 1 else 0

    def run(block):
        for ex_i, (st, before, _after, dtype) in enumerate(stages):
            # emulate the fft-stage shape *and dtype* change between
            # exchanges (an r2c mid-plan means later exchanges carry
            # complex64 while earlier ones carried f32)
            want = (nfields,) * nbatch + tuple(np.array(before.local_shape))
            if block.shape != want or block.dtype != dtype:
                block = jnp.zeros(want, dtype)
            method, chunks, comm_dtype, ex_impl, _fusion = schedule[ex_i]
            if nbatch and batch_fusion != "stacked":
                # per-field and pipelined-across-fields both issue N
                # per-field collectives here (no FFTs to interleave with)
                block = jnp.stack([
                    exchange_shard(block[f], st.v, st.w, st.group,
                                   method=method, chunks=chunks,
                                   comm_dtype=comm_dtype, impl=ex_impl)
                    for f in range(nfields)])
            else:
                block = exchange_shard(block, st.v, st.w, st.group,
                                       method=method, chunks=chunks,
                                       comm_dtype=comm_dtype, impl=ex_impl,
                                       nbatch=nbatch)
        return block

    first, first_dtype = stages[0][1], stages[0][3]
    fn = shard_map(run, mesh=plan.mesh, in_specs=first.batched_spec(nbatch),
                   out_specs=stages[-1][2].batched_spec(nbatch), check_vma=False)
    return jax.jit(fn), first, first_dtype


METHODS = ("fused", "traditional", "pipelined", "auto")


def _best_of(once, xg, *, outer, inner):
    """Fastest outer iteration of ``inner`` consecutive applications."""
    return _timed(once, xg, outer=outer, inner=inner)[0]


def _timed(once, xg, *, outer, inner):
    """(fastest, median) outer iteration of ``inner`` consecutive
    applications — the median rides along so downstream consumers
    (benchdiff's noise-aware regression gate) can tell run-to-run spread
    from a real slowdown."""
    once(xg).block_until_ready()  # compile + warm
    times = []
    for _ in range(outer):
        t0 = time.perf_counter()
        v = xg
        for _ in range(inner):
            v = once(v)
        v.block_until_ready()
        times.append((time.perf_counter() - t0) / inner)
    return min(times), float(np.median(times))


def _make_input(plan, shape, nfields=1):
    """Random logical input at the plan's true input dtype (real for r2c
    and all-real dct/dst transform plans, complex otherwise); ``nfields``
    stacks N fields along a leading batch axis."""
    rng = np.random.default_rng(0)
    full = ((nfields,) if nfields > 1 else ()) + tuple(shape)
    x = rng.standard_normal(full).astype(np.float32)
    if plan.input_dtype == jnp.complex64:
        x = (x + 1j * rng.standard_normal(full)).astype(np.complex64)
    return x


def _time_plan(plan, shape, args):
    """Time one forward+backward round trip of ``plan`` (total measure),
    batched over ``--fields`` stacked fields when N > 1; returns
    ``(best_s, p50_s)``."""
    nf = args.fields
    x = _make_input(plan, shape, nf)
    from repro.core.pencil import pad_global

    nbatch, stack = (1, nf) if nf > 1 else (0, None)
    xg = jax.device_put(pad_global(jnp.asarray(x), plan.input_pencil, nbatch=nbatch),
                        plan.input_pencil.batched_sharding(nbatch))
    fwd = jax.jit(plan.executor("forward", stack))
    bwd = jax.jit(plan.executor("backward", stack))
    return _timed(lambda v: bwd(fwd(v)), xg, outer=args.outer, inner=args.inner)


def _time_guard_pair(plan, shape, args):
    """Measure the guarded round trip (fused health checks + per-shard
    stat partials) against the unguarded one on the same input, returning
    ``(unguarded_s, guarded_s)``.  The two executors alternate within
    every outer round: timing them in separate back-to-back sweeps
    conflates guard cost with machine drift (thermal/cache state shifts
    over a sweep easily exceed the real overhead).  The guarded jits
    return the stats vector, so XLA cannot dead-code-eliminate the guard
    ops — this measures the real ``guard != "off"`` overhead."""
    from repro.core.pencil import pad_global
    from repro.core.pfft import ParallelFFT

    nf = args.fields
    x = _make_input(plan, shape, nf)
    gplan = ParallelFFT(plan.mesh, plan.shape, plan.grid, transforms=plan.transforms,
                        config=plan.config.replace(guard=args.guard))
    nbatch, stack = (1, nf) if nf > 1 else (0, None)
    xg = jax.device_put(pad_global(jnp.asarray(x), plan.input_pencil, nbatch=nbatch),
                        plan.input_pencil.batched_sharding(nbatch))
    ufwd = jax.jit(plan.executor("forward", stack))
    ubwd = jax.jit(plan.executor("backward", stack))
    gfwd = jax.jit(gplan.executor("forward", stack))
    gbwd = jax.jit(gplan.executor("backward", stack))

    def unguarded(v):
        return ubwd(ufwd(v))

    def guarded(v):
        y, _ = gfwd(v)
        z, _ = gbwd(y)
        return z

    unguarded(xg).block_until_ready()  # compile + warm
    guarded(xg).block_until_ready()
    best = {"u": float("inf"), "g": float("inf")}
    for _ in range(args.outer):
        for k, once in (("u", unguarded), ("g", guarded)):
            t0 = time.perf_counter()
            v = xg
            for _ in range(args.inner):
                v = once(v)
            v.block_until_ready()
            best[k] = min(best[k], (time.perf_counter() - t0) / args.inner)
    return best["u"], best["g"]


#: "infinite" bandwidth for isolating the model's comm-free residual
_NO_COMM_BW = 1e30


def _model_features(plan, measure: str, nfields: int) -> dict:
    """Analytic-model terms for the measured quantity, in the linear
    surrogate form :mod:`repro.core.modelfit` fits — ``time_s`` at the
    reference coefficients, the comm-free ``compute_s`` residual
    (bandwidth → ∞, latency → 0: FFT flops + codec/copy HBM passes), the
    wire bytes, and the latency-priced collective launch count.  A
    ``total`` measure is a forward+backward round trip, so every term sums
    both directions; ``redistribution`` prices the exchanges-only
    executor."""
    from repro.core.modelfit import REFERENCE_COEFFS

    if measure == "redistribution":
        kw = {"itemsize": None, "nfields": nfields, "exchange_only": True}
        time_s = plan.model_time_s(**kw)
        compute_s = plan.model_time_s(ici_bw=_NO_COMM_BW, ici_latency_s=0.0, **kw)
        wire = plan.comm_bytes_per_device(None, nfields=nfields)
        launches = plan.model_collective_launches(nfields=nfields)
    else:
        kw = {"itemsize": None, "nfields": nfields}
        time_s = compute_s = 0.0
        launches = 0
        for direction in ("forward", "backward"):
            time_s += plan.model_time_s(direction=direction, **kw)
            compute_s += plan.model_time_s(direction=direction, ici_bw=_NO_COMM_BW,
                                           ici_latency_s=0.0, **kw)
            launches += plan.model_collective_launches(nfields=nfields,
                                                       direction=direction)
        # backward walks the same exchanges reversed: same wire volume
        wire = 2 * plan.comm_bytes_per_device(None, nfields=nfields)
    return {
        "time_s": time_s,
        "compute_s": compute_s,
        "wire_bytes_per_dev": wire,
        "launches": launches,
        "coeffs": dict(REFERENCE_COEFFS),
    }


def _rand_block(shape, dtype):
    """Random buffer for exchange timings, complex when the stage is."""
    rng = np.random.default_rng(0)
    buf = rng.standard_normal(shape).astype(np.float32)
    if dtype == jnp.complex64:
        buf = (buf + 1j * rng.standard_normal(shape)).astype(np.complex64)
    return jnp.asarray(buf)


def _exchange_comparison(plan, args):
    """Time the exchanges-only plan over N stacked fields, batched (one
    collective per stage) vs as a per-field loop (N per stage): the
    message-aggregation win in isolation."""
    out = {}
    for fusion in ("stacked", "per-field"):
        fn, first, first_dtype = exchanges_only(plan, nfields=args.fields,
                                                batch_fusion=fusion)
        xg = jax.device_put(_rand_block((args.fields, *first.physical), first_dtype),
                            first.batched_sharding())
        out[fusion.replace("-", "_") + "_s"] = _best_of(
            fn, xg, outer=args.outer, inner=args.inner)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", type=str, required=True)  # e.g. 128,128,128
    ap.add_argument("--grid", choices=["slab", "pencil", "grid3"], default="slab")
    ap.add_argument("--method", choices=METHODS, default="fused")
    ap.add_argument("--chunks", type=int, default=4,
                    help="slice count for method=pipelined")
    ap.add_argument("--tune-cache", type=str, default=None,
                    help="schedule cache path for method=auto")
    ap.add_argument("--comm-dtype", choices=["complex64", "bf16", "int8"],
                    default="complex64",
                    help="exchange wire payload (auto: accuracy budget)")
    ap.add_argument("--comm-dtypes", type=str, default="complex64,bf16,int8",
                    help="comma list of payloads the --compare sweep covers")
    ap.add_argument("--fields", type=int, default=1,
                    help="number of stacked fields per transform (N>1 "
                         "benchmarks the batched multi-field path)")
    ap.add_argument("--batch-fusion", default="stacked",
                    choices=["stacked", "pipelined-across-fields", "per-field"],
                    help="multi-field execution mode for single-method runs "
                         "(--compare sweeps all three)")
    ap.add_argument("--guard", choices=["off", "strict", "degrade"],
                    default="off",
                    help="also time the guarded executor (fused runtime "
                         "health checks) and report the overhead vs the "
                         "unguarded round trip")
    ap.add_argument("--compare", action="store_true",
                    help="time all four methods x all --comm-dtypes payloads "
                         "and report one table")
    ap.add_argument("--real", action="store_true")
    ap.add_argument("--transforms", type=str, default=None,
                    help="comma list of per-axis transform tags (c2c, r2c, "
                         "dct2, dct3, dst2, dst3), overriding --real; e.g. "
                         "--transforms dct2,c2c,r2c")
    ap.add_argument("--impl", default="jnp")
    ap.add_argument("--exchange-impl", choices=["jnp", "pallas"], default="jnp",
                    help="exchange-local pack/codec implementation: 'pallas' "
                         "runs the fused quantize+pack / unpack+dequantize "
                         "kernels on lossy payloads (auto: candidate budget)")
    ap.add_argument("--exchange-impls", type=str, default="jnp",
                    help="comma list of exchange impls the --compare sweep "
                         "covers; pallas rows appear only where the fused "
                         "kernels apply (lossy payloads)")
    ap.add_argument("--inner", type=int, default=3)
    ap.add_argument("--outer", type=int, default=10)
    ap.add_argument("--measure", choices=["total", "redistribution"], default="total")
    ap.add_argument("--no-audit", action="store_true",
                    help="skip the per-row planlint audit (one extra compile "
                         "per --compare row)")
    args = ap.parse_args(argv)
    from repro.core.compile_cache import enable_compile_cache

    enable_compile_cache()

    shape = tuple(int(s) for s in args.shape.split(","))
    if args.transforms and args.real:
        ap.error("--transforms and --real are mutually exclusive "
                 "(use --transforms ...,r2c for a real plan)")
    transforms = tuple(args.transforms.split(",")) if args.transforms else None
    ndev = len(jax.devices())
    if args.compare:
        out = {"shape": shape, "grid": args.grid, "real": bool(args.real),
               "transforms": list(transforms) if transforms else None,
               "ndev": ndev, "fields": args.fields,
               "device_kind": jax.devices()[0].device_kind,
               "backend": jax.default_backend(), "methods": {}}
        fusions = (["stacked", "pipelined-across-fields", "per-field"]
                   if args.fields > 1 else ["stacked"])
        from repro.kernels.exchange import pallas_applicable

        # pallas rows only where the fused kernels apply (lossy payloads);
        # elsewhere the plan is identical to the jnp row
        rows = [(m, d, x) for m in METHODS
                for d in args.comm_dtypes.split(",")
                for x in args.exchange_impls.split(",")
                if x == "jnp" or pallas_applicable(m, d)]
        for method, comm_dtype, ximpl in rows:
            for fusion in fusions:
                plan = build_plan(shape, args.grid, ndev, real=args.real,
                                  method=method, impl=args.impl,
                                  chunks=args.chunks, comm_dtype=comm_dtype,
                                  tuner_cache=args.tune_cache,
                                  transforms=transforms, batch_fusion=fusion,
                                  exchange_impl=ximpl)
                if not out["methods"]:
                    # the workload's true input kind, once from the first
                    # plan (a --transforms plan can be real without --real)
                    out["real"] = bool(plan.input_dtype == jnp.float32)
                sched = (plan.batched_schedule(args.fields)
                         if args.fields > 1 else plan.schedule)
                tag = (f"{method}@{comm_dtype}@{fusion}"
                       if args.fields > 1 else f"{method}@{comm_dtype}")
                if ximpl != "jnp":
                    tag += f"@{ximpl}"
                best_s, p50_s = _time_plan(plan, shape, args)
                out["methods"][tag] = {
                    "comm_dtype": comm_dtype,
                    "exchange_impl": ximpl,
                    "batch_fusion": fusion if args.fields > 1 else None,
                    "best_s": best_s,
                    "p50_s": p50_s,
                    "schedule": [list(s) for s in sched],
                    # itemsize=None prices each exchange at its traced
                    # dtype width (complex64 after the r2c stage, f32 for
                    # exchanges of still-real dct/dst data)
                    "model_time_s": plan.model_time_s(
                        itemsize=None, nfields=args.fields),
                    "wire_bytes_per_dev": plan.comm_bytes_per_device(
                        None, nfields=args.fields),
                    # static certification of the timed artifact: the
                    # row's numbers are meaningless if the compiled plan
                    # doesn't match its claimed schedule
                    "audit": None if args.no_audit
                    else plan.audit(nfields=args.fields).summary(),
                }
                if args.fields > 1 and method == "auto":
                    # one fusion pass suffices: auto tunes batch_fusion
                    # per stage itself, so the plan's own mode is moot
                    break
        if args.fields > 1:
            plan = build_plan(shape, args.grid, ndev, real=args.real,
                              method="fused", impl=args.impl,
                              transforms=transforms)
            out["exchange"] = {"fields": args.fields,
                               **_exchange_comparison(plan, args)}
        print(json.dumps(out))
        return
    plan = build_plan(shape, args.grid, ndev, real=args.real,
                      method=args.method, impl=args.impl, chunks=args.chunks,
                      comm_dtype=args.comm_dtype, tuner_cache=args.tune_cache,
                      transforms=transforms, batch_fusion=args.batch_fusion,
                      exchange_impl=args.exchange_impl)
    nf = args.fields

    if args.measure == "redistribution":
        fusion = args.batch_fusion if nf > 1 else "stacked"
        fn, first, first_dtype = exchanges_only(plan, nfields=nf,
                                                batch_fusion=fusion)
        nbatch = 1 if nf > 1 else 0
        xg = jax.device_put(
            _rand_block((nf,) * nbatch + tuple(first.physical), first_dtype),
            first.batched_sharding(nbatch))

        def once(v):
            return fn(v)

        best, p50 = _timed(once, xg, outer=args.outer, inner=args.inner)
    else:
        best, p50 = _time_plan(plan, shape, args)
    guard_section = None
    if args.guard != "off" and args.measure == "total":
        unguarded_s, guarded_s = _time_guard_pair(plan, shape, args)
        guard_section = {
            "mode": args.guard,
            "unguarded_s": unguarded_s,
            "guarded_s": guarded_s,
            "overhead_frac": guarded_s / unguarded_s - 1.0,
        }
    print(json.dumps({
        "shape": shape, "grid": args.grid, "method": args.method,
        "comm_dtype": plan.config.comm_dtype,
        "exchange_impl": args.exchange_impl,
        "fields": nf,
        "batch_fusion": args.batch_fusion if nf > 1 else None,
        "real": bool(plan.input_dtype == jnp.float32),
        "ndev": ndev, "measure": args.measure,
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
        "transforms": [sp.tag() for sp in plan.transforms],
        "best_s": best,
        "p50_s": p50,
        "spread_frac": p50 / best - 1.0 if best > 0 else 0.0,
        "guard": guard_section,
        "comm_bytes_per_dev": plan.comm_bytes_per_device(None, nfields=nf),
        "model_flops": plan.model_flops(nfields=nf),
        "model": _model_features(plan, args.measure, nf),
    }))


if __name__ == "__main__":
    main()
