#!/usr/bin/env python3
"""On-chip smoke test: drive the distributed FFT's main path on a TPU and
check every result against float64 numpy.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # 2x2 pencil + 4-way slab only

Phases, all through the user entry points with explicit schedules
(``method="fused"``, no tuner, no degradation ladder):

(a) 512^3 complex64 c2c plan, ``impl="jnp"`` (XLA's FFT): forward vs
    ``numpy.fft.fftn``, and the backward round trip.
(b) the same plan with ``impl="matmul"`` (the four-step Pallas kernel,
    n = 512 = 32 x 16); its compiled text must hold ``tpu_custom_call``.
(c) the dealiased pseudo-spectral call of ``examples/navier_stokes.py``:
    pruned x2 + r2c(n_keep) on the padded M = 3N/2 grid, N = 256, as a
    3-field ``forward_many``/``backward_many``.
(d) a ``SpectralServer`` on the one-chip mesh answering 256^3 requests;
    each must resolve ``ok`` with no retry, no ladder transition and no
    fallback.  The server refuses ``guard="off"``, so its plans run
    ``guard="strict"``: any guard trip fails the request.

``--chips 4`` runs 512^3 on a 2x2 pencil and a 4-way slab, each with the
lossless fused exchange and with bf16 and int8 wire payloads through the
Pallas exchange kernels; each result is compared with numpy and with the
one-chip jnp result, and each device's memory use is printed.

Inputs are generated on the device from ``--seed`` with the plan's own
sharding.  Per-phase times are informational, not benchmark metrics.
The script exits non-zero, printing no result line, on any failure or
when JAX finds no TPU.  The last line of a passing run is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.core.compile_cache import enable_compile_cache  # noqa: E402

#: the accelerator the smoke must run on (there is no CPU branch), and
#: the text a compiled program holds where a Pallas kernel runs on it
REQUIRED_PLATFORM = "tpu"
KERNEL_MARKER = "tpu_custom_call"

#: problem sizes: the c2c cube edge, the dealiased retained-mode count
#: (padded grid 3N/2), the served cube edge and request count
N_C2C = 512
N_DEALIAS = 256
N_SERVE = 256
SERVE_REQUESTS = 4

#: relative L2 bounds.  Lossless phases must hold f32 accuracy — a DFT
#: run at the MXU's default bf16-pass precision misses this by ~100x.
#: Lossy wire payloads round each of two exchanges: bf16 keeps 8
#: mantissa bits (~1e-3 per pass), int8 a per-chunk max-abs/127 step
#: (~1e-2 per pass on Gaussian data).
LOSSLESS_TOL = 1e-4
LOSSY_TOL = {"bf16": 5e-3, "int8": 5e-2}


class SmokeFailure(RuntimeError):
    pass


def rel_l2(got, ref) -> float:
    """||got - ref|| / ||ref|| in float64 on the host."""
    got = np.asarray(got)
    ref = np.asarray(ref)
    num = np.linalg.norm((got.astype(np.complex128) - ref).ravel())
    return float(num / np.linalg.norm(ref.ravel()))


def check(name: str, value: float, bound: float):
    if not value <= bound:  # NaN fails too
        raise SmokeFailure(f"{name}: {value!r} exceeds the bound {bound!r}")


def require_platform():
    """The first device, or SmokeFailure when it is not a TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != REQUIRED_PLATFORM:
        raise SmokeFailure(f"no {REQUIRED_PLATFORM} found: jax.devices()[0] "
                           f"is {dev.platform!r} ({dev.device_kind})")
    return dev


def device_label() -> str:
    import jax

    d = jax.devices()
    return f"{d[0].platform}:{d[0].device_kind} x{len(d)}"


def random_field(seed: int, shape, dtype, sharding):
    """Standard-normal data made on the device, placed with ``sharding``
    (threefry values do not depend on the sharding)."""
    import jax
    import jax.numpy as jnp

    def gen(key):
        if np.dtype(dtype).kind == "c":
            kr, ki = jax.random.split(key)
            return jax.lax.complex(jax.random.normal(kr, shape, jnp.float32),
                                   jax.random.normal(ki, shape, jnp.float32))
        return jax.random.normal(key, shape, jnp.float32)

    return jax.jit(gen, out_shardings=sharding)(jax.random.key(seed))


def compile_pair(fwd, bwd, x, y_shape, y_dtype):
    """AOT-compile a forward/backward pair; returns ``(f, b, seconds)``."""
    import jax

    t0 = time.perf_counter()
    f = jax.jit(fwd).lower(x).compile()
    b = jax.jit(bwd).lower(jax.ShapeDtypeStruct(y_shape, y_dtype)).compile()
    return f, b, time.perf_counter() - t0


def run_pair(f, b, x, repeats: int = 3):
    """One checked forward+backward, then the best of ``repeats`` timed
    forward+backward round trips (``block_until_ready``)."""
    import jax

    y = jax.block_until_ready(f(x))
    back = jax.block_until_ready(b(y))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(b(jax.block_until_ready(f(x))))
        best = min(best, time.perf_counter() - t0)
    return y, back, best


def has_kernel(*compiled) -> bool:
    return all(KERNEL_MARKER in c.as_text() for c in compiled)


def report(phase: str, **fields):
    parts = " ".join(f"{k}={v:.3e}" if isinstance(v, float) else f"{k}={v}"
                     for k, v in fields.items())
    print(f"[{phase}] {parts} device={device_label()}", flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def c2c_plan(mesh, grid, n: int, **config):
    from repro.core.pfft import ParallelFFT
    from repro.core.planconfig import PlanConfig

    cfg = PlanConfig(method="fused", guard="off", **config)
    return ParallelFFT(mesh, (n, n, n), grid, config=cfg)


def phase_c2c(name: str, plan, seed: int, ref_in, ref_out, *, tol: float,
              kernel: bool = False, against=None):
    """Forward/backward one c2c plan on device-made input; check the
    forward against ``ref_out`` (numpy of ``ref_in``), the round trip, and
    optionally the compiled kernel and a second reference ``against``.
    Returns the forward result on the host."""
    pen = plan.input_pencil
    assert pen.logical == pen.physical, "smoke sizes divide the mesh"
    x = random_field(seed, pen.logical, np.complex64, pen.sharding)
    if not np.array_equal(np.asarray(x), ref_in):
        raise SmokeFailure(f"{name}: device input differs from the reference input")
    f, b, compile_s = compile_pair(plan.forward, plan.backward, x,
                                   plan.output_pencil.logical, plan.spectral_dtype)
    if kernel and not has_kernel(f, b):
        raise SmokeFailure(f"{name}: compiled plan holds no {KERNEL_MARKER}")
    y, back, wall = run_pair(f, b, x)
    y = np.asarray(y)
    err = rel_l2(y, ref_out)
    rt = rel_l2(np.asarray(back), ref_in)
    fields = dict(fwd_rel_l2=err, roundtrip_rel_l2=rt, bound=tol)
    if against is not None:
        fields["vs_one_chip_rel_l2"] = rel_l2(y, against)
    report(name, **fields, compile_s=compile_s, fwd_bwd_s=wall)
    check(f"{name} forward", err, tol)
    check(f"{name} round trip", rt, tol)
    if against is not None:
        check(f"{name} vs one chip", fields["vs_one_chip_rel_l2"], tol)
    return y


def c2c_reference(seed: int, n: int):
    """Host copy of the seed's input and its float64 numpy spectrum."""
    import jax

    from repro.core.meshutil import make_mesh

    one = make_mesh((1,), ("p",), devices=jax.devices()[:1])
    sharding = c2c_plan(one, ("p",), n).input_pencil.sharding
    x = np.asarray(random_field(seed, (n, n, n), np.complex64, sharding))
    return x, np.fft.fftn(x.astype(np.complex128))


def phase_dealias(mesh, grid, n_keep: int, seed: int, nfields: int = 3):
    """(c) the 3/2-rule dealiased pruned x2 + r2c(n_keep) plan on the
    padded grid, as one ``nfields``-field batched call each way."""
    from repro.core.fftcore import TransformSpec, dealias_grid
    from repro.core.pfft import ParallelFFT
    from repro.core.planconfig import PlanConfig

    m = dealias_grid(n_keep)
    plan = ParallelFFT(
        mesh, (m, m, m), grid, config=PlanConfig(method="fused", guard="off"),
        transforms=(TransformSpec.pruned(n_keep), TransformSpec.pruned(n_keep),
                    TransformSpec.r2c(n_keep=n_keep // 2 + 1)))
    pen = plan.input_pencil
    assert pen.logical == pen.physical, "smoke sizes divide the mesh"
    x = random_field(seed, (nfields, m, m, m), np.float32, pen.batched_sharding(1))
    spec_shape = (nfields, *plan.output_pencil.logical)
    f, b, compile_s = compile_pair(plan.forward_many, plan.backward_many, x,
                                   spec_shape, plan.spectral_dtype)
    y, back, wall = run_pair(f, b, x)

    # numpy: rfftn, keep the centered n_keep modes on the pruned axes and
    # the leading n_keep//2+1 bins on the r2c axis; backward zero-scatters
    head, tail = (n_keep + 1) // 2, n_keep // 2
    keep = np.r_[0:head, m - tail:m]
    kz = n_keep // 2 + 1
    xh = np.asarray(x).astype(np.float64)
    ref = np.empty(spec_shape, np.complex128)
    ref_back = np.empty(xh.shape)
    for i in range(nfields):
        full = np.fft.rfftn(xh[i])
        ref[i] = full[keep][:, keep][:, :, :kz]
        scat = np.zeros_like(full)
        scat[np.ix_(keep, keep, np.arange(kz))] = ref[i]
        ref_back[i] = np.fft.irfftn(scat, s=(m, m, m), axes=(0, 1, 2))
    err = rel_l2(np.asarray(y), ref)
    rt = rel_l2(np.asarray(back), ref_back)
    report(f"c dealias N={n_keep} M={m} fields={nfields}", fwd_rel_l2=err,
           backward_rel_l2=rt, bound=LOSSLESS_TOL, compile_s=compile_s,
           fwd_bwd_s=wall)
    check("dealias forward", err, LOSSLESS_TOL)
    check("dealias backward", rt, LOSSLESS_TOL)


def phase_serve(mesh, grid, n: int, seed: int, requests: int):
    """(d) a SpectralServer answering ``requests`` c2c transforms of
    ``n^3``: every outcome ``ok``, no retry, transition or fallback."""
    from repro.core.planconfig import PlanConfig
    from repro.serve import ServeConfig, SpectralServer

    pc = PlanConfig(method="fused", guard="strict")
    sharding = c2c_plan(mesh, grid, n).input_pencil.sharding
    xs = [random_field(seed + 1 + i, (n, n, n), np.complex64, sharding)
          for i in range(requests)]
    cfg = ServeConfig(deadline_s=900.0, max_batch=requests)
    with SpectralServer(mesh, grid, plan_config=pc, config=cfg) as server:
        t0 = time.perf_counter()
        server.registry.get((n, n, n))  # build + warm: compile off the clock
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        futures = [server.submit(x) for x in xs]
        outcomes = [fu.result() for fu in futures]
        wall = time.perf_counter() - t0
        stats = server.stats()
    worst = 0.0
    for x, out in zip(xs, outcomes):
        if out.status != "ok" or out.retries or out.transitions:
            raise SmokeFailure(f"serve: request {out.summary()} is not a clean ok")
        worst = max(worst, rel_l2(np.asarray(out.value),
                                  np.fft.fftn(np.asarray(x).astype(np.complex128))))
    if stats["fallback_served"] or stats["retries"]:
        raise SmokeFailure(f"serve: fallback/retry counters non-zero: {stats}")
    report(f"d serve {requests}x{n}^3", worst_rel_l2=worst, bound=LOSSLESS_TOL,
           batches=stats["coalesced_batches"], compile_s=compile_s, serve_s=wall)
    check("serve", worst, LOSSLESS_TOL)


def run_one_chip(seed: int):
    import jax

    from repro.core.meshutil import make_mesh

    devs = jax.devices()[:1]
    slab = make_mesh((1,), ("p",), devices=devs)
    x, ref = c2c_reference(seed, N_C2C)
    for label, impl in (("a", "jnp"), ("b", "matmul")):
        phase_c2c(f"{label} c2c {impl} {N_C2C}^3", c2c_plan(slab, ("p",), N_C2C, impl=impl),
                  seed, x, ref, tol=LOSSLESS_TOL, kernel=impl == "matmul")
    del x, ref
    pencil = make_mesh((1, 1), ("p0", "p1"), devices=devs)
    phase_dealias(pencil, ("p0", "p1"), N_DEALIAS, seed)
    phase_serve(slab, ("p",), N_SERVE, seed, SERVE_REQUESTS)


def run_four_chips(seed: int):
    import jax

    from repro.core.meshutil import make_mesh

    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    devs = jax.devices()[:4]
    x, ref = c2c_reference(seed, N_C2C)
    one = make_mesh((1,), ("p",), devices=devs[:1])
    y1 = phase_c2c(f"one-chip c2c jnp {N_C2C}^3", c2c_plan(one, ("p",), N_C2C),
                   seed, x, ref, tol=LOSSLESS_TOL)
    meshes = {"pencil 2x2": (make_mesh((2, 2), ("p0", "p1"), devices=devs), ("p0", "p1")),
              "slab 4": (make_mesh((4,), ("p",), devices=devs), ("p",))}
    for mname, (mesh, grid) in meshes.items():
        for comm in ("complex64", "bf16", "int8"):
            lossy = comm != "complex64"
            plan = c2c_plan(mesh, grid, N_C2C, comm_dtype=comm,
                            exchange_impl="pallas" if lossy else "jnp")
            phase_c2c(f"{mname} {comm}{' pallas' if lossy else ''} {N_C2C}^3",
                      plan, seed, x, ref, against=y1, kernel=lossy,
                      tol=LOSSY_TOL[comm] if lossy else LOSSLESS_TOL)
    for d in devs:
        st = d.memory_stats() or {}
        print(f"[memory] {d} bytes_in_use={st.get('bytes_in_use')} "
              f"peak_bytes_in_use={st.get('peak_bytes_in_use')}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2-pencil and 4-slab phases")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        dev = require_platform()
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    try:
        (run_four_chips if args.chips == 4 else run_one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    import jax

    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
