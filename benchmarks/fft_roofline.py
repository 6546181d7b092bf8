import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

"""Production-scale FFT dry-run roofline (paper Figs. 10-11 analogue).

Lowers the paper's big transforms on the production mesh and compares the
fused (paper) vs traditional (P3DFFT-style) redistribution at the HLO level:

  fig10: 2048^3 r2c pencil FFT on 16x16 = 256 chips
  fig11: 128^4  c2c FFT on an (8,8,4) 3-D processor grid (256 chips)

For each: trip-aware FLOPs, HBM bytes, collective payloads, the three
roofline terms, and the fused-vs-traditional delta (the paper's claim,
restated for TPU: the traditional path pays extra HBM traffic for the
pack/unpack copies while moving the same collective payload).
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

REPO = Path(__file__).resolve().parent.parent
ART = REPO / "benchmarks" / "artifacts" / "figs"

PEAK, HBM, ICI = 197e12, 819e9, 50e9


def lower_fft(shape, mesh_shape, axis_names, grid, *, real, method, impl="jnp"):
    from repro.core.meshutil import make_mesh
    from repro.core.pfft import ParallelFFT
    from repro.core.planconfig import PlanConfig
    from repro.launch.hlo_account import account

    mesh = make_mesh(mesh_shape, axis_names)
    # real: r2c on the last axis, c2c elsewhere
    transforms = (("c2c",) * (len(shape) - 1) + ("r2c",)) if real else None
    plan = ParallelFFT(mesh, shape, grid, transforms=transforms,
                       config=PlanConfig(method=method, impl=impl))
    dtype = jnp.float32 if real else jnp.complex64
    x = jax.ShapeDtypeStruct(plan.input_pencil.physical, dtype)

    def fwd_bwd(v):
        return plan.executor("backward")(plan.executor("forward")(v))

    jfn = jax.jit(fwd_bwd,
                  in_shardings=plan.input_pencil.sharding,
                  out_shardings=plan.input_pencil.sharding)
    compiled = jfn.lower(x).compile()
    acct = account(compiled.as_text())
    chips = int(np.prod(mesh_shape))
    rec = {
        "shape": shape, "mesh": mesh_shape, "grid": [str(g) for g in grid],
        "real": real, "method": method, "impl": impl, "chips": chips,
        "flops_per_device": acct["flops"],
        "hbm_bytes_per_device": acct["hbm_bytes"],
        "collectives_per_device": acct["collectives"],
        "compute_s": acct["flops"] / PEAK,
        "memory_s": acct["hbm_bytes"] / HBM,
        "collective_s": acct["collectives"].get("total", 0.0) / ICI,
        "model_flops": 2 * plan.model_flops(),  # fwd + bwd
        # exchange payloads are complex64 even for r2c (exchanges run after
        # the r2c stage), so all modeled comm terms use itemsize 8
        "comm_model_bytes_per_dev": 2 * plan.comm_bytes_per_device(8),
        # overlap-aware analytic wall time (core/redistribute.exchange_time_model):
        # what the same plan would cost with the pipelined exchange engine
        "model_time_s": 2 * plan.model_time_s(itemsize=8),
        "model_time_pipelined_s": 2 * ParallelFFT(
            mesh, shape, grid, transforms=transforms,
            config=PlanConfig(method="pipelined", impl=impl),
        ).model_time_s(itemsize=8),
        # comm-compression lever: same pipelined plan with bf16 wire payloads
        # (2x fewer ICI bytes, priced against the extra quant HBM passes)
        "model_time_pipelined_bf16_s": 2 * ParallelFFT(
            mesh, shape, grid, transforms=transforms,
            config=PlanConfig(method="pipelined", impl=impl, comm_dtype="bf16"),
        ).model_time_s(itemsize=8),
        "comm_model_bytes_per_dev_bf16": 2 * plan.comm_bytes_per_device(
            8, comm_dtype="bf16"),
    }
    dom = max(("compute_s", "memory_s", "collective_s"), key=lambda k: rec[k])
    rec["dominant"] = dom.replace("_s", "")
    ideal = rec["model_flops"] / (chips * PEAK)
    rec["roofline_frac"] = ideal / rec[dom]
    return rec


def main(_argv=None):
    ART.mkdir(parents=True, exist_ok=True)
    scale = os.environ.get("REPRO_BENCH_SCALE", "small")
    if scale == "paper":
        fig10_shape, fig11_shape = (2048, 2048, 2048), (128, 128, 128, 128)
    else:  # container default: same structure, 4x smaller to keep compile fast
        fig10_shape, fig11_shape = (512, 512, 512), (64, 64, 64, 64)
    out = {}
    # TPU-native serial-FFT variant: four-step matmul DFT on the MXU
    # (DESIGN.md §4) — ~10x the FLOPs of radix FFT but on the 197-TFLOP unit
    out["fig10_fused_matmulDFT"] = lower_fft(
        fig10_shape, (16, 16), ("p0", "p1"), ("p0", "p1"),
        real=True, method="fused", impl="matmul")
    r = out["fig10_fused_matmulDFT"]
    print(f"fig10_fused_matmulDFT: dominant={r['dominant']} "
          f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
          f"collective={r['collective_s']:.3e}s", flush=True)
    for method in ("fused", "traditional"):
        out[f"fig10_{method}"] = lower_fft(
            fig10_shape, (16, 16), ("p0", "p1"), ("p0", "p1"),
            real=True, method=method)
        out[f"fig11_{method}"] = lower_fft(
            fig11_shape, (8, 8, 4), ("p0", "p1", "p2"), ("p0", "p1", "p2"),
            real=False, method=method)
        for k in (f"fig10_{method}", f"fig11_{method}"):
            r = out[k]
            print(f"{k}: dominant={r['dominant']} compute={r['compute_s']:.3e}s "
                  f"memory={r['memory_s']:.3e}s collective={r['collective_s']:.3e}s "
                  f"frac={r['roofline_frac']:.3f}", flush=True)
    for fig in ("fig10", "fig11"):
        f, t = out[f"{fig}_fused"], out[f"{fig}_traditional"]
        print(f"{fig}: traditional/fused HBM bytes = "
              f"{t['hbm_bytes_per_device'] / max(f['hbm_bytes_per_device'], 1):.2f}x, "
              f"collective bytes = "
              f"{t['collectives_per_device'].get('total', 0) / max(f['collectives_per_device'].get('total', 1), 1):.2f}x")
    (ART / "fft_roofline.json").write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
