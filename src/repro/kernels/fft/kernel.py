"""Pallas TPU kernel: batched four-step matmul DFT.

Complex data travels as separate (re, im) f32 planes — the TPU MXU has no
complex type.  The batch of transforms rides the lane axis: one grid step
holds ``tile_b`` transforms of length n = n1·n2 as an ``(n2, n1, tile_b)``
VMEM tile (input digit ``i = i1·n2 + i2`` at ``[i2, i1]``), and every
contraction is a plain 2-D matmul whose N dimension is the batch:

    step 1+2  for each i2: Y[:, i2] = G[i2] @ X[i2]          (MXU)
              G[i2] = diag(twiddle[:, i2]) · F1 — the twiddle multiply is
              folded into n2 precomputed (n1, n1) matrices
    step 3    for each k1: Z[k1] = F2 @ Y[k1]                 (MXU)

Between the steps the ``(n1, tile_b)`` step-1 products are stored into a
``(n1, n2, tile_b)`` VMEM scratch (a sublane-strided store), so step 3
reads each k1's ``(n2, tile_b)`` operand as one leading-index load.  The
output tile is ``(n1, n2, tile_b)`` — output bin ``k = k1 + n1·k2`` at
``[k1, k2]``; the wrapper's inverse layout pass restores natural order.

Every dot runs at ``Precision.HIGHEST`` (the MXU's multi-pass f32), so
the transform keeps f32 accuracy on the chip.  A complex matmul is 4 real
matmuls, or 3 with ``karatsuba=True`` (P1=Fr·Ar, P2=Fi·Ai,
P3=(Fr+Fi)·(Ar+Ai); Re=P1−P2, Im=P3−P1−P2).  Real-input tiles (rfft path)
need only 2 step-1 matmuls via ``real_input=True``.  With n2 = 1 (short or
prime n) step 1 is the whole DFT and step 3 is skipped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_DOT = functools.partial(lax.dot_general,
                         dimension_numbers=(((1,), (0,)), ((), ())),
                         precision=lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)


def _cmatmul(fr, fi, ar, ai, *, karatsuba: bool):
    """(fr + i·fi) @ (ar + i·ai) via real 2-D dots; ``ai is None`` means a
    real right operand (2 dots)."""
    if ai is None:
        return _DOT(fr, ar), _DOT(fi, ar)
    if karatsuba:
        p1 = _DOT(fr, ar)
        p2 = _DOT(fi, ai)
        p3 = _DOT(fr + fi, ar + ai)
        return p1 - p2, p3 - p1 - p2
    return _DOT(fr, ar) - _DOT(fi, ai), _DOT(fr, ai) + _DOT(fi, ar)


def fourstep_kernel(xr_ref, xi_ref, gr_ref, gi_ref, f2r_ref, f2i_ref,
                    or_ref, oi_ref, *scratch, n1: int, n2: int,
                    karatsuba: bool):
    """One batch tile: ``x[0]`` is ``(n2, n1, tile_b)``, ``o[0]`` is
    ``(n1, n2, tile_b)``.  ``xi_ref`` is ``None`` on the real-input path —
    the operand is dropped from the pallas_call, so no zero plane ever
    reaches VMEM."""
    # step 1+2: twiddled n1-point DFTs, one (n1, n1) @ (n1, tile_b) per i2
    for i2 in range(n2):
        ai = None if xi_ref is None else xi_ref[0, i2]
        yr, yi = _cmatmul(gr_ref[i2], gi_ref[i2], xr_ref[0, i2], ai,
                          karatsuba=karatsuba)
        if n2 == 1:
            or_ref[0, :, 0, :] = yr
            oi_ref[0, :, 0, :] = yi
            return
        scratch[0][:, i2, :] = yr
        scratch[1][:, i2, :] = yi
    yr_ref, yi_ref = scratch
    # step 3: n2-point DFTs, one (n2, n2) @ (n2, tile_b) per k1
    f2r, f2i = f2r_ref[...], f2i_ref[...]
    for k1 in range(n1):
        zr, zi = _cmatmul(f2r, f2i, yr_ref[k1], yi_ref[k1], karatsuba=karatsuba)
        or_ref[0, k1] = zr
        oi_ref[0, k1] = zi


def vmem_bytes(n1: int, n2: int, tile_b: int) -> int:
    """Scoped VMEM the kernel needs: double-buffered in/out tiles and
    constants, plus the step-1 scratch (all f32 re/im pairs)."""
    tile = n1 * n2 * tile_b * 4 * 2
    consts = (n2 * n1 * n1 + n2 * n2) * 4 * 2
    return 2 * (2 * tile + consts) + tile


def fourstep_pallas_call(
    ntiles: int, n1: int, n2: int, *, tile_b: int, karatsuba: bool,
    real_input: bool, interpret: bool,
):
    """Build the pallas_call mapping ``(ntiles, n2, n1, tile_b)`` input
    planes to ``(ntiles, n1, n2, tile_b)`` output planes.

    Constant operands: ``G`` re/im ``(n2, n1, n1)`` and ``F2`` re/im
    ``(n2, n2)``.  ``real_input=True`` takes a single ``xr`` input operand
    (rfft path: there is no imaginary plane to ship)."""
    tile_in = pl.BlockSpec((1, n2, n1, tile_b), lambda j: (j, 0, 0, 0))
    tile_out = pl.BlockSpec((1, n1, n2, tile_b), lambda j: (j, 0, 0, 0))
    g_spec = pl.BlockSpec((n2, n1, n1), lambda j: (0, 0, 0))
    f2_spec = pl.BlockSpec((n2, n2), lambda j: (0, 0))
    body = functools.partial(fourstep_kernel, n1=n1, n2=n2, karatsuba=karatsuba)
    if real_input:
        def kern(xr_ref, *refs):
            body(xr_ref, None, *refs)
        x_specs = [tile_in]
    else:
        kern = body
        x_specs = [tile_in, tile_in]
    out = jax.ShapeDtypeStruct((ntiles, n1, n2, tile_b), jnp.float32)
    limit = min(max(32 << 20, 2 * vmem_bytes(n1, n2, tile_b)), 100 << 20)
    return pl.pallas_call(
        kern,
        grid=(ntiles,),
        in_specs=[*x_specs, g_spec, g_spec, f2_spec, f2_spec],
        out_specs=[tile_out, tile_out],
        out_shape=[out, out],
        scratch_shapes=[pltpu.VMEM((n1, n2, tile_b), jnp.float32)] * 2 if n2 > 1 else [],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=limit),
        name="repro_fft_fourstep",
        interpret=interpret,
    )
