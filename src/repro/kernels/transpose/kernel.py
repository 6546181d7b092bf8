"""Pallas TPU kernel: tiled local transpose (A, B, C) -> (B, A, C).

This is the *traditional* redistribution's pack/unpack hot-spot (paper
Eq. 16): swapping the two leading axes of a rank-3 view.  The paper's whole
point is that the fused method never runs this; we implement it as a
first-class kernel so the baseline is honestly optimized — tiles of
(block_a, block_b, C) are staged through VMEM so HBM sees two streaming
passes instead of a strided gather.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _transpose_kernel(x_ref, o_ref):
    # x tile: (ba, bb, C) -> o tile: (bb, ba, C)
    o_ref[...] = jnp.swapaxes(x_ref[...], 0, 1)


def transpose01_pallas_call(a: int, b: int, c: int, *, block_a: int, block_b: int,
                            dtype, interpret: bool):
    assert a % block_a == 0 and b % block_b == 0, (a, b, block_a, block_b)
    grid = (a // block_a, b // block_b)
    return pl.pallas_call(
        _transpose_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_a, block_b, c), lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((block_b, block_a, c), lambda i, j: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, a, c), dtype),
        interpret=interpret,
        name="repro_transpose01",
    )
