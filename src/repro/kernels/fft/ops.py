"""Jit'd wrappers for the four-step matmul DFT Pallas kernel.

``fft_matmul(x, axis, inverse)``   — complex-to-complex, any axis.
``rfft_matmul(x, axis)``           — real input, Hermitian-reduced output
                                     (its inverse is ``fftcore``'s c2r: a
                                     dense real DFT from the kept bins, or
                                     past its bound a Hermitian extension
                                     + inverse ``fft_matmul``).

Factorization policy (``plan_factors``): N = n1·n2 with n1 ≥ n2, both as
close to √N (and MXU-friendly multiples of 8/128) as possible; prime or tiny
N degenerates to a single (N,N) DFT matmul.  Inverse transforms use
ifft(x) = conj(fft(conj(x)))/N so one kernel serves both directions.
Every DFT matmul here runs at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fft import ref
from repro.kernels.fft.kernel import fourstep_pallas_call

_SINGLE_MATMUL_MAX = 256  # below this, one (N,N) DFT matmul beats two steps


def plan_factors(n: int) -> tuple[int, int]:
    """Pick (n1, n2), n = n1*n2, n1 >= n2, n1 minimal such — or (n, 1)."""
    if n <= _SINGLE_MATMUL_MAX:
        return n, 1
    best = (n, 1)
    for n2 in range(int(math.isqrt(n)), 0, -1):
        if n % n2 == 0:
            best = (n // n2, n2)
            break
    return best


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("inverse", "axis", "karatsuba", "block_b", "interpret"))
def fft_matmul(
    x: jax.Array,
    *,
    axis: int = -1,
    inverse: bool = False,
    karatsuba: bool = True,
    block_b: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Complex 1-D DFT along ``axis`` via the four-step Pallas kernel.

    ``block_b`` is the number of transforms per grid step (the kernel's
    lane tile; default :func:`tile_width`).  On the chip it must be a
    multiple of 128 unless it covers the whole batch."""
    x = jnp.asarray(x, jnp.complex64)
    axis = axis % x.ndim
    n = x.shape[axis]
    if inverse:
        y = fft_matmul(jnp.conj(x), axis=axis, inverse=False, karatsuba=karatsuba,
                       block_b=block_b, interpret=interpret)
        return jnp.conj(y) / n
    yr, yi = _fourstep(jnp.real(x), jnp.imag(x), axis, n, karatsuba=karatsuba,
                       block_b=block_b, interpret=interpret)
    return jax.lax.complex(yr, yi)


@functools.partial(jax.jit, static_argnames=("axis", "karatsuba", "block_b", "interpret"))
def rfft_matmul(
    x: jax.Array, *, axis: int = -1, karatsuba: bool = True,
    block_b: int | None = None, interpret: bool | None = None,
) -> jax.Array:
    """Real-input DFT; returns the n//2+1 non-redundant bins (rfft)."""
    x = jnp.asarray(x, jnp.float32)
    axis = axis % x.ndim
    n = x.shape[axis]
    yr, yi = _fourstep(x, None, axis, n // 2 + 1, karatsuba=karatsuba,
                       block_b=block_b, interpret=interpret)
    return jax.lax.complex(yr, yi)


@functools.partial(jax.jit, static_argnames=("axis", "trig_type"))
def dct_matmul(x: jax.Array, *, axis: int = -1, trig_type: int = 2) -> jax.Array:
    """Unnormalized DCT-II/III along ``axis`` as one transform-matrix matmul.

    The MXU path for trigonometric axes: unlike the DFT there is no
    four-step factorization with real twiddles, so the whole (n, n) cosine
    matrix is applied in a single f32 matmul (HIGHEST precision — the MXU
    runs it as 3-pass bf16 passes, which keeps ~f32 accuracy).  Complex
    blocks transform re/im independently (the DCT is real-to-real).
    """
    return _trig_matmul(x, axis, ref.dct_matrix(x.shape[axis % x.ndim], trig_type))


@functools.partial(jax.jit, static_argnames=("axis", "trig_type"))
def dst_matmul(x: jax.Array, *, axis: int = -1, trig_type: int = 2) -> jax.Array:
    """Unnormalized DST-II/III along ``axis`` (see :func:`dct_matmul`)."""
    return _trig_matmul(x, axis, ref.dst_matrix(x.shape[axis % x.ndim], trig_type))


def _trig_matmul(x, axis, mat):
    m = jnp.asarray(mat)
    axis = axis % x.ndim

    def apply(real_block):
        y = jnp.moveaxis(real_block.astype(jnp.float32), axis, -1)
        y = jnp.matmul(y, m.T, precision=jax.lax.Precision.HIGHEST)
        return jnp.moveaxis(y, -1, axis)

    if jnp.iscomplexobj(x):
        return jax.lax.complex(apply(jnp.real(x)), apply(jnp.imag(x)))
    return apply(x).astype(x.dtype)


# ---------------------------------------------------------------------------


def tile_width(n: int, nbatch: int) -> int:
    """Transforms per grid step: a multiple of 128 lanes keeping one f32
    plane tile near 512 KiB, or the whole batch when it is smaller."""
    tile = 128
    while tile * 2 * n <= 131072 and tile * 2 <= nbatch:
        tile *= 2
    return min(tile, nbatch)


@functools.lru_cache(maxsize=None)
def _constants(n1: int, n2: int) -> tuple[np.ndarray, ...]:
    """Kernel operands: ``G[i2] = diag(twiddle[:, i2]) · F1`` and ``F2``,
    computed in float64 and rounded once to f32 re/im planes."""
    f1 = ref.dft_matrix(n1, np.complex128)
    tw = ref.twiddle_matrix(n1, n2, np.complex128)
    g = tw.T[:, :, None] * f1[None]  # (n2, n1, n1)
    f2 = ref.dft_matrix(n2, np.complex128)
    return tuple(a.astype(np.float32) for a in
                 (g.real, g.imag, f2.real, f2.imag))


def _fourstep(xr, xi, axis, keep, *, karatsuba, block_b, interpret):
    """DFT of the (re, im) planes along ``axis``, keeping the first
    ``keep`` output bins.  The layout pass into the kernel's
    ``(tiles, n2, n1, tile_b)`` form (transform axis leading, digit-
    permuted; batch on lanes) and the pass back are each one XLA
    transpose."""
    if interpret is None:
        interpret = _interpret_default()
    n = xr.shape[axis]
    n1, n2 = plan_factors(n)
    rest = xr.shape[:axis] + xr.shape[axis + 1:]
    b = int(np.prod(rest, dtype=np.int64))
    tb = tile_width(n, b) if block_b is None else max(1, min(block_b, b))
    ntiles = -(-b // tb)

    def prep(a):
        a = jnp.moveaxis(a, axis, 0).reshape(n1, n2, b)
        if ntiles * tb != b:
            a = jnp.pad(a, ((0, 0), (0, 0), (0, ntiles * tb - b)))
        return a.reshape(n1, n2, ntiles, tb).transpose(2, 1, 0, 3)

    def post(y):  # (tiles, k1, k2, t) -> bin k = k1 + n1*k2 along axis
        y = y.transpose(2, 1, 0, 3).reshape(n, ntiles * tb)[:keep, :b]
        return jnp.moveaxis(y.reshape(keep, *rest), 0, axis)

    planes = (prep(xr),) if xi is None else (prep(xr), prep(xi))
    call = fourstep_pallas_call(ntiles, n1, n2, tile_b=tb, karatsuba=karatsuba,
                                real_input=xi is None, interpret=interpret)
    yr, yi = call(*planes, *map(jnp.asarray, _constants(n1, n2)))
    return post(yr), post(yi)
