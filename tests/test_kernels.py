"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.core.fftcore import BACKWARD, TransformSpec, local_transform
from repro.kernels.fft import ops as fops
from repro.kernels.fft import ref as fref
from repro.kernels.transpose.ops import transpose01


# -- four-step factorization + reference ------------------------------------


@given(n=st.integers(1, 4096))
@settings(max_examples=200, deadline=None)
def test_plan_factors(n):
    n1, n2 = fops.plan_factors(n)
    assert n1 * n2 == n and n1 >= n2 >= 1


@pytest.mark.parametrize("n1,n2", [(4, 4), (8, 4), (16, 16), (32, 8), (12, 5)])
def test_fourstep_ref_matches_fft(n1, n2):
    rng = np.random.default_rng(0)
    x = (rng.standard_normal((3, n1 * n2)) + 1j * rng.standard_normal((3, n1 * n2))
         ).astype(np.complex64)
    got = fref.fourstep_ref(jnp.asarray(x), n1, n2)
    np.testing.assert_allclose(np.asarray(got), np.fft.fft(x, axis=-1),
                               rtol=2e-3, atol=2e-3)


# -- Pallas kernel sweeps ------------------------------------------------------


@pytest.mark.parametrize("n", [8, 17, 96, 128, 384, 1024])  # prime + composite
@pytest.mark.parametrize("karatsuba", [True, False])
def test_fft_matmul_sweep(n, karatsuba):
    rng = np.random.default_rng(n)
    x = (rng.standard_normal((5, n)) + 1j * rng.standard_normal((5, n))).astype(np.complex64)
    got = fops.fft_matmul(jnp.asarray(x), karatsuba=karatsuba)
    tol = 2e-3 * max(1, n // 128)
    np.testing.assert_allclose(np.asarray(got), np.fft.fft(x, axis=-1),
                               rtol=tol, atol=tol * 10)
    inv = fops.fft_matmul(got, inverse=True, karatsuba=karatsuba)
    np.testing.assert_allclose(np.asarray(inv), x, rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("axis", [0, 1, 2])
def test_fft_matmul_axes(axis):
    rng = np.random.default_rng(9)
    shape = (6, 10, 8)
    x = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)
    got = fops.fft_matmul(jnp.asarray(x), axis=axis)
    np.testing.assert_allclose(np.asarray(got), np.fft.fft(x, axis=axis),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("n", [16, 30, 256, 700])
def test_rfft_irfft_matmul(n):
    rng = np.random.default_rng(n)
    x = rng.standard_normal((4, n)).astype(np.float32)
    got = fops.rfft_matmul(jnp.asarray(x))
    tol = 3e-3 * max(1, n // 256)
    np.testing.assert_allclose(np.asarray(got), np.fft.rfft(x, axis=-1),
                               rtol=tol, atol=tol * 20)
    back = local_transform(jnp.asarray(np.fft.rfft(x).astype(np.complex64)), 1,
                           BACKWARD, TransformSpec.r2c(), n=n, impl="matmul")
    np.testing.assert_allclose(np.asarray(back), x, rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("impl", ["jnp", "matmul"])
@pytest.mark.parametrize("n", [12, 384, 385])
def test_c2r_matches_numpy_irfft(impl, n):
    """The plan's c2r (whichever form its shape takes) equals numpy's
    irfft, including dropping the imaginary parts of the DC and
    Nyquist bins of a non-Hermitian input, on a non-last axis."""
    rng = np.random.default_rng(n)
    shape = (n // 2 + 1, 3, 5)
    spec = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
            ).astype(np.complex64)
    got = local_transform(jnp.asarray(spec), 0, BACKWARD, TransformSpec.r2c(),
                          n=n, impl=impl)
    want = np.fft.irfft(spec.astype(np.complex128), n=n, axis=0)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def _extension_c2r(x, axis, n, impl, nbatch=0):
    """The c2r past the dense form's bound, at any shape: the Hermitian
    extension and a complex inverse FFT, packed where the block has an axis
    to split (``fftcore._c2r_split``), else one per row."""
    from repro.core import fftcore

    axis += nbatch
    split = fftcore._c2r_split(x.shape, axis, nbatch)
    if split is None:
        return fftcore._c2r_full(x, axis, n, impl)
    return fftcore._c2r_packed(x, axis, n, impl, split)


#: (other axes with the c2r axis at ``axis``, n, n_keep, axis, nbatch,
#: the axis whose halves are packed together, None for one FFT per row)
C2R_CASES = {
    "even-split": ((4, 6), 12, None, 2, 0, 0),
    "even-inner-split": ((3, 6), 12, None, 0, 0, 2),
    "odd-others": ((3, 5), 12, None, 1, 0, None),
    "one-d": ((), 12, None, 0, 0, None),
    "odd-n": ((4, 2), 13, None, 1, 0, 0),
    "odd-n-full": ((3,), 385, None, 1, 0, None),
    "pruned-packed": ((6, 3), 12, 5, 2, 0, 0),
    "pruned-odd-n": ((2,), 15, 5, 1, 0, 0),
    "pruned-full": ((5, 3), 48, 17, 0, 0, None),
    "stacked": ((3, 6, 5), 48, 17, 2, 1, 1),
    "stacked-all-odd": ((9, 5, 3), 48, 25, 2, 1, None),
    "stacked-even-fields": ((4, 6, 3), 12, None, 2, 1, 1),
    "stacked-even-fields-odd-space": ((2, 5, 3), 12, None, 2, 1, None),
}


@pytest.mark.parametrize("impl", ["jnp", "matmul"])
@pytest.mark.parametrize("case", sorted(C2R_CASES))
def test_packed_c2r_matches_numpy_irfft(impl, case):
    """Past the dense form's bound the c2r packs two real rows into one
    complex inverse FFT where the block has a spatial axis of even length
    besides the c2r axis (the outermost; batch axes are never split), and
    falls back to one per row where it has none: both equal numpy's irfft
    of the kept bins zero-padded to ``n//2+1``, on a non-Hermitian spectrum
    (complex DC and Nyquist bins)."""
    from repro.core import fftcore

    others, n, n_keep, axis, nbatch, split = C2R_CASES[case]
    m = n // 2 + 1 if n_keep is None else n_keep
    shape = list(others)
    shape.insert(axis + nbatch, m)
    assert fftcore._c2r_split(tuple(shape), axis + nbatch, nbatch) == split
    rng = np.random.default_rng(len(case) * n)
    spec = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
            ).astype(np.complex64)
    got = _extension_c2r(jnp.asarray(spec), axis, n, impl, nbatch)
    pads = [(0, 0)] * spec.ndim
    pads[axis + nbatch] = (0, n // 2 + 1 - m)
    want = np.fft.irfft(np.pad(spec.astype(np.complex128), pads), n=n, axis=axis + nbatch)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("impl", ["jnp", "matmul"])
@pytest.mark.parametrize("others", [(2, 6, 5), (2, 5, 3)], ids=["packed", "full"])
def test_c2r_keeps_each_fields_precision(impl, others):
    """Two stacked fields, the second 1e-5 times the first: each keeps its
    own relative precision, because the packed c2r pairs rows of one field
    (here halves of its even spatial axis), never rows of two fields."""
    n, m = 12, 7
    shape = (*others, m)
    rng = np.random.default_rng(17)
    spec = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
    spec[1] *= 1e-5
    spec = spec.astype(np.complex64)
    got = np.asarray(_extension_c2r(jnp.asarray(spec), 2, n, impl, nbatch=1))
    want = np.fft.irfft(spec.astype(np.complex128), n=n, axis=3)
    for f in range(2):
        err = np.linalg.norm(got[f] - want[f]) / np.linalg.norm(want[f])
        assert err < 2e-6, (f, err)


@pytest.mark.parametrize("impl", ["jnp", "matmul"])
@pytest.mark.parametrize("n", [12, 13])
def test_packed_c2r_drops_edge_imaginary_parts(impl, n):
    """A spectrum of only a DC and a Nyquist bin, each with an imaginary
    part, in one half of the packed pair: nothing leaks into the other
    half, whose spectrum is zero."""
    m = n // 2 + 1
    spec = np.zeros((2, m), np.complex64)
    spec[0, 0] = 1.5 + 2.0j
    spec[0, m - 1] = -0.5 + 3.0j  # Nyquist for even n, an ordinary bin for odd
    got = np.asarray(_extension_c2r(jnp.asarray(spec), 1, n, impl))
    want = np.fft.irfft(spec.astype(np.complex128), n=n, axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.abs(got[1]).max() < 1e-6


@pytest.mark.parametrize("block_b", [1, 4, 16])
def test_fft_matmul_block_invariance(block_b):
    rng = np.random.default_rng(3)
    x = (rng.standard_normal((7, 64)) + 1j * rng.standard_normal((7, 64))).astype(np.complex64)
    got = fops.fft_matmul(jnp.asarray(x), block_b=block_b)
    np.testing.assert_allclose(np.asarray(got), np.fft.fft(x, axis=-1),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("n", [64, 512])
def test_fft_matmul_dots_run_at_highest_precision(n):
    """Every dot inside the four-step kernel asks for HIGHEST precision:
    at the MXU's default bf16 passes an f32 DFT loses ~3 digits."""
    import jax
    from jax import lax

    jaxpr = jax.make_jaxpr(lambda x: fops.fft_matmul(x))(
        jnp.zeros((4, n), jnp.complex64))
    dots = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "dot_general":
                dots.append(eqn.params["precision"])
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jaxpr.jaxpr)
    assert dots
    assert all(p == (lax.Precision.HIGHEST,) * 2 for p in dots), set(dots)


# -- transpose kernel ----------------------------------------------------------


@given(a=st.integers(1, 24), b=st.integers(1, 24), c=st.integers(1, 8),
       dt=st.sampled_from(["float32", "complex64"]))
@settings(max_examples=25, deadline=None)
def test_transpose01_sweep(a, b, c, dt):
    rng = np.random.default_rng(a * 100 + b)
    x = rng.standard_normal((a, b, c)).astype(dt)
    if dt == "complex64":
        x = (x + 1j * rng.standard_normal((a, b, c))).astype(dt)
    got = transpose01(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), x.swapaxes(0, 1))
