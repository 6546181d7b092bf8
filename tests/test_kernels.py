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
    """The plan's c2r (Hermitian extension + inverse complex DFT) equals
    numpy's irfft, including dropping the imaginary parts of the DC and
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
