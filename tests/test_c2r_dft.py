"""The c2r stage's dense form (``fftcore._c2r_dft``): a real inverse DFT
read straight from the kept bins, one real matmul of their real and
imaginary parts at HIGHEST precision, and the shape rule that chooses it
(``fftcore._c2r_dense``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core import fftcore
from repro.core.fftcore import TransformSpec
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT

CASES = [(n, m) for n in (6, 7, 8, 48, 384) for m in (n // 3 + 1, n // 2 + 1)]


@pytest.mark.parametrize("nbatch", [0, 1])
@pytest.mark.parametrize("last", [True, False], ids=["last", "inner"])
@pytest.mark.parametrize("n,m", CASES)
def test_c2r_dft_matches_numpy_irfft(n, m, last, nbatch):
    """Equal to numpy's float64 irfft of the kept bins zero-padded to
    ``n//2+1``, on a spectrum whose DC and Nyquist bins have imaginary
    parts (they drop out), along the last axis and an inner one."""
    others = [2] * nbatch + [3, 4]
    axis = len(others) if last else nbatch
    shape = others[:axis] + [m] + others[axis:]
    rng = np.random.default_rng(n * m + 7 * last + nbatch)
    spec = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
    edge = [slice(None)] * len(shape)
    edge[axis] = 0
    spec[tuple(edge)] += 2.5j  # DC
    if n % 2 == 0 and m == n // 2 + 1:
        edge[axis] = m - 1
        spec[tuple(edge)] -= 1.5j  # Nyquist
    spec = spec.astype(np.complex64)
    got = fftcore._c2r_dft(jnp.asarray(spec), axis, n, nbatch)
    pads = [(0, 0)] * len(shape)
    pads[axis] = (0, n // 2 + 1 - m)
    want = np.fft.irfft(np.pad(spec.astype(np.complex128), pads), n=n, axis=axis)
    assert got.shape == want.shape and got.dtype == jnp.float32
    err = np.linalg.norm(np.asarray(got, np.float64) - want) / np.linalg.norm(want)
    assert err <= 1e-5, err


def test_shape_rule():
    """The dealiased DNS axis (129 kept bins to 384 points) is dense, and
    so is every length up to the bound; past it the c2r keeps the extension
    and the inverse FFT, packed where the block has an axis to split."""
    bound = fftcore._C2R_DFT_MAX_N
    assert fftcore._c2r_dense(384)
    assert all(fftcore._c2r_dense(n) for n in (1, 6, 7, 1000, bound))
    assert not any(fftcore._c2r_dense(n) for n in (bound + 1, bound + 2, 4 * bound))
    assert fftcore._c2r_split((3, 384, 384, 129), 3, 1) == 1


def test_dns_backward_dots_ask_for_highest():
    """Every ``dot_general`` of the DNS-shaped backward (3 fields of N = 256
    modes to M = 384 points) asks for HIGHEST precision and a float32
    result: the chip's default float32 dot is a single bf16 pass."""
    n, m = 256, 384
    mesh = make_mesh((1, 1), ("p0", "p1"), devices=jax.devices()[:1])
    plan = ParallelFFT(mesh, (m, m, m), ("p0", "p1"), transforms=(
        TransformSpec.pruned(n), TransformSpec.pruned(n), TransformSpec.r2c(n // 2 + 1)))
    x = jax.ShapeDtypeStruct((3, *plan.output_pencil.physical), plan.spectral_dtype)
    dots = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "dot_general":
                dots.append((eqn.params["precision"], eqn.params["preferred_element_type"]))
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jax.make_jaxpr(plan.backward)(x).jaxpr)
    assert dots
    assert all(p == (lax.Precision.HIGHEST,) * 2 and t == jnp.float32 for p, t in dots), dots


def _bits(a):
    return np.ascontiguousarray(np.asarray(a)).view(np.uint32)


@pytest.mark.parametrize("shape,n", [((3, 8, 3, 11), 20), ((2, 5, 6, 5), 12),
                                     ((3, 6, 4, 9), 24)])
def test_c2r_dft_bits_independent_of_the_block(shape, n):
    """Each field of a stacked block comes out bit for bit as when it is
    transformed alone, and a pruned spectrum as its zero-padded whole."""
    rng = np.random.default_rng(n)
    x = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)
    stacked = fftcore._c2r_dft(jnp.asarray(x), 3, n, 1)
    alone = [fftcore._c2r_dft(jnp.asarray(f), 2, n) for f in x]
    np.testing.assert_array_equal(_bits(stacked), _bits(np.stack(alone)))
    pads = [(0, 0)] * 3 + [(0, n // 2 + 1 - shape[-1])]
    whole = fftcore._c2r_dft(jnp.asarray(np.pad(x, pads)), 3, n, 1)
    np.testing.assert_array_equal(_bits(stacked), _bits(whole))
