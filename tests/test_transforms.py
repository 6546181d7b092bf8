"""Per-axis TransformSpec plans (r2c / DCT / DST / pruned) — roundtrip and
scipy-reference correctness on slab and pencil grids, spec validation, and
the mixed-transform autotuner path (issue acceptance criteria)."""

import numpy as np
import pytest

from repro.core.fftcore import TransformSpec, as_spec, dealias_grid


# ---------------------------------------------------------------------------
# Unit tests (no devices)
# ---------------------------------------------------------------------------


def test_spec_parsing_and_tags():
    assert as_spec("c2c") == TransformSpec.c2c()
    assert as_spec("r2c") == TransformSpec.r2c()
    assert as_spec("dct2") == TransformSpec.dct(2)
    assert as_spec("dct3") == TransformSpec.dct(3)
    assert as_spec("dst2") == TransformSpec.dst(2)
    assert as_spec("dst3") == TransformSpec.dst(3)
    spec = TransformSpec.pruned(12)
    assert as_spec(spec) is spec
    assert spec.tag() == "c2c[12]"
    assert TransformSpec.r2c(n_keep=5).tag() == "r2c[5]"
    assert TransformSpec.dct(3).tag() == "dct3"
    with pytest.raises(ValueError):
        as_spec("dft")
    with pytest.raises(TypeError):
        as_spec(42)


def test_spec_validation():
    with pytest.raises(ValueError):
        TransformSpec("hartley")
    with pytest.raises(ValueError):
        TransformSpec.dct(1)  # only II/III supported
    with pytest.raises(ValueError):
        TransformSpec("dct", n_keep=4)  # pruning is c2c/r2c only
    with pytest.raises(ValueError):
        TransformSpec.pruned(0)
    with pytest.raises(ValueError):
        TransformSpec.pruned(9).spectral_extent(8)  # n_keep > spectrum
    assert TransformSpec.c2c().spectral_extent(8) == 8
    assert TransformSpec.r2c().spectral_extent(9) == 5
    assert TransformSpec.r2c(n_keep=3).spectral_extent(9) == 3
    assert TransformSpec.pruned(8).spectral_extent(12) == 8
    assert TransformSpec.dst().spectral_extent(7) == 7
    assert dealias_grid(32) == 48


def test_plan_transforms_validation():
    from repro.core.meshutil import make_mesh
    from repro.core.pfft import ParallelFFT

    mesh = make_mesh((1,), ("p0",))
    with pytest.raises(ValueError):  # wrong arity
        ParallelFFT(mesh, (8, 8, 8), ("p0",), transforms=("c2c", "c2c"))
    # the constructor's whole surface: no real= shorthand, no execution
    # keywords beside config=
    import inspect

    assert list(inspect.signature(ParallelFFT).parameters) == [
        "mesh", "shape", "grid", "config", "transforms"]
    # r2c must be applied while the data is still real: every axis after it
    # (higher index, applied earlier) must be dct/dst
    with pytest.raises(ValueError):
        ParallelFFT(mesh, (8, 8), ("p0",), transforms=("r2c", "c2c"))
    with pytest.raises(ValueError):  # two r2c axes
        ParallelFFT(mesh, (8, 8, 8), ("p0",), transforms=("c2c", "r2c", "r2c"))
    # legal: r2c with trailing real-to-real axes, c2c applied after
    plan = ParallelFFT(mesh, (8, 8, 8), ("p0",), transforms=("c2c", "r2c", "dst2"))
    assert plan.output_pencil.logical == (8, 5, 8)
    # all-real plans keep a real spectral dtype end to end
    plan = ParallelFFT(mesh, (8, 8), ("p0",), transforms=("dct2", "dct2"))
    import jax.numpy as jnp

    assert plan.input_dtype == jnp.float32
    assert plan.spectral_dtype == jnp.float32


def test_pruned_plan_structure():
    """Pruned axes shrink the pencil trace (exchanges after a truncation
    carry only the retained modes) and the default transforms equal the
    explicit all-c2c spec."""
    from repro.core.meshutil import make_mesh
    from repro.core.pfft import ParallelFFT

    mesh = make_mesh((1, 1), ("p0", "p1"))
    plan = ParallelFFT(mesh, (12, 12, 12), ("p0", "p1"),
                       transforms=(TransformSpec.pruned(8), TransformSpec.pruned(8),
                                   TransformSpec.r2c(n_keep=5)))
    assert plan.output_pencil.logical == (8, 8, 5)
    # dealiased exchanges move fewer elements than the full-spectrum plan:
    # every post-truncation pencil in the trace is elementwise smaller
    import numpy as np
    from repro.core.pfft import ExchangeStage

    full = ParallelFFT(mesh, (12, 12, 12), ("p0", "p1"),
                       transforms=("c2c", "c2c", "r2c"))
    pruned_elems = sum(int(np.prod(p.logical)) for st, p in
                       zip(plan.stages, plan.pencil_trace)
                       if isinstance(st, ExchangeStage))
    full_elems = sum(int(np.prod(p.logical)) for st, p in
                     zip(full.stages, full.pencil_trace)
                     if isinstance(st, ExchangeStage))
    assert pruned_elems < full_elems
    default = ParallelFFT(mesh, (12, 12, 12), ("p0", "p1"))
    spec = ParallelFFT(mesh, (12, 12, 12), ("p0", "p1"),
                       transforms=("c2c", "c2c", "c2c"))
    assert default.transforms == spec.transforms
    assert default.output_pencil == spec.output_pencil


def test_trig_matrices_are_mutual_inverses():
    from repro.kernels.fft import ref

    for n in (5, 8, 16):
        c2, c3 = ref.dct_matrix(n, 2, np.float64), ref.dct_matrix(n, 3, np.float64)
        np.testing.assert_allclose(c3 @ c2, 2 * n * np.eye(n), atol=1e-9)
        s2, s3 = ref.dst_matrix(n, 2, np.float64), ref.dst_matrix(n, 3, np.float64)
        np.testing.assert_allclose(s3 @ s2, 2 * n * np.eye(n), atol=1e-9)


def test_local_trig_transforms_vs_scipy():
    """fftcore's FFT-trick DCT/DST and the kernels' matmul path both match
    scipy's unnormalized conventions, every type, both parities."""
    sf = pytest.importorskip("scipy.fft")
    import jax.numpy as jnp

    from repro.core import fftcore

    rng = np.random.default_rng(0)
    for n in (8, 9):
        x = rng.standard_normal((3, n)).astype(np.float32)
        for kind, sref in (("dct", sf.dct), ("dst", sf.dst)):
            for tt in (2, 3):
                spec = TransformSpec(kind, trig_type=tt)
                want = sref(x, type=tt, axis=1)
                for impl in ("jnp", "matmul"):
                    got = np.asarray(fftcore.local_transform(
                        jnp.asarray(x), 1, fftcore.FORWARD, spec, n=n, impl=impl))
                    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
                    back = np.asarray(fftcore.local_transform(
                        jnp.asarray(want), 1, fftcore.BACKWARD, spec, n=n, impl=impl))
                    np.testing.assert_allclose(back, x, rtol=1e-4, atol=1e-4)


#: (spec, n, axis, nbatch): even and odd keeps, a keep of one mode (no
#: negative half), the whole spectrum, r2c keeps below and at n//2+1, and
#: a stacked field axis
PRUNE_CASES = {
    "c2c-even": (TransformSpec.pruned(8), 12, 1, 0),
    "c2c-odd": (TransformSpec.pruned(7), 12, 0, 0),
    "c2c-one": (TransformSpec.pruned(1), 6, 2, 0),
    "c2c-whole": (TransformSpec.pruned(8), 8, 1, 0),
    "c2c-stacked": (TransformSpec.pruned(8), 12, 1, 1),
    "r2c-pruned": (TransformSpec.r2c(5), 12, 2, 0),
    "r2c-whole": (TransformSpec.r2c(7), 12, 2, 0),
    "r2c-stacked": (TransformSpec.r2c(5), 12, 1, 1),
}


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint32)


def _primitives(jaxpr):
    """Every primitive of a jaxpr, sub-jaxprs included."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("case", sorted(PRUNE_CASES))
def test_pruning_is_exact_static_slicing(case):
    """The keep, the zero-scatter and the r2c keep equal a NumPy ``np.take``
    of the retained modes bit for bit, and a pruned stage traces no
    ``gather`` in either direction."""
    import jax
    import jax.numpy as jnp

    from repro.core import fftcore

    spec, n, axis, nbatch = PRUNE_CASES[case]
    k = spec.spectral_extent(n)
    ax = axis + nbatch
    full = TransformSpec(spec.kind)
    if spec.kind == "c2c":
        kept = np.r_[0:(k + 1) // 2, n - k // 2:n]
    else:
        kept = np.arange(k)
    rng = np.random.default_rng(0)
    shape = [5, 6, 7]
    shape[axis] = n
    shape = [2] * nbatch + shape
    x = rng.standard_normal(shape).astype(np.float32)
    if spec.kind == "c2c":
        x = (x + 1j * rng.standard_normal(shape)).astype(np.complex64)

    def stage(v, sign, s):
        return fftcore.local_transform(jnp.asarray(v), axis, sign, s, n=n, nbatch=nbatch)

    got = np.asarray(stage(x, fftcore.FORWARD, spec))
    want = np.take(np.asarray(stage(x, fftcore.FORWARD, full)), kept, axis=ax)
    np.testing.assert_array_equal(_bits(got), _bits(want))

    # the retained modes back in place: a take of them and one zero
    y = rng.standard_normal(got.shape) + 1j * rng.standard_normal(got.shape)
    y = y.astype(np.complex64)
    y.flat[::5] = -0.0  # signed zeros keep their sign
    slot = np.full(full.spectral_extent(n), k)
    slot[kept] = np.arange(k)
    zero_shape = list(y.shape)
    zero_shape[ax] = 1
    scattered = np.take(np.concatenate([y, np.zeros(zero_shape, y.dtype)], axis=ax), slot,
                        axis=ax)
    got = np.asarray(stage(y, fftcore.BACKWARD, spec))
    want = np.asarray(stage(scattered, fftcore.BACKWARD, full))
    np.testing.assert_array_equal(_bits(got), _bits(want))
    if spec.kind == "c2c":
        np.testing.assert_array_equal(
            _bits(fftcore._keep_centered(jnp.asarray(scattered), ax, k)), _bits(y))
        np.testing.assert_array_equal(
            _bits(fftcore._scatter_centered(jnp.asarray(y), ax, n, k)), _bits(scattered))

    for sign, v in ((fftcore.FORWARD, x), (fftcore.BACKWARD, y)):
        jaxpr = jax.make_jaxpr(lambda v, sign=sign: stage(v, sign, spec))(v)
        assert "gather" not in set(_primitives(jaxpr.jaxpr)), (case, sign)


# ---------------------------------------------------------------------------
# Distributed plans (subprocess, 8 fake devices)
# ---------------------------------------------------------------------------


def test_transform_plans_vs_scipy(subproc):
    """Every TransformSpec kind in a distributed plan, slab and pencil
    grids: forward matches the scipy/np reference composition and
    backward(forward(x)) round-trips below 1e-5 relative L2."""
    pytest.importorskip("scipy.fft")
    subproc("""
import jax, jax.numpy as jnp, numpy as np
import scipy.fft as sf
from repro.core.fftcore import TransformSpec
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT

mesh = make_mesh((2, 4), ("p0", "p1"))
rng = np.random.default_rng(0)
shape = (16, 12, 20)

def ref_nd(x, specs):
    y = np.asarray(x, np.float64)
    for axis in range(len(specs) - 1, -1, -1):  # plan apply order
        sp = specs[axis]
        if sp.kind == "r2c":
            y = np.fft.rfft(y, axis=axis)
        elif sp.kind == "c2c":
            y = np.fft.fft(y, axis=axis)
        elif sp.kind == "dct":
            y = sf.dct(y.real, type=sp.trig_type, axis=axis) + (
                1j * sf.dct(y.imag, type=sp.trig_type, axis=axis)
                if np.iscomplexobj(y) else 0)
        else:
            y = sf.dst(y.real, type=sp.trig_type, axis=axis) + (
                1j * sf.dst(y.imag, type=sp.trig_type, axis=axis)
                if np.iscomplexobj(y) else 0)
    return y

cases = [
    ("dct2", "dct2", "dct2"),
    ("dst2", "dst2", "dst2"),
    ("dct3", "dst3", "dct2"),
    ("dct2", "c2c", "r2c"),      # the Chebyshev-Dirichlet Poisson layout
    ("c2c", "r2c", "dst2"),      # r2c mid-plan behind a trailing DST
]
for grid in (("p0",), ("p0", "p1")):
    for tags in cases:
        specs = tuple(TransformSpec(t[:3], trig_type=int(t[3])) if t[0] == "d"
                      else TransformSpec(t) for t in tags)
        plan = ParallelFFT(mesh, shape, grid, transforms=tags)
        x = rng.standard_normal(shape).astype(np.float32)
        y = np.asarray(plan.forward(jnp.asarray(x)))
        want = ref_nd(x, specs)
        scale = np.abs(want).max()
        np.testing.assert_allclose(y, want.astype(y.dtype), rtol=2e-4,
                                   atol=2e-5 * scale)
        back = np.asarray(plan.backward(jnp.asarray(y)))
        rel = np.linalg.norm(back - x) / np.linalg.norm(x)
        assert rel < 1e-5, (grid, tags, rel)
        print("ok", grid, tags)
print("TRANSFORM PLANS VS SCIPY OK")
""", ndev=8)


def test_pruned_dealias_plans(subproc):
    """Pruned/truncated axes (the fused 3/2-rule): forward equals
    truncate(fft_n(x)) with the centered keep, spectral round trip
    forward(backward(s)) == s below 1e-5, and backward+forward of a
    physical field equals the np dealiasing projection — slab and pencil."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.fftcore import TransformSpec, dealias_grid
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT

mesh = make_mesh((2, 4), ("p0", "p1"))
rng = np.random.default_rng(0)
N = 8
M = dealias_grid(N)  # 12
keep = np.r_[0:(N + 1) // 2, M - N // 2:M]

for grid in (("p0",), ("p0", "p1")):
    # pure c2c pruning: arbitrary complex spectra round-trip exactly
    plan = ParallelFFT(mesh, (M, M, M), grid,
                       transforms=(TransformSpec.pruned(N),) * 3)
    assert plan.output_pencil.logical == (N, N, N)
    x = (rng.standard_normal((M, M, M))
         + 1j * rng.standard_normal((M, M, M))).astype(np.complex64)
    y = np.asarray(plan.forward(jnp.asarray(x)))
    want = np.fft.fftn(x)[np.ix_(keep, keep, keep)]
    np.testing.assert_allclose(y, want, rtol=3e-4, atol=3e-3)
    s = (rng.standard_normal((N, N, N))
         + 1j * rng.standard_normal((N, N, N))).astype(np.complex64)
    rt = np.asarray(plan.forward(plan.backward(jnp.asarray(s))))
    rel = np.linalg.norm(rt - s) / np.linalg.norm(s)
    assert rel < 1e-5, (grid, rel)
    # backward o forward is the np dealiasing projection of the field
    proj = np.asarray(plan.backward(plan.forward(jnp.asarray(x))))
    full = np.fft.fftn(x)
    mask = np.zeros((M, M, M))
    mask[np.ix_(keep, keep, keep)] = 1.0
    np.testing.assert_allclose(proj, np.fft.ifftn(full * mask),
                               rtol=3e-4, atol=3e-3)

    # dealiased rfft pipeline (the navier_stokes layout): valid spectra
    # (unpaired -N/2 rows empty) round-trip below 1e-5
    plan = ParallelFFT(mesh, (M, M, M), grid,
                       transforms=(TransformSpec.pruned(N), TransformSpec.pruned(N),
                                   TransformSpec.r2c(n_keep=N // 2 + 1)))
    assert plan.output_pencil.logical == (N, N, N // 2 + 1)
    u = rng.standard_normal((M, M, M)).astype(np.float32)
    s = np.array(plan.forward(jnp.asarray(u)))
    s[N // 2, :, :] = 0
    s[:, N // 2, :] = 0
    rt = np.asarray(plan.forward(plan.backward(jnp.asarray(s))))
    rel = np.linalg.norm(rt - s) / np.linalg.norm(s)
    assert rel < 1e-5, (grid, rel)
    print("ok", grid)
print("PRUNED DEALIAS OK")
""", ndev=8)


def test_mixed_transform_auto_tuned(subproc, tmp_path):
    """method="auto" tunes a mixed-transform (pruned + r2c) plan end to
    end: the tuned schedule round-trips through the disk cache into a
    fresh-memo plan, and the transform stays correct under the tuned
    per-stage schedule (issue acceptance criterion)."""
    cache = tmp_path / "fft_tuner.json"
    subproc(f"""
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core import tuner
from repro.core.fftcore import TransformSpec
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig

cache = {str(cache)!r}
mesh = make_mesh((2, 2), ("p0", "p1"))
specs = (TransformSpec.pruned(8), TransformSpec.c2c(), TransformSpec.r2c())
plan = ParallelFFT(mesh, (12, 8, 8), ("p0", "p1"), transforms=specs,
                   config=PlanConfig(method="auto", tuner_cache=cache))
sched = plan.schedule
assert len(sched) == plan.n_exchanges == 2

# the cache key must carry the per-axis transform tags (a pruned plan's
# stage shapes differ from the plain c2c plan of the same global shape)
disk = json.loads(open(cache).read())
key = tuner.plan_key(plan)
assert key in disk
assert json.loads(key)["transforms"] == ["c2c[8]", "c2c", "r2c"]

# fresh-memo reload must consume the cache, not re-benchmark
tuner._MEMO.clear()
tuner.tune_plan = None
plan2 = ParallelFFT(mesh, (12, 8, 8), ("p0", "p1"), transforms=specs,
                    config=PlanConfig(method="auto", tuner_cache=cache))
assert plan2.schedule == sched

# and the tuned mixed-transform plan is still correct
rng = np.random.default_rng(0)
u = rng.standard_normal((12, 8, 8)).astype(np.float32)
fused = ParallelFFT(mesh, (12, 8, 8), ("p0", "p1"), transforms=specs)
np.testing.assert_allclose(np.asarray(plan2.forward(jnp.asarray(u))),
                           np.asarray(fused.forward(jnp.asarray(u))),
                           rtol=1e-5, atol=1e-5)
s = np.array(plan2.forward(jnp.asarray(u)))
s[4, :, :] = 0  # unpaired -4 row of the even pruned axis (see TransformSpec.pruned)
rt = np.asarray(plan2.forward(plan2.backward(jnp.asarray(s))))
rel = np.linalg.norm(rt - s) / np.linalg.norm(s)
assert rel < 1e-5, rel
print("MIXED AUTO OK", json.dumps([list(s) for s in sched]))
""", ndev=4)


def test_all_real_plan_exchanges_f32(subproc):
    """An all-DCT plan never goes complex: the spectral output is float32
    and the modeled wire bytes price f32 (4-byte) payloads — half the
    complex plan's traffic."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT

mesh = make_mesh((2, 4), ("p0", "p1"))
plan = ParallelFFT(mesh, (16, 12, 20), ("p0", "p1"),
                   transforms=("dct2", "dct2", "dct2"))
x = np.random.default_rng(0).standard_normal((16, 12, 20)).astype(np.float32)
y = plan.forward(jnp.asarray(x))
assert y.dtype == jnp.float32, y.dtype
assert all(dt == jnp.float32 for dt in plan.dtype_trace)
c2c = ParallelFFT(mesh, (16, 12, 20), ("p0", "p1"))
# auto itemsize: real exchanges at 4 bytes vs complex at 8
assert plan.comm_bytes_per_device() * 2 == c2c.comm_bytes_per_device()
assert plan.model_time_s() < c2c.model_time_s()
print("ALL REAL F32 OK")
""", ndev=8)
