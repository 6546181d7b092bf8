"""Which c2r form a plan's backward takes: dense (the real DFT from the
kept bins, up to ``fftcore._C2R_DFT_MAX_N`` points), and past that
packed (two real rows per complex inverse FFT, the halves of a spatial
axis) or full (one per row), decided by the block's shape alone
(``fftcore._c2r_dense``, ``fftcore._c2r_split``)."""

import jax
import pytest

from repro.core import fftcore
from repro.core.fftcore import TransformSpec
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT

GRID = ("p0", "p1")
#: an even r2c axis past the dense form's bound (plans here are only traced)
PAST = fftcore._C2R_DFT_MAX_N + 2


def _plan(m, transforms):
    mesh = make_mesh((1, 1), GRID, devices=jax.devices()[:1])
    shape = m if isinstance(m, tuple) else (m, m, m)
    return ParallelFFT(mesh, shape, GRID, transforms=transforms)


def _dealiased(n):
    """The DNS cell's plan, small: N modes on M = 3N/2 points."""
    return _plan(3 * n // 2, (TransformSpec.pruned(n), TransformSpec.pruned(n),
                              TransformSpec.r2c(n // 2 + 1)))


@pytest.fixture
def forms(monkeypatch):
    """Lowers a plan's direction on a stack of fields and returns the c2r
    forms it traced: ``("dense", block shape)``, ``("packed", block shape,
    split axis)`` or ``("full", block shape)``."""
    seen = []
    dense, packed, full = fftcore._c2r_dft, fftcore._c2r_packed, fftcore._c2r_full

    def spy_dense(x, axis, n, nbatch):
        seen.append(("dense", x.shape))
        return dense(x, axis, n, nbatch)

    def spy_packed(x, axis, n, impl, split):
        seen.append(("packed", x.shape, split))
        return packed(x, axis, n, impl, split)

    def spy_full(x, axis, n, impl):
        seen.append(("full", x.shape))
        return full(x, axis, n, impl)

    monkeypatch.setattr(fftcore, "_c2r_dft", spy_dense)
    monkeypatch.setattr(fftcore, "_c2r_packed", spy_packed)
    monkeypatch.setattr(fftcore, "_c2r_full", spy_full)

    def lower(plan, direction, nfields):
        pen, dt = ((plan.input_pencil, plan.input_dtype) if direction == "forward"
                   else (plan.output_pencil, plan.spectral_dtype))
        x = jax.ShapeDtypeStruct((nfields, *pen.physical), dt,
                                 sharding=pen.batched_sharding(1))
        seen.clear()
        jax.jit(getattr(plan, direction)).lower(x)
        return list(seen)

    return lower


@pytest.mark.parametrize("nfields", [3, 9])
def test_dealiased_plan_packs_every_c2r(forms, nfields):
    """The DNS-shaped ``pruned, pruned, r2c`` plan: the backward's one c2r
    stage is dense (N/2 + 1 kept bins to M points); with an r2c axis longer
    than the dense form's bound it is packed, split on a spatial axis of M
    points (never the field axis).  The forward has none."""
    n, m = 16, 24
    plan = _dealiased(n)
    assert forms(plan, "backward", nfields) == [("dense", (nfields, m, m, n // 2 + 1))]
    assert forms(plan, "forward", nfields) == []
    plan = _plan((m, m, PAST), (TransformSpec.pruned(n), TransformSpec.pruned(n),
                                TransformSpec.r2c(PAST // 3 + 1)))
    (rec,) = forms(plan, "backward", nfields)
    assert rec[0] == "packed"
    shape, split = rec[1], rec[2]
    assert shape[0] == nfields and split >= 1 and shape[split] == m
    assert forms(plan, "forward", nfields) == []


def _all_odd_past_the_bound():
    return _plan((15, 15, PAST + 1), (TransformSpec.pruned(10), TransformSpec.pruned(10),
                                      TransformSpec.r2c(PAST // 3 + 1)))


def test_all_odd_block_takes_full(forms):
    """Odd extents everywhere, past the dense form's bound: no axis to
    split, so the c2r takes one inverse FFT per row."""
    assert [rec[0] for rec in forms(_all_odd_past_the_bound(), "backward", 3)] == ["full"]


def test_even_field_axis_alone_takes_full(forms):
    """Two fields on an all-odd grid, past the dense form's bound: the even
    field axis is not split, so no field's rows share an inverse FFT with
    another field's."""
    assert [rec[0] for rec in forms(_all_odd_past_the_bound(), "backward", 2)] == ["full"]


def test_c2c_plan_takes_no_c2r(forms):
    plan = _plan(8, (TransformSpec.c2c(),) * 3)
    for direction in ("forward", "backward"):
        assert forms(plan, direction, 2) == []
