"""The nominal FFT work count and the peaks table."""

import math

import pytest
from benchtest_util import tiny_root

from bench import harness, peaks, workcount

C2C = [{"kind": "c2c", "keep": None}] * 3


def test_c2c_512_by_hand():
    n = 512
    # three stages, each reads and writes n^3 complex64
    want_bytes = 3 * 2 * n**3 * 8
    want_flops = 3 * 5 * n * math.log2(n) * n**2
    w = workcount.field_transform((n, n, n), C2C)
    assert w.bytes == want_bytes == 6_442_450_944
    assert w.flops == pytest.approx(want_flops)
    # a round trip, split over four chips, is bound by HBM on a v5e
    p = peaks.peaks_for("TPU v5 lite")
    least, bound = (2 * w * 0.25).least_seconds(p.flops_per_s, p.hbm_bytes_per_s)
    assert bound == "hbm"
    assert least == pytest.approx(2 * want_bytes / 4 / 819e9)


def test_dealiased_256_by_hand():
    n, m, kz = 256, 384, 129
    transforms = [{"kind": "c2c", "keep": n}, {"kind": "c2c", "keep": n}, {"kind": "r2c", "keep": kz}]
    stages = workcount.stage_works((m, m, m), transforms)
    f = 5 * m * math.log2(m)
    # z: r2c of real float32 M^3 -> M x M x 129 complex64, half the flops
    assert stages[0].bytes == m**3 * 4 + m * m * kz * 8
    assert stages[0].flops == pytest.approx(0.5 * f * m * m)
    # y: pruned c2c M x M x 129 -> M x N x 129
    assert stages[1].bytes == m * m * kz * 8 + m * n * kz * 8
    assert stages[1].flops == pytest.approx(f * m * kz)
    # x: pruned c2c M x N x 129 -> N x N x 129
    assert stages[2].bytes == m * n * kz * 8 + n * n * kz * 8
    assert stages[2].flops == pytest.approx(f * n * kz)
    w = workcount.field_transform((m, m, m), transforms)
    assert w.bytes == 801_374_208
    # 30 field-transforms a step: 24.0 GB, 29.4 ms at 819 GB/s
    step = 30 * w
    assert step.bytes / 819e9 == pytest.approx(0.02935, rel=1e-3)


def test_bad_transforms_refused():
    with pytest.raises(ValueError):
        workcount.field_transform((8, 8), [{"kind": "r2c", "keep": None}, {"kind": "c2c", "keep": None}])
    with pytest.raises(ValueError):
        workcount.field_transform((8,), [{"kind": "dct", "keep": None}])


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v99")
    assert peaks.peaks_for("TPU v5 lite").hbm_bytes_per_s == 819e9


@pytest.mark.parametrize("workload", ["tgv_dns.rk2.1chip", "c2c512.pencil2x2"])
def test_count_is_the_same_for_either_fft_impl(tmp_path, workload):
    """The cell's work, as its step kind computes it from the
    configuration, does not change with the plan's FFT implementation."""
    import jax

    spec = harness.find(tiny_root(tmp_path), workload)
    spec.traffic["mesh"] = {"shape": [1] * len(spec.traffic["grid"]), "axes": spec.traffic["mesh"]["axes"]}
    works = []
    for impl in ("jnp", "matmul"):
        cell, _ = harness.build_cell(spec, jax.devices()[:1], {"impl": impl})
        works.append(cell.work)
    assert works[0] == works[1]
    cfg = spec.config
    shape = cfg.get("shape") or [cfg["points"]] * 3
    assert works[0].bytes > 0 and works[0].bytes % workcount.field_transform(shape, cfg["transforms"]).bytes == 0
