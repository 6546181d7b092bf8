"""Fused exchange-kernel parity suite (repro.kernels.exchange).

Every kernel path — encode/decode (fused & pipelined wire form) and
pack/unpack (traditional chunk-major form, both scatter orders) — against
the jnp reference codec, across codecs x complex/real x odd extents x
batch counts, in interpret mode on CPU.  Engine-level and full-plan
``impl="pallas"``-vs-``"jnp"`` parity runs on multi-device subprocesses
through real collectives.

Parity contract: bf16 is **bitwise** against the jnp codec (same
round-to-nearest convert on both paths).  int8 payloads may differ by
±1 quantum at exact round boundaries and scales by 1 ULP between
compilation contexts, so int8 comparisons bound the error by one
quantization step instead of demanding bit equality.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels import exchange as xk
from repro.kernels.transpose.ops import transpose01


def _rand(shape, iscomplex, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape).astype(np.float32)
    if iscomplex:
        x = (x + 1j * rng.standard_normal(shape)).astype(np.complex64)
    return x


def _ref_codec_roundtrip(y, *, axis, m, nbatch, codec):
    """The jnp reference codec loss for an identity exchange: encode then
    decode with the same per-(field, chunk) blocking
    ``redistribute._all_to_all_comm`` uses (``axis`` split into ``m``
    chunks; one int8 scale per field x chunk block)."""
    iscomplex = np.iscomplexobj(y)
    planes = (quant.complex_to_planes(jnp.asarray(y)) if iscomplex
              else jnp.asarray(y)[None].astype(jnp.float32))
    if codec == "bf16":
        p = quant.decode_bf16(quant.encode_bf16(planes))
    else:
        sa = axis + 1  # planes coords
        view = list(planes.shape)
        view[sa:sa + 1] = [m, planes.shape[sa] // m]
        block = (sa,) + tuple(range(1, nbatch + 1))
        q, scale = quant.quantize_int8(planes.reshape(view), block_axis=block)
        p = quant.dequantize_int8(q, scale).reshape(planes.shape)
    return np.asarray(quant.planes_to_complex(p) if iscomplex else p[0])


def _quantum(y):
    """Upper bound on one int8 quantization step anywhere in ``y``."""
    return float(np.max(np.abs(np.stack([y.real, np.imag(y)])))) / 127.0


@pytest.mark.parametrize("codec", ["bf16", "int8"])
@pytest.mark.parametrize("iscomplex", [True, False])
@pytest.mark.parametrize("shape,axis,m,nbatch", [
    ((6, 8, 10), 1, 4, 0),     # mid split axis, odd neighbours
    ((8, 6, 10), 0, 2, 0),     # leading split axis
    ((3, 6, 8, 10), 2, 4, 1),  # stacked fields: per-field scale blocks
])
def test_encode_decode_matches_jnp_codec(codec, iscomplex, shape, axis, m, nbatch):
    """decode(encode(y)) — the fused/pipelined wire form under an identity
    exchange — must equal the jnp codec roundtrip: bitwise for bf16,
    within one quantum for int8."""
    y = _rand(shape, iscomplex, seed=axis + m)
    q, scale, stats = xk.encode_payload(jnp.asarray(y), axis=axis, m=m,
                                        nbatch=nbatch, codec=codec)
    assert stats is None  # guard off: no counters traced
    if codec == "int8":
        assert scale is not None and scale.dtype == jnp.float32
    out = np.asarray(xk.decode_payload(q, axis=axis, m=m, nbatch=nbatch,
                                       scale=scale, codec=codec,
                                       iscomplex=iscomplex))
    ref = _ref_codec_roundtrip(y, axis=axis, m=m, nbatch=nbatch, codec=codec)
    if codec == "bf16":
        np.testing.assert_array_equal(out, ref)
    else:
        np.testing.assert_allclose(out, ref, atol=1.25 * _quantum(y), rtol=0)


def test_pack_chunks_bf16_layout_bitwise():
    """pack_chunks' chunk-major payload must be exactly the jnp pack
    (reshape + moveaxis) of the bf16-encoded planes — the kernel's output
    index map IS Eq. 16, not an approximation of it."""
    y = _rand((8, 6, 10), True)
    axis, m = 0, 4
    payload, scale, _ = xk.pack_chunks(jnp.asarray(y), axis=axis, m=m,
                                       codec="bf16")
    assert scale is None
    planes = quant.encode_bf16(quant.complex_to_planes(jnp.asarray(y)))
    view = list(planes.shape)
    view[axis + 1:axis + 2] = [m, planes.shape[axis + 1] // m]
    ref = jnp.moveaxis(planes.reshape(view), axis + 1, 0)
    np.testing.assert_array_equal(np.asarray(payload), np.asarray(ref))


@pytest.mark.parametrize("codec", ["bf16", "int8"])
@pytest.mark.parametrize("iscomplex", [True, False])
@pytest.mark.parametrize("shape,v,w,m,nbatch", [
    ((8, 6, 10), 0, 2, 4, 0),     # scatter axis after the chunk source
    ((6, 10, 8), 2, 0, 2, 0),     # w < v: the other scatter order
    ((3, 8, 6, 10), 0, 1, 4, 1),  # stacked fields
])
def test_unpack_inverts_pack_both_orders(codec, iscomplex, shape, v, w, m, nbatch):
    """unpack(pack(y)) under an identity exchange must equal the jnp
    traditional path (reshape/moveaxis pack, codec roundtrip, moveaxis/
    merge unpack) for both w<v and w>v scatter orders."""
    y = _rand(shape, iscomplex, seed=v * 10 + w)
    bv, bw = v + nbatch, w + nbatch
    payload, scale, _ = xk.pack_chunks(jnp.asarray(y), axis=bv, m=m,
                                       nbatch=nbatch, codec=codec)
    out = np.asarray(xk.unpack_chunks(payload, w=w, m=m, nbatch=nbatch,
                                      scale=scale, codec=codec,
                                      iscomplex=iscomplex))
    # reference: same codec loss, then the jnp pack/unpack layout ops
    yc = _ref_codec_roundtrip(y, axis=bv, m=m, nbatch=nbatch, codec=codec)
    view = list(yc.shape)
    view[bv:bv + 1] = [m, yc.shape[bv] // m]
    z = np.moveaxis(np.moveaxis(yc.reshape(view), bv, 0), 0, bw)
    ref = z.reshape(z.shape[:bw] + (m * z.shape[bw + 1],) + z.shape[bw + 2:])
    assert out.shape == ref.shape
    if codec == "bf16":
        np.testing.assert_array_equal(out, ref)
    else:
        np.testing.assert_allclose(out, ref, atol=1.25 * _quantum(y), rtol=0)


@pytest.mark.parametrize("codec", ["bf16", "int8"])
def test_guard_stats_ride_the_fused_codec(codec):
    """guard=True must return the health counters from inside the kernel:
    injected non-finites are counted exactly; int8 counts its saturated
    (clipped-to-127) elements."""
    y = _rand((8, 6, 10), True).copy()
    y[0, 0, :3] = np.nan  # 3 non-finite real-plane elements
    _, _, stats = xk.encode_payload(jnp.asarray(y), axis=0, m=4, codec=codec,
                                    guard=True)
    assert int(stats["nonfinite"]) == 3
    if codec == "int8":
        # each (field, chunk) block's max-abs element lands exactly on 127
        assert int(stats["saturated"]) >= 1
    _, _, pstats = xk.pack_chunks(jnp.asarray(y), axis=0, m=4, codec=codec,
                                  guard=True)
    assert int(pstats["nonfinite"]) == 3


@pytest.mark.parametrize("pack", [False, True])
@pytest.mark.parametrize("shape,axis,m,nbatch,tile_bytes", [
    ((3, 2, 16384), 1, 2, 0, 40000),     # A tiles x S tiles per chunk
    ((2, 8, 4, 1024), 2, 4, 1, 16384),   # stacked fields, A tiles
])
def test_int8_tiled_grid_bitwise(monkeypatch, pack, shape, axis, m, nbatch,
                                 tile_bytes):
    """With the VMEM tile budget shrunk so each (field, chunk) spans
    several grid steps, the max-abs accumulated across tiles must still
    give the reference codec's scales and payload bit for bit, and the
    guard counters must equal the reference counts."""
    from repro.kernels.exchange import kernel as xkernel

    monkeypatch.setattr(xkernel, "_TILE_BYTES", tile_bytes)
    y = _rand(shape, True, seed=len(shape)).copy()
    y.flat[5] = np.inf
    y.flat[77] = np.nan
    planes = quant.complex_to_planes(jnp.asarray(y))
    P = planes.shape[0]
    F = int(np.prod(shape[:nbatch]))
    A = int(np.prod(shape[nbatch:axis]))
    view = (P, F, A, m, planes.size // (P * F * A * m))
    assert np.prod(xkernel._Layout(view).grid[2:]) > 1
    q_ref, s_ref, st_ref = quant.quantize_int8(planes.reshape(view),
                                               block_axis=(1, 3), with_stats=True)
    fn = xk.pack_chunks if pack else xk.encode_payload
    q, scale, stats = fn(jnp.asarray(y), axis=axis, m=m, nbatch=nbatch,
                         codec="int8", guard=True)
    s_ref = np.asarray(s_ref).reshape(F, m)
    q_ref = np.asarray(q_ref)
    if pack:
        s_ref = s_ref.T
        q_ref = np.moveaxis(q_ref, 3, 0)
    np.testing.assert_array_equal(np.asarray(scale), s_ref)
    np.testing.assert_array_equal(np.asarray(q).reshape(q_ref.shape), q_ref)
    assert float(stats["nonfinite"]) == float(st_ref["nonfinite"]) == 2
    assert float(stats["saturated"]) == float(st_ref["saturated"])


def test_pallas_applicable_gate():
    """The one shared gate: lossy payloads only — lossless stages always
    run the jnp reference path regardless of the requested impl."""
    for method in ("fused", "traditional", "pipelined"):
        assert xk.pallas_applicable(method, "bf16")
        assert xk.pallas_applicable(method, "int8")
        assert not xk.pallas_applicable(method, None)
        assert not xk.pallas_applicable(method, "complex64")


@pytest.mark.parametrize("shape", [(9, 17, 5), (1, 31, 2), (8, 8, 3), (13, 7, 1)])
def test_transpose01_pad_and_slice_non_tile_multiples(shape):
    """The tiled local-transpose kernel at non-tile-multiple extents: the
    pad-to-tile/run/slice-back path must be exact (the padding must never
    leak into the result)."""
    x = _rand(shape, False, seed=sum(shape))
    np.testing.assert_array_equal(np.asarray(transpose01(jnp.asarray(x))),
                                  x.swapaxes(0, 1))
    xc = _rand(shape, True, seed=sum(shape))
    np.testing.assert_array_equal(np.asarray(transpose01(jnp.asarray(xc))),
                                  xc.swapaxes(0, 1))


def test_engine_impl_parity_through_collectives(subproc):
    """exchange(impl="pallas") vs impl="jnp" through real all-to-alls on a
    (2, 2) mesh, every engine x payload: lossless and bf16 bitwise, int8
    within one quantization step."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.pencil import make_pencil, pad_global
from repro.core.redistribute import exchange

mesh = make_mesh((2, 2), ("p0", "p1"))
rng = np.random.default_rng(0)
shape = (16, 12, 10)   # odd trailing extents: padded pencil
cases = [
    ((None, "p1", None), (2, 2, 1), 0, 1),          # slab
    (("p0", "p1", None), (2, 2, 2), 2, 1),          # pencil, v trailing
]
for placement, divisors, v, w in cases:
    src = make_pencil(mesh, shape, placement, divisors=divisors)
    x = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)
    xs = jax.device_put(pad_global(jnp.asarray(x), src), src.sharding)
    quantum = float(np.abs(np.stack([x.real, x.imag])).max()) / 127.0
    for method in ("fused", "traditional", "pipelined"):
        for cd in ("complex64", "bf16", "int8"):
            gj, dj = exchange(xs, src, v=v, w=w, method=method, chunks=2,
                              comm_dtype=cd, impl="jnp")
            gp, dp = exchange(xs, src, v=v, w=w, method=method, chunks=2,
                              comm_dtype=cd, impl="pallas")
            assert dp.placement == dj.placement
            gj, gp = np.asarray(gj), np.asarray(gp)
            if cd == "int8":
                np.testing.assert_allclose(gp, gj, atol=2.1 * quantum, rtol=0)
            else:
                # lossless: pallas is a documented no-op; bf16: same
                # round-to-nearest convert on both paths
                assert np.array_equal(gp, gj), (placement, method, cd)
print("ENGINE IMPL PARITY OK")
""", ndev=4)


def test_plan_impl_parity_and_guard(subproc):
    """Full ParallelFFT parity: an exchange_impl="pallas" plan against the
    jnp reference plan, per engine x payload, including an r2c plan with
    odd extents, the batched multi-field path, and a guarded pallas plan
    whose health stats flow out of the fused kernels."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig

mesh = make_mesh((2, 2), ("p0", "p1"))
rng = np.random.default_rng(0)

def plans(shape, transforms, **kw):
    base = {"method": "fused", **kw}
    pj = ParallelFFT(mesh, shape, ("p0", "p1"), transforms=transforms,
                     config=PlanConfig(**base))
    pp = ParallelFFT(mesh, shape, ("p0", "p1"), transforms=transforms,
                     config=PlanConfig(exchange_impl="pallas", **base))
    return pj, pp

shape = (16, 12, 20)
x = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)
for method in ("fused", "traditional", "pipelined"):
    for cd in ("bf16", "int8"):
        pj, pp = plans(shape, None, method=method, chunks=2, comm_dtype=cd)
        yj = np.asarray(pj.forward(jnp.asarray(x)))
        yp = np.asarray(pp.forward(jnp.asarray(x)))
        if cd == "bf16":
            assert np.array_equal(yp, yj), (method, cd)
        else:
            # +-1 quantum per exchange, amplified by the later FFT stages:
            # bound the relative spectrum error between the impls instead
            rel = np.linalg.norm(yp - yj) / np.linalg.norm(yj)
            assert rel < 5e-3, (method, cd, rel)
        back = np.asarray(pp.backward(pp.forward(jnp.asarray(x))))
        rel = np.linalg.norm(back - x) / np.linalg.norm(x)
        assert rel < (1e-2 if cd == "bf16" else 5e-2), (method, cd, rel)

# r2c with an odd trailing extent (pad-and-slice inside the plan)
rshape = (16, 12, 9)
xr = rng.standard_normal(rshape).astype(np.float32)
pj, pp = plans(rshape, ("c2c", "c2c", "r2c"), comm_dtype="bf16")
assert np.array_equal(np.asarray(pp.forward(jnp.asarray(xr))),
                      np.asarray(pj.forward(jnp.asarray(xr))))

# batched multi-field path: one exchange ships all fields
xb = (rng.standard_normal((3, *shape))
      + 1j * rng.standard_normal((3, *shape))).astype(np.complex64)
pj, pp = plans(shape, None, comm_dtype="bf16")
assert np.array_equal(np.asarray(pp.forward_many(jnp.asarray(xb))),
                      np.asarray(pj.forward_many(jnp.asarray(xb))))

# guarded pallas plan: stats ride the fused codec out of the kernels
gp = ParallelFFT(mesh, shape, ("p0", "p1"),
                 config=PlanConfig(method="fused", comm_dtype="int8",
                                   exchange_impl="pallas", guard="strict"))
y, rep = gp.forward(jnp.asarray(x))
assert rep.ok and rep.attempts == 1
assert len(rep.stages) == gp.n_exchanges
print("PLAN IMPL PARITY OK")
""", ndev=4, timeout=1200)
