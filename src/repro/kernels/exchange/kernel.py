"""Pallas TPU kernels: fused exchange-local codec + chunk-layout passes.

The paper's thesis (Sec. 3) is that redistribution should never need a
separate local-realignment pass.  The jnp reference engines honor that for
the *lossless* exchange (the strided split/concat rides inside the one
``all_to_all``), but a lossy ``comm_dtype`` reintroduces local passes:
quantize → (pack) → collective → (unpack) → dequantize each materialize
the block in HBM.  These kernels collapse each side into streaming
HBM-read → VMEM-tile → HBM-write passes:

encode side (``encode_pallas_call``) — narrows each tile to the wire dtype
    and writes it directly in the outgoing wire layout.  With the
    chunk-major arrangement the write is the traditional engine's pack
    gather (paper Eq. 16): the pack transpose costs no extra pass, it is
    just the kernel's output index map.  int8 first runs
    ``scale_pallas_call``, a read-only reduction pass for the
    per-(field, chunk) max-abs.

decode side (``decode_pallas_call``) — the inverse: dequantize fused with
    the received-chunk scatter; for the traditional engine the unpack
    transpose (Eq. 17's realignment) is again only the input index map.

Canonical view: each ``*_pallas_call`` function takes the ``(P, F, A, M, Z)`` extents and
returns a function that reshapes its operands (stride-only, free) to

    block arrangement  (P, F, A, M, S, T)
    chunk arrangement  (M, P, F, A, S, T)   (traditional wire layout)

``P`` re/im planes (1 for real data), ``F`` collapsed leading batch/field
axes, ``A`` the collapsed axes before the exchange axis, ``M`` the
subgroup size, and ``S × T`` the contiguous per-chunk run (``T`` = 128
lanes when the run is a multiple of 128, else the whole run).  The grid
is ``(F, M, A-tiles, S-tiles)``; every grid step moves one
``(P, tA, tS, T)`` tile, sized by :func:`tiling` to fit the scoped VMEM.
One int8 scale per (field, chunk) — exactly the scale blocking of
:func:`repro.core.quant.quantize_int8`: the max-abs is accumulated over the
tiles in SMEM (max is exact in any order) and the scale, payload and
clip use the reference codec's ops, so the int8 math is bitwise identical
to the reference.  Both planes share one scale, as in the reference.
Guard counters ``(nonfinite, saturated)`` accumulate over the whole grid
into a ``(2,)`` SMEM output.

The kernels run on TPU natively and everywhere else via ``interpret=True``
(pure-jax emulation).  No complex dtype ever enters VMEM: callers pass
(re, im) planes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import _EPS

_WIRE_DTYPES = {"int8": jnp.int8, "bf16": jnp.bfloat16}

#: padded f32 bytes of one grid step's input tile
_TILE_BYTES = 512 * 1024


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def _tile_bytes(P: int, ta: int, ts: int, T: int) -> int:
    """f32 VMEM bytes of a ``(P, ta, ts, T)`` tile, padded to (8, 128)."""
    return P * ta * _ceil_to(ts, 8) * _ceil_to(T, 128) * 4


def tiling(P: int, A: int, Z: int) -> tuple[int, int, int, int]:
    """``(S, T, tA, tS)`` for a per-chunk run of ``Z`` contiguous elements
    repeated over ``A``: the run splits into ``S`` rows of ``T`` lanes; a
    grid step covers ``tA`` of the A rows and ``tS`` of the S rows, with
    ``tS`` a multiple of 32 (int8's sublane tile) or all of ``S``."""
    S, T = (Z // 128, 128) if Z % 128 == 0 else (1, Z)
    if _tile_bytes(P, 1, S, T) <= _TILE_BYTES:
        ta = max(d for d in _divisors(A) if _tile_bytes(P, d, S, T) <= _TILE_BYTES)
        return S, T, ta, S
    fits = [d for d in _divisors(S) if d % 32 == 0
            and _tile_bytes(P, 1, d, T) <= _TILE_BYTES]
    return S, T, 1, max(fits) if fits else S


def _vmem_limit(P: int, ta: int, ts: int, T: int) -> int:
    """Scoped-VMEM limit: double-buffered f32 in/out tiles plus room for
    the codec's tile-sized temporaries."""
    need = 10 * _tile_bytes(P, ta, ts, T)
    return min(max(32 << 20, 2 * need), 100 << 20)


class _Layout:
    """Grid and BlockSpecs of one ``(P, F, A, M, Z)`` canonical view."""

    def __init__(self, view):
        P, F, A, M, Z = view
        self.P, self.F, self.A, self.M = P, F, A, M
        self.S, self.T, self.ta, self.ts = tiling(P, A, Z)
        self.grid = (F, M, A // self.ta, self.S // self.ts)

    def shape(self, chunk_major: bool) -> tuple[int, ...]:
        P, F, A, M, S, T = self.P, self.F, self.A, self.M, self.S, self.T
        return (M, P, F, A, S, T) if chunk_major else (P, F, A, M, S, T)

    def spec(self, chunk_major: bool) -> pl.BlockSpec:
        P, ta, ts, T = self.P, self.ta, self.ts, self.T
        if chunk_major:
            return pl.BlockSpec((None, P, None, ta, ts, T),
                                lambda f, m, a, s: (m, 0, f, a, s, 0))
        return pl.BlockSpec((P, None, ta, None, ts, T),
                            lambda f, m, a, s: (0, f, a, m, s, 0))

    def scale_index(self, chunk_major: bool):
        """Flat index of the current (field, chunk) scale: ``(M, F)`` order
        alongside a chunk-major payload, ``(F, M)`` otherwise."""
        f, m = pl.program_id(0), pl.program_id(1)
        return m * self.F + f if chunk_major else f * self.M + m

    def params(self):
        # the scale/stat outputs accumulate across grid steps: sequential
        return pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4,
            vmem_limit_bytes=_vmem_limit(self.P, self.ta, self.ts, self.T))


def _smem(shape, dtype=jnp.float32):
    return (pl.BlockSpec(memory_space=pltpu.SMEM),
            jax.ShapeDtypeStruct(shape, dtype))


def _first_tile():
    return (pl.program_id(2) == 0) & (pl.program_id(3) == 0)


def _last_tile():
    return ((pl.program_id(2) == pl.num_programs(2) - 1)
            & (pl.program_id(3) == pl.num_programs(3) - 1))


def _tile_max(x):
    return jnp.max(jnp.max(x, axis=(0, 1)))


def _tile_sum(x):
    return jnp.sum(jnp.sum(x, axis=(0, 1)))


def scale_pallas_call(view, *, chunk_major: bool, scale_div, interpret: bool):
    """Build the int8 scale pass for a ``(P, F, A, M, Z)`` block-arranged
    input: the flat per-(field, chunk) f32 scales, ``max(max|x|, eps) /
    127`` over the finite elements (``/ scale_div`` under fault
    injection), in the order :meth:`_Layout.scale_index` gives."""
    lay = _Layout(view)
    s_spec, s_shape = _smem((lay.F * lay.M,))

    def body(x_ref, s_ref):
        i = lay.scale_index(chunk_major)

        @pl.when(_first_tile())
        def _():
            s_ref[i] = jnp.float32(0.0)

        x = x_ref[...]
        amax = _tile_max(jnp.abs(jnp.where(jnp.isfinite(x), x, 0.0)))
        s_ref[i] = jnp.maximum(s_ref[i], amax)

        @pl.when(_last_tile())
        def _():
            scale = jnp.maximum(s_ref[i], _EPS) / 127.0
            if scale_div is not None:
                scale = scale / scale_div
            s_ref[i] = scale

    call = pl.pallas_call(
        body, grid=lay.grid, in_specs=[lay.spec(False)], out_specs=s_spec,
        out_shape=s_shape, compiler_params=lay.params(), interpret=interpret,
        name="repro_exchange_scale")
    return lambda x: call(x.reshape(lay.shape(False)))


def encode_pallas_call(view, *, codec: str, chunk_major: bool, guard: bool,
                       interpret: bool):
    """Build the fused encode kernel for a ``(P, F, A, M, Z)`` view.

    Inputs: the block-arranged f32 planes, then for int8 the flat scales of
    :func:`scale_pallas_call`.  Outputs (in order): the narrow payload in
    the chunk-major (traditional wire) or block arrangement, then
    (``guard=True``) the ``(2,)`` ``(nonfinite, saturated)`` counts."""
    lay = _Layout(view)
    in_specs = [lay.spec(False)]
    if codec == "int8":
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    out_specs = [lay.spec(chunk_major)]
    out_shapes = [jax.ShapeDtypeStruct(lay.shape(chunk_major), _WIRE_DTYPES[codec])]
    if guard:
        st_spec, st_shape = _smem((2,))
        out_specs.append(st_spec)
        out_shapes.append(st_shape)

    def body(x_ref, *refs):
        refs = list(refs)
        s_ref = refs.pop(0) if codec == "int8" else None
        q_ref = refs.pop(0)
        x = x_ref[...]
        finite = jnp.isfinite(x)
        if codec == "bf16":
            q = x.astype(jnp.bfloat16)
        else:
            scale = s_ref[lay.scale_index(chunk_major)]
            xf = jnp.where(finite, x, 0.0)
            r = jnp.clip(jnp.round(xf / scale), -127, 127)
            q = r.astype(jnp.int8)
        q_ref[...] = q
        if guard:
            (st_ref,) = refs
            first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0) & _first_tile()

            @pl.when(first)
            def _():
                st_ref[0] = jnp.float32(0.0)
                st_ref[1] = jnp.float32(0.0)

            st_ref[0] += _tile_sum(jnp.where(finite, 0.0, 1.0))
            if codec == "int8":
                # counted on the f32 codes: an int8 compare mask needs a
                # relayout Mosaic refuses
                sat = (r == 127) | (r == -127)
                st_ref[1] += _tile_sum(jnp.where(sat, 1.0, 0.0))

    call = pl.pallas_call(
        body, grid=lay.grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shapes, compiler_params=lay.params(), interpret=interpret,
        name=f"repro_exchange_encode_{codec}")
    return lambda x, *scale: call(x.reshape(lay.shape(False)), *scale)


def decode_pallas_call(view, *, codec: str, chunk_major: bool, interpret: bool):
    """Build the fused decode kernel for a received ``(P, F, A, M, Z)``
    payload view (``M`` = sender-chunk axis): widen back to block-arranged
    f32, for int8 dequantizing chunk ``j`` with sender ``j``'s scale (a
    second, flat input in the payload's scale order).  A chunk-major input
    is the traditional engine's received payload: the scatter into the
    block arrangement is the unpack (Eq. 17)."""
    lay = _Layout(view)
    in_specs = [lay.spec(chunk_major)]
    if codec == "int8":
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    def body(q_ref, *rest):
        if codec == "int8":
            s_ref, o_ref = rest
            o_ref[...] = (q_ref[...].astype(jnp.float32)
                          * s_ref[lay.scale_index(chunk_major)])
        else:
            (o_ref,) = rest
            o_ref[...] = q_ref[...].astype(jnp.float32)

    call = pl.pallas_call(
        body, grid=lay.grid, in_specs=in_specs, out_specs=lay.spec(False),
        out_shape=jax.ShapeDtypeStruct(lay.shape(False), jnp.float32),
        compiler_params=lay.params(), interpret=interpret,
        name=f"repro_exchange_decode_{codec}")
    return lambda q, *scale: call(q.reshape(lay.shape(chunk_major)), *scale)
