"""Engine-facing wrappers for the fused exchange kernels.

Four entry points, mirroring the wire pattern of
:func:`repro.core.redistribute._all_to_all_comm`:

fused / pipelined engines (payload keeps the block layout):
    :func:`encode_payload`  — codec in one pass, payload stays in place.
    :func:`decode_payload`  — inverse, dequantizing each received chunk
                              with its sender's scale.

traditional engine (payload is chunk-major, paper Eqs. 15-17):
    :func:`pack_chunks`     — codec *and* the pack transpose in one pass
                              (the chunk-major gather is the kernel's
                              output index map, not a separate moveaxis).
    :func:`unpack_chunks`   — inverse scatter fused with dequantize: the
                              unpack realignment costs no extra HBM pass.

Every wrapper collapses its operand to the kernels' canonical
``(P, F, A, M, Z)`` view — stride-only, free — and reshapes the result
back.  Complex blocks travel as a leading (re, im) plane pair built by the
module-local :func:`_to_planes` / :func:`_from_planes` (same math as
:mod:`repro.core.quant`'s helpers, duplicated *here* so planlint's source
attribution sees the marshalling on the kernel side of the line: a plan
whose lossy stages all run ``impl="pallas"`` traces zero eqns attributed
to ``core/quant.py`` — the PLAN009 invariant).

``pallas_applicable`` is the one shared gate: the pallas impl exists for
*lossy* payloads (there the codec gives the kernels work to fuse with);
a lossless exchange has no local pass to eliminate — the engines'
complex64 path is already realignment-free for ``fused``/``pipelined``,
and kernelizing traditional's lossless pack would add plane-marshalling
passes for nothing — so lossless stages always execute the jnp reference
path regardless of the requested impl.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.quant import canonical_comm_dtype
from repro.kernels.exchange import kernel as _k


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def pallas_applicable(method: str, comm_dtype) -> bool:  # noqa: ARG001 — method kept for future per-engine gating
    """Whether ``impl="pallas"`` changes anything for this stage config.
    False means the stage canonically executes the jnp reference path."""
    return canonical_comm_dtype(comm_dtype) != "complex64"


def _prod(xs) -> int:
    return int(math.prod(xs))


def _to_planes(y: jax.Array) -> jax.Array:
    """Block -> leading (re, im) f32 plane pair ``(2, *shape)`` (``(1, ...)``
    for real input).  Module-local twin of quant.complex_to_planes — see
    module docstring for why the eqns must attribute here."""
    if jnp.iscomplexobj(y):
        return jnp.stack([jnp.real(y), jnp.imag(y)]).astype(jnp.float32)
    return y.astype(jnp.float32)[None]


def _from_planes(p: jax.Array, iscomplex: bool) -> jax.Array:
    if iscomplex:
        return lax.complex(p[0], p[1])
    return p[0]


def _stats_dict(st: jax.Array | None) -> dict | None:
    """The kernel's whole-grid ``(nonfinite, saturated)`` counters -> the
    executor's stats dict (health.payload_stats' shape)."""
    if st is None:
        return None
    return {"nonfinite": st[0], "saturated": st[1]}


def _payload_view(shape: tuple[int, ...], axis: int, m: int,
                  nbatch: int) -> tuple[int, ...]:
    """Collapse a planes shape ``(P, *s)`` around split/concat axis ``axis``
    (block coords) into the canonical ``(P, F, A, M, Z)``: ``Z`` is one
    chunk's contiguous run (its slice of ``axis`` times the trailing axes)."""
    P, s = shape[0], shape[1:]
    n = s[axis]
    if n % m != 0:
        raise ValueError(f"axis extent {n} not divisible by group size {m}")
    return (P, _prod(s[:nbatch]), _prod(s[nbatch:axis]), m,
            n // m * _prod(s[axis + 1:]))


def _encode(planes, view, *, codec, chunk_major, guard, scale_div, interpret):
    """Run the encode kernels: ``(payload, flat scales | None, stats)``."""
    scale = ()
    if codec == "int8":
        scale = (_k.scale_pallas_call(view, chunk_major=chunk_major,
                                      scale_div=scale_div,
                                      interpret=interpret)(planes),)
    call = _k.encode_pallas_call(view, codec=codec, chunk_major=chunk_major,
                                 guard=guard, interpret=interpret)
    outs = call(planes, *scale)
    return outs[0], (scale[0] if scale else None), _stats_dict(outs[1] if guard else None)


# ---------------------------------------------------------------------------
# fused / pipelined engines: payload in block layout
# ---------------------------------------------------------------------------


def encode_payload(y: jax.Array, *, axis: int, m: int, nbatch: int = 0,
                   codec: str, guard: bool = False, scale_div=None,
                   interpret: bool | None = None):
    """One-pass encode of a block for the fused/pipelined wire: returns
    ``(payload, scale, stats)`` — the narrow (bf16/int8) payload as
    ``(P, *y.shape)`` planes ready for an all-to-all with the split/concat
    axes shifted by one, the ``(F, M)`` per-(field, chunk) f32 scales
    (int8; None otherwise), and the guard stats dict (None unless
    ``guard``).  ``axis`` is the split axis in block coords; the leading
    ``nbatch`` axes are stacked fields."""
    if interpret is None:
        interpret = _interpret_default()
    planes = _to_planes(y)
    P, F, A, M, Z = view = _payload_view(planes.shape, axis, m, nbatch)
    q, scale, stats = _encode(planes, view, codec=codec, chunk_major=False,
                              guard=guard, scale_div=scale_div,
                              interpret=interpret)
    if scale is not None:
        scale = scale.reshape(F, M)
    return q.reshape(planes.shape), scale, stats


def decode_payload(p: jax.Array, *, axis: int, m: int, nbatch: int = 0,
                   scale: jax.Array | None, codec: str, iscomplex: bool,
                   interpret: bool | None = None) -> jax.Array:
    """Inverse of :func:`encode_payload` for the *received* payload ``p``
    (``(P, *out_shape)`` planes whose ``axis`` now carries ``m``
    sender-chunks): dequantize/widen in one pass — chunk ``j`` with sender
    ``j``'s scale from the ``(F, M)`` scale exchange — and rebuild the
    complex block."""
    if interpret is None:
        interpret = _interpret_default()
    view = _payload_view(p.shape, axis, m, nbatch)
    call = _k.decode_pallas_call(view, codec=codec, chunk_major=False,
                                 interpret=interpret)
    out = call(p, *(() if scale is None else (scale.reshape(-1),)))
    return _from_planes(out.reshape(p.shape), iscomplex)


# ---------------------------------------------------------------------------
# traditional engine: chunk-major payload (paper Eqs. 15-17)
# ---------------------------------------------------------------------------


def pack_chunks(y: jax.Array, *, axis: int, m: int, nbatch: int = 0,
                codec: str, guard: bool = False, scale_div=None,
                interpret: bool | None = None):
    """One-pass pack+encode for the traditional engine: the codec write
    lands directly in chunk-major layout ``(m, P, *s)`` (``s`` = block
    shape with ``axis`` shrunk to its per-chunk extent), ready for a
    contiguous all-to-all on axis 0.  Returns ``(payload, scale, stats)``
    with ``(M, F)`` scales (int8) whose leading axis matches the
    payload's, so both collectives split the same way."""
    if interpret is None:
        interpret = _interpret_default()
    planes = _to_planes(y)
    P, F, A, M, Z = view = _payload_view(planes.shape, axis, m, nbatch)
    q, scale, stats = _encode(planes, view, codec=codec, chunk_major=True,
                              guard=guard, scale_div=scale_div,
                              interpret=interpret)
    if scale is not None:
        scale = scale.reshape(M, F)
    s = list(planes.shape[1:])
    s[axis] //= M
    return q.reshape((M, P, *s)), scale, stats


def unpack_chunks(p: jax.Array, *, w: int, m: int, nbatch: int = 0,
                  scale: jax.Array | None, codec: str, iscomplex: bool,
                  interpret: bool | None = None) -> jax.Array:
    """Inverse of :func:`pack_chunks` for the received chunk-major payload:
    scatter chunk ``j`` into w-slot ``j`` (chunk-major == global w order,
    the Eq. 17 realignment) fused with dequantize/widen, and rebuild the
    block — w axis full, the v axis holding this rank's shard.  ``w`` is
    a block coord of the inner shape ``p.shape[2:]``."""
    if interpret is None:
        interpret = _interpret_default()
    M, P = p.shape[0], p.shape[1]
    s = p.shape[2:]
    bw = w + nbatch
    F = _prod(s[:nbatch])
    # chunk j lands just before the w axis (chunk-major == global w order):
    # (M, P, F, A, Z) -> (P, F, A, M, Z) with A everything before w
    view = (P, F, _prod(s[nbatch:bw]), M, _prod(s[bw:]))
    call = _k.decode_pallas_call(view, codec=codec, chunk_major=True,
                                 interpret=interpret)
    out = call(p, *(() if scale is None else (scale.reshape(-1),)))
    final = list(s)
    final[bw] = M * s[bw]
    return _from_planes(out.reshape((P, *final)), iscomplex)
