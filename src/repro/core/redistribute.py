"""Global redistribution (the paper's contribution — Sec. 3.3.2, Alg. 2/3).

Three implementations of the v→w exchange of a distributed array:

``method="fused"`` — the paper's method.  One ``lax.all_to_all`` with
    ``split_axis=v, concat_axis=w``: the strided split/concat description
    plays the role of MPI subarray datatypes, and the single collective is
    the analogue of ``MPI_ALLTOALLW``.  No local transpose materializes in
    user code; XLA:TPU's collective engine performs the strided
    gather/scatter as part of the exchange.

``method="traditional"`` — what P3DFFT/2DECOMP&FFT/FFTW-MPI do (paper
    Sec. 3.3.1, Eqs. 15–17): pack chunks contiguously with an explicit local
    transpose (a materialized copy), run a contiguous all-to-all on the
    leading chunk axis, then unpack with a second local transpose.  With
    ``transposed_out=True`` the unpack copy is skipped and the output keeps
    the permuted chunk-major layout (FFTW's "transposed out", Eq. 19) —
    callers must handle the layout.

``method="pipelined"`` — the fused exchange sliced into ``chunks`` pieces
    along the *post-exchange v shard* so each slice is an independent
    all-to-all whose output is one contiguous sub-range of the fused
    output.  The union of the slices is bit-identical to ``fused``; the
    point is scheduling freedom: a caller (``pfft._run_stages``) can
    interleave each slice's collective with the next stage's 1-D FFT on the
    previous slice, letting XLA overlap collective DMA with MXU/VPU compute
    instead of serializing exchange→transform.  This is the TPU analogue of
    the paper's note that the single-collective formulation "enables future
    speedups from optimizations in the internal datatype handling engines"
    (cf. partitioned/persistent-collective MPI FFTs, arXiv:2306.16589).

``method="auto"`` (plan level only, see :mod:`repro.core.tuner`) —
    micro-benchmarks {fused, traditional, pipelined×chunks} × the allowed
    ``comm_dtype`` payloads per exchange stage of a plan and caches the
    winning schedule on disk.

Both operate *per shard* (inside ``shard_map``) via ``exchange_shard`` and
at the jit level on globally-sharded arrays via ``exchange``.

Batched multi-field exchange (``nbatch``)
-----------------------------------------

Real spectral workloads (Navier–Stokes: u, v, w plus nonlinear products)
push *many* fields through the same plan, and issuing one small all-to-all
per field per stage leaves the interconnect latency-bound.  Every engine
therefore accepts ``nbatch``: the leading ``nbatch`` axes of ``block`` are
field/batch axes and ``v``/``w`` are *field-relative* array axes (the
engine offsets them internally).  The whole stacked payload of all fields
ships in **one** collective per exchange — message aggregation in the
spirit of P3DFFT's many-variable API (arXiv:1905.02803) and the
collective-optimized FFTs of arXiv:2306.16589 — and a lossy ``comm_dtype``
codec runs once over the stacked block (one HBM quantize/dequantize pass
total instead of one per field; int8 keeps one scale per (field,
destination chunk) so fields of different magnitude never share a
max-abs).  ``exchange_shard(stacked, v, w, group, nbatch=1)`` is the
batched entry point :class:`repro.core.pfft.ParallelFFT` uses for its
``batch_fusion="stacked"`` execution mode.

Communication compression (``comm_dtype``)
------------------------------------------

Every engine accepts a ``comm_dtype`` payload policy (codecs in
:mod:`repro.core.quant`); the wire pattern is encode → all-to-all the
narrow payload (+ one tiny f32 scale all-to-all for int8) → decode:

``"complex64"`` (default / ``None``) — lossless passthrough.  Bit-identical
    to the uncompressed exchange for all three engines: the collective sees
    the original complex64 buffer.
``"bf16"`` — the complex block travels as stacked (re, im) bf16 planes:
    2× fewer wire bytes.  bf16 keeps f32's exponent so no scale is shipped;
    accuracy contract: each exchanged value is rounded to 8 mantissa bits
    (~3 decimal digits), and a full FFT round trip stays within ~1e-3
    relative L2 of the exact result.
``"int8"`` — per-destination-chunk max-abs int8 planes: 4× fewer wire
    bytes plus one f32 scale per destination rank (a second, scale-sized
    all-to-all).  Accuracy contract: per-element error ≤ chunk-max/254 per
    exchange; a full round trip stays within ~1e-2 relative L2.  Expected
    to win only when the exchange is firmly ICI-bound — the codec pays two
    extra HBM passes over the block (quantize + dequantize), so on small /
    compute-bound shapes complex64 or bf16 wins; the tuner prices exactly
    this trade when ``method="auto"`` is given an accuracy budget.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import quant, spans
from repro.core.decomp import local_lengths
from repro.core.meshutil import axis_size as _mesh_axis_size, shard_map
from repro.core.pencil import Group, Pencil, group_names, group_size
from repro.core.planconfig import BATCH_FUSIONS, EXCHANGE_IMPLS  # noqa: F401 — re-exported
from repro.core.quant import canonical_comm_dtype, wire_ratio
from repro.kernels.exchange import ops as _xk
from repro.robustness import faults as _faults, health as _health

Method = str  # "fused" | "traditional" | "pipelined"
CommDtype = str  # "complex64" | "bf16" | "int8" (None accepted as complex64)
Impl = str  # "jnp" | "pallas" (exchange-local implementation, see planconfig)

#: chunk counts the tuner sweeps for the pipelined method
PIPELINE_CHUNK_CANDIDATES = (2, 4, 8)


def _a2a(x: jax.Array, axis_name, *, split_axis: int, concat_axis: int,
         wire: str = "payload") -> jax.Array:
    """Every engine's all-to-all: ``lax.all_to_all(..., tiled=True)``,
    counted in the traced executor's exchange record
    (:func:`repro.core.spans.count_all_to_all`; ``wire="scale"`` for int8's
    scale exchange), then the received buffer's fault tap."""
    spans.count_all_to_all(x, _axis_size(axis_name), scale=wire == "scale")
    out = lax.all_to_all(x, axis_name, split_axis=split_axis,
                         concat_axis=concat_axis, tiled=True)
    return _faults.tap_wire(out, wire)


def _all_to_all_comm(
    y: jax.Array,
    axis_name,
    *,
    split_axis: int,
    concat_axis: int,
    comm_dtype: CommDtype | None = None,
    batch_axes: tuple[int, ...] = (),
    guard: bool = False,
    impl: Impl = "jnp",
) -> jax.Array:
    """``lax.all_to_all(..., tiled=True)`` with an optional reduced-precision
    wire payload (the comm-compression core all three engines share).

    ``complex64``: the collective runs on ``y`` directly — bit-identical to
    an uncompressed exchange.  ``bf16``/``int8``: ``y`` is encoded to
    stacked (re, im) planes (a plain f32 plane for real input), the narrow
    payload is exchanged with the split/concat axes shifted past the plane
    axis, and the result is decoded back to ``y``'s dtype.  For int8 the
    per-destination-chunk scales ride in a second, scale-sized all-to-all
    so each receiver dequantizes chunk ``j`` with sender ``j``'s scale.

    ``batch_axes`` names the field/batch axes of a stacked multi-field
    payload (``y``-axis indices): the collective and the bf16 codec are
    batch-oblivious, but the int8 codec blocks its scales per (field,
    destination chunk) so fields of different magnitude never share one
    max-abs — the scale all-to-all ships ``m × prod(batch extents)`` f32s.

    ``guard=True`` additionally returns per-payload health stats (see
    :mod:`repro.robustness.health`) riding the codec's existing reductions:
    the return becomes ``(out, {"nonfinite", "saturated"})``.  Only the
    lossy codecs scan their payload — a complex64 exchange returns zero
    counters at zero traced cost, because any non-finite it ships
    propagates through the remaining stages into the executor's
    output-energy guard (detection is global there, not per-stage).  The
    fault taps (:mod:`repro.robustness.faults`) trace zero ops unless a
    FaultPlan is armed, so an unguarded exchange compiles bit-identically.

    ``impl="pallas"`` runs the lossy codec through the fused exchange
    kernels (:mod:`repro.kernels.exchange`): encode and decode each become
    one pallas call instead of the multi-pass jnp chain, and — because the
    narrowing convert lives *inside* an opaque kernel — XLA cannot hoist
    it across the collective, so the wire genuinely carries the narrow
    payload (the single-host CPU backend widens the jnp bf16 wire back to
    f32; see planlint PLAN002).  A lossless payload has no codec to fuse
    and always takes the jnp path below (``pallas_applicable``).

    The work is named in the program (:mod:`repro.core.spans`): the codec
    before the collective ``encode``, every all-to-all ``a2a``, the codec
    after it ``decode``.
    """
    d = canonical_comm_dtype(comm_dtype)
    if d == "complex64":
        stats = _health.zero_stats() if guard else None
        with spans.kind("a2a"):
            out = _a2a(y, axis_name, split_axis=split_axis, concat_axis=concat_axis)
        return (out, stats) if guard else out
    iscomplex = jnp.iscomplexobj(y)
    if impl == "pallas":
        if batch_axes != tuple(range(len(batch_axes))):
            raise ValueError("impl='pallas' requires leading batch axes; "
                             f"got {batch_axes}")
        m = _axis_size(axis_name)
        sd = _faults.scale_div() if d == "int8" else None
        with spans.kind("encode"):
            q, scale, stats = _xk.encode_payload(
                y, axis=split_axis, m=m, nbatch=len(batch_axes), codec=d,
                guard=guard, scale_div=sd)
        with spans.kind("a2a"):
            # payload is (P, *y.shape) re/im planes: split/concat shift past P
            qx = _a2a(q, axis_name, split_axis=split_axis + 1,
                      concat_axis=concat_axis + 1)
            sx = None
            if scale is not None:  # int8: (F, M) per-(field, chunk) scales
                sx = _a2a(scale, axis_name, split_axis=1, concat_axis=1, wire="scale")
        with spans.kind("decode"):
            out = _xk.decode_payload(qx, axis=concat_axis, m=m,
                                     nbatch=len(batch_axes), scale=sx, codec=d,
                                     iscomplex=iscomplex)
        return (out, stats) if guard else out
    with spans.kind("encode"):
        planes = quant.complex_to_planes(y) if iscomplex else y[None].astype(jnp.float32)
    sa, ca = split_axis + 1, concat_axis + 1
    ba = tuple(b + 1 for b in batch_axes)  # planes coords

    if d == "bf16":
        with spans.kind("encode"):
            stats = _health.payload_stats(planes) if guard else None
            wire = quant.encode_bf16(planes)
        with spans.kind("a2a"):
            p = _a2a(wire, axis_name, split_axis=sa, concat_axis=ca)
        with spans.kind("decode"):
            p = quant.decode_bf16(p)
            out = quant.planes_to_complex(p) if iscomplex else p[0]
        return (out, stats) if guard else out

    # int8: one scale per (field, destination chunk) of the split axis.
    m = _axis_size(axis_name)
    with spans.kind("encode"):
        nv = planes.shape[sa]
        if nv % m != 0:
            raise ValueError(f"split axis extent {nv} not divisible by group size {m}")
        view = list(planes.shape)
        view[sa : sa + 1] = [m, nv // m]
        # block axes in view coords: the m-chunk axis plus every batch axis
        # (axes past the inserted nv//m axis shift right by one)
        block_axes = (sa,) + tuple(b if b < sa else b + 1 for b in ba)
        qargs = dict(block_axis=block_axes, scale_div=_faults.scale_div())
        if guard:
            q, scale, stats = quant.quantize_int8(planes.reshape(view),
                                                  with_stats=True, **qargs)
        else:
            q, scale = quant.quantize_int8(planes.reshape(view), **qargs)
            stats = None
        q = q.reshape(planes.shape)
        # scale keepdims (view coords) -> planes coords: drop the nv//m axis
        s = scale.reshape([e for i, e in enumerate(scale.shape) if i != sa + 1])
    with spans.kind("a2a"):
        qx = _a2a(q, axis_name, split_axis=sa, concat_axis=ca)
        sx = _a2a(s, axis_name, split_axis=sa, concat_axis=ca, wire="scale")
    with spans.kind("decode"):
        # received chunk j along the concat axis was quantized with sender j's
        # scale: view ca as (m, ca_out/m) and broadcast sx over the chunk
        out_view = list(qx.shape)
        out_view[ca : ca + 1] = [m, qx.shape[ca] // m]
        dq = quant.dequantize_int8(qx.reshape(out_view), jnp.expand_dims(sx, ca + 1))
        p = dq.reshape(qx.shape)
        out = quant.planes_to_complex(p) if iscomplex else p[0]
    return (out, stats) if guard else out


def exchange_shard(
    block: jax.Array,
    v: int,
    w: int,
    group: Group,
    *,
    method: Method = "fused",
    chunks: int = 1,
    transposed_out: bool = False,
    comm_dtype: CommDtype | None = None,
    nbatch: int = 0,
    guard: bool = False,
    impl: Impl = "jnp",
) -> jax.Array:
    """Per-shard v→w exchange over mesh subgroup ``group``.

    Input block: axis ``v`` full (locally complete), axis ``w`` holds this
    rank's shard.  Output block: axis ``v`` holds this rank's shard, axis
    ``w`` full.  Mirrors the paper's EXCHANGE(P, A, v, B, w) (Alg. 3).

    ``chunks`` only affects ``method="pipelined"``; ``transposed_out`` only
    affects ``method="traditional"``.  ``comm_dtype`` selects the wire
    payload encoding (see module docstring): ``None``/``"complex64"`` is
    lossless and bit-identical to the uncompressed exchange.

    ``nbatch`` marks the leading ``nbatch`` axes of ``block`` as stacked
    field/batch axes (see module docstring): ``v``/``w`` stay
    *field-relative* and the one collective ships every field's payload —
    the batched multi-field entry point.  With ``transposed_out=True`` the
    chunk axis still comes out leading (before the batch axes).

    ``guard=True`` returns ``(out, stats)`` with this exchange's fused
    health counters (see :func:`_all_to_all_comm`).

    ``impl="pallas"`` fuses each side's local work (codec, and for
    ``traditional`` the pack/unpack realignment too) into one exchange
    kernel per side — see :mod:`repro.kernels.exchange`.  It applies to
    lossy payloads only (a lossless exchange has no local codec pass to
    fuse) and to ``transposed_out=False``; inapplicable combinations
    execute the jnp reference path, so ``impl`` never changes results
    beyond the documented codec parity bounds.
    """
    if v == w:
        raise ValueError("exchange requires v != w (paper Alg. 3)")
    names = group_names(group)
    axis_name = names[0] if len(names) == 1 else names
    bv, bw = v + nbatch, w + nbatch
    batch_axes = tuple(range(nbatch))

    if method == "fused":
        # The paper's method: one generalized all-to-all; the split/concat
        # axes are the "subarray datatype" description.
        return _all_to_all_comm(block, axis_name, split_axis=bv, concat_axis=bw,
                                comm_dtype=comm_dtype, batch_axes=batch_axes,
                                guard=guard, impl=impl)

    if method == "pipelined":
        r = exchange_shard_sliced(block, v, w, group, chunks=chunks,
                                  comm_dtype=comm_dtype, nbatch=nbatch,
                                  guard=guard, impl=impl)
        pieces, stats = r if guard else (r, None)
        if len(pieces) == 1:
            out = pieces[0]
        else:
            with spans.kind("decode"):
                out = jnp.concatenate(pieces, axis=bv)
        return (out, stats) if guard else out

    if method == "traditional":
        m = _axis_size(axis_name)
        nv = block.shape[bv]
        if nv % m != 0:
            raise ValueError(f"axis v={v} extent {nv} not divisible by group size {m}")
        d = canonical_comm_dtype(comm_dtype)
        if impl == "pallas" and not transposed_out and _xk.pallas_applicable(method, d):
            # One kernel packs chunk-major AND encodes (Eqs. 15-16 cost no
            # extra pass); the inverse kernel scatters + dequantizes (Eq. 17).
            sd = _faults.scale_div() if d == "int8" else None
            with spans.kind("encode"):
                payload, scale, stats = _xk.pack_chunks(
                    block, axis=bv, m=m, nbatch=nbatch, codec=d, guard=guard,
                    scale_div=sd)
            with spans.kind("a2a"):
                y = _a2a(payload, axis_name, split_axis=0, concat_axis=0)
                sx = None
                if scale is not None:  # int8: (M, F) scales, chunk-major like the payload
                    sx = _a2a(scale, axis_name, split_axis=0, concat_axis=0, wire="scale")
            with spans.kind("decode"):
                out = _xk.unpack_chunks(y, w=w, m=m, nbatch=nbatch,
                                        scale=sx, codec=d,
                                        iscomplex=jnp.iscomplexobj(block))
            return (out, stats) if guard else out
        with spans.kind("encode"):
            # Eq. (15): reshape v -> (m, nv/m); stride change only, free.
            shape = list(block.shape)
            shape[bv : bv + 1] = [m, nv // m]
            y = block.reshape(shape)
            # Eq. (16): bring the chunk axis to the front — the materialized
            # local transpose (the costly pack step traditional codes pay for).
            y = jnp.moveaxis(y, bv, 0)
        # Eq. (17)+ALLTOALL: contiguous exchange on the leading chunk axis.
        r = _all_to_all_comm(y, axis_name, split_axis=0, concat_axis=0,
                             comm_dtype=comm_dtype,
                             batch_axes=tuple(b + 1 for b in batch_axes),
                             guard=guard)
        y, stats = r if guard else (r, None)
        # Unpack: leading chunk q now carries peer q's w-shard (global w order).
        if transposed_out:
            # FFTW "transposed out": keep chunk-major layout, caller handles it.
            return (y, stats) if guard else y
        with spans.kind("decode"):
            # Insert the chunk axis just before w (chunk-major == global w
            # order) and merge (m, w_shard) -> w_full: the second
            # materialized copy.
            z = jnp.moveaxis(y, 0, bw)
            shape = list(z.shape)
            shape[bw : bw + 2] = [shape[bw] * shape[bw + 1]]
            z = z.reshape(shape)
        return (z, stats) if guard else z

    raise ValueError(f"unknown method {method!r}")


def exchange_shard_sliced(
    block: jax.Array,
    v: int,
    w: int,
    group: Group,
    *,
    chunks: int,
    comm_dtype: CommDtype | None = None,
    nbatch: int = 0,
    guard: bool = False,
    impl: Impl = "jnp",
) -> list[jax.Array]:
    """The fused v→w exchange as ``chunks`` independent per-slice
    all-to-alls (the ``pipelined`` engine).

    The input's v axis is viewed as ``(m, b)`` — ``m`` the subgroup size,
    ``b = n_v/m`` the post-exchange shard extent — and sliced along ``b``.
    Slice ``i``'s all-to-all splits the ``m`` factor across ranks and
    concatenates along ``w``, so rank ``r``'s slice ``i`` output is exactly
    rows ``[r*b + off_i, r*b + off_i + len_i)`` of the fused output:
    concatenating the slices along ``v`` reproduces ``fused`` bit for bit
    for lossless payloads (``comm_dtype=None``/``"complex64"``), while each
    slice remains a standalone collective XLA may overlap with unrelated
    compute.  (Under a lossy ``comm_dtype`` the slices quantize
    independently — different max-abs blocks than the fused engine — so the
    results agree only to the codec's error bound, not bitwise.)

    ``nbatch`` leading batch axes ride along whole in every slice
    (``v``/``w`` field-relative, as in :func:`exchange_shard`): each slice
    is still one collective carrying all fields' sub-range.

    ``guard=True`` returns ``(pieces, stats)``: one stats dict summed over
    all slices (each slice's codec counters added together).
    """
    names = group_names(group)
    axis_name = names[0] if len(names) == 1 else names
    m = _axis_size(axis_name)
    bv, bw = v + nbatch, w + nbatch
    nv = block.shape[bv]
    if nv % m != 0:
        raise ValueError(f"axis v={v} extent {nv} not divisible by group size {m}")
    b = nv // m
    sizes = [n for n in local_lengths(b, max(1, min(chunks, b))) if n > 0]
    # view v as (m, b); the concat axis shifts right if it follows v
    shape = list(block.shape)
    shape[bv : bv + 1] = [m, b]
    with spans.kind("encode"):
        y = block.reshape(shape)
    w_eff = bw if bw < bv else bw + 1
    pieces = []
    stats = _health.zero_stats() if guard else None
    off = 0
    for n in sizes:
        with spans.kind("encode"):
            piece = lax.slice_in_dim(y, off, off + n, axis=bv + 1)
        off += n
        r = _all_to_all_comm(piece, axis_name, split_axis=bv, concat_axis=w_eff,
                             comm_dtype=comm_dtype,
                             batch_axes=tuple(range(nbatch)), guard=guard,
                             impl=impl)
        if guard:
            p, s = r
            stats = _health.add_stats(stats, s)
        else:
            p = r
        # p's m-factor axis now has extent 1: merge (1, n) -> (n,)
        pshape = list(p.shape)
        pshape[bv : bv + 2] = [n]
        with spans.kind("decode"):
            pieces.append(p.reshape(pshape))
    return (pieces, stats) if guard else pieces


def _axis_size(axis_name) -> int:
    return _mesh_axis_size(axis_name)


def exchange(
    x: jax.Array,
    src: Pencil,
    v: int,
    w: int,
    *,
    method: Method = "fused",
    chunks: int = 1,
    comm_dtype: CommDtype | None = None,
    impl: Impl = "jnp",
) -> tuple[jax.Array, Pencil]:
    """Jit-level v→w exchange of a globally-sharded array.

    ``x`` must be laid out per ``src``: axis ``v`` aligned (locally
    complete) and axis ``w`` distributed on *input*; the paper's Eq. (20)
    contract is that the output has the roles swapped — axis ``v``
    distributed over ``w``'s subgroup and axis ``w`` aligned.  Returns the
    redistributed array and its Pencil.
    """
    if not src.aligned(v):
        raise ValueError(f"input axis v={v} must be aligned; placement={src.placement}")
    group = src.placement[w]
    if group is None:
        raise ValueError(f"input axis w={w} must be distributed; placement={src.placement}")
    dst = src.exchanged(v, w)
    fn = shard_map(
        partial(exchange_shard, v=v, w=w, group=group, method=method,
                chunks=chunks, comm_dtype=comm_dtype, impl=impl),
        mesh=src.mesh,
        in_specs=src.spec,
        out_specs=dst.spec,
        check_vma=False,
    )
    return fn(x), dst


# ---------------------------------------------------------------------------
# Cost / time models (roofline + tuner priors)
# ---------------------------------------------------------------------------


def exchange_cost_bytes(src: Pencil, v: int, w: int) -> int:  # noqa: ARG001 — (src, v, w) parity with the exchange_* family
    """Elements each rank sends in the exchange (itemsize excluded): the
    full local block minus the chunk it keeps.  Identical for all methods —
    the element count is a property of the redistribution, not the engine.
    Used by the roofline model; see :func:`exchange_wire_bytes` for the
    actual wire bytes under a ``comm_dtype`` payload policy."""
    m = group_size(src.mesh, src.placement[w])  # type: ignore[arg-type]
    local = int(np.prod(src.local_shape, dtype=np.int64))
    return local * (m - 1) // m


def exchange_wire_bytes(
    src: Pencil, v: int, w: int, *, itemsize: int = 8,
    comm_dtype: CommDtype | None = None, nfields: int = 1, slices: int = 1,
) -> int:
    """Bytes each rank actually puts on the wire: the exchanged elements at
    the narrowed payload width (bf16 planes: itemsize/2; int8 planes:
    itemsize/4 plus one f32 scale per peer destination).  ``nfields``
    prices a stacked multi-field exchange: payload × N, and int8 ships one
    scale per (field, destination).  ``slices`` is the pipelined engine's
    collective count (see :func:`pipeline_slices`): the payload bytes are
    invariant to slicing, but each int8 slice quantizes independently and
    ships its own scale set."""
    d = canonical_comm_dtype(comm_dtype)
    total = exchange_cost_bytes(src, v, w) * nfields * itemsize // wire_ratio(d)
    if d == "int8":
        m = group_size(src.mesh, src.placement[w])  # type: ignore[arg-type]
        # per-(field, destination) f32 scales (kept chunk excluded)
        total += 4 * (m - 1) * nfields * max(1, slices)
    return total


def pipeline_slices(src: Pencil, v: int, w: int, *, chunks: int) -> int:
    """Number of independent all-to-all slices the pipelined engine emits
    for this exchange: ``min(chunks, b)`` nonempty pieces of the
    post-exchange shard extent ``b = n_v/m`` (mirrors the slicing loop in
    :func:`exchange_shard_sliced`, so planlint's expected-launch count and
    the executed collective count can never drift apart)."""
    m = group_size(src.mesh, src.placement[w])  # type: ignore[arg-type]
    b = src.local_shape[v] // m
    return len([n for n in local_lengths(b, max(1, min(chunks, b))) if n > 0])


def exchange_engine_ops(
    src: Pencil, v: int, w: int, *, method: Method = "fused", chunks: int = 1,
    transposed_out: bool = False, nbatch: int = 0,
    comm_dtype: CommDtype | None = None, impl: Impl = "jnp",
) -> dict[str, int]:
    """Materialized realignment ops (``transpose`` / ``concatenate`` jaxpr
    eqns) each engine's shard function emits *outside* the collective — the
    contract :mod:`repro.analysis.planlint` checks the lowered jaxpr
    against.

    ``fused`` emits none: the strided split/concat rides inside the single
    all-to-all (the paper's Sec. 3.3.2 claim, stated as an auditable
    count).  ``traditional`` pays its documented pack and unpack moveaxis
    copies — except when the moved axis is already leading (``v+nbatch ==
    0`` packs for free; ``w+nbatch == 0`` or ``transposed_out`` skips the
    unpack), where jnp.moveaxis is the identity and no transpose eqn
    exists.  ``pipelined`` emits one concatenate reassembling its slices
    whenever it actually slices (>1 pieces).

    ``impl="pallas"`` (where applicable: lossy payload, and for
    traditional no ``transposed_out``) folds traditional's pack/unpack
    into the exchange kernels' index maps — zero engine-attributed
    transposes, the no-realignment invariant planlint's PLAN009 verifies.
    Pipelined's slice-reassembly concatenate remains either way."""
    if method == "traditional":
        if (impl == "pallas" and not transposed_out
                and canonical_comm_dtype(comm_dtype) != "complex64"):
            return {"transposes": 0, "concats": 0}
        bv, bw = v + nbatch, w + nbatch
        t = int(bv != 0) + int(bw != 0 and not transposed_out)
        return {"transposes": t, "concats": 0}
    if method == "pipelined":
        s = pipeline_slices(src, v, w, chunks=chunks)
        return {"transposes": 0, "concats": int(s > 1)}
    if method == "fused":
        return {"transposes": 0, "concats": 0}
    raise ValueError(f"unknown method {method!r}")


def exchange_local_copy_elems(
    src: Pencil, v: int, w: int, *, method: Method = "fused",
    comm_dtype: CommDtype | None = None, impl: Impl = "jnp",
) -> int:  # noqa: ARG001 — (src, v, w) parity with the exchange_* family
    """Elements of *materialized local copies* the method pays on top of the
    wire payload and codec: traditional's pack+unpack transposes touch the
    local block twice; pipelined's final concat materializes it once; fused
    pays none (the layout change rides inside the collective).  Under
    ``impl="pallas"`` with a lossy payload, traditional's pack/unpack ride
    the codec kernels' index maps — the engine pays no copies of its own
    (pipelined's reassembly concat remains)."""
    local = int(np.prod(src.local_shape, dtype=np.int64))
    if impl == "pallas" and canonical_comm_dtype(comm_dtype) != "complex64":
        return {"fused": 0, "pipelined": local, "traditional": 0}.get(method, 0)
    return {"fused": 0, "pipelined": local, "traditional": 2 * local}.get(method, 0)


#: modeled fixed cost per issued collective (launch + rendezvous); the term
#: that makes per-field exchanges of many small fields latency-bound and a
#: stacked batched exchange win
ICI_LATENCY_S = 1e-6


def exchange_collective_launches(
    src: Pencil, v: int, w: int, *, method: Method = "fused",
    chunks: int = 1, nfields: int = 1, batch_fusion: str = "stacked",
) -> int:  # noqa: ARG001 — (src, v, w) parity with the exchange_* family
    """Number of latency-priced collective launches this exchange issues —
    exactly the multiplier :func:`exchange_time_model` applies to
    ``ici_latency_s``, stated as a count so the scaling harness can fit the
    latency coefficient against measurements (the int8 scale all-to-all is
    not latency-priced by the time model, so it is not counted here
    either; planlint's launch audit covers it instead).

    ``stacked`` (or a single field) issues one collective per exchange —
    ``chunks`` of them for a chunked pipelined engine; ``per-field`` and
    ``pipelined-across-fields`` both issue that count per field."""
    per_exchange = chunks if method == "pipelined" and chunks > 1 else 1
    n = max(1, nfields)
    if n == 1 or batch_fusion == "stacked":
        return per_exchange
    if batch_fusion in ("per-field", "pipelined-across-fields"):
        return n * per_exchange
    raise ValueError(f"unknown batch_fusion {batch_fusion!r}; expected one of {BATCH_FUSIONS}")


def exchange_time_model(
    src: Pencil,
    v: int,
    w: int,
    *,
    itemsize: int = 8,
    method: Method = "fused",
    chunks: int = 1,
    comm_dtype: CommDtype | None = None,
    ici_bw: float = 50e9,
    hbm_bw: float = 819e9,
    overlap_compute_s: float = 0.0,
    nfields: int = 1,
    batch_fusion: str = "stacked",
    ici_latency_s: float = ICI_LATENCY_S,
    impl: Impl = "jnp",
) -> float:
    """Overlap-aware modeled seconds for one exchange (+ the 1-D FFT stage
    that follows it, whose *per-field* time the caller passes as
    ``overlap_compute_s``).

    fused/traditional serialize collective then compute; pipelined with c
    slices exposes only the first slice's collective and the last slice's
    compute, overlapping the rest:

        T = c·T_lat + T_comm/c + max(T_comm, T_fft)·(c-1)/c + T_fft/c

    A narrowed ``comm_dtype`` shrinks T_comm to the wire bytes of
    :func:`exchange_wire_bytes` but adds two HBM passes over the local
    block (quantize before / dequantize after the collective).

    ``nfields`` fields ship under one of the ``batch_fusion`` modes:

    ``"stacked"``                  — one collective carries all N fields:
        1 latency, N× bytes/compute (wins when latency-bound).
    ``"pipelined-across-fields"``  — N collectives, field i's collective
        hidden under field i-1's FFT:
        T = N·T_lat + T_comm + (N-1)·max(T_comm, T_fft) + T_fft.
    ``"per-field"``                — N fully serialized exchange+FFT pairs
        (the baseline a per-field loop pays).
    """
    d = canonical_comm_dtype(comm_dtype)
    comm_s = exchange_wire_bytes(src, v, w, itemsize=itemsize, comm_dtype=d) / ici_bw
    copy_s = (exchange_local_copy_elems(src, v, w, method=method, comm_dtype=d,
                                        impl=impl) * itemsize / hbm_bw)
    if d != "complex64":
        # pallas: the codec is one lean pass per side (read wide + write
        # narrow / read narrow + write wide) — the scale reduction and any
        # pack realignment ride the same pass.  jnp: each side additionally
        # materializes the full-width re/im plane stack (the quantize pass
        # cannot fuse with the producer across its own amax reduction).
        local = int(np.prod(src.local_shape, dtype=np.int64))
        per_side = itemsize + itemsize // wire_ratio(d)
        if impl != "pallas":
            per_side += itemsize
        copy_s += 2 * local * per_side / hbm_bw

    def one(comm, fft):
        """One exchange of ``comm`` seconds of wire plus ``fft`` seconds of
        following compute, under the plan's engine."""
        if method == "pipelined" and chunks > 1:
            c = chunks
            return (c * ici_latency_s + comm / c
                    + max(comm, fft) * (c - 1) / c + fft / c)
        return ici_latency_s + comm + fft

    n = max(1, nfields)
    if n == 1 or batch_fusion == "stacked":
        return one(comm_s * n, overlap_compute_s * n) + copy_s * n
    if batch_fusion == "per-field":
        return n * (one(comm_s, overlap_compute_s) + copy_s)
    if batch_fusion == "pipelined-across-fields":
        # each field's exchange is emitted whole (chunked engines still
        # issue `chunks` collectives per field — price every launch)
        launches = n * (chunks if method == "pipelined" and chunks > 1 else 1)
        fft = overlap_compute_s
        return (launches * ici_latency_s + comm_s + (n - 1) * max(comm_s, fft)
                + fft + n * copy_s)
    raise ValueError(f"unknown batch_fusion {batch_fusion!r}; expected one of {BATCH_FUSIONS}")
