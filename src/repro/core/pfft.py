"""Distributed multidimensional FFT — paper Secs. 3.3, 3.5, 3.6.

``ParallelFFT`` plans a d-dimensional transform of a global array decomposed
on a k-dimensional Cartesian mesh subgrid (k ≤ d-1): slab (k=1), pencil
(k=2), or higher.  The plan is the paper's schedule:

  forward:  F_{d-1} … F_k (local trailing axes), then for i = k-1 … 0:
            exchange(v=i+1 → w=i over subgroup P_i); F_i
  backward: the exact reverse (paper Eq. 8 / Eqs. 26–32).

Every exchange is one call to :func:`repro.core.redistribute.exchange_shard`
— the same ~40-line routine regardless of dimensionality, which is the
paper's headline simplicity claim.  ``method`` selects the paper's fused
all-to-all ("fused"), the traditional transpose+all-to-all baseline
("traditional"), the sliced exchange interleaved with the next stage's 1-D
FFTs ("pipelined", comm/compute overlap), or the autotuned per-stage mix
("auto", see :mod:`repro.core.tuner`).

Per-axis transforms (``transforms=``): each axis carries a
:class:`repro.core.fftcore.TransformSpec` — c2c, r2c, DCT-II/III, DST-II/III,
or a pruned/truncated spectrum (``n_keep``).  Pruned axes fold 3/2-rule
dealiasing into the plan itself: the truncation happens inside the FFT
stage right next to the exchange unpack, so downstream exchanges ship only
the retained modes (the dealiased Navier–Stokes pipeline pays *less* wire
traffic than the undealiased one, not an extra HBM pass).  Spectral extents
therefore differ stage by stage between the forward and backward plans;
``pencil_trace``/``dtype_trace`` record the (extent, dtype) state before
every stage and all analytic models read them.

The whole plan executes inside a single ``shard_map``, so XLA sees the
entire FFT↔collective pipeline and can schedule/overlap it (the TPU
equivalent of taking data rearrangement off the critical path).
:meth:`ParallelFFT.executor` builds that ``shard_map`` once per direction,
stacking and schedule; every entry point runs through it.

Batched multi-field execution (``forward_many``/``backward_many``): real
spectral workloads run the *same* plan over many fields at once (the
Navier–Stokes example transforms u, v, w plus nonlinear products through
identical stages).  ``forward``/``backward`` accept a leading batch axis
and ``forward_many``/``backward_many`` additionally accept a pytree of
fields; the executor runs the whole batch through one ``shard_map`` whose
per-stage behavior is the plan's ``batch_fusion`` mode:

``"stacked"`` (default)        — every exchange ships the stacked payload
    of all N fields in **one** all-to-all (message aggregation; a lossy
    ``comm_dtype`` codec runs once over the stacked block), and FFT stages
    transform all fields in one vectorized call.  Bit-identical to the
    per-field loop for lossless payloads.  Wins when exchanges are
    latency-bound (small per-field messages).
``"pipelined-across-fields"``  — per-field collectives emitted interleaved
    with the previous field's 1-D FFT, so collective DMA overlaps MXU
    compute even when per-field slicing (``method="pipelined"``) is too
    fine.  Wins when stages are compute-heavy.
``"per-field"``                — N serialized exchange+FFT pairs inside
    one jit (the baseline the other modes are judged against).

``method="auto"`` prices all three: the tuned schedule is a
:class:`~repro.core.planconfig.StageEntry` — ``(method, chunks,
comm_dtype, impl, batch_fusion)`` — per stage, cached per batch size
(see :mod:`repro.core.tuner`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import cached_property, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import fftcore, spans
from repro.core.fftcore import TransformSpec, as_spec
from repro.core.meshutil import shard_map
from repro.core.decomp import pad_to_multiple
from repro.core.pencil import Group, Pencil, group_names, group_size, make_pencil, pad_global, unpad_global
from repro.core.planconfig import PlanConfig, StageEntry, as_schedule
from repro.core.quant import canonical_comm_dtype
from repro.core.redistribute import exchange_shard, exchange_shard_sliced
from repro.robustness import faults as _faults, health as _health

#: StageEntry per ExchangeStage, in forward stage order
Schedule = tuple[StageEntry, ...]

# ---------------------------------------------------------------------------
# Plan construction
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FFTStage:
    axis: int
    spec: TransformSpec
    n: int  # full (physical-grid) transform length; spectral extent is spec.spectral_extent(n)


@dataclass(frozen=True)
class ExchangeStage:
    v: int
    w: int
    group: Group


Stage = FFTStage | ExchangeStage


class _Walk(NamedTuple):
    """One direction of a plan in execution order: ``pencils[i]`` and
    ``dtypes[i]`` describe the block before ``stages[i]``; ``schedule``
    holds one entry per exchange stage, in the same order."""

    stages: tuple
    pencils: tuple
    dtypes: tuple
    in_pencil: Pencil
    out_pencil: Pencil
    sign: int
    schedule: tuple


class ParallelFFT:
    """Plan + executor for a distributed d-dim transform.

    Args:
      mesh:   jax Mesh (any dimensionality; unrelated axes are untouched).
      shape:  logical global array shape (d axes) — the *physical-grid*
              extents; pruned axes emit fewer spectral modes than this.
      grid:   k mesh axis names (or tuples of names) decomposing array axes
              0..k-1, k ≤ d-1.  (C row-major convention, like the paper.)
      config: a :class:`~repro.core.planconfig.PlanConfig` carrying every
              execution knob — method, FFT impl, exchange_impl, chunks,
              comm_dtype, batch_fusion, tuner_cache, guard (see its
              docstring for field semantics); ``config=None`` means
              ``PlanConfig()`` defaults.  The plan reads it as
              ``plan.config``.
      transforms: per-axis :class:`TransformSpec` (or tag strings "c2c",
              "r2c", "dct2", "dct3", "dst2", "dst3"), length d; ``None``
              means c2c on every axis.  Transforms are applied in
              descending axis order; an r2c axis must come before any
              complex-producing axis in that order (i.e. every axis to its
              right is dct/dst), and at most one r2c is allowed.

    Guarded plans' ``forward``/``backward`` (and the ``_many`` variants)
    return ``(result, HealthReport)``.
    """

    def __init__(
        self,
        mesh: Mesh,
        shape: tuple[int, ...],
        grid: tuple[Group, ...],
        *,
        config: PlanConfig | None = None,
        transforms=None,
    ):
        d, k = len(shape), len(grid)
        if not 1 <= k <= d - 1:
            raise ValueError(f"need 1 <= len(grid)={k} <= d-1={d - 1}")
        if transforms is None:
            specs = (TransformSpec.c2c(),) * d
        else:
            specs = tuple(as_spec(s) for s in transforms)
            if len(specs) != d:
                raise ValueError(f"transforms must have one spec per axis: got {len(specs)}, need {d}")
        # dtype legality in apply order (axis d-1 → 0): r2c must see real data
        seen_complex = False
        for a in range(d - 1, -1, -1):
            if specs[a].kind == "r2c":
                if seen_complex:
                    raise ValueError(
                        f"r2c on axis {a} would see complex data: every axis after it "
                        f"(higher index) must be dct/dst, and only one r2c is allowed")
                seen_complex = True
            elif specs[a].kind == "c2c":
                seen_complex = True
        self.transforms = specs
        self.mesh, self.shape, self.grid = mesh, tuple(shape), tuple(grid)
        self.config = config if config is not None else PlanConfig()
        self.d, self.k = d, k
        self._batched_sched_memo: dict[int, Schedule] = {}
        self._executors: dict = {}

        sizes = [group_size(mesh, g) for g in grid]
        # Per-axis divisibility: every subgroup an axis is ever distributed
        # over, in either direction of the plan: an exchange splits an axis
        # into equal blocks, so its padded extent must divide by each.
        divisors = [1] * d
        for j in range(k):
            divisors[j] = math.lcm(divisors[j], sizes[j])  # initial placement
        for j in range(1, k + 1):
            divisors[j] = math.lcm(divisors[j], sizes[j - 1])  # gained at exchange
        # subgroup an axis is split over *after* its own transform (the one
        # the spectral extent must stay divisible by)
        future_div = [sizes[j - 1] if 1 <= j <= k else 1 for j in range(d)]

        placement: list[Group | None] = [grid[i] if i < k else None for i in range(d)]
        self.input_pencil = make_pencil(mesh, self.shape, tuple(placement), divisors=tuple(divisors))

        # input/spectral dtypes: real input iff the first applied transform
        # that produces complex output is r2c (or no axis ever goes complex)
        first_complex = next((specs[a].kind for a in range(d - 1, -1, -1)
                              if not specs[a].real_to_real), None)
        in_real = first_complex in (None, "r2c")
        out_real = first_complex is None

        # Forward schedule + pencil/dtype trace.  pencil_trace[i] /
        # dtype_trace[i] describe the block *before* stages[i].
        stages: list[Stage] = []
        pencils: list[Pencil] = [self.input_pencil]
        dtypes: list = [jnp.float32 if in_real else jnp.complex64]
        cur = self.input_pencil
        cur_dt = dtypes[0]

        def push_fft(axis: int):
            nonlocal cur, cur_dt
            sp = specs[axis]
            n = self.shape[axis]
            stages.append(FFTStage(axis, sp, n))
            ext = sp.spectral_extent(n)
            if ext != cur.logical[axis]:
                cur = cur.with_axis_extent(axis, ext)
                cur = _repad(cur, axis, future_div[axis])
            if not sp.real_to_real:
                cur_dt = jnp.complex64
            pencils.append(cur)
            dtypes.append(cur_dt)

        for axis in range(d - 1, k - 1, -1):  # trailing local axes
            push_fft(axis)
        for i in range(k - 1, -1, -1):
            stages.append(ExchangeStage(v=i + 1, w=i, group=grid[i]))
            cur = cur.exchanged(i + 1, i)
            pencils.append(cur)
            dtypes.append(cur_dt)
            push_fft(i)
        self.stages = tuple(stages)
        self.pencil_trace = tuple(pencils)
        self.dtype_trace = tuple(dtypes)
        self.output_pencil = cur
        self.input_dtype = dtypes[0]
        self.spectral_dtype = jnp.float32 if out_real else jnp.complex64

    # -- schedule ------------------------------------------------------------

    @property
    def n_exchanges(self) -> int:
        return sum(isinstance(s, ExchangeStage) for s in self.stages)

    @cached_property
    def schedule(self) -> Schedule:
        """:class:`StageEntry` per exchange stage, forward order.  Uniform
        for the explicit methods; tuned (and disk-cached) for
        method="auto", where ``comm_dtype`` is the per-stage payload the
        tuner picked within the plan's accuracy budget and ``impl`` is
        swept only within the plan's ``exchange_impl`` candidate budget."""
        if self.config.method == "auto":
            from repro.core import tuner

            return as_schedule(tuner.get_or_tune(self, cache_path=self.config.tuner_cache))
        entry = self.config.stage_entry()._replace(batch_fusion="stacked")
        return (entry,) * self.n_exchanges

    def batched_schedule(self, nfields: int) -> Schedule:
        """:class:`StageEntry` per exchange stage for an ``nfields``-field
        execution, forward order.  Explicit methods use the plan's uniform
        ``batch_fusion``; method="auto" tunes the full batch-aware
        candidate space per stage, cached per batch size."""
        if nfields <= 1:
            return tuple(e._replace(batch_fusion="stacked") for e in self.schedule)
        if nfields not in self._batched_sched_memo:
            if self.config.method == "auto":
                from repro.core import tuner

                sched = as_schedule(tuner.get_or_tune(
                    self, cache_path=self.config.tuner_cache, nfields=nfields))
            else:
                sched = (self.config.stage_entry(),) * self.n_exchanges
            self._batched_sched_memo[nfields] = sched
        return self._batched_sched_memo[nfields]

    # -- executors ----------------------------------------------------------

    def _walk(self, direction: str, schedule=()) -> _Walk:
        """The plan in ``direction``'s execution order, ``schedule`` (given
        in forward order) included; backward is :func:`_reverse_plan`."""
        if direction == "forward":
            return _Walk(self.stages, self.pencil_trace, self.dtype_trace,
                         self.input_pencil, self.output_pencil, fftcore.FORWARD,
                         tuple(schedule))
        if direction == "backward":
            stages, pencils = _reverse_plan(self.stages, self.pencil_trace)
            return _Walk(stages, pencils, self.dtype_trace[::-1],
                         self.output_pencil, self.input_pencil, fftcore.BACKWARD,
                         tuple(schedule)[::-1])
        raise ValueError(f"unknown direction {direction!r}")

    def executor(self, direction: str, nfields: int | None = None, *,
                 schedule=None):
        """The ``shard_map``'d executor of one direction on *physical*
        (padded) global blocks, built once per (direction, stacking,
        schedule).

        ``nfields=None`` runs one unstacked block under :attr:`schedule`;
        an integer runs a stacked ``(nfields, *physical)`` block (leading
        field axis replicated) under :meth:`batched_schedule`.
        ``schedule`` (forward order) overrides either — the degradation
        ladder re-executes through here with widened entries.  A guarded
        plan's executor returns ``(block, stats)``: ``stats`` carries every
        shard's packed guard-stat partial (sharded out_spec, no extra
        collective), which :func:`repro.robustness.health.unpack_partials`
        sums for :func:`~repro.robustness.health.build_report`."""
        nbatch = 0 if nfields is None else 1
        if schedule is None:
            schedule = self.schedule if nfields is None else self.batched_schedule(nfields)
        key = (direction, nbatch, as_schedule(schedule))
        if key not in self._executors:
            walk = self._walk(direction, key[2])
            guard = self.config.guard != "off"
            out_specs = walk.out_pencil.batched_spec(nbatch)
            if guard:
                # shard-local stat vectors concatenate along axis 0 — the
                # runner sums the partials on the host, so the guarded hot
                # path carries no stats collective at all
                axes = tuple(n for g in self.grid for n in group_names(g))
                out_specs = (out_specs, P(axes) if axes else P())
            fn = partial(_run_stages, stages=walk.stages, pencils=walk.pencils,
                         schedule=walk.schedule, impl=self.config.impl,
                         sign=walk.sign, nbatch=nbatch, guard=guard)
            self._executors[key] = shard_map(
                fn, mesh=self.mesh, in_specs=walk.in_pencil.batched_spec(nbatch),
                out_specs=out_specs, check_vma=False)
        return self._executors[key]

    def warm(self, directions=("forward", "backward"), *,
             nfields: int = 1) -> int:
        """Precompile the plan's hot executors by running each requested
        direction once on a zero block — schedule resolution (including a
        tuner sweep for ``method="auto"``), tracing, compilation and
        weight transfer all happen here instead of on the first real
        request (the serving registry's warm start).  ``nfields > 1``
        warms the stacked executor for that batch size; a guarded plan's
        executor is the one :func:`~repro.robustness.runner.run_guarded`
        dispatches to.  Returns the number of executors exercised."""
        stack = nfields if nfields > 1 else None
        n = 0
        for direction in directions:
            walk = self._walk(direction)
            pen, nbatch = walk.in_pencil, 0 if stack is None else 1
            xpad = jax.device_put(jnp.zeros((nfields,) * nbatch + pen.physical, walk.dtypes[0]),
                                  pen.batched_sharding(nbatch))
            jax.block_until_ready(self.executor(direction, stack)(xpad))
            n += 1
        return n

    def forward(self, x: jax.Array) -> jax.Array:
        """Logical-shape convenience wrapper (pads, transforms, unpads).
        A ``d+1``-dim input is treated as a stack of fields along a leading
        batch axis and routed through the stacked executor.  When the plan
        was built with ``guard != "off"`` this returns
        ``(result, HealthReport)`` instead (see :mod:`repro.robustness`)."""
        if x.ndim == self.d + 1:
            return self._apply_many(x, "forward")
        return self._apply(x, "forward")

    def backward(self, x: jax.Array) -> jax.Array:
        if x.ndim == self.d + 1:
            return self._apply_many(x, "backward")
        return self._apply(x, "backward")

    def forward_many(self, xs):
        """Transform N fields through one batched plan execution.

        ``xs`` is either one array with a leading batch axis
        (``(N, *shape)``) or a pytree (list/tuple/dict/...) of N
        logical-shape fields; the result mirrors the input structure.
        Every exchange stage ships all N fields per its batched-schedule
        entry — one collective per stage under ``batch_fusion="stacked"``
        instead of the N a per-field loop issues."""
        return self._apply_many(xs, "forward")

    def backward_many(self, xs):
        return self._apply_many(xs, "backward")

    def _apply_many(self, xs, direction: str):
        """Run a stacked array, or a pytree of fields stacked here, as one
        stack through :meth:`_apply`; a pytree comes back as a pytree."""
        if hasattr(xs, "ndim"):  # stacked array, not a pytree of fields
            if xs.ndim != self.d + 1:
                raise ValueError(f"stacked {direction} input must be (nfields, *field) with "
                                 f"ndim={self.d + 1}; got ndim={xs.ndim} for a d={self.d} plan")
            return self._apply(xs, direction, xs.shape[0])
        leaves, treedef = jax.tree_util.tree_flatten(xs)
        if not leaves:
            raise ValueError(f"{direction}_many needs at least one field")
        out = self._apply(jnp.stack([jnp.asarray(leaf) for leaf in leaves]), direction, len(leaves))
        y, report = out if self.config.guard != "off" else (out, None)
        y = jax.tree_util.tree_unflatten(treedef, [y[i] for i in range(len(leaves))])
        return y if report is None else (y, report)

    def _apply(self, x, direction: str, nfields: int | None = None):
        """Pad, execute ``direction`` and unpad one logical block, or a
        stack of ``nfields`` of them; guarded plans also return the
        :class:`~repro.robustness.health.HealthReport`."""
        walk = self._walk(direction)
        nbatch = 0 if nfields is None else 1
        xpad = pad_global(x.astype(walk.dtypes[0]), walk.in_pencil, nbatch=nbatch)
        if self.config.guard == "off":
            return unpad_global(self.executor(direction, nfields)(xpad), walk.out_pencil, nbatch=nbatch)
        from repro.robustness import runner

        if nfields == 1:  # the guarded runner stacks only nfields > 1
            y, report = runner.run_guarded(self, xpad[0], direction)
            y = y[None]
        else:
            y, report = runner.run_guarded(self, xpad, direction, nfields=nfields or 1)
        return unpad_global(y, walk.out_pencil, nbatch=nbatch), report

    # -- analysis -----------------------------------------------------------

    def model_flops(self, nfields: int = 1) -> float:
        """5 N log2 N per 1-D transform, summed over the plan (the classic
        FFT nominal-flops convention; stages transforming real data — r2c
        and dct/dst on a still-real block — counted as half).  ``nfields``
        scales the whole plan for a batched multi-field execution (every
        field walks identical stage traces)."""
        return nfields * sum(self._stage_flops_at(i) for i, st in enumerate(self.stages)
                             if isinstance(st, FFTStage))

    def _stage_flops_at(self, i: int, stages=None, pencils=None, dtypes=None) -> float:
        """Nominal flops of FFT stage ``i`` of a plan walk: 5 n log2 n per
        transform × the batch of the other axes' *current* logical extents
        (read off the pencil trace, so pruned/Hermitian-reduced axes count
        at their truncated extent once truncated)."""
        stages = stages if stages is not None else self.stages
        pencils = pencils if pencils is not None else self.pencil_trace
        dtypes = dtypes if dtypes is not None else self.dtype_trace
        st = stages[i]
        before = pencils[i]
        n = st.n
        batch = 1.0
        for ax, ext in enumerate(before.logical):
            if ax != st.axis:
                batch *= ext
        flops = 5.0 * n * math.log2(max(n, 2)) * batch
        if st.spec.kind == "r2c" or dtypes[i] == jnp.float32:
            flops *= 0.5  # transform of real data
        return flops

    def _stage_itemsize(self, i: int, dtypes=None) -> int:
        dtypes = dtypes if dtypes is not None else self.dtype_trace
        return 8 if dtypes[i] == jnp.complex64 else 4

    def comm_bytes_per_device(
        self, itemsize: int | None = None, *, method: str | None = None,
        comm_dtype: str | None = None, nfields: int = 1,
    ) -> int:
        """Wire bytes each device sends across all exchanges (roofline
        term), at the narrowed payload width of each stage's ``comm_dtype``
        (default: the plan's resolved schedule — per-stage tuned payloads
        for method="auto", the uniform policy otherwise; pass
        ``comm_dtype`` to price a hypothetical uniform payload).  The
        element count is method-independent; ``method`` adds the
        materialized local-copy traffic the engine pays on top
        (traditional: pack+unpack; pipelined: slice concat; fused: none).
        ``itemsize=None`` prices each stage at its traced dtype width
        (complex64 exchanges at 8, still-real f32 exchanges at 4).
        ``nfields`` prices a batched multi-field execution (stacked wire
        payload and N× local-copy traffic)."""
        from repro.core.redistribute import (
            exchange_local_copy_elems, exchange_wire_bytes, pipeline_slices)

        if comm_dtype is None:
            batched = self._batched_sched_memo.get(nfields) if nfields > 1 else None
            if batched is not None:
                # a resolved batched schedule carries the per-stage tuned
                # payloads of *this* batch size
                entries = [tuple(e)[:3] for e in as_schedule(batched)]
            elif self.config.method == "auto" and "schedule" not in self.__dict__:
                # stay pure arithmetic: a byte count must never trigger the
                # tuner; price the uniform budget until a schedule exists
                entries = [("fused", 1, self.config.comm_dtype)] * self.n_exchanges
            else:
                entries = [tuple(e)[:3] for e in self.schedule]
        else:
            entries = [("fused", 1, canonical_comm_dtype(comm_dtype))] * self.n_exchanges
        total, ex_i = 0, 0
        for i, st in enumerate(self.stages):
            if isinstance(st, ExchangeStage):
                isz = itemsize if itemsize is not None else self._stage_itemsize(i)
                e_method, e_chunks, e_dtype = entries[ex_i]
                slices = (pipeline_slices(self.pencil_trace[i], st.v, st.w,
                                          chunks=e_chunks)
                          if e_method == "pipelined" else 1)
                total += exchange_wire_bytes(self.pencil_trace[i], st.v, st.w,
                                             itemsize=isz, comm_dtype=e_dtype,
                                             nfields=nfields, slices=slices)
                ex_i += 1
                if method is not None:
                    total += exchange_local_copy_elems(
                        self.pencil_trace[i], st.v, st.w, method=method) * isz * nfields
        return total

    def model_time_s(
        self,
        *,
        itemsize: int | None = None,
        peak_flops: float = 197e12,
        ici_bw: float = 50e9,
        hbm_bw: float = 819e9,
        ici_latency_s: float | None = None,
        schedule: Schedule | None = None,
        direction: str = "forward",
        nfields: int = 1,
        batch_fusion: str | None = None,
        exchange_only: bool = False,
    ) -> float:
        """Overlap-aware modeled wall time of one transform: FFT stages at
        ``peak_flops``; each exchange via
        :func:`repro.core.redistribute.exchange_time_model`, which credits a
        pipelined exchange with hiding the following stage's FFT compute.
        ``direction="backward"`` walks the reversed plan (whose per-stage
        logical extents and overlap pairings differ for pruned/r2c axes);
        ``itemsize=None`` prices each exchange at its traced dtype width.

        ``nfields > 1`` prices a batched multi-field execution; each stage's
        fusion mode comes from the (possibly 4-field) ``schedule`` entries,
        or uniformly from ``batch_fusion`` when given — stacked exchanges
        pay one collective latency for all fields, pipelined-across-fields
        hides per-field collectives under the previous field's FFT.

        The hardware coefficients (``peak_flops`` / ``ici_bw`` / ``hbm_bw``
        / ``ici_latency_s``) are free parameters so the scaling harness
        (:mod:`repro.core.modelfit`) can least-squares fit them against
        measured sweeps; ``exchange_only=True`` prices the exchanges-only
        executor fftbench times under ``--measure redistribution`` (FFT
        stages contribute nothing and no overlap credit applies)."""
        from repro.core.redistribute import ICI_LATENCY_S, exchange_time_model

        if ici_latency_s is None:
            ici_latency_s = ICI_LATENCY_S

        if schedule is None:
            schedule = self.batched_schedule(nfields) if nfields > 1 else self.schedule
        stages, pencils, dtypes, *_, schedule = self._walk(direction, schedule)
        ndev = group_size(self.mesh, tuple(n for g in self.grid for n in group_names(g)))
        total, ex_i, i = 0.0, 0, 0
        while i < len(stages):
            st = stages[i]
            if isinstance(st, ExchangeStage):
                entry = StageEntry.make(schedule[ex_i])
                method, chunks, comm_dtype, ex_impl, fusion = entry
                if batch_fusion is not None:
                    fusion = batch_fusion
                ex_i += 1
                src_pen = pencils[i]  # state before this exchange
                isz = itemsize if itemsize is not None else self._stage_itemsize(i, dtypes)
                nxt = stages[i + 1] if i + 1 < len(stages) else None
                fft_s = 0.0
                if isinstance(nxt, FFTStage) and nxt.axis == st.w:
                    if not exchange_only:
                        fft_s = (self._stage_flops_at(i + 1, stages, pencils, dtypes)
                                 / ndev / peak_flops)
                    i += 1  # folded into the exchange term
                total += exchange_time_model(
                    src_pen, st.v, st.w, itemsize=isz, method=method,
                    chunks=chunks, comm_dtype=comm_dtype, impl=ex_impl,
                    ici_bw=ici_bw, hbm_bw=hbm_bw, ici_latency_s=ici_latency_s,
                    overlap_compute_s=fft_s,
                    nfields=nfields, batch_fusion=fusion)
            elif not exchange_only:
                total += nfields * self._stage_flops_at(i, stages, pencils, dtypes) / ndev / peak_flops
            i += 1
        return total

    def model_collective_launches(
        self, *, nfields: int = 1, schedule: Schedule | None = None,
        batch_fusion: str | None = None, direction: str = "forward",
    ) -> int:
        """Total latency-priced collective launches one transform issues
        under its (resolved) schedule — the exact multiplier
        :meth:`model_time_s` applies to ``ici_latency_s``, exposed so the
        scaling harness can fit the latency coefficient from measured
        sweeps (see :func:`repro.core.redistribute
        .exchange_collective_launches` for the per-exchange accounting)."""
        from repro.core.redistribute import exchange_collective_launches

        if schedule is None:
            schedule = self.batched_schedule(nfields) if nfields > 1 else self.schedule
        walk = self._walk(direction, schedule)
        total, ex_i = 0, 0
        for i, st in enumerate(walk.stages):
            if not isinstance(st, ExchangeStage):
                continue
            entry = StageEntry.make(walk.schedule[ex_i])
            ex_i += 1
            fusion = batch_fusion if batch_fusion is not None else entry.batch_fusion
            total += exchange_collective_launches(
                walk.pencils[i], st.v, st.w, method=entry.method,
                chunks=entry.chunks, nfields=nfields, batch_fusion=fusion)
        return total

    def audit(self, *, nfields: int = 1, direction: str = "forward",
              schedule=None):
        """Statically audit this plan's compiled artifact against its
        schedule contracts (collective counts, wire bytes, the
        no-realignment invariant, dtype flow).  Convenience wrapper around
        :func:`repro.analysis.planlint.audit_plan`; returns its
        :class:`~repro.analysis.planlint.AuditReport`."""
        from repro.analysis.planlint import audit_plan

        return audit_plan(self, nfields=nfields, direction=direction,
                          schedule=schedule)


def _repad(pencil: Pencil, axis: int, divisor: int) -> Pencil:
    m = divisor
    if pencil.placement[axis] is not None:
        m = math.lcm(m, group_size(pencil.mesh, pencil.placement[axis]))
    new_physical = list(pencil.physical)
    new_physical[axis] = pad_to_multiple(pencil.logical[axis], m)
    from dataclasses import replace

    return replace(pencil, physical=tuple(new_physical))


def _reverse_plan(stages, pencils):
    """Backward schedule: reverse stage order; exchanges swap v/w; each FFT
    stage keeps its spec — the BACKWARD sign selects the inverse transform
    (ifft, c2r, DCT/DST inverse, pruned zero-scatter)."""
    rev_stages: list[Stage] = []
    rev_pencils: list[Pencil] = [pencils[-1]]
    # pencils[i] is the state *before* stages[i]; build reversed trace.
    for idx in range(len(stages) - 1, -1, -1):
        st = stages[idx]
        before = pencils[idx]
        if isinstance(st, ExchangeStage):
            rev_stages.append(ExchangeStage(v=st.w, w=st.v, group=st.group))
        else:
            rev_stages.append(st)
        rev_pencils.append(before)
    return tuple(rev_stages), tuple(rev_pencils)


def _run_stages(block, *, stages, pencils, schedule, impl, sign, nbatch=0,
                guard=False):
    """Execute the plan on one shard (inside shard_map).  ``schedule`` gives
    a :class:`StageEntry` per exchange stage, in this plan's stage order;
    each exchange is emitted together with the FFT of its newly-aligned
    axis (always the next stage in forward and backward plans) so the
    engine can interleave collective and compute — per slice
    for method="pipelined", per field for batch_fusion="pipelined-across-
    fields".  ``nbatch=1`` executes a stacked multi-field block: FFT stages
    transform all fields in one vectorized call and exchange stages follow
    their schedule entry's batch_fusion mode.

    ``guard=True`` additionally returns this shard's packed guard-stat
    vector (:func:`repro.robustness.health.pack_stats`): the always-on
    output probe, plus — only when the schedule has lossy wire stages —
    the pre/post block-energy Parseval bracket and the per-stage
    non-finite/saturation counters.  No collective is emitted for it —
    the guarded executor's sharded out_spec hands the runner every
    shard's partial and the host sums them.

    The work is named in the program (:mod:`repro.core.spans`): the whole
    executor under ``pfft.fwd``/``pfft.bwd``, stage ``i``'s work under
    ``stage{i}.<kind>``; its all-to-alls are counted in the executor's
    record of :func:`repro.core.spans.exchange_totals`, keyed by what fixes
    them."""
    with spans.executor(sign, repr((block.shape, block.dtype, stages, schedule, guard))):
        cur = pencils[0]
        per_stage = []
        lossy = guard and _health.schedule_is_lossy(as_schedule(schedule))
        energy_in = _health.block_energy(block) if lossy else jnp.float32(0.0)
        ex_i = i = 0
        while i < len(stages):
            st = stages[i]
            if isinstance(st, ExchangeStage):
                entry = StageEntry.make(schedule[ex_i])
                nxt_st = stages[i + 1] if i + 1 < len(stages) else None
                fft_st = nxt_st if isinstance(nxt_st, FFTStage) and nxt_st.axis == st.w else None
                block, used_fft, stats = _run_exchange_stage(
                    block, st, fft_st, pencils[i + 1],
                    pencils[i + 2] if fft_st is not None else None,
                    entry, impl=impl, sign=sign, nbatch=nbatch, at=i, guard=guard,
                    stage_index=ex_i)
                ex_i += 1
                if guard:
                    per_stage.append(stats)
                i += 2 if used_fft else 1
            else:
                block = _fft_padded_axis(block, st, cur, pencils[i + 1], impl=impl,
                                         sign=sign, nbatch=nbatch, at=i)
                i += 1
            cur = pencils[i]
        if not guard:
            return block
        energy_out = _health.block_energy(block) if lossy else jnp.float32(0.0)
        last = stages[-1]
        probe_axis = last.axis + nbatch if isinstance(last, FFTStage) else None
        probe = _health.output_probe(block, probe_axis)
        return block, _health.pack_stats(per_stage, energy_in, energy_out, probe)


def _run_exchange_stage(block, ex: ExchangeStage, fft_st: FFTStage | None,
                        mid: Pencil, after: Pencil | None, entry, *,
                        impl, sign, nbatch, at: int, guard=False, stage_index=None):
    """One exchange stage (+ the FFT of its newly-aligned axis, when
    ``fft_st`` is given), under one :class:`StageEntry` schedule entry.  Returns ``(block, used_fft, stats)``
    where ``stats`` is the stage's guard-counter dict (None unless
    ``guard``).  The fault-injection taps are free no-ops without an armed
    :class:`repro.robustness.FaultPlan`.  ``at`` is the exchange's index
    in the executed plan (its scopes' stage); the FFT is stage ``at + 1``.

    batch_fusion (stacked ``nbatch=1`` blocks only):

    ``"stacked"``                 — one collective ships all fields (plus
        the chunk-sliced interleave when method="pipelined"); the FFT runs
        batched over the whole stack.
    ``"pipelined-across-fields"`` — per-field collectives emitted so field
        i's all-to-all sits between field i-1's and field i's FFTs, giving
        XLA a per-field DMA/compute overlap window.
    ``"per-field"``               — strictly serialized per-field
        exchange+FFT pairs (the baseline loop, inside one jit).
    """
    method, chunks, comm_dtype, ex_impl, fusion = entry
    with _faults.stage_context(stage_index, method, comm_dtype):
        _faults.check_compile(method, comm_dtype)
        block = _faults.tap_stage_input(block)
        if nbatch and fusion != "stacked":
            nf = block.shape[0]
            with spans.stage(at), spans.kind("encode"):
                fields = [jax.lax.index_in_dim(block, f, axis=0, keepdims=False)
                          for f in range(nf)]
                stats = _health.zero_stats() if guard else None

            def do_exchange(fb):
                nonlocal stats
                with spans.stage(at):
                    r = exchange_shard(fb, ex.v, ex.w, ex.group, method=method,
                                       chunks=chunks, comm_dtype=comm_dtype,
                                       impl=ex_impl, guard=guard)
                    if guard:
                        r, s = r
                        stats = _health.add_stats(stats, s)
                return r

            def do_fft(fb):
                if fft_st is None:
                    return fb
                return _fft_padded_axis(fb, fft_st, mid, after, impl=impl, sign=sign,
                                        at=at + 1)

            outs = []
            if fusion == "per-field":
                for fb in fields:
                    if fft_st is not None and method == "pipelined" and chunks > 1:
                        r = _exchange_then_fft(
                            fb, ex, fft_st, mid, after, chunks=chunks,
                            comm_dtype=comm_dtype, exchange_impl=ex_impl,
                            impl=impl, sign=sign, at=at, guard=guard)
                        if guard:
                            r, s = r
                            with spans.stage(at):
                                stats = _health.add_stats(stats, s)
                        outs.append(r)
                    else:
                        outs.append(do_fft(do_exchange(fb)))
            else:  # pipelined-across-fields
                exchanged = []
                for f, fb in enumerate(fields):
                    exchanged.append(do_exchange(fb))
                    if f:  # field f's collective emitted before field f-1's FFT
                        outs.append(do_fft(exchanged[f - 1]))
                outs.append(do_fft(exchanged[-1]))
            with spans.stage(at), spans.kind("decode"):
                out = jnp.stack(outs)
            return out, fft_st is not None, stats

        if fft_st is not None and method == "pipelined" and chunks > 1:
            res = _exchange_then_fft(block, ex, fft_st, mid, after,
                                     chunks=chunks, comm_dtype=comm_dtype,
                                     exchange_impl=ex_impl, impl=impl,
                                     sign=sign, at=at, nbatch=nbatch, guard=guard)
            block, stats = res if guard else (res, None)
            return block, True, stats
        with spans.stage(at):
            res = exchange_shard(block, ex.v, ex.w, ex.group, method=method,
                                 chunks=chunks, comm_dtype=comm_dtype,
                                 impl=ex_impl, nbatch=nbatch, guard=guard)
        block, stats = res if guard else (res, None)
        if fft_st is not None:
            block = _fft_padded_axis(block, fft_st, mid, after, impl=impl,
                                     sign=sign, nbatch=nbatch, at=at + 1)
        return block, fft_st is not None, stats


def _exchange_then_fft(block, ex: ExchangeStage, fft_st: FFTStage,
                       mid: Pencil, after: Pencil, *, chunks, impl, sign, at: int,
                       comm_dtype=None, exchange_impl="jnp", nbatch=0,
                       guard=False):
    """Pipelined exchange fused with the next stage's 1-D FFT: issue the
    per-slice all-to-alls interleaved with the per-slice transforms.  Each
    slice is a disjoint v-subrange of the fused output, so slicing commutes
    with the FFT along ``w`` and the concat reproduces the unpipelined
    result (bitwise for lossless ``comm_dtype``, to the codec's error bound
    for bf16/int8 since slices quantize independently); the payoff is that
    XLA may run slice i+1's collective DMA under slice i's FFT compute.
    With ``nbatch=1`` each slice carries every field's sub-range.  The
    exchange is stage ``at``, the FFT stage ``at + 1``; the concatenation
    of the slices is the exchange's decode."""
    with spans.stage(at):
        res = exchange_shard_sliced(block, ex.v, ex.w, ex.group, chunks=chunks,
                                    comm_dtype=comm_dtype, impl=exchange_impl,
                                    nbatch=nbatch, guard=guard)
    pieces, stats = res if guard else (res, None)
    out = [_fft_padded_axis(p, fft_st, mid, after, impl=impl, sign=sign, nbatch=nbatch,
                            at=at + 1)
           for p in pieces]
    if len(out) == 1:
        out = out[0]
    else:
        with spans.stage(at), spans.kind("decode"):
            out = jnp.concatenate(out, axis=ex.v + nbatch)
    return (out, stats) if guard else out


def _fft_padded_axis(block, st: FFTStage, cur: Pencil, nxt: Pencil, *, impl, sign, at: int,
                     nbatch=0):
    """One transform stage along a locally-complete axis, honouring padding:
    slice to the logical extent, transform at the true length (pruning
    keep/zero-scatter folded in by :func:`fftcore.local_transform`), re-pad.
    Because the slice/pad bracket the transform inside the shard function,
    XLA fuses them with the adjacent exchange's unpack — dealiasing rides
    the existing exchange path instead of costing separate HBM passes.
    ``nbatch`` leading batch axes transform vectorized (``st.axis`` stays
    field-relative, matching the pencil traces).  ``at`` is the stage's
    index in the executed plan: the slice and pad are its ``repad``, the
    transform's own scopes are numbered with it."""
    axis = st.axis + nbatch
    n_log_in = cur.logical[st.axis]
    if block.shape[axis] != cur.physical[st.axis]:
        raise AssertionError(
            f"axis {st.axis}: local extent {block.shape[axis]} != physical {cur.physical[st.axis]}"
        )
    with spans.stage(at):
        if n_log_in != block.shape[axis]:
            with spans.kind("repad"):
                block = jax.lax.slice_in_dim(block, 0, n_log_in, axis=axis)
        block = fftcore.local_transform(block, st.axis, sign, st.spec, n=st.n,
                                        impl=impl, nbatch=nbatch)
        n_phys_out = nxt.physical[st.axis]
        if block.shape[axis] != n_phys_out:
            pads = [(0, 0)] * block.ndim
            pads[axis] = (0, n_phys_out - block.shape[axis])
            with spans.kind("repad"):
                block = jnp.pad(block, pads)
    return block
