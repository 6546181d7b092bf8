"""Runtime health: fused guard statistics and the HealthReport.

Two halves:

* **Traced guard ops** (:func:`output_probe`, :func:`payload_stats`,
  :func:`block_energy`, :func:`zero_stats`, :func:`add_stats`,
  :func:`pack_stats`) — reductions the plan executor runs when
  ``ParallelFFT(guard != "off")``, sized so the lossless hot path stays
  within a few percent of the unguarded plan:

  - always: the :func:`output_probe`, a single-plane sum that witnesses
    any non-finite value the execution produced (each 1-D transform mixes
    every input of a line into each output mode, so NaN/Inf anywhere
    upstream of the final FFT stage reaches the probe plane) at ~1/n the
    cost of a full scan;
  - only for schedules with lossy wire stages (:func:`schedule_is_lossy`):
    the block-energy Parseval bracket (full reductions before/after the
    plan — lossy codecs can corrupt *finitely*, e.g. a bad int8 scale, so
    an energy-conservation check is required there), per-stage non-finite
    counts over bf16 payloads, and the int8 saturation count (piggybacked
    on the codec's clip, see :func:`repro.core.quant.quantize_int8`).

  Lossless (complex64) stages carry no per-stage scan — their only
  corruption mode is non-finite values, which the probe catches globally.
  The executor emits NO collective for the stats either — each shard
  returns its local packed vector and the runner sums the partials on the
  host, keeping the guarded hot path free of extra all-reduces.  These
  ops live in this module so planlint's source attribution can prove
  they are present exactly when guarding is on (PLAN008): guard="off"
  compiles to the bit-identical unguarded jaxpr.

* **Host-side evaluation** (:func:`unpack_partials`,
  :func:`build_report`) — sums the per-shard stat vectors one execution
  produced and turns them into a :class:`HealthReport`: per-stage
  :class:`StageHealth` rows, trip codes, and the Parseval relative error
  where it applies (all-c2c plans, where energy is conserved up to the
  unnormalized-FFT factor ``prod(shape)``).

This module must not import :mod:`repro.core` at module scope (the plan
executor imports it); the one plan-shape helper, and the ``guard`` scope
that names the traced guard ops in the program, do so lazily.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np
from jax import lax

#: guard modes ParallelFFT accepts
GUARD_MODES = ("off", "strict", "degrade")

#: int8 saturation fraction above which a stage trips (per-block max-abs
#: scaling saturates ~1 element per block in healthy runs; a meaningful
#: fraction of the payload at ±127 means the dynamic range collapsed)
SAT_FRACTION_TRIP = 0.05

#: per-stage Parseval tolerance contribution by wire payload (the lossy
#: codecs' documented round-trip error bounds, with headroom)
PARSEVAL_TOL = {"complex64": 1e-3, "bf16": 5e-2, "int8": 2e-1}


# ---------------------------------------------------------------------------
# traced guard ops (run inside shard_map; keep them in THIS module so
# planlint attributes their eqns to robustness/health.py)
# ---------------------------------------------------------------------------


def _guard_scope(fn):
    """Run ``fn`` under the plan's ``guard`` scope (:mod:`repro.core.spans`)."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        from repro.core import spans

        with spans.kind("guard"):
            return fn(*args, **kwargs)
    return scoped


@_guard_scope
def count_nonfinite(x) -> jnp.ndarray:
    """f32 scalar count of non-finite elements (complex: either part)."""
    return jnp.sum(~jnp.isfinite(x), dtype=jnp.float32)


@_guard_scope
def payload_stats(x) -> dict:
    """Guard stats for a bf16 exchange payload: non-finite count only
    (saturation is an int8-codec concept; the codec reports its own)."""
    return {"nonfinite": count_nonfinite(x), "saturated": jnp.zeros((), jnp.float32)}


@_guard_scope
def output_probe(block, axis: int | None) -> jnp.ndarray:
    """Near-free non-finite detector for the executor's output block: the
    sum over the index-0 plane along the final FFT stage's ``axis``.

    Every 1-D transform the executor runs (c2c/r2c/DCT/DST, pruned or
    not) mixes *all* inputs of a line into each retained output mode, so
    a single non-finite element anywhere upstream of the last FFT stage
    contaminates that stage's entire transform line.  The index-0 plane
    intersects every such line, so its sum goes NaN/Inf iff the execution
    produced any non-finite value — at ~1/n the cost of a full-block
    scan, which is what keeps the guarded lossless hot path under the
    overhead budget.  ``axis=None`` (a plan whose last stage is not an
    FFT — none of the current plan shapes) falls back to summing the
    whole block."""
    plane = block if axis is None else lax.index_in_dim(block, 0, axis=axis,
                                                        keepdims=False)
    s = jnp.sum(plane)
    if jnp.iscomplexobj(s):
        s = jnp.real(s) + jnp.imag(s)
    return s.astype(jnp.float32)


@_guard_scope
def block_energy(x) -> jnp.ndarray:
    """f32 scalar sum |x|^2 over one shard (zero padding contributes 0, so
    padded and logical blocks have identical energy).  Computed as
    ``re^2 + im^2`` rather than ``abs(x)^2`` — complex abs lowers to a
    per-element hypot (libm sqrt) on CPU, several times the cost of the
    two multiplies this needs."""
    if jnp.iscomplexobj(x):
        r, i = jnp.real(x), jnp.imag(x)
        return (jnp.sum(r * r) + jnp.sum(i * i)).astype(jnp.float32)
    x = x.astype(jnp.float32)
    return jnp.sum(x * x)


@_guard_scope
def zero_stats() -> dict:
    return {"nonfinite": jnp.zeros((), jnp.float32),
            "saturated": jnp.zeros((), jnp.float32)}


@_guard_scope
def add_stats(a: dict, b: dict) -> dict:
    return {k: a[k] + b[k] for k in a}


@_guard_scope
def pack_stats(per_stage: list, energy_in, energy_out, probe) -> jnp.ndarray:
    """Pack one shard's guard stats into the executor's flat f32 output
    vector ``[energy_in, energy_out, probe, nonfinite_0..S-1,
    saturated_0..S-1]`` (``S`` exchange stages).  One vector per shard, no
    collective: the runner gathers the shards and :func:`unpack_partials`
    sums them.  Lives here (not in the executor) so the concatenate it
    emits is attributed to robustness/ — planlint must not count it
    against the exchange engine's realignment contract (PLAN004)."""
    parts = [jnp.stack([energy_in, energy_out, probe])]
    if per_stage:
        parts.append(jnp.stack([s["nonfinite"] for s in per_stage]))
        parts.append(jnp.stack([s["saturated"] for s in per_stage]))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def unpack_partials(raw, nstages: int) -> dict:
    """Sum the per-shard packed stat vectors (host side, outside the
    compiled hot path) back into the stats dict :func:`build_report`
    evaluates.  ``raw`` is the executor's stats output: the shard-local
    vectors concatenated along axis 0 by the sharded out_spec."""
    width = 3 + 2 * nstages
    vec = np.asarray(raw, np.float64).reshape(-1, width).sum(axis=0)
    return {"energy_in": vec[0], "energy_out": vec[1], "probe": vec[2],
            "nonfinite": vec[3:3 + nstages],
            "saturated": vec[3 + nstages:]}


def schedule_is_lossy(entries) -> bool:
    """True when any schedule entry ships a lossy wire payload.  The full
    Parseval energy bracket only runs for such schedules: lossless wire is
    bit-exact, so its only corruption mode is non-finite values — which
    :func:`output_probe` catches without the two full-block reductions."""
    return any(e[2] in ("bf16", "int8") for e in entries)


# ---------------------------------------------------------------------------
# host-side report
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StageHealth:
    """One exchange stage's guard outcome (counts are global: summed over
    every shard's partial stats).  Lossless (complex64) stages always show
    zero counts — their corruption surfaces as the global
    ``output:nonfinite``/``parseval`` trips instead."""

    stage: int
    method: str
    comm_dtype: str
    nonfinite: int
    saturated: int
    elems: int  # payload elements the counters ran over (all ranks)
    tripped: tuple[str, ...] = ()

    @property
    def sat_fraction(self) -> float:
        return self.saturated / max(self.elems, 1)

    def to_dict(self) -> dict:
        return {"stage": self.stage, "method": self.method,
                "comm_dtype": self.comm_dtype, "nonfinite": self.nonfinite,
                "saturated": self.saturated, "elems": self.elems,
                "sat_fraction": self.sat_fraction,
                "tripped": list(self.tripped)}


@dataclass(frozen=True)
class HealthReport:
    """Guard outcome of one guarded plan execution.

    ``tripped`` collects every trip code: per-stage ``"stage{i}:nonfinite"``
    / ``"stage{i}:saturation"``, plus the global ``"input:nonfinite"``,
    ``"output:nonfinite"`` and ``"parseval"``.  ``energy_in`` /
    ``energy_out`` / ``parseval_rel_err`` are None for all-lossless
    schedules — there the always-on :func:`output_probe` is the (global)
    corruption detector and the two full-block energy reductions are not
    paid (see :func:`schedule_is_lossy`).  ``transitions`` records every
    degradation-ladder step the runner took to produce this (clean)
    result; ``attempts`` is the execution count including the final one.
    """

    guard: str
    direction: str
    nfields: int
    schedule: tuple
    stages: tuple[StageHealth, ...]
    energy_in: float | None
    energy_out: float | None
    parseval_rel_err: float | None
    parseval_tol: float | None
    tripped: tuple[str, ...]
    transitions: tuple = ()
    attempts: int = 1
    fired_faults: tuple = field(default=(), compare=False)

    @property
    def ok(self) -> bool:
        return not self.tripped

    def tripped_stage_indices(self) -> tuple[int, ...]:
        """Exchange-stage indices named by per-stage trip codes (empty when
        only global codes tripped)."""
        out = []
        for code in self.tripped:
            if code.startswith("stage") and ":" in code:
                out.append(int(code.split(":")[0][len("stage"):]))
        return tuple(sorted(set(out)))

    @property
    def has_global_trip(self) -> bool:
        return any(not c.startswith("stage") for c in self.tripped)

    def to_dict(self) -> dict:
        return {
            "guard": self.guard, "direction": self.direction,
            "nfields": self.nfields,
            "schedule": [list(e) for e in self.schedule],
            "stages": [s.to_dict() for s in self.stages],
            "energy_in": self.energy_in, "energy_out": self.energy_out,
            "parseval_rel_err": self.parseval_rel_err,
            "parseval_tol": self.parseval_tol,
            "tripped": list(self.tripped),
            "transitions": [dict(t) for t in self.transitions],
            "attempts": self.attempts,
        }


def _walk(plan, direction: str):
    """(stages, pencils, dtypes) in execution order for ``direction``."""
    from repro.core.pfft import _reverse_plan

    if direction == "forward":
        return plan.stages, plan.pencil_trace, plan.dtype_trace
    stages, pencils = _reverse_plan(plan.stages, plan.pencil_trace)
    return stages, pencils, plan.dtype_trace[::-1]


def parseval_factor(plan, direction: str) -> float | None:
    """Expected ``energy_out / energy_in`` ratio, or None when the plan
    does not conserve energy analytically (any non-c2c axis: r2c halves the
    stored spectrum, pruning drops modes, DCT/DST carry other norms).  The
    repo's unnormalized forward multiplies energy by ``prod(shape)``; the
    normalized backward divides it back out."""
    if any(sp.kind != "c2c" for sp in plan.transforms):
        return None
    n = float(math.prod(plan.shape))
    return n if direction == "forward" else 1.0 / n


def build_report(plan, *, direction: str, nfields: int, schedule, stats,
                 guard: str, transitions=(), attempts: int = 1,
                 fired_faults=()) -> HealthReport:
    """Evaluate one execution's summed guard stats into a HealthReport.

    ``stats`` is :func:`unpack_partials`' output: per-exchange-stage
    ``nonfinite``/``saturated`` vectors plus scalar ``energy_in`` /
    ``energy_out``, summed over all shards.  Payload element counts come
    analytically from the pencil/dtype traces — nothing here touches
    devices."""
    from repro.core.pfft import ExchangeStage

    stages, pencils, dtypes = _walk(plan, direction)
    # schedule arrives in forward plan order; stats/stage rows are in
    # execution order, so a backward walk reads it reversed
    entries = list(schedule) if direction == "forward" else list(schedule)[::-1]
    lossy = schedule_is_lossy(entries)
    nonfinite = [float(v) for v in stats["nonfinite"]]
    saturated = [float(v) for v in stats["saturated"]]
    e_in = float(stats["energy_in"])
    e_out = float(stats["energy_out"])
    probe = float(stats.get("probe", 0.0))

    rows: list[StageHealth] = []
    tripped: list[str] = []
    ex_i = 0
    for i, st in enumerate(stages):
        if not isinstance(st, ExchangeStage):
            continue
        method, _, comm_dtype = entries[ex_i][0], entries[ex_i][1], entries[ex_i][2]
        # the codec sees the physical (padded) block as re/im planes; count
        # the same elements the traced reductions saw, across all ranks
        planes = 2 if dtypes[i] == jnp.complex64 else 1
        elems = max(1, nfields) * planes * math.prod(pencils[i].physical)
        codes = []
        if nonfinite[ex_i] > 0:
            codes.append(f"stage{ex_i}:nonfinite")
        if comm_dtype == "int8" and saturated[ex_i] / elems > SAT_FRACTION_TRIP:
            codes.append(f"stage{ex_i}:saturation")
        rows.append(StageHealth(
            stage=ex_i, method=method, comm_dtype=comm_dtype,
            nonfinite=int(nonfinite[ex_i]), saturated=int(saturated[ex_i]),
            elems=elems, tripped=tuple(codes)))
        tripped.extend(codes)
        ex_i += 1

    # the energy bracket only runs for lossy schedules (see
    # schedule_is_lossy); the probe is the always-on output detector
    if lossy and not math.isfinite(e_in):
        tripped.append("input:nonfinite")
    if (lossy and not math.isfinite(e_out)) or not math.isfinite(probe):
        tripped.append("output:nonfinite")

    factor = parseval_factor(plan, direction) if lossy else None
    rel_err = tol = None
    if factor is not None and math.isfinite(e_in) and math.isfinite(e_out):
        want = factor * e_in
        rel_err = abs(e_out - want) / max(want, 1e-30)
        tol = max(1e-3, sum(PARSEVAL_TOL.get(e[2], 1e-3) for e in entries))
        if rel_err > tol:
            tripped.append("parseval")

    return HealthReport(
        guard=guard, direction=direction, nfields=nfields,
        schedule=tuple(tuple(e) for e in entries), stages=tuple(rows),
        energy_in=e_in if lossy else None,
        energy_out=e_out if lossy else None,
        parseval_rel_err=rel_err, parseval_tol=tol, tripped=tuple(tripped),
        transitions=tuple(transitions), attempts=attempts,
        fired_faults=tuple(fired_faults))
