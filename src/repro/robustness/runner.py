"""Guarded plan execution: evaluate the fused guards, degrade, retry.

:func:`run_guarded` is what ``ParallelFFT.forward/backward`` (and the
``_many`` variants) route through when ``guard != "off"``.  One attempt =
build/reuse the guarded executor for the current schedule, run it, sum
the per-shard guard-stat partials it returned, and evaluate them into a
:class:`~.health.HealthReport`.

``guard="strict"``: any tripped guard or failed execution raises
:class:`GuardError` (carrying the report) — the caller gets a structured
error, never a silently corrupted spectrum.

``guard="degrade"``: the runner walks the degradation ladder and
re-executes, bounded by :data:`MAX_ATTEMPTS`:

* a *tripped stage* widens that stage's wire payload one rung
  (int8 → bf16 → complex64), then drops a fused Pallas exchange kernel
  back to the jnp reference impl (pallas → jnp), before falling back
  through the engines (pipelined → fused → traditional);
* a *global* trip (Parseval, non-finite output) degrades every stage;
* a *failed execution* of a ``method="auto"`` plan quarantines the cache
  entry that produced the schedule (schema-v5 per-entry ``bad`` mark, see
  :func:`repro.core.tuner.quarantine`) and retunes, capped at
  :data:`~repro.core.tuner.MAX_QUARANTINE_RETUNES`; explicit-method plans
  degrade the whole schedule instead.

Every transition is logged on the ``repro.robustness`` logger and recorded
in the final report's ``transitions``; a ladder with no rung left raises
:class:`GuardError` — zero silent-corruption outcomes either way.
"""

from __future__ import annotations

import logging

import numpy as np

from repro.robustness import faults, health

log = logging.getLogger("repro.robustness")

#: hard cap on executions per guarded call (ladder depth is at most
#: 2 payload rungs + 1 impl rung + 2 engine rungs; +headroom for retunes)
MAX_ATTEMPTS = 8

#: one-rung payload widening (lossier -> less lossy)
DTYPE_LADDER = {"int8": "bf16", "bf16": "complex64"}

#: engine fallback order once the payload is lossless
ENGINE_LADDER = {"pipelined": "fused", "fused": "traditional"}


class GuardError(RuntimeError):
    """A guarded execution could not produce a clean result.  ``report``
    carries the last :class:`~.health.HealthReport` (None when the failure
    happened before any execution completed)."""

    def __init__(self, message: str, report=None):
        super().__init__(message)
        self.report = report


def degrade_entry(entry):
    """One ladder rung for a :class:`~repro.core.planconfig.StageEntry`:
    widen the payload, then drop a fused pallas kernel back to the jnp
    reference, then fall back through the engines; None when the entry is
    already at the bottom (traditional @ complex64 @ jnp)."""
    from repro.core.planconfig import StageEntry

    e = StageEntry.make(entry)
    if e.comm_dtype in DTYPE_LADDER:
        return e._replace(comm_dtype=DTYPE_LADDER[e.comm_dtype])
    if e.impl == "pallas":
        return e._replace(impl="jnp")
    if e.method in ENGINE_LADDER:
        return e._replace(method=ENGINE_LADDER[e.method], chunks=1)
    return None


def degrade_schedule(schedule, stages=None):
    """Degrade the entries at ``stages`` (all when None) one rung each;
    returns the new schedule, or None when no targeted entry has a rung
    left (ladder exhausted)."""
    target = set(stages) if stages else set(range(len(schedule)))
    out, moved = [], False
    for i, e in enumerate(schedule):
        d = degrade_entry(e) if i in target else None
        if d is not None:
            out.append(d)
            moved = True
        else:
            out.append(e)
    return tuple(out) if moved else None


def _resolve_schedule(plan, nfields: int):
    from repro.core.planconfig import as_schedule

    sched = plan.batched_schedule(nfields) if nfields > 1 else plan.schedule
    return as_schedule(sched)


def _quarantine_and_retune(plan, nfields: int, err) -> int:
    """Mark the plan's current cache entry bad, drop every in-process copy
    of the schedule it produced, and return the entry's total quarantine
    count (the retune happens lazily at the next schedule resolve)."""
    from repro.core import tuner

    path = plan.config.tuner_cache or tuner.default_cache_path()
    key = tuner.plan_key(plan, nfields=nfields)
    n = tuner.quarantine(path, key, repr(err)[:300])
    plan.__dict__.pop("schedule", None)  # cached_property reset
    plan._batched_sched_memo.pop(nfields, None)
    return n


def run_guarded(plan, xpad, direction: str, nfields: int = 1, *,
                schedule=None):
    """Execute ``plan`` on the padded block ``xpad`` under its guard mode;
    returns ``(ypad, HealthReport)``.  See the module docstring for the
    strict/degrade semantics.

    ``schedule`` forces the starting schedule instead of resolving the
    plan's own — the serving engine's circuit breaker routes requests
    through here with a pre-degraded (bottom-ladder) schedule while the
    quarantined entry retunes off the hot path.  A forced schedule that
    fails walks the degradation ladder from where it stands; it never
    quarantines the tuner cache (it is not the cache's schedule)."""
    from repro.core import tuner

    strict = plan.config.guard == "strict"
    forced = schedule is not None
    if forced:
        from repro.core.planconfig import as_schedule

        schedule = as_schedule(schedule)
    transitions: list[dict] = []
    report = None
    for attempt in range(1, MAX_ATTEMPTS + 1):
        err = None
        try:
            if schedule is None:
                schedule = _resolve_schedule(plan, nfields)
            fn = plan.executor(direction, nfields if nfields > 1 else None,
                               schedule=schedule)
            y, raw = fn(xpad)
            # per-shard partial vectors; summing them happens here on the
            # host so the compiled executor stays collective-free
            stats = health.unpack_partials(np.asarray(raw), len(schedule))
        except faults.FaultInjected as e:
            err = e
        except GuardError:
            raise
        except Exception as e:  # genuine compile/resolve/run failure
            err = e
        if err is not None:
            log.warning("guarded %s execution failed (attempt %d): %r",
                        direction, attempt, err)
            if strict:
                raise GuardError(
                    f"schedule failed to execute: {err!r}") from err
            if plan.config.method == "auto":
                n = _quarantine_and_retune(plan, nfields, err)
                if n > tuner.MAX_QUARANTINE_RETUNES:
                    raise GuardError(
                        f"cache entry quarantined {n}x and still failing: "
                        f"{err!r}") from err
                transitions.append({"attempt": attempt, "kind": "retune",
                                    "quarantines": n,
                                    "reason": repr(err)[:200]})
                log.warning("quarantined tuner cache entry (count %d); "
                            "retuning", n)
                schedule = None
                continue
            new = degrade_schedule(schedule)
            if new is None:
                raise GuardError(
                    f"degradation ladder exhausted after execution failure: "
                    f"{err!r}") from err
            transitions.append({"attempt": attempt, "kind": "degrade",
                                "from": [list(e) for e in schedule],
                                "to": [list(e) for e in new],
                                "reason": repr(err)[:200]})
            log.warning("degrading schedule after failure: %s -> %s",
                        schedule, new)
            schedule = new
            continue

        report = health.build_report(
            plan, direction=direction, nfields=nfields, schedule=schedule,
            stats=stats, guard=plan.config.guard, transitions=transitions,
            attempts=attempt,
            fired_faults=tuple(faults._ACTIVE.fired) if faults._ACTIVE else ())
        if report.ok:
            if transitions:
                log.info("guarded %s recovered after %d attempt(s): %s",
                         direction, attempt,
                         [t["kind"] for t in transitions])
            return y, report
        if strict:
            raise GuardError(
                f"runtime guard tripped: {report.tripped}", report)
        stages = (None if report.has_global_trip
                  else report.tripped_stage_indices())
        if stages and direction == "backward":
            # report indices are execution-order; the schedule is forward-order
            stages = tuple(len(schedule) - 1 - i for i in stages)
        new = degrade_schedule(schedule, stages)
        if new is None:
            raise GuardError(
                f"degradation ladder exhausted; still tripping "
                f"{report.tripped}", report)
        transitions.append({"attempt": attempt, "kind": "degrade",
                            "tripped": list(report.tripped),
                            "from": [list(e) for e in schedule],
                            "to": [list(e) for e in new]})
        log.warning("guard tripped %s; degrading %s -> %s",
                    report.tripped, schedule, new)
        schedule = new
    raise GuardError(f"guarded execution hit the {MAX_ATTEMPTS}-attempt cap",
                     report)
