"""The resilient spectral serving engine.

:class:`SpectralServer` turns :class:`~repro.core.pfft.ParallelFFT` into a
long-running service.  One dispatch worker drains a bounded admission queue;
requests for the same ``(shape, direction)`` are **coalesced** into one
batched ``forward_many``/``backward_many`` invocation (PR 4's engine: one
collective per exchange stage for the whole group instead of one per
request).  Every request rides the full resilience lifecycle:

admission    — the queue is bounded (``max_queue``); overload is *shed* at
               submit time with a structured ``shed`` outcome, never queued
               into unbounded latency.
deadline     — per-request; the future self-resolves ``deadline-exceeded``
               so a wedged execution is observable (``late_results``) but
               can never hang a caller.
retry        — transient failures (injected crashes, non-guard exceptions)
               re-dispatch with exponential backoff + deterministic jitter,
               bounded by ``max_retries`` and the group's earliest deadline.
breaker      — terminal ``GuardError`` failures count against the plan's
               circuit breaker; a trip quarantines the schedule in the
               shared tuner DB (:func:`repro.core.tuner.quarantine`) and
               kicks a *background* retune (``plan.warm`` off the hot
               path), while requests keep flowing through the bottom of the
               degradation ladder (:func:`~repro.serve.registry.
               fallback_schedule`) as ``degraded`` / ``circuit-open``.

Fault hooks (:mod:`repro.robustness.faults`) are called at fixed points —
``tap_serve_execute`` before every execution attempt, ``tap_serve_cache``
against the shared schedule DB per dispatch — so the whole lifecycle is
deterministically chaos-testable.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from dataclasses import dataclass

from repro.core import spans
from repro.robustness import faults
from repro.robustness.runner import GuardError, run_guarded
from repro.serve.lifecycle import (
    TRIP_CIRCUIT, TRIP_SHED, TRIP_TIMEOUT,
    Outcome, Request, RequestFuture, backoff_s, next_request_id,
)
from repro.serve.registry import PlanRegistry, fallback_schedule

log = logging.getLogger("repro.serve")

_COUNTERS = ("submitted", "ok", "degraded", "shed", "deadline_exceeded",
             "error", "retries", "coalesced_batches", "batched_requests",
             "fallback_served", "late_results", "expired_before_dispatch",
             "retunes")


@dataclass
class ServeConfig:
    """Engine knobs (plan-level knobs live in the PlanConfig template)."""

    capacity: int = 8              #: LRU plan slots
    max_queue: int = 64            #: admission bound; beyond -> shed
    max_batch: int = 8             #: coalescing cap per dispatch
    deadline_s: float = 30.0       #: default per-request deadline
    grace_s: float = 0.25          #: result() slack past the deadline
    max_retries: int = 2           #: transient re-dispatches per group
    backoff_base_s: float = 0.05
    backoff_cap_s: float = 1.0
    breaker_threshold: int = 3     #: consecutive GuardErrors to trip
    breaker_cooldown_s: float = 5.0
    warm_directions: tuple = ("forward",)
    warm_nfields: int = 1


class SpectralServer:
    """Long-running spectral FFT service over one device mesh.

    ``submit`` is thread-safe and non-blocking (shed rather than block);
    results come back through :class:`~repro.serve.lifecycle.RequestFuture`.
    Plans are forced to ``guard="degrade"`` unless the template already
    asks for ``"strict"`` — an unguarded plan has no ladder to serve
    through, which would void the engine's no-silent-corruption contract.
    """

    def __init__(self, mesh, grid, *, plan_config=None,
                 config: ServeConfig | None = None):
        from repro.core.planconfig import PlanConfig

        self.config = config if config is not None else ServeConfig()
        pc = plan_config if plan_config is not None else PlanConfig()
        if pc.guard == "off":
            pc = pc.replace(guard="degrade")
        self.plan_config = pc
        self.registry = PlanRegistry(
            mesh, grid, config=pc, capacity=self.config.capacity,
            warm_directions=self.config.warm_directions,
            warm_nfields=self.config.warm_nfields,
            breaker_threshold=self.config.breaker_threshold,
            breaker_cooldown_s=self.config.breaker_cooldown_s)
        self._queue: deque[Request] = deque()
        self._cv = threading.Condition()
        self._closed = False
        self._stats = dict.fromkeys(_COUNTERS, 0)
        self._stats_lock = threading.Lock()
        self._retune_threads: list[threading.Thread] = []
        self._worker = threading.Thread(target=self._worker_loop,
                                        name="repro-serve-dispatch",
                                        daemon=True)
        self._worker.start()

    # -- public surface ------------------------------------------------------

    def submit(self, x, *, direction: str = "forward",
               deadline_s: float | None = None) -> RequestFuture:
        """Admit one field for transform; returns its future immediately.
        A full queue sheds the request (structured ``shed`` outcome) —
        overload degrades throughput, never latency honesty."""
        if direction not in ("forward", "backward"):
            raise ValueError(f"unknown direction {direction!r}")
        deadline_s = self.config.deadline_s if deadline_s is None else deadline_s
        rid = next_request_id()
        fut = RequestFuture(rid, time.monotonic() + deadline_s)
        with self._cv:
            if self._closed:
                raise RuntimeError("server is closed")
            self._bump("submitted")
            if len(self._queue) >= self.config.max_queue:
                fut.resolve(Outcome("shed", rid, trip=TRIP_SHED))
                self._bump("shed")
                return fut
            self._queue.append(Request(x=x, shape=tuple(x.shape),
                                       direction=direction, future=fut))
            self._cv.notify()
        return fut

    def stats(self) -> dict:
        with self._stats_lock:
            out = dict(self._stats)
        out["queue_depth"] = len(self._queue)
        out["registry"] = self.registry.stats()
        out["compile"] = spans.compile_totals()
        return out

    def drain(self, timeout: float = 60.0) -> bool:
        """Wait until the queue is empty and the worker is idle."""
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            with self._cv:
                if not self._queue and not self._dispatching:
                    return True
            time.sleep(0.01)
        return False

    def close(self, timeout: float = 30.0):
        """Stop admitting, drain in-flight work, join the worker."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._worker.join(timeout)
        for t in self._retune_threads:
            t.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- dispatch ------------------------------------------------------------

    _dispatching = False

    def _bump(self, counter: str, n: int = 1):
        with self._stats_lock:
            self._stats[counter] += n

    def _worker_loop(self):
        while True:
            with self._cv:
                while not self._queue and not self._closed:
                    self._cv.wait(0.05)
                if not self._queue:
                    if self._closed:
                        return
                    continue
                group = self._take_group_locked()
                self._dispatching = True
            try:
                self._execute_group(group)
            except BaseException as e:  # the worker must never die silently
                log.exception("dispatch group failed terminally: %r", e)
                for r in group:
                    self._resolve(r, Outcome("error", r.future.request_id,
                                             error=repr(e)[:300],
                                             batched=len(group)))
            finally:
                with self._cv:
                    self._dispatching = False

    def _take_group_locked(self) -> list[Request]:
        """Pop the head request plus every queued request with the same
        ``(shape, direction)``, up to ``max_batch`` — the coalescer."""
        head = self._queue.popleft()
        group = [head]
        rest = deque()
        while self._queue and len(group) < self.config.max_batch:
            r = self._queue.popleft()
            (group if r.group_key == head.group_key else rest).append(r)
        self._queue.extendleft(reversed(rest))
        return group

    def _resolve(self, req: Request, outcome: Outcome):
        if req.future.resolve(outcome):
            self._bump(outcome.status.replace("-", "_"))
        else:
            self._bump("late_results")

    def _tuner_path(self):
        from repro.core import tuner

        return self.plan_config.tuner_cache or tuner.default_cache_path()

    def _execute_group(self, group: list[Request]):
        import jax
        import jax.numpy as jnp

        # mid-flight cache-corruption fault point: the shared schedule DB
        # may be scribbled on between any two dispatches
        faults.tap_serve_cache(self._tuner_path())

        now = time.monotonic()
        reqs = []
        for r in group:
            if r.future.deadline <= now:
                self._bump("expired_before_dispatch")
                self._resolve(r, Outcome("deadline-exceeded",
                                         r.future.request_id,
                                         trip=TRIP_TIMEOUT))
            else:
                reqs.append(r)
        if not reqs:
            return
        direction = reqs[0].direction
        if len(reqs) > 1:
            self._bump("coalesced_batches")
            self._bump("batched_requests", len(reqs))
        try:
            key, plan = self.registry.get(reqs[0].shape)
        except Exception as e:
            for r in reqs:
                self._resolve(r, Outcome("error", r.future.request_id,
                                         error=f"plan build failed: {e!r}"[:300],
                                         batched=len(reqs)))
            return
        breaker = self.registry.breaker(key)
        stacked = jnp.stack([jnp.asarray(r.x) for r in reqs])
        earliest = min(r.future.deadline for r in reqs)

        attempt = 0
        while True:
            if not breaker.allow():
                self._serve_fallback(reqs, plan, stacked, direction,
                                     trip=TRIP_CIRCUIT, retries=attempt)
                return
            try:
                faults.tap_serve_execute()
                out = plan._apply_many(stacked, direction)
                y, report = out if isinstance(out, tuple) else (out, None)
                jax.block_until_ready(y)
            except GuardError as e:
                tripped = breaker.record_failure()
                if tripped:
                    self._on_trip(plan, key, direction, len(reqs), e)
                self._serve_fallback(reqs, plan, stacked, direction,
                                     trip=(TRIP_CIRCUIT if tripped
                                           else "guard-error"),
                                     retries=attempt, cause=e)
                return
            except Exception as e:  # transient: injected crash, XLA hiccup
                attempt += 1
                self._bump("retries")
                wait = backoff_s(reqs[0].future.request_id, attempt,
                                 base=self.config.backoff_base_s,
                                 cap=self.config.backoff_cap_s)
                out_of_time = time.monotonic() + wait >= earliest
                if attempt > self.config.max_retries or out_of_time:
                    breaker.record_failure()
                    status = ("deadline-exceeded" if out_of_time
                              and attempt <= self.config.max_retries
                              else "error")
                    for r in reqs:
                        self._resolve(r, Outcome(
                            status, r.future.request_id,
                            trip=TRIP_TIMEOUT if status == "deadline-exceeded"
                            else "retries-exhausted",
                            error=repr(e)[:300], retries=attempt,
                            batched=len(reqs)))
                    return
                log.warning("transient failure (attempt %d), retrying in "
                            "%.3fs: %r", attempt, wait, e)
                time.sleep(wait)
                continue
            break  # success

        breaker.record_success()
        transitions = len(report.transitions) if report is not None else 0
        status = "degraded" if transitions else "ok"
        trip = "guard-degrade" if transitions else None
        for i, r in enumerate(reqs):
            self._resolve(r, Outcome(status, r.future.request_id, value=y[i],
                                     trip=trip, retries=attempt,
                                     transitions=transitions,
                                     batched=len(reqs)))

    def _serve_fallback(self, reqs, plan, stacked, direction, *, trip,
                        retries=0, cause=None):
        """Serve a group through the bottom of the degradation ladder —
        the breaker-open (or ladder-exhausted) path.  Still guarded: a
        fallback that fails too yields structured errors, not silence."""
        import jax

        from repro.core.pencil import pad_global, unpad_global

        self._bump("fallback_served", len(reqs))
        try:
            faults.tap_serve_execute()
            if direction == "forward":
                in_pen, out_pen = plan.input_pencil, plan.output_pencil
                dt = plan.input_dtype
            else:
                in_pen, out_pen = plan.output_pencil, plan.input_pencil
                dt = plan.spectral_dtype
            sched = fallback_schedule(plan)
            xpad = pad_global(stacked.astype(dt), in_pen, nbatch=1)
            if len(reqs) == 1:
                y, report = run_guarded(plan, xpad[0], direction,
                                        schedule=sched)
                y = y[None]
            else:
                y, report = run_guarded(plan, xpad, direction,
                                        nfields=len(reqs), schedule=sched)
            jax.block_until_ready(y)
            y = unpad_global(y, out_pen, nbatch=1)
        except Exception as e:
            log.warning("fallback execution failed: %r (primary cause: %r)",
                        e, cause)
            err = repr(e)[:200] + (f" [primary: {cause!r}]"[:100]
                                   if cause is not None else "")
            for r in reqs:
                self._resolve(r, Outcome("error", r.future.request_id,
                                         trip=trip, error=err,
                                         retries=retries, batched=len(reqs)))
            return
        transitions = len(report.transitions) if report is not None else 0
        for i, r in enumerate(reqs):
            self._resolve(r, Outcome("degraded", r.future.request_id,
                                     value=y[i], trip=trip, retries=retries,
                                     transitions=transitions,
                                     batched=len(reqs)))

    def _on_trip(self, plan, key, direction, nfields, err):
        """Breaker just tripped: quarantine the failing schedule in the
        shared DB and retune + re-warm in the background, off the hot
        path (requests keep flowing through the fallback meanwhile)."""
        from repro.robustness import runner

        log.warning("circuit breaker tripped for plan %s...: %r",
                    key[:60], err)
        if plan.config.method == "auto":
            try:
                runner._quarantine_and_retune(
                    plan, nfields if nfields > 1 else 1, err)
            except Exception as qe:  # pragma: no cover - quarantine best-effort
                log.warning("quarantine failed: %r", qe)

        def _retune():
            try:
                plan.warm((direction,),
                          nfields=nfields if nfields > 1 else 1)
                self._bump("retunes")
                log.info("background retune/rewarm complete for %s...",
                         key[:60])
            except Exception as re_:  # pragma: no cover - retune best-effort
                log.warning("background retune failed: %r", re_)

        t = threading.Thread(target=_retune, name="repro-serve-retune",
                             daemon=True)
        self._retune_threads.append(t)
        t.start()
