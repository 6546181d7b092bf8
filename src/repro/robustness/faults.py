"""Deterministic fault injection for guarded-execution testing.

:class:`FaultPlan` is a context manager that arms injectors; the plan
executor calls tiny tap functions at fixed points of every exchange stage
(wire buffers after the collective, stage inputs, the int8 codec's scale,
executor build) and each tap perturbs the traced values only while a
matching fault is armed — with no active FaultPlan every tap returns its
input untouched and traces **zero** ops, so ``guard="off"`` artifacts stay
bit-identical (planlint PLAN008 proves it).

Faults target a (stage, engine, codec) triple — any field left ``None``
is a wildcard — which is what makes the degradation ladder testable: a
fault pinned to ``engine="pipelined"`` stops matching once the runner
falls back to ``fused``, so "recovered" means the ladder actually moved
execution off the faulted configuration.

Injectors:

* :meth:`FaultPlan.corrupt_wire` — burst corruption of a received wire
  buffer (exponent bits forced to ones: the payload element becomes
  Inf/NaN; int8 payloads flip a magnitude bit, bounded by the codec's
  error contract — target ``label="scale"`` for a detectable int8 hit).
* :meth:`FaultPlan.nan_input` — a NaN/Inf element in an exchange stage's
  input block.
* :meth:`FaultPlan.saturate` — divides the int8 codec's scale, collapsing
  the dynamic range so the payload clips (trips the saturation counter).
* :meth:`FaultPlan.fail_compile` — raises :class:`FaultInjected` while the
  executor for a matching schedule entry is being built/traced (a
  schedule-compile failure, e.g. of a poisoned cache entry's engine).
* :meth:`FaultPlan.poison_cache` — writes a structurally *valid* tuner
  cache entry naming a schedule the tuner never timed (pair with
  ``fail_compile`` on that schedule's engine to model a cache entry that
  replays but cannot execute).

Injection happens at trace time, so a fault armed while an executor is
first traced persists in that compiled artifact for its cache lifetime —
construct fresh plans inside the ``with FaultPlan()`` block (tests do).

Serve-level injectors (:mod:`repro.serve`): these fire on the *host* side
of the serving engine's request lifecycle — not at trace time — so they
stay deterministic across backends and hit hot (already-compiled)
executors, which trace-time faults cannot:

* :meth:`FaultPlan.slow_collective` — stalls a plan execution for
  ``seconds`` (models a degraded interconnect wedging a collective; the
  dispatch blocks exactly like a slow all-to-all would), exercising the
  deadline machinery.
* :meth:`FaultPlan.executor_crash` — raises :class:`FaultInjected` from a
  plan execution attempt (a crashed backend executor), exercising the
  bounded retry/backoff path.  Defaults to firing once (``times=1``) so a
  retry can observe recovery.
* :meth:`FaultPlan.cache_corruption` — scribbles over the shared schedule
  DB *between* requests (mode ``"garbage"``: unparseable bytes; mode
  ``"truncate"``: an empty file) — the mid-flight corruption another
  crashed replica could leave behind.
* :meth:`FaultPlan.request_burst` — tells the load harness (CLI / soak
  test) to multiply its offered load by ``factor`` for one wave,
  exercising admission control and load shedding.

Every serve-level fault takes ``times`` (default varies per injector;
``None`` = unlimited): the fault disarms itself after firing that many
times, so a bounded injection provably recovers.
"""

from __future__ import annotations

import threading
import time as _time
from contextlib import contextmanager
from dataclasses import dataclass
from pathlib import Path

import jax.numpy as jnp
from jax import lax


class FaultInjected(RuntimeError):
    """Raised at executor build/trace time by an armed compile-failure
    fault (the stand-in for a schedule that cannot compile)."""


@dataclass
class _Fault:
    kind: str                 # corrupt_wire | nan_input | saturate | compile_fail
                              # | slow_collective | executor_crash
                              # | cache_corruption | request_burst
    stage: int | None = None  # exchange index (execution order); None = any
    engine: str | None = None
    codec: str | None = None
    label: str | None = None  # corrupt_wire: "payload" | "scale"
    value: float = 0.0
    times: int | None = None  # max fires before the fault disarms (None = ∞)


#: the armed FaultPlan (module-global: tests arm exactly one plan at a time)
_ACTIVE: "FaultPlan | None" = None

#: trace-time context the executor sets per exchange stage — **per thread**:
#: the serving engine traces its fallback executor concurrently with a
#: background retune thread re-tracing the primary schedule, and a shared
#: dict would leak one thread's (stage, engine, codec) into the other's
#: trace (a bf16-targeted fault would hit a complex64 fallback stage)
_CTX_LOCAL = threading.local()


def _ctx() -> dict:
    if not hasattr(_CTX_LOCAL, "ctx"):
        _CTX_LOCAL.ctx = {"stage": None, "engine": None, "codec": None}
    return _CTX_LOCAL.ctx


class FaultPlan:
    """Armed set of deterministic faults (see module docstring).

    Use as a context manager; injector methods return ``self`` so they
    chain.  ``fired`` records every injection that actually happened (at
    trace time), with the (stage, engine, codec) context it matched.
    """

    def __init__(self):
        self._faults: list[_Fault] = []
        self.fired: list[dict] = []

    # -- injectors ----------------------------------------------------------

    def corrupt_wire(self, *, stage=None, engine=None, codec=None,
                     label="payload"):
        self._faults.append(_Fault("corrupt_wire", stage, engine, codec, label))
        return self

    def nan_input(self, *, stage=None, engine=None, codec=None,
                  value=float("nan")):
        self._faults.append(_Fault("nan_input", stage, engine, codec,
                                   None, value))
        return self

    def saturate(self, *, stage=None, engine=None, factor=64.0):
        self._faults.append(_Fault("saturate", stage, engine, "int8",
                                   None, factor))
        return self

    def fail_compile(self, *, stage=None, engine=None, codec=None):
        self._faults.append(_Fault("compile_fail", stage, engine, codec))
        return self

    # -- serve-level injectors (host-side request lifecycle) ----------------

    def slow_collective(self, *, seconds=1.0, times=None):
        """Stall matching plan executions by ``seconds`` (a wedged/slow
        collective as the serving engine experiences it)."""
        self._faults.append(_Fault("slow_collective", value=seconds,
                                   times=times))
        return self

    def executor_crash(self, *, times=1):
        """Raise :class:`FaultInjected` from ``times`` plan execution
        attempts (a crashed executor; the retry path's test hook)."""
        self._faults.append(_Fault("executor_crash", times=times))
        return self

    def cache_corruption(self, *, mode="garbage", times=1):
        """Corrupt the shared schedule DB between requests: ``"garbage"``
        writes unparseable bytes, ``"truncate"`` empties the file."""
        if mode not in ("garbage", "truncate"):
            raise ValueError(f"unknown cache_corruption mode {mode!r}")
        self._faults.append(_Fault("cache_corruption", label=mode, times=times))
        return self

    def request_burst(self, *, factor=4, times=1):
        """Tell the load harness to multiply its offered load by ``factor``
        for ``times`` waves (admission-control / load-shedding pressure)."""
        self._faults.append(_Fault("request_burst", value=float(factor),
                                   times=times))
        return self

    @staticmethod
    def poison_cache(path, plan, schedule, *, nfields: int = 1) -> str:
        """Write a structurally valid tuner-cache entry for ``plan``'s key
        naming ``schedule`` (which the tuner never timed); returns the key.
        The process's memo for the key is dropped, so the next resolve
        replays the poisoned entry even if this key was resolved before."""
        from repro.core import tuner

        key = tuner.plan_key(plan, nfields=nfields)
        entry = {"schedule": [list(s) for s in schedule],
                 "timings": {"poisoned": {}}}
        tuner.save_cache(path, {key: entry})
        tuner.forget(key)
        return key

    # -- context ------------------------------------------------------------

    def __enter__(self):
        global _ACTIVE
        if _ACTIVE is not None:
            raise RuntimeError("a FaultPlan is already active")
        _ACTIVE = self
        return self

    def __exit__(self, *exc):
        global _ACTIVE
        _ACTIVE = None
        return False


@contextmanager
def stage_context(stage, engine, codec):
    """Executor hook: scope the (stage, engine, codec) the taps match
    against.  Free when no FaultPlan is armed."""
    if _ACTIVE is None:
        yield
        return
    ctx = _ctx()
    prev = dict(ctx)
    ctx.update(stage=stage, engine=engine, codec=codec)
    try:
        yield
    finally:
        ctx.update(prev)


def _matching(kind: str, label: str | None = None):
    if _ACTIVE is None:
        return []
    out = []
    ctx = _ctx()
    for f in _ACTIVE._faults:
        if f.kind != kind:
            continue
        if f.times is not None and f.times <= 0:
            continue  # bounded fault already used up its fires
        if f.stage is not None and f.stage != ctx["stage"]:
            continue
        if f.engine is not None and f.engine != ctx["engine"]:
            continue
        if f.codec is not None and f.codec != ctx["codec"]:
            continue
        if label is not None and f.label is not None and f.label != label:
            continue
        out.append(f)
    return out


def _fire(f: _Fault, **note):
    if f.times is not None:
        f.times -= 1
    _ACTIVE.fired.append({"kind": f.kind, **dict(_ctx()), **note})


# -- taps (each is a no-op tracing zero eqns when nothing matches) ----------


def check_compile(engine: str, codec: str):
    """Raise :class:`FaultInjected` if a compile-failure fault matches the
    current stage context (called while the executor traces)."""
    for f in _matching("compile_fail"):
        _fire(f)
        raise FaultInjected(
            f"injected schedule-compile failure (engine={engine!r}, "
            f"codec={codec!r}, stage={_ctx()['stage']})")


def tap_stage_input(block):
    """Poison element 0 of a matching exchange stage's input block."""
    for f in _matching("nan_input"):
        _fire(f, value=f.value)
        flat = block.reshape(-1)
        flat = flat.at[0].set(jnp.asarray(f.value, dtype=block.dtype))
        block = flat.reshape(block.shape)
    return block


def scale_div():
    """Combined scale divisor armed saturation faults impose on the int8
    codec (None when none match)."""
    div = 1.0
    for f in _matching("saturate"):
        _fire(f, factor=f.value)
        div *= f.value
    return div if div != 1.0 else None


_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}
#: exponent-burst masks: OR-ing forces the exponent field to all ones
#: (Inf/NaN) for float payloads; int8 flips a magnitude bit (bounded)
_BURST = {jnp.dtype(jnp.float32): (4, 0x7F800000),
          jnp.dtype(jnp.bfloat16): (2, 0x7F80),
          jnp.dtype(jnp.int8): (1, 0x40)}


def tap_wire(x, label: str = "payload"):
    """Corrupt element 0 of a received wire buffer (post-collective,
    pre-decode) when a matching corrupt_wire fault is armed."""
    for f in _matching("corrupt_wire", label):
        _fire(f, label=label, dtype=str(x.dtype))
        x = _burst(x)
    return x


def _burst(x):
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        return lax.complex(_burst(jnp.real(x)), jnp.imag(x))
    size, mask = _BURST[jnp.dtype(x.dtype)]
    u = lax.bitcast_convert_type(x, _UINT[size]).reshape(-1)
    if x.dtype == jnp.int8:
        u = u.at[0].set(u[0] ^ mask)  # single bit flip: bounded by the codec
    else:
        u = u.at[0].set(u[0] | mask)  # stuck-at-ones exponent burst -> Inf/NaN
    return lax.bitcast_convert_type(u.reshape(x.shape), x.dtype)


# -- serve-level taps (host side; free no-ops when nothing matches) ---------


def tap_serve_execute():
    """Serving-engine hook, called at the top of every plan execution
    attempt: an armed ``slow_collective`` stalls the dispatch, then an
    armed ``executor_crash`` raises :class:`FaultInjected`.  The crash is
    raised *after* any stall so a slow-then-dead executor is modelable by
    arming both."""
    for f in _matching("slow_collective"):
        _fire(f, seconds=f.value)
        _time.sleep(f.value)
    for f in _matching("executor_crash"):
        _fire(f)
        raise FaultInjected("injected executor crash")


def tap_serve_cache(path):
    """Serving-engine hook, called between request waves: an armed
    ``cache_corruption`` fault scribbles over the shared schedule DB at
    ``path`` (the torn write a crashed replica could leave).  Returns True
    when a corruption fired."""
    fired = False
    for f in _matching("cache_corruption"):
        _fire(f, mode=f.label, path=str(path))
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text("" if f.label == "truncate" else '{"schema": 6, "trunca')
        fired = True
    return fired


def serve_burst() -> int:
    """Load-harness hook: the offered-load multiplier armed
    ``request_burst`` faults impose this wave (1 when none match)."""
    factor = 1.0
    for f in _matching("request_burst"):
        _fire(f, factor=f.value)
        factor *= f.value
    return max(1, int(factor))
