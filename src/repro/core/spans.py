"""Names of the plan's work inside the compiled program, and a recorder of
the process's compilations.

Every op a plan emits sits under one direction scope, ``pfft.fwd`` or
``pfft.bwd``, and beneath it under one stage scope ``stage{i}.<kind>``,
``i`` the stage's index in the executed plan (``stage.<kind>`` for work of
no single stage: the guard's bracket around the whole plan, or an exchange
called on its own).  The kinds:

``xform``      the 1-D transform proper (FFT, its inverse, the c2r's dense
               real DFT, DCT/DST pre- and post-processing, the four-step
               kernel);
``prune``      the truncated spectrum's keep and zero-scatter, the r2c keep
               (its zero-pad is the c2r's);
``c2r_extend`` the c2r's work around its inverse FFT, where it takes one
               (more kept bins than the dense DFT's bound): building the
               spectrum from the kept bins (the folded zero-pad, the
               Hermitian mirror, the packing of two real rows into one
               complex row) and unpacking the result (real and imaginary
               parts, their concatenate);
``repad``      the slice to the logical extent and the pad back to the
               physical one around a stage;
``encode``     all local work of an exchange before its collective (pack,
               narrowing, int8 scales, the encode kernels);
``a2a``        every all-to-all, the int8 scale exchange included;
``decode``     all local work after it (unpack, widening, the decode
               kernels);
``guard``      the runtime health checks of :mod:`repro.robustness.health`.

The names are :func:`jax.named_scope` s: they reach the compiled HLO's
``op_name`` metadata (``jit(step)/pfft.fwd/stage0.prune/slice``)
and from there a profiler trace, and change metadata only, never an op.
No name is a path component that names an FFT or a data-movement
primitive (``fft``, ``gather``, ...), so a reader that classes an op by
the last components of its ``op_name`` reads the same class with or
without them.

The compile recorder (:func:`compile_totals`), installed on import,
listens to ``jax.monitoring``: seconds spent tracing and lowering, in
XLA's compile (a persistent-cache load included, which JAX times inside
it), and the persistent cache's hits and misses, for the whole process.

The exchange counter (:func:`exchange_totals`) is written while an
executor is traced: for each traced executor, its all-to-all launches and
the bytes each device sends to the others.  Plain Python at trace time,
it adds no op and no metadata to the program.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
import threading

import jax
import numpy as np
from jax import monitoring

FORWARD = "pfft.fwd"
BACKWARD = "pfft.bwd"
KINDS = ("xform", "prune", "c2r_extend", "repad", "encode", "a2a", "decode", "guard")

_stage: contextvars.ContextVar[int | None] = contextvars.ContextVar("repro_stage", default=None)


def scope(name: str):
    """``jax.named_scope(name)``: the one place a name enters the program."""
    return jax.named_scope(name)


@contextlib.contextmanager
def executor(sign: int, key: str):
    """Scope of one executor, ``pfft.fwd`` for a forward sign (< 0) and
    ``pfft.bwd`` otherwise, and its record in the exchange counter: the
    all-to-alls traced inside are counted under ``"<direction> <key>"``,
    ``key`` naming what the executor exchanges.  On a clean exit a record
    with any exchange replaces what an earlier trace of the same executor
    left."""
    name = FORWARD if sign < 0 else BACKWARD
    rec = {"direction": name, "launches": 0, "scale_launches": 0, "bytes": 0}
    token = _exchange.set(rec)
    try:
        with scope(name):
            yield
    finally:
        _exchange.reset(token)
    if rec["launches"] + rec["scale_launches"]:
        with _lock:
            _exchanges[f"{name} {key}"] = rec


@contextlib.contextmanager
def stage(i: int):
    """Number the stage scopes opened inside as stage ``i``."""
    token = _stage.set(i)
    try:
        yield
    finally:
        _stage.reset(token)


def stage_name(name: str) -> str:
    if name not in KINDS:
        raise ValueError(f"unknown scope kind {name!r}; known: {KINDS}")
    i = _stage.get()
    return f"stage{'' if i is None else i}.{name}"


def kind(name: str):
    """Scope of ``name`` work in the current stage (``stage{i}.<name>``)."""
    return scope(stage_name(name))


def under(name: str):
    """Decorator: run the function inside :func:`kind` ``(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with kind(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


# ---------------------------------------------------------------------------
# exchange counter

_exchange: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "repro_exchange", default=None)
_exchanges: dict[str, dict] = {}


def count_all_to_all(x, m: int, *, scale: bool = False):
    """Count one tiled all-to-all of ``x`` over an axis of ``m`` devices in
    the record of the executor being traced: one launch (``scale``: the
    int8 scale exchange, counted apart), and ``x``'s bytes × (m−1)/m, what
    each device sends to the others at the wire's dtype.  Nothing outside
    an executor, or where ``m`` is 1 and nothing leaves the device."""
    rec = _exchange.get()
    if rec is None or m <= 1:
        return
    rec["scale_launches" if scale else "launches"] += 1
    nbytes = int(np.prod(x.shape, dtype=np.int64)) * np.dtype(x.dtype).itemsize
    rec["bytes"] += nbytes * (m - 1) // m


def exchange_totals() -> dict:
    """``{"<direction> <executor>": {"direction", "launches",
    "scale_launches", "bytes"}}``: one record per executor traced in this
    process that exchanged anything, its latest trace's.  ``direction`` is
    ``pfft.fwd`` or ``pfft.bwd``; ``launches`` counts the payload
    all-to-alls, ``scale_launches`` int8's scale exchanges; ``bytes`` is
    what one device sends to the others in one run of the executor."""
    with _lock:
        return {k: dict(v) for k, v in _exchanges.items()}


# ---------------------------------------------------------------------------
# compile recorder

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

#: spans kept per thread until a span enclosing them arrives; past the
#: limit the oldest is dropped, its seconds staying in the totals
_OPEN_LIMIT = 1024

_lock = threading.Lock()
_installed = False
_thread = threading.local()
_totals = {"trace_lower_s": 0.0, "xla_compile_s": 0.0, "cache_load_s": 0.0,
           "compile_spans": 0, "cache_hits": 0, "cache_misses": 0}


def _on_span(event: str, start: float, end: float, **_kw):
    """Add one span to the running totals.

    JAX reports a span when it ends, on the thread that ran it, and a
    thread's spans nest: the spans reported before on this thread that
    began inside this one are nested in it.  Each kept span carries its
    subtree's ``(front, compiled)`` seconds, so an enclosing span replaces
    its children's seconds rather than adding to them: a nested trace
    counts once, and a trace or lowering inside a compile counts as
    compile."""
    if event not in (_TRACE, _LOWER, _COMPILE):
        return
    kept = getattr(_thread, "spans", None)
    if kept is None:
        kept = _thread.spans = collections.deque(maxlen=_OPEN_LIMIT)
    front = compiled = 0.0
    while kept and kept[-1][0] >= start:
        _, f, c = kept.pop()
        front, compiled = front + f, compiled + c
    if event == _COMPILE:
        node = (start, 0.0, end - start)
    else:
        node = (start, end - start - compiled, compiled)
    kept.append(node)
    with _lock:
        _totals["trace_lower_s"] += node[1] - front
        _totals["xla_compile_s"] += node[2] - compiled
        _totals["compile_spans"] += event == _COMPILE


def _on_duration(event: str, seconds: float, **_kw):
    if event == _CACHE_LOAD:
        with _lock:
            _totals["cache_load_s"] += seconds


def _on_event(event: str, **_kw):
    key = {_CACHE_HIT: "cache_hits", _CACHE_MISS: "cache_misses"}.get(event)
    if key:
        with _lock:
            _totals[key] += 1


def install():
    """Register the recorder's ``jax.monitoring`` listeners, once per
    process (importing this module does; later calls do nothing)."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    monitoring.register_event_time_span_listener(_on_span)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def compile_totals() -> dict:
    """The compile work of the whole process since this module was
    imported, whoever compiled: the plan, the user's own jits, eager ops.

    ``trace_lower_s``: seconds tracing to a jaxpr and lowering to
    StableHLO, outside any XLA compile (a nested trace counts once);
    ``xla_compile_s``: seconds in XLA's compile or the persistent cache's
    load of it; ``cache_load_s``: the loads' part of that; both summed
    over threads.  ``xla_compiles``: compiles XLA ran (cache loads not
    counted); ``cache_hits``, ``cache_misses``: the persistent cache's
    loads, and compiles it stored."""
    with _lock:
        t = dict(_totals)
    return {"trace_lower_s": t["trace_lower_s"],
            "xla_compile_s": t["xla_compile_s"],
            "cache_load_s": t["cache_load_s"],
            "xla_compiles": t["compile_spans"] - t["cache_hits"],
            "cache_hits": t["cache_hits"],
            "cache_misses": t["cache_misses"]}


install()
