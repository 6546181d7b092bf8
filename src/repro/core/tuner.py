"""Per-plan exchange-schedule autotuner (``PlanConfig(method="auto")``).

The paper's single-collective formulation leaves the *engine* of each
exchange open — the MPI analogue is the library's freedom to implement
``MPI_ALLTOALLW`` however it likes, and FLUPS (arXiv:2211.07777) shows the
winning strategy is shape/topology dependent.  Here the candidate space per
exchange stage is the cross product of

* engine: ``fused``, ``traditional``, ``pipelined×chunks∈{2,4,8}``
  (comm/compute overlap, arXiv:2306.16589 lineage), and
* wire payload (``comm_dtype``): every payload no lossier than the plan's
  accuracy budget (see :mod:`repro.core.redistribute`) — ``complex64``
  only for the default lossless budget, ``{complex64, bf16}`` for
  ``comm_dtype="bf16"``, ``{complex64, bf16, int8}`` for ``"int8"``.
  int8 is expected to win only on firmly ICI-bound stages: the narrowed
  payload must buy back the codec's two extra HBM passes over the block.

* batch fusion (multi-field executions, ``nfields > 1``): how the stacked
  fields traverse the stage — ``stacked`` (one collective ships all
  fields), ``pipelined-across-fields`` (field i's collective emitted under
  field i-1's FFT), or ``per-field`` (serialized baseline).  Latency-bound
  small grids favor stacked; compute-heavy stages favor
  pipelined-across-fields.

* exchange-local impl (``StageEntry.impl``): the jnp reference pack/codec
  vs the fused Pallas exchange kernels of :mod:`repro.kernels.exchange`.
  Pallas candidates are swept only when the plan's ``exchange_impl``
  budget is ``"pallas"`` *and* the payload is lossy (a lossless exchange
  has no local pass for the kernels to fuse away — see
  ``pallas_applicable``), so ``method="auto"`` picks the kernels per
  stage only where they actually win.

This module micro-benchmarks each candidate on the stage's real shapes (the
exchange plus the 1-D FFT it feeds, so overlap is priced in) and caches the
winning schedule on disk.

Cache schema v6: each entry maps a :func:`plan_key` — mesh shape, global
shape, grid, the per-axis transform tags (so a dealiased/pruned or DCT plan
never collides with the plain c2c plan of the same shape), impl, backend
*and device kind* (so timings from different TPU generations under the same
``backend`` string never collide), **the batch size** (``nfields`` — a
3-field schedule must never be replayed for a 16-field execution), the
candidate set, and ``schema: 6`` — to ``{"schedule": [[method, chunks,
comm_dtype, impl, batch_fusion], ...], "timings": {...}}`` (full
:class:`~repro.core.planconfig.StageEntry` rows).  Entry health marks
(since v5): :func:`quarantine` sets ``entry["bad"] = {"reason": ...}``
(and bumps ``entry["quarantines"]``) when a guarded execution catches the
entry's schedule failing at runtime; a marked entry is never replayed —
:func:`_parse_entry` rejects it, forcing a retune whose fresh timings
(under whatever fault made the old winner lose) replace the mark.

Entries of older schemas (v1–v5) carry another ``schema`` in their key,
so a lookup never matches them and the plan retunes; a row that is not a
5-field :class:`~repro.core.planconfig.StageEntry` (v5 stored 3/4-field
rows) fails :func:`_parse_entry` the same way.  Stale entries are harmless
and a corrupt or non-dict cache file is silently treated as empty and
rewritten — a stale cache must never raise.  Writes are atomic (temp
file + ``os.replace``) and **merge** by default: the writer re-reads the
file and overlays only its own keys, so concurrent workers tuning
*different* plans no longer clobber each other's entries
(last-writer-wins now applies per key, not per file).

Cache location: ``$REPRO_TUNER_CACHE`` or ``~/.cache/repro/fft_tuner.json``;
an in-process memo avoids re-reading the file per plan.
"""

from __future__ import annotations

import contextlib
import json
import os
import tempfile
import time
from pathlib import Path

try:  # POSIX advisory locks; absent on some platforms (lock becomes a no-op)
    import fcntl
except ImportError:  # pragma: no cover - non-POSIX
    fcntl = None

import jax
import jax.numpy as jnp

from repro.core import modelfit
from repro.core.meshutil import shard_map
from repro.core.planconfig import BATCH_FUSIONS, StageEntry, as_schedule
from repro.core.quant import canonical_comm_dtype
from repro.core.redistribute import PIPELINE_CHUNK_CANDIDATES
from repro.kernels.exchange import pallas_applicable

#: cache schema version (bump when the key or entry layout changes)
SCHEMA_VERSION = 6

#: how many times a guarded execution may quarantine-and-retune one cache
#: entry before the runner gives up and raises (see repro.robustness.runner)
MAX_QUARANTINE_RETUNES = 3

#: (method, chunks) engine candidates benchmarked per exchange stage
ENGINE_CANDIDATES: tuple[tuple[str, int], ...] = (
    ("fused", 1),
    ("traditional", 1),
    *(("pipelined", c) for c in PIPELINE_CHUNK_CANDIDATES),
)

#: payloads allowed under each accuracy budget, lossless first
COMM_DTYPE_LADDER = {
    "complex64": ("complex64",),
    "bf16": ("complex64", "bf16"),
    "int8": ("complex64", "bf16", "int8"),
}


def candidates_for(comm_dtype=None, exchange_impl: str = "jnp",
                   ) -> tuple[StageEntry, ...]:
    """Full :class:`StageEntry` candidate set for an accuracy budget: every
    engine × every payload no lossier than ``comm_dtype``; an
    ``exchange_impl="pallas"`` budget additionally sweeps the fused Pallas
    kernels for every candidate they apply to (lossy payloads)."""
    ladder = COMM_DTYPE_LADDER[canonical_comm_dtype(comm_dtype)]
    out = [StageEntry(m, c, d) for d in ladder for m, c in ENGINE_CANDIDATES]
    if exchange_impl == "pallas":
        out += [StageEntry(m, c, d, "pallas") for d in ladder
                for m, c in ENGINE_CANDIDATES if pallas_applicable(m, d)]
    return tuple(out)


def batched_candidates_for(comm_dtype=None, exchange_impl: str = "jnp",
                           ) -> tuple[StageEntry, ...]:
    """Batch-aware candidate set for a multi-field execution: every
    single-field candidate × every batch fusion mode."""
    return tuple(e._replace(batch_fusion=f) for f in BATCH_FUSIONS
                 for e in candidates_for(comm_dtype, exchange_impl))


def _default_candidates(plan, nfields: int):
    budget, impl_budget = plan.config.comm_dtype, plan.config.exchange_impl
    return (candidates_for(budget, impl_budget) if nfields <= 1
            else batched_candidates_for(budget, impl_budget))


def _tag(cand) -> str:
    return "@".join(str(p) for p in cand)


#: default candidate set (lossless budget)
DEFAULT_CANDIDATES = candidates_for("complex64")

_MEMO: dict[str, tuple[StageEntry, ...]] = {}

#: per-candidate stage timings memo shared across accuracy budgets in one
#: process: a --compare sweep tuning the same plan under complex64, bf16
#: and int8 budgets re-times only the candidates it has not seen yet
_STAGE_MEMO: dict[tuple[str, int, str], float] = {}


def default_cache_path() -> Path:
    env = os.environ.get("REPRO_TUNER_CACHE")
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro" / "fft_tuner.json"


def _key_fields(plan, nfields: int = 1) -> dict:
    """Everything that determines the stage shapes and the hardware the
    timings are valid for (the candidate-set-independent part of the key).
    ``nfields`` is part of the identity: batched stage shapes (and the
    stacked-vs-per-field trade) change with the batch size."""
    mesh_sig = tuple(zip(plan.mesh.axis_names, plan.mesh.devices.shape))
    try:
        device_kind = jax.devices()[0].device_kind
    except Exception:  # no devices (analysis-only contexts)
        device_kind = "unknown"
    return {"schema": SCHEMA_VERSION, "mesh": mesh_sig, "shape": plan.shape,
            "grid": plan.grid,
            "transforms": tuple(sp.tag() for sp in plan.transforms),
            "impl": plan.config.impl, "backend": jax.default_backend(),
            "device_kind": device_kind, "nfields": nfields}


def plan_key(plan, candidates=None, *, nfields: int = 1) -> str:
    """Cache key: everything that determines the stage shapes, the engines,
    payloads and batch fusions swept, the batch size, and the hardware the
    timings are valid for."""
    if candidates is None:
        candidates = _default_candidates(plan, nfields)
    fields = _key_fields(plan, nfields)
    fields["candidates"] = sorted(_tag(c) for c in candidates)
    return json.dumps(fields, sort_keys=True, default=str)


def load_cache(path: Path) -> dict:
    """Read a schedule cache, returning ``{}`` for anything unusable — a
    missing file, unreadable bytes, invalid JSON, or a JSON payload that is
    not an object (a stale or corrupt cache must never raise: it is simply
    retuned and rewritten)."""
    try:
        data = json.loads(Path(path).read_text())
    except (OSError, ValueError):
        return {}
    return data if isinstance(data, dict) else {}


@contextlib.contextmanager
def _file_lock(path: Path):
    """Cross-process advisory lock (``fcntl.flock`` on ``<path>.lock``)
    serializing the read-merge-write cycle against concurrent serve
    replicas sharing one schedule DB.  Atomic replace alone only prevents
    torn *reads*; two processes interleaving read→merge→replace can still
    drop each other's keys.  No-op when ``fcntl`` is unavailable or the
    lock file cannot be created (read-only FS) — behavior then degrades to
    the previous merge-on-save semantics, never an error.  flock is held
    per open-file-description, so callers must not nest this for the same
    path within one process (see :func:`quarantine` → ``lock=False``)."""
    if fcntl is None:
        yield
        return
    try:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        fd = os.open(str(path) + ".lock", os.O_RDWR | os.O_CREAT, 0o644)
    except OSError:
        yield
        return
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        yield
    finally:
        os.close(fd)  # closing releases the flock


def save_cache(path: Path, data: dict, *, merge: bool = True,
               lock: bool = True) -> bool:
    """Atomically write cache entries: write a temp file in the same
    directory, then ``os.replace`` — readers can never observe partial
    JSON.  With ``merge=True`` (default) the writer first re-reads the file
    and overlays only the keys in ``data``, so a worker that tuned plan A
    no longer erases the entry a concurrent worker just wrote for plan B
    (the pre-v5 last-writer-wins clobber).  The read-merge-write cycle
    runs under :func:`_file_lock` (``lock=True``), closing the remaining
    cross-process interleave where two racing writers both read the same
    snapshot and the second replace drops the first writer's keys; pass
    ``lock=False`` only when the caller already holds the lock.
    ``merge=False`` replaces the whole file (tests / explicit resets)."""
    try:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        with _file_lock(path) if lock else contextlib.nullcontext():
            if merge:
                current = load_cache(path)
                current.update(data)
                data = current
            fd, tmp = tempfile.mkstemp(dir=path.parent, prefix=path.name,
                                       suffix=".tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    f.write(json.dumps(data, indent=1))
                os.replace(tmp, path)
            except BaseException:
                os.unlink(tmp)
                raise
        return True
    except OSError:
        return False  # read-only FS etc.: tuning still works, just uncached


def get_or_tune(plan, *, cache_path: str | None = None,
                candidates=None, nfields: int = 1):
    """Return the tuned schedule for ``plan`` — a :class:`StageEntry` per
    exchange stage — consulting the in-process memo, then the disk cache
    then benchmarking.  The default candidate set is every engine × every
    payload within the plan's ``comm_dtype`` accuracy budget × every
    exchange impl within its ``exchange_impl`` budget (× every batch
    fusion mode for a batched plan).  A stale-schema or otherwise
    malformed cache entry is ignored and overwritten, never raised on."""
    if candidates is None:
        candidates = _default_candidates(plan, nfields)
    candidates = as_schedule(candidates)
    path = Path(cache_path) if cache_path else default_cache_path()
    key = plan_key(plan, candidates, nfields=nfields)
    memo_key = f"{path}|{key}"
    if memo_key in _MEMO:
        return _MEMO[memo_key]
    disk = load_cache(path)
    sched = _parse_entry(disk.get(key), plan.n_exchanges, candidates=candidates)
    if sched is None:
        sched, timings = tune_plan(plan, candidates=candidates, nfields=nfields)
        entry = {"schedule": [list(s) for s in sched], "timings": timings}
        prev = disk.get(key)
        if isinstance(prev, dict) and prev.get("quarantines"):
            # retune after a quarantine: clear the bad mark, keep the count
            # so a still-failing entry eventually exhausts the runner's cap
            entry["quarantines"] = int(prev["quarantines"])
        save_cache(path, {key: entry})  # delta write: merge keeps other plans
    _MEMO[memo_key] = sched
    return sched


def quarantine(path, key: str, reason: str) -> int:
    """Mark the cache entry at ``key`` bad (a guarded execution caught its
    schedule failing at runtime): the entry stops parsing, so the next
    schedule resolve retunes.  Bumps and returns the entry's lifetime
    quarantine count; also drops the in-process memos — including the
    stage-timing memo, which may hold the faulted candidate's healthy-run
    timings — so the retune actually re-measures.

    The whole read-bump-write runs under one :func:`_file_lock` hold (the
    inner save passes ``lock=False``: flock is per open-file-description,
    so re-acquiring from a second fd in the same process would deadlock) —
    two serve replicas quarantining concurrently can't lose a count."""
    with _file_lock(path):
        disk = load_cache(path)
        entry = disk.get(key)
        if not isinstance(entry, dict):
            entry = {}
        entry["bad"] = {"reason": reason}
        entry["quarantines"] = int(entry.get("quarantines", 0)) + 1
        save_cache(path, {key: entry}, lock=False)
    forget(key)
    return entry["quarantines"]


def forget(key: str) -> None:
    """Drop this process's memos for the plan ``key`` (and the stage-timing
    memo), so the next schedule resolve reads the disk cache again."""
    for k in [k for k in _MEMO if k.endswith("|" + key)]:
        del _MEMO[k]
    _STAGE_MEMO.clear()


def _parse_entry(entry, n_exchanges: int, candidates=None):
    """Validate one disk-cache entry into a :class:`StageEntry` schedule,
    or ``None`` if missing/malformed — wrong stage count, junk types, or
    unknown engine/payload/impl/fusion *values* (a hand-edited or
    bit-rotted entry must retune, never raise later inside the executor);
    so does a row that is not a 5-field :class:`StageEntry`.

    When ``candidates`` is given, every stage entry must additionally be a
    member of that *live* candidate set: an entry naming an engine, chunk
    count, payload, impl or fusion that has since been dropped from the
    sweep (e.g. a hand-edited chunks=16 after ``PIPELINE_CHUNK_CANDIDATES``
    shrank) is a retune, not a schedule the executor should replay.

    A quarantined entry (``entry["bad"]`` set, see :func:`quarantine`)
    never parses either — that is the whole point of the mark."""
    if not isinstance(entry, dict) or entry.get("bad"):
        return None
    try:
        sched = as_schedule(entry["schedule"])
        if len(sched) != n_exchanges:
            return None
        if candidates is not None:
            live = set(as_schedule(candidates))
            if any(e not in live for e in sched):
                return None
        return sched
    except (TypeError, KeyError, IndexError, ValueError):
        pass
    return None


#: with model priors armed, how many top-ranked candidates per stage the
#: tuner still micro-benchmarks (0 disables pruning: rank only)
PRIOR_TOPK_DEFAULT = 6


def _prior_stage_time(plan, si: int, entry: StageEntry, nfields: int,
                      coeffs: dict) -> float:
    """Modeled seconds for one stage candidate at the *fitted* hardware
    coefficients of a scaling-sweep fit report (see
    :mod:`repro.core.modelfit`) — the ranking key prior-guided tuning
    prunes the sweep with.  Mirrors :meth:`ParallelFFT.model_time_s`'s
    per-stage accounting: the exchange plus the 1-D FFT it feeds."""
    from repro.core.pfft import FFTStage
    from repro.core.redistribute import exchange_time_model

    st = plan.stages[si]
    follow = plan.stages[si + 1] if si + 1 < len(plan.stages) else None
    fft_s = 0.0
    if isinstance(follow, FFTStage) and follow.axis == st.w:
        ndev = int(plan.mesh.devices.size)
        fft_s = plan._stage_flops_at(si + 1) / ndev / coeffs["peak_flops"]
    return exchange_time_model(
        plan.pencil_trace[si], st.v, st.w, itemsize=plan._stage_itemsize(si),
        method=entry.method, chunks=entry.chunks, comm_dtype=entry.comm_dtype,
        impl=entry.impl, ici_bw=coeffs["ici_bw"], hbm_bw=coeffs["hbm_bw"],
        ici_latency_s=coeffs["ici_latency_s"], overlap_compute_s=fft_s,
        nfields=nfields, batch_fusion=entry.batch_fusion)


def tune_plan(plan, *, candidates=None, repeats: int = 3, inner: int = 2,
              nfields: int = 1):
    """Micro-benchmark every :class:`StageEntry` candidate for every
    exchange stage of ``plan`` (each stage timed together with the 1-D FFT
    it feeds, so pipelined candidates get credit for overlap; batched
    candidates run on the real stacked ``(nfields, …)`` stage shapes) and
    return (schedule, timings) with ``timings[stage][tag] = seconds``.

    With model priors armed (``$REPRO_MODEL_PRIORS`` names a
    :mod:`repro.core.modelfit` fit report), each stage's candidate set is
    first *ranked* by modeled time at the fitted coefficients and only the
    top ``$REPRO_TUNER_PRIOR_TOPK`` (default 6, ``0`` disables) are
    micro-benchmarked; pruned candidates keep their model estimate in the
    timings dict under a ``pruned:`` tag so the cache records what the
    prior skipped."""
    from repro.core.pfft import ExchangeStage

    if candidates is None:
        candidates = _default_candidates(plan, nfields)
    candidates = as_schedule(candidates)
    priors = modelfit.active_priors()
    try:
        topk = int(os.environ.get("REPRO_TUNER_PRIOR_TOPK",
                                  str(PRIOR_TOPK_DEFAULT)))
    except ValueError:
        topk = PRIOR_TOPK_DEFAULT
    base_key = json.dumps(_key_fields(plan, nfields), sort_keys=True, default=str)
    schedule = []
    timings: dict[str, dict[str, float]] = {}
    for si, st in enumerate(plan.stages):
        if not isinstance(st, ExchangeStage):
            continue
        per = {}
        by_tag = {}
        sweep = candidates
        if priors is not None and 0 < topk < len(candidates):
            est = {cand: _prior_stage_time(plan, si, cand, nfields, priors)
                   for cand in candidates}
            ranked = sorted(candidates, key=lambda c: est[c])
            sweep, skipped = ranked[:topk], ranked[topk:]
            for cand in skipped:
                per[f"pruned:{_tag(cand)}"] = est[cand]
        for cand in sweep:
            tag = _tag(cand)
            by_tag[tag] = cand
            memo_key = (base_key, si, tag)
            if memo_key in _STAGE_MEMO:
                per[tag] = _STAGE_MEMO[memo_key]
                continue
            try:
                per[tag] = _time_stage(plan, si, *cand, repeats=repeats,
                                       inner=inner, nfields=nfields)
                _STAGE_MEMO[memo_key] = per[tag]
            except Exception as e:  # candidate invalid for this shape
                per[tag] = float("inf")
                per[f"{tag}:error"] = repr(e)[:200]
        best = min((k for k in per if ":" not in k), key=lambda k: per[k])
        schedule.append(by_tag[best])
        timings[f"stage{si}"] = per  # errors kept: an inf needs its reason
    return tuple(schedule), timings


def _time_stage(plan, si: int, method: str, chunks: int, comm_dtype: str,
                impl: str = "jnp", batch_fusion: str = "stacked", *,
                repeats: int, inner: int, nfields: int = 1) -> float:
    """Wall-time one exchange stage (+ its following FFT) under one engine,
    payload, exchange impl, and — for a stacked ``nfields > 1`` input —
    batch fusion mode, via the same stage executor the plan runs
    (:func:`repro.core.pfft._run_exchange_stage`)."""
    from repro.core import fftcore
    from repro.core.pfft import FFTStage, _run_exchange_stage

    st = plan.stages[si]
    before = plan.pencil_trace[si]
    follow = plan.stages[si + 1] if si + 1 < len(plan.stages) else None
    has_fft = isinstance(follow, FFTStage) and follow.axis == st.w
    out_pen = plan.pencil_trace[si + 2] if has_fft else plan.pencil_trace[si + 1]
    nbatch = 1 if nfields > 1 else 0
    entry = StageEntry(method, chunks, comm_dtype, impl, batch_fusion)

    def run(block):
        out, _, _ = _run_exchange_stage(
            block, st, follow if has_fft else None, plan.pencil_trace[si + 1],
            out_pen if has_fft else None, entry, impl=plan.config.impl,
            sign=fftcore.FORWARD, nbatch=nbatch, at=si)
        return out

    fn = jax.jit(shard_map(run, mesh=plan.mesh,
                           in_specs=before.batched_spec(nbatch),
                           out_specs=out_pen.batched_spec(nbatch),
                           check_vma=False))
    # time at the stage's true dtype: exchanges before any complex-producing
    # transform (all-real DCT/DST plans) ship f32, not complex64
    x = jax.device_put(jnp.zeros((nfields,) * nbatch + tuple(before.physical),
                                 plan.dtype_trace[si]),
                       before.batched_sharding(nbatch))
    jax.block_until_ready(fn(x))  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(inner):
            y = fn(x)
        jax.block_until_ready(y)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best
