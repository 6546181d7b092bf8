"""Serving-engine tests: lifecycle units single-process, engine behavior in
8-virtual-device subprocesses, and the chaos soak (`-m faults`).

The soak is the PR's acceptance test: waves of serve-level fault matrices
against fresh servers sharing one schedule DB — every request must land in
a structured terminal outcome within deadline+grace (zero hangs, zero
silent corruption) and quarantine counts must track breaker trips, not
request counts (no leak across requests)."""

from __future__ import annotations

import json
import threading
import time

import pytest

from repro.robustness import faults
from repro.serve import (
    OUTCOME_STATUSES, TRIP_SHED, TRIP_TIMEOUT,
    Outcome, RequestFuture, backoff_s,
)
from repro.serve.registry import CircuitBreaker


# -- lifecycle units (no devices needed) ------------------------------------


def test_backoff_deterministic_and_bounded():
    a = [backoff_s("r1", k, base=0.05, cap=1.0) for k in range(1, 8)]
    b = [backoff_s("r1", k, base=0.05, cap=1.0) for k in range(1, 8)]
    assert a == b  # deterministic jitter: same (rid, attempt) -> same delay
    assert a != [backoff_s("r2", k, base=0.05, cap=1.0) for k in range(1, 8)]
    for k, v in enumerate(a, start=1):
        raw = min(1.0, 0.05 * 2 ** (k - 1))
        assert 0.5 * raw <= v < raw  # jitter fraction in [0.5, 1.0)
    assert backoff_s("r1", 0) == 0.0
    assert backoff_s("r1", 50) < 1.0  # capped


def test_outcome_status_validated():
    with pytest.raises(ValueError):
        Outcome("exploded", "r0")
    o = Outcome("shed", "r0", trip=TRIP_SHED)
    assert o.summary()["status"] == "shed"
    assert set(OUTCOME_STATUSES) == {
        "ok", "degraded", "shed", "deadline-exceeded", "error"}


def test_request_future_first_resolve_wins():
    fut = RequestFuture("r0", time.monotonic() + 5.0)
    assert fut.resolve(Outcome("ok", "r0", value=1))
    assert not fut.resolve(Outcome("error", "r0"))  # loser observes the race
    assert fut.result().status == "ok"
    assert fut.result().value == 1


def test_request_future_deadline_self_resolves():
    fut = RequestFuture("r0", time.monotonic() + 0.05)
    t0 = time.monotonic()
    out = fut.result(grace=0.05)
    assert time.monotonic() - t0 < 2.0  # bounded wait, no hang
    assert out.status == "deadline-exceeded" and out.trip == TRIP_TIMEOUT
    # a late completion loses the race but is observable to the resolver
    assert not fut.resolve(Outcome("ok", "r0", value=1))
    assert fut.result().status == "deadline-exceeded"


def test_request_future_result_concurrent_with_resolve():
    fut = RequestFuture("r0", time.monotonic() + 5.0)
    got = []
    t = threading.Thread(target=lambda: got.append(fut.result()))
    t.start()
    time.sleep(0.02)
    fut.resolve(Outcome("ok", "r0"))
    t.join(timeout=5.0)
    assert got and got[0].status == "ok"


def test_circuit_breaker_transitions():
    b = CircuitBreaker(threshold=2, cooldown_s=0.05)
    assert b.state == "closed" and b.allow()
    assert not b.record_failure()          # 1 failure: still closed
    assert b.record_failure()              # 2nd trips
    assert b.state == "open" and not b.allow()
    time.sleep(0.06)
    assert b.state == "half-open"
    assert b.allow()                       # probe slot
    assert not b.allow()                   # ... exactly one
    assert b.record_failure()              # failed probe re-opens instantly
    assert b.state == "open"
    time.sleep(0.06)
    assert b.allow()
    b.record_success()                     # clean probe closes
    assert b.state == "closed" and b.trips == 2
    # success also resets the consecutive-failure count
    b.record_failure()
    b.record_success()
    assert not b.record_failure()


def test_serve_taps_unarmed_are_noops(tmp_path):
    # no FaultPlan armed: every serve tap must be free and side-effect-less
    t0 = time.monotonic()
    faults.tap_serve_execute()
    assert time.monotonic() - t0 < 0.05
    assert faults.serve_burst() == 1
    p = tmp_path / "cache.json"
    assert faults.tap_serve_cache(p) is False
    assert not p.exists()


def test_serve_faults_bounded_times(tmp_path):
    with faults.FaultPlan().executor_crash(times=2).request_burst(
            factor=3, times=1).cache_corruption(mode="truncate", times=1):
        assert faults.serve_burst() == 3
        assert faults.serve_burst() == 1   # bounded: used up
        p = tmp_path / "db.json"
        assert faults.tap_serve_cache(p) and p.read_text() == ""
        assert not faults.tap_serve_cache(p)  # disarmed after 1 fire
        for _ in range(2):
            with pytest.raises(faults.FaultInjected):
                faults.tap_serve_execute()
        faults.tap_serve_execute()         # 3rd call: crash exhausted


def test_fault_context_is_thread_local():
    # the serve engine traces fallback executors concurrently with a
    # background retune thread; stage context must not leak across threads
    with faults.FaultPlan().corrupt_wire(codec="bf16"):
        seen = {}

        def other():
            seen["match"] = bool(faults._matching("corrupt_wire"))

        with faults.stage_context(0, "fused", "bf16"):
            assert faults._matching("corrupt_wire")
            t = threading.Thread(target=other)
            t.start()
            t.join()
        assert seen["match"] is False  # peer thread saw no bf16 context


# -- engine behavior (8 virtual devices, subprocess) ------------------------

_CLEAN_SCRIPT = r"""
import json, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig
from repro.serve import ServeConfig, SpectralServer

mesh, grid = make_mesh((8,), ("p0",)), ("p0",)
pc = PlanConfig(method="fused", guard="degrade")
rng = np.random.default_rng(0)
xs = [rng.standard_normal((16, 16, 16)).astype(np.float32) for _ in range(5)]
with SpectralServer(mesh, grid, plan_config=pc,
                    config=ServeConfig(deadline_s=120.0, max_batch=8)) as srv:
    futs = [srv.submit(x) for x in xs]
    outs = [f.result() for f in futs]
    stats = srv.stats()
ref = ParallelFFT(mesh, (16, 16, 16), grid,
                  config=PlanConfig(method="fused")).forward(xs[0])
match = bool(np.allclose(np.asarray(outs[0].value), np.asarray(ref),
                         atol=1e-4))
print("CLEAN=" + json.dumps({
    "statuses": [o.status for o in outs],
    "batched": [o.batched for o in outs],
    "match": match,
    "coalesced_batches": stats["coalesced_batches"],
    "batched_requests": stats["batched_requests"],
    "plans": stats["registry"]["plans"],
    "compile": stats["compile"]}))
"""


def test_serve_clean_coalescing(subproc):
    out = json.loads(subproc(_CLEAN_SCRIPT).split("CLEAN=")[1])
    assert out["statuses"] == ["ok"] * 5
    assert out["match"], "served spectrum != direct plan.forward"
    # all five same-shape requests rode one batched invocation
    assert out["coalesced_batches"] >= 1
    assert out["batched_requests"] >= 4
    assert max(out["batched"]) >= 4
    assert out["plans"] == 1
    # the operator sees the process's compiles: at least the plan's own
    assert out["compile"]["xla_compiles"] >= 1
    assert out["compile"]["xla_compile_s"] > 0 and out["compile"]["trace_lower_s"] > 0


_LRU_SCRIPT = r"""
import json, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.planconfig import PlanConfig
from repro.serve import ServeConfig, SpectralServer

mesh, grid = make_mesh((8,), ("p0",)), ("p0",)
pc = PlanConfig(method="fused", guard="degrade")
rng = np.random.default_rng(0)
with SpectralServer(mesh, grid, plan_config=pc,
                    config=ServeConfig(deadline_s=120.0, capacity=1)) as srv:
    outs = []
    for shape in [(16, 16, 16), (8, 16, 16), (16, 16, 16)]:
        x = rng.standard_normal(shape).astype(np.float32)
        outs.append(srv.submit(x).result())
    stats = srv.stats()
print("LRU=" + json.dumps({
    "statuses": [o.status for o in outs],
    "shapes_ok": [list(np.asarray(o.value).shape) for o in outs],
    "plans": stats["registry"]["plans"],
    "builds": stats["registry"]["builds"],
    "evictions": stats["registry"]["evictions"]}))
"""


def test_serve_lru_eviction(subproc):
    out = json.loads(subproc(_LRU_SCRIPT).split("LRU=")[1])
    assert out["statuses"] == ["ok"] * 3
    assert out["shapes_ok"] == [[16, 16, 16], [8, 16, 16], [16, 16, 16]]
    assert out["plans"] == 1               # capacity-1 LRU
    assert out["builds"] == 3              # third request rebuilt evicted plan
    assert out["evictions"] == 2


_SHED_SCRIPT = r"""
import json, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.planconfig import PlanConfig
from repro.robustness import faults
from repro.serve import ServeConfig, SpectralServer

mesh, grid = make_mesh((8,), ("p0",)), ("p0",)
pc = PlanConfig(method="fused", guard="degrade")
rng = np.random.default_rng(0)
x = rng.standard_normal((16, 16, 16)).astype(np.float32)
burst = faults.serve_burst()
with faults.FaultPlan().slow_collective(seconds=0.4, times=100) \
        .request_burst(factor=4, times=1):
    burst = faults.serve_burst()
    with SpectralServer(mesh, grid, plan_config=pc,
                        config=ServeConfig(deadline_s=120.0, max_queue=2,
                                           max_batch=1)) as srv:
        futs = [srv.submit(x) for _ in range(2 * burst)]
        outs = [f.result() for f in futs]
        stats = srv.stats()
print("SHED=" + json.dumps({
    "burst": burst,
    "statuses": [o.status for o in outs],
    "shed_latency": max(o.latency_s for o in outs if o.status == "shed"),
    "shed_stat": stats["shed"]}))
"""


@pytest.mark.faults
def test_serve_overload_shed(subproc):
    out = json.loads(subproc(_SHED_SCRIPT).split("SHED=")[1])
    assert out["burst"] == 4
    statuses = out["statuses"]
    assert len(statuses) == 8
    n_shed = statuses.count("shed")
    assert n_shed >= 4                     # bounded queue under 4x burst
    assert n_shed == out["shed_stat"]
    assert statuses.count("ok") + n_shed == len(statuses)
    assert out["shed_latency"] < 0.1       # shed is instant, never queued


_BREAKER_SCRIPT = r"""
import json, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.planconfig import PlanConfig
from repro.robustness import faults
from repro.serve import ServeConfig, SpectralServer

mesh, grid = make_mesh((8,), ("p0",)), ("p0",)
pc = PlanConfig(method="fused", comm_dtype="bf16", guard="strict")
sc = ServeConfig(deadline_s=120.0, breaker_threshold=2,
                 breaker_cooldown_s=60.0, max_retries=0)
rng = np.random.default_rng(0)
x = rng.standard_normal((16, 16, 16)).astype(np.float32)
with faults.FaultPlan().corrupt_wire(codec="bf16"):
    with SpectralServer(mesh, grid, plan_config=pc, config=sc) as srv:
        outs = [srv.submit(x).result(grace=5.0) for _ in range(4)]
        stats = srv.stats()
ref = None
print("BREAKER=" + json.dumps({
    "statuses": [o.status for o in outs],
    "trips": [o.trip for o in outs],
    "breaker_trips": stats["registry"]["breaker_trips"],
    "fallback_served": stats["fallback_served"],
    "errors": stats["error"]}))
"""


@pytest.mark.faults
def test_serve_breaker_trips_and_degrades(subproc):
    out = json.loads(subproc(_BREAKER_SCRIPT).split("BREAKER=")[1])
    # persistent wire corruption on the strict bf16 plan: every request is
    # still served — through the lossless fallback ladder — as degraded
    assert out["statuses"] == ["degraded"] * 4
    assert out["trips"][0] == "guard-error"       # pre-trip one-off fallback
    assert set(out["trips"][2:]) == {"circuit-open"}
    assert out["breaker_trips"] >= 1
    assert out["fallback_served"] == 4
    assert out["errors"] == 0


_CRASH_SCRIPT = r"""
import json, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.planconfig import PlanConfig
from repro.robustness import faults
from repro.serve import ServeConfig, SpectralServer

mesh, grid = make_mesh((8,), ("p0",)), ("p0",)
pc = PlanConfig(method="fused", guard="degrade")
rng = np.random.default_rng(0)
x = rng.standard_normal((16, 16, 16)).astype(np.float32)
with faults.FaultPlan().executor_crash(times=1).slow_collective(
        seconds=0.05, times=2):
    with SpectralServer(mesh, grid, plan_config=pc,
                        config=ServeConfig(deadline_s=120.0,
                                           backoff_base_s=0.01)) as srv:
        out = srv.submit(x).result()
        stats = srv.stats()
print("CRASH=" + json.dumps({
    "status": out.status, "retries": out.retries,
    "stat_retries": stats["retries"], "errors": stats["error"]}))
"""


@pytest.mark.faults
def test_serve_crash_retry_recovers(subproc):
    out = json.loads(subproc(_CRASH_SCRIPT).split("CRASH=")[1])
    # a bounded (times=1) crash burns one retry and then recovers cleanly
    assert out["status"] == "ok"
    assert out["retries"] == 1
    assert out["stat_retries"] == 1
    assert out["errors"] == 0


# -- chaos soak (the PR acceptance test) ------------------------------------

_SOAK_SCRIPT = r"""
import json, numpy as np, os, time
from repro.core import tuner
from repro.core.meshutil import make_mesh
from repro.core.planconfig import PlanConfig
from repro.robustness import faults
from repro.serve import OUTCOME_STATUSES, ServeConfig, SpectralServer

mesh, grid = make_mesh((8,), ("p0",)), ("p0",)
CACHE = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                     "serve_soak_%d.json" % os.getpid())
DEADLINE, GRACE = 120.0, 5.0
rng = np.random.default_rng(0)

def wave(plan_config, fault_plan, n, *, max_batch=4):
    sc = ServeConfig(deadline_s=DEADLINE, grace_s=GRACE, max_batch=max_batch,
                     max_queue=16, backoff_base_s=0.01,
                     breaker_threshold=2, breaker_cooldown_s=60.0)
    ctx = fault_plan if fault_plan is not None else faults.FaultPlan()
    with ctx:
        n = n * faults.serve_burst()
        with SpectralServer(mesh, grid, plan_config=plan_config,
                            config=sc) as srv:
            futs = [srv.submit(
                rng.standard_normal((16, 16, 16)).astype(np.float32))
                for _ in range(n)]
            outs = [f.result(grace=GRACE) for f in futs]
            stats = srv.stats()
    return outs, stats, list(ctx.fired)

auto = PlanConfig(method="auto", comm_dtype="bf16", guard="degrade",
                  tuner_cache=CACHE)
strict = PlanConfig(method="auto", comm_dtype="bf16", guard="strict",
                    tuner_cache=CACHE)

def poison_strict_entry():
    # the ISSUE's "poisoned cache entry" fault: plant a structurally valid
    # bf16 schedule the tuner never timed, so the strict wave's auto plan
    # replays it and the bf16-targeted wire corruption deterministically
    # hits the primary path (a freshly tuned winner might be lossless)
    from repro.core.pfft import ParallelFFT
    probe = ParallelFFT(mesh, (16, 16, 16), grid, config=strict)
    faults.FaultPlan.poison_cache(
        CACHE, probe, [("fused", 1, "bf16", "jnp", "stacked")])

waves = [
    ("clean", auto, None, 4, 4, None),
    ("transient", auto,
     faults.FaultPlan().executor_crash(times=1)
                       .slow_collective(seconds=0.05, times=2), 4, 4, None),
    ("corrupt-degrade", auto,
     faults.FaultPlan().corrupt_wire(codec="bf16"), 3, 4, None),
    ("breaker-strict", strict,
     faults.FaultPlan().corrupt_wire(codec="bf16"), 4, 1,
     poison_strict_entry),
    ("cache-corruption-burst", auto,
     faults.FaultPlan().cache_corruption(mode="garbage", times=1)
                       .request_burst(factor=2, times=1), 3, 4, None),
]

report = {"waves": {}}
total_trips = 0
for name, pc, fp, n, mb, setup in waves:
    if setup is not None:
        setup()
    t0 = time.monotonic()
    outs, stats, fired = wave(pc, fp, n, max_batch=mb)
    total_trips += stats["registry"]["breaker_trips"]
    report["waves"][name] = {
        "n": len(outs),
        "statuses": [o.status for o in outs],
        "trips": [o.trip for o in outs],
        "unresolved": sum(o is None for o in outs),
        "bad_status": [o.status for o in outs
                       if o.status not in OUTCOME_STATUSES],
        "over_deadline": [o.latency_s for o in outs
                          if o.latency_s > DEADLINE + GRACE + 1.0],
        "errors": stats["error"],
        "breaker_trips": stats["registry"]["breaker_trips"],
        "fired": len(fired),
        "wall_s": round(time.monotonic() - t0, 2),
    }

disk = tuner.load_cache(CACHE)
quarantines = {k[:40]: v.get("quarantines", 0)
               for k, v in disk.items() if isinstance(v, dict)}
report["total_quarantines"] = sum(quarantines.values())
report["total_breaker_trips"] = total_trips
report["cache_entries"] = len(disk)
report["cache_well_formed"] = bool(disk)
print("SOAK=" + json.dumps(report))
"""


@pytest.mark.faults
def test_chaos_soak(subproc):
    out = json.loads(subproc(_SOAK_SCRIPT, timeout=1500).split("SOAK=")[1])
    waves = out["waves"]
    assert set(waves) == {"clean", "transient", "corrupt-degrade",
                          "breaker-strict", "cache-corruption-burst"}
    for name, w in waves.items():
        # every request resolved, structured, and inside deadline+grace
        assert w["unresolved"] == 0, (name, w)
        assert w["bad_status"] == [], (name, w)
        assert w["over_deadline"] == [], (name, w)
        assert len(w["statuses"]) == w["n"]
    assert waves["clean"]["statuses"] == ["ok"] * waves["clean"]["n"]
    assert waves["clean"]["breaker_trips"] == 0
    assert waves["transient"]["errors"] == 0
    # persistent wire corruption under degrade: served, never erroring out
    cd = waves["corrupt-degrade"]
    assert set(cd["statuses"]) <= {"ok", "degraded"}
    # strict wave: breaker engaged, everything still served degraded
    bs = waves["breaker-strict"]
    assert bs["breaker_trips"] >= 1
    assert set(bs["statuses"]) <= {"degraded", "error"}
    assert bs["statuses"].count("degraded") >= bs["n"] - 1
    # burst wave doubled the offered load and still terminated everything
    cb = waves["cache-corruption-burst"]
    assert cb["n"] == 6
    # quarantine counts track breaker trips, not request volume (no leak)
    assert out["total_quarantines"] <= out["total_breaker_trips"]
    assert out["cache_well_formed"]  # corrupted DB was rebuilt, not kept
    # (the soak uses a fresh server per wave — trace-time faults only bake
    # into newly compiled executors — but one shared schedule DB across all
    # waves; the quarantine-leak assertion is about that shared state)
