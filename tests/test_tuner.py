"""Exchange-schedule autotuner: candidate sweep (engines × comm_dtype
payloads × exchange impls × batch fusions), schema-v6 disk cache
round-trip, stale caches retuned, atomic merge writes, quarantine
marks."""

import json
import threading

import pytest

from repro.core import tuner


def test_tuner_cache_roundtrip(subproc, tmp_path):
    """Tuning writes the schedule+timings to disk; a fresh plan (fresh
    process, empty memo) must reload it instead of re-benchmarking."""
    cache = tmp_path / "tune" / "fft_tuner.json"
    code = f"""
import json
import jax, numpy as np
from repro.core import tuner
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig

cache = {str(cache)!r}
mesh = make_mesh((2, 2), ("p0", "p1"))
plan = ParallelFFT(mesh, (16, 8, 8), ("p0", "p1"), config=PlanConfig(method="auto", tuner_cache=cache))
sched = plan.schedule
assert len(sched) == plan.n_exchanges == 2
for method, chunks, comm_dtype, impl, fusion in sched:
    assert method in ("fused", "traditional", "pipelined")
    assert chunks >= 1
    # default accuracy budget is lossless: only complex64 may be picked
    assert comm_dtype == "complex64"
    # no pallas budget requested: every entry runs the jnp reference impl
    assert impl == "jnp" and fusion == "stacked"

disk = json.loads(open(cache).read())
key = tuner.plan_key(plan)
assert key in disk
assert json.loads(key)["schema"] == tuner.SCHEMA_VERSION
assert "device_kind" in json.loads(key)
assert [tuple(s) for s in disk[key]["schedule"]] == list(sched)
# every candidate was timed for both exchange stages
stages = disk[key]["timings"]
assert len(stages) == 2
for per in stages.values():
    timed = {{k: v for k, v in per.items() if ":" not in k}}  # drop error notes
    assert set(timed) == {{tuner._tag(c) for c in tuner.DEFAULT_CANDIDATES}}
    assert all(t > 0 for t in timed.values())

# fresh-memo reload: poison tune_plan; a cache hit must not call it
tuner._MEMO.clear()
def boom(*a, **k):
    raise AssertionError("cache miss: tune_plan re-ran")
tuner.tune_plan = boom
plan2 = ParallelFFT(mesh, (16, 8, 8), ("p0", "p1"), config=PlanConfig(method="auto", tuner_cache=cache))
assert plan2.schedule == sched
print("TUNER CACHE OK", json.dumps([list(s) for s in sched]))
"""
    out = subproc(code, ndev=4)
    assert "TUNER CACHE OK" in out


def test_tuner_comm_dtype_budget_cache_roundtrip(subproc, tmp_path):
    """An int8 accuracy budget widens the sweep to engines × {complex64,
    bf16, int8}; per-stage comm_dtype choices round-trip through the disk
    cache into a fresh process (issue acceptance criterion)."""
    cache = tmp_path / "fft_tuner.json"
    code = f"""
import json
from repro.core import tuner
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig

cache = {str(cache)!r}
mesh = make_mesh((2, 2), ("p0", "p1"))
plan = ParallelFFT(mesh, (16, 8, 8), ("p0", "p1"),
                   config=PlanConfig(method="auto", comm_dtype="int8", tuner_cache=cache))
sched = plan.schedule
assert len(sched) == 2
for method, chunks, comm_dtype, impl, fusion in sched:
    assert comm_dtype in ("complex64", "bf16", "int8")

disk = json.loads(open(cache).read())
key = tuner.plan_key(plan)
want_tags = {{tuner._tag(c) for c in tuner.candidates_for("int8")}}
for per in disk[key]["timings"].values():
    assert {{k for k in per if ":" not in k}} == want_tags

# a fresh process (memo empty) must reload the same schedule
tuner._MEMO.clear()
tuner.tune_plan = None  # cache hit must not benchmark
plan2 = ParallelFFT(mesh, (16, 8, 8), ("p0", "p1"),
                    config=PlanConfig(method="auto", comm_dtype="int8", tuner_cache=cache))
assert plan2.schedule == sched
print("BUDGET CACHE OK", json.dumps([list(s) for s in sched]))
"""
    out = subproc(code, ndev=4)
    assert "BUDGET CACHE OK" in out


def test_stale_or_corrupt_cache_ignored_and_rewritten(subproc, tmp_path):
    """Cache migration (PR 4 satellite): a stale-schema (or corrupt) cache
    file dropped in the cache path before ``method="auto"`` must be
    silently ignored and rewritten with a valid current-schema entry —
    never raise.  Covers: invalid JSON, a JSON non-dict, a stale v3-style
    entry set, a matching current key whose entry body is malformed, and a
    schema-5 cache with 3-field rows."""
    cache = tmp_path / "fft_tuner.json"
    code = f"""
import json
from pathlib import Path
from repro.core import tuner
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig

cache = Path({str(cache)!r})
mesh = make_mesh((2, 2), ("p0", "p1"))
stale_payloads = [
    '{{ not json',                                     # corrupt bytes
    '[1, 2, 3]',                                       # valid JSON, wrong container
    json.dumps({{'{{"schema": 3, "mesh": []}}':        # v3-era entry set
                 {{"schedule": [["fused", 1, "complex64"]], "timings": {{}}}}}}),
]
for payload in stale_payloads:
    cache.write_text(payload)
    tuner._MEMO.clear()
    plan = ParallelFFT(mesh, (16, 8, 8), ("p0", "p1"),
                       config=PlanConfig(method="auto", tuner_cache=str(cache)))
    sched = plan.schedule  # must tune and rewrite, not raise
    assert len(sched) == plan.n_exchanges == 2
    disk = json.loads(cache.read_text())  # rewritten as valid JSON
    key = tuner.plan_key(plan)
    assert key in disk
    assert json.loads(key)["schema"] == tuner.SCHEMA_VERSION == 6
    print("ok", payload[:30])

# a *matching* v4 key whose entry body is junk must also fall back to
# retuning instead of raising or replaying garbage
plan = ParallelFFT(mesh, (16, 8, 8), ("p0", "p1"),
                   config=PlanConfig(method="auto", tuner_cache=str(cache)))
key = tuner.plan_key(plan)
ok_row = ["fused", 1, "complex64", "jnp", "stacked"]
for bad_entry in ("garbage", {{"schedule": "garbage"}}, {{"schedule": [["x"]]}},
                  {{"schedule": [ok_row]}},  # wrong stage count
                  # structurally valid but unknown engine / payload values:
                  # must retune, not raise later inside the executor
                  {{"schedule": [["bogus", 1, "complex64", "jnp", "stacked"], ok_row]}},
                  {{"schedule": [["fused", 1, "float8", "jnp", "stacked"], ok_row]}}):
    cache.write_text(json.dumps({{key: bad_entry}}))
    tuner._MEMO.clear()
    p = ParallelFFT(mesh, (16, 8, 8), ("p0", "p1"),
                    config=PlanConfig(method="auto", tuner_cache=str(cache)))
    sched = p.schedule
    assert len(sched) == 2 and all(len(e) == 5 for e in sched)
    disk = json.loads(cache.read_text())
    assert [tuple(s) for s in disk[key]["schedule"]] == list(sched)

# entries that parse fine but name candidates OUTSIDE the live sweep (a
# cache written by a different build, or hand-edited) must retune too —
# replaying them would execute a schedule the tuner never timed
for poisoned in ([["pipelined", 16, "complex64", "jnp", "stacked"], ok_row],
                 [["fused", 1, "int8", "jnp", "stacked"], ok_row]):
    cache.write_text(json.dumps({{key: {{"schedule": poisoned, "timings": {{}}}}}}))
    tuner._MEMO.clear()
    p = ParallelFFT(mesh, (16, 8, 8), ("p0", "p1"),
                    config=PlanConfig(method="auto", tuner_cache=str(cache)))
    sched = p.schedule
    live = set(tuner.candidates_for(None))
    assert all(tuple(e) in live for e in sched), (poisoned, sched)
    assert list(map(list, sched)) != poisoned

# a schema-5 cache (3-field rows, under its v5 key and under the live key)
# is a stale cache like any other: it retunes, never replays or raises
fields = tuner._key_fields(plan, 1)
fields["schema"] = 5  # the key v5 wrote: its jnp-only 3-field candidate tags
fields["candidates"] = sorted(f"{{m}}@{{c}}@complex64" for m, c in tuner.ENGINE_CANDIDATES)
v5_key = json.dumps(fields, sort_keys=True, default=str)
v5_rows = [["fused", 1, "complex64"], ["traditional", 1, "complex64"]]
tuned = []
tune_plan = tuner.tune_plan
def counting(*a, **k):
    tuned.append(1)
    return tune_plan(*a, **k)
tuner.tune_plan = counting
for entries in ({{v5_key: {{"schedule": v5_rows, "timings": {{}}}}}},
                {{key: {{"schedule": v5_rows, "timings": {{}}}}}}):
    cache.write_text(json.dumps(entries))
    tuner._MEMO.clear()
    p = ParallelFFT(mesh, (16, 8, 8), ("p0", "p1"),
                    config=PlanConfig(method="auto", tuner_cache=str(cache)))
    sched = p.schedule
    assert all(len(e) == 5 for e in sched), sched
    disk = json.loads(cache.read_text())
    assert [tuple(s) for s in disk[key]["schedule"]] == list(sched)
    assert "migrated_from_schema" not in disk[key]
assert len(tuned) == 2, tuned
tuner.tune_plan = tune_plan
print("STALE CACHE MIGRATION OK")
"""
    out = subproc(code, ndev=4)
    assert "STALE CACHE MIGRATION OK" in out


def test_plan_key_discriminates():
    """Key must change with anything that changes stage shapes/engines."""
    from repro.core.meshutil import make_mesh
    from repro.core.pfft import ParallelFFT
    from repro.core.planconfig import PlanConfig

    mesh = make_mesh((1, 1), ("p0", "p1"))
    auto = PlanConfig(method="auto")
    base = ParallelFFT(mesh, (8, 8, 8), ("p0",), config=auto)
    keys = {tuner.plan_key(base)}
    for plan in (
        ParallelFFT(mesh, (8, 8, 16), ("p0",), config=auto),
        ParallelFFT(mesh, (8, 8, 8), ("p0", "p1"), config=auto),
        ParallelFFT(mesh, (8, 8, 8), ("p0",), config=auto,
                    transforms=("c2c", "c2c", "r2c")),
        ParallelFFT(mesh, (8, 8, 8), ("p0",), config=auto.replace(impl="matmul")),
        ParallelFFT(mesh, (8, 8, 8), ("p0",), config=auto.replace(comm_dtype="bf16")),
        ParallelFFT(mesh, (8, 8, 8), ("p0",), config=auto.replace(comm_dtype="int8")),
    ):
        keys.add(tuner.plan_key(plan))
    assert len(keys) == 7
    # batch size is part of the key: 1-field and N-field schedules never collide
    keys.add(tuner.plan_key(base, nfields=3))
    keys.add(tuner.plan_key(base, nfields=8))
    assert len(keys) == 9
    # keys are deterministic and json-round-trippable
    assert tuner.plan_key(base) == tuner.plan_key(base)
    decoded = json.loads(tuner.plan_key(base))
    assert decoded["shape"] == [8, 8, 8]
    # hardware identity: timings from different device generations under
    # the same backend string must not collide
    assert decoded["schema"] == tuner.SCHEMA_VERSION
    assert decoded["device_kind"]
    assert decoded["backend"]


def test_candidates_cover_issue_matrix():
    assert ("fused", 1) in tuner.ENGINE_CANDIDATES
    assert ("traditional", 1) in tuner.ENGINE_CANDIDATES
    for c in (2, 4, 8):
        assert ("pipelined", c) in tuner.ENGINE_CANDIDATES
    # default budget is lossless
    assert set(e.comm_dtype for e in tuner.DEFAULT_CANDIDATES) == {"complex64"}
    # the ladder is monotone: each budget adds payloads, never drops them
    assert set(tuner.candidates_for("bf16")) > set(tuner.candidates_for(None))
    assert set(tuner.candidates_for("int8")) > set(tuner.candidates_for("bf16"))
    for e in tuner.candidates_for("int8"):
        assert (e.method, e.chunks) in tuner.ENGINE_CANDIDATES
        assert e.comm_dtype in ("complex64", "bf16", "int8")
        assert e.impl == "jnp"  # no pallas budget requested
    # a pallas budget adds fused-kernel candidates for every lossy payload
    pall = tuner.candidates_for("int8", "pallas")
    assert set(pall) > set(tuner.candidates_for("int8"))
    extra = set(pall) - set(tuner.candidates_for("int8"))
    assert extra and all(e.impl == "pallas" and e.comm_dtype != "complex64"
                         for e in extra)
    # batched candidates: every single-field candidate x every fusion mode
    batched = tuner.batched_candidates_for("bf16")
    assert len(batched) == 3 * len(tuner.candidates_for("bf16"))
    assert {e.batch_fusion for e in batched} == {
        "stacked", "pipelined-across-fields", "per-field"}
    assert {e._replace(batch_fusion="stacked") for e in batched} == set(
        tuner.candidates_for("bf16"))


def test_save_cache_atomic(tmp_path):
    """save_cache must never leave a partially-written cache visible: the
    final file is always complete JSON and no temp droppings remain."""
    path = tmp_path / "sub" / "cache.json"
    data = {"k": {"schedule": [["fused", 1, "complex64"]], "timings": {}}}
    assert tuner.save_cache(path, data)
    assert json.loads(path.read_text()) == data
    # overwrite with concurrent writers: every reader observes valid JSON
    errs = []

    def writer(i):
        try:
            assert tuner.save_cache(path, {f"key{i}": i})
            json.loads(path.read_text())
        except Exception as e:  # pragma: no cover - failure reporting
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    json.loads(path.read_text())  # final state is one writer's full payload
    # no temp files left behind (the advisory .lock file is expected)
    leftovers = [p for p in path.parent.iterdir()
                 if p.name not in (path.name, path.name + ".lock")]
    assert leftovers == []
    # merge semantics: every writer's key survived (the in-process flock
    # serialized the read-merge-write cycles)
    final = json.loads(path.read_text())
    assert {f"key{i}" for i in range(8)} <= set(final)


def test_quarantine_locks_without_self_deadlock(tmp_path):
    """quarantine holds the cross-process file lock across its whole
    read-bump-write and must not re-acquire it from a second fd inside
    save_cache (flock is per open-file-description: that would deadlock).
    Regression: this call simply has to return."""
    path = tmp_path / "cache.json"
    tuner.save_cache(path, {"k": {"schedule": [["fused", 1, "complex64"]],
                                  "timings": {}}})
    assert tuner.quarantine(path, "k", "boom") == 1
    assert tuner.quarantine(path, "k", "boom again") == 2
    entry = json.loads(path.read_text())["k"]
    assert entry["bad"]["reason"] == "boom again"
    assert entry["quarantines"] == 2


def test_save_cache_cross_process_lock(tmp_path):
    """Concurrent *processes* merging disjoint keys into one cache must not
    lose updates: the fcntl.flock around the read-merge-write cycle closes
    the interleave where two writers read the same snapshot and the later
    os.replace drops the earlier writer's keys."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    fcntl = pytest.importorskip("fcntl")
    assert fcntl  # the lock is a no-op without it; nothing to test then
    path = tmp_path / "shared.json"
    nproc, nkeys = 4, 12
    code = """
import sys
from repro.core import tuner
path, wid = sys.argv[1], int(sys.argv[2])
for j in range({nkeys}):
    assert tuner.save_cache(path, {{"w%d-k%d" % (wid, j): {{"v": wid}}}})
print("WRITER-DONE")
""".format(nkeys=nkeys)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    procs = [subprocess.Popen([sys.executable, "-c", code, str(path), str(i)],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for i in range(nproc)]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
        assert "WRITER-DONE" in out
    final = json.loads(path.read_text())
    expect = {f"w{i}-k{j}" for i in range(nproc) for j in range(nkeys)}
    missing = expect - set(final)
    assert not missing, f"lost {len(missing)} updates: {sorted(missing)[:5]}"


def test_poison_cache_replaces_a_memoized_schedule(subproc, tmp_path):
    """A schedule this process already resolved is memoized; poisoning the
    cache entry afterwards must still be what the next resolve replays
    (otherwise a fault wave silently runs the earlier winner)."""
    cache = tmp_path / "fft_tuner.json"
    out = subproc(f"""
from repro.core import tuner
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig
from repro.robustness.faults import FaultPlan

mesh = make_mesh((2,), ("p0",))
cfg = PlanConfig(method="auto", comm_dtype="bf16", tuner_cache={str(cache)!r})
first = ParallelFFT(mesh, (8, 8, 8), ("p0",), config=cfg).schedule
poison = tuple(("traditional" if s.method != "traditional" else "fused", 1,
                "bf16", "jnp", "stacked") for s in first)
FaultPlan.poison_cache({str(cache)!r}, ParallelFFT(mesh, (8, 8, 8), ("p0",), config=cfg), poison)
again = ParallelFFT(mesh, (8, 8, 8), ("p0",), config=cfg).schedule
assert [tuple(s) for s in again] == list(poison), (first, again)
print("POISON REPLAYED")
""", ndev=2)
    assert "POISON REPLAYED" in out
