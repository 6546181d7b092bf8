"""Guarded execution: degradation ladder, fused guard stats, the fault
matrix, tuner-cache merge semantics, checkpoint fallback, and adversarial
codec properties.

Unit tests run on the default single device.  Anything needing a real
process mesh goes through ``subproc`` (fresh interpreter, 8 virtual
devices).  The full injector x {strict, degrade} matrix is marked
``faults`` — CI runs it as its own chaos job (``pytest -m faults``).
"""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.core import quant, tuner
from repro.core.planconfig import StageEntry
from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.robustness import FaultPlan, faults, health
from repro.robustness.runner import degrade_entry, degrade_schedule


# ---------------------------------------------------------------------------
# degradation ladder (pure host logic)
# ---------------------------------------------------------------------------


def test_degrade_entry_walks_payload_then_impl_then_engine():
    """int8 -> bf16 -> complex64, then pallas -> jnp, then pipelined ->
    fused -> traditional (chunks collapse to 1 with the engine), then the
    bottom (None)."""
    e = ("pipelined", 4, "int8", "pallas", "stacked")
    seen = []
    while e is not None:
        seen.append(tuple(e))
        e = degrade_entry(e)
    assert seen == [
        ("pipelined", 4, "int8", "pallas", "stacked"),
        ("pipelined", 4, "bf16", "pallas", "stacked"),
        ("pipelined", 4, "complex64", "pallas", "stacked"),
        ("pipelined", 4, "complex64", "jnp", "stacked"),
        ("fused", 1, "complex64", "jnp", "stacked"),
        ("traditional", 1, "complex64", "jnp", "stacked"),
    ]
    # a StageEntry's default impl and fusion walk the same ladder; a row
    # that is not a 5-field entry (v5's 4-tuples) is refused
    assert tuple(degrade_entry(StageEntry("pipelined", 4, "int8"))) == (
        "pipelined", 4, "bf16", "jnp", "stacked")
    with pytest.raises(ValueError, match="expected 5"):
        degrade_entry(("pipelined", 4, "int8", "stacked"))


def test_degrade_schedule_targets_only_named_stages():
    sched = (("fused", 1, "int8", "jnp", "stacked"),) * 2
    new = degrade_schedule(sched, stages=(1,))
    # untargeted entries pass through as-is; degraded ones come back as
    # StageEntry rows
    assert new == (("fused", 1, "int8", "jnp", "stacked"),
                   ("fused", 1, "bf16", "jnp", "stacked"))
    assert new[0] is sched[0] and isinstance(new[1], StageEntry)


def test_degrade_schedule_exhaustion():
    bottom = (("traditional", 1, "complex64", "jnp", "stacked"),)
    assert degrade_schedule(bottom) is None
    mixed = (("traditional", 1, "complex64", "jnp", "stacked"),
             ("fused", 1, "int8", "jnp", "stacked"))
    # the targeted stage has no rung left -> exhausted, even though the
    # untargeted one does
    assert degrade_schedule(mixed, stages=(0,)) is None
    assert degrade_schedule(mixed) == (
        ("traditional", 1, "complex64", "jnp", "stacked"),
        ("fused", 1, "bf16", "jnp", "stacked"))


def test_guard_mode_validated():
    from jax.sharding import Mesh

    from repro.core.pfft import ParallelFFT
    from repro.core.planconfig import PlanConfig

    mesh = Mesh(np.array(jax.devices()[:1]), ("p0",))
    with pytest.raises(ValueError, match="unknown guard"):
        ParallelFFT(mesh, (4, 4), grid=("p0",), config=PlanConfig(guard="paranoid"))


# ---------------------------------------------------------------------------
# packed guard stats (the no-collective wire format)
# ---------------------------------------------------------------------------


def _shard_vec(e_in, e_out, probe, nf, sat):
    return health.pack_stats(
        [{"nonfinite": jnp.float32(a), "saturated": jnp.float32(b)}
         for a, b in zip(nf, sat)],
        jnp.float32(e_in), jnp.float32(e_out), jnp.float32(probe))


def test_pack_unpack_partials_sums_shards():
    raw = jnp.concatenate([_shard_vec(1.0, 2.0, 0.0, [3, 0], [0, 5]),
                           _shard_vec(0.5, 1.5, 0.0, [1, 2], [4, 0])])
    stats = health.unpack_partials(np.asarray(raw), nstages=2)
    assert stats["energy_in"] == pytest.approx(1.5)
    assert stats["energy_out"] == pytest.approx(3.5)
    np.testing.assert_allclose(stats["nonfinite"], [4, 2])
    np.testing.assert_allclose(stats["saturated"], [4, 5])


def test_unpack_partials_propagates_nonfinite():
    """A NaN probe on any one shard must survive the host-side sum."""
    a = np.array([0.0, 0.0, np.nan], np.float32)
    b = np.zeros(3, np.float32)
    stats = health.unpack_partials(np.concatenate([a, b]), nstages=0)
    assert math.isnan(stats["probe"])
    assert stats["energy_in"] == 0.0


def test_pack_stats_lossless_is_just_the_triple():
    raw = health.pack_stats([], jnp.float32(1), jnp.float32(2), jnp.float32(3))
    assert raw.shape == (3,)
    np.testing.assert_allclose(np.asarray(raw), [1, 2, 3])


def test_output_probe_flags_nonfinite():
    x = jnp.ones((4, 6), jnp.complex64)
    assert math.isfinite(float(health.output_probe(x, 1)))
    bad = x.at[2, 0].set(jnp.nan + 0j)  # sits on the index-0 plane of axis 1
    assert not math.isfinite(float(health.output_probe(bad, 1)))
    assert not math.isfinite(float(health.output_probe(bad, None)))
    imag_bad = x.at[1, 0].set(1.0 + 1j * jnp.inf)  # imaginary part counts too
    assert not math.isfinite(float(health.output_probe(imag_bad, 1)))


def test_block_energy_matches_numpy():
    rng = np.random.default_rng(0)
    x = (rng.standard_normal((5, 7)) + 1j * rng.standard_normal((5, 7)))
    got = float(health.block_energy(jnp.asarray(x, jnp.complex64)))
    assert got == pytest.approx(float(np.sum(np.abs(x) ** 2)), rel=1e-5)
    r = rng.standard_normal(9).astype(np.float32)
    assert float(health.block_energy(jnp.asarray(r))) == pytest.approx(
        float(np.sum(r * r)), rel=1e-5)


def test_schedule_is_lossy():
    assert not health.schedule_is_lossy([("fused", 1, "complex64", "stacked")])
    assert health.schedule_is_lossy([("fused", 1, "complex64", "stacked"),
                                     ("pipelined", 2, "int8", "stacked")])


# ---------------------------------------------------------------------------
# fault harness (matching + unarmed no-op contract)
# ---------------------------------------------------------------------------


def test_fault_taps_are_noops_when_unarmed():
    x = jnp.arange(4.0)
    assert faults.tap_wire(x) is x
    assert faults.tap_stage_input(x) is x
    assert faults.scale_div() is None


def test_fault_matching_respects_context():
    fp = FaultPlan().corrupt_wire(stage=1, engine="fused", codec="bf16")
    with fp:
        with pytest.raises(RuntimeError, match="already active"):
            FaultPlan().__enter__()
        with faults.stage_context(0, "fused", "bf16"):
            assert faults._matching("corrupt_wire", "payload") == []
        with faults.stage_context(1, "pipelined", "bf16"):
            assert faults._matching("corrupt_wire", "payload") == []
        with faults.stage_context(1, "fused", "bf16"):
            assert len(faults._matching("corrupt_wire", "payload")) == 1
    assert faults._ACTIVE is None


def test_wire_burst_poisons_float_payloads():
    with FaultPlan().corrupt_wire():
        with faults.stage_context(0, "fused", "complex64"):
            y = faults.tap_wire(jnp.ones((3, 3), jnp.complex64))
            assert not bool(jnp.isfinite(jnp.real(y)).all())
            f = faults.tap_wire(jnp.ones(8, jnp.float32))
            assert not bool(jnp.isfinite(f).all())
            # int8 payloads get a bounded magnitude bit flip, never garbage
            q = faults.tap_wire(jnp.zeros(8, jnp.int8))
            assert int(np.abs(np.asarray(q)).max()) == 0x40


# ---------------------------------------------------------------------------
# report classification on a real plan (synthetic stats, subprocess mesh)
# ---------------------------------------------------------------------------

_REPORT_SCRIPT = """
import json, math
import numpy as np, jax
from jax.sharding import Mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig, StageEntry
from repro.robustness import health

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("p0", "p1"))
plan = ParallelFFT(mesh, (16, 8, 8), grid=("p0", "p1"), config=PlanConfig(method="fused"))
S = plan.n_exchanges
N = math.prod(plan.shape)

def stats(e_in=1.0, e_out=None, probe=0.0, nonfinite=None, saturated=None):
    return {"energy_in": e_in,
            "energy_out": (N * e_in) if e_out is None else e_out,
            "probe": probe,
            "nonfinite": np.array(nonfinite if nonfinite else [0.0] * S),
            "saturated": np.array(saturated if saturated else [0.0] * S)}

def report(schedule, st):
    return health.build_report(plan, direction="forward", nfields=1,
                               schedule=schedule, stats=st, guard="strict")

lossless = tuple(("fused", 1, "complex64") for _ in range(S))
lossy = tuple(("fused", 1, "int8") for _ in range(S))
out = {"S": S}

r = report(lossless, stats())
out["clean_lossless"] = {"ok": r.ok, "energy_in": r.energy_in,
                         "rel_err": r.parseval_rel_err}
r = report(lossless, stats(probe=float("nan")))
out["probe_nan"] = {"tripped": list(r.tripped), "global": r.has_global_trip}
r = report(lossy, stats())
out["clean_lossy"] = {"ok": r.ok, "rel_err": r.parseval_rel_err,
                      "tol": r.parseval_tol, "energy_in": r.energy_in}
r = report(lossy, stats(e_out=1.0))
out["parseval"] = {"tripped": list(r.tripped)}
r = report(lossy, stats(e_in=float("nan"), e_out=float("nan")))
out["nan_energy"] = {"tripped": list(r.tripped)}
elems1 = r.stages[1].elems
r = report(lossy, stats(saturated=[0.0, 0.10 * elems1]))
out["saturation"] = {"tripped": list(r.tripped),
                     "idx": list(r.tripped_stage_indices()),
                     "global": r.has_global_trip,
                     "sat_fraction": r.stages[1].sat_fraction}
r = report(lossy, stats(nonfinite=[2.0, 0.0]))
out["stage_nonfinite"] = {"tripped": list(r.tripped),
                          "idx": list(r.tripped_stage_indices())}
print("REPORT=" + json.dumps(out))
"""


def test_build_report_classification(subproc):
    out = json.loads(subproc(_REPORT_SCRIPT).split("REPORT=")[1])
    assert out["S"] == 2
    c = out["clean_lossless"]
    # lossless schedules pay no energy bracket: probe-only, energies None
    assert c["ok"] and c["energy_in"] is None and c["rel_err"] is None
    p = out["probe_nan"]
    assert p["tripped"] == ["output:nonfinite"] and p["global"]
    cl = out["clean_lossy"]
    assert cl["ok"] and cl["energy_in"] == 1.0
    assert cl["rel_err"] == pytest.approx(0.0) and cl["tol"] >= 2 * 2e-1
    assert "parseval" in out["parseval"]["tripped"]
    assert {"input:nonfinite", "output:nonfinite"} <= set(
        out["nan_energy"]["tripped"])
    s = out["saturation"]
    assert s["tripped"] == ["stage1:saturation"] and s["idx"] == [1]
    # StageHealth stores integral counts, so the fraction floors slightly
    assert not s["global"] and s["sat_fraction"] == pytest.approx(0.10, rel=0.05)
    n = out["stage_nonfinite"]
    assert "stage0:nonfinite" in n["tripped"] and n["idx"] == [0]


# ---------------------------------------------------------------------------
# PLAN008: guard="off" artifacts carry zero guard eqns
# ---------------------------------------------------------------------------

_PLAN008_SCRIPT = """
import json
import numpy as np, jax
from jax.sharding import Mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig, StageEntry
from repro.analysis import planlint

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("p0", "p1"))
def mk(guard):
    return ParallelFFT(mesh, (16, 8, 8), grid=("p0", "p1"),
                       config=PlanConfig(method="fused", guard=guard))
a_off = planlint.audit_plan(mk("off"))
a_on = planlint.audit_plan(mk("strict"))
print("PLAN008=" + json.dumps({
    "off": {"ok": a_off.ok, "guard_eqns": a_off.observed["guard_eqns"],
            "codes": sorted({v.code for v in a_off.violations})},
    "on": {"ok": a_on.ok, "guard_eqns": a_on.observed["guard_eqns"],
           "codes": sorted({v.code for v in a_on.violations})},
}))
"""


def test_plan008_guard_presence(subproc):
    out = json.loads(subproc(_PLAN008_SCRIPT).split("PLAN008=")[1])
    # guard="off" compiles with zero robustness/-attributed eqns (the
    # bit-identical contract) and still satisfies every plan contract
    assert out["off"]["ok"], out["off"]["codes"]
    assert out["off"]["guard_eqns"] == 0
    # a guarded plan carries guard eqns yet keeps the same contracts
    # (no realignment pass, same collective count and wire bytes)
    assert out["on"]["ok"], out["on"]["codes"]
    assert out["on"]["guard_eqns"] > 0


# ---------------------------------------------------------------------------
# the fault matrix: every injector x {strict, degrade} (chaos CI job)
# ---------------------------------------------------------------------------

_MATRIX_SCRIPT = """
import json, pathlib, tempfile
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig, StageEntry
from repro.robustness import FaultPlan
from repro.robustness.runner import GuardError

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("p0", "p1"))
SHAPE, GRID = (16, 8, 8), ("p0", "p1")
rng = np.random.default_rng(0)
x = jnp.asarray((rng.standard_normal(SHAPE)
                 + 1j * rng.standard_normal(SHAPE)).astype(np.complex64))
base = ParallelFFT(mesh, SHAPE, grid=GRID, config=PlanConfig(method="fused"))
y_ref = base.forward(x)
x_back_ref = base.backward(y_ref)

def plan(**kw):
    kw.setdefault("method", "fused")
    return ParallelFFT(mesh, SHAPE, grid=GRID, config=PlanConfig(**kw))

def rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))

def strict_case(fp, **kw):
    with fp:
        try:
            plan(guard="strict", **kw).forward(x)
            return {"raised": False}
        except GuardError as e:
            return {"raised": True,
                    "tripped": list(e.report.tripped) if e.report else []}

def degrade_case(fp, **kw):
    with fp:
        y, rep = plan(guard="degrade", **kw).forward(x)
    return {"ok": rep.ok, "kinds": [t["kind"] for t in rep.transitions],
            "attempts": rep.attempts,
            "schedule": [list(e) for e in rep.schedule],
            "rel": rel(y, y_ref)}

out = {}

y, rep = plan(guard="strict").forward(x)
out["clean_strict"] = {"ok": rep.ok, "energy_in": rep.energy_in,
                       "rel_err": rep.parseval_rel_err, "rel": rel(y, y_ref)}

y, rep = plan(guard="strict", comm_dtype="bf16").forward(x)
out["clean_bf16"] = {"ok": rep.ok, "rel_err": rep.parseval_rel_err,
                     "tol": rep.parseval_tol,
                     "has_energy": rep.energy_in is not None}

c64_burst = lambda: FaultPlan().corrupt_wire(engine="fused", codec="complex64")
out["wire_c64_strict"] = strict_case(c64_burst())
out["wire_c64_degrade"] = degrade_case(c64_burst())

nan_in = lambda: FaultPlan().nan_input(stage=0, engine="fused")
out["nan_input_strict"] = strict_case(nan_in())
out["nan_input_degrade"] = degrade_case(nan_in())

bf16_burst = lambda: FaultPlan().corrupt_wire(engine="fused", codec="bf16")
out["wire_bf16_strict"] = strict_case(bf16_burst(), comm_dtype="bf16")
out["wire_bf16_degrade"] = degrade_case(bf16_burst(), comm_dtype="bf16")

out["int8_scale_degrade"] = degrade_case(
    FaultPlan().corrupt_wire(engine="fused", codec="int8", label="scale"),
    comm_dtype="int8")

sat = lambda: FaultPlan().saturate(engine="fused")
out["saturate_strict"] = strict_case(sat(), comm_dtype="int8")
out["saturate_degrade"] = degrade_case(sat(), comm_dtype="int8")

with sat():
    xb, rep = plan(guard="degrade", comm_dtype="int8").backward(y_ref)
out["saturate_backward"] = {"ok": rep.ok, "direction": rep.direction,
                            "kinds": [t["kind"] for t in rep.transitions],
                            "rel": rel(xb, x_back_ref)}

cache = pathlib.Path(tempfile.mkdtemp()) / "tuner.json"
p = plan(method="auto", guard="degrade", tuner_cache=str(cache))
# must be inside the live candidate set or the entry is rejected as
# malformed (and simply retuned) instead of replayed and quarantined
poisoned = tuple(StageEntry("pipelined", 2, "complex64") for _ in range(p.n_exchanges))
FaultPlan.poison_cache(cache, p, poisoned)
with FaultPlan().fail_compile(engine="pipelined"):
    y, rep = p.forward(x)
from repro.core import tuner as _tuner
disk = _tuner.load_cache(cache)
qcounts = [e.get("quarantines") for e in disk.values()
           if isinstance(e, dict) and e.get("quarantines")]
out["poison_auto"] = {"ok": rep.ok,
                      "kinds": [t["kind"] for t in rep.transitions],
                      "rel": rel(y, y_ref), "quarantines": qcounts,
                      "fired": sorted({f["kind"] for f in rep.fired_faults})}

with FaultPlan().nan_input():  # wildcard: no ladder rung escapes it
    try:
        plan(guard="degrade").forward(x)
        out["exhausted"] = {"raised": False}
    except GuardError:
        out["exhausted"] = {"raised": True}

xs = jnp.stack([x, 2 * x, x - 1])
ys, rep = plan(guard="strict").forward_many(xs)
out["batched_clean"] = {"ok": rep.ok, "nfields": rep.nfields,
                        "rel": rel(ys[1], 2 * np.asarray(y_ref))}

print("MATRIX=" + json.dumps(out))
"""


@pytest.mark.faults
def test_fault_matrix(subproc):
    """Every injector under strict (structured GuardError, never a silent
    bad spectrum) and degrade (ladder moves off the faulted config and the
    recovered result matches the healthy plan)."""
    out = json.loads(subproc(_MATRIX_SCRIPT).split("MATRIX=")[1])

    c = out["clean_strict"]
    assert c["ok"] and c["rel"] < 1e-5
    assert c["energy_in"] is None and c["rel_err"] is None

    b = out["clean_bf16"]
    assert b["ok"] and b["has_energy"] and b["rel_err"] < b["tol"]

    s = out["wire_c64_strict"]
    assert s["raised"] and "output:nonfinite" in s["tripped"]
    d = out["wire_c64_degrade"]
    assert d["ok"] and d["kinds"] and d["rel"] < 1e-5
    assert any(e[0] != "fused" for e in d["schedule"])  # engine rung moved

    assert out["nan_input_strict"]["raised"]
    d = out["nan_input_degrade"]
    assert d["ok"] and d["kinds"] and d["rel"] < 1e-5

    s = out["wire_bf16_strict"]
    assert s["raised"] and any("nonfinite" in t for t in s["tripped"])
    d = out["wire_bf16_degrade"]
    assert d["ok"] and d["kinds"] and d["rel"] < 1e-4
    assert any(e[2] == "complex64" for e in d["schedule"])  # payload widened

    d = out["int8_scale_degrade"]
    assert d["ok"] and d["kinds"] and d["rel"] < 0.05
    assert any(e[2] != "int8" for e in d["schedule"])

    s = out["saturate_strict"]
    assert s["raised"] and any("saturation" in t for t in s["tripped"])
    d = out["saturate_degrade"]
    assert d["ok"] and d["kinds"] and d["rel"] < 0.05

    d = out["saturate_backward"]
    assert d["ok"] and d["direction"] == "backward" and d["rel"] < 0.05

    d = out["poison_auto"]
    assert d["ok"] and "retune" in d["kinds"] and d["rel"] < 1e-4
    assert d["quarantines"] and "compile_fail" in d["fired"]

    assert out["exhausted"]["raised"]  # zero silent-corruption outcomes

    bc = out["batched_clean"]
    assert bc["ok"] and bc["nfields"] == 3 and bc["rel"] < 1e-5


# ---------------------------------------------------------------------------
# tuner cache: merge-on-save closes the concurrent-writer lost update
# ---------------------------------------------------------------------------


def _entry(method):
    return {"schedule": [[method, 1, "complex64"]], "timings": {}}


def test_save_cache_merge_keeps_concurrent_writer_keys(tmp_path):
    """The stale-read race: worker A read the cache before worker B wrote
    plan B's entry; A's delta write must overlay, not clobber."""
    path = tmp_path / "cache.json"
    assert tuner.save_cache(path, {"plan-b": _entry("traditional")})
    # A writes only its own key, computed against a pre-B view
    assert tuner.save_cache(path, {"plan-a": _entry("fused")})
    disk = tuner.load_cache(path)
    assert set(disk) == {"plan-a", "plan-b"}
    assert disk["plan-b"] == _entry("traditional")
    tuner.save_cache(path, {"only": _entry("fused")}, merge=False)
    assert set(tuner.load_cache(path)) == {"only"}


def test_quarantine_mark_survives_concurrent_merge(tmp_path):
    path = tmp_path / "cache.json"
    tuner.save_cache(path, {"plan-a": _entry("fused")})
    assert tuner.quarantine(path, "plan-a", "injected failure") == 1
    tuner.save_cache(path, {"plan-b": _entry("traditional")})
    disk = tuner.load_cache(path)
    assert disk["plan-a"]["bad"]["reason"] == "injected failure"
    assert "plan-b" in disk
    # the lifetime count keeps climbing toward the runner's retune cap
    assert tuner.quarantine(path, "plan-a", "again") == 2


def test_save_cache_atomic_never_partial(tmp_path):
    """Readers racing a save see either the old or the new file, never a
    truncated one — the write goes through a same-dir temp + os.replace."""
    path = tmp_path / "cache.json"
    tuner.save_cache(path, {f"k{i}": _entry("fused") for i in range(50)})
    assert json.loads(path.read_text())  # well-formed at rest
    assert not list(tmp_path.glob("*.tmp"))  # no temp droppings


# ---------------------------------------------------------------------------
# checkpoint fallback (the guarded-pipeline restart path)
# ---------------------------------------------------------------------------


def _ck_tree():
    return {"a": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones(5, jnp.float32)}


def _corrupt_leaf(step_dir, key="a"):
    target = step_dir / f"{key}.npy"
    np.save(target, np.load(target) + 1)


def test_load_checkpoint_falls_back_past_corruption(tmp_path):
    t = _ck_tree()
    save_checkpoint(tmp_path, 1, t)
    t2 = {"a": t["a"] * 2, "b": t["b"] * 2}
    _corrupt_leaf(save_checkpoint(tmp_path, 2, t2))
    with pytest.warns(UserWarning, match="skipping corrupt checkpoint step 2"):
        out, manifest = load_checkpoint(tmp_path, t)
    assert manifest["step"] == 1
    assert [d["step"] for d in manifest["skipped_steps"]] == [2]
    assert "checksum" in manifest["skipped_steps"][0]["error"]
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(t["a"]))
    # an explicit step= keeps the old fail-fast contract
    with pytest.raises(IOError, match="checksum"):
        load_checkpoint(tmp_path, t, step=2)


def test_load_checkpoint_all_corrupt_raises_with_detail(tmp_path):
    t = _ck_tree()
    for s in (1, 2):
        _corrupt_leaf(save_checkpoint(tmp_path, s, t))
    with pytest.warns(UserWarning):
        with pytest.raises(IOError, match="every checkpoint"):
            load_checkpoint(tmp_path, t)


# ---------------------------------------------------------------------------
# adversarial codec properties (hypothesis, or the _hyp fallback sweep)
# ---------------------------------------------------------------------------


@settings(max_examples=24, deadline=None)
@given(log_scale=st.floats(-30, 30), n=st.integers(1, 64))
def test_int8_roundtrip_error_bounded(log_scale, n):
    """Round-trip error stays within half a quantization step per element
    across ~60 decades of input magnitude (denormal-adjacent to near-f32
    overflow)."""
    rng = np.random.default_rng(n)
    x = (rng.standard_normal((2, n)) * 10.0 ** log_scale).astype(np.float32)
    q, scale = quant.quantize_int8(jnp.asarray(x), block_axis=0)
    back = np.asarray(quant.dequantize_int8(q, scale))
    bound = np.broadcast_to(np.asarray(scale), x.shape) * 0.5
    assert np.all(np.abs(back - x) <= bound * 1.01 + 1e-38)


@settings(max_examples=16, deadline=None)
@given(nbad=st.integers(1, 5), kind=st.sampled_from(["nan", "inf", "-inf"]))
def test_int8_nonfinite_sanitized_and_counted(nbad, kind):
    """NaN/Inf inputs must not poison the block scale: bad elements
    quantize to 0, everything decodes finite, and the stats hook reports
    the exact count."""
    rng = np.random.default_rng(nbad)
    x = rng.standard_normal((3, 32)).astype(np.float32)
    bad = rng.choice(x.size, size=nbad, replace=False)
    x.reshape(-1)[bad] = {"nan": np.nan, "inf": np.inf, "-inf": -np.inf}[kind]
    q, scale, stats = quant.quantize_int8(jnp.asarray(x), block_axis=0,
                                          with_stats=True)
    assert np.all(np.isfinite(np.asarray(scale)))
    assert np.all(np.isfinite(np.asarray(quant.dequantize_int8(q, scale))))
    assert float(stats["nonfinite"]) == nbad
    np.testing.assert_array_equal(np.asarray(q).reshape(-1)[bad], 0)


def test_int8_all_zero_block():
    q, scale = quant.quantize_int8(jnp.zeros((2, 8)), block_axis=0)
    s = np.asarray(scale)
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.isfinite(s)) and np.all(s > 0)
    assert np.all(np.asarray(quant.dequantize_int8(q, scale)) == 0)


@settings(max_examples=12, deadline=None)
@given(ratio=st.floats(1.0, 1e8))
def test_int8_tuple_block_axis_isolates_field_scales(ratio):
    """Stacked fields of wildly different magnitude: per-(field, chunk)
    blocks mean the small field's error is set by its own max-abs, not the
    big field's (the batched-exchange payload contract)."""
    rng = np.random.default_rng(3)
    small = rng.standard_normal((4, 16)).astype(np.float32)
    big = (rng.standard_normal((4, 16)) * ratio).astype(np.float32)
    x = jnp.asarray(np.stack([small, big]))  # (field, chunk, n)
    q, scale = quant.quantize_int8(x, block_axis=(0, 1))
    back = np.asarray(quant.dequantize_int8(q, scale))
    err_small = float(np.max(np.abs(back[0] - small)))
    assert err_small <= float(np.abs(small).max()) / 127 * 0.5 * 1.01 + 1e-12


@settings(max_examples=12, deadline=None)
@given(log_scale=st.floats(-20, 20))
def test_bf16_roundtrip_relative_error(log_scale):
    rng = np.random.default_rng(7)
    x = (rng.standard_normal(256) * 10.0 ** log_scale).astype(np.float32)
    back = np.asarray(quant.decode_bf16(quant.encode_bf16(jnp.asarray(x))))
    # round-to-nearest-even on an 8-bit significand: rel err <= 2^-9 + slack
    assert np.all(np.abs(back - x) <= np.abs(x) * 2.0 ** -8 + 1e-38)


def test_complex_planes_roundtrip_exact():
    rng = np.random.default_rng(5)
    x = (rng.standard_normal((3, 4))
         + 1j * rng.standard_normal((3, 4))).astype(np.complex64)
    back = np.asarray(quant.planes_to_complex(
        quant.complex_to_planes(jnp.asarray(x))))
    np.testing.assert_array_equal(back, x)
