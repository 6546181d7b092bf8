"""planlint contract suite.

Positive side: the auditor certifies every engine x transform spec x wire
payload x batch fusion on slab and pencil meshes, agrees with the analytic
``comm_bytes_per_device``/``model_time_s`` models, and the fused engine
shows **zero** engine realignment ops (the paper's no-realignment
invariant, machine-checked).  Negative side: deliberately mis-claimed
schedules (a traditional plan claiming fused, a quantized plan claiming
lossless, ...) must each be caught with the right violation code.

Multi-device audits run in subprocesses (conftest.run_devices); the
srclint checks are pure AST and run in-process on fabricated sources.
"""

import json
from pathlib import Path

from repro.analysis.srclint import lint_paths

REPO = Path(__file__).resolve().parent.parent

_PRELUDE = """
import json
from repro.analysis.planlint import audit_plan
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig, StageEntry

mesh = make_mesh((2, 2), ("p0", "p1"))
PENCIL, SLAB = ("p0", "p1"), ("p0",)

def codes(rep):
    return sorted({v.code for v in rep.violations})
"""


def test_audit_engines_specs_directions(subproc):
    """Every engine x {c2c, r2c, mixed} on the pencil mesh (plus a slab
    fused run) audits clean, forward and backward; fused shows zero engine
    realignment ops and traditional exactly its documented copies."""
    code = _PRELUDE + """
SPECS = {"c2c": None, "r2c": ("c2c", "c2c", "r2c"),
         "mixed": ("dct2", "c2c", "r2c")}
for method in ("fused", "traditional", "pipelined"):
    for sname, transforms in SPECS.items():
        plan = ParallelFFT(mesh, (8, 8, 8), PENCIL,
                           transforms=transforms,
                           config=PlanConfig(method=method, chunks=2))
        rep = audit_plan(plan, label=f"{method}/{sname}")
        assert rep.ok, (method, sname, codes(rep), rep.violations)
        if method == "fused":
            # the no-realignment invariant, observed in the artifact
            assert rep.observed["engine_transposes"] == 0
            assert rep.observed["engine_concats"] == 0
        elif method == "traditional":
            assert (rep.observed["engine_transposes"]
                    == rep.expected["engine_transposes"] > 0)
        else:  # pipelined: one launch per slice, slices reassembled
            assert (rep.observed["jaxpr_all_to_alls"]
                    == rep.expected["launches"] > plan.n_exchanges)
            assert rep.observed["engine_concats"] == rep.expected["engine_concats"]
        json.dumps(rep.to_dict(), default=str)  # report is serializable
        s = rep.summary()
        assert s["ok"] and s["violations"] == []
        assert s["wire_bytes"] == rep.expected["wire_bytes"]

# backward direction walks the reversed plan
for method in ("fused", "traditional"):
    plan = ParallelFFT(mesh, (8, 8, 8), PENCIL,
                       transforms=("dct2", "c2c", "r2c"),
                       config=PlanConfig(method=method))
    rep = audit_plan(plan, direction="backward")
    assert rep.ok, (method, codes(rep))
    if method == "fused":
        assert rep.observed["engine_transposes"] == 0

# slab decomposition: one exchange stage
slab = ParallelFFT(mesh, (8, 8, 8), SLAB, config=PlanConfig(method="fused"))
rep = audit_plan(slab)
assert rep.ok and slab.n_exchanges == 1
assert rep.observed["jaxpr_all_to_alls"] == 1

# check_hlo=False skips compilation but keeps the jaxpr-level invariants
rep = audit_plan(slab, check_hlo=False)
assert rep.ok and "hlo_all_to_alls" not in rep.observed
# the ParallelFFT.audit convenience wrapper returns the same report type
assert slab.audit().ok
print("ENGINES SPECS OK")
"""
    assert "ENGINES SPECS OK" in subproc(code, ndev=4)


def test_audit_wire_bytes_match_models(subproc):
    """For every engine x comm_dtype on slab and pencil 8^3, the audited
    HLO payload bytes equal the ``exchange_wire_bytes`` model (exactly for
    complex64/int8; at the flagged CPU f32 widening for bf16), and
    ``comm_bytes_per_device``/``model_time_s`` are consistent with it."""
    code = _PRELUDE + """
BW = 1e9
for grid in (PENCIL, SLAB):
    for method in ("fused", "traditional", "pipelined"):
        for cd in (None, "bf16", "int8"):
            plan = ParallelFFT(mesh, (8, 8, 8), grid,
                               config=PlanConfig(method=method, chunks=2, comm_dtype=cd))
            rep = audit_plan(plan, label=f"{grid}/{method}/{cd}")
            assert rep.ok, (grid, method, cd, codes(rep), rep.violations)
            wire = rep.expected["wire_bytes"]
            assert wire == sum(rep.expected["payload_bytes"])
            assert wire == plan.comm_bytes_per_device()
            hlo = rep.observed["hlo_all_to_all_bytes"]
            if cd == "bf16":
                # single-host CPU XLA hoists the rounding convert across
                # the collective: exact widened multiset, and flagged
                assert rep.observed["backend_widened_wire"]
                assert hlo == sum(rep.expected["payload_bytes_widened"]) == 2 * wire
            else:
                assert hlo == wire, (grid, method, cd, hlo, wire)
            # time model lower-bounded by the audited wire term
            t = plan.model_time_s(ici_bw=BW, peak_flops=1e30, hbm_bw=1e30)
            assert t * BW >= 0.99 * wire, (grid, method, cd, t * BW, wire)
print("WIRE MODEL OK")
"""
    assert "WIRE MODEL OK" in subproc(code, ndev=4, timeout=1200)


def test_audit_batched_fusions(subproc):
    """nfields=3 under each batch fusion mode: stacked keeps one collective
    per exchange; per-field / pipelined-across-fields launch per field and
    restack with exactly one engine concatenate per stage."""
    code = _PRELUDE + """
for fusion in ("stacked", "per-field", "pipelined-across-fields"):
    plan = ParallelFFT(mesh, (8, 8, 8), PENCIL,
                       config=PlanConfig(method="fused", batch_fusion=fusion))
    rep = audit_plan(plan, nfields=3, label=f"fused/{fusion}")
    assert rep.ok, (fusion, codes(rep), rep.violations)
    want = plan.n_exchanges if fusion == "stacked" else plan.n_exchanges * 3
    assert rep.observed["jaxpr_all_to_alls"] == want
    if fusion == "stacked":
        assert rep.observed["engine_concats"] == 0
    else:
        assert rep.observed["engine_concats"] == plan.n_exchanges

# traditional batched: per-field pack/unpack copies scale with nfields
plan = ParallelFFT(mesh, (8, 8, 8), PENCIL,
                   config=PlanConfig(method="traditional", batch_fusion="per-field"))
rep = audit_plan(plan, nfields=3)
assert rep.ok, (codes(rep), rep.violations)
assert rep.observed["engine_transposes"] == rep.expected["engine_transposes"] > 0

# batched backward + a narrowed batched payload
plan = ParallelFFT(mesh, (8, 8, 8), PENCIL, config=PlanConfig(method="fused", comm_dtype="bf16"))
for direction in ("forward", "backward"):
    rep = audit_plan(plan, nfields=3, direction=direction)
    assert rep.ok, (direction, codes(rep), rep.violations)
print("BATCHED OK")
"""
    assert "BATCHED OK" in subproc(code, ndev=4, timeout=1200)


def test_audit_negative_claims(subproc):
    """The auditor must reject artifacts whose claimed schedule lies: each
    mis-claim is caught with the violation code that names the lie."""
    code = _PRELUDE + """
SCHED_FUSED = (StageEntry("fused", 1, "complex64"),) * 2
SCHED_BF16 = (StageEntry("fused", 1, "bf16"),) * 2

# 1) traditional artifact claiming fused: realignment transposes appear
rep = audit_plan(ParallelFFT(mesh, (8, 8, 8), PENCIL, config=PlanConfig(method="traditional")),
                 schedule=SCHED_FUSED)
assert "PLAN003" in codes(rep), codes(rep)

# 2) pipelined artifact claiming fused: launch count betrays the slices
rep = audit_plan(ParallelFFT(mesh, (8, 8, 8), PENCIL,
                             config=PlanConfig(method="pipelined", chunks=2)), schedule=SCHED_FUSED)
assert "PLAN001" in codes(rep), codes(rep)

# 3) lossless artifact claiming bf16: no quantize converts in the jaxpr
#    (the CPU widening acceptance must NOT let this one through)
rep = audit_plan(ParallelFFT(mesh, (8, 8, 8), PENCIL, config=PlanConfig(method="fused")),
                 schedule=SCHED_BF16)
assert "PLAN006" in codes(rep), codes(rep)

# 4) bf16 artifact claiming lossless: converts present but unclaimed
rep = audit_plan(ParallelFFT(mesh, (8, 8, 8), PENCIL,
                             config=PlanConfig(method="fused", comm_dtype="bf16")), schedule=SCHED_FUSED)
assert "PLAN006" in codes(rep), codes(rep)

# 5) int8 artifact claiming lossless: scale exchanges double the launch
#    count and the payload bytes shrink 4x
rep = audit_plan(ParallelFFT(mesh, (8, 8, 8), PENCIL,
                             config=PlanConfig(method="fused", comm_dtype="int8")), schedule=SCHED_FUSED)
got = set(codes(rep))
assert {"PLAN001", "PLAN006"} <= got, got
json.dumps(rep.to_dict(), default=str)  # failing reports serialize too

# 6) jnp artifact claiming the fused pallas kernels: zero kernel
#    launches in the artifact betray the claim
SCHED_PALLAS = (StageEntry("fused", 1, "int8", "pallas"),) * 2
rep = audit_plan(ParallelFFT(mesh, (8, 8, 8), PENCIL,
                             config=PlanConfig(method="fused", comm_dtype="int8")), schedule=SCHED_PALLAS)
assert "PLAN009" in codes(rep), codes(rep)

# a claimed schedule with the wrong stage count is a usage error
try:
    audit_plan(ParallelFFT(mesh, (8, 8, 8), PENCIL),
               schedule=(StageEntry("fused", 1, "complex64"),))
except ValueError as e:
    assert "exchange stages" in str(e)
else:
    raise AssertionError("wrong-length schedule not rejected")
print("NEGATIVE CLAIMS OK")
"""
    assert "NEGATIVE CLAIMS OK" in subproc(code, ndev=4, timeout=1200)


def test_audit_pallas_impl(subproc):
    """An ``exchange_impl="pallas"`` plan audits clean: the expected number
    of fused-kernel launches appear attributed to kernels/exchange/, and no
    codec eqns leak outside them (PLAN009 both ways)."""
    code = _PRELUDE + """
from repro.core.planconfig import PlanConfig

for method, cd in (("fused", "int8"), ("traditional", "bf16"),
                   ("pipelined", "int8")):
    plan = ParallelFFT(mesh, (8, 8, 8), PENCIL,
                       config=PlanConfig(method=method, chunks=2,
                                         comm_dtype=cd,
                                         exchange_impl="pallas"))
    rep = audit_plan(plan, label=f"pallas/{method}/{cd}")
    assert rep.ok, (method, cd, codes(rep), rep.violations)
    assert (rep.observed["exchange_pallas_calls"]
            == rep.expected["pallas_calls"] > 0)
    # codec math must live inside the kernels, not core/quant.py
    assert rep.observed["quant_eqns"] == 0

# a lossless pallas config is a no-op: jnp reference path, zero launches
plan = ParallelFFT(mesh, (8, 8, 8), PENCIL,
                   config=PlanConfig(method="fused", exchange_impl="pallas"))
rep = audit_plan(plan)
assert rep.ok and rep.observed["exchange_pallas_calls"] == 0
print("PALLAS IMPL OK")
"""
    assert "PALLAS IMPL OK" in subproc(code, ndev=4, timeout=1200)


def test_audit_auto_schedule_and_cli(subproc, tmp_path):
    """A tuned (method="auto") plan audits clean against its own resolved
    per-stage schedule, and the ``python -m repro.analysis.planlint`` CLI
    writes a JSON report with the documented shape and exits 0."""
    cache = tmp_path / "fft_tuner.json"
    report = tmp_path / "plan_audit.json"
    code = _PRELUDE + f"""
cache = {str(cache)!r}
plan = ParallelFFT(mesh, (8, 8, 8), PENCIL,
                   config=PlanConfig(method="auto", comm_dtype="bf16", tuner_cache=cache))
sched = plan.schedule  # resolves via the tuner sweep
rep = audit_plan(plan, label="auto")
assert rep.ok, (sched, codes(rep), rep.violations)
assert [tuple(e) for e in rep.schedule] == [tuple(s) for s in sched]

from repro.analysis import planlint
rc = planlint.main(["--out", {str(report)!r}, "--only", "poisson"])
assert rc == 0, rc
payload = json.loads(open({str(report)!r}).read())
assert payload["ok"] is True
assert set(payload["plans"]) == {{"poisson"}}
pr = payload["plans"]["poisson"]
assert pr["ok"] and pr["violations"] == []
assert pr["observed"]["engine_transposes"] == 0  # fused example: invariant
assert isinstance(payload["srclint"], list)
print("AUTO AND CLI OK")
"""
    assert "AUTO AND CLI OK" in subproc(code, ndev=4, timeout=1200)


# ---------------------------------------------------------------------------
# srclint: pure-AST unit tests on fabricated sources (no jax, no subprocess)
# ---------------------------------------------------------------------------


def _lint(tmp_path, **files):
    for name, src in files.items():
        (tmp_path / name).write_text(src)
    return lint_paths([str(tmp_path)])


def test_srclint_collective_reachability(tmp_path):
    """A collective in a helper reached from a shard_map body is fine; the
    same collective in an orphan function is SRC101."""
    findings = _lint(tmp_path, **{"mod.py": """
from jax import lax
from jax import shard_map

def helper(x):
    return lax.psum(x, "p0")

def mapped(x):
    return helper(x)

def build(mesh):
    return shard_map(mapped, mesh=mesh, in_specs=(None,), out_specs=None)

def orphan(x):
    return lax.all_gather(x, "p0")
"""})
    assert [f.code for f in findings] == ["SRC101"]
    assert "all_gather" in findings[0].message and "orphan" in findings[0].message


def test_srclint_alias_import_reaches_across_files(tmp_path):
    """Reachability follows ``from m import f as g`` aliases project-wide
    (the false positive that bit repro.core.meshutil.axis_size)."""
    findings = _lint(tmp_path, **{
        "a.py": """
from jax import lax

def axis_size(mesh, name):
    return lax.psum(1, name)
""",
        "b.py": """
from a import axis_size as _mesh_axis_size
from jax import shard_map

def body(x):
    return _mesh_axis_size(None, "p0") * x

def build(mesh):
    return shard_map(body, mesh=mesh, in_specs=(None,), out_specs=None)
"""})
    assert findings == []


def test_srclint_undeclared_axis_name(tmp_path):
    """An axis literal outside every declared mesh axis tuple is SRC102 —
    but only when the tree declares literal axis names at all."""
    findings = _lint(tmp_path, **{"mod.py": """
from jax import lax
from jax.sharding import Mesh
from jax import shard_map

def body(x):
    return lax.psum(x, "rows")

def build(devices):
    mesh = Mesh(devices, ("p0", "p1"))
    return shard_map(body, mesh=mesh, in_specs=(None,), out_specs=None)
"""})
    assert [f.code for f in findings] == ["SRC102"]
    assert "'rows'" in findings[0].message
    # no mesh ctor in the tree: axis names may flow in as parameters, skip
    sub = tmp_path / "sub2"
    sub.mkdir()
    findings = _lint(sub, **{"mod.py": """
from jax import lax
from jax import shard_map

def body(x):
    return lax.psum(x, "rows")

def build(mesh):
    return shard_map(body, mesh=mesh, in_specs=(None,), out_specs=None)
"""})
    assert findings == []


def test_srclint_in_specs_arity(tmp_path):
    """in_specs tuple length outside the mapped function's positional arity
    range is SRC103; defaulted params widen the accepted range."""
    findings = _lint(tmp_path, **{"mod.py": """
def body2(a, b):
    return a

def body_opt(a, b=None):
    return a

def build(mesh):
    shard_map(body2, mesh=mesh, in_specs=(None,), out_specs=None)
    shard_map(body_opt, mesh=mesh, in_specs=(None,), out_specs=None)
    shard_map(body_opt, mesh=mesh, in_specs=(None, None), out_specs=None)
"""})
    assert [f.code for f in findings] == ["SRC103"]
    assert "body2" in findings[0].message


def test_srclint_cache_key_hazards(tmp_path):
    findings = _lint(tmp_path, **{"mod.py": """
import json

def make_key(d):
    return json.dumps(d)

def make_key_sorted(d):
    return json.dumps(d, sort_keys=True)

def lookup(cache):
    return cache[{"a": 1}]
"""})
    assert [f.code for f in findings] == ["SRC104", "SRC104"]
    assert any("sort_keys" in f.message for f in findings)
    assert any("unhashable" in f.message for f in findings)


def test_srclint_unparseable_file(tmp_path):
    findings = _lint(tmp_path, **{"bad.py": "def broken(:\n"})
    assert [f.code for f in findings] == ["SRC100"]
    json.dumps([f.to_dict() for f in findings])


def test_srclint_repo_src_is_clean():
    """The repo's own src/ tree must stay lint-clean (CI runs the same
    check through the planlint CLI)."""
    assert lint_paths([str(REPO / "src")]) == []
