"""planlint — static auditor proving a compiled plan matches its schedule.

The paper's thesis (Sec. 3.3.2) is that the generalized all-to-all over
discontiguous subarrays *eliminates local realignment passes*.  This module
turns that claim — and the rest of a plan's schedule contracts — into
machine-checked invariants over the compiled artifact, before any benchmark
runs:

``audit_plan(plan)`` lowers the plan's executor, walks the jaxpr and the
post-SPMD optimized HLO (via :mod:`repro.launch.hlo_account`), and checks:

PLAN001  jaxpr ``all_to_all`` launch count == the schedule's expected count
         (× pipeline slices for ``pipelined``, × 2 for int8's scale
         exchange, × nfields under non-stacked batch fusions).
PLAN002  the multiset of per-collective HLO payload bytes == the analytic
         :func:`repro.core.redistribute.exchange_wire_bytes` model for each
         stage's tuned ``comm_dtype``.
PLAN003  realignment transposes: ``transpose`` eqns source-attributed to the
         exchange engine (``core/redistribute.py`` / ``core/pfft.py``) ==
         the engine contract of
         :func:`repro.core.redistribute.exchange_engine_ops` — **zero** for
         fused (the no-realignment invariant), exactly the documented
         pack/unpack copies for traditional.
PLAN004  realignment concatenates attributed to the engine == the contract
         (pipelined's slice reassembly, non-stacked batch restacking).
PLAN005  silent f64/complex128 upcast anywhere in the lowered program.
PLAN006  unpaired quantize/dequantize: ``convert_element_type`` eqns into a
         narrow wire dtype (int8/bf16) must balance the converts back out.
PLAN007  trip-aware HLO ``all-to-all`` instruction count == expected
         launches (the post-optimization cross-check of PLAN001).
PLAN008  guard-op presence matches the plan's ``guard`` mode: eqns
         source-attributed to ``repro/robustness/`` (the fused health
         checks) must appear in a guarded executor's jaxpr and must be
         **absent** — zero eqns — when ``guard="off"``, proving the
         unguarded artifact is bit-identical to a pre-guard plan.
PLAN009  fused-kernel containment: ``pallas_call`` eqns attributed to
         ``kernels/exchange/`` == the schedule's expected kernel launches
         (2 per ``impl="pallas"`` lossy stage side-pair, × pipeline
         slices, × nfields under non-stacked fusions; **zero** for jnp
         stages), and when *every* lossy stage runs the fused kernels the
         artifact carries **zero** eqns attributed to ``core/quant.py`` —
         the whole codec (quantize, scales, plane marshalling) lives
         inside the kernel calls, so no engine-side pack/unpack/codec
         pass survives outside them.

Realignment is asserted at the **jaxpr** level: on the CPU backend XLA
decomposes the tiled all-to-all into slice/concat + a tuple-operand
collective, materializing transposes for *every* engine, so the optimized
HLO cannot distinguish fused from traditional there — the jaxpr, with
source attribution of each transpose/concatenate to the module that emitted
it, can.  Transposes inside the transform itself (``core/fftcore.py``'s
DCT/DST axis brackets, ``kernels/``) and the wire codec
(``core/quant.py``'s plane stacking) are the transform's own business and
are tracked but never counted against the engine.

The ``schedule=`` override audits the artifact against a *claimed* schedule
instead of the plan's own resolved one — the negative-test hook: auditing a
traditional plan under a fused-claiming schedule must report PLAN003.

CLI::

    python -m repro.analysis.planlint [--out report.json] [--devices N]

audits mirrors of the three example plans (quickstart / navier_stokes /
poisson, including a batched navier_stokes invocation), runs
:mod:`repro.analysis.srclint` over ``src/``, writes a JSON report, and
exits nonzero on any violation.
"""

from __future__ import annotations

import json
import os
import sys
from dataclasses import dataclass, field

#: modules whose transposes/concatenates are engine realignment ops: the
#: exchange implementations and the plan executor that reassembles them
ENGINE_MODULES = ("core/redistribute.py", "core/pfft.py")

#: module prefix whose eqns are runtime guard ops (PLAN008): the fused
#: health checks live in repro/robustness/ precisely so this attribution
#: can prove guard="off" artifacts contain none of them
GUARD_MODULE_PREFIX = "robustness/"

#: module prefix of the fused exchange kernels (PLAN009): pallas_call eqns
#: attributed here are the kernel launches a pallas-impl stage must emit
EXCHANGE_KERNEL_PREFIX = "kernels/exchange/"

#: the reference wire codec (PLAN009): a plan whose lossy stages all run
#: the fused kernels must trace zero eqns attributed to this module
QUANT_MODULE = "core/quant.py"

#: narrow wire dtypes whose converts must pair up (PLAN006)
_NARROW_WIRE_DTYPES = ("int8", "bfloat16")

#: result-dtype tokens that flag a silent upcast (PLAN005)
_WIDE_DTYPES = ("float64", "complex128")
_WIDE_HLO_TOKENS = ("f64[", "c128[")


@dataclass
class Violation:
    code: str
    message: str
    stage: int | None = None

    def to_dict(self) -> dict:
        d = {"code": self.code, "message": self.message}
        if self.stage is not None:
            d["stage"] = self.stage
        return d


@dataclass
class AuditReport:
    """Outcome of one :func:`audit_plan` call.

    ``expected`` is the analytic side (launch counts, wire bytes, engine-op
    contract, with a per-stage breakdown), ``observed`` the measured side
    (jaxpr and HLO), ``collectives`` the per-instruction HLO records of
    :func:`repro.launch.hlo_account.collective_instrs`, and ``violations``
    every contract the artifact broke (empty == the plan is certified)."""

    label: str
    direction: str
    nfields: int
    schedule: list
    expected: dict
    observed: dict
    collectives: list = field(default_factory=list)
    violations: list = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> dict:
        return {
            "label": self.label, "direction": self.direction,
            "nfields": self.nfields, "ok": self.ok,
            "schedule": [list(e) for e in self.schedule],
            "expected": self.expected, "observed": self.observed,
            "collectives": self.collectives,
            "violations": [v.to_dict() for v in self.violations],
        }

    def summary(self) -> dict:
        """Compact per-plan audit record for BENCH JSON rows: enough to diff
        model-vs-artifact drift across PRs without the full report."""
        return {
            "ok": self.ok,
            "violations": sorted({v.code for v in self.violations}),
            "all_to_alls": self.observed.get("jaxpr_all_to_alls"),
            "wire_bytes": self.expected.get("wire_bytes"),
            "hlo_wire_bytes": self.observed.get("hlo_all_to_all_bytes"),
            "engine_transposes": self.observed.get("engine_transposes"),
            "engine_concats": self.observed.get("engine_concats"),
            "guard_eqns": self.observed.get("guard_eqns"),
            "exchange_pallas_calls": self.observed.get("exchange_pallas_calls"),
        }


# ---------------------------------------------------------------------------
# jaxpr walk
# ---------------------------------------------------------------------------


def _as_jaxprs(v):
    from jax._src import core as jcore

    if isinstance(v, jcore.ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, jcore.Jaxpr):
        return [v]
    if isinstance(v, (list, tuple)):
        return [j for x in v for j in _as_jaxprs(x)]
    return []


def _iter_eqns(jaxpr):
    """Every eqn of ``jaxpr`` and all sub-jaxprs (shard_map/pjit/scan/...)."""
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            yield eqn
            for v in eqn.params.values():
                stack.extend(_as_jaxprs(v))


def _eqn_module(eqn) -> str | None:
    """Repo-relative module (``core/redistribute.py``) that emitted ``eqn``,
    from the innermost in-repo traceback frame; None for pure-jax eqns."""
    tb = getattr(eqn.source_info, "traceback", None)
    if tb is None:
        return None
    for fr in tb.frames:
        fname = fr.file_name.replace(os.sep, "/")
        if "/repro/" in fname and "/analysis/" not in fname:
            return fname.rsplit("/repro/", 1)[1]
    return None


def _jaxpr_stats(jaxpr) -> dict:
    """Counts planlint checks against: all_to_all launches, source-attributed
    transposes/concatenates, narrow-dtype convert pairs, wide-dtype eqns."""
    a2a = 0
    guard_eqns = 0
    kernel_pallas_calls = 0
    quant_eqns = 0
    transposes: dict[str, int] = {}
    concats: dict[str, int] = {}
    conv_in: dict[str, int] = {d: 0 for d in _NARROW_WIRE_DTYPES}
    conv_out: dict[str, int] = {d: 0 for d in _NARROW_WIRE_DTYPES}
    wide: list[str] = []
    for eqn in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        mod = _eqn_module(eqn)
        if mod is not None and mod.startswith(GUARD_MODULE_PREFIX):
            guard_eqns += 1
        if mod == QUANT_MODULE:
            quant_eqns += 1
        if name == "pallas_call":
            if mod is not None and mod.startswith(EXCHANGE_KERNEL_PREFIX):
                kernel_pallas_calls += 1
        elif name == "all_to_all":
            a2a += 1
        elif name in ("transpose", "concatenate"):
            mod = mod or "<jax>"
            tgt = transposes if name == "transpose" else concats
            tgt[mod] = tgt.get(mod, 0) + 1
        elif name == "convert_element_type":
            out_dt = str(eqn.outvars[0].aval.dtype)
            in_dt = str(eqn.invars[0].aval.dtype)
            if out_dt in conv_in:
                conv_in[out_dt] += 1
            if in_dt in conv_out:
                conv_out[in_dt] += 1
        for ov in eqn.outvars:
            dt = str(getattr(ov.aval, "dtype", ""))
            if dt in _WIDE_DTYPES:
                wide.append(f"{name} -> {dt} at {_eqn_module(eqn) or '<jax>'}")
    eng_t = sum(n for m, n in transposes.items() if m in ENGINE_MODULES)
    eng_c = sum(n for m, n in concats.items() if m in ENGINE_MODULES)
    return {
        "jaxpr_all_to_alls": a2a,
        "guard_eqns": guard_eqns,
        "exchange_pallas_calls": kernel_pallas_calls,
        "quant_eqns": quant_eqns,
        "engine_transposes": eng_t,
        "engine_concats": eng_c,
        "transposes_by_module": transposes,
        "concats_by_module": concats,
        "narrow_converts_in": conv_in,
        "narrow_converts_out": conv_out,
        "wide_dtype_eqns": wide,
    }


# ---------------------------------------------------------------------------
# expected side (the analytic schedule contract)
# ---------------------------------------------------------------------------


def _stage_payload_multiset(src_pen, v, w, isz, comm_dtype, nfields, fusion,
                            method, chunks, nbatch) -> list[int]:
    """Per-collective wire bytes this stage should put on the wire, one
    entry per expected all-to-all (payload and, for int8, scale)."""
    import numpy as np

    from repro.core.decomp import local_lengths
    from repro.core.pencil import group_size
    from repro.core.quant import wire_ratio

    m = group_size(src_pen.mesh, src_pen.placement[w])
    local = int(np.prod(src_pen.local_shape, dtype=np.int64))
    b = src_pen.local_shape[v] // m
    if method == "pipelined":
        lengths = [n for n in local_lengths(b, max(1, min(chunks, b))) if n > 0]
    else:
        lengths = [b]
    if nbatch and fusion != "stacked":
        calls, fields_per_call = nfields, 1
    else:
        calls, fields_per_call = 1, nfields
    ratio = wire_ratio(comm_dtype)
    out: list[tuple[int, int]] = []
    for _ in range(calls):
        for n in lengths:
            elems = local * fields_per_call * n // b
            narrow = elems * (m - 1) // m * isz // ratio
            # the bf16 payload is a pure rounding convert, which XLA may
            # legally hoist across the (data-movement-only) collective; the
            # single-host CPU backend does exactly that, shipping the
            # rounded values at f32 width.  (int8 cannot be hoisted: its
            # dequantize needs the separately-shipped scales.)  This holds
            # for impl="pallas" too on CPU: interpret mode lowers the
            # kernel to transparent HLO, so the same rewrite applies —
            # only a real (TPU) kernel launch is opaque to it, and there
            # the cpu-only acceptance below never triggers.
            widened = narrow * 2 if comm_dtype == "bf16" else narrow
            out.append((narrow, widened))
            if comm_dtype == "int8":
                out.append((4 * (m - 1) * fields_per_call,) * 2)
    return out


def _expected_contract(plan, direction: str, schedule4, nfields: int) -> dict:
    """The analytic side of the audit: per exchange stage, the launch count,
    wire bytes, payload multiset, and engine-op contract its schedule entry
    implies, plus plan-level totals."""
    from repro.core.pfft import ExchangeStage
    from repro.core.redistribute import (
        exchange_engine_ops, exchange_wire_bytes, pipeline_slices)
    from repro.kernels.exchange import pallas_applicable

    stages, pencils, dtypes, *_, sched = plan._walk(direction, schedule4)
    nbatch = 1 if nfields > 1 else 0
    per_stage = []
    ex_i = 0
    for i, st in enumerate(stages):
        if not isinstance(st, ExchangeStage):
            continue
        method, chunks, comm_dtype, impl, fusion = sched[ex_i]
        ex_i += 1
        src_pen = pencils[i]
        isz = plan._stage_itemsize(i, dtypes)
        slices = (pipeline_slices(src_pen, st.v, st.w, chunks=chunks)
                  if method == "pipelined" else 1)
        per_field_launches = slices * (2 if comm_dtype == "int8" else 1)
        # a pallas stage emits one encode + one decode kernel per
        # payload collective side-pair (per slice for pipelined), plus
        # int8's max-abs scale pass before the encode
        fused_kernel = impl == "pallas" and pallas_applicable(method, comm_dtype)
        kernels = 3 if comm_dtype == "int8" else 2
        per_field_pcalls = kernels * slices if fused_kernel else 0
        if nbatch and fusion != "stacked":
            launches = per_field_launches * nfields
            pcalls = per_field_pcalls * nfields
            ops = exchange_engine_ops(src_pen, st.v, st.w, method=method,
                                      chunks=chunks, nbatch=0,
                                      comm_dtype=comm_dtype, impl=impl)
            transposes = ops["transposes"] * nfields
            # per-field outputs are restacked with one concatenate
            concats = ops["concats"] * nfields + 1
        else:
            launches = per_field_launches
            pcalls = per_field_pcalls
            ops = exchange_engine_ops(src_pen, st.v, st.w, method=method,
                                      chunks=chunks, nbatch=nbatch,
                                      comm_dtype=comm_dtype, impl=impl)
            transposes, concats = ops["transposes"], ops["concats"]
        wire = exchange_wire_bytes(src_pen, st.v, st.w, itemsize=isz,
                                   comm_dtype=comm_dtype, nfields=nfields,
                                   slices=slices)
        payloads = _stage_payload_multiset(
            src_pen, st.v, st.w, isz, comm_dtype, nfields, fusion, method,
            chunks, nbatch)
        per_stage.append({
            "stage": ex_i - 1, "v": st.v, "w": st.w, "method": method,
            "chunks": chunks, "comm_dtype": comm_dtype, "impl": impl,
            "batch_fusion": fusion,
            "itemsize": isz, "slices": slices, "launches": launches,
            "wire_bytes": wire,
            "payload_bytes": sorted(p for p, _ in payloads),
            "payload_bytes_widened": sorted(wp for _, wp in payloads),
            "engine_transposes": transposes, "engine_concats": concats,
            "pallas_calls": pcalls,
        })
    return {
        "launches": sum(s["launches"] for s in per_stage),
        "wire_bytes": sum(s["wire_bytes"] for s in per_stage),
        "payload_bytes": sorted(p for s in per_stage for p in s["payload_bytes"]),
        "payload_bytes_widened": sorted(
            p for s in per_stage for p in s["payload_bytes_widened"]),
        "engine_transposes": sum(s["engine_transposes"] for s in per_stage),
        "engine_concats": sum(s["engine_concats"] for s in per_stage),
        "pallas_calls": sum(s["pallas_calls"] for s in per_stage),
        "stages": per_stage,
    }


# ---------------------------------------------------------------------------
# the audit
# ---------------------------------------------------------------------------


def audit_plan(plan, *, nfields: int = 1, direction: str = "forward",
               schedule=None, label: str = "", check_hlo: bool = True) -> AuditReport:
    """Audit one compiled plan executor against its schedule contracts.

    The executor always runs the plan's *own* resolved schedule;
    ``schedule=`` only overrides the *claimed* contract the artifact is
    checked against (identical by default) — auditing a traditional plan
    against a fused-claiming schedule is how the negative tests prove the
    auditor catches a silently-reintroduced realignment pass.

    ``check_hlo=False`` skips compilation (PLAN002/PLAN007 and the HLO side
    of PLAN005) for contexts without enough devices to back the mesh; the
    jaxpr-level checks — including the realignment invariant — still run.
    """
    import jax

    from repro.core.planconfig import as_schedule
    from repro.core.quant import canonical_comm_dtype

    actual = plan.batched_schedule(nfields) if nfields > 1 else plan.schedule
    claimed = as_schedule(schedule if schedule is not None else actual)
    if len(claimed) != plan.n_exchanges:
        raise ValueError(f"claimed schedule has {len(claimed)} entries for "
                         f"{plan.n_exchanges} exchange stages")

    guard = plan.config.guard
    walk = plan._walk(direction)
    # a guarded plan's executor returns (block, stats), which is fine for
    # make_jaxpr/lower
    fn = plan.executor(direction, nfields if nfields > 1 else None)
    shape = ((nfields,) if nfields > 1 else ()) + tuple(walk.in_pencil.physical)
    aval = jax.ShapeDtypeStruct(shape, walk.dtypes[0])

    expected = _expected_contract(plan, direction, claimed, nfields)
    observed = _jaxpr_stats(jax.make_jaxpr(fn)(aval).jaxpr)
    violations: list[Violation] = []

    if observed["jaxpr_all_to_alls"] != expected["launches"]:
        violations.append(Violation(
            "PLAN001",
            f"jaxpr all_to_all count {observed['jaxpr_all_to_alls']} != "
            f"expected {expected['launches']} launches"))
    if guard == "off" and observed["guard_eqns"]:
        violations.append(Violation(
            "PLAN008",
            f"guard='off' artifact contains {observed['guard_eqns']} eqn(s) "
            f"attributed to {GUARD_MODULE_PREFIX} — the unguarded jaxpr must "
            f"be bit-identical to a pre-guard plan"))
    elif guard != "off" and not observed["guard_eqns"]:
        violations.append(Violation(
            "PLAN008",
            f"guard={guard!r} artifact contains no {GUARD_MODULE_PREFIX} "
            f"eqns — the fused health checks are missing"))
    if observed["engine_transposes"] != expected["engine_transposes"]:
        violations.append(Violation(
            "PLAN003",
            f"engine realignment transposes {observed['engine_transposes']} "
            f"(by module: { {m: n for m, n in observed['transposes_by_module'].items() if m in ENGINE_MODULES} }) "
            f"!= contract {expected['engine_transposes']}"))
    if observed["engine_concats"] != expected["engine_concats"]:
        violations.append(Violation(
            "PLAN004",
            f"engine concatenates {observed['engine_concats']} != contract "
            f"{expected['engine_concats']}"))
    if observed["wide_dtype_eqns"]:
        violations.append(Violation(
            "PLAN005",
            f"silent wide-dtype eqns: {observed['wide_dtype_eqns'][:4]}"))
    if observed["exchange_pallas_calls"] != expected["pallas_calls"]:
        violations.append(Violation(
            "PLAN009",
            f"{EXCHANGE_KERNEL_PREFIX} pallas_call count "
            f"{observed['exchange_pallas_calls']} != the schedule's expected "
            f"{expected['pallas_calls']} fused-kernel launches"))
    lossy_entries = [e for e in claimed
                     if canonical_comm_dtype(e.comm_dtype) != "complex64"]
    if (lossy_entries and all(e.impl == "pallas" for e in lossy_entries)
            and observed["quant_eqns"]):
        violations.append(Violation(
            "PLAN009",
            f"every lossy stage claims impl='pallas' but {observed['quant_eqns']} "
            f"eqn(s) still attribute to {QUANT_MODULE} — codec work leaked "
            f"outside the fused kernels"))
    claimed_narrow = {"bfloat16": 0, "int8": 0}
    for e in claimed:
        if e.comm_dtype == "bf16":
            claimed_narrow["bfloat16"] += 1
        elif e.comm_dtype == "int8":
            claimed_narrow["int8"] += 1
    for d in _NARROW_WIRE_DTYPES:
        enc, dec = observed["narrow_converts_in"][d], observed["narrow_converts_out"][d]
        if enc != dec:
            violations.append(Violation(
                "PLAN006",
                f"unpaired {d} quantize/dequantize: {enc} encodes vs "
                f"{dec} decodes"))
        elif claimed_narrow[d] and not enc:
            violations.append(Violation(
                "PLAN006",
                f"schedule claims a {d} wire payload on "
                f"{claimed_narrow[d]} stage(s) but the jaxpr contains no "
                f"{d} quantize converts"))
        elif enc and not claimed_narrow[d]:
            violations.append(Violation(
                "PLAN006",
                f"artifact quantizes to {d} ({enc} converts) but no "
                f"schedule entry claims that payload"))

    collectives: list = []
    if check_hlo:
        from repro.launch.hlo_account import collective_instrs

        hlo = jax.jit(fn).lower(aval).compile().as_text()
        collectives = collective_instrs(hlo)
        a2a = [r for r in collectives if r["kind"] == "all-to-all"]
        hlo_launches = int(round(sum(r["mult"] for r in a2a)))
        hlo_payloads = sorted(int(round(r["payload_bytes"])) for r in a2a)
        observed["hlo_all_to_alls"] = hlo_launches
        observed["hlo_all_to_all_bytes"] = sum(hlo_payloads)
        observed["hlo_payload_bytes"] = hlo_payloads
        observed["hlo_wide_dtypes"] = sorted(
            {t for t in _WIDE_HLO_TOKENS if t in hlo})
        if hlo_launches != expected["launches"]:
            violations.append(Violation(
                "PLAN007",
                f"HLO all-to-all count {hlo_launches} != expected "
                f"{expected['launches']} launches"))
        observed["backend_widened_wire"] = False
        if hlo_payloads != expected["payload_bytes"]:
            # single-host CPU XLA hoists the bf16 rounding convert across
            # the collective (the wire is free there), shipping rounded
            # values at f32 width: accept that exact widening on the cpu
            # backend, flagged, so the strict contract still binds on real
            # accelerator backends.
            widened = expected["payload_bytes_widened"]
            if (jax.default_backend() == "cpu" and hlo_payloads == widened
                    and widened != expected["payload_bytes"]):
                observed["backend_widened_wire"] = True
            else:
                violations.append(Violation(
                    "PLAN002",
                    f"HLO per-collective payload bytes {hlo_payloads} != "
                    f"exchange_wire_bytes model {expected['payload_bytes']}"))
        if observed["hlo_wide_dtypes"]:
            violations.append(Violation(
                "PLAN005",
                f"wide dtypes in optimized HLO: {observed['hlo_wide_dtypes']}"))

    return AuditReport(
        label=label or f"{plan.shape}:{plan.config.method}", direction=direction,
        nfields=nfields, schedule=list(claimed), expected=expected,
        observed=observed, collectives=collectives, violations=violations)


# ---------------------------------------------------------------------------
# CLI: audit the example plans + lint src/
# ---------------------------------------------------------------------------


def _example_plans():
    """Mirrors of the three example plans (examples/*.py shapes, transforms
    and methods) plus the fused-kernel (PLAN009) cases, built on however
    many devices the backend provides."""
    import jax

    from repro.core.fftcore import TransformSpec, dealias_grid
    from repro.core.meshutil import balanced_dims, make_mesh
    from repro.core.pfft import ParallelFFT
    from repro.core.planconfig import PlanConfig

    mesh = make_mesh(balanced_dims(len(jax.devices())), ("p0", "p1"))
    n = 32
    m = dealias_grid(n)
    return {
        "quickstart": (ParallelFFT(mesh, (42, 63, 64), grid=("p0", "p1"),
                                   config=PlanConfig(method="fused")), 1),
        # same plan with runtime guards on: PLAN008's positive case (guard
        # eqns present) and proof the guarded artifact still meets every
        # other schedule contract
        "quickstart[guarded]": (ParallelFFT(
            mesh, (42, 63, 64), grid=("p0", "p1"),
            config=PlanConfig(method="fused", guard="degrade")), 1),
        # the fused exchange kernels on both lossy payloads: PLAN009's
        # positive cases — every codec/pack eqn must live inside the
        # kernels/exchange/ pallas calls, none in core/quant.py
        "quickstart[int8-pallas]": (ParallelFFT(
            mesh, (42, 63, 64), grid=("p0", "p1"),
            config=PlanConfig(method="fused", comm_dtype="int8",
                              exchange_impl="pallas")), 1),
        "quickstart[bf16-pallas-trad]": (ParallelFFT(
            mesh, (42, 63, 64), grid=("p0", "p1"),
            config=PlanConfig(method="traditional", comm_dtype="bf16",
                              exchange_impl="pallas")), 1),
        "navier_stokes": (ParallelFFT(
            mesh, (m, m, m), grid=("p0", "p1"),
            config=PlanConfig(method="fused"),
            transforms=(TransformSpec.pruned(n), TransformSpec.pruned(n),
                        TransformSpec.r2c(n_keep=n // 2 + 1))), 1),
        "navier_stokes[batched]": (ParallelFFT(
            mesh, (m, m, m), grid=("p0", "p1"),
            config=PlanConfig(method="fused"),
            transforms=(TransformSpec.pruned(n), TransformSpec.pruned(n),
                        TransformSpec.r2c(n_keep=n // 2 + 1))), 3),
        "poisson": (ParallelFFT(mesh, (32, 32, 32), grid=("p0", "p1"),
                                transforms=("dct2", "c2c", "r2c"),
                                config=PlanConfig(method="fused")), 1),
    }


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.planlint",
        description="Audit the example plans' compiled artifacts against "
                    "their schedule contracts and lint src/ for shard_map "
                    "pitfalls.")
    ap.add_argument("--out", default="plan_audit.json",
                    help="JSON report path (default: %(default)s)")
    ap.add_argument("--devices", type=int, default=8,
                    help="host device count to request when XLA_FLAGS is "
                         "unset (default: %(default)s)")
    ap.add_argument("--only", default=None,
                    help="comma-separated plan labels to audit (default: all)")
    ap.add_argument("--src", default=None,
                    help="source tree to lint (default: the repo's src/)")
    ap.add_argument("--no-src-lint", action="store_true",
                    help="skip the AST source lint")
    args = ap.parse_args(argv)

    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={args.devices}")

    from repro.analysis.srclint import lint_paths

    plans = _example_plans()
    if args.only:
        keep = {s.strip() for s in args.only.split(",")}
        plans = {k: v for k, v in plans.items() if k in keep}
        missing = keep - set(plans)
        if missing:
            print(f"planlint: unknown plan labels {sorted(missing)}",
                  file=sys.stderr)
            return 2

    reports = {}
    for lbl, (plan, nfields) in plans.items():
        rep = audit_plan(plan, nfields=nfields, label=lbl)
        reports[lbl] = rep
        status = "ok" if rep.ok else "FAIL " + ",".join(
            sorted({v.code for v in rep.violations}))
        print(f"planlint: {lbl:24s} a2a={rep.observed['jaxpr_all_to_alls']} "
              f"wire={rep.expected['wire_bytes']}B "
              f"engine_transposes={rep.observed['engine_transposes']} "
              f"[{status}]")
        for v in rep.violations:
            print(f"  {v.code}: {v.message}", file=sys.stderr)

    findings = []
    if not args.no_src_lint:
        src_root = args.src or os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        findings = lint_paths([src_root])
        for f in findings:
            print(f"srclint: {f.path}:{f.line}: {f.code} {f.message}",
                  file=sys.stderr)
        print(f"planlint: srclint over {src_root}: "
              f"{len(findings)} finding(s)")

    ok = all(r.ok for r in reports.values()) and not findings
    payload = {
        "ok": ok,
        "plans": {lbl: r.to_dict() for lbl, r in reports.items()},
        "srclint": [f.to_dict() for f in findings],
    }
    with open(args.out, "w") as fh:
        json.dump(payload, fh, indent=1, default=str)
    print(f"planlint: report written to {args.out}; "
          f"{'all clean' if ok else 'VIOLATIONS FOUND'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
