"""The exchange counter (``repro.core.spans.exchange_totals``): what each
traced executor's all-to-alls put on the interconnect, counted at trace
time, against the wire model of ``repro.core.redistribute`` and the bytes
planlint reads from the compiled HLO.

Every plan compiles on four virtual CPU devices in one child process; each
test reads what it needs from that run."""

import itertools
import json

import pytest

ENGINES = ("fused", "traditional", "pipelined")
PAYLOADS = ("complex64", "bf16", "int8")
CASES = [f"{e}-{p}" for e in ENGINES for p in PAYLOADS]

_CHILD = r"""
import itertools
import json
import jax
from repro.analysis.planlint import audit_plan
from repro.core import spans
from repro.core.meshutil import make_mesh
from repro.core.pfft import ExchangeStage, ParallelFFT, _reverse_plan
from repro.core.planconfig import PlanConfig
from repro.core.redistribute import (exchange_collective_launches, exchange_wire_bytes,
                                     pipeline_slices)

GRIDS = {"pencil2x2": (make_mesh((2, 2), ("p0", "p1")), ("p0", "p1")),
         "slab4": (make_mesh((4,), ("p",)), ("p",))}


def recorded(trace):
    # the records of executors traced for the first time
    before = spans.exchange_totals()
    trace()
    return [v for k, v in spans.exchange_totals().items() if k not in before]


def model(plan, direction, method, chunks, payload):
    stages, pencils = plan.stages, plan.pencil_trace
    if direction == "backward":
        stages, pencils = _reverse_plan(stages, pencils)
    launches = wire = 0
    for st, src in zip(stages, pencils):
        if isinstance(st, ExchangeStage):
            slices = (pipeline_slices(src, st.v, st.w, chunks=chunks)
                      if method == "pipelined" else 1)
            launches += exchange_collective_launches(src, st.v, st.w, method=method,
                                                     chunks=chunks)
            wire += exchange_wire_bytes(src, st.v, st.w, itemsize=8, comm_dtype=payload,
                                        slices=slices)
    return launches, wire


out = {}
for case in CASES:
    method, payload = case.split("-")
    chunks = 2 if method == "pipelined" else 1
    impls = ("jnp",) if payload == "complex64" else ("jnp", "pallas")
    for (grid_name, (mesh, grid)), impl in itertools.product(GRIDS.items(), impls):
        plan = ParallelFFT(mesh, (16, 8, 8), grid, config=PlanConfig(
            method=method, chunks=chunks, comm_dtype=payload, exchange_impl=impl))
        for direction in ("forward", "backward"):
            reports = []
            recs = recorded(lambda: reports.append(audit_plan(plan, direction=direction)))
            obs = reports[0].observed
            launches, wire = model(plan, direction, method, chunks, payload)
            out[f"{case}/{impl}/{grid_name}/{direction}"] = {
                "records": recs, "launches": launches, "wire_bytes": wire,
                "jaxpr_all_to_alls": obs["jaxpr_all_to_alls"],
                "hlo_bytes": obs["hlo_all_to_all_bytes"],
                "hlo_widened": obs["backend_widened_wire"]}

# one device: an exchange over a group of one sends nothing
one = ParallelFFT(make_mesh((1, 1), ("p0", "p1"), devices=jax.devices()[:1]),
                  (16, 8, 8), ("p0", "p1"))
x1 = jax.ShapeDtypeStruct(one.input_pencil.physical, one.input_dtype,
                          sharding=one.input_pencil.sharding)
out["one_device"] = recorded(lambda: jax.jit(one.executor("forward")).lower(x1).compile())

# the counter read between two lowerings of one executor changes nothing
mesh, grid = GRIDS["pencil2x2"]
plan = ParallelFFT(mesh, (16, 8, 8), grid, config=PlanConfig(comm_dtype="int8"))
x = jax.ShapeDtypeStruct(plan.input_pencil.physical, plan.input_dtype,
                         sharding=plan.input_pencil.sharding)
first = jax.jit(plan.executor("forward")).lower(x).as_text(debug_info=True)
read = spans.exchange_totals()
second = jax.jit(plan.executor("forward")).lower(x).as_text(debug_info=True)
out["relowered"] = {"same": first == second, "read": len(read),
                    "after": len(spans.exchange_totals())}
print("COUNTS=" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def counts(subproc):
    out = subproc(f"CASES = {CASES!r}\n" + _CHILD, ndev=4)
    return json.loads(out.split("COUNTS=", 1)[1])


@pytest.mark.parametrize("case", CASES)
def test_counter_matches_wire_model_and_hlo(counts, case):
    """For a 2x2 pencil and a 4-way slab, both directions, a lossy payload
    through the jnp codec and the exchange kernels: one record per traced
    executor, its launches the model's (int8's scale exchanges apart), its
    bytes ``exchange_wire_bytes`` and the compiled HLO's."""
    payload = case.split("-")[1]
    impls = ("jnp",) if payload == "complex64" else ("jnp", "pallas")
    for impl, grid, direction in itertools.product(
            impls, ("pencil2x2", "slab4"), ("forward", "backward")):
        c = counts[f"{case}/{impl}/{grid}/{direction}"]
        assert len(c["records"]) == 1, c["records"]
        rec = c["records"][0]
        assert rec["direction"] == ("pfft.fwd" if direction == "forward" else "pfft.bwd")
        assert rec["launches"] == c["launches"] > 0
        assert rec["scale_launches"] == (c["launches"] if payload == "int8" else 0)
        assert rec["launches"] + rec["scale_launches"] == c["jaxpr_all_to_alls"]
        assert rec["bytes"] == c["wire_bytes"] > 0
        # the CPU backend may ship a bf16 payload at f32 width (planlint PLAN002)
        widened = 2 if c["hlo_widened"] and payload == "bf16" else 1
        assert c["hlo_bytes"] == widened * rec["bytes"]


def test_one_device_plan_records_nothing(counts):
    assert counts["one_device"] == []


def test_reading_the_counter_leaves_the_program_unchanged(counts):
    r = counts["relowered"]
    assert r["same"] is True
    assert r["read"] == r["after"] >= 1
