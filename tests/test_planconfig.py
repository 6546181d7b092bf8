"""PlanConfig / StageEntry surface tests (repro.core.planconfig).

These are pure construction/validation tests — no devices, no
collectives — so they pin the API contract cheaply: StageEntry.make's
5-field rows, and PlanConfig validation/canonicalization round-trips.
"""

import pytest

from repro.core.planconfig import PlanConfig, StageEntry, as_schedule


# ---------------------------------------------------------------------------
# StageEntry
# ---------------------------------------------------------------------------

def test_stage_entry_make_all_forms():
    full = StageEntry("fused", 1, "bf16", "pallas", "per-field")
    assert StageEntry.make(full) == full
    # a StageEntry's defaults fill in impl and batch_fusion
    e = StageEntry.make(StageEntry("traditional", 2, "int8"))
    assert e == ("traditional", 2, "int8", "jnp", "stacked")
    # a 5-field row (the tuner cache's form) passes straight through
    e = StageEntry.make(("pipelined", 4, "int8", "pallas", "stacked"))
    assert e == full._replace(method="pipelined", chunks=4, comm_dtype="int8",
                              batch_fusion="stacked")


def test_stage_entry_indexing_contract():
    """entry[2] is the comm_dtype everywhere it always was; the new fields
    sit behind it so index-based consumers (health, planlint) still work."""
    e = StageEntry("fused", 1, "int8", "pallas")
    assert e[0] == "fused" and e[1] == 1 and e[2] == "int8"
    assert e[3] == "pallas" and e[4] == "stacked"
    m, c, d, i, f = e
    assert (m, c, d, i, f) == ("fused", 1, "int8", "pallas", "stacked")
    # equality against the equivalent plain tuple (NamedTuple semantics)
    assert e == ("fused", 1, "int8", "pallas", "stacked")


def test_stage_entry_validation_and_canonicalization():
    # comm_dtype canonicalizes (None -> complex64) through validate()
    assert StageEntry.make(("fused", 1, None, "jnp", "stacked")).comm_dtype == "complex64"
    with pytest.raises(ValueError, match="unknown method"):
        StageEntry.make(("auto", 1, "bf16", "jnp", "stacked"))  # "auto" is plan-level only
    with pytest.raises(ValueError, match="chunks"):
        StageEntry.make(("fused", 0, "bf16", "jnp", "stacked"))
    with pytest.raises(ValueError, match="exchange impl"):
        StageEntry.make(("fused", 1, "bf16", "cuda", "stacked"))
    with pytest.raises(ValueError, match="batch_fusion"):
        StageEntry.make(("fused", 1, "bf16", "jnp", "interleaved"))
    # only the 5-field row is a schedule entry (v5's 3/4-field rows are not)
    for short in (("fused", 1), ("fused", 1, "bf16"), ("fused", 1, "bf16", "pallas")):
        with pytest.raises(ValueError, match="expected 5"):
            StageEntry.make(short)
    with pytest.raises(ValueError, match="expected 5"):
        StageEntry.make(("fused", 1, "bf16", "jnp", "stacked", "extra"))


def test_as_schedule_normalizes_mixed_forms():
    sched = as_schedule([["fused", 1, "bf16", "jnp", "stacked"],
                         ("pipelined", 4, "int8", "pallas", "per-field"),
                         StageEntry("traditional", 1, None)])
    assert all(isinstance(e, StageEntry) and len(e) == 5 for e in sched)
    assert [e.impl for e in sched] == ["jnp", "pallas", "jnp"]
    assert sched[2].comm_dtype == "complex64"


# ---------------------------------------------------------------------------
# PlanConfig
# ---------------------------------------------------------------------------

def test_planconfig_roundtrip_and_replace():
    cfg = PlanConfig(method="pipelined", chunks=3, comm_dtype="int8",
                     exchange_impl="pallas", guard="degrade")
    assert PlanConfig(**cfg.to_dict()) == cfg
    # replace() re-validates and re-canonicalizes
    assert cfg.replace(comm_dtype=None).comm_dtype == "complex64"
    with pytest.raises(ValueError, match="unknown exchange_impl"):
        cfg.replace(exchange_impl="cuda")
    # frozen: attribute assignment is an error
    with pytest.raises(AttributeError):
        cfg.method = "fused"


def test_planconfig_validation_errors():
    for bad in (dict(method="bogus"), dict(impl="fftw"),
                dict(exchange_impl="triton"), dict(chunks=0),
                dict(batch_fusion="zipped"), dict(guard="maybe")):
        with pytest.raises(ValueError):
            PlanConfig(**bad)


def test_planconfig_stage_entry():
    # chunks collapse to 1 unless the engine actually pipelines
    e = PlanConfig(method="fused", chunks=4, comm_dtype="bf16",
                   exchange_impl="pallas").stage_entry()
    assert e == ("fused", 1, "bf16", "pallas", "stacked")
    e = PlanConfig(method="pipelined", chunks=4, comm_dtype="int8").stage_entry()
    assert e == ("pipelined", 4, "int8", "jnp", "stacked")
