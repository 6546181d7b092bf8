"""The paper's 512^3 pencil cell, ``c2c512.pencil2x2``, as BENCHMARK.json
holds it, and the reader of its exchange's interconnect share."""

import json
import sys

import pytest
from benchtest_util import REPO, run_devices, tiny_root

from bench import harness, peaks, tracereduce

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "c2c512.pencil2x2"
EXCHANGE = {"exchange.collective_ms", "exchange.exposed_frac", "exchange_roofline"}


def roofline_reader():
    return harness.load_module(REPO / "bench" / "layer_metrics" / "exchange_roofline.py")


def readings(collective_ns, steps, *, on_chip=True):
    classes = dict.fromkeys(tracereduce.CLASSES, 0.0)
    classes["collective"] = collective_ns
    red = tracereduce.Reduction(ndev=4, window_ns=1e9, busy_ns=1e9, class_ns=classes,
                                exposed_collective_ns=collective_ns, top_ops=[], idle_gaps=[])
    return harness.Readings(red, steps, {}, None, 4,
                            peaks.peaks_for("TPU v5 lite") if on_chip else None)


def test_the_cell_resolves_to_the_papers_deployment():
    spec = harness.find(REPO, CELL)
    assert spec.workload["chips"] == 4
    assert spec.workload["config"] == "paper_c2c_512"
    assert spec.config["shape"] == [512, 512, 512] and spec.config["dtype"] == "complex64"
    assert spec.traffic["step"] == "c2c_roundtrip"
    assert spec.traffic["mesh"]["shape"] == [2, 2]
    cfg = {c["name"]: c for c in BENCH["configs"]}["paper_c2c_512"]
    assert cfg["reduced"] == ["dtype", "scaling_points"]
    assert cfg["source"] == spec.config["source"]


def test_every_metric_of_the_cell_has_a_reader():
    spec = harness.find(REPO, CELL)
    assert {m["name"] for m in spec.end_to_end()} == {"step_ms", "setup_s"}
    names = {m["name"] for m in spec.per_layer()}
    assert EXCHANGE <= names
    assert {"fft_roofline", "device.idle_frac", "plan.compile_s"} <= names
    for name in names:
        reader = harness.load_module(REPO / "bench" / "layer_metrics" / f"{name}.py")
        assert callable(reader.read), name
    for m in BENCH["per_layer"]:
        if m["name"] in EXCHANGE:
            assert m["workloads"] == [CELL] and m["layer"] == "exchange"


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_exchange_roofline_reads_nothing_without_a_counter(monkeypatch):
    from repro.core import spans

    reader = roofline_reader()
    monkeypatch.setattr(spans, "exchange_totals", lambda: {})
    assert reader.read(readings(4e6, 2)) is None  # no exchange traced
    monkeypatch.delattr(spans, "exchange_totals")  # a program with no counter
    assert reader.read(readings(4e6, 2)) is None
    import repro.core

    monkeypatch.delattr(repro.core, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)  # no spans at all
    assert reader.read(readings(4e6, 2)) is None


def test_exchange_roofline_is_the_step_bytes_at_the_ici_peak_over_collective_time(
        monkeypatch):
    from repro.core import spans

    reader = roofline_reader()
    records = {"pfft.fwd a": {"direction": "pfft.fwd", "launches": 2, "scale_launches": 0,
                              "bytes": 300_000_000},
               "pfft.bwd a": {"direction": "pfft.bwd", "launches": 2, "scale_launches": 0,
                              "bytes": 100_000_000}}
    monkeypatch.setattr(spans, "exchange_totals", lambda: records)
    # 400 MB a step at 200 GB/s is 2 ms; 3 steps of 8 ms of collectives each
    assert reader.read(readings(24e6, 3)) == pytest.approx(25.0)
    assert reader.read(readings(0.0, 3)) is None  # no collective ran
    assert reader.read(readings(24e6, 3, on_chip=False)) is None


TRACED = """
import json
from pathlib import Path
from benchtest_util import run_cell, tiny_root
from repro.core import spans
result = run_cell(tiny_root(Path({tmp!r})), "c2c512.pencil2x2", seed=3_000_000_019, trace=True)
print(json.dumps({{"result": result, "exchanges": spans.exchange_totals()}}))
"""


def test_traced_run_reads_the_exchange_metrics(tmp_path):
    """The cell's traced run at 16^3 on four CPU devices reads every
    per-layer metric without error, and the counter holds the step's
    exchanges: two all-to-alls each way, each sending half of an 8x8x16
    complex64 block."""
    out = json.loads(run_devices(TRACED.format(tmp=str(tmp_path)), ndev=4).strip()
                     .splitlines()[-1])
    result = out["result"]
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] == 10
    spec = harness.find(tiny_root(tmp_path / "spec"), CELL)
    assert set(result["metrics"]) <= {m["name"] for m in spec.per_layer()}
    assert "plan.compile_s" in result["metrics"]
    records = sorted(out["exchanges"].values(), key=lambda rec: rec["direction"])
    assert [rec["direction"] for rec in records] == ["pfft.bwd", "pfft.fwd"]
    for rec in records:
        assert (rec["launches"], rec["scale_launches"], rec["bytes"]) == (2, 0, 2 * 8 * 8 * 16 * 8 // 2)
