"""BENCHMARK.json against its contract, lookup by name, and the refusals."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest
from benchtest_util import REPO, run_cell, tiny_root

from bench import harness

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_texts_use_allowed_characters():
    names = [e["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for e in BENCH[key]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert one_line(w["why"]), w["name"]
        assert w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert one_line(c["why"]) and one_line(c["source"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["name"]
        assert m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert one_line(m["layer"])


def test_metrics_follow_the_contract():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells), (m["name"], w)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_every_cell_and_metric_has_its_files():
    for c in BENCH["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert (REPO / "bench" / "configs" / f"{c['name']}.py").is_file()
    for w in BENCH["workloads"]:
        spec = harness.find(REPO, w["name"])
        assert (REPO / "bench" / "steps" / f"{spec.traffic['step']}.py").is_file()
        assert len(spec.traffic["mesh"]["shape"]) == len(spec.traffic["mesh"]["axes"])
        n = 1
        for s in spec.traffic["mesh"]["shape"]:
            n *= s
        assert n == w["chips"]
        reported = {m["name"] for m in spec.end_to_end()}
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.per_layer()
    for m in BENCH["per_layer"]:
        reader = harness.load_module(REPO / "bench" / "layer_metrics" / f"{m['name']}.py")
        assert callable(reader.read)


def test_additions_are_found_by_name(tmp_path):
    """A configuration, a traffic mix, a cell and a per-layer metric added
    as new files and new BENCHMARK.json entries only, with no edit of an
    existing file, run."""
    root = tiny_root(tmp_path)
    d = root / "bench"
    cfg = json.loads((d / "configs" / "tgv_dns_re1600.json").read_text())
    cfg.update(name="tgv_small", modes=8, points=12,
               transforms=[{"kind": "c2c", "keep": 8}, {"kind": "c2c", "keep": 8},
                           {"kind": "r2c", "keep": 5}])
    (d / "configs" / "tgv_small.json").write_text(json.dumps(cfg))
    shutil.copy(d / "configs" / "tgv_dns_re1600.py", d / "configs" / "tgv_small.py")
    traffic = json.loads((d / "traffic" / "rk2.pencil1x1.json").read_text())
    traffic.update(min_steps=2, trace_steps=2)
    (d / "traffic" / "rk2.short.json").write_text(json.dumps(traffic))
    (d / "layer_metrics" / "other.device_ms.py").write_text(
        "def read(r):\n    return r.reduction.class_ns['other'] / r.steps * 1e-6 or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tgv_small", "source": "https://doi.org/10.1002/fld.3767",
                             "file": "bench/configs/tgv_small.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tgv_small.rk2", "config": "tgv_small",
                               "traffic": "rk2.short", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "other.device_ms", "unit": "ms", "better": "lower",
                               "source": "device_trace", "layer": "device", "moves": "step_ms",
                               "workloads": ["tgv_small.rk2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.find(root, "tgv_small.rk2")
    assert [m["name"] for m in spec.per_layer()] == ["other.device_ms"]
    result = run_cell(root, "tgv_small.rk2")
    assert result["correct"] is True
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert list(result)[-1] == "checks"


def test_unknown_workload_is_refused(capsys):
    assert harness.main(["--workload", "nope", "--seed", "1", "--seconds", "1"],
                        root=REPO, t0=0.0) == 2
    assert "no workload" in capsys.readouterr().err


def run_py(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=str(cwd), env=env,
                          capture_output=True, text=True, timeout=300)


ARGS = ("--workload", "tgv_dns.rk2.1chip", "--seed", "3000000001", "--seconds", "1",
        "--trace", "0")


def test_run_refuses_without_a_tpu():
    proc = run_py(REPO, *ARGS)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no tpu found" in proc.stderr


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_run_fails_with_only_the_benchmark_files(tmp_path, platform):
    """A directory holding only BENCHMARK.json and bench/ has no program:
    a run exits non-zero and prints no result, also past the look for a
    chip (steered to the CPU here in a child process)."""
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    if platform == "tpu":
        proc = run_py(tmp_path, *ARGS)
    else:
        code = ("import sys; from pathlib import Path; sys.path.insert(0, '.'); "
                "from bench import harness; "
                f"sys.exit(harness.main({list(ARGS)!r}, root=Path('.'), t0=0.0, platform='cpu'))")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("PYTHONPATH", None)
        proc = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path), env=env,
                              capture_output=True, text=True, timeout=300)
        assert "No module named 'repro'" in proc.stderr
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
