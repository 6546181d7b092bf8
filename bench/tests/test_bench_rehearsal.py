"""CPU rehearsal of both step kinds at a tiny size: a whole run of each
cell, past the look for a chip, agrees with its plain numpy reference."""

import json

from benchtest_util import run_cell, run_devices, tiny_root

from bench import harness


def test_dns_rk2_cell_agrees_with_its_reference(tmp_path):
    root = tiny_root(tmp_path)
    result = run_cell(root, "tgv_dns.rk2.1chip", seed=2**31 + 5)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert result["device"]["platform"] == "cpu"
    checks = result["checks"]
    assert set(checks) == {"du_rel_l2", "du_worst"}
    assert all(0 < c["value"] < c["limit"] for c in checks.values())


def test_dns_rk2_traced_run(tmp_path):
    result = run_cell(tiny_root(tmp_path), "tgv_dns.rk2.1chip", trace=True)
    assert result["correct"] is True
    assert result["attempted"] == 3  # the traffic's trace_steps
    assert "plan.compile_s" in result["metrics"]
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


C2C = """
import json
from pathlib import Path
from benchtest_util import run_cell, tiny_root
root = tiny_root(Path({tmp!r}))
for trace in (False, True):
    print(json.dumps(run_cell(root, "c2c512.pencil2x2", seed=4_000_000_007, trace=trace)))
"""


def test_c2c_roundtrip_cell_on_four_devices(tmp_path):
    out = run_devices(C2C.format(tmp=str(tmp_path)), ndev=4)
    plain, traced = (json.loads(line) for line in out.strip().splitlines()[-2:])
    for result in (plain, traced):
        assert result["correct"] is True, result["checks"]
        assert result["device"]["count"] == 4
        assert set(result["checks"]) == {"fwd_rel_l2", "fwd_worst", "rt_rel_l2", "rt_worst"}
    spec = harness.find(tiny_root(tmp_path / "spec"), "c2c512.pencil2x2")
    assert set(plain["metrics"]) == {m["name"] for m in spec.end_to_end()}
    assert plain["attempted"] >= 8  # the traffic's min_steps
    assert traced["attempted"] == 10
