"""``correct`` comes out false for the control and for each fault a cell
can have, at a CPU size: the whole run is driven past the look for a chip
with the timed path broken underneath.

- the control: the program's own lower-precision path (bfloat16 wire);
- a step that returns its state unchanged;
- half of the batch left out (the DNS's stacked fields);
- the exchange between chips left out (the four-device pencil);
- an answer altered where it is produced.
"""

import json

import pytest
from benchtest_util import run_cell, run_devices, tiny_root

from bench import harness


@pytest.fixture
def tampered(monkeypatch):
    """Install ``tamper(cell, spec)``, run after each cell is built."""

    def install(tamper=None, overrides=None):
        build = harness.build_cell

        def build_cell(spec, devices, plan_overrides=None):
            cell, ctx = build(spec, devices, overrides if overrides is not None else plan_overrides)
            if tamper:
                tamper(cell, spec)
            return cell, ctx

        monkeypatch.setattr(harness, "build_cell", build_cell)

    return install


def state_unchanged(cell, _spec):
    cell.step = lambda u: u


def answer_altered(cell, _spec):
    step = cell.step
    cell.step = lambda u: step(u).at[0, 1, 1, 1].set(0)  # a Taylor-Green mode


def run_dns(tmp_path):
    return run_cell(tiny_root(tmp_path), "tgv_dns.rk2.1chip", seed=2**31 + 3)


@pytest.mark.parametrize("fault", [state_unchanged, answer_altered])
def test_dns_fault_is_not_correct(tmp_path, tampered, fault):
    tampered(fault)
    result = run_dns(tmp_path)
    assert result["correct"] is False
    assert result["failed"] >= 1


def test_dns_half_of_the_batch_left_out(tmp_path, monkeypatch):
    """Each batched transform computes the first half of its fields and
    leaves the rest zero."""
    import jax.numpy as jnp

    from repro.core.pfft import ParallelFFT

    apply_many = ParallelFFT._apply_many

    def half(self, xs, direction):
        keep = (xs.shape[0] + 1) // 2
        y = apply_many(self, xs[:keep], direction)
        return jnp.concatenate([y, jnp.zeros((xs.shape[0] - keep, *y.shape[1:]), y.dtype)])

    monkeypatch.setattr(ParallelFFT, "_apply_many", half)
    assert run_dns(tmp_path)["correct"] is False


def test_dns_control_is_not_correct(tmp_path, tampered):
    spec = harness.find(tiny_root(tmp_path), "tgv_dns.rk2.1chip")
    tampered(overrides=spec.config["control"])
    result = run_dns(tmp_path / "again")
    assert result["correct"] is False
    limits = {k: v["limit"] for k, v in result["checks"].items()}
    assert all(result["checks"][k]["value"] > limits[k] for k in limits)


C2C = """
import json, sys
import jax, jax.numpy as jnp
from pathlib import Path
from benchtest_util import run_cell, tiny_root
from bench import harness

tmp = Path({tmp!r})
build = harness.build_cell

def run(name, tamper=None, overrides=None):
    def build_cell(spec, devices, plan_overrides=None):
        cell, ctx = build(spec, devices, overrides)
        if tamper:
            tamper(cell, spec)
        return cell, ctx
    harness.build_cell = build_cell
    res = run_cell(tiny_root(tmp / name), "c2c512.pencil2x2", seed=77)
    harness.build_cell = build
    print(json.dumps({{"name": name, "correct": res["correct"], "checks": res["checks"]}}))

def unchanged(cell, spec):
    out = cell.fwd.output_shardings
    cell.fwd = lambda x: jax.device_put(x, out)

def altered(cell, spec):
    fwd = cell.fwd
    cell.fwd = lambda x: fwd(x).at[3, 5, 7].set(0)

run("sound")
run("state_unchanged", unchanged)
run("answer_altered", altered)
spec = harness.find(tiny_root(tmp / "spec"), "c2c512.pencil2x2")
run("control", overrides=spec.config["control"])

# the exchange left out: every all-to-all keeps this device's own chunk
# in place of its peers'
def local(x, axis_name, split_axis, concat_axis, tiled=False, **kw):
    m = jax.lax.axis_size(axis_name)
    size = x.shape[split_axis] // m
    own = jax.lax.dynamic_slice_in_dim(x, jax.lax.axis_index(axis_name) * size, size, split_axis)
    return jnp.concatenate([own] * m, axis=concat_axis)

jax.lax.all_to_all = local
run("exchange_left_out")
"""


def test_c2c_faults_and_control_are_not_correct(tmp_path):
    out = run_devices(C2C.format(tmp=str(tmp_path)), ndev=4)
    results = {r["name"]: r for r in map(json.loads, out.strip().splitlines()[-5:])}
    assert results["sound"]["correct"] is True
    for name in ("state_unchanged", "answer_altered", "control", "exchange_left_out"):
        assert results[name]["correct"] is False, (name, results[name]["checks"])
