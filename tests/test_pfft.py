"""Distributed FFT vs np.fft oracles on 8 virtual devices."""

import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.core.pfft import ParallelFFT


def test_pfft_all_decompositions(subproc):
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig

def tf(shape, real):  # r2c on the last axis, c2c elsewhere
    return ("c2c",) * (len(shape) - 1) + ("r2c",) if real else None

mesh = make_mesh((2, 4), ("p0", "p1"))
rng = np.random.default_rng(0)
cases = [
    # (shape, grid, real, method)
    ((16, 12, 20), ("p0",), False, "fused"),          # slab
    ((16, 12, 20), ("p0", "p1"), False, "fused"),     # pencil
    ((16, 12, 20), (("p0", "p1"),), False, "fused"),  # slab on composed group
    ((16, 12, 20), ("p0", "p1"), True, "fused"),      # r2c pencil
    ((16, 12, 20), ("p0", "p1"), False, "traditional"),
    ((16, 12, 20), ("p0", "p1"), True, "traditional"),
    ((13, 9, 11), ("p0", "p1"), False, "fused"),      # non-divisible (padding)
    ((13, 9, 11), ("p0", "p1"), True, "fused"),
    ((8, 6, 10, 12), ("p0", "p1"), False, "fused"),   # 4-D on 2-D grid
]
for shape, grid, real, method in cases:
    plan = ParallelFFT(mesh, shape, grid, transforms=tf(shape, real),
                       config=PlanConfig(method=method))
    x = rng.standard_normal(shape).astype(np.float32)
    if not real:
        x = (x + 1j * rng.standard_normal(shape)).astype(np.complex64)
    y = plan.forward(jnp.asarray(x))
    want = np.fft.rfftn(x) if real else np.fft.fftn(x)
    np.testing.assert_allclose(np.asarray(y), want, rtol=3e-4, atol=3e-3)
    back = plan.backward(y)
    np.testing.assert_allclose(np.asarray(back), x, rtol=3e-4, atol=3e-3)
    print("ok", shape, grid, real, method)

# 4-D array on a 3-D processor grid (paper Sec. 3.6 / Appendix B)
mesh3 = make_mesh((2, 2, 2), ("a", "b", "c"))
plan = ParallelFFT(mesh3, (8, 8, 8, 8), ("a", "b", "c"))
x = (rng.standard_normal((8, 8, 8, 8)) + 1j * rng.standard_normal((8, 8, 8, 8))).astype(np.complex64)
np.testing.assert_allclose(np.asarray(plan.forward(jnp.asarray(x))), np.fft.fftn(x),
                           rtol=3e-4, atol=3e-3)
print("PFFT DECOMPS OK")
""", ndev=8)


def test_pfft_pipelined_and_auto_match_fused(subproc):
    """method="pipelined" (several chunk counts) and method="auto" produce
    the same pencils and allclose values as "fused" for slab and pencil
    decompositions, c2c and r2c — and match the np.fft oracle."""
    subproc("""
import tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig

mesh = make_mesh((2, 4), ("p0", "p1"))
rng = np.random.default_rng(0)
cache = tempfile.mktemp(suffix=".json")
shape = (16, 12, 20)
for grid in (("p0",), ("p0", "p1")):
    for real in (False, True):
        tf = ("c2c", "c2c", "r2c") if real else None
        ref = ParallelFFT(mesh, shape, grid, transforms=tf,
                          config=PlanConfig(method="fused"))
        x = rng.standard_normal(shape).astype(np.float32)
        if not real:
            x = (x + 1j * rng.standard_normal(shape)).astype(np.complex64)
        want = np.asarray(ref.forward(jnp.asarray(x)))
        variants = [ParallelFFT(mesh, shape, grid, transforms=tf,
                                config=PlanConfig(method="pipelined", chunks=c))
                    for c in (1, 2, 4)]
        variants.append(ParallelFFT(mesh, shape, grid, transforms=tf,
                                    config=PlanConfig(method="auto", tuner_cache=cache)))
        for plan in variants:
            assert plan.output_pencil == ref.output_pencil   # identical pencils
            y = np.asarray(plan.forward(jnp.asarray(x)))
            np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
            oracle = np.fft.rfftn(x) if real else np.fft.fftn(x)
            np.testing.assert_allclose(y, oracle, rtol=3e-4, atol=3e-3)
            back = np.asarray(plan.backward(jnp.asarray(y)))
            np.testing.assert_allclose(back, x, rtol=3e-4, atol=3e-3)
        print("ok", grid, real)
print("PFFT PIPELINED/AUTO OK")
""", ndev=8)


def test_pfft_comm_dtype_accuracy(subproc):
    """Compressed-exchange accuracy contract at the plan level, slab and
    pencil grids: comm_dtype=None/"complex64" is bit-identical to today's
    output for all three engines; "bf16" round-trips backward(forward(x))
    to < 1e-2 relative L2; "int8" to < 5e-2."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig

mesh = make_mesh((2, 4), ("p0", "p1"))
rng = np.random.default_rng(0)
shape = (16, 12, 20)
x = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)
for grid in (("p0",), ("p0", "p1")):
    ref = ParallelFFT(mesh, shape, grid)
    want = np.asarray(ref.forward(jnp.asarray(x)))
    for method in ("fused", "traditional", "pipelined"):
        for comm_dtype in (None, "complex64", "bf16", "int8"):
            plan = ParallelFFT(mesh, shape, grid,
                               config=PlanConfig(method=method, chunks=2, comm_dtype=comm_dtype))
            y = plan.forward(jnp.asarray(x))
            back = np.asarray(plan.backward(y))
            if comm_dtype in (None, "complex64"):
                # lossless payload: bit-identical forward transform
                assert np.array_equal(np.asarray(y), want), (grid, method)
            rel = np.linalg.norm(back - x) / np.linalg.norm(x)
            bound = {None: 1e-5, "complex64": 1e-5, "bf16": 1e-2, "int8": 5e-2}[comm_dtype]
            assert rel < bound, (grid, method, comm_dtype, rel)
    print("ok", grid)
print("PFFT COMM DTYPE OK")
""", ndev=8)


def test_backward_consumes_reversed_tuned_schedule(subproc):
    """method="auto" backward pass: the backward executor must consume the
    tuned schedule in reversed stage order, and backward(forward(x)) must
    round-trip to the identity for a *mixed* per-stage schedule (different
    engine, chunks and comm_dtype per exchange)."""
    subproc("""
import json, tempfile
from pathlib import Path
import jax, jax.numpy as jnp, numpy as np
from repro.core import fftcore, pfft, tuner
from repro.core.meshutil import make_mesh
from repro.core.pfft import ExchangeStage, ParallelFFT
from repro.core.planconfig import PlanConfig, as_schedule

cache = tempfile.mktemp(suffix=".json")
mesh = make_mesh((2, 4), ("p0", "p1"))
shape = (16, 12, 20)
plan = ParallelFFT(mesh, shape, ("p0", "p1"),
                   config=PlanConfig(method="auto", comm_dtype="int8", tuner_cache=cache))
# seed the disk cache with a hand-mixed schedule BEFORE plan.schedule is
# first read: the plan must consume it instead of benchmarking
mixed = [["traditional", 1, "complex64", "jnp", "stacked"],
         ["pipelined", 2, "bf16", "jnp", "stacked"]]
Path(cache).write_text(json.dumps(
    {tuner.plan_key(plan): {"schedule": mixed, "timings": {}}}))
assert plan.schedule == as_schedule(mixed)

# what each executor hands the shard function, recorded as it is traced
traced = {}
run_stages = pfft._run_stages
def spy(block, **kw):
    traced[kw["sign"]] = kw
    return run_stages(block, **kw)
pfft._run_stages = spy

# mixed-schedule round trip: backward(forward(x)) ~= x (bf16-stage lossy)
rng = np.random.default_rng(0)
x = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)
back = np.asarray(plan.backward(plan.forward(jnp.asarray(x))))

# backward executor: same schedule, reversed stage order
bwd = traced[fftcore.BACKWARD]
assert bwd["schedule"] == plan.schedule[::-1]
assert traced[fftcore.FORWARD]["schedule"] == plan.schedule
# and its exchange stages are the forward ones reversed with v/w swapped
fwd_ex = [s for s in plan.stages if isinstance(s, ExchangeStage)]
bwd_ex = [s for s in bwd["stages"] if isinstance(s, ExchangeStage)]
assert [(s.v, s.w) for s in bwd_ex] == [(s.w, s.v) for s in reversed(fwd_ex)]

rel = np.linalg.norm(back - x) / np.linalg.norm(x)
assert rel < 1e-2, rel
print("BACKWARD AUTO OK", rel)
""", ndev=8)


def test_r2c_backward_odd_trailing_extents(subproc):
    """r2c-plan backward transforms with odd trailing extents: the c2r
    stage must irfft at the explicit logical length (n=), which the
    Hermitian-reduced extent alone cannot recover (n//2+1 maps both n and
    n-1 onto the same spectrum length).  Feeds np.fft.rfftn oracles
    straight into backward() on slab and pencil grids."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT

mesh = make_mesh((2, 4), ("p0", "p1"))
rng = np.random.default_rng(0)
# odd trailing extents, including odd == even+1 aliasing pairs (11 vs 10)
for shape in ((8, 6, 11), (12, 10, 9), (13, 9, 7)):
    for grid in (("p0",), ("p0", "p1")):
        plan = ParallelFFT(mesh, shape, grid, transforms=("c2c", "c2c", "r2c"))
        assert plan.output_pencil.logical[-1] == shape[-1] // 2 + 1
        x = rng.standard_normal(shape).astype(np.float32)
        # backward of the numpy oracle spectrum reproduces x: proves the
        # irfft ran at n=shape[-1], not 2*(n//2+1-1)
        back = np.asarray(plan.backward(jnp.asarray(np.fft.rfftn(x))))
        assert back.shape == shape
        np.testing.assert_allclose(back, x, rtol=3e-4, atol=3e-3)
        # and the plan's own spectrum round-trips too
        back2 = np.asarray(plan.backward(plan.forward(jnp.asarray(x))))
        np.testing.assert_allclose(back2, x, rtol=3e-4, atol=3e-3)
        print("ok", shape, grid)
print("R2C ODD BACKWARD OK")
""", ndev=8)


def test_model_flops_known_shapes():
    """Pin the 5 N log2 N accounting: c2c counts every stage at the full
    logical length; r2c halves the real stage and shrinks the Hermitian
    axis's contribution to later stages' batches."""
    from repro.core.meshutil import make_mesh

    mesh = make_mesh((1,), ("p0",))
    # c2c (8,8,8): 3 stages x 5*8*log2(8) * batch 64
    assert ParallelFFT(mesh, (8, 8, 8), ("p0",)).model_flops() == 3 * 5 * 8 * 3 * 64
    # r2c (8,8,8): r2c stage at half, then two c2c stages with the last
    # axis reduced to 8//2+1 = 5 in their batches
    want = 0.5 * 5 * 8 * 3 * 64 + 2 * (5 * 8 * 3 * (8 * 5))
    r2c = ParallelFFT(mesh, (8, 8, 8), ("p0",), transforms=("c2c", "c2c", "r2c"))
    assert r2c.model_flops() == want
    # non-power-of-two length uses log2 of the true logical n
    import math
    got = ParallelFFT(mesh, (6, 4), ("p0",)).model_flops()
    assert abs(got - (5 * 6 * math.log2(6) * 4 + 5 * 4 * 2 * 6)) < 1e-9


def test_pfft_matmul_impl(subproc):
    """Local FFT via the Pallas four-step matmul kernel inside the plan."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig
mesh = make_mesh((4,), ("p0",))
rng = np.random.default_rng(0)
shape = (16, 8, 12)
plan = ParallelFFT(mesh, shape, ("p0",), config=PlanConfig(impl="matmul"))
x = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)
y = plan.forward(jnp.asarray(x))
np.testing.assert_allclose(np.asarray(y), np.fft.fftn(x), rtol=3e-4, atol=5e-3)
back = plan.backward(y)
np.testing.assert_allclose(np.asarray(back), x, rtol=3e-4, atol=5e-3)
print("PFFT MATMUL OK")
""", ndev=4)


@given(d=st.integers(2, 4), seed=st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_plan_structure_properties(d, seed):
    """Plan invariants on a trivial 1-device mesh: d transforms, k exchanges,
    output pencil aligned in the axes the paper says (hypothesis over dims)."""
    from repro.core.meshutil import make_mesh
    from repro.core.pfft import ExchangeStage, FFTStage

    rng = np.random.default_rng(seed)
    shape = tuple(int(rng.integers(4, 10)) for _ in range(d))
    mesh = make_mesh((1,), ("p0",))
    plan = ParallelFFT(mesh, shape, ("p0",))
    ffts = [s for s in plan.stages if isinstance(s, FFTStage)]
    exs = [s for s in plan.stages if isinstance(s, ExchangeStage)]
    assert len(ffts) == d                      # d partial transforms
    assert len(exs) == 1                       # k = 1 redistribution (slab)
    assert {s.axis for s in ffts} == set(range(d))
    # paper Eq. 14: output is x-aligned (axis 0 local), axis 1 distributed
    assert plan.output_pencil.placement[0] is None
    assert plan.output_pencil.placement[1] == "p0" 


@pytest.mark.parametrize("guard", ["off", "strict"])
@pytest.mark.parametrize("nfields", [None, 3], ids=["unstacked", "stacked3"])
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_executor_one_per_key(subproc, direction, nfields, guard):
    """One executor per (direction, stacking, schedule): ``warm()`` builds
    the executor that ``forward``/``forward_many`` (or their backward
    twins) then run, a repeat ``executor`` call returns that same object,
    no call adds a second one, and the result matches numpy's FFT."""
    subproc(f"""
import jax.numpy as jnp, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig

direction, nfields, guard = {direction!r}, {nfields!r}, {guard!r}
mesh = make_mesh((2, 2), ("p0", "p1"))
shape = (8, 6, 10)
plan = ParallelFFT(mesh, shape, ("p0", "p1"), config=PlanConfig(guard=guard))
assert plan.warm((direction,), nfields=nfields or 1) == 1
assert len(plan._executors) == 1
fn = plan.executor(direction, nfields)
assert plan.executor(direction, nfields) is fn
sched = plan.schedule if nfields is None else plan.batched_schedule(nfields)
assert plan.executor(direction, nfields, schedule=list(sched)) is fn

rng = np.random.default_rng(0)
full = ((nfields,) if nfields else ()) + shape
x = (rng.standard_normal(full) + 1j * rng.standard_normal(full)).astype(np.complex64)
if nfields:
    run = plan.forward_many if direction == "forward" else plan.backward_many
else:
    run = plan.forward if direction == "forward" else plan.backward
out = run(jnp.asarray(x))
if guard != "off":
    out, report = out
    assert report.ok, report
assert len(plan._executors) == 1, list(plan._executors)
axes = (-3, -2, -1)
want = (np.fft.fftn if direction == "forward" else np.fft.ifftn)(x, axes=axes)
np.testing.assert_allclose(np.asarray(out), want, rtol=3e-4, atol=3e-3)
print("EXECUTOR OK")
""", ndev=4)
