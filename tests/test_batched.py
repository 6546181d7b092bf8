"""Batched multi-field plan execution: N fields per invocation, one
collective per exchange stage, bit-identical to the per-field loop for
lossless payloads, tuner batch dimension, batch-aware cost models."""


def test_forward_many_matches_per_field_loop(subproc):
    """forward_many/backward_many over N fields is bit-identical to an
    N-iteration per-field loop with the lossless payload, on slab and
    pencil grids, forward and backward, c2c and r2c specs, all three
    batch_fusion modes (issue acceptance criterion)."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig

mesh = make_mesh((2, 4), ("p0", "p1"))
rng = np.random.default_rng(0)
shape = (16, 12, 20)
N = 3
R2C = ("c2c", "c2c", "r2c")
cases = [
    (("p0",), None, dict()),                               # slab c2c
    (("p0", "p1"), None, dict()),                          # pencil c2c
    (("p0", "p1"), R2C, dict()),                           # pencil r2c spec
    (("p0", "p1"), None, dict(method="pipelined", chunks=2)),  # sliced exchange
]
for grid, tf, kw in cases:
    for fusion in ("stacked", "pipelined-across-fields", "per-field"):
        plan = ParallelFFT(mesh, shape, grid, transforms=tf,
                           config=PlanConfig(batch_fusion=fusion, **kw))
        x = rng.standard_normal((N, *shape)).astype(np.float32)
        if plan.input_dtype == jnp.complex64:
            x = (x + 1j * rng.standard_normal((N, *shape))).astype(np.complex64)
        xs = jnp.asarray(x)
        ref = jnp.stack([plan.forward(xs[i]) for i in range(N)])
        got = plan.forward_many(xs)
        assert jnp.array_equal(got, ref), (grid, tf, kw, fusion, "forward")
        back_ref = jnp.stack([plan.backward(ref[i]) for i in range(N)])
        back = plan.backward_many(got)
        assert jnp.array_equal(back, back_ref), (grid, tf, kw, fusion, "backward")
        np.testing.assert_allclose(np.asarray(back), x, rtol=3e-4, atol=3e-3)
    print("ok", grid, tf, kw)

# pytree API mirrors structure; a d+1-dim forward() input routes batched
plan = ParallelFFT(mesh, shape, ("p0", "p1"))
x = (rng.standard_normal((N, *shape))
     + 1j * rng.standard_normal((N, *shape))).astype(np.complex64)
ref = plan.forward_many(jnp.asarray(x))
tree = plan.forward_many({"u": jnp.asarray(x[0]), "v": jnp.asarray(x[1]),
                          "w": jnp.asarray(x[2])})
assert set(tree) == {"u", "v", "w"}
for i, k in enumerate(sorted(("u", "v", "w"))):
    assert jnp.array_equal(tree[k], ref[i]), k
assert jnp.array_equal(plan.forward(jnp.asarray(x)), ref)
back_tree = plan.backward_many(tree)
assert set(back_tree) == {"u", "v", "w"}
print("BATCHED LOOP EQUIV OK")
""", ndev=8)


def test_batched_stacked_issues_one_collective_per_stage(subproc):
    """Acceptance criterion: the stacked batched path issues exactly one
    all-to-all per exchange stage for N fields (counted in the jaxpr),
    forward and backward; the per-field baseline pays N per stage."""
    subproc("""
import jax, jax.numpy as jnp
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig

mesh = make_mesh((2, 4), ("p0", "p1"))
shape = (16, 12, 20)
N = 3
def count_a2a(fn, shape, dtype):
    return str(jax.make_jaxpr(fn)(jax.ShapeDtypeStruct(shape, dtype))).count("all_to_all")

for grid in (("p0",), ("p0", "p1")):
    plan = ParallelFFT(mesh, shape, grid)  # stacked default, lossless payload
    n_fwd = count_a2a(plan.executor("forward", N),
                      (N, *plan.input_pencil.physical), plan.input_dtype)
    assert n_fwd == plan.n_exchanges, (grid, n_fwd)
    n_bwd = count_a2a(plan.executor("backward", N),
                      (N, *plan.output_pencil.physical), plan.spectral_dtype)
    assert n_bwd == plan.n_exchanges, (grid, n_bwd)
    pf = ParallelFFT(mesh, shape, grid, config=PlanConfig(batch_fusion="per-field"))
    n_pf = count_a2a(pf.executor("forward", N),
                     (N, *pf.input_pencil.physical), pf.input_dtype)
    assert n_pf == N * pf.n_exchanges, (grid, n_pf)
    print("ok", grid, n_fwd, n_pf)
print("BATCHED COLLECTIVE COUNT OK")
""", ndev=8)


def test_exchange_nbatch_matches_per_field(subproc):
    """redistribute-level contract of the batched entry point: one
    ``exchange_shard(..., nbatch=1)`` over a stacked block equals the
    per-field loop bitwise for the lossless payload (all three engines,
    slab and pencil inputs, including traditional ``transposed_out``);
    lossy payloads stay within codec bounds per field even when one field
    is 1000x larger (per-(field, chunk) int8 scales)."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from repro.core.meshutil import make_mesh, shard_map
from repro.core.pencil import make_pencil, pad_global
from repro.core.redistribute import exchange_shard

mesh = make_mesh((2, 4), ("p0", "p1"))
rng = np.random.default_rng(0)
shape = (16, 12, 10)
N = 3
cases = [
    ((None, "p1", None), (4, 4, 1), 0, 1),   # slab
    (("p0", "p1", None), (4, 4, 4), 2, 1),   # pencil, v trailing
]
for placement, divisors, v, w in cases:
    src = make_pencil(mesh, shape, placement, divisors=divisors)
    dst = src.exchanged(v, w)
    x = (rng.standard_normal((N, *shape))
         + 1j * rng.standard_normal((N, *shape))).astype(np.complex64)
    x[1] *= 1e3  # int8 scales must not let this field drown the others
    xs = jax.device_put(pad_global(jnp.asarray(x), src, nbatch=1),
                        src.batched_sharding())
    for method in ("fused", "traditional", "pipelined"):
        one = shard_map(partial(exchange_shard, v=v, w=w, group="p1",
                                method=method, chunks=2),
                        mesh=mesh, in_specs=src.spec, out_specs=dst.spec,
                        check_vma=False)
        want = jnp.stack([one(xs[i]) for i in range(N)])
        for comm_dtype in (None, "bf16", "int8"):
            many = shard_map(partial(exchange_shard, v=v, w=w, group="p1",
                                     method=method, chunks=2,
                                     comm_dtype=comm_dtype, nbatch=1),
                             mesh=mesh, in_specs=src.batched_spec(),
                             out_specs=dst.batched_spec(), check_vma=False)
            got = many(xs)
            if comm_dtype is None:
                assert jnp.array_equal(got, want), (placement, method)
            else:
                bound = 5e-3 if comm_dtype == "bf16" else 2e-2
                for f in range(N):
                    rel = (np.linalg.norm(np.asarray(got[f] - want[f]))
                           / np.linalg.norm(np.asarray(want[f])))
                    assert rel < bound, (placement, method, comm_dtype, f, rel)
    print("ok", placement)

# traditional transposed_out with a batch: chunk axis leads, batch follows
src = make_pencil(mesh, shape, (None, "p1", None), divisors=(4, 4, 1))
dst = src.exchanged(0, 1)
x = (rng.standard_normal((N, *shape))
     + 1j * rng.standard_normal((N, *shape))).astype(np.complex64)
xs = jax.device_put(pad_global(jnp.asarray(x), src, nbatch=1),
                    src.batched_sharding())
tspec1 = jax.sharding.PartitionSpec(None, *dst.spec)
one_t = shard_map(partial(exchange_shard, v=0, w=1, group="p1",
                          method="traditional", transposed_out=True),
                  mesh=mesh, in_specs=src.spec, out_specs=tspec1, check_vma=False)
want_t = jnp.stack([one_t(xs[i]) for i in range(N)], axis=1)  # (m, N, ...)
tspecN = jax.sharding.PartitionSpec(None, None, *dst.spec)
many_t = shard_map(partial(exchange_shard, v=0, w=1, group="p1",
                           method="traditional", transposed_out=True, nbatch=1),
                   mesh=mesh, in_specs=src.batched_spec(), out_specs=tspecN,
                   check_vma=False)
got_t = many_t(xs)
assert got_t.shape == want_t.shape and jnp.array_equal(got_t, want_t)
print("BATCHED EXCHANGE NBATCH OK")
""", ndev=8)


def test_batched_auto_tuner_schedule(subproc, tmp_path):
    """method="auto" with N fields tunes the 4-dimensional candidate space
    (engine x chunks x payload x batch_fusion), keys the cache on the batch
    size (schema v4), round-trips through disk into a fresh memo, and the
    tuned batched plan still matches the stacked reference bitwise."""
    cache = tmp_path / "fft_tuner.json"
    code = f"""
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core import tuner
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig

cache = {str(cache)!r}
mesh = make_mesh((2, 2), ("p0", "p1"))
plan = ParallelFFT(mesh, (16, 8, 8), ("p0", "p1"), config=PlanConfig(method="auto", tuner_cache=cache))
bs = plan.batched_schedule(3)
assert len(bs) == plan.n_exchanges == 2
for method, chunks, comm_dtype, impl, fusion in bs:
    assert method in ("fused", "traditional", "pipelined")
    assert comm_dtype == "complex64"  # lossless budget
    assert impl == "jnp"  # no pallas budget requested
    assert fusion in ("stacked", "pipelined-across-fields", "per-field")

disk = json.loads(open(cache).read())
bkey = tuner.plan_key(plan, nfields=3)
assert bkey in disk
decoded = json.loads(bkey)
assert decoded["schema"] == tuner.SCHEMA_VERSION and decoded["nfields"] == 3
want_tags = {{tuner._tag(c) for c in tuner.batched_candidates_for(None)}}
for per in disk[bkey]["timings"].values():
    assert {{k for k in per if ":" not in k}} == want_tags

# batch size is part of the key: 1-field and 3-field entries never collide
assert tuner.plan_key(plan, nfields=1) != bkey

# fresh memo must reload from disk, not re-benchmark
tuner._MEMO.clear()
tuner.tune_plan = None
plan2 = ParallelFFT(mesh, (16, 8, 8), ("p0", "p1"), config=PlanConfig(method="auto", tuner_cache=cache))
assert plan2.batched_schedule(3) == bs

rng = np.random.default_rng(0)
x = (rng.standard_normal((3, 16, 8, 8))
     + 1j * rng.standard_normal((3, 16, 8, 8))).astype(np.complex64)
ref = ParallelFFT(mesh, (16, 8, 8), ("p0", "p1")).forward_many(jnp.asarray(x))
got = plan2.forward_many(jnp.asarray(x))
assert jnp.array_equal(got, ref)  # lossless budget: bit-identical to fused
back = plan2.backward_many(got)
np.testing.assert_allclose(np.asarray(back), x, rtol=3e-4, atol=3e-3)
print("BATCHED TUNER OK", json.dumps([list(s) for s in bs]))
"""
    out = subproc(code, ndev=4)
    assert "BATCHED TUNER OK" in out


def test_batched_models(subproc):
    """Batch-aware analytic models: flops and wire bytes scale linearly in
    nfields (int8 scale vectors included); the time model prices stacked
    below per-field (one collective latency instead of N) and
    pipelined-across-fields between them on compute-heavy stages."""
    code = """
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.redistribute import ICI_LATENCY_S, exchange_time_model

mesh = make_mesh((2, 2), ("p0", "p1"))
plan = ParallelFFT(mesh, (16, 8, 8), ("p0", "p1"))
assert plan.model_flops(nfields=3) == 3 * plan.model_flops()
assert plan.comm_bytes_per_device(8, nfields=3) == 3 * plan.comm_bytes_per_device(8)
b1 = plan.comm_bytes_per_device(8, comm_dtype="int8")
b3 = plan.comm_bytes_per_device(8, comm_dtype="int8", nfields=3)
assert b3 == 3 * b1  # per-(field, destination) scales scale with N too

t_st = plan.model_time_s(nfields=3, batch_fusion="stacked")
t_pl = plan.model_time_s(nfields=3, batch_fusion="pipelined-across-fields")
t_pf = plan.model_time_s(nfields=3, batch_fusion="per-field")
assert t_st < t_pf  # N-1 collective latencies saved
assert plan.model_time_s(nfields=1) < t_st

# stage-level: on a compute-heavy stage whose comm and FFT times are both
# large next to the collective latency, pipelined-across-fields hides
# (N-1) x max(comm, fft) and beats both stacked and per-field
from repro.core.pencil import make_pencil
src = make_pencil(mesh, (256, 256, 64), (None, "p1", "p0"))
args = dict(itemsize=8, overlap_compute_s=100e-6, nfields=4)
stacked = exchange_time_model(src, 0, 1, batch_fusion="stacked", **args)
across = exchange_time_model(src, 0, 1, batch_fusion="pipelined-across-fields", **args)
serial = exchange_time_model(src, 0, 1, batch_fusion="per-field", **args)
assert across < stacked < serial, (across, stacked, serial)
# and on a latency-bound stage (tiny block, no compute) stacked wins: one
# collective launch instead of N
tiny = make_pencil(mesh, (16, 8, 8), (None, "p1", "p0"))
args = dict(itemsize=8, overlap_compute_s=0.0, nfields=4)
t_tiny_st = exchange_time_model(tiny, 0, 1, batch_fusion="stacked", **args)
t_tiny_pf = exchange_time_model(tiny, 0, 1, batch_fusion="per-field", **args)
assert t_tiny_st < t_tiny_pf
assert ICI_LATENCY_S > 0
print("BATCHED MODELS OK")
"""
    out = subproc(code, ndev=4)
    assert "BATCHED MODELS OK" in out
