"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel at the sizes users run and
compiles it for a v5e chip that is described, not attached, then checks
that the compiled program holds the kernel (``tpu_custom_call``) and fits
the chip's HBM.  This catches what interpret mode cannot: block shapes the
TPU tiling refuses, layouts Mosaic cannot lower, and tiles that overflow
the scoped VMEM.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import exchange as xk
from repro.kernels.fft.ops import fft_matmul, rfft_matmul

#: one v5e chip's HBM
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache here: keep it out of the cache entirely
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
    assert used <= V5E_HBM_BYTES, used
    return compiled


@pytest.mark.parametrize("n", [512, 2048])
def test_fourstep_kernel_compiles(one_chip, n):
    """The four-step kernel (n = 32x16 and 64x32) along a batch of 512
    transforms, complex and real input."""
    x = jax.ShapeDtypeStruct((512, n), jnp.complex64, sharding=one_chip)
    _compile(functools.partial(fft_matmul, axis=1, interpret=False), x)
    xr = jax.ShapeDtypeStruct((512, n), jnp.float32, sharding=one_chip)
    _compile(functools.partial(rfft_matmul, axis=1, interpret=False), xr)


#: per-shard exchange views of a 512^3 complex64 plan:
#: (block shape, split axis v, concat axis w, group size m)
STAGE_VIEWS = {
    "one-chip": ((512, 512, 512), 1, 0, 1),
    "pencil2x2-stage1": ((256, 256, 512), 2, 1, 2),
    "pencil2x2-stage2": ((256, 512, 256), 1, 0, 2),
    "slab4": ((128, 512, 512), 1, 0, 4),
}

KERNEL_OPS = ["encode-bf16", "encode-bf16-guard", "encode-int8",
              "encode-int8-guard", "pack-bf16-guard", "pack-int8-guard",
              "decode-bf16", "decode-int8", "unpack-bf16", "unpack-int8"]


@pytest.mark.parametrize("op", KERNEL_OPS)
@pytest.mark.parametrize("view", sorted(STAGE_VIEWS))
def test_exchange_kernel_compiles(one_chip, view, op):
    """Every encode/decode/pack/unpack kernel at each stage view."""
    shape, v, w, m = STAGE_VIEWS[view]
    kind, codec, *guard = op.split("-")
    guard = bool(guard)
    wire = jnp.int8 if codec == "int8" else jnp.bfloat16

    def sds(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    if kind in ("encode", "pack"):
        fn = xk.encode_payload if kind == "encode" else xk.pack_chunks
        _compile(lambda y: fn(y, axis=v, m=m, codec=codec, guard=guard,
                              interpret=False), sds(shape, jnp.complex64))
        return
    if kind == "decode":
        received = list(shape)
        received[v] //= m
        received[w] *= m
        scale = (sds((1, m), jnp.float32),) if codec == "int8" else ()
        _compile(lambda q, *s: xk.decode_payload(
            q, axis=w, m=m, scale=s[0] if s else None, codec=codec,
            iscomplex=True, interpret=False),
            sds((2, *received), wire), *scale)
        return
    chunk = list(shape)
    chunk[v] //= m
    scale = (sds((m, 1), jnp.float32),) if codec == "int8" else ()
    _compile(lambda q, *s: xk.unpack_chunks(
        q, w=w, m=m, scale=s[0] if s else None, codec=codec, iscomplex=True,
        interpret=False),
        sds((m, 2, *chunk), wire), *scale)


#: each kernel's stable name, and a lowering that holds it
KERNEL_NAMES = {
    "repro_fft_fourstep": ("fft", None),
    "repro_exchange_scale": ("encode", "int8"),
    "repro_exchange_encode_bf16": ("encode", "bf16"),
    "repro_exchange_encode_int8": ("encode", "int8"),
    "repro_exchange_decode_bf16": ("decode", "bf16"),
    "repro_exchange_decode_int8": ("decode", "int8"),
}


@pytest.mark.parametrize("name", sorted(KERNEL_NAMES))
def test_kernel_names_reach_the_lowered_module(one_chip, name):
    """Each Pallas kernel keeps its stable name in the module lowered for
    the chip, where a trace reader finds it."""
    kind, codec = KERNEL_NAMES[name]

    def sds(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    if kind == "fft":
        lowered = jax.jit(functools.partial(fft_matmul, axis=1, interpret=False)).lower(
            sds((512, 512), jnp.complex64))
    elif kind == "encode":
        lowered = jax.jit(lambda y: xk.encode_payload(
            y, axis=1, m=2, codec=codec, interpret=False)).lower(
            sds((256, 256, 512), jnp.complex64))
    else:
        wire = jnp.int8 if codec == "int8" else jnp.bfloat16
        scale = (sds((1, 2), jnp.float32),) if codec == "int8" else ()
        lowered = jax.jit(lambda q, *s: xk.decode_payload(
            q, axis=0, m=2, scale=s[0] if s else None, codec=codec, iscomplex=True,
            interpret=False)).lower(sds((2, 256, 128, 512), wire), *scale)
    assert name in lowered.as_text()


@pytest.mark.parametrize("direction", ["backward", "forward"])
def test_pruning_compiles_to_no_loop(one_chip, direction):
    """The DNS cell's dealiased plan (``pruned, pruned, r2c``), small (N = 32
    modes on M = 48 points, 3 fields), compiled for the chip: the pruning is
    static slices, so no ``gather`` and no ``while`` loop of row copies is
    left in the program, and its ops keep their ``stage{i}.prune`` names."""
    from test_spans import innermost_kind, instructions

    from repro.core.fftcore import TransformSpec
    from repro.core.meshutil import make_mesh
    from repro.core.pfft import ParallelFFT

    n, m = 32, 48
    mesh = make_mesh((1, 1), ("p0", "p1"), devices=list(one_chip.device_set))
    plan = ParallelFFT(mesh, (m, m, m), ("p0", "p1"), transforms=(
        TransformSpec.pruned(n), TransformSpec.pruned(n), TransformSpec.r2c(n // 2 + 1)))
    if direction == "backward":
        x = jax.ShapeDtypeStruct((3, n, n, n // 2 + 1), jnp.complex64,
                                 sharding=plan.output_pencil.batched_sharding(1))
    else:
        x = jax.ShapeDtypeStruct((3, m, m, m), jnp.float32,
                                 sharding=plan.input_pencil.batched_sharding(1))
    text = jax.jit(getattr(plan, direction)).lower(x).compile().as_text()
    ops = [(opcode, innermost_kind(op_name)) for _, _, opcode, op_name in instructions(text)]
    assert not [op for op in ops if op[0] in ("gather", "while")], direction
    assert any(kind == "prune" for _, kind in ops), direction


def _cost(compiled):
    cost = compiled.cost_analysis()
    return cost[0] if isinstance(cost, list) else cost


@pytest.fixture(scope="module")
def dns_c2r(one_chip):
    """The DNS cell's 9-field c2r block, (9, 384, 384, 129) kept bins to
    n = 384 along the last axis, compiled in each form: the plan's (dense),
    packed two rows to a complex inverse FFT, and one inverse FFT per row."""
    from repro.core import fftcore

    x = jax.ShapeDtypeStruct((9, 384, 384, 129), jnp.complex64, sharding=one_chip)
    forms = {"plan": lambda b: fftcore._irfft(b, 3, 384, "jnp", 1),
             "packed": lambda b: fftcore._c2r_packed(b, 3, 384, "jnp", 1),
             "full": lambda b: fftcore._c2r_full(b, 3, 384, "jnp")}
    lowered = {k: jax.jit(f).lower(x) for k, f in forms.items()}
    return {"lowered": {k: v.as_text() for k, v in lowered.items()},
            "compiled": {k: v.compile() for k, v in lowered.items()}}


def test_packed_c2r_halves_the_work(dns_c2r):
    """Packed two rows to a complex inverse FFT, the DNS block's c2r costs
    at most 0.55x the FLOPs of one inverse FFT per row of the full
    Hermitian extension, in fewer temporaries."""
    packed, full = dns_c2r["compiled"]["packed"], dns_c2r["compiled"]["full"]
    assert _cost(packed)["flops"] <= 0.55 * _cost(full)["flops"]
    assert (packed.memory_analysis().temp_size_in_bytes
            < full.memory_analysis().temp_size_in_bytes)


def test_dense_c2r_builds_no_spectrum(dns_c2r):
    """The DNS block's c2r takes the dense real DFT: no inverse FFT as
    traced, no ``reverse`` and no ``concatenate`` once compiled (no
    Hermitian mirror, no 384-bin spectrum), and fewer temporaries than the
    packed form."""
    from test_spans import instructions

    dense = dns_c2r["compiled"]["plan"]
    assert "stablehlo.fft" not in dns_c2r["lowered"]["plan"]
    assert "stablehlo.fft" in dns_c2r["lowered"]["packed"]
    opcodes = {opcode for _, _, opcode, _ in instructions(dense.as_text())}
    assert "convolution" in opcodes
    assert not opcodes & {"reverse", "concatenate", "fft"}, opcodes
    assert (dense.memory_analysis().temp_size_in_bytes
            < dns_c2r["compiled"]["packed"].memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("direction", ["backward", "forward"])
def test_c2c_plan_has_no_c2r_extend(one_chip, direction):
    """A pure c2c plan, compiled for the chip, holds no op under
    ``c2r_extend``, and neither does the dealiased plan, whose backward's
    c2r is the dense DFT: its dots sit under ``jit(irfft)`` in ``xform``
    and are classed ``fft``.  A plan whose r2c axis is longer than the
    dense form's bound builds its c2r spectrum under ``c2r_extend``."""
    from test_spans import innermost_kind, instructions

    from bench.tracereduce import opcode_class
    from repro.core import fftcore
    from repro.core.fftcore import TransformSpec
    from repro.core.meshutil import make_mesh
    from repro.core.pfft import ParallelFFT

    past = fftcore._C2R_DFT_MAX_N + 2
    mesh = make_mesh((1, 1), ("p0", "p1"), devices=list(one_chip.device_set))
    kinds, dots = {}, {}
    for name, shape, transforms in (
            ("c2c", (32, 32, 32), (TransformSpec.c2c(),) * 3),
            ("dealiased", (48, 48, 48), (TransformSpec.pruned(32), TransformSpec.pruned(32),
                                         TransformSpec.r2c(17))),
            ("past", (8, 8, past), (TransformSpec.c2c(), TransformSpec.c2c(),
                                    TransformSpec.r2c(past // 3 + 1)))):
        plan = ParallelFFT(mesh, shape, ("p0", "p1"), transforms=transforms)
        pen, dt = ((plan.input_pencil, plan.input_dtype) if direction == "forward"
                   else (plan.output_pencil, plan.spectral_dtype))
        x = jax.ShapeDtypeStruct((3, *pen.physical), dt, sharding=pen.batched_sharding(1))
        text = jax.jit(getattr(plan, direction)).lower(x).compile().as_text()
        ops = list(instructions(text))
        kinds[name] = {innermost_kind(op) for *_, op in ops}
        dots[name] = [(innermost_kind(op), opcode_class(opcode, op_name=op))
                      for _, _, opcode, op in ops
                      if opcode == "convolution" and "jit(irfft)" in op.split("/")]
    assert "xform" in kinds["c2c"]
    assert "c2r_extend" not in kinds["c2c"] | kinds["dealiased"]
    assert ("c2r_extend" in kinds["past"]) == (direction == "backward")
    assert not dots["c2c"] and not dots["past"]
    if direction == "backward":
        assert dots["dealiased"] and set(dots["dealiased"]) == {("xform", "fft")}
    else:
        assert not dots["dealiased"]
