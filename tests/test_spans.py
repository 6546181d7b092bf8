"""The names the plan gives its work in the compiled program
(``repro.core.spans``), and the compile recorder.

The plans compile on four virtual CPU devices in one child process; each
test reads what it needs from that one compile."""

import json
import os
import re
import subprocess
import sys

import pytest

from conftest import REPO, SRC

if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))  # bench/ is a top-level package

ENGINES = ("fused", "traditional", "pipelined")
PAYLOADS = ("complex64", "bf16", "int8")
#: the batched executions: each field's exchange on its own
FUSIONS = ("per-field", "pipelined-across-fields")
CASES = ([f"{e}-{p}" for e in ENGINES for p in PAYLOADS] + ["pruned-r2c"]
         + [f"{f}-bf16" for f in FUSIONS])

_COMPILE = r"""
import contextlib, json, re
import jax
from repro.core import spans
from repro.core.fftcore import TransformSpec
from repro.core.meshutil import make_mesh
from repro.core.pfft import ParallelFFT
from repro.core.planconfig import PlanConfig

mesh, grid = make_mesh((2, 2), ("p0", "p1")), ("p0", "p1")

def plan_of(case):
    if case == "pruned-r2c":
        return ParallelFFT(mesh, (12, 12, 12), grid, config=PlanConfig(method="fused"),
                           transforms=(TransformSpec.pruned(8), TransformSpec.pruned(8),
                                       TransformSpec.r2c(5)))
    method, payload = case.rsplit("-", 1)
    if method in FUSIONS:
        return ParallelFFT(mesh, (8, 8, 8), grid, config=PlanConfig(
            method="pipelined", chunks=2, comm_dtype=payload, batch_fusion=method))
    return ParallelFFT(mesh, (8, 8, 8), grid, config=PlanConfig(
        method=method, comm_dtype=payload, chunks=2 if method == "pipelined" else 1,
        guard="strict" if case == "fused-int8" else "off"))

def lowered(case, direction, nfields=1):
    plan = plan_of(case)
    pen, dt = ((plan.input_pencil, plan.input_dtype) if direction == "forward"
               else (plan.output_pencil, plan.spectral_dtype))
    fn = plan.executor(direction, nfields if nfields > 1 else None)
    shape = ((nfields,) if nfields > 1 else ()) + pen.physical
    shard = pen.batched_sharding(1) if nfields > 1 else pen.sharding
    x = jax.ShapeDtypeStruct(shape, dt, sharding=shard)
    return jax.jit(fn).lower(x)

def hlo(case, direction, nfields=1):
    return lowered(case, direction, nfields).compile().as_text()

out = {"ops": {}, "plain": {}, "scoped": {}}
# before XLA's rewrites, which may merge a slice into the next one's scope
out["lowered"] = lowered("pruned-r2c", "backward").as_text(debug_info=True)
for case in CASES:
    nfields = 2 if case.rsplit("-", 1)[0] in FUSIONS else 1
    out["ops"][case] = {d: hlo(case, d, nfields) for d in ("forward", "backward")}

def both(case, nfields):
    return "\n".join(hlo(case, d, nfields) for d in ("backward", "forward"))

for case, nfields in (("fused-int8", 1), ("pruned-r2c", 3)):
    out["scoped"][case] = both(case, nfields)
    real = spans.scope
    spans.scope = lambda name: contextlib.nullcontext()
    try:
        out["plain"][case] = both(case, nfields)
    finally:
        spans.scope = real

from repro.core import fftcore

def unreachable(*args, **kwargs):
    raise AssertionError("an unpruned plan reached the pruning")
pruning = fftcore._keep_centered, fftcore._scatter_centered
fftcore._keep_centered = fftcore._scatter_centered = unreachable
try:
    out["unpruned"] = {d: hlo("fused-complex64", d) for d in ("forward", "backward")}
finally:
    fftcore._keep_centered, fftcore._scatter_centered = pruning
print("HLO=" + json.dumps(out))
"""

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\S+|\(.*?\))\s+([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_KIND = re.compile(r"^stage\d*\.(\w+)$")


def _run(code: str, ndev: int, timeout: int = 900) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
               PYTHONPATH=os.pathsep.join([str(SRC), str(REPO)]))
    proc = subprocess.run([sys.executable, "-c", code], env=env, timeout=timeout,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-6000:]
    return proc.stdout


@pytest.fixture(scope="module")
def compiled():
    out = _run(f"CASES = {CASES!r}\nFUSIONS = {FUSIONS!r}\n" + _COMPILE, ndev=4)
    return json.loads(out.split("HLO=", 1)[1])


def instructions(text: str):
    """``(name, result type, opcode, op_name)`` of every instruction of a
    module's text, fused computations included."""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            yield m.group(1), m.group(2), m.group(3), op.group(1) if op else ""


def innermost_kind(op_name: str) -> str | None:
    kinds = [m.group(1) for m in map(_KIND.match, op_name.split("/")) if m]
    return kinds[-1] if kinds else None


@pytest.mark.parametrize("case", CASES)
def test_every_op_of_an_exchange_sits_under_its_kind(compiled, case):
    from bench import tracereduce

    payload = case.rsplit("-", 1)[1]
    for direction, text in compiled["ops"][case].items():
        kinds = {}
        pruned = 0  # slices, concatenates and pads under ``prune``
        for name, rtype, opcode, op_name in instructions(text):
            kind = innermost_kind(op_name)
            kinds.setdefault(kind, 0)
            kinds[kind] += 1
            if opcode.startswith("all-to-all"):
                assert kind == "a2a", (direction, name, op_name)
            if case == "pruned-r2c":
                # the pruning is static slices: no gather, no loop, and each
                # slice, concatenate or pad is the pruning's, the padded
                # axis's or the dense c2r's own (its kept bins, inside
                # ``jit(irfft)``), under its kind
                assert opcode not in ("gather", "while"), (direction, name, op_name)
                assert not op_name.endswith("/gather"), (direction, name, op_name)
                if op_name.split("/")[-1] in ("slice", "concatenate", "pad"):
                    allowed = ("prune", "repad") + (
                        ("xform",) if "jit(irfft)" in op_name.split("/") else ())
                    assert kind in allowed, (direction, name, op_name)
                    pruned += kind == "prune"
            if op_name and opcode != "constant" and re.match(r"\(?(bf16|s8)\[", rtype):
                # the narrow wire payload (the CPU compiler's own widening
                # converts carry no op_name)
                assert kind in ("encode", "a2a"), (direction, name, op_name)
            # the names change no class: an FFT stays an FFT, a gather a gather
            parts = op_name.split("/")
            bare = "/".join(p for p in parts if not (_KIND.match(p) or p.startswith("pfft.")))
            assert (bool(tracereduce._FFT_OP.search(op_name))
                    == bool(tracereduce._FFT_OP.search(bare))), op_name
            assert parts[-1] == bare.split("/")[-1]
            if op_name:
                scopes = [p for p in parts if _KIND.match(p) or p.startswith("pfft.")]
                assert not any(tracereduce._FFT_OP.search(p) for p in scopes), op_name
        assert kinds.get("xform", 0) > 0 and kinds.get("a2a", 0) > 0
        if payload in ("bf16", "int8"):
            assert kinds.get("encode", 0) > 0 and kinds.get("decode", 0) > 0, direction
        if case == "pruned-r2c":
            assert kinds.get("prune", 0) > 0
            assert pruned > 0, direction
            if direction == "backward":
                # the c2r is the dense DFT (5 kept bins to 12 points): no
                # spectrum is built around an inverse FFT
                assert kinds.get("c2r_extend", 0) == 0
                assert any("jit(irfft)" in op.split("/") and innermost_kind(op) == "xform"
                           for *_, op in instructions(text))
                # the slice to the r2c axis's logical extent sits under
                # ``repad`` in the program as traced; compiled, it merges
                # into the dense c2r's slices of the kept bins
                assert re.search(r"stage\d+\.repad/slice", compiled["lowered"])
            else:
                assert kinds.get("repad", 0) > 0
        if case == "fused-int8":  # the guarded plan
            assert kinds.get("guard", 0) > 0
        scope = "pfft.fwd" if direction == "forward" else "pfft.bwd"
        assert any(scope in op.split("/") for *_, op in instructions(text)), direction


def _without_metadata(text: str) -> list[str]:
    """The module's computations and instructions, metadata left out (the
    text's tables of source files and stack frames are metadata too)."""
    lines = []
    for line in text.splitlines():
        if _INSTR.match(line) or line.endswith("{") or line.strip() == "}":
            lines.append(re.sub(r",?\s*metadata=\{[^}]*\}", "", line).rstrip())
    return lines


@pytest.mark.parametrize("case", ["fused-int8", "pruned-r2c"])
def test_names_change_metadata_only(compiled, case):
    """Compiled with every scope a null context, the optimized HLO of both
    directions is the same, op for op."""
    scoped = _without_metadata(compiled["scoped"][case])
    plain = _without_metadata(compiled["plain"][case])
    assert "pfft.bwd" in compiled["scoped"][case]
    assert "pfft.bwd" not in compiled["plain"][case]
    assert len(scoped) > 100
    assert scoped == plain


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_unpruned_plan_emits_no_pruning(compiled, direction):
    """A plain c2c plan has no op under ``prune``, and compiles to the same
    HLO, op for op, with the pruning helpers made unreachable."""
    text = compiled["ops"]["fused-complex64"][direction]
    assert not any(innermost_kind(op) == "prune" for *_, op in instructions(text))
    ops = _without_metadata(text)
    assert len(ops) > 50
    assert ops == _without_metadata(compiled["unpruned"][direction])


_RECORDER = r"""
import json, sys
import jax, numpy as np
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from repro.core import spans
spans.install()
spans.install()  # idempotent: events still count once
x = np.arange(64, dtype=np.float32)
f = lambda v: (v * 3.0 + 1.0).sum()
before = spans.compile_totals()
jax.jit(f)(x).block_until_ready()
first = spans.compile_totals()
jax.clear_caches()
jax.jit(f)(x).block_until_ready()
second = spans.compile_totals()
print("REC=" + json.dumps([before, first, second]))
"""


def test_recorder_counts_a_compile_then_a_cache_hit(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC))
    proc = subprocess.run([sys.executable, "-c", _RECORDER, str(tmp_path / "cache")], env=env,
                          timeout=300, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-4000:]
    before, first, second = json.loads(proc.stdout.split("REC=", 1)[1])
    assert before["xla_compiles"] == before["cache_hits"] == 0
    assert first["xla_compiles"] == 1 and first["cache_misses"] == 1
    assert first["cache_hits"] == 0
    assert first["trace_lower_s"] > 0 and first["xla_compile_s"] > 0
    assert second["xla_compiles"] == 1 and second["cache_hits"] == 1
    assert second["cache_load_s"] > 0
    assert second["xla_compile_s"] >= first["xla_compile_s"] + second["cache_load_s"] * 0.999


def test_stage_names():
    from repro.core import spans

    assert spans.stage_name("xform") == "stage.xform"
    with spans.stage(3):
        assert spans.stage_name("a2a") == "stage3.a2a"
        with spans.stage(4):
            assert spans.stage_name("prune") == "stage4.prune"
        assert spans.stage_name("decode") == "stage3.decode"
    with pytest.raises(ValueError):
        spans.stage_name("fft")


def test_recorder_counts_every_event_from_many_threads(monkeypatch):
    """The listeners run on whichever thread compiles: no count is lost."""
    import threading

    from repro.core import spans

    monkeypatch.setattr(spans, "_totals", dict.fromkeys(spans._totals, 0))
    threads, per = 16, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(k):
            for j in range(per):
                t = float(k * per + j)
                spans._on_span(spans._COMPILE, t, t + 0.5, fun_name="f")
                spans._on_event(spans._CACHE_HIT)
                spans._on_duration(spans._CACHE_LOAD, 0.25, fun_name="f")
        pool = [threading.Thread(target=work, args=(k,)) for k in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(old)
    totals = spans.compile_totals()
    assert totals["cache_hits"] == threads * per
    assert totals["xla_compile_s"] == pytest.approx(0.5 * threads * per)
    assert totals["cache_load_s"] == pytest.approx(0.25 * threads * per)
    assert totals["xla_compiles"] == 0  # every compile here was a cache load


def test_recorder_counts_nested_spans_once(monkeypatch):
    """Spans arrive as they end, children first: an enclosing span takes
    its children's place, and a trace inside a compile counts as compile."""
    import threading

    from repro.core import spans

    monkeypatch.setattr(spans, "_totals", dict.fromkeys(spans._totals, 0))
    monkeypatch.setattr(spans, "_thread", threading.local())
    for event, start, end in [(spans._TRACE, 1.0, 2.0),     # inner trace
                              (spans._TRACE, 2.5, 3.0),     # a second one
                              (spans._TRACE, 0.0, 4.0),     # the outer trace
                              (spans._LOWER, 4.0, 5.0),     # its lowering
                              (spans._TRACE, 6.5, 7.0),     # an eager op's trace
                              (spans._COMPILE, 6.0, 8.0)]:  # inside the compile
        spans._on_span(event, start, end)
    totals = spans.compile_totals()
    assert totals["trace_lower_s"] == pytest.approx(4.0 + 1.0)
    assert totals["xla_compile_s"] == pytest.approx(2.0)
    assert totals["xla_compiles"] == 1
    spans._on_span(spans._TRACE, 5.5, 8.5)  # a trace around the compile
    spans._on_span(spans._LOWER, 9.0, 9.5)  # after it all
    totals = spans.compile_totals()
    assert totals["trace_lower_s"] == pytest.approx(4.0 + 1.0 + 1.0 + 0.5)
    assert totals["xla_compile_s"] == pytest.approx(2.0)
