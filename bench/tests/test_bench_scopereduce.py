"""Device time by the program's own names, on hand-built traces and HLO
texts, and the readers of it."""

import types

import pytest
from benchtest_util import REPO, tiny_root  # noqa: F401  (puts the repo on sys.path)
from test_bench_tracereduce import US, event, load_reader, readings

from bench import harness, scopereduce, tracereduce

PFFT = "jit(step)/shard_map/pfft.bwd"

MODULE_TEXT = f"""HloModule jit_step, entry_computation_layout={{()}}

%fused_computation (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  ROOT %convolution.1 = f32[8]{{0}} convolution(%param_0, %param_0), metadata={{op_name="{PFFT}/stage0.xform/jit(fft)"}}
}}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  %transpose.2 = f32[8]{{0}} transpose(%param_0.1), metadata={{op_name="{PFFT}/stage0.c2r_extend/concatenate"}}
  ROOT %bitcast.3 = f32[8]{{0}} bitcast(%transpose.2)
}}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %p = (s32[], f32[8]{{0}}) parameter(0)
  %dynamic-update-slice.4 = f32[8]{{0}} dynamic-update-slice(%p), metadata={{op_name="{PFFT}/stage2.prune/jit(_take)/gather"}}
  ROOT %copy.5 = f32[8]{{0}} copy(%p)
}}

%cond (p.1: (s32[], f32[8])) -> pred[] {{
  %p.1 = (s32[], f32[8]{{0}}) parameter(0)
  ROOT %compare.6 = pred[] compare(%p.1)
}}

ENTRY %main (x: f32[8]) -> f32[8] {{
  %x = f32[8]{{0}} parameter(0)
  %fusion.10 = f32[8]{{0}} fusion(%x), kind=kOutput, calls=%fused_computation, metadata={{op_name="{PFFT}/stage0.xform/jit(fft)"}}
  %copy_fusion = f32[8]{{0}} fusion(%fusion.10), kind=kLoop, calls=%fused_computation.1
  %all-to-all.5 = f32[8]{{0}} all-to-all(%copy_fusion), dimensions={{0}}, metadata={{op_name="{PFFT}/stage1.a2a/all_to_all"}}
  %while.6 = (s32[], f32[8]{{0}}) while(%tuple.0), condition=%cond, body=%body, metadata={{op_name="{PFFT}/stage2.prune/jit(_take)/gather"}}
  %multiply.11 = f32[8]{{0}} multiply(%all-to-all.5, %x), metadata={{op_name="jit(step)/mul"}}
  %copy.12 = f32[8]{{0}} copy(%multiply.11)
  ROOT %pad.13 = f32[8]{{0}} pad(%copy.12), metadata={{op_name="{PFFT}/reshape"}}
}}
"""

#: event metadata id -> the op's HLO text, as a trace names it
NAMES = {1: "%fusion.10 = f32[8]{0} fusion(%x), kind=kOutput",   # xform (root's scope)
         2: "%copy_fusion = f32[8]{0} fusion(%fusion.10)",       # c2r_extend (root bitcast)
         3: "%all-to-all.5 = f32[8]{0} all-to-all(%copy_fusion)",  # a2a
         4: "%multiply.11 = f32[8]{0} multiply(%all-to-all.5)",  # user
         5: "%while.6 = (s32[], f32[8]{0}) while(%tuple.0)",     # a loop, prune
         6: "%copy.5 = f32[8]{0} copy(%p)",                      # loop body, no op_name
         7: "%copy.12 = f32[8]{0} copy(%multiply.11)",           # no op_name: unattributed
         8: "%pad.13 = f32[8]{0} pad(%copy.12)",                 # plan, no stage kind
         9: "%dynamic-update-slice.4 = f32[8]{0} dynamic-update-slice(%p)"}  # prune


def xspace(device_events: str, host_events: str) -> str:
    dmeta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                      for i, n in NAMES.items())
    hnames = ["bench.window", "bench.dispatch", "bench.block"]
    hmeta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                      for i, n in enumerate(hnames, 1))
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {device_events} }}
  {dmeta}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0 {host_events} }}
  {hmeta}
}}
"""


def test_scopes_by_root_and_enclosing_loop():
    module, table = scopereduce.hlo_op_scopes(MODULE_TEXT)
    assert module == "jit_step"
    kinds = {n: scopereduce.parse(s) for n, s in table.items()}
    assert kinds["fusion.10"] == ("bwd", "0", "xform")
    assert kinds["copy_fusion"] == ("bwd", "0", "c2r_extend")  # its root's operand
    assert kinds["all-to-all.5"] == ("bwd", "1", "a2a")
    assert kinds["while.6"] == ("bwd", "2", "prune")
    assert kinds["copy.5"] == ("bwd", "2", "prune")            # the loop's body takes its scope
    assert kinds["compare.6"] == ("bwd", "2", "prune")         # and its condition
    assert kinds["dynamic-update-slice.4"] == ("bwd", "2", "prune")
    assert kinds["multiply.11"] == ("-", "-", "user")
    assert kinds["copy.12"] == ("-", "-", "unattributed")
    assert kinds["pad.13"] == ("bwd", "-", "plan")


def test_parse_takes_the_innermost_stage_kind():
    assert scopereduce.parse("jit(f)/pfft.fwd/stage1.encode/stage1.guard/is_finite") == \
        ("fwd", "1", "guard")
    assert scopereduce.parse("jit(f)/stage.a2a/all_to_all") == ("-", "", "a2a")
    assert scopereduce.parse("jit(f)/fft") == ("-", "-", "user")
    assert scopereduce.parse("") == ("-", "-", "unattributed")


def reduce_text(text, steps):
    from jax.profiler import ProfileData

    _, classes = tracereduce.hlo_op_classes(MODULE_TEXT)
    return scopereduce.reduce(ProfileData.from_text_proto(text), {"jit_step": classes},
                              scopereduce.tables([MODULE_TEXT]), steps=steps)


#: window 0..100 us: xform 0-20, c2r 20-25, a2a 25-35, a loop 40-60 around its
#: body's copy 40-45 and prune op 45-60, user 60-70, unattributed 70-80,
#: plan 80-90, idle 35-40 and 90-100
DEVICE = " ".join([event(1, 0, 20 * US), event(2, 20 * US, 5 * US), event(3, 25 * US, 10 * US),
                   event(5, 40 * US, 20 * US), event(6, 40 * US, 5 * US),
                   event(9, 45 * US, 15 * US), event(4, 60 * US, 10 * US),
                   event(7, 70 * US, 10 * US), event(8, 80 * US, 10 * US)])
HOST = " ".join([event(1, 0, 100 * US), event(2, 0, 5 * US), event(3, 5 * US, 95 * US)])


@pytest.mark.parametrize("steps", [1, 4])
def test_partition_sums_to_busy_per_step(steps):
    sr = reduce_text(xspace(DEVICE, HOST), steps)
    assert sr.ndev == 1
    assert sr.busy_ns == pytest.approx(85_000 / steps)
    assert sum(sr.kind_ns.values()) == pytest.approx(sr.busy_ns)
    assert sr.overlap_ns == pytest.approx(0)
    expect = {"xform": 20_000, "c2r_extend": 5_000, "a2a": 10_000, "prune": 20_000,
              "user": 10_000, "unattributed": 10_000, "plan": 10_000}
    assert sr.kind_ns == pytest.approx({b: expect.get(b, 0.0) / steps
                                        for b in scopereduce.BUCKETS})
    assert sr.kind_ops["prune"] == pytest.approx(2 / steps)  # the body's two ops, not the loop
    assert sr.user_ns == pytest.approx(10_000 / steps)
    assert sr.unattributed_ns == pytest.approx(10_000 / steps)
    assert sr.stages[("bwd", "2", "prune")] == pytest.approx(20_000 / steps)
    assert sr.top_unattributed == [("layout:copy.12", pytest.approx(10_000 / steps))]
    assert sr.scoped_ops == 6
    lines = scopereduce.table(sr)
    assert any("prune" in line for line in lines)
    assert "busy" in lines[-2] and lines[-1].startswith("unattributed layout:copy.12")


def test_clock_shift_and_window_clip_follow_tracereduce():
    # ops 5 us before the first dispatch, the last one past the window's end
    dev = " ".join([event(1, 5 * US, 10 * US), event(4, 15 * US, 30 * US)])
    host = " ".join([event(1, 10 * US, 30 * US), event(2, 10 * US, 1 * US)])
    sr = reduce_text(xspace(dev, host), 1)
    assert sr.kind_ns["xform"] == pytest.approx(10_000)
    # shifted 5 us later: 10-20 and 20-50, the second clipped to the window's 40
    assert sr.kind_ns["user"] == pytest.approx(20_000)
    red = tracereduce.reduce(*_profile_and_classes(xspace(dev, host)))
    assert sr.busy_ns == pytest.approx(red.busy_ns)


def _profile_and_classes(text):
    from jax.profiler import ProfileData

    _, classes = tracereduce.hlo_op_classes(MODULE_TEXT)
    return ProfileData.from_text_proto(text), {"jit_step": classes}


def test_scope_reduction_leaves_the_class_reduction_as_it_was():
    """The tool reduces one trace both ways: the class reduction reads
    the same numbers with or without the scope reduction beside it."""
    pd, classes = _profile_and_classes(xspace(DEVICE, HOST))
    alone = tracereduce.reduce(pd, classes)
    snapshot = {m: dict(t) for m, t in classes.items()}
    scopereduce.reduce(pd, classes, scopereduce.tables([MODULE_TEXT]), steps=3)
    assert classes == snapshot
    assert tracereduce.reduce(pd, classes) == alone


def test_a_program_without_names_reads_as_user_work():
    text = "\n".join(line.replace(f"{PFFT}/", "jit(step)/").replace("stage0.xform/", "")
                     .replace("stage1.a2a/", "").replace("stage2.prune/", "")
                     .replace("stage0.c2r_extend/", "")
                     for line in MODULE_TEXT.splitlines())
    from jax.profiler import ProfileData

    _, classes = tracereduce.hlo_op_classes(text)
    sr = scopereduce.reduce(ProfileData.from_text_proto(xspace(DEVICE, HOST)),
                            {"jit_step": classes}, scopereduce.tables([text]), steps=1)
    assert sr.scoped_ops == 0
    r = types.SimpleNamespace(scopes=sr)
    for name in scopereduce.METRICS:
        assert load_reader(name).read(r) is None, name


def test_readers_of_scopes():
    sr = reduce_text(xspace(DEVICE, HOST), 2)
    r = readings(types.SimpleNamespace(), 2)
    for name in scopereduce.METRICS:
        assert load_reader(name).read(r) is None, name  # readings without scopes
    r = types.SimpleNamespace(scopes=sr)
    assert load_reader("fft.xform_ms").read(r) == pytest.approx(0.020 / 2)
    assert load_reader("dealias.prune_ms").read(r) == pytest.approx(0.020 / 2)
    assert load_reader("c2r.extend_ms").read(r) == pytest.approx(0.005 / 2)
    assert load_reader("user.device_ms").read(r) == pytest.approx(0.010 / 2)
    assert load_reader("exchange.a2a_ms").read(r) == pytest.approx(0.010 / 2)
    assert load_reader("exchange.realign_ms").read(r) is None  # no encode or decode ran


def test_readers_of_the_compile_recorder(monkeypatch):
    from repro.core import spans

    totals = {"trace_lower_s": 1.25, "xla_compile_s": 3.5, "cache_load_s": 0.0,
              "xla_compiles": 2, "cache_hits": 0, "cache_misses": 2}
    monkeypatch.setattr(spans, "compile_totals", lambda: dict(totals))
    r = readings(types.SimpleNamespace(), 2)
    assert load_reader("plan.trace_lower_s").read(r) == 1.25
    assert load_reader("plan.xla_compile_s").read(r) == 3.5
    totals["xla_compiles"] = 0
    assert load_reader("plan.xla_compile_s").read(r) is None  # the recorder saw nothing


def test_compile_readers_read_nothing_from_a_program_without_a_recorder(monkeypatch):
    import sys

    import repro.core

    monkeypatch.delattr(repro.core, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)  # import fails
    r = readings(types.SimpleNamespace(), 1)
    assert load_reader("plan.trace_lower_s").read(r) is None
    assert load_reader("plan.xla_compile_s").read(r) is None


def test_scopetrace_runs_a_cell(tmp_path, monkeypatch, capsys):
    """The tool's whole path on the CPU at a tiny size: the cell's own
    per-layer metrics and the compile recorder's, one JSON line last."""
    import json

    import jax

    monkeypatch.setattr(harness, "enable_compile_cache", lambda _root: "(off)")
    try:
        rc = scopereduce.main(["--workload", "tgv_dns.rk2.1chip", "--seed", str(2**31 + 7)],
                              root=tiny_root(tmp_path), platform="cpu")
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    assert rc == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    assert {"plan.compile_s", "plan.trace_lower_s", "plan.xla_compile_s"} <= set(result["metrics"])
    assert result["metrics"]["plan.xla_compile_s"] > 0
    assert set(result["set_up_s"]) == {"plan.compile", "inputs", "warmup"}
    assert "scopes  all" in out.err and "busy" in out.err


COMPILER_NAMES = f"""HloModule jit_step, entry_computation_layout={{()}}

%gather_fusion (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  %gather.1 = f32[8]{{0}} gather(%param_0), metadata={{op_name="gather"}}
  ROOT %reshape.2 = f32[8]{{0}} reshape(%gather.1), metadata={{op_name="gather"}}
}}

%stack_fusion (param_1: f32[8]) -> f32[16] {{
  %custom-call.3 = f32[16]{{0}} custom-call(), custom_call_target="AllocateBuffer"
  %param_1 = f32[8]{{0}} parameter(0)
  %multiply.4 = f32[8]{{0}} multiply(%param_1, %param_1), metadata={{op_name="jit(step)/mul"}}
  ROOT %dynamic-update-slice.5 = f32[16]{{0}} dynamic-update-slice(%custom-call.3, %multiply.4)
}}

%two_outputs (param_2: f32[8]) -> (f32[8], f32[8]) {{
  %param_2 = f32[8]{{0}} parameter(0)
  %pad.6 = f32[8]{{0}} pad(%param_2), metadata={{op_name="{PFFT}/stage4.c2r_extend/concatenate"}}
  %convolution.7 = f32[8]{{0}} convolution(%param_2, %param_2), metadata={{op_name="{PFFT}/stage4.xform/jit(fft)/fft"}}
  ROOT %tuple.8 = (f32[8]{{0}}, f32[8]{{0}}) tuple(%pad.6, %convolution.7)
}}

ENTRY %main (x: f32[8]) -> f32[8] {{
  %x = f32[8]{{0}} parameter(0)
  %fusion.10 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%gather_fusion, metadata={{op_name="{PFFT}/stage0.prune/jit(_take)/gather"}}
  %fusion.11 = f32[16]{{0}} fusion(%fusion.10), kind=kLoop, calls=%stack_fusion
  ROOT %fusion.12 = (f32[8]{{0}}, f32[8]{{0}}) fusion(%x), kind=kLoop, calls=%two_outputs
}}
"""


def test_a_jax_path_wins_over_a_compiler_name():
    _, table = scopereduce.hlo_op_scopes(COMPILER_NAMES)
    _, classes = tracereduce.hlo_op_classes(COMPILER_NAMES)
    # the root is named "gather" by a compiler pass; the fusion keeps the path
    assert scopereduce.parse(table["fusion.10"]) == ("bwd", "0", "prune")
    # a root with no name of its own takes its nearest operand's
    assert table["fusion.11"] == "jit(step)/mul"
    # a tuple root takes the operand whose class the op takes, as the class does
    assert classes["fusion.12"] == "fft"
    assert scopereduce.parse(table["fusion.12"]) == ("bwd", "4", "xform")
