"""The trace reduction on hand-built traces and HLO texts."""

import base64
import types

import pytest
from benchtest_util import REPO  # noqa: F401  (puts the repo on sys.path)

from bench import harness, tracereduce

MODULE_TEXT = """HloModule jit_step, entry_computation_layout={()}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %convolution.1 = f32[8]{0} convolution(%param_0, %param_0), metadata={op_name="jit(step)/jit(fft)"}
}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %transpose.2 = f32[8]{0} transpose(%param_0.1), metadata={op_name="jit(step)/transpose"}
  ROOT %bitcast.3 = f32[8]{0} bitcast(%transpose.2)
}

%fused_computation.2 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  ROOT %multiply.4 = f32[8]{0} multiply(%param_0.2, %param_0.2), metadata={op_name="jit(step)/mul"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.10 = f32[8]{0} fusion(%x), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(step)/jit(fft)"}
  %copy_fusion = f32[8]{0} fusion(%fusion.10), kind=kLoop, calls=%fused_computation.1
  %all-to-all.5 = f32[8]{0} all-to-all(%copy_fusion), dimensions={0}
  %while.6 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(step)/jit(_take)/gather"}
  %fusion.11 = f32[8]{0} fusion(%all-to-all.5), kind=kLoop, calls=%fused_computation.2
  %custom-call.7 = f32[8]{0} custom-call(%fusion.11), custom_call_target="tpu_custom_call", backend_config={"custom_call_config":{"body":"BODY"}}
  ROOT %custom-call.8 = f32[8]{0} custom-call(%custom-call.7), custom_call_target="tpu_custom_call", backend_config={"custom_call_config":{"body":"KERN"}}
}
"""


def module_text():
    fft_body = base64.b64encode(b"\x00func fourstep_pallas_call.\x01").decode()
    other_body = base64.b64encode(b"\x00func encode_pallas_call.\x01").decode()
    return MODULE_TEXT.replace("BODY", fft_body).replace("KERN", other_body)


def test_hlo_classes_by_root_metadata_and_kernel():
    module, table = tracereduce.hlo_op_classes(module_text())
    assert module == "jit_step"
    assert table["fusion.10"] == "fft"          # a DFT convolution XLA made of an fft
    assert table["copy_fusion"] == "layout"      # root bitcast of a transpose
    assert table["all-to-all.5"] == "collective"
    assert table["while.6"] == "layout"          # a gather loop
    assert table["fusion.11"] == "other"
    assert table["custom-call.7"] == "fft"       # the four-step kernel
    assert table["custom-call.8"] == "pallas"


def test_hlo_classes_of_a_compiled_cpu_module():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.fft.fft(jnp.concatenate([x, 2 * x]), axis=0).T * 3)
    _, table = tracereduce.hlo_op_classes(f.lower(jnp.zeros((8, 4), jnp.complex64)).compile().as_text())
    assert "fft" in table.values()
    assert "layout" in table.values()


def event(meta: int, start_ps: int, dur_ps: int) -> str:
    return f"events {{ metadata_id: {meta} offset_ps: {start_ps} duration_ps: {dur_ps} }}"


def xspace(device_events: str, host_events: str, async_events: str = "") -> str:
    names = ["%fusion.10 = f32[8]{0} fusion(%x), kind=kOutput",  # 1 fft
             "%copy_fusion = f32[8]{0} fusion(%fusion.10)",       # 2 layout
             "%all-to-all.5 = f32[8]{0} all-to-all(%copy_fusion)",  # 3 collective
             "%fusion.11 = f32[8]{0} fusion(%all-to-all.5)",      # 4 other
             "%while.6 = (s32[], f32[8]{0}) while(%tuple.0)"]     # 5 layout (loop)
    dmeta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                      for i, n in enumerate(names, 1))
    hnames = ["bench.window", "bench.dispatch", "bench.block"]
    hmeta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                      for i, n in enumerate(hnames, 1))
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {device_events} }}
  lines {{ id: 2 name: "Async XLA Ops" timestamp_ns: 0 {async_events} }}
  {dmeta}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0 {host_events} }}
  {hmeta}
}}
"""


US = 1_000_000  # picoseconds in a microsecond


def reduce_text(text):
    from jax.profiler import ProfileData

    _, table = tracereduce.hlo_op_classes(module_text())
    return tracereduce.reduce(ProfileData.from_text_proto(text), {"jit_step": table})


def test_busy_union_idle_share_and_classes():
    # window 0..100 us; dispatch at 0; ops: fft 0-30, layout 30-40, idle 40-50,
    # collective 50-70, other 70-90, idle 90-100
    dev = " ".join([event(1, 0, 30 * US), event(2, 30 * US, 10 * US), event(3, 50 * US, 20 * US),
                    event(4, 70 * US, 20 * US)])
    host = " ".join([event(1, 0, 100 * US), event(2, 0, 5 * US), event(3, 5 * US, 95 * US)])
    red = reduce_text(xspace(dev, host))
    assert red.ndev == 1
    assert red.window_ns == pytest.approx(100_000)
    assert red.busy_ns == pytest.approx(80_000)
    assert 1 - red.busy_ns / red.window_ns == pytest.approx(0.2)
    assert red.class_ns == pytest.approx({"fft": 30_000, "collective": 20_000, "pallas": 0,
                                          "layout": 10_000, "other": 20_000})
    assert red.exposed_collective_ns == pytest.approx(20_000)  # nothing overlaps it
    assert red.top_ops[0] == ("fft:fusion.10", pytest.approx(30_000))
    assert [g for _, g in red.idle_gaps[:2]] == pytest.approx([10_000, 10_000])
    assert red.idle_gaps[0][0] == "bench.block"


def test_exposed_share_with_overlapping_compute():
    # an async collective 10-50 overlapped by an fft op 20-40: 20 of its
    # 40 us exposed; a prefetch copy on the async line is no work of its own
    dev = event(1, 20 * US, 20 * US)
    host = " ".join([event(1, 0, 60 * US), event(2, 0, 1 * US)])
    red = reduce_text(xspace(dev, host, " ".join([event(3, 10 * US, 40 * US),
                                                  event(2, 0, 60 * US)])))
    assert red.class_ns["collective"] == pytest.approx(40_000)
    assert red.exposed_collective_ns == pytest.approx(20_000)
    assert red.busy_ns == pytest.approx(40_000)


def test_enclosing_loop_counts_its_body_once():
    # a while 0-50 encloses a copy 0-20 and an fft 20-50
    dev = " ".join([event(5, 0, 50 * US), event(2, 0, 20 * US), event(1, 20 * US, 30 * US)])
    host = " ".join([event(1, 0, 50 * US), event(2, 0, 1 * US)])
    red = reduce_text(xspace(dev, host))
    assert red.class_ns["layout"] == pytest.approx(20_000)
    assert red.class_ns["fft"] == pytest.approx(30_000)
    assert red.busy_ns == pytest.approx(50_000)


def test_device_clock_is_put_after_the_first_dispatch():
    # the device's ops appear 5 us before the host dispatched anything
    dev = " ".join([event(1, 5 * US, 10 * US), event(4, 15 * US, 10 * US)])
    host = " ".join([event(1, 10 * US, 30 * US), event(2, 10 * US, 1 * US)])
    red = reduce_text(xspace(dev, host))
    assert red.busy_ns == pytest.approx(20_000)
    assert red.class_ns["fft"] == pytest.approx(10_000)


def readings(red, steps):
    from bench.peaks import peaks_for
    from bench.workcount import Work

    return harness.Readings(reduction=red, steps=steps, spans_s={"plan.compile": 1.5},
                            work=Work(flops=1e9, bytes=8.19e8), chips=1,
                            peaks=peaks_for("TPU v5 lite"))


def load_reader(name):
    return harness.load_module(REPO / "bench" / "layer_metrics" / f"{name}.py")


@pytest.mark.parametrize("steps", [1, 4])
def test_readers_normalise_per_step(steps):
    dev = " ".join([event(1, 0, 30 * US), event(2, 30 * US, 10 * US), event(3, 50 * US, 20 * US),
                    event(4, 70 * US, 20 * US)])
    host = " ".join([event(1, 0, 100 * US), event(2, 0, 5 * US)])
    r = readings(reduce_text(xspace(dev, host)), steps)
    assert load_reader("fft.device_ms").read(r) == pytest.approx(0.030 / steps)
    assert load_reader("layout.copy_ms").read(r) == pytest.approx(0.010 / steps)
    assert load_reader("exchange.collective_ms").read(r) == pytest.approx(0.020 / steps)
    assert load_reader("exchange.exposed_frac").read(r) == pytest.approx(1.0)
    assert load_reader("device.idle_frac").read(r) == pytest.approx(0.2)
    assert load_reader("plan.compile_s").read(r) == 1.5
    # the work's least time is 1 ms (819 MB at 819 GB/s) against 30/steps us
    assert load_reader("fft_roofline").read(r) == pytest.approx(100 * 1e-3 / (30e-6 / steps))


def test_readers_return_nothing_where_nothing_ran():
    red = types.SimpleNamespace(class_ns={c: 0.0 for c in tracereduce.CLASSES},
                                exposed_collective_ns=0.0, busy_ns=0.0, window_ns=0.0, ndev=0)
    r = readings(red, 3)
    for name in ("fft.device_ms", "fft_roofline", "layout.copy_ms", "exchange.collective_ms",
                 "exchange.exposed_frac", "device.idle_frac"):
        assert load_reader(name).read(r) is None, name
