"""One run of one cell of the benchmark.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name in ``BENCHMARK.json``:

- ``bench/configs/<config>.json``: sizes, transforms, dtypes, the limits
  of the comparison, and what was reduced or assumed; beside it
  ``bench/configs/<config>.py``, the configuration's plain reference;
- ``bench/traffic/<traffic>.json``: the step kind, the mesh and the loop;
- ``bench/steps/<step>.py``: the inputs made from the seed, the timed step
  and the comparison (``build(ctx)`` returns a cell with ``reset(seed)``,
  ``dispatch(i)``, ``check()``, ``work`` and ``executables``);
- ``bench/layer_metrics/<metric>.py``: ``read(readings)`` returns one
  per-layer number, or ``None`` where there is nothing to read.

A run: set-up (backend, plan build and compile or cache load, inputs from
the seed, warm-up steps), then either the measured window of ``--seconds``
(``--trace 0``: the end-to-end metrics) or a traced window of the
traffic's ``trace_steps`` (``--trace 1``: the per-layer metrics); then the
device's peak memory is read, the program's answers are read back and the
reference compares them.  The last line of standard output is the result.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path

REQUIRED_PLATFORM = "tpu"


class RunError(RuntimeError):
    pass


def load_module(path: Path):
    if not path.is_file():
        raise RunError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise RunError(f"missing {path}")
    return json.loads(path.read_text())


@dataclass
class Spec:
    """A cell as ``BENCHMARK.json`` and its files describe it."""

    root: Path
    benchmark: dict
    workload: dict
    config: dict
    traffic: dict

    @property
    def dir(self) -> Path:
        return self.root / "bench"

    def end_to_end(self) -> list[dict]:
        return [m for m in self.benchmark["end_to_end"] if self.covers(m)]

    def per_layer(self) -> list[dict]:
        reported = {m["name"] for m in self.end_to_end()}
        return [m for m in self.benchmark["per_layer"]
                if m["moves"] in reported and self.covers(m)]

    def covers(self, metric: dict) -> bool:
        return self.workload["name"] in metric.get("workloads", [self.workload["name"]])


def find(root: Path, workload: str) -> Spec:
    benchmark = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise RunError(f"no workload {workload!r} in BENCHMARK.json; known: {sorted(cells)}")
    w = cells[workload]
    return Spec(root, benchmark, w,
                load_json(root / "bench" / "configs" / f"{w['config']}.json"),
                load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"))


@dataclass
class Ctx:
    """What a step kind is given to build its cell."""

    config: dict
    traffic: dict
    mesh: object
    grid: tuple
    reference: object
    plan_overrides: dict = field(default_factory=dict)
    spans_s: dict = field(default_factory=dict)

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans_s[name] = self.spans_s.get(name, 0.0) + time.perf_counter() - t0


@dataclass
class Readings:
    """What a per-layer reader reads from."""

    reduction: object     # tracereduce.Reduction
    steps: int            # steps in the traced window
    spans_s: dict         # host-clock spans of set-up, in seconds
    work: object          # workcount.Work of one step, all chips together
    chips: int
    peaks: object         # peaks.Peaks


def require_devices(chips: int, platform: str = REQUIRED_PLATFORM):
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise RunError(f"no {platform} found: jax.devices()[0] is "
                       f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise RunError(f"the cell needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or ``$JAX_COMPILATION_CACHE_DIR``), every executable cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def make_mesh(traffic: dict, devices):
    import jax
    from jax.sharding import AxisType

    shape, axes = tuple(traffic["mesh"]["shape"]), tuple(traffic["mesh"]["axes"])
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def build_cell(spec: Spec, devices, plan_overrides=None):
    """The step kind's cell, built (plan and compile) in the ``plan.compile``
    span of the returned ctx."""
    ctx = Ctx(config=spec.config, traffic=spec.traffic,
              mesh=make_mesh(spec.traffic, devices),
              grid=tuple(spec.traffic["grid"]),
              reference=load_module(spec.dir / "configs" / f"{spec.workload['config']}.py"),
              plan_overrides=dict(plan_overrides or {}))
    step = load_module(spec.dir / "steps" / f"{spec.traffic['step']}.py")
    return step.build(ctx), ctx


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def annotated(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def traced_window(cell, steps: int):
    """Trace ``steps`` steps; returns ``(window, ProfileData)``."""
    import jax
    from jax.profiler import ProfileData

    from bench import timing

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            w = timing.closed_loop(cell.dispatch, steps=steps, span=annotated)
        finally:
            jax.profiler.stop_trace()
        found = sorted(Path(tmp).rglob("*.xplane.pb"))
        if not found:
            raise RunError("the profiler wrote no .xplane.pb")
        return w, ProfileData.from_file(str(found[-1]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def layer_metrics(spec: Spec, readings: Readings) -> dict:
    out = {}
    for m in spec.per_layer():
        reader = load_module(spec.dir / "layer_metrics" / f"{m['name']}.py")
        v = reader.read(readings)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def hlo_tables(executables: dict) -> dict:
    from bench import tracereduce

    tables = {}
    for exe in executables.values():
        module, table = tracereduce.hlo_op_classes(exe.as_text())
        tables[module] = table
    return tables


def breakdown(red) -> dict:
    return {"device_ops": [[n, ns * 1e-9] for n, ns in red.top_ops],
            "idle_gaps": [[n, ns * 1e-9] for n, ns in red.idle_gaps]}


def run(spec: Spec, *, seed: int, seconds: float, trace: bool, t0: float,
        platform: str = REQUIRED_PLATFORM) -> dict:
    """One run of the cell; returns the result object."""
    from bench import peaks as peaks_mod
    from bench import timing, tracereduce

    devices = require_devices(spec.workload["chips"], platform)
    enable_compile_cache(spec.root)
    cell, ctx = build_cell(spec, devices)
    with ctx.span("inputs"):
        cell.reset(seed)
    with ctx.span("warmup"):
        timing.closed_loop(lambda _i: cell.dispatch(-1), steps=spec.traffic["warmup_steps"])
    setup_s = time.perf_counter() - t0
    print(f"bench: setup_s={setup_s:.3f} of which "
          + " ".join(f"{k}={v:.3f}" for k, v in ctx.spans_s.items()), file=sys.stderr, flush=True)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    import jax

    device["count"] = len(jax.devices())
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {}, "device": device}
    if trace:
        w, pd = traced_window(cell, spec.traffic["trace_steps"])
        red = tracereduce.reduce(pd, hlo_tables(cell.executables))
        del pd
        readings = Readings(red, w.steps, dict(ctx.spans_s), cell.work,
                            spec.workload["chips"],
                            peaks_mod.peaks_for(dev.device_kind) if platform == REQUIRED_PLATFORM
                            else None)
        result["metrics"] = layer_metrics(spec, readings)
        device["busy_s"] = red.busy_ns * 1e-9
        device["window_s"] = red.window_ns * 1e-9
        result["breakdown"] = breakdown(red)
    else:
        w = timing.closed_loop(cell.dispatch, seconds=seconds,
                               min_steps=spec.traffic["min_steps"])
        values = {"setup_s": setup_s, "step_ms": timing.step_ms(w),
                  "step_p90_ms": timing.step_p90_ms(w)}
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in spec.end_to_end()}
    device["memory_peak_bytes"] = memory_peak(devices)
    result["attempted"] = w.steps
    print(f"bench: window steps={w.steps} wall_s={w.wall_s:.4f} step_ms "
          f"min={1e3 * min(w.step_s):.3f} max={1e3 * max(w.step_s):.3f} "
          f"(step {w.step_s.index(max(w.step_s))})", file=sys.stderr, flush=True)

    checks = cell.check()
    del cell
    result["failed"] = sum(not c.ok for c in checks)
    result["correct"] = result["failed"] == 0
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one cell of the benchmark once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: Path, t0: float, platform: str = REQUIRED_PLATFORM) -> int:
    args = parse(argv)
    try:
        spec = find(root, args.workload)
        result = run(spec, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                     t0=t0, platform=platform)
    except RunError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} value={c['value']!r} limit={c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0

