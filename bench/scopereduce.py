"""Device time by the names the program gives its own work.

The program names each op it emits for a plan (``repro.core.spans``): a
direction scope ``pfft.fwd``/``pfft.bwd`` and beneath it a stage scope
``stage{i}.<kind>``.  The names reach each instruction's ``op_name``
metadata, which this module reads from the executables' HLO text, and the
ops' device time comes from the same trace lines :mod:`bench.tracereduce`
reads, with the same clock shift and window clip.

An instruction's scope path is its ``op_name``, a fusion's that of its
root, followed exactly as :func:`tracereduce.hlo_op_classes` follows it
for the class (through pass-through ops, and through a tuple to the
operand whose class it takes).  A JAX path
(``jit(step)/...``) wins over a bare name a compiler pass gave an op it
made (``gather``): on the path the innermost JAX path is taken; where the
path holds none, a fusion takes the nearest among its root's operands
(an ``AllocateBuffer`` + ``dynamic-update-slice`` root has none of its
own).  An instruction with no ``op_name`` at all inside a loop's body or
condition, or a called computation, takes the scope of the loop or call.  Each op
counts once, by the innermost ``stage{i}.<kind>`` on its path:

- one of :data:`KINDS`;
- ``plan``: inside a ``pfft.*`` scope and outside every stage kind;
- ``user``: with an ``op_name`` outside every ``pfft.*`` scope (the
  user's own work around the plan);
- ``unattributed``: no ``op_name`` (or an op the executables' text does
  not name).

On a device whose ops do not overlap, these sum to its busy time; where
an async collective runs under other ops, the overlapped time is counted
in both and given as ``overlap_ns``.  Every number is per step and the
mean over the devices traced.  A program that names nothing reads
``scoped_ops == 0``: its readers return nothing.

The readers of these numbers (``bench/layer_metrics/``: ``fft.xform_ms``,
``dealias.prune_ms``, ``c2r.extend_ms``, ``user.device_ms``,
``exchange.a2a_ms``, ``exchange.realign_ms``) read ``r.scopes``, a
:class:`ScopeReduction`.  ``bench/scopetrace.py`` runs one cell's traced
window, reduces it both ways and prints them (:func:`main`).
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import types
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

from bench import tracereduce as tr

KINDS = ("xform", "prune", "c2r_extend", "repad", "encode", "a2a", "decode", "guard")
BUCKETS = KINDS + ("plan", "user", "unattributed")

_STAGE = re.compile(r"^stage(\d*)\.(" + "|".join(KINDS) + r")$")
_DIRECTION = re.compile(r"^pfft\.(fwd|bwd)$")
_CALLED = re.compile(r"\b(?:body|condition|calls|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_CONTROL = {"while", "call", "conditional"}


# ---------------------------------------------------------------------------
# the executables' text


def hlo_op_scopes(hlo_text: str) -> tuple[str, dict[str, str]]:
    """``(module name, {instruction name: scope path})`` of one HLO
    module's text; ``""`` where no ``op_name`` applies."""
    module = ""
    comps: dict[str, dict] = {}
    callers: dict[str, tuple[str, str]] = {}  # computation -> (comp, instr) of its loop/call
    cur = None
    for line in hlo_text.splitlines():
        m = tr._MODULE.match(line)
        if m:
            module = m.group(1)
            continue
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = line.split()[1] if line.startswith("ENTRY") else line.split()[0]
            cur = name.lstrip("%")
            comps.setdefault(cur, {"instrs": {}, "root": None})
            continue
        if cur is None:
            continue
        m = tr._INSTR.match(line)
        if not m:
            continue
        root, name, opcode, rest = m.groups()
        calls = tr._CALLS.search(rest)
        op_name = tr._OP_NAME.search(rest)
        comps[cur]["instrs"][name] = (opcode, tr._OPERAND.findall(rest.split(")", 1)[0]),
                                      calls.group(1) if calls else None,
                                      op_name.group(1) if op_name else "")
        if root:
            comps[cur]["root"] = name
        if opcode in _CONTROL:
            called = _CALLED.findall(rest)
            b = _BRANCHES.search(rest)
            if b:
                called += [c.strip().lstrip("%") for c in b.group(1).split(",")]
            for c in called:
                callers.setdefault(c, (cur, name))

    _, classes = tr.hlo_op_classes(hlo_text)
    memo: dict[tuple[str, str], str] = {}

    def nearest(comp: str, name: str) -> str:
        """The first JAX path among the operands of ``name``, breadth first."""
        todo, seen = [name], {name}
        while todo:
            instr = comps[comp]["instrs"].get(todo.pop(0))
            if instr is None:
                continue
            if "/" in instr[3]:
                return instr[3]
            for o in instr[1]:
                if o not in seen:
                    seen.add(o)
                    todo.append(o)
        return ""

    def own(comp: str, name: str, depth: int = 0) -> str:
        """The scope along the path the class takes: the innermost JAX path
        (``jit(...)/...``) on it, over a compiler's bare ``op_name``."""
        instr = comps[comp]["instrs"].get(name)
        if instr is None or depth > 32:
            return ""
        opcode, operands, calls, op_name = instr
        if opcode == "fusion" and calls in comps and comps[calls]["root"]:
            root = comps[calls]["root"]
            inner = own(calls, root, depth + 1) or nearest(calls, root)
        elif opcode == "tuple" and operands:
            # the operand whose class the tuple takes
            first = min(operands, key=lambda o: tr._PRIORITY.get(classes.get(o), len(tr.CLASSES)))
            inner = own(comp, first, depth + 1)
        elif opcode in tr._PASS_THROUGH and operands and operands[0] in comps[comp]["instrs"]:
            inner = own(comp, operands[0], depth + 1)
        else:
            inner = ""
        if "/" in inner or "/" not in op_name:
            return inner or op_name
        return op_name

    def scope(comp: str, name: str, depth: int = 0) -> str:
        key = (comp, name)
        if key not in memo:
            s = own(comp, name)
            if not s and comp in callers and depth <= 32:
                s = scope(*callers[comp], depth + 1)
            memo[key] = s
        return memo[key]

    return module, {name: scope(comp, name)
                    for comp, body in comps.items() for name in body["instrs"]}


def tables(hlo_texts) -> dict[str, dict[str, str]]:
    """``{module: {instruction: scope path}}`` of the executables' texts."""
    out = {}
    for text in hlo_texts:
        module, table = hlo_op_scopes(text)
        out[module] = table
    return out


def parse(path: str) -> tuple[str, str, str]:
    """``(direction, stage, bucket)`` of one scope path: direction ``fwd``,
    ``bwd`` or ``-``; the stage index (``""`` for stage-less work, ``-``
    outside every stage kind); the bucket, one of :data:`BUCKETS`."""
    if not path:
        return "-", "-", "unattributed"
    direction, stage, bucket = "-", "-", None
    for part in path.split("/"):
        d = _DIRECTION.match(part)
        if d:
            direction = d.group(1)
            continue
        s = _STAGE.match(part)
        if s:
            stage, bucket = s.group(1), s.group(2)
    if bucket is None:
        bucket = "plan" if direction != "-" else "user"
    return direction, stage, bucket


# ---------------------------------------------------------------------------
# the trace


class _Scoped(str):
    """An op's class as :mod:`bench.tracereduce` reads it, carrying its
    scope path, so that tracereduce's own reader of the device lines
    yields both."""

    scope: str

    def __new__(cls, op_class: str, scope: str):
        out = super().__new__(cls, op_class)
        out.scope = scope
        return out


@dataclass
class ScopeReduction:
    ndev: int
    busy_ns: float                                  # per step, mean over devices
    kind_ns: dict[str, float]                       # per step, each of BUCKETS
    kind_ops: dict[str, float]                      # ops per step, each of BUCKETS
    stages: dict[tuple[str, str, str], float]       # (direction, stage, kind) -> ns per step
    overlap_ns: float                               # counted under two ops at once
    scoped_ops: int                                 # ops inside a pfft.* or stage scope
    top_unattributed: list[tuple[str, float]]       # (class:name, ns per step)

    @property
    def user_ns(self) -> float:
        return self.kind_ns["user"]

    @property
    def unattributed_ns(self) -> float:
        return self.kind_ns["unattributed"]


def _aligned(pd, devices):
    """The device ops shifted and the window clipped as
    :func:`tracereduce.reduce` does: the least shift that puts a device's
    first op after the first dispatch; the window of ``bench.window``."""
    spans = tr._host_spans(pd)
    dispatched = [s for s, _, n in spans if n == tr.DISPATCH_SPAN]
    if dispatched:
        for plane, ops in devices.items():
            early = min(dispatched) - min((o.start for o in ops), default=min(dispatched))
            if early > 0:
                devices[plane] = [tr.Op(o.start + early, o.end + early, o.name, o.cls)
                                  for o in ops]
    windows = [(s, e) for s, e, n in spans if n == tr.WINDOW_SPAN]
    all_ops = [op for ops in devices.values() for op in ops]
    if windows:
        w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    elif all_ops:
        w0, w1 = min(op.start for op in all_ops), max(op.end for op in all_ops)
    else:
        w0 = w1 = 0.0
    return {plane: [tr.Op(max(o.start, w0), min(o.end, w1), o.name, o.cls)
                    for o in ops if o.end > w0 and o.start < w1]
            for plane, ops in devices.items()}


def reduce(pd, class_tables: dict[str, dict[str, str]],
           scope_tables: dict[str, dict[str, str]], *, steps: int,
           top: int = 10) -> ScopeReduction:
    """Reduce a ``jax.profiler.ProfileData`` of ``steps`` steps by scope.
    ``class_tables`` are :func:`tracereduce.hlo_op_classes` tables (they
    pick the device lines' ops as :func:`tracereduce.reduce` does),
    ``scope_tables`` :func:`tables` of the same executables."""
    joined = {m: {n: _Scoped(c, scope_tables.get(m, {}).get(n, "")) for n, c in t.items()}
              for m, t in class_tables.items()}
    devices = _aligned(pd, tr._device_ops(pd, joined))
    nd = max(len(devices), 1) * max(steps, 1)
    ns: dict[str, float] = defaultdict(float)
    count: dict[str, float] = defaultdict(float)
    stages: dict[tuple[str, str, str], float] = defaultdict(float)
    unattributed: dict[str, float] = defaultdict(float)
    busy = total = 0.0
    scoped = 0
    for ops in devices.values():
        busy += tr.length((o.start, o.end) for o in ops)
        for o in ops:
            d = o.end - o.start
            direction, stage, bucket = parse(getattr(o.cls, "scope", ""))
            total += d
            ns[bucket] += d
            count[bucket] += 1
            if bucket in KINDS or bucket == "plan":
                scoped += 1
                stages[(direction, stage, bucket)] += d
            if bucket == "unattributed":
                unattributed[f"{o.cls}:{o.name}"] += d
    return ScopeReduction(
        ndev=len(devices),
        busy_ns=busy / nd,
        kind_ns={b: ns.get(b, 0.0) / nd for b in BUCKETS},
        kind_ops={b: count.get(b, 0.0) / nd for b in BUCKETS},
        stages={k: v / nd for k, v in sorted(stages.items())},
        overlap_ns=(total - busy) / nd,
        scoped_ops=scoped,
        top_unattributed=sorted(((k, v / nd) for k, v in unattributed.items()),
                                key=lambda kv: -kv[1])[:top],
    )


def table(sr: ScopeReduction) -> list[str]:
    """The reduction as text lines, ms per step: by (direction, stage,
    kind), then each bucket's total, then the largest unattributed ops."""
    lines = [f"{'dir':>4} {'stage':>5} {'kind':<12} {'ms/step':>10}"]
    for (direction, stage, kind), v in sr.stages.items():
        lines.append(f"{direction:>4} {stage or '*':>5} {kind:<12} {v * 1e-6:10.3f}")
    for b in BUCKETS:
        lines.append(f"{'all':>4} {'':>5} {b:<12} {sr.kind_ns[b] * 1e-6:10.3f}"
                     f"  ({sr.kind_ops[b]:.0f} ops)")
    lines.append(f"{'all':>4} {'':>5} {'busy':<12} {sr.busy_ns * 1e-6:10.3f}"
                 f"  (overlap {sr.overlap_ns * 1e-6:.3f})")
    lines += [f"unattributed {name} {v * 1e-6:.3f}" for name, v in sr.top_unattributed]
    return lines


# ---------------------------------------------------------------------------
# one cell, traced

#: the readers of a :class:`ScopeReduction`
METRICS = ("fft.xform_ms", "dealias.prune_ms", "c2r.extend_ms", "user.device_ms",
           "exchange.a2a_ms", "exchange.realign_ms")


def main(argv=None, *, root: Path, platform: str = "tpu") -> int:
    """Trace one cell's ``trace_steps`` steps after its set-up, as a
    ``--trace 1`` run of ``bench/run.py`` does, and print the table by
    scope on standard error and, last on standard output, one JSON object:
    the cell's per-layer metrics, the readers of :data:`METRICS` and the
    largest unattributed ops."""
    from bench import harness, timing
    from bench import peaks as peaks_mod

    ap = argparse.ArgumentParser(description="Device time of one cell by the program's names.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    try:
        spec = harness.find(root, args.workload)
        devices = harness.require_devices(spec.workload["chips"], platform)
    except harness.RunError as e:
        print(f"scopetrace: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(spec.root)
    import jax

    # the names are read from op_name metadata, which JAX's cache key leaves
    # out by default: keyed by it, the executable traced has this program's
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    cell, ctx = harness.build_cell(spec, devices)
    with ctx.span("inputs"):
        cell.reset(args.seed)
    with ctx.span("warmup"):
        timing.closed_loop(lambda _i: cell.dispatch(-1), steps=spec.traffic["warmup_steps"])
    w, pd = harness.traced_window(cell, spec.traffic["trace_steps"])
    classes = harness.hlo_tables(cell.executables)
    red = tr.reduce(pd, classes)
    sr = reduce(pd, classes, tables(e.as_text() for e in cell.executables.values()),
                steps=w.steps)
    del pd
    for line in table(sr):
        print(f"scopes {line}", file=sys.stderr, flush=True)
    peaks = peaks_mod.peaks_for(devices[0].device_kind) if platform == "tpu" else None
    metrics = {k: v["value"] for k, v in harness.layer_metrics(spec, harness.Readings(
        red, w.steps, dict(ctx.spans_s), cell.work, spec.workload["chips"], peaks)).items()}
    scoped = types.SimpleNamespace(scopes=sr)
    for name in METRICS:
        v = harness.load_module(spec.dir / "layer_metrics" / f"{name}.py").read(scoped)
        if v is not None:
            metrics[name] = float(v)
    out = {"metrics": metrics,
           "busy_ms": sr.busy_ns * 1e-6, "unattributed_ms": sr.unattributed_ns * 1e-6,
           "top_unattributed": [[n, v * 1e-6] for n, v in sr.top_unattributed],
           "set_up_s": dict(ctx.spans_s)}
    print(json.dumps(out), flush=True)
    return 0
