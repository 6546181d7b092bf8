"""Reduce a profiler trace (``.xplane.pb``) to per-layer numbers.

Device ops are read from each device plane's ``XLA Ops`` line and put in
one class each, looked up by instruction name in the text of the compiled
executables the window drove.  A fusion counts as its root (through
bitcasts, reshapes and tuple elements).  An instruction is classed by:

1. its collective opcode (all-to-all, all-gather, all-reduce,
   reduce-scatter, collective-permute, and their async halves):
   ``collective``;
2. a ``custom-call`` to a TPU kernel: ``pallas``, or ``fft`` where the
   kernel's serialized body names the four-step FFT (``fourstep``);
3. the JAX operation its ``op_name`` metadata names: ``fft`` for anything
   XLA made of an FFT (the TPU compiler expands an ``fft`` instruction into
   DFT convolutions, transposes and twiddle fusions, all tagged
   ``jit(fft)``); for a ``while``/``call``, the operation (``gather`` is
   data movement);
4. its opcode: ``fft``; data movement (copy, transpose, pad, slice,
   concatenate, gather, scatter, reverse, ...) is ``layout``; anything else
   (elementwise arithmetic, reductions) is ``other``.

Ops come from the ``XLA Ops`` line, where an op that encloses others (a
loop around its body) is left out for the ops it encloses, and the
collectives of the ``Async XLA Ops`` line.  Busy time is the union of a
device's op intervals inside the traced window.  The exposed part of a
collective is the part no other op on that device overlaps.
Device numbers are averaged over the devices traced.  The benchmark's own
host spans (``bench.*`` ``TraceAnnotation``) give the window and name each
idle gap of the first device by what the host was doing in it.  The trace
names an op by its HLO text (``%fusion.12 = f32[...] fusion(...)``); the
instruction name is read from its head.  The device timeline is shifted
by the least amount that puts its first op after the first dispatch.
"""

from __future__ import annotations

import base64
import re
from bisect import bisect_right
from collections import defaultdict
from dataclasses import dataclass

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DISPATCH_SPAN = "bench.dispatch"

CLASSES = ("fft", "collective", "pallas", "layout", "other")

_COLLECTIVE = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "collective-broadcast", "send", "recv")
_LAYOUT = {"copy", "copy-start", "copy-done", "transpose", "pad", "slice",
           "dynamic-slice", "dynamic-update-slice", "concatenate", "gather",
           "scatter", "reverse", "reshape", "broadcast", "bitcast", "iota"}
_PASS_THROUGH = {"bitcast", "reshape", "get-tuple-element", "convert"}
_CONTROL = {"while", "call", "conditional"}
_FFT_OP = re.compile(r"(^|/)(jit\()?i?r?fft[n2]?\)?(/|$)")
_FFT_KERNEL = ("fourstep",)
_LAYOUT_PRIMS = {"gather", "scatter", "transpose", "concatenate", "pad", "rev",
                 "slice", "dynamic_slice", "dynamic_update_slice", "copy",
                 "broadcast_in_dim", "reshape", "squeeze", "expand_dims"}


def opcode_class(opcode: str, custom_call_target: str = "", op_name: str = "",
                 kernel: str = "") -> str:
    if any(opcode == c or opcode.startswith(c + "-") for c in _COLLECTIVE):
        return "collective"
    if opcode == "custom-call" and "tpu_custom_call" in custom_call_target:
        return "fft" if any(k in kernel.lower() for k in _FFT_KERNEL) else "pallas"
    if opcode == "fft" or _FFT_OP.search(op_name):
        return "fft"
    if opcode in _CONTROL:
        prim = op_name.rsplit("/", 1)[-1]
        return "layout" if prim in _LAYOUT_PRIMS else "other"
    if opcode in _LAYOUT:
        return "layout"
    return "other"


_INSTR = re.compile(
    r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(?:\(.*?\)|\S+)\s+([a-z][\w\-]*)\((.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_EVENT = re.compile(r"%?([\w.\-]+)\s*=")
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_PRIORITY = {c: i for i, c in enumerate(CLASSES)}


def kernel_text(body_b64: str) -> str:
    """The readable strings of a Mosaic kernel's serialized body: its
    source file and function names (``fourstep_pallas_call``, ...)."""
    try:
        raw = base64.b64decode(body_b64)
    except ValueError:
        return ""
    return " ".join(s.decode() for s in re.findall(rb"[A-Za-z_][A-Za-z0-9_.]{3,}", raw))


def hlo_op_classes(hlo_text: str) -> tuple[str, dict[str, str]]:
    """``(module name, {instruction name: class})`` of one HLO module's
    text, each fusion classed by its root."""
    module = ""
    comps: dict[str, dict] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _MODULE.match(line)
        if m:
            module = m.group(1)
            continue
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = line.split()[1] if line.startswith("ENTRY") else line.split()[0]
            cur = comps.setdefault(name.lstrip("%"), {"instrs": {}, "root": None})
            continue
        if cur is None:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        root, name, opcode, rest = m.groups()
        calls = _CALLS.search(rest)
        target = _TARGET.search(rest)
        args = rest.split(")", 1)[0]
        op_name = _OP_NAME.search(rest)
        body = _BODY.search(rest)
        cur["instrs"][name] = (opcode, _OPERAND.findall(args),
                               calls.group(1) if calls else None,
                               target.group(1) if target else "",
                               op_name.group(1) if op_name else "",
                               kernel_text(body.group(1)) if body else "")
        if root:
            cur["root"] = name

    memo: dict[tuple[str, str], str] = {}

    def cls_of(comp: str, name: str, depth: int = 0) -> str:
        key = (comp, name)
        if key in memo:
            return memo[key]
        instr = comps[comp]["instrs"].get(name)
        if instr is None or depth > 32:
            return "other"
        opcode, operands, calls, target, op_name, kernel = instr
        if opcode == "fusion" and calls in comps and comps[calls]["root"]:
            out = cls_of(calls, comps[calls]["root"], depth + 1)
        elif opcode == "tuple" and operands:
            out = min((cls_of(comp, o, depth + 1) for o in operands), key=_PRIORITY.get)
        elif opcode in _PASS_THROUGH and operands and operands[0] in comps[comp]["instrs"]:
            out = cls_of(comp, operands[0], depth + 1)
        else:
            out = opcode_class(opcode, target, op_name, kernel)
        memo[key] = out
        return out

    table = {}
    for comp, body in comps.items():
        for name in body["instrs"]:
            table[name] = cls_of(comp, name)
    return module, table


def guess_class(op_name: str) -> str:
    """Class of an op the executables' text does not name, from the opcode
    that XLA's default instruction names start with."""
    base = op_name.split(".")[0]
    return opcode_class(base)


# ---------------------------------------------------------------------------
# intervals


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def overlap(a: tuple[float, float], merged: list[tuple[float, float]]) -> float:
    """Length of interval ``a`` covered by the sorted disjoint ``merged``."""
    s0, e0 = a
    i = max(bisect_right([s for s, _ in merged], s0) - 1, 0)
    got = 0.0
    while i < len(merged) and merged[i][0] < e0:
        s, e = merged[i]
        got += max(0.0, min(e, e0) - max(s, s0))
        i += 1
    return got


# ---------------------------------------------------------------------------
# the trace


@dataclass
class Op:
    start: float  # ns
    end: float
    name: str
    cls: str


@dataclass
class Reduction:
    ndev: int
    window_ns: float
    busy_ns: float                       # mean over devices
    class_ns: dict[str, float]           # mean over devices
    exposed_collective_ns: float         # mean over devices
    top_ops: list[tuple[str, float]]     # (class:name, ns per device)
    idle_gaps: list[tuple[str, float]]   # (host span, ns), longest first


def leaves(ops: list[Op]) -> list[Op]:
    """The ops that enclose no other op of the same line."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt.start < o.end and nxt.end <= o.end)]


def _host_spans(pd):
    spans = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    return spans


def _device_ops(pd, tables: dict[str, dict[str, str]]):
    flat = {}
    for t in tables.values():
        flat.update(t)
    devices = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        mods = []
        if MODULES_LINE in lines:
            mods = sorted((ev.start_ns, ev.end_ns, ev.name.split("(")[0])
                          for ev in lines[MODULES_LINE].events)
        starts = [m[0] for m in mods]
        def classed(line):
            out = []
            for ev in line.events:
                m = _EVENT.match(ev.name)  # the trace names an op by its HLO text
                name = m.group(1) if m else ev.name
                table = flat
                i = bisect_right(starts, ev.start_ns) - 1
                if i >= 0 and ev.start_ns < mods[i][1] and mods[i][2] in tables:
                    table = tables[mods[i][2]]
                cls = table.get(name) or flat.get(name) or guess_class(name)
                out.append(Op(ev.start_ns, ev.end_ns, name, cls))
            return out

        # the ops line, without the loops that enclose their bodies, and
        # the collectives of the async line (its prefetch copies are not
        # work of their own)
        ops = leaves(classed(lines[OPS_LINE]))
        if ASYNC_LINE in lines:
            ops += [o for o in classed(lines[ASYNC_LINE]) if o.cls == "collective"]
        devices[plane.name] = ops
    return devices


def reduce(pd, tables: dict[str, dict[str, str]], *, top: int = 10) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData``.  ``tables`` maps each module
    name to its :func:`hlo_op_classes` table."""
    spans = _host_spans(pd)
    devices = _device_ops(pd, tables)
    dispatched = [s for s, _, n in spans if n == DISPATCH_SPAN]
    if dispatched:
        # the trace puts a device's clock on the host's only to within a
        # millisecond or two: no op can start before the first dispatch
        for plane, ops in devices.items():
            early = min(dispatched) - min((o.start for o in ops), default=min(dispatched))
            if early > 0:
                devices[plane] = [Op(o.start + early, o.end + early, o.name, o.cls) for o in ops]
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    all_ops = [op for ops in devices.values() for op in ops]
    if windows:
        w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    elif all_ops:
        w0, w1 = min(op.start for op in all_ops), max(op.end for op in all_ops)
    else:
        w0 = w1 = 0.0
    nd = max(len(devices), 1)
    busy = 0.0
    exposed = 0.0
    class_ns: dict[str, float] = defaultdict(float)
    by_op: dict[str, float] = defaultdict(float)
    gaps = []
    inner = [(s, e, n) for s, e, n in spans if n != WINDOW_SPAN]
    for plane in sorted(devices):
        ops = [Op(max(o.start, w0), min(o.end, w1), o.name, o.cls)
               for o in devices[plane] if o.end > w0 and o.start < w1]
        merged = union((o.start, o.end) for o in ops)
        busy += length(merged)
        others = union((o.start, o.end) for o in ops if o.cls != "collective")
        for o in ops:
            d = o.end - o.start
            class_ns[o.cls] += d
            by_op[f"{o.cls}:{o.name}"] += d
            if o.cls == "collective":
                exposed += d - overlap((o.start, o.end), others)
        if not gaps:  # the idle gaps of the first device
            edges = [w0] + [x for s, e in merged for x in (s, e)] + [w1]
            gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s] or [(w0, w0)]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:top]:
        mid = 0.5 * (s + e)
        around = [x for x in inner if x[0] <= mid <= x[1]]
        label = min(around, key=lambda x: x[1] - x[0])[2] if around else "no host span"
        named.append((label, e - s))
    return Reduction(
        ndev=len(devices),
        window_ns=w1 - w0,
        busy_ns=busy / nd,
        class_ns={c: class_ns.get(c, 0.0) / nd for c in CLASSES},
        exposed_collective_ns=exposed / nd,
        top_ops=sorted(((k, v / nd) for k, v in by_op.items()), key=lambda kv: -kv[1])[:top],
        idle_gaps=named,
    )
