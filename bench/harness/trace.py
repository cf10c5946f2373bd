"""Reduction of a profiler trace to the numbers the per-layer readers use.

The JAX profiler writes an XSpace (``*.xplane.pb``); ``load_events`` turns
it into plain ``Event`` tuples so that every function below is plain
arithmetic over a list, testable on a recorded or a made-up trace.

Device planes are named ``/device:<KIND>:<n>``; on a TPU their ``XLA Ops``
line holds one event per executed operation (a Pallas kernel is one
custom-call operation) and their ``XLA Modules`` line one per executed
program.  Host planes hold the threads' spans, the harness's own
``bench.*`` annotations among them.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import NamedTuple, Optional

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int
    stats: dict


def load_events(trace_dir: str) -> list:
    """Every event of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                try:
                    stats = dict(ev.stats)
                except (TypeError, ValueError):
                    stats = {}
                out.append(Event(plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns),
                                 stats))
    return out


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def device_planes(events: list) -> list:
    return sorted({e.plane for e in events if is_device(e.plane)})


def op_events(events: list, plane: Optional[str] = None) -> list:
    return [e for e in events if is_device(e.plane) and e.line == OPS_LINE
            and (plane is None or e.plane == plane)]


def module_events(events: list) -> list:
    return [e for e in events if is_device(e.plane)
            and e.line == MODULES_LINE]


def busy_intervals(events: list, plane: str) -> list:
    """Merged [start, end) intervals in which an operation ran on a plane."""
    merged = []
    for s, e in sorted((ev.start_ns, ev.start_ns + ev.dur_ns)
                       for ev in op_events(events, plane)):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_seconds(events: list) -> float:
    """Device busy time, averaged over the device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    return sum(e - s for p in planes for s, e in busy_intervals(events, p)
               ) / len(planes) / 1e9


def long_name(ev: Event) -> str:
    """The operation's HLO text: a TPU trace names each operation by it;
    other backends may keep it in a stat."""
    for key in ("long_name", "hlo_op"):
        v = ev.stats.get(key)
        if isinstance(v, str) and v:
            return v
    return ev.name


_INSTR = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s*=|$)")
_CONTAINERS = {"while", "conditional", "call"}


def op_name(ev: Event) -> str:
    """The instruction's name without its ``%`` and numeric suffix, e.g.
    ``adc_distances_kernel`` for ``%adc_distances_kernel.6 = f32[..] ...``;
    a Pallas kernel's instruction is named after its jitted wrapper."""
    m = _INSTR.match(ev.name)
    return m.group(1) if m else ev.name.split(" ")[0]


def program_name(name: str) -> str:
    """``jit_unified_search(6283...)`` -> ``unified_search``."""
    name = name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def matching(events: list, names) -> list:
    """Device operations whose instruction is one of ``names``."""
    names = set(names)
    return [e for e in op_events(events) if op_name(e) in names]


_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64)"
                    r"\[([0-9,]*)\]")


def operand_shapes(text: str) -> list:
    """[(dtype, dims)] of the shapes written in an HLO instruction, in
    order: the result's first (each of a tuple's), then the operands'."""
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in _SHAPE.finditer(text)]


def programs_of(ops: list, modules: list) -> list:
    """Program name enclosing each op (``""`` where none does)."""
    spans = {}
    for m in modules:
        spans.setdefault(m.plane, []).append(
            (m.start_ns, m.start_ns + m.dur_ns, program_name(m.name)))
    for v in spans.values():
        v.sort()
    starts = {p: [s for s, _, _ in v] for p, v in spans.items()}
    out = []
    for e in ops:
        v = spans.get(e.plane, [])
        i = bisect.bisect_right(starts.get(e.plane, []), e.start_ns) - 1
        out.append(v[i][2] if i >= 0 and e.start_ns < v[i][1] else "")
    return out


def top_ops(events: list, n: int = 10) -> list:
    """[(program/operation, seconds)] of the device operations that took
    most time; loops and calls, which hold other operations, are left
    out."""
    ops = [e for e in op_events(events) if op_name(e) not in _CONTAINERS]
    tot: dict = {}
    for e, prog in zip(ops, programs_of(ops, module_events(events))):
        key = f"{prog}/{op_name(e)}"
        tot[key] = tot.get(key, 0) + e.dur_ns
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: list, n: int = 10) -> list:
    """[(label, seconds)] of the longest idle gaps of the first device,
    each labelled by what the host was doing: the shortest host span that
    covers at most of the gap, else the one that covers most of it."""
    planes = device_planes(events)
    if not planes:
        return []
    busy = busy_intervals(events, planes[0])
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])),
                  reverse=True)[:n]
    host = [e for e in events if not is_device(e.plane) and e.dur_ns > 0]
    out = []
    for length, s, t in gaps:
        cover = [(min(t, h.start_ns + h.dur_ns) - max(s, h.start_ns), h)
                 for h in host if h.start_ns < t and h.start_ns + h.dur_ns > s]
        most = [h for ov, h in cover if 2 * ov >= length]
        if most:
            label = min(most, key=lambda h: h.dur_ns).name
        elif cover:
            label = max(cover, key=lambda c: c[0])[1].name
        else:
            label = "idle"
        out.append([label[:80], length / 1e9])
    return out
