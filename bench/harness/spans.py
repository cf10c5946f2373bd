"""Arithmetic over the program's own spans.

The program opens ``jax.profiler.TraceAnnotation`` spans named
``fdann.<layer>.<step>`` at its layer boundaries (docs/SERVING.md,
"Tracing"); they land in the trace's host plane on the profiler's clock,
beside the device's operations, with their metadata as the event's stats.
Each line of a host plane is one thread, named by the OS: the program names
its merge thread ``fdann-merge``.  A trace of a program without these spans
reads as nothing here: every function returns an empty list or None and
never raises.
"""
from __future__ import annotations

import statistics
import sys
from typing import Optional

from . import trace as tr

PREFIX = "fdann."
LAUNCH = "PjitFunction("
MERGE = "fdann.merge"
MERGE_HOST_PHASES = ("fdann.merge.stage", "fdann.merge.publish",
                     "fdann.merge.retire")


def log(msg: str) -> None:
    print(f"[bench] spans: {msg}", file=sys.stderr, flush=True)


def host_events(events: list) -> list:
    return [e for e in events if not tr.is_device(e.plane)]


def named(events: list, name: str) -> list:
    """Host events called ``name``, in order of start."""
    return sorted((e for e in host_events(events) if e.name == name),
                  key=lambda e: e.start_ns)


def program_spans(events: list) -> list:
    """Every ``fdann.*`` span, in order of start."""
    return sorted((e for e in host_events(events)
                   if e.name.startswith(PREFIX)), key=lambda e: e.start_ns)


def inside(inner, outer) -> bool:
    return (outer.start_ns <= inner.start_ns and inner.start_ns
            + inner.dur_ns <= outer.start_ns + outer.dur_ns)


def merged(intervals) -> list:
    """Union of [start, end) intervals, as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap_ns(a: list, b: list) -> int:
    """Length of the intersection of two unions from ``merged``."""
    total, j = 0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def mean_ms(spans: list) -> Optional[float]:
    return (sum(e.dur_ns for e in spans) / len(spans) / 1e6
            if spans else None)


def device_waits_ns(events: list) -> Optional[list]:
    """For each search micro-batch, the time from the start of its
    ``fdann.search.device`` span to the start of its unified search program
    on the first device.  One thread serves the micro-batches, one program
    each, so the k-th span launched the k-th program; where the counts
    differ the join is unsound and the answer is None."""
    spans = named(events, "fdann.search.device")
    planes = tr.device_planes(events)
    if not spans or not planes:
        return None
    runs = sorted((m for m in tr.module_events(events)
                   if m.plane == planes[0]
                   and tr.program_name(m.name) == "unified_search"),
                  key=lambda m: m.start_ns)
    if len(runs) != len(spans):
        log(f"{len(spans)} fdann.search.device spans but {len(runs)} "
            f"unified_search programs on {planes[0]}: no join")
        return None
    return [r.start_ns - s.start_ns for s, r in zip(spans, runs)]


def launched(name: str) -> Optional[str]:
    """``PjitFunction(decode)`` -> ``decode``; None for other events."""
    if name.startswith(LAUNCH) and name.endswith(")"):
        return name[len(LAUNCH):-1]
    return None


def merge_programs(events: list) -> Optional[set]:
    """Names of the programs the merge launched: those whose every launch
    event lies inside an ``fdann.merge`` span on that span's own thread.
    A name launched both there and elsewhere belongs to neither, and is
    logged."""
    merges = named(events, MERGE)
    if not merges:
        return None
    where: dict = {}
    for e in host_events(events):
        name = launched(e.name)
        if name is None:
            continue
        mine = any(m.line == e.line and inside(e, m) for m in merges)
        where.setdefault(name, set()).add(mine)
    both = sorted(n for n, w in where.items() if w == {True, False})
    if both:
        log(f"launched by the merge and by another layer: {both}")
    return {n for n, w in where.items() if w == {True}}


def merge_program_ms(events: list) -> Optional[list]:
    """Device time of each execution of a program the merge launched."""
    names = merge_programs(events)
    if names is None:
        return None
    return [m.dur_ns / 1e6 for m in tr.module_events(events)
            if tr.program_name(m.name) in names]


def span_stat(spans: list, key: str) -> list:
    return [e.stats[key] for e in spans if key in e.stats]


def median_stat(events: list, name: str, key: str) -> Optional[float]:
    values = span_stat(named(events, name), key)
    return float(statistics.median(values)) if values else None


def idle_unspanned_ns(events: list) -> Optional[tuple]:
    """(device idle, device idle with no ``fdann.*`` span open on any host
    thread), in ns, over the trace: from its first event's start to its
    last event's end, on the first device."""
    planes = tr.device_planes(events)
    spans = program_spans(events)
    if not planes or not spans:
        return None
    lo = min(e.start_ns for e in events)
    hi = max(e.start_ns + e.dur_ns for e in events)
    busy = tr.busy_intervals(events, planes[0])
    idle, t = [], lo
    for s, e in busy:
        if s > t:
            idle.append([t, s])
        t = max(t, e)
    if hi > t:
        idle.append([t, hi])
    covered = merged((e.start_ns, e.start_ns + e.dur_ns) for e in spans)
    total = sum(e - s for s, e in idle)
    return total, total - overlap_ns(idle, covered)
