"""The program's own profiler spans (docs/SERVING.md, "Tracing"): a tiny
system traced through a scheduled search, inserts that flush, and one
foreground merge carries every ``fdann.*`` span with its metadata, on the
same clock as the XLA operations it launches; and tracing changes no
result."""
import glob
import os
import warnings

import jax
import numpy as np
import pytest

from repro.core import index as mem
from repro.serving import BatchScheduler, ReplicaSet, VirtualClock

from test_serving import _three_tier_system

BATCH_SPANS = ("fdann.search.flush", "fdann.search.prepare",
               "fdann.search.device")
MERGE_PHASES = tuple(f"fdann.merge.{p}" for p in
                     ("stage", "launch", "wait", "publish", "retire",
                      "probe"))
METADATA = {
    "fdann.serve.batch": {"batch", "n", "oldest_wait_ms"},
    "fdann.search.flush": {"batch", "points"},
    "fdann.search.prepare": {"batch", "lanes"},
    "fdann.search.device": {"batch", "max_hops", "p50_hops"},
    "fdann.flush": {"points", "chunks"},
    "fdann.merge": {"merge", "staged", "deletes", "repair_mode", "slices"},
    **{p: {"merge"} for p in MERGE_PHASES},
}


def _start(trace_dir):
    """A trace without the Python function tracer, which would slow the
    traced calls many times over and adds nothing the spans need."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _events(trace_dir):
    """(line, name, start_ns, end_ns, stats) of every event recorded."""
    from jax.profiler import ProfileData
    f, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                   recursive=True)
    out = []
    with warnings.catch_warnings():     # jaxlib's stats type warns on read
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(f).planes:
            for line in plane.lines:
                for ev in line.events:
                    out.append((line.name, ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                dict(ev.stats)))
    return out


def _inside(inner, outer):
    return outer[2] <= inner[2] and inner[3] <= outer[3]


@pytest.fixture(scope="module")
def traced(points, queries, tmp_path_factory):
    """(system, events) of a scheduled search, flushing inserts and a
    foreground merge, under one trace."""
    sys_ = _three_tier_system(points, batch_queries=8, slo_ms=50.0,
                              clock=VirtualClock())
    sched = BatchScheduler(sys_, k=5)
    for e in (3, 7, 2001):
        sys_.delete(e)
    for i in range(5):                     # buffered: the search flushes
        sys_.insert(3000 + i, points[700 + i])
    sys_.search_batch(queries[:8], k=5)    # compiles outside the trace
    for i in range(5, 10):
        sys_.insert(3000 + i, points[700 + i])
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    _start(trace_dir)
    try:
        for q in queries[:6]:
            sched.submit(q)
        sched.flush()
        for i in range(10, 10 + sys_.cfg.insert_batch):   # a full buffer
            sys_.insert(3000 + i, points[700 + i])
        sys_.merge()
    finally:
        jax.profiler.stop_trace()
    return sys_, _events(trace_dir)


def test_every_span_appears_with_its_metadata(traced):
    sys_, events = traced
    seen = {}
    for ev in events:
        if ev[1] in METADATA:
            seen.setdefault(ev[1], []).append(ev)
    assert set(seen) == set(METADATA)
    for name, keys in METADATA.items():
        for ev in seen[name]:
            assert keys <= set(ev[4]), (name, ev[4])
    batch, = seen["fdann.serve.batch"]
    assert batch[4]["n"] == 6
    flush, = seen["fdann.search.flush"]
    assert flush[4]["points"] == 5               # the buffered inserts
    assert any(ev[4]["points"] == 32 and ev[4]["chunks"] == 1
               for ev in seen["fdann.flush"])
    device, = seen["fdann.search.device"]
    assert 1 <= device[4]["p50_hops"] <= device[4]["max_hops"]
    merge, = seen["fdann.merge"]
    assert merge[4]["deletes"] == 3 and merge[4]["staged"] > 0
    assert merge[4]["repair_mode"] in ("local", "global")
    assert sys_.stats.merges == 1
    assert merge[4]["slices"] == sys_.stats.merge_slices
    assert (merge[4]["slices"] > 1) == (merge[4]["repair_mode"] == "local")
    for name in MERGE_PHASES:
        phase, = seen[name]
        assert _inside(phase, merge)
        assert phase[4]["merge"] == merge[4]["merge"]
    order = [seen[p][0][2] for p in MERGE_PHASES]
    assert order == sorted(order)


def _check_batch_nesting(events):
    batch, = [ev for ev in events if ev[1] == "fdann.serve.batch"]
    children = [ev for ev in events if ev[1] in BATCH_SPANS]
    assert sorted(ev[1] for ev in children) == sorted(BATCH_SPANS)
    for ev in children:
        assert _inside(ev, batch) and ev[0] == batch[0]
        assert ev[4]["batch"] == batch[4]["batch"]
    return batch


def test_search_spans_nest_in_their_batch(traced):
    _check_batch_nesting(traced[1])


def test_replica_dispatch_has_the_system_spans(traced, queries, tmp_path):
    """A micro-batch routed through ``ReplicaSet`` carries the same spans,
    and samples one ``search_latency`` covering its prepare and device
    steps, as the system's own dispatch does."""
    sys_ = traced[0]
    rs = ReplicaSet(sys_, 1)
    sched = BatchScheduler(sys_, k=5, serve=rs.search_batch)
    for q in queries[:8]:              # compiles the replica's program
        sched.submit(q)
    sched.flush()
    seen = sys_.stats.search_latency.seen
    _start(str(tmp_path))
    try:
        for q in queries[:6]:
            sched.submit(q)
        sched.flush()
    finally:
        jax.profiler.stop_trace()
    assert rs.dispatches == [2]
    assert sys_.stats.search_latency.seen == seen + 1
    events = _events(str(tmp_path))
    _check_batch_nesting(events)
    device, = [ev for ev in events if ev[1] == "fdann.search.device"]
    prepare, = [ev for ev in events if ev[1] == "fdann.search.prepare"]
    sample = sys_.stats.search_latency.sample[seen]
    assert sample * 1e9 >= (device[3] - prepare[2]) * 0.99
    assert "max_hops" in device[4]


def test_the_search_program_runs_inside_its_device_span(traced):
    """The span and the XLA operations share one clock: the unified search
    program's operations lie inside the span that launched it."""
    _, events = traced
    device, = [ev for ev in events if ev[1] == "fdann.search.device"]
    ops = [ev for ev in events
           if ev[4].get("hlo_module", "").startswith("jit_unified_search")]
    assert ops
    assert all(_inside(op, device) for op in ops)
    launch = [ev for ev in events if ev[1] == "PjitFunction(unified_search)"]
    assert launch and all(_inside(ev, device) and ev[0] == device[0]
                          for ev in launch)


def test_tracing_changes_no_result(traced, queries, tmp_path):
    sys_ = traced[0]
    ids, d = sys_.search_batch(queries[:8], k=5)
    _start(str(tmp_path))
    try:
        t_ids, t_d = sys_.search_batch(queries[:8], k=5)
    finally:
        jax.profiler.stop_trace()
    assert any(ev[1] == "fdann.search.device" and "max_hops" in ev[4]
               for ev in _events(str(tmp_path)))
    np.testing.assert_array_equal(ids, t_ids)
    np.testing.assert_array_equal(d, t_d)


class _Unread:
    """A device output that fails if anything reads it."""

    def __array__(self, *a, **kw):
        raise AssertionError("hops read with no profiler session active")

    def __getitem__(self, i):
        raise AssertionError("hops read with no profiler session active")


def test_hops_are_not_fetched_without_a_session(traced, queries,
                                                monkeypatch):
    sys_ = traced[0]
    real = mem.unified_search

    def no_hops(*a, **kw):
        ids, d, _, _ = real(*a, **kw)
        return ids, d, _Unread(), _Unread()

    monkeypatch.setattr(mem, "unified_search", no_hops)
    ids, d = sys_.search_batch(queries[:8], k=5)
    monkeypatch.setattr(mem, "unified_search", real)
    ref_ids, ref_d = sys_.search_batch(queries[:8], k=5)
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_array_equal(d, ref_d)
