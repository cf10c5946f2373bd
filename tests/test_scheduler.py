"""The continuous-batching scheduler (serving/scheduler.py) under a
virtual clock: every policy decision — close vs wait, admit vs shed, miss
vs meet — is deterministic because the policy core consults only the
injected clock.  The suite pins the batch-close invariants (a batch never
exceeds `batch_queries`; an admitted request's wait never exceeds its SLO
budget when the driver polls at `next_close_time`; an empty queue never
dispatches), bit-parity of scheduled results vs direct `search_batch` on
randomized ragged arrival traces, the backpressure/shed contract, and the
Reservoir percentile machinery behind the serving stats."""
import numpy as np
import pytest

from repro.core.system import Reservoir
from repro.serving import BatchScheduler, VirtualClock, WallClock

from conftest import DIM
from test_serving import _sys_cfg, _three_tier_system

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _sched_system(points, *, slo_ms=50.0, batch_queries=8, capacity=1024,
                  est_ms=5.0, **kw):
    clk = VirtualClock()
    sys_ = _three_tier_system(
        points, batch_queries=batch_queries, slo_ms=slo_ms,
        serve_queue_capacity=capacity, dispatch_estimate_ms=est_ms,
        clock=clk, **kw)
    return sys_, clk


def _advance(clk, sched, dt):
    """Advance the virtual clock by ``dt``, stopping at every intermediate
    batch-close time to run the scheduler — the deterministic equivalent of
    the wall-clock worker waking at ``next_close_time``."""
    target = clk.now() + dt
    while True:
        nct = sched.next_close_time()
        if nct is None or nct > target:
            break
        if nct > clk.now():
            clk.advance(nct - clk.now())
        if sched.run_once() == 0:
            break
    if target > clk.now():
        clk.advance(target - clk.now())


def _pump(sched):
    while sched.run_once():
        pass


# ------------------------------------------------------ close invariants

def test_full_batch_closes_immediately(points, queries):
    """Fill-to-width close: a full queue closes NOW, never overfills, and
    the partial remainder waits for its deadline."""
    sys_, clk = _sched_system(points)
    sizes = []
    ref = sys_.search_batch

    def serve(qs, k, L=None, beam_width=None):
        sizes.append(len(qs))
        return ref(qs, k, L=L, beam_width=beam_width)

    sched = BatchScheduler(sys_, k=5, serve=serve)
    assert sched.clock is clk        # injected via SystemConfig.clock
    for q in queries[:19]:
        sched.submit(q)
        _pump(sched)
    assert sizes == [8, 8]           # two full closes, 3 still queued
    assert sched.pending == 3
    assert sched.next_close_time() == pytest.approx(
        clk.now() + 0.050 - sched.dispatch_estimate)
    _advance(clk, sched, 1.0)
    assert sizes == [8, 8, 3]        # deadline close drained the tail
    assert max(sizes) <= sys_.cfg.batch_queries
    assert sys_.stats.deadline_misses == 0


def test_deadline_close_bounds_wait(points, queries):
    """SLO-budget invariant: driving the scheduler at `next_close_time`,
    no admitted request waits past its deadline (dispatch is instant on the
    virtual clock, and the close fires `dispatch_estimate` early)."""
    sys_, clk = _sched_system(points, slo_ms=20.0)
    sched = BatchScheduler(sys_, k=5)
    tickets = []
    for i, q in enumerate(queries[:7]):     # never fills the width of 8
        tickets.append(sched.submit(q))
        _advance(clk, sched, 0.003)
    _advance(clk, sched, 0.050)
    for t in tickets:
        assert t.done.is_set()
        assert t.latency <= 0.020 + 1e-12
        assert not t.missed
    assert sys_.stats.deadline_misses == 0
    # Batches closed on deadlines, not on fill: more than one dispatch.
    assert sys_.stats.batches_dispatched >= 2


def test_empty_queue_never_dispatches(points):
    """An empty queue has no close time and `run_once` is a no-op at any
    clock value — a deadline close never fires on nothing."""
    sys_, clk = _sched_system(points)
    sched = BatchScheduler(sys_, k=5)
    assert sched.next_close_time() is None
    assert sched.run_once() == 0
    clk.advance(10.0)
    assert sched.run_once() == 0
    assert sys_.stats.batches_dispatched == 0
    assert sched.flush() == 0


def test_no_slo_closes_only_on_fill(points, queries):
    """slo_ms=0 disables deadline closes: a partial batch sits until the
    queue fills or `flush` drains it."""
    sys_, clk = _sched_system(points, slo_ms=0.0)
    sched = BatchScheduler(sys_, k=5)
    for q in queries[:5]:
        sched.submit(q)
    assert sched.next_close_time() is None
    clk.advance(1e6)
    assert sched.run_once() == 0 and sched.pending == 5
    assert sched.flush() == 5
    assert sys_.stats.deadline_misses == 0   # no SLO -> nothing to miss


def test_deadline_miss_is_counted(points, queries):
    """A request completing past its deadline (the driver polled late) is
    served anyway and counted in `deadline_misses` with `missed` set."""
    sys_, clk = _sched_system(points, slo_ms=10.0)
    sched = BatchScheduler(sys_, k=5)
    t = sched.submit(queries[0])
    clk.advance(0.100)               # blow straight past the deadline
    assert sched.run_once() == 1
    assert t.missed and t.done.is_set()
    assert sys_.stats.deadline_misses == 1


def test_dispatch_estimate_ewma_is_deterministic(points, queries):
    """Under a virtual clock a dispatch measures 0 s, so the EWMA estimate
    decays as 0.8^n of its seed — the close-time policy is a pure function
    of the trace."""
    sys_, clk = _sched_system(points, est_ms=10.0)
    sched = BatchScheduler(sys_, k=5)
    assert sched.dispatch_estimate == pytest.approx(0.010)
    for q in queries[:16]:
        sched.submit(q)
    _pump(sched)                     # two full-width dispatches
    assert sched.dispatch_estimate == pytest.approx(0.010 * 0.8 ** 2)


def test_virtual_clock_only_advances():
    clk = VirtualClock(5.0)
    assert clk.now() == 5.0
    assert clk.advance(1.5) == 6.5
    with pytest.raises(ValueError):
        clk.advance(-0.1)
    assert isinstance(WallClock().now(), float)


# ------------------------------------------------------------- bit-parity

def test_scheduled_results_match_direct_search(points, queries, rng):
    """The de-interleave contract on randomized ragged traces: every
    scheduled request's (ids, dists) row is bit-identical to calling
    `search_batch` directly, whatever batches the arrivals landed in."""
    sys_, clk = _sched_system(points, batch_queries=4)
    for e in (0, 5, 2000, 2149):     # deletes in every tier, as in the
        sys_.delete(e)               # serving parity suite
    ref_ids, ref_d = sys_.search_batch(queries, k=5)
    sched = BatchScheduler(sys_, k=5)
    tickets, qi = [], 0
    while qi < len(queries):
        group = int(rng.integers(0, 4))
        for _ in range(min(group, len(queries) - qi)):
            tickets.append((qi, sched.submit(queries[qi])))
            qi += 1
            _pump(sched)
        _advance(clk, sched, float(rng.integers(0, 30)) / 1e3)
    _advance(clk, sched, 1.0)
    sched.flush()
    for i, t in tickets:
        assert t is not None and t.done.is_set()
        np.testing.assert_array_equal(t.ids, ref_ids[i])
        np.testing.assert_array_equal(t.dists, ref_d[i])


def test_worker_thread_serves_on_wall_clock(points, queries):
    """The threaded loop end-to-end (wall clock, no injected clock):
    submitted requests complete with the same rows as direct search."""
    sys_ = _three_tier_system(points, batch_queries=4, slo_ms=10.0)
    ref_ids, ref_d = sys_.search_batch(queries[:6], k=5)
    sched = BatchScheduler(sys_, k=5)
    sched.start()
    try:
        tickets = [sched.submit(q) for q in queries[:6]]
        for i, t in enumerate(tickets):
            ids, d = t.result(timeout=60.0)
            np.testing.assert_array_equal(ids, ref_ids[i])
            np.testing.assert_array_equal(d, ref_d[i])
    finally:
        sched.stop()


def test_worker_thread_failure_surfaces_in_stop():
    """A dispatch that raises on the worker thread fails its tickets'
    result(), fails every ticket still queued behind it, makes later
    submits raise, and is re-raised by stop() — the loop does not die
    silently and leave callers waiting or the owner with success."""
    import threading
    from types import SimpleNamespace
    from repro.core.config import IndexConfig, PQConfig, SystemConfig
    from repro.core.system import SystemStats

    cfg = SystemConfig(index=IndexConfig(capacity=64, dim=DIM),
                       pq=PQConfig(dim=DIM, m=8), batch_queries=2)
    fake = SimpleNamespace(cfg=cfg, stats=SystemStats())
    entered, release = threading.Event(), threading.Event()

    def serve(qs, k, **kw):
        entered.set()
        release.wait(60.0)
        raise RuntimeError("search program failed")

    sched = BatchScheduler(fake, k=1, serve=serve)
    sched.start()
    tickets = [sched.submit(np.zeros(DIM, np.float32)) for _ in range(2)]
    assert entered.wait(60.0)           # the first batch is in dispatch
    queued = sched.submit(np.zeros(DIM, np.float32))
    release.set()
    for t in tickets + [queued]:
        with pytest.raises(RuntimeError, match="search program failed"):
            t.result(timeout=60.0)
    with pytest.raises(RuntimeError, match="worker failed") as info:
        sched.submit(np.zeros(DIM, np.float32))
    assert "search program failed" in str(info.value.__cause__)
    assert sched.pending == 0
    with pytest.raises(RuntimeError, match="search program failed"):
        sched.stop()


# ----------------------------------------------------------- backpressure

def test_backpressure_sheds_beyond_capacity(points, queries):
    """The bounded-queue contract: submissions past capacity return None
    and count in `shed_requests`; nothing else is dropped, and capacity
    frees as batches dispatch."""
    sys_, clk = _sched_system(points, capacity=6, slo_ms=0.0)
    sched = BatchScheduler(sys_, k=5)
    outs = [sched.submit(q) for q in queries[:10]]
    assert [t is None for t in outs] == [False] * 6 + [True] * 4
    assert sys_.stats.shed_requests == 4
    assert sys_.stats.scheduled_requests == 6
    assert sys_.stats.queue_depth == 6
    assert sched.flush() == 6        # shed requests are NOT in the queue
    assert sys_.stats.queue_depth == 0
    assert sched.submit(queries[0]) is not None   # capacity freed
    for t in outs[:6]:
        assert t.done.is_set()       # admitted requests were all served


# ------------------------------------------- filtered / multi-tenant

def _labeled_sched_system(points, **kw):
    """Labeled three-tier system behind a virtual clock: every point owns
    a tenant (id parity) and label bit 0, for filtered-ticket tests."""
    from repro.core.system import bootstrap_system
    clk = VirtualClock()
    cfg = _sys_cfg(batch_queries=4, slo_ms=50.0, serve_queue_capacity=64,
                   dispatch_estimate_ms=5.0, clock=clk, filter_words=1,
                   **kw)
    sys_ = bootstrap_system(points[:400], np.arange(400), cfg,
                            labels=[[0] for _ in range(400)],
                            tenants=[i % 2 for i in range(400)])
    for i in range(60):
        sys_.insert(2000 + i, points[500 + i], labels=[0], tenant=i % 2)
    return sys_, clk


def test_mixed_filter_batches_deinterleave(points, queries):
    """Tickets with different FilterSpecs never share a micro-batch: the
    scheduler groups on the OLDEST ticket's spec, preserving per-spec FIFO,
    and every served row is bit-identical to a direct filtered
    ``search_batch`` on that ticket's own query."""
    from repro.core.graph import FilterSpec
    sys_, clk = _labeled_sched_system(points)
    served = []
    ref = sys_.search_batch

    def serve(qs, k, L=None, beam_width=None, **kw):
        served.append((len(qs), kw.get("filter")))
        return ref(qs, k, L=L, beam_width=beam_width, **kw)

    sched = BatchScheduler(sys_, k=5, serve=serve)
    spec0, spec1 = FilterSpec(tenant=0), FilterSpec(tenant=1)
    plan = [spec0, spec1, spec0, None, spec1, spec0, None, spec1]
    tickets = [(sched.submit(queries[i], filter=s), s)
               for i, s in enumerate(plan)]
    _advance(clk, sched, 1.0)
    assert sched.flush() >= 0 and sched.pending == 0
    # every batch was single-spec, and per-spec arrival order was kept
    specs_served = [s for _, s in served]
    assert all(n <= 4 for n, _ in served)
    assert sorted(specs_served, key=str) == sorted(
        [spec0, spec1, None], key=str)       # one batch per distinct spec
    for (t, s), q in zip(tickets, queries):
        assert t is not None and t.done.is_set()
        kw = {"filter": s} if s is not None else {}
        ids, d = ref(q[None, :], 5, **kw)
        np.testing.assert_array_equal(t.ids, np.asarray(ids)[0])
        np.testing.assert_array_equal(t.dists, np.asarray(d)[0])
        if s is not None:                    # zero cross-tenant rows
            for e in (int(x) for x in t.ids if x >= 0):
                owner = (e % 2) if e < 2000 else ((e - 2000) % 2)
                assert owner == s.tenant


def test_tenant_quota_sheds_counted(points, queries):
    """``cfg.tenant_quota`` bounds one tenant's queued tickets: the excess
    is shed (None) and counted per tenant in ``tenant_sheds`` as well as
    ``shed_requests``; other tenants are untouched, and the quota frees as
    the tenant's batches dispatch."""
    from repro.core.graph import FilterSpec
    sys_, clk = _labeled_sched_system(points, tenant_quota=2)
    sched = BatchScheduler(sys_, k=5)
    spec0, spec1 = FilterSpec(tenant=0), FilterSpec(tenant=1)
    outs0 = [sched.submit(queries[i], filter=spec0) for i in range(4)]
    assert [t is None for t in outs0] == [False, False, True, True]
    assert sys_.stats.tenant_sheds == {0: 2}
    assert sys_.stats.shed_requests == 2
    # another tenant has its own quota — unaffected by tenant 0's sheds
    outs1 = [sched.submit(queries[4 + i], filter=spec1) for i in range(2)]
    assert all(t is not None for t in outs1)
    assert sys_.stats.tenant_sheds == {0: 2}
    # unfiltered traffic is never quota-shed
    assert sched.submit(queries[6]) is not None
    sched.flush()                            # drains tenant 0's tickets
    assert sched.submit(queries[7], filter=spec0) is not None
    assert sys_.stats.tenant_sheds == {0: 2}     # no new sheds


# ------------------------------------------------- hypothesis property

if HAVE_HYPOTHESIS:

    @given(st.lists(st.tuples(st.integers(0, 30), st.integers(0, 3)),
                    min_size=1, max_size=8),
           st.integers(5, 40))
    @settings(max_examples=10, deadline=None)
    def test_property_random_traces_hold_invariants(points, queries,
                                                    trace, slo_ms):
        """Random (inter-arrival ms, burst size) interleavings with random
        SLOs: batches never overfill, no admitted wait exceeds the budget,
        every request is served exactly once with rows bit-identical to
        direct search, and the accounting adds up."""
        sys_, clk = _sched_system(points, batch_queries=4,
                                  slo_ms=float(slo_ms))
        ref_ids, ref_d = sys_.search_batch(queries, k=5)
        sizes = []
        ref = sys_.search_batch

        def serve(qs, k, L=None, beam_width=None):
            sizes.append(len(qs))
            return ref(qs, k, L=L, beam_width=beam_width)

        sched = BatchScheduler(sys_, k=5, serve=serve)
        tickets, qi = [], 0
        for gap_ms, burst in trace:
            _advance(clk, sched, gap_ms / 1e3)
            for _ in range(burst):
                if qi >= len(queries):
                    break
                tickets.append((qi, sched.submit(queries[qi])))
                qi += 1
                _pump(sched)
        _advance(clk, sched, slo_ms / 1e3 + 1.0)
        assert sched.pending == 0    # every deadline has passed
        assert sizes and max(sizes) <= 4
        assert sum(sizes) == len(tickets)
        assert sys_.stats.deadline_misses == 0
        for i, t in tickets:
            assert t.done.is_set()
            assert t.latency <= slo_ms / 1e3 + 1e-12
            np.testing.assert_array_equal(t.ids, ref_ids[i])
            np.testing.assert_array_equal(t.dists, ref_d[i])


# ----------------------------------------------------- reservoir contract

def test_reservoir_exact_percentiles_when_unsaturated():
    """While seen <= size the reservoir holds the whole stream, so the
    percentile snapshot is exact: p50/p99 of 0..100 are 50 and 99."""
    r = Reservoir(size=1024)
    for x in np.random.default_rng(0).permutation(101):
        r.record(float(x))
    assert r.percentile(50.0) == 50.0
    assert r.percentile(99.0) == 99.0
    snap = r.snapshot()
    assert snap == {"p50": 50.0, "p99": 99.0, "n": 101}


def test_reservoir_empty_is_nan():
    r = Reservoir(size=8)
    assert np.isnan(r.percentile(50.0))
    assert r.snapshot()["n"] == 0


def test_reservoir_uniformity_smoke():
    """Vitter's R keeps each stream element with probability size/seen: the
    retained sample of the stream 0..9999 should look uniform — its mean
    within a few sigma of the stream mean, occupancy exactly `size`."""
    r = Reservoir(size=64, seed=3)
    n = 10_000
    for x in range(n):
        r.record(float(x))
    assert len(r.sample) == 64 and r.seen == n
    mean, mid = np.mean(r.sample), (n - 1) / 2
    sigma = (n / np.sqrt(12)) / np.sqrt(64)
    assert abs(mean - mid) < 4 * sigma
    # and the early prefix was not pinned: some late elements made it in.
    assert max(r.sample) > n * 0.8 and min(r.sample) < n * 0.2


def test_search_latency_sampled_per_dispatched_microbatch(points, queries):
    """The bench contract: every dispatched micro-batch is one sample in
    `stats.search_latency` (10 queries at width 4 -> 3 samples), and a
    no-op empty request adds none."""
    sys_ = _three_tier_system(points, batch_queries=4)
    assert sys_.stats.search_latency.seen == 0
    sys_.search_batch(queries[:10], k=5)
    assert sys_.stats.search_latency.seen == 3
    sys_.search_batch(np.zeros((0, DIM), np.float32), k=5)
    assert sys_.stats.search_latency.seen == 3
    snap = sys_.stats.serving_snapshot()
    assert snap["search"]["n"] == 3
    assert snap["search"]["p50"] > 0.0
