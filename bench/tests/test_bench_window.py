"""Open-loop timing: every request is timed from its due time, so a stall
counts against each request it delays, whatever its submission time."""
import threading
import time
import types

import numpy as np
import pytest

import _paths  # noqa: F401
from harness import spec as specs
from harness.traffic import DELETE, INSERT, make_plan
from harness.window import Cell

CONFIG = dict(dim=4, bootstrap_points=64, merge_threshold=16,
              ro_snapshot_points=4, insert_batch=4, batch_queries=4, k=2,
              data=dict(components=4, center_scale=3.0, noise_scale=1.0,
                        spectrum_decay=2.0, cluster_points=16))
TRAFFIC = dict(insert_order="shuffled", delete_order="random",
               query_order="shuffled", warmup_rounds=0, stage_inserts=0,
               stage_deletes=0, searches_per_s=40, inserts_per_s=10,
               deletes_per_s=10)


class Res:
    size = 1024

    def __init__(self):
        self.sample, self.seen = [], 0


class FakeSystem:
    def __init__(self):
        self.stats = types.SimpleNamespace(
            searches=0, batches_dispatched=0, shed_requests=0, flushes=0,
            merges=0, merge_seconds=0.0, search_latency=Res(),
            flush_latency=Res())
        self.ops = []

    def insert(self, e, v):
        self.ops.append(("i", e))

    def delete(self, e):
        self.ops.append(("d", e))

    def wait_merge(self):
        pass


class StallingScheduler:
    """Answers nothing until ``stall`` seconds after the first submission,
    then every request at once."""

    def __init__(self, stall):
        self.stall = stall
        self.tickets = []
        self.first = None
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self._run)
        self.thread.start()

    def submit(self, q):
        t = types.SimpleNamespace(done=threading.Event(), error=None,
                                  completion=None, ids=np.zeros(2, np.int64),
                                  dists=np.zeros(2, np.float32))
        if self.first is None:
            self.first = time.monotonic()
        self.tickets.append(t)
        return t

    def _run(self):
        while not self.stop.is_set():
            now = time.monotonic()
            if self.first is not None and now >= self.first + self.stall:
                for t in list(self.tickets):
                    if not t.done.is_set():
                        t.completion = now
                        t.done.set()
            time.sleep(0.002)


def test_latency_is_counted_from_the_due_time():
    plan = make_plan(CONFIG, TRAFFIC, seed=2**31 + 7, seconds=1.0)
    assert plan.n_searches == 40
    assert sorted(set(plan.update_kinds)) == [INSERT, DELETE]
    cell = Cell(CONFIG, plan, lambda m: None)
    cell.sys = FakeSystem()
    cell.sched = sched = StallingScheduler(1.2)
    try:
        rec = cell.window()
    finally:
        sched.stop.set()
        sched.thread.join(5)
    assert not sched.thread.is_alive()
    lat = rec.search_done - rec.search_due
    assert np.all(rec.search_submit - rec.search_due > -1e-3)  # never early
    # Every answer came at the stall's end: the earliest-due waited longest.
    assert np.ptp(rec.search_done) < 0.05
    assert lat[0] > lat[-1] + 0.5
    assert len(cell.sys.ops) == len(plan.update_times)
    ctx = types.SimpleNamespace(rec=rec, drain_end=time.monotonic(),
                                config=CONFIG)
    p99 = specs.load_reader("search_p99_ms")(ctx)
    assert p99 == pytest.approx(np.percentile(lat, 99) * 1e3)
    stall = specs.load_reader("merge_stall_ms")(ctx)
    assert stall == pytest.approx((rec.search_done.max()
                                   - rec.search_submit.min()) * 1e3,
                                  rel=1e-6)
    ups = specs.load_reader("update_ops_per_s")(ctx)
    assert ups == pytest.approx(len(plan.update_times) / 1.0)


def test_plans_have_the_same_sizes_for_every_seed():
    a = make_plan(CONFIG, TRAFFIC, seed=1, seconds=2.0)
    b = make_plan(CONFIG, TRAFFIC, seed=-5, seconds=2.0)
    assert a.vectors.shape == b.vectors.shape
    assert a.n_searches == b.n_searches
    assert np.array_equal(np.sort(a.update_kinds), np.sort(b.update_kinds))
    assert not np.array_equal(a.vectors, b.vectors)
    c = make_plan(CONFIG, TRAFFIC, seed=1, seconds=2.0)
    assert np.array_equal(a.vectors, c.vectors)
    assert np.array_equal(a.search_times, c.search_times)


def test_a_geometry_seed_fixes_the_data_set_not_the_sample():
    fixed = dict(CONFIG, data=dict(CONFIG["data"], geometry_seed=3))
    a = make_plan(fixed, TRAFFIC, seed=1, seconds=1.0)
    b = make_plan(fixed, TRAFFIC, seed=2, seconds=1.0)
    from harness.data import Mixture
    assert np.array_equal(Mixture.from_config(fixed, 1).centers,
                          Mixture.from_config(fixed, 2).centers)
    assert not np.array_equal(Mixture.from_config(CONFIG, 1).centers,
                              Mixture.from_config(CONFIG, 2).centers)
    assert not np.array_equal(a.vectors, b.vectors)
