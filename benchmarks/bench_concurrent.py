"""Paper Fig. 5/6: the full-system workload — concurrent inserts, deletes
and searches with periodic background StreamingMerge; reports user-facing
latencies and recall (CPU-scale rendition of the week-long experiment).

Latency reporting is reservoir-backed (``SystemStats`` — docs/SERVING.md,
"Counters"): searches ride ``batch_queries`` micro-batches so EVERY
dispatched micro-batch is one sample in ``stats.search_latency``, and the
rows carry structured ``p50``/``p99`` fields instead of free-text notes.

``poisson_serving`` is the serving-front-end bench (ISSUE: sustained QPS
under a Poisson arrival process): an open-loop arrival process drives the
``BatchScheduler`` worker thread while an updater thread inserts/deletes
concurrently and threshold merges run in the background; rows report
sustained QPS, p50/p99 serve latency, deadline-miss rate, mean batch
occupancy and shed counts per offered-load level.  It lands in
``BENCH_serving.json`` via ``bench_throughput.serving_sweeps``.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np
import jax.numpy as jnp

from repro.core.config import IndexConfig, PQConfig, SystemConfig
from repro.core.index import brute_force, recall_at_k
from repro.core.system import Reservoir, bootstrap_system
from repro.serving import BatchScheduler

from .common import DIM, dataset, emit, queryset, timed, write_bench_json


def main(quick: bool = False):
    n = 1024 if quick else 2048
    updates = 512 if quick else 2048
    pts = dataset(n * 3)
    q = queryset(32)
    cfg = SystemConfig(
        index=IndexConfig(capacity=n * 8, dim=DIM, R=24, L_build=32,
                          L_search=48, alpha=1.2),
        pq=PQConfig(dim=DIM, m=8, ksub=32, kmeans_iters=4),
        ro_snapshot_points=n // 8, merge_threshold=n // 4,
        temp_capacity=n, insert_batch=64, batch_queries=8)
    sys_ = bootstrap_system(pts[:n], np.arange(n), cfg)
    live = dict(enumerate(pts[:n]))
    rng = np.random.default_rng(2)

    del_lat, recalls = [], []
    next_id = n
    for i in range(updates):
        sys_.insert(next_id, pts[n + (next_id % (2 * n))])
        live[next_id] = pts[n + (next_id % (2 * n))]
        next_id += 1
        victim = int(rng.choice(sorted(live)))
        t = time.perf_counter()
        sys_.delete(victim)
        del_lat.append(time.perf_counter() - t)
        live.pop(victim)
        # Search every 1/16th of the stream: each call is 4 micro-batches
        # of 8, each one a sample in stats.search_latency (insert latency
        # samples land in stats.insert_latency).
        if (i + 1) % (updates // 16) == 0:
            ids, _ = sys_.search_batch(q, k=5)
        if (i + 1) % (updates // 4) == 0:     # recall needs ground truth
            keys = np.asarray(sorted(live))
            mat = np.stack([live[k] for k in keys])
            gt = brute_force(jnp.asarray(mat), jnp.ones(len(keys), bool),
                             jnp.asarray(q), 5)
            recalls.append(float(recall_at_k(
                jnp.asarray(ids), jnp.asarray(keys[np.asarray(gt)]))))

    ins, sea = sys_.stats.insert_latency, sys_.stats.search_latency
    emit("fig6_insert_latency", ins.percentile(50.0),
         f"p99={ins.percentile(99.0) * 1e6:.0f}us n={ins.seen}",
         p50=ins.percentile(50.0), p99=ins.percentile(99.0), n=ins.seen)
    emit("fig6_delete_latency", float(np.median(del_lat)),
         f"p99={np.percentile(del_lat, 99) * 1e6:.0f}us n={len(del_lat)}",
         p50=float(np.percentile(del_lat, 50)),
         p99=float(np.percentile(del_lat, 99)), n=len(del_lat))
    disp_per_q = sys_.stats.search_dispatches / max(sys_.stats.searches, 1)
    emit("fig5_search_latency", sea.percentile(50.0),
         "p99=%.0fus recall_mean=%.3f merges=%d microbatches=%d "
         "disp/query=%.3f" % (sea.percentile(99.0) * 1e6, np.mean(recalls),
                              sys_.stats.merges, sea.seen, disp_per_q),
         p50=sea.percentile(50.0), p99=sea.percentile(99.0), n=sea.seen,
         batch_queries=cfg.batch_queries, recall_mean=float(np.mean(recalls)),
         dispatches_per_query=disp_per_q)
    write_bench_json("concurrent", quick=quick, n=n, updates=updates)


def poisson_serving(quick: bool = True, rates=(0.7, 2.5), tag="poisson"):
    """Sustained QPS under open-loop Poisson arrivals, per offered load.

    Offered load is relative to measured capacity: one warmed micro-batch
    dispatch is timed, capacity = batch_queries / dispatch_time, and each
    ``rate`` drives arrivals at rate * capacity (0.7 = sustainable,
    2.5 = overload — the row where shed/miss counters move; full batches
    amortize better than the single-batch calibration, so saturation needs
    real margin over the estimate).  Inserts and
    deletes run concurrently on an updater thread and threshold merges in
    the background (``background_merge``), so the rows price the serving
    loop against the full mutation pipeline, not a frozen index.
    """
    n = 768 if quick else 1536
    n_req = 192 if quick else 640
    pts = dataset(n * 2, seed=11)
    q = queryset(64, seed=12)
    cfg = SystemConfig(
        index=IndexConfig(capacity=n * 8, dim=DIM, R=24, L_build=32,
                          L_search=48, alpha=1.2),
        pq=PQConfig(dim=DIM, m=8, ksub=32, kmeans_iters=4),
        ro_snapshot_points=96, merge_threshold=384, temp_capacity=n,
        insert_batch=32, batch_queries=8, serve_queue_capacity=64,
        background_merge=True)
    sys_ = bootstrap_system(pts[:n], np.arange(n), cfg)
    sys_.search_batch(q[:8], k=5)                     # warm the base shape

    stop = threading.Event()
    next_id = [10_000_000]

    def updater():
        rngu = np.random.default_rng(7)
        start = next_id[0]
        while not stop.is_set():
            i = next_id[0]
            if i - start < 2 * n:       # bound liveset growth vs capacity
                sys_.insert(i, pts[n + i % n])
                next_id[0] = i + 1
            sys_.delete(int(rngu.integers(0, n)))      # base-set victims
            time.sleep(0.005)                          # don't starve serving

    upd = threading.Thread(target=updater, daemon=True)
    upd.start()

    # Prime under churn: walk the system through rollovers/merges so the
    # per-tier-count programs are compiled, then calibrate the dispatch
    # cost as the MEDIAN of single-micro-batch calls on the LIVE system —
    # an idle-system estimate undershoots wildly once flushes and lane
    # restacks ride the serving path.
    for _ in range(24):
        sys_.search_batch(q[:8], k=5)
    lats = []
    for _ in range(9):
        _, s = timed(lambda: sys_.search_batch(q[:8], k=5))
        lats.append(s)
    per_batch = float(np.median(lats))
    capacity_qps = cfg.batch_queries / per_batch
    # SLO sized to the machine: ~4 dispatch times of headroom.
    slo_ms = max(4.0 * per_batch * 1e3, 10.0)
    sys_.cfg = dataclasses.replace(sys_.cfg, slo_ms=slo_ms,
                                   dispatch_estimate_ms=per_batch * 1e3)

    for rate in rates:
        lam = rate * capacity_qps
        rng = np.random.default_rng(int(rate * 100))
        gaps = rng.exponential(1.0 / lam, n_req)
        sys_.stats.serve_latency = Reservoir(seed=2)  # fresh per load row
        s0 = sys_.stats.serving_snapshot()
        sched = BatchScheduler(sys_, k=5)
        sched.start()
        t0 = time.perf_counter()
        t_next = t0
        for gap in gaps:
            t_next += gap
            delay = t_next - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            sched.submit(q[int((t_next * 1e6)) % len(q)])
        sched.stop(flush=True)                         # drain the tail
        wall = time.perf_counter() - t0

        s1 = sys_.stats.serving_snapshot()
        served = s1["scheduled_requests"] - s0["scheduled_requests"]
        shed = s1["shed_requests"] - s0["shed_requests"]
        misses = s1["deadline_misses"] - s0["deadline_misses"]
        lat = sys_.stats.serve_latency
        qps = served / wall
        miss_rate = misses / max(served, 1)
        emit(f"{tag}_load{rate}", wall,
             f"qps={qps:.0f} offered={lam:.0f} p50={lat.percentile(50.0) * 1e3:.1f}ms "
             f"p99={lat.percentile(99.0) * 1e3:.1f}ms miss={miss_rate:.3f} "
             f"occ={sched.mean_occupancy:.2f} shed={shed} "
             f"merges={sys_.stats.merges}",
             rate=rate, offered_qps=lam, qps=qps, slo_ms=slo_ms,
             p50=lat.percentile(50.0), p99=lat.percentile(99.0),
             miss_rate=miss_rate, occupancy=sched.mean_occupancy,
             served=served, shed=shed, deadline_misses=misses,
             merges=sys_.stats.merges)

    stop.set()
    upd.join()
    sys_.wait_merge()


if __name__ == "__main__":
    main()
