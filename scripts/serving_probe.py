#!/usr/bin/env python
"""Serving front-end smoke probe (run by ``scripts/smoke.sh --serving``
and CI).

Forces 4 fake host devices and asserts the continuous-batching + replica
contracts end to end (docs/SERVING.md):

  1. scheduler invariants under an injected VirtualClock — a full queue
     closes immediately at exactly ``batch_queries``, a partial batch
     closes at the oldest deadline minus the dispatch estimate, an empty
     queue never dispatches, overflow submissions shed;
  2. every scheduled request's (ids, dists) row is bit-identical to
     calling ``search_batch`` directly;
  3. replica-count invariance on REAL device groups — 1 vs 2 vs 4
     replicas return bit-identical rows, micro-batches land round-robin
     (``ReplicaSet.dispatches``);
  4. the 2-axis composition: 2 replicas x 2 ``shard_lti`` row shards on
     the same 4 devices, still bit-identical;
  5. routing survives a background merge: the LTI generation swap misses
     every replica's placement cache and re-places the new graph.

Exits non-zero on the first violated contract.  The single-device halves
of these contracts run in-process in ``tests/test_scheduler.py`` and
``tests/test_serving.py``; this probe is the multi-device half, invoked
as a subprocess there and as a dedicated CI step.
"""
import dataclasses
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"   # a CPU probe: never take the chip
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import numpy as np                                    # noqa: E402
import jax                                            # noqa: E402

from repro.core.config import (IndexConfig, PQConfig,  # noqa: E402
                               SystemConfig)
from repro.core.system import bootstrap_system        # noqa: E402
from repro.serving import (BatchScheduler, ReplicaSet,  # noqa: E402
                           VirtualClock)


def build_system(**kw):
    dim = 24
    rng = np.random.default_rng(0)
    pts = rng.standard_normal((700, dim)).astype(np.float32)
    cfg = SystemConfig(
        index=IndexConfig(capacity=2048, dim=dim, R=24, L_build=32,
                          L_search=64, alpha=1.2),
        pq=PQConfig(dim=dim, m=8, ksub=32, kmeans_iters=4),
        ro_snapshot_points=64, merge_threshold=100_000,
        temp_capacity=256, insert_batch=32, **kw)
    sys_ = bootstrap_system(pts[:400], np.arange(400), cfg)
    for i in range(150):                      # 2 RO rollovers + live RW tier
        sys_.insert(2000 + i, pts[500 + i])
    for e in (0, 5, 2000, 2149):              # deletes across every tier
        sys_.delete(e)
    return sys_, rng.standard_normal((16, dim)).astype(np.float32)


def probe_scheduler() -> None:
    clk = VirtualClock()
    sys_, q = build_system(batch_queries=4, slo_ms=25.0,
                           serve_queue_capacity=8, dispatch_estimate_ms=5.0,
                           clock=clk)
    ref_ids, ref_d = sys_.search_batch(q, k=5)
    sizes = []
    ref = sys_.search_batch

    def serve(qs, k, L=None, beam_width=None):
        sizes.append(len(qs))
        return ref(qs, k, L=L, beam_width=beam_width)

    sched = BatchScheduler(sys_, k=5, serve=serve)
    assert sched.clock is clk, "scheduler must use the injected clock"
    assert sched.run_once() == 0, "empty queue must never dispatch"
    tickets = [sched.submit(qi) for qi in q[:6]]
    assert sched.run_once() == 4, "full queue closes at batch_queries"
    close = sched.next_close_time()
    assert close == clk.now() + 0.025 - sched.dispatch_estimate, \
        "partial close time = oldest deadline - dispatch estimate"
    clk.advance(close - clk.now())
    assert sched.run_once() == 2, "deadline close takes the partial batch"
    assert sizes == [4, 2] and sys_.stats.deadline_misses == 0
    for i, t in enumerate(tickets):
        np.testing.assert_array_equal(t.ids, ref_ids[i])
        np.testing.assert_array_equal(t.dists, ref_d[i])
    print("# scheduler: close policy + bit-parity OK on the virtual clock")

    outs = [sched.submit(q[0]) for _ in range(10)]      # capacity 8
    assert sum(t is None for t in outs) == 2
    assert sys_.stats.shed_requests == 2, "overflow must shed, not queue"
    assert sched.flush() == 8
    print("# scheduler: backpressure sheds beyond capacity OK")


def probe_replicas() -> None:
    sys_, q = build_system(batch_queries=4)
    ref_ids, ref_d = sys_.search_batch(q, k=5)

    for nr in (1, 2, 4):
        rs = ReplicaSet(sys_, nr)
        assert rs.n_replicas == nr, f"wanted {nr} replicas on 4 devices"
        ids, d = rs.search_batch(q, k=5)                # 16 -> 4 micro-batches
        np.testing.assert_array_equal(ids, ref_ids)
        np.testing.assert_array_equal(d, ref_d)
        spread = 4 // nr
        assert rs.dispatches == [spread] * nr, \
            f"round-robin spread {rs.dispatches} != uniform over {nr}"
        print(f"# replicas={nr}: bit-identical, dispatches={rs.dispatches}")

    # 2-axis composition: 2 replicas x 2 LTI row shards on the same grid.
    rs = ReplicaSet(sys_, 2, n_shards=2)
    assert (rs.n_replicas, rs.n_shards) == (2, 2)
    ids, d = rs.search_batch(q, k=5)
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_array_equal(d, ref_d)
    print("# 2 replicas x 2 shards: composition bit-identical")

    # The kernel-routed engine (what a TPU runs): replicas take the same
    # distance kernels as the direct program.
    sys_.cfg = dataclasses.replace(sys_.cfg, index=dataclasses.replace(
        sys_.cfg.index, use_kernel=True))
    kref_ids, kref_d = sys_.search_batch(q, k=5)
    ids, d = ReplicaSet(sys_, 4).search_batch(q, k=5)
    np.testing.assert_array_equal(ids, kref_ids)
    np.testing.assert_array_equal(d, kref_d)
    print("# use_kernel=True: 4 replicas bit-identical to direct")

    # Generation swap under routing: background merge, then re-serve.
    sys_, q = build_system(batch_queries=4, background_merge=True)
    rs = ReplicaSet(sys_, 2)
    rs.search_batch(q[:4], k=5)                 # warm every placement path
    sys_.delete(2001)
    sys_.merge(background=True)
    sys_.wait_merge()
    assert sys_.stats.merges == 1
    ref_ids, ref_d = sys_.search_batch(q, k=5)  # post-merge reference
    ids, d = rs.search_batch(q, k=5)
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_array_equal(d, ref_d)
    print("# routing survives the background merge's generation swap")


def main() -> int:
    n_dev = len(jax.devices())
    print(f"# serving probe: {n_dev} devices ({jax.default_backend()})")
    assert n_dev >= 4, "expected 4 fake host devices (set XLA_FLAGS)"
    probe_scheduler()
    probe_replicas()
    print("# SERVING-PROBE OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
