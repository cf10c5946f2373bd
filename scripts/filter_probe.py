#!/usr/bin/env python
"""Filtered & multi-tenant serving probe (run by ``scripts/smoke.sh
--filters`` and CI).

Forces 4 fake host devices and asserts the filtered-search contracts of
docs/ARCHITECTURE.md ("Filtered & multi-tenant search") end to end:

  1. selectivity-1.0 parity — a filter every live point matches returns
     bit-identical (ids, dists) to the unfiltered call, on the system
     path AND through a 2-replica ``ReplicaSet`` on real device groups
     (the filter folds into the same cached drop mask, applied
     post-search, so it can never perturb the unfiltered program);
  2. tenant isolation — per-tenant filtered searches across all three
     tiers (LTI + RO + RW) never return a cross-tenant id, replica-routed
     or direct, and the per-tenant search counters accrue;
  3. post-merge label survival — labels follow points through a
     StreamingMerge's slot scatter: filtered searches stay leak-free and
     the merged LTI's label side tables carry every live tenant;
  4. scheduler de-interleave — mixed-FilterSpec tickets through a
     ``BatchScheduler`` under a VirtualClock close into single-spec
     micro-batches, per-tenant quota sheds are counted in
     ``SystemStats.tenant_sheds``, and every served row is bit-identical
     to direct filtered ``search_batch``.

Exits non-zero on the first violated contract.  The single-device halves
run in-process in ``tests/test_filtered.py`` / ``tests/test_scheduler.py``;
this probe is the multi-device half.
"""
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
from repro.core.graph import FilterSpec               # noqa: E402
from repro.core.system import bootstrap_system        # noqa: E402
from repro.serving import (BatchScheduler, ReplicaSet,  # noqa: E402
                           VirtualClock)

N_TENANTS = 3


def build_system(**kw):
    dim = 24
    rng = np.random.default_rng(0)
    pts = rng.standard_normal((700, dim)).astype(np.float32)
    cfg = SystemConfig(
        index=IndexConfig(capacity=2048, dim=dim, R=24, L_build=32,
                          L_search=64, alpha=1.2),
        pq=PQConfig(dim=dim, m=8, ksub=32, kmeans_iters=4),
        ro_snapshot_points=64, merge_threshold=100_000,
        temp_capacity=256, insert_batch=32, filter_words=1, **kw)
    sys_ = bootstrap_system(pts[:400], np.arange(400), cfg,
                            labels=[[0, i % 4] for i in range(400)],
                            tenants=[i % N_TENANTS for i in range(400)])
    for i in range(150):                      # 2 RO rollovers + live RW tier
        sys_.insert(2000 + i, pts[500 + i], labels=[0, i % 4],
                    tenant=(2000 + i) % N_TENANTS)
    for e in (0, 5, 2000, 2149):              # deletes across every tier
        sys_.delete(e)
    return sys_, rng.standard_normal((16, dim)).astype(np.float32)


def tenant_of(e):
    return e % N_TENANTS


def check(cond, msg):
    if not cond:
        print(f"FILTER-PROBE FAILED: {msg}", file=sys.stderr)
        sys.exit(1)


def main():
    check(len(jax.devices()) == 4, f"expected 4 devices, {jax.devices()}")

    # ---- 1. selectivity-1.0 parity, direct and replica-routed ----------
    sys_, q = build_system(batch_queries=8)
    ids_u, d_u = sys_.search_batch(q, k=5)
    ids_f, d_f = sys_.search_batch(q, k=5, filter=FilterSpec(all_of=(0,)))
    check(np.array_equal(ids_f, ids_u) and np.array_equal(d_f, d_u),
          "selectivity-1.0 filter not bit-identical to unfiltered")
    rs = ReplicaSet(sys_, 2, n_shards=1)
    check(rs.n_replicas == 2, f"wanted 2 replicas, got {rs.n_replicas}")
    ids_r, d_r = rs.search_batch(q, k=5, filter=FilterSpec(all_of=(0,)))
    check(np.array_equal(ids_r, ids_u) and np.array_equal(d_r, d_u),
          "replica-routed sel-1.0 filter not bit-identical")
    print("# sel-1.0 parity ok (direct + 2 replicas)")

    # ---- 2. tenant isolation across tiers, both paths ------------------
    for t in range(N_TENANTS):
        for tag, (ids, _) in (
                ("direct", sys_.search_batch(q, 5, filter=FilterSpec(tenant=t))),
                ("replica", rs.search_batch(q, 5, filter=FilterSpec(tenant=t)))):
            for row in np.asarray(ids):
                for e in (int(x) for x in row if x >= 0):
                    check(tenant_of(e) == t,
                          f"{tag}: id {e} leaked into tenant {t}")
    check(sum(sys_.stats.tenant_searches.values()) > 0,
          "tenant search counters did not accrue")
    print("# tenant isolation ok (3 tenants x direct/replica)")

    # ---- 3. post-merge label survival ----------------------------------
    sys_.merge()
    sys_.wait_merge()
    for t in range(N_TENANTS):
        ids, _ = sys_.search_batch(q, 5, filter=FilterSpec(tenant=t))
        for row in np.asarray(ids):
            for e in (int(x) for x in row if x >= 0):
                check(tenant_of(e) == t,
                      f"post-merge: id {e} leaked into tenant {t}")
    live = sys_.lti_ext_ids >= 0
    check((sys_.lti_labels.tenant[live] >= 0).all(),
          "merged LTI rows lost their tenant tags")
    check(set(np.unique(sys_.lti_labels.tenant[live]).tolist())
          == set(range(N_TENANTS)),
          "merged LTI label table does not cover every tenant")
    print("# post-merge label survival ok")

    # ---- 4. scheduler de-interleave + tenant quota ---------------------
    clk = VirtualClock()
    sys2, q2 = build_system(batch_queries=4, slo_ms=50.0,
                            serve_queue_capacity=64, clock=clk,
                            tenant_quota=2)
    served = []
    ref = sys2.search_batch

    def serve(qs, k, L=None, beam_width=None, **kw):
        served.append(kw.get("filter"))
        return ref(qs, k, L=L, beam_width=beam_width, **kw)

    sched = BatchScheduler(sys2, k=5, serve=serve)
    s0, s1 = FilterSpec(tenant=0), FilterSpec(tenant=1)
    tickets = [(sched.submit(q2[i], filter=s), s) for i, s in
               enumerate([s0, s1, s0, None, s1, None])]
    check(all(t is not None for t, _ in tickets), "in-quota ticket shed")
    check(sched.submit(q2[7], filter=s0) is None,
          "3rd queued tenant-0 ticket not quota-shed")
    check(sys2.stats.tenant_sheds == {0: 1},
          f"tenant_sheds {sys2.stats.tenant_sheds} != {{0: 1}}")
    while sched.flush():
        pass
    specs = {str(s) for s in served}
    check(specs == {str(s0), str(s1), str(None)},
          f"expected one single-spec batch per distinct spec, got {specs}")
    for t, s in tickets:
        kw = {"filter": s} if s is not None else {}
        ids, d = ref(t.query[None, :], 5, **kw)
        check(np.array_equal(t.ids, np.asarray(ids)[0])
              and np.array_equal(t.dists, np.asarray(d)[0]),
              "scheduled filtered row not bit-identical to direct")
    print("# scheduler de-interleave + quota ok "
          f"({len(served)} single-spec batches)")

    print("FILTER-PROBE OK")


if __name__ == "__main__":
    main()
