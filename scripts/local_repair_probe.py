#!/usr/bin/env python
"""Localized delete-repair smoke probe (``scripts/smoke.sh --local-repair``).

Builds two small live FreshDiskANN systems that differ only in how merges
route the Delete phase — ``local_repair_threshold=1.0`` (every merge runs
the localized affected-set repair) vs ``0.0`` (every merge runs the global
Algorithm-4 sweep) — and asserts the contracts of docs/ARCHITECTURE.md,
"Localized delete repair", end to end:

  1. after interleaved inserts / deletes / merges, the two systems' LTI
     adjacencies and search results are **bit-identical** (routing is a
     cost choice, never a result choice);
  2. the routing counters split as configured: the local system logs only
     local_repairs, the global one only global_repairs;
  3. the reachability monitor ran after every merge (reach_probes), its
     gauge is a valid fraction, and the localized system's gauge did not
     degrade past the escalation bar relative to the global system's;
  4. a standalone ``consolidate(mode="local")`` repairs LTI-resident
     deletes in place and retires them from the DeleteList.

Exits non-zero on the first violated contract.  The same invariants run
as tier-1 tests in ``tests/test_streaming_property.py`` and
``tests/test_update_engine.py``; this probe is the CI-visible end-to-end
pass, mirroring shard_probe.py / disk_probe.py.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"   # a CPU probe: never take the chip
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import numpy as np                                    # noqa: E402

from repro.core.config import (IndexConfig, PQConfig,  # noqa: E402
                               SystemConfig)
from repro.core.system import bootstrap_system        # noqa: E402

DIM = 24


def build_system(threshold):
    rng = np.random.default_rng(0)
    pts = rng.standard_normal((700, DIM)).astype(np.float32)
    cfg = SystemConfig(
        index=IndexConfig(capacity=2048, dim=DIM, R=24, L_build=32,
                          L_search=64, alpha=1.2),
        pq=PQConfig(dim=DIM, m=8, ksub=32, kmeans_iters=4),
        ro_snapshot_points=64, merge_threshold=100_000,
        temp_capacity=256, insert_batch=32,
        local_repair_threshold=threshold, reach_probe_samples=64,
        # The probe checks the *routing* split, so keep it deterministic:
        # a noisy 64-sample probe must not escalate a merge to global
        # mid-run.  The drift check below uses an explicit bar instead.
        reach_escalate_frac=1.0)
    sys_ = bootstrap_system(pts[:400], np.arange(400), cfg)
    return sys_, pts, rng.standard_normal((16, DIM)).astype(np.float32)


def drive(sys_, pts, n_rounds=3):
    """Interleave inserts, LTI-resident deletes and explicit merges."""
    for r in range(n_rounds):
        for i in range(40):
            sys_.insert(2000 + 100 * r + i, pts[400 + 40 * r + i])
        for e in range(10 * r, 10 * r + 8):           # bootstrap residents
            sys_.delete(e)
        sys_.merge()


def main() -> int:
    sys_l, pts, queries = build_system(threshold=1.0)   # always localized
    sys_g, _, _ = build_system(threshold=0.0)           # always global
    drive(sys_l, pts)
    drive(sys_g, pts)

    # 1. bit-parity of the merged LTI and of served results.
    np.testing.assert_array_equal(
        np.asarray(sys_l.lti.graph.adjacency),
        np.asarray(sys_g.lti.graph.adjacency))
    ids_l, d_l = sys_l.search(queries, k=10)
    ids_g, d_g = sys_g.search(queries, k=10)
    np.testing.assert_array_equal(np.asarray(ids_l), np.asarray(ids_g))
    np.testing.assert_array_equal(np.asarray(d_l), np.asarray(d_g))
    print(f"# parity ok: adjacency + search bit-identical across routing")

    # 2. the routing counters split as configured.
    assert sys_l.stats.local_repairs == 3, sys_l.stats.local_repairs
    assert sys_l.stats.global_repairs == 0
    assert sys_g.stats.global_repairs == 3, sys_g.stats.global_repairs
    assert sys_g.stats.local_repairs == 0
    print(f"# routing ok: local={sys_l.stats.local_repairs} "
          f"global={sys_g.stats.global_repairs}")

    # 3. the reachability monitor ran and its gauge held.
    for s in (sys_l, sys_g):
        assert s.stats.reach_probes == 3, s.stats.reach_probes
        assert 0.0 <= s.stats.unreachable_frac <= 1.0
    bar = 0.05 + 2.0 / 64                             # escalation bar + noise
    drift = sys_l.stats.unreachable_frac - sys_g.stats.unreachable_frac
    assert drift <= bar, (sys_l.stats.unreachable_frac,
                          sys_g.stats.unreachable_frac)
    print(f"# reachability ok: local={sys_l.stats.unreachable_frac:.3f} "
          f"global={sys_g.stats.unreachable_frac:.3f} "
          f"(probes={sys_l.stats.reach_probes})")

    # 4. standalone localized consolidate retires LTI-resident deletes.
    victims = [100, 101, 102]
    for e in victims:
        sys_l.delete(e)
    n = sys_l.consolidate(mode="local")
    assert n == len(victims), n
    assert not (set(victims) & sys_l.deleted_ext)
    ids, _ = sys_l.search(pts[100:101], k=10)
    assert 100 not in np.asarray(ids)
    print(f"# consolidate ok: {n} deletes repaired in place and retired")
    return 0


if __name__ == "__main__":
    sys.exit(main())
