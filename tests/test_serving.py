"""The batched serving engine (docs/SERVING.md): `search_batch` must be
bit-identical per query to serving each query alone — across deletes, after
a merge, with and without the PQ-navigated LTI lane; `batch_queries`
micro-batching must chunk/pad without changing any result while
`search_dispatches` counts programs (B queries in one launch == 1); the
mesh-sharded LTI lane (`shard_lti`) must return bit-identical results for
any shard count — exercised in-process on 1 device and, via the
`scripts/shard_probe.py` subprocess, on 4 fake host devices; and the
query-batched `frontier_select` launch must match its vmapped reference."""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.config import IndexConfig, PQConfig, SystemConfig
from repro.core.system import FreshDiskANN, bootstrap_system
from repro.kernels import ops

from conftest import DIM


def _sys_cfg(**kw):
    base = dict(
        index=IndexConfig(capacity=2048, dim=DIM, R=24, L_build=32,
                          L_search=64, alpha=1.2),
        pq=PQConfig(dim=DIM, m=8, ksub=32, kmeans_iters=4),
        ro_snapshot_points=64, merge_threshold=100_000,   # keep tiers staged
        temp_capacity=256, insert_batch=32)
    base.update(kw)
    return SystemConfig(**base)


def _three_tier_system(points, **kw):
    """LTI + 2 frozen RO snapshots + a live RW tier."""
    sys_ = bootstrap_system(points[:400], np.arange(400), _sys_cfg(**kw))
    for i in range(150):
        sys_.insert(2000 + i, points[500 + i])
    return sys_


def _per_query(sys_, queries, k):
    outs = [sys_.search_batch(queries[i:i + 1], k=k)
            for i in range(len(queries))]
    return (np.concatenate([o[0] for o in outs]),
            np.concatenate([o[1] for o in outs]))


# ---------------------------------------------------------- batched serving

def test_search_batch_matches_per_query(points, queries):
    """The tentpole bar: B queries in one program == B one-query programs,
    row for row — with DeleteList members spread across every tier."""
    sys_ = _three_tier_system(points)
    for e in (0, 5, 2000, 2149):
        sys_.delete(e)
    ids_b, d_b = sys_.search_batch(queries[:16], k=5)
    ids_1, d_1 = _per_query(sys_, queries[:16], k=5)
    np.testing.assert_array_equal(ids_b, ids_1)
    np.testing.assert_array_equal(d_b, d_1)


def test_search_batch_matches_per_query_no_lti_lane(points, queries):
    """PQ lane off: a system with no LTI (temp tiers only) must hold the
    same per-query contract through the temps-only unified program."""
    sys_ = FreshDiskANN(_sys_cfg())
    for i in range(150):
        sys_.insert(2000 + i, points[500 + i])
    sys_.delete(2003)
    ids_b, d_b = sys_.search_batch(queries[:12], k=5)
    ids_1, d_1 = _per_query(sys_, queries[:12], k=5)
    np.testing.assert_array_equal(ids_b, ids_1)
    np.testing.assert_array_equal(d_b, d_1)


def test_search_batch_matches_per_query_post_merge(points, queries):
    """After a StreamingMerge retires the RO tiers, the restacked program
    must still serve batches bit-identically to per-query calls."""
    sys_ = _three_tier_system(points)
    sys_.delete(2001)
    sys_.merge()
    assert sys_.stats.merges == 1 and not sys_.ro
    ids_b, d_b = sys_.search_batch(queries[:12], k=5)
    ids_1, d_1 = _per_query(sys_, queries[:12], k=5)
    np.testing.assert_array_equal(ids_b, ids_1)
    np.testing.assert_array_equal(d_b, d_1)


def test_search_batch_matches_sequential_oracle(points, queries):
    """Transitivity anchor: the batched program vs the per-tier sequential
    oracle on the same batch (batch_fanout=False)."""
    sys_b = _three_tier_system(points)
    sys_s = _three_tier_system(points, batch_fanout=False)
    ids_b, d_b = sys_b.search_batch(queries, k=5)
    ids_s, d_s = sys_s.search_batch(queries, k=5)
    np.testing.assert_array_equal(ids_b, ids_s)
    np.testing.assert_array_equal(d_b, d_s)


# ------------------------------------------------- micro-batching contract

def test_batch_queries_chunks_bit_identical(points, queries):
    """batch_queries=N serves a B-query request in ceil(B/N) fixed-shape
    programs with bit-identical results (tail chunk zero-padded)."""
    ref = _three_tier_system(points)
    ids_r, d_r = ref.search_batch(queries[:16], k=5)
    sys_ = _three_tier_system(points, batch_queries=6)
    d0, s0 = sys_.stats.search_dispatches, sys_.stats.searches
    ids, d = sys_.search_batch(queries[:16], k=5)     # 6 + 6 + 4(padded)
    assert sys_.stats.search_dispatches - d0 == 3
    assert sys_.stats.searches - s0 == 16             # queries, not pad rows
    np.testing.assert_array_equal(ids, ids_r)
    np.testing.assert_array_equal(d, d_r)


def test_batch_queries_pads_small_requests(points, queries):
    """A request smaller than the micro-batch width pads up to ONE program
    and slices the pad rows back off."""
    ref = _three_tier_system(points)
    ids_r, d_r = ref.search_batch(queries[:3], k=5)
    sys_ = _three_tier_system(points, batch_queries=8)
    d0 = sys_.stats.search_dispatches
    ids, d = sys_.search_batch(queries[:3], k=5)
    assert sys_.stats.search_dispatches - d0 == 1
    np.testing.assert_array_equal(ids, ids_r)
    np.testing.assert_array_equal(d, d_r)
    assert ids.shape == (3, 5)


def test_empty_request_is_a_no_op(points):
    """Regression: an empty query batch must return (0, k) arrays — not
    crash in the chunk concatenation — and launch no program, with and
    without micro-batching."""
    for bq in (0, 4):
        sys_ = _three_tier_system(points, batch_queries=bq)
        d0 = sys_.stats.search_dispatches
        ids, d = sys_.search_batch(np.zeros((0, DIM), np.float32), k=3)
        assert ids.shape == (0, 3) and d.shape == (0, 3)
        assert sys_.stats.search_dispatches == d0


def test_search_dispatches_counts_programs_not_queries(points, queries):
    """The counter contract under batching: B queries in one launch count
    ONE dispatch (and one per live tier on the sequential oracle), while
    `stats.searches` keeps counting queries."""
    sys_u = _three_tier_system(points)
    d0, s0 = sys_u.stats.search_dispatches, sys_u.stats.searches
    sys_u.search_batch(queries[:32], k=5)
    assert sys_u.stats.search_dispatches - d0 == 1
    assert sys_u.stats.searches - s0 == 32
    sys_s = _three_tier_system(points, batch_fanout=False)
    d0 = sys_s.stats.search_dispatches
    sys_s.search_batch(queries[:32], k=5)
    assert sys_s.stats.search_dispatches - d0 == 4    # LTI + RW + 2 RO
    # micro-batched sequential oracle: per tier per chunk.
    sys_c = _three_tier_system(points, batch_fanout=False, batch_queries=16)
    d0 = sys_c.stats.search_dispatches
    sys_c.search_batch(queries[:32], k=5)
    assert sys_c.stats.search_dispatches - d0 == 8    # 2 chunks x 4 tiers


# ------------------------------------------------------- sharded LTI lane

def test_shard_lti_single_device_parity(points, queries):
    """shard_lti on one device runs the real shard_map program (mesh of 1)
    and must be bit-identical to the unsharded unified path — the tier-1
    half of the shard-invariance contract."""
    ref = _three_tier_system(points)
    for e in (0, 5, 2000):
        ref.delete(e)
    ids_r, d_r = ref.search_batch(queries[:12], k=5)
    sys_ = _three_tier_system(points, shard_lti=1)
    for e in (0, 5, 2000):
        sys_.delete(e)
    d0 = sys_.stats.search_dispatches
    ids, d = sys_.search_batch(queries[:12], k=5)
    assert sys_.stats.search_dispatches - d0 == 1     # still ONE program
    np.testing.assert_array_equal(ids, ids_r)
    np.testing.assert_array_equal(d, d_r)


def test_shard_lti_survives_merge(points, queries):
    """A merge swaps the LTI generation: the sharded placement cache must
    miss and re-shard the NEW graph, keeping parity with the oracle."""
    ref = _three_tier_system(points)
    sys_ = _three_tier_system(points, shard_lti=1)
    for s in (ref, sys_):
        s.search_batch(queries[:4], k=5)      # warm the sharded placement
        s.delete(2001)
        s.merge()
    ids_r, d_r = ref.search_batch(queries[:12], k=5)
    ids, d = sys_.search_batch(queries[:12], k=5)
    np.testing.assert_array_equal(ids, ids_r)
    np.testing.assert_array_equal(d, d_r)


def test_shard_count_caps_at_device_census(points):
    """shard_lti beyond the device count degrades to every device present,
    never errors (the recipe says 'ask for the fleet you wish you had')."""
    sys_ = _three_tier_system(points, shard_lti=64)
    assert sys_._shard_count() >= 1
    ids, _ = sys_.search_batch(points[:4], k=3)
    assert ids.shape == (4, 3)


@pytest.mark.parametrize("n_dev", [4])
def test_shard_invariance_on_fake_devices(n_dev):
    """The multi-device half: run scripts/shard_probe.py in a subprocess
    with XLA_FLAGS forcing 4 fake host devices — shard counts 1/2/4 must be
    bit-identical to the unsharded program, one dispatch per micro-batch,
    chunk/pad invariant.  (A subprocess because the device census is fixed
    at jax import.)"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env.pop("PYTHONPATH", None)               # probe inserts src/ itself
    out = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "shard_probe.py")],
        env=env, capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, f"probe failed:\n{out.stdout}\n{out.stderr}"
    assert "SHARD-PROBE OK" in out.stdout


# ------------------------------------------------- multi-replica routing

def test_replica_set_single_device_parity(points, queries):
    """ReplicaSet on one device (1 replica x 1 shard) runs the real
    replica-routed program and must be bit-identical to `search_batch` —
    the tier-1 half of the replica-invariance contract (the multi-device
    half is `scripts/serving_probe.py`)."""
    from repro.serving import ReplicaSet
    sys_ = _three_tier_system(points, batch_queries=4)
    for e in (0, 5, 2000, 2149):
        sys_.delete(e)
    ref_ids, ref_d = sys_.search_batch(queries[:12], k=5)
    rs = ReplicaSet(sys_, 1)
    ids, d = rs.search_batch(queries[:12], k=5)
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_array_equal(d, ref_d)


def test_replica_round_robin_accounting(points, queries):
    """Each fixed-shape micro-batch lands on the next replica in turn and
    is counted in `dispatches[r]`; `search_dispatches` still counts every
    program once.  (One device -> one replica; the spread across 2/4
    replicas is asserted in the probe.)"""
    from repro.serving import ReplicaSet
    sys_ = _three_tier_system(points, batch_queries=4)
    rs = ReplicaSet(sys_, 1)
    d0 = sys_.stats.search_dispatches
    rs.search_batch(queries[:10], k=5)          # 4 + 4 + 2(padded) chunks
    assert rs.dispatches == [3]
    assert sys_.stats.search_dispatches - d0 == 3
    # pinned routing bypasses round-robin
    rs.search_batch(queries[:2], k=5, replica=0)
    assert rs.dispatches == [4]
    with pytest.raises(ValueError):
        rs.search_batch(queries[:2], k=5, replica=7)


def test_replica_set_filter_parity(points, queries):
    """Filtered micro-batches through the replica router are bit-identical
    to the system's own filtered ``search_batch`` — the filter folds into
    the same drop mask on both paths, so replica routing cannot perturb a
    filtered result (the 4-fake-device half is ``scripts/filter_probe.py``).
    Stats accounting (filtered/tenant counters) accrues on either path."""
    from repro.core.graph import FilterSpec
    from repro.serving import ReplicaSet
    sys_ = bootstrap_system(
        points[:400], np.arange(400), _sys_cfg(batch_queries=4,
                                               filter_words=1),
        labels=[[i % 3] for i in range(400)],
        tenants=[i % 2 for i in range(400)])
    for i in range(60):
        sys_.insert(2000 + i, points[500 + i], labels=[i % 3],
                    tenant=i % 2)
    for e in (0, 5, 2000):
        sys_.delete(e)
    rs = ReplicaSet(sys_, 1)
    for spec in (FilterSpec(tenant=1), FilterSpec(all_of=(1,)),
                 FilterSpec(all_of=(0,), tenant=0)):
        ref_ids, ref_d = sys_.search_batch(queries[:12], k=5, filter=spec)
        ids, d = rs.search_batch(queries[:12], k=5, filter=spec)
        np.testing.assert_array_equal(ids, ref_ids)
        np.testing.assert_array_equal(d, ref_d)
    f0 = sys_.stats.filtered_searches
    rs.search_batch(queries[:4], k=5, filter=FilterSpec(tenant=1))
    assert sys_.stats.filtered_searches - f0 == 4
    assert sys_.stats.tenant_searches.get(1, 0) >= 4
    # unfiltered requests through the same router stay on the cached
    # unfiltered drop mask — parity with the direct path is unchanged
    ref_ids, ref_d = sys_.search_batch(queries[:8], k=5)
    ids, d = rs.search_batch(queries[:8], k=5)
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_array_equal(d, ref_d)


def test_replica_set_degrades_to_device_census(points):
    """Asking for more replicas x shards than devices exist degrades (cap
    shards, then replicas) instead of raising — same posture as
    `shard_lti`'s census cap."""
    from repro.serving import ReplicaSet
    sys_ = _three_tier_system(points)
    rs = ReplicaSet(sys_, 8, n_shards=8)
    assert rs.n_replicas >= 1 and rs.n_shards >= 1
    ids, _ = rs.search_batch(points[:4], k=3)
    assert ids.shape == (4, 3)


def test_replica_routing_survives_background_merge(points, queries):
    """A background merge swaps the LTI generation mid-service: every
    replica's placement cache must miss on its next dispatch and re-place
    the new graph, keeping parity with a fresh reference system."""
    from repro.serving import ReplicaSet
    ref = _three_tier_system(points)
    sys_ = _three_tier_system(points, batch_queries=4,
                              background_merge=True)
    rs = ReplicaSet(sys_, 1)
    rs.search_batch(queries[:4], k=5)           # warm the placement cache
    for s in (ref, sys_):
        s.delete(2001)
    ref.merge()
    sys_.merge(background=True)
    sys_.wait_merge()
    assert sys_.stats.merges == 1
    ids_r, d_r = ref.search_batch(queries[:12], k=5)
    ids, d = rs.search_batch(queries[:12], k=5)
    np.testing.assert_array_equal(ids, ids_r)
    np.testing.assert_array_equal(d, d_r)


def test_serving_invariance_on_fake_devices():
    """Multi-device half of the replica contract: scripts/serving_probe.py
    in a subprocess with 4 fake host devices — scheduler invariants under a
    virtual clock, per-query bit-parity 1 vs 2 vs 4 replicas, 2x2
    replicas-x-shards composition, round-robin accounting, and routing
    survival across a background merge."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("PYTHONPATH", None)               # probe inserts src/ itself
    out = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "serving_probe.py")],
        env=env, capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, f"probe failed:\n{out.stdout}\n{out.stderr}"
    assert "SERVING-PROBE OK" in out.stdout


# ------------------------------------------- query-batched frontier kernel

def test_frontier_select_batch_matches_vmapped_ref(rng):
    """The [B]-leading-axis launch (one grid point per query row) must match
    the vmapped single-row reference bit-for-bit, mixed occupancy and all."""
    B, L, K, V, W = 5, 16, 24, 30, 4
    ci = np.full((B, L), -1, np.int32)
    cd = np.full((B, L), np.inf, np.float32)
    ni = np.full((B, K), -1, np.int32)
    nd = np.full((B, K), np.inf, np.float32)
    vi = np.full((B, V), -1, np.int32)
    vd = np.full((B, V), np.inf, np.float32)
    vc = np.zeros((B,), np.int32)
    for b in range(B):
        nc = int(rng.integers(1, L))
        ci[b, :nc] = rng.permutation(200)[:nc]
        cd[b, :nc] = np.sort(rng.random(nc)).astype(np.float32)
        nn = int(rng.integers(0, K))
        ni[b, :nn] = 300 + rng.permutation(200)[:nn]
        nd[b, :nn] = rng.random(nn).astype(np.float32)
        # Contract: vis_cnt == number of valid ids in vis_ids (the kernel
        # re-derives the count from occupancy), so only seed visited slots
        # from the VALID candidate prefix.
        nv = min(int(rng.integers(0, 4)), nc)
        vi[b, :nv] = ci[b, :nv]
        vd[b, :nv] = cd[b, :nv]
        vc[b] = nv
    args = [jnp.asarray(x) for x in (ci, cd, ni, nd, vi, vd, vc)]
    out_k = ops.frontier_select_batch(*args, W=W, max_visits=V,
                                      use_kernel=True)
    out_r = ops.frontier_select_batch(*args, W=W, max_visits=V,
                                      use_kernel=False)
    for x, y in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # ... and each row equals the single-lane call (B=1 grid).
    for b in range(B):
        one = ops.frontier_select(*[a[b] for a in args], W=W, max_visits=V,
                                  use_kernel=True)
        for x, y in zip(one, out_k):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y[b]))
