#!/usr/bin/env python
"""Sharded-serving smoke probe (run by ``scripts/smoke.sh --shards`` and CI).

Forces 4 fake host devices (the XLA_FLAGS trick from docs/SERVING.md), builds
one live FreshDiskANN system — LTI + two frozen RO snapshots + an RW tier,
with DeleteList members in every tier — and asserts the serving-engine
contracts end to end on REAL multi-device sharding:

  1. `search_batch` under `shard_lti` in {1, 2, 4} returns (ids, dists)
     bit-identical to the unsharded unified program — shard-count invariance
     by construction (owner-computes + psum, replicated beam state);
  2. every sharded batch is still ONE device program
     (`SystemStats.search_dispatches` += 1 per micro-batch);
  3. `batch_queries` micro-batching chunks/pads without changing any result
     and counts ceil(B/N) programs;
  4. per-query serving (B=1 calls) matches the batch, row for row;
  5. with the kernels routed (``use_kernel=True``, the TPU engine), 4
     shards still match the unsharded kernel program bit for bit.

Exits non-zero on the first violated contract.  The same invariants run
in-process (single device, shards=1) in ``tests/test_serving.py``; this
probe is the multi-device half, invoked as a subprocess there and as a
dedicated CI step.
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


def main() -> int:
    n_dev = len(jax.devices())
    print(f"# shard probe: {n_dev} devices ({jax.default_backend()})")
    assert n_dev >= 4, "expected 4 fake host devices (set XLA_FLAGS)"
    sys_, q = build_system()
    ref_ids, ref_d = sys_.search_batch(q, k=5)

    # 1+2: shard-count invariance + one-program dispatch on the SAME system
    # (reconfiguring shard_lti in place exercises the mesh/placement cache
    # turnover too).
    for ns in (1, 2, 4):
        sys_.cfg = dataclasses.replace(sys_.cfg, shard_lti=ns)
        ids, d = sys_.search_batch(q, k=5)
        np.testing.assert_array_equal(ids, ref_ids)
        np.testing.assert_array_equal(d, ref_d)
        d0 = sys_.stats.search_dispatches
        sys_.search_batch(q, k=5)
        assert sys_.stats.search_dispatches - d0 == 1, \
            f"shards={ns}: batch must stay ONE program"
        print(f"# shards={ns}: bit-identical to unsharded, 1 dispatch/batch")

    # 3: micro-batching under sharding — chunk + pad, same bits, ceil(B/N).
    sys_.cfg = dataclasses.replace(sys_.cfg, shard_lti=4, batch_queries=6)
    d0 = sys_.stats.search_dispatches
    ids, d = sys_.search_batch(q, k=5)                 # 16 -> 3 micro-batches
    assert sys_.stats.search_dispatches - d0 == 3
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_array_equal(d, ref_d)
    ids, d = sys_.search_batch(q[:3], k=5)             # 3 < 6 -> padded
    np.testing.assert_array_equal(ids, ref_ids[:3])
    np.testing.assert_array_equal(d, ref_d[:3])
    print("# batch_queries=6: ceil(B/N) programs, results unchanged")

    # 4: per-query oracle under the sharded engine.
    for i in range(4):
        ids, d = sys_.search_batch(q[i:i + 1], k=5)
        np.testing.assert_array_equal(ids[0], ref_ids[i])
        np.testing.assert_array_equal(d[0], ref_d[i])
    print("# per-query == batched, row for row")

    # 5: the kernel-routed engine (what a TPU runs): the sharded lane must
    # take the same distance kernels as the unsharded program.
    kcfg = dataclasses.replace(sys_.cfg.index, use_kernel=True)
    sys_.cfg = dataclasses.replace(sys_.cfg, index=kcfg, shard_lti=0)
    kref_ids, kref_d = sys_.search_batch(q, k=5)
    sys_.cfg = dataclasses.replace(sys_.cfg, shard_lti=4)
    ids, d = sys_.search_batch(q, k=5)
    np.testing.assert_array_equal(ids, kref_ids)
    np.testing.assert_array_equal(d, kref_d)
    print("# use_kernel=True: shards=4 bit-identical to unsharded")
    print("# SHARD-PROBE OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
