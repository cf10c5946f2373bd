#!/usr/bin/env python
"""Disk-tier smoke probe (run by ``scripts/smoke.sh --disk`` and CI).

Builds one small live FreshDiskANN system with ``storage_dir`` set — so the
LTI is mirrored to the decoupled on-disk layout (topology.bin + data.bin,
docs/STORAGE.md) — and asserts the storage-tier contracts end to end:

  1. `search_disk` at prefetch_depth in {0, 1, 2} returns (ids, dists)
     bit-identical to the in-memory engine's `search_batch` oracle —
     prefetch moves IO off the critical path, it never changes results;
  2. read accounting obeys the conservation law: with the block cache on,
     every requested adjacency row is either a file read
     (SystemStats.io_rows_read) or a cache hit (io_cache_hits), and with
     the cache off the reads match the in-memory engine's n_reads;
  3. a StreamingMerge delta-patches the layout in place
     (storage_rows_patched > 0) and post-merge disk results still match;
  4. the prefetcher's two staging buffers are identity-stable across
     searches (allocation-free steady state).

Exits non-zero on the first violated contract.  The same invariants run as
tier-1 tests in ``tests/test_storage.py``; this probe is the CI-visible
end-to-end pass over a real tempdir layout, mirroring shard_probe.py.
"""
import dataclasses
import os
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"   # a CPU probe: never take the chip
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import numpy as np                                    # noqa: E402

from repro.core.config import (IndexConfig, PQConfig,  # noqa: E402
                               SystemConfig)
from repro.core.system import bootstrap_system        # noqa: E402


def build_system(storage_dir, **kw):
    dim = 24
    rng = np.random.default_rng(0)
    pts = rng.standard_normal((700, dim)).astype(np.float32)
    cfg = SystemConfig(
        index=IndexConfig(capacity=2048, dim=dim, R=24, L_build=32,
                          L_search=64, alpha=1.2),
        pq=PQConfig(dim=dim, m=8, ksub=32, kmeans_iters=4),
        ro_snapshot_points=64, merge_threshold=100_000,
        temp_capacity=256, insert_batch=32,
        storage_dir=storage_dir, **kw)
    sys_ = bootstrap_system(pts[:400], np.arange(400), cfg)
    for i in range(150):                      # 2 RO rollovers + live RW tier
        sys_.insert(2000 + i, pts[500 + i])
    for e in (0, 5, 2000, 2149):              # deletes across every tier
        sys_.delete(e)
    return sys_, rng.standard_normal((16, dim)).astype(np.float32)


def main() -> int:
    with tempfile.TemporaryDirectory() as td:
        sys_, q = build_system(td)
        assert os.path.exists(os.path.join(td, "lti", "topology.bin")), \
            "storage_dir must mirror the LTI as a decoupled layout"
        ref_ids, ref_d = sys_.search_batch(q, k=5)

        # 1+2: depth sweep — bit-parity + the read conservation law.
        # Cache off: disk reads must equal the in-memory engine's n_reads.
        for depth in (0, 1, 2):
            sys_.cfg = dataclasses.replace(
                sys_.cfg, prefetch_depth=depth, adjacency_cache_mb=0)
            sys_.close_storage()              # re-open with the new knobs
            r0, c0 = sys_.stats.io_rows_read, sys_.stats.io_cache_hits
            ids, d = sys_.search_disk(q, k=5)
            np.testing.assert_array_equal(ids, ref_ids)
            np.testing.assert_array_equal(d, ref_d)
            reads = sys_.stats.io_rows_read - r0
            assert sys_.stats.io_cache_hits == c0, "cache off -> no hits"
            if depth == 0:
                reads_ref = reads
            else:
                assert reads == reads_ref, \
                    f"depth={depth}: n_reads must not depend on prefetch"
            print(f"# depth={depth}: bit-identical to in-memory, "
                  f"reads={reads}")

        # 2b: cache on — every requested row is a read XOR a cache hit.
        sys_.cfg = dataclasses.replace(
            sys_.cfg, prefetch_depth=1, adjacency_cache_mb=4)
        sys_.close_storage()
        r0, c0 = sys_.stats.io_rows_read, sys_.stats.io_cache_hits
        ids, d = sys_.search_disk(q, k=5)
        np.testing.assert_array_equal(ids, ref_ids)
        np.testing.assert_array_equal(d, ref_d)
        reads = sys_.stats.io_rows_read - r0
        hits = sys_.stats.io_cache_hits - c0
        assert hits > 0, "a 4MB cache over a tiny layout must hit"
        assert reads + hits == reads_ref, \
            f"conservation: {reads} reads + {hits} hits != {reads_ref}"
        print(f"# cache on: {reads} reads + {hits} hits == {reads_ref}")

        # 4: staging buffers are identity-stable across searches.
        pf = sys_._disk_searcher_get().reader.prefetcher
        a0 = pf.allocations
        b0 = [id(b) for b in pf.staging_buffers()]
        sys_.search_disk(q, k=5)
        assert pf.allocations == a0, "steady state must not reallocate"
        assert [id(b) for b in pf.staging_buffers()] == b0, \
            "staging buffers must keep their identity"
        print(f"# staging buffers stable (allocations={a0})")

        # 3: merge -> in-place delta patch -> post-merge parity.
        sys_.merge()
        assert sys_.stats.storage_rows_patched > 0, \
            "StreamingMerge must delta-patch the layout"
        ref_ids2, ref_d2 = sys_.search_batch(q, k=5)
        ids, d = sys_.search_disk(q, k=5)
        np.testing.assert_array_equal(ids, ref_ids2)
        np.testing.assert_array_equal(d, ref_d2)
        print(f"# post-merge: {sys_.stats.storage_rows_patched} rows "
              f"patched, disk == in-memory")
        sys_.close_storage()
    print("# DISK-PROBE OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
