#!/usr/bin/env python
"""Locality-aware update batching probe (``scripts/smoke.sh --locality``).

Builds two small live FreshDiskANN systems that differ only in
``SystemConfig.locality_order`` and drives them through the same clustered
insert/delete/merge stream, asserting the contracts of
docs/ARCHITECTURE.md, "Update-path locality", end to end:

  1. determinism — ``locality_order`` is a permutation and bit-stable for a
     fixed (batch, seed), and a SECOND locality system driven through the
     identical op stream lands a bit-identical LTI adjacency (the
     proximity schedule is seeded, never clock- or thread-dependent);
  2. work reduction — the locality system's flush + merge Delta prunes
     launch strictly fewer rows than the arrival-order worst case, with
     the distinct-target counters accumulating on both systems;
  3. storage — with ``storage_dir`` set, merges patch the delta only:
     rows patched stay well below a full rewrite, the 4KB block counter
     tracks the row counter, and the locality system does not patch more
     rows than the arrival-order system (same logical update stream);
  4. recall equivalence — after the full stream, both systems serve the
     same clustered queries with recall within a small tolerance of each
     other (topology differs; quality must not).

Exits non-zero on the first violated contract.  The same invariants run
as tier-1 tests in ``tests/test_locality.py``; this probe is the
CI-visible end-to-end pass, mirroring disk_probe.py /
local_repair_probe.py.
"""
import os
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"   # a CPU probe: never take the chip
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import numpy as np                                    # noqa: E402
import jax.numpy as jnp                               # noqa: E402

from repro.core.config import (IndexConfig, PQConfig,  # noqa: E402
                               SystemConfig)
from repro.core.locality import locality_order        # noqa: E402
from repro.core.system import bootstrap_system        # noqa: E402

DIM = 24
N_CENTERS = 16


def make_points(rng, n, spread=0.25):
    centers = rng.standard_normal((N_CENTERS, DIM)) * 4.0
    which = rng.integers(0, N_CENTERS, n)
    return (centers[which] + spread * rng.standard_normal((n, DIM))
            ).astype(np.float32)


def build_system(locality, storage_dir):
    rng = np.random.default_rng(0)
    pts = make_points(rng, 900)
    cfg = SystemConfig(
        index=IndexConfig(capacity=2048, dim=DIM, R=24, L_build=32,
                          L_search=64, alpha=1.2),
        pq=PQConfig(dim=DIM, m=8, ksub=32, kmeans_iters=4),
        ro_snapshot_points=64, merge_threshold=100_000,
        temp_capacity=256, insert_batch=32,
        locality_order=locality, storage_dir=storage_dir)
    sys_ = bootstrap_system(pts[:500], np.arange(500), cfg)
    return sys_, pts, make_points(np.random.default_rng(5), 32)


def drive(sys_, pts, n_rounds=3):
    """Clustered inserts + deletes + explicit merges, identical stream."""
    for r in range(n_rounds):
        for i in range(48):
            sys_.insert(2000 + 100 * r + i, pts[500 + 48 * r + i])
        for e in range(12 * r, 12 * r + 10):          # bootstrap residents
            sys_.delete(e)
        sys_.merge()


def live_recall(sys_, pts, queries, k=10):
    ids, _ = sys_.search(queries, k=k)
    ids = np.asarray(ids)
    ext = {}
    for e in range(500):
        if e not in sys_.deleted_ext:
            ext[e] = pts[e]
    for r in range(3):
        for i in range(48):
            ext[2000 + 100 * r + i] = pts[500 + 48 * r + i]
    keys = np.asarray(sorted(ext))
    mat = np.stack([ext[kk] for kk in keys])
    hits = 0
    for qi, q in enumerate(queries):
        d = ((mat - q) ** 2).sum(1)
        gt = set(keys[np.argsort(d)[:k]].tolist())
        hits += len(gt & set(ids[qi].tolist()))
    return hits / (k * len(queries))


def main() -> int:
    # 1a. the ordering primitive: permutation + bit-determinism.
    rng = np.random.default_rng(9)
    batch = jnp.asarray(make_points(rng, 128))
    p1 = np.asarray(locality_order(batch, seed=4))
    p2 = np.asarray(locality_order(batch, seed=4))
    np.testing.assert_array_equal(p1, p2)
    np.testing.assert_array_equal(np.sort(p1), np.arange(128))
    print("# ordering ok: seeded permutation, bit-stable")

    with tempfile.TemporaryDirectory() as td:
        sys_off, pts, queries = build_system(False, os.path.join(td, "off"))
        sys_on, _, _ = build_system(True, os.path.join(td, "on"))
        sys_on2, _, _ = build_system(True, os.path.join(td, "on2"))
        for s in (sys_off, sys_on, sys_on2):
            drive(s, pts)

        # 1b. run-twice bit-determinism of the whole locality update path.
        np.testing.assert_array_equal(
            np.asarray(sys_on.lti.graph.adjacency),
            np.asarray(sys_on2.lti.graph.adjacency))
        print("# determinism ok: locality LTI bit-identical across runs")

        # 2. bucketed launches strictly beat the arrival-order worst case.
        st_on, st_off = sys_on.stats, sys_off.stats
        assert st_on.flushes == st_off.flushes >= 3
        assert st_on.merges == st_off.merges == 3
        for s in (st_on, st_off):
            assert s.flush_backedge_targets > 0
            assert s.merge_backedge_targets > 0
        assert st_on.flush_prune_rows < st_off.flush_prune_rows, (
            st_on.flush_prune_rows, st_off.flush_prune_rows)
        assert st_on.merge_prune_rows < st_off.merge_prune_rows, (
            st_on.merge_prune_rows, st_off.merge_prune_rows)
        print(f"# prune-work ok: flush rows {st_off.flush_prune_rows}->"
              f"{st_on.flush_prune_rows}, merge rows "
              f"{st_off.merge_prune_rows}->{st_on.merge_prune_rows}")

        # 3. storage deltas: patches stay partial, block counter coheres,
        # and locality does not inflate the patched footprint.
        for s in (st_on, st_off):
            assert s.storage_rows_patched > 0
            assert s.storage_blocks_patched > 0
            assert s.storage_blocks_patched <= s.storage_rows_patched
            assert s.storage_rows_patched < 3 * 2048   # never full rewrites
        assert st_on.storage_rows_patched <= int(
            1.15 * st_off.storage_rows_patched), (
            st_on.storage_rows_patched, st_off.storage_rows_patched)
        print(f"# storage ok: rows patched off={st_off.storage_rows_patched} "
              f"on={st_on.storage_rows_patched}, blocks "
              f"off={st_off.storage_blocks_patched} "
              f"on={st_on.storage_blocks_patched}")

        # 4. recall equivalence on the served surface.
        r_off = live_recall(sys_off, pts, queries)
        r_on = live_recall(sys_on, pts, queries)
        assert r_on >= r_off - 0.05, (r_off, r_on)
        print(f"# recall ok: off={r_off:.3f} on={r_on:.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
