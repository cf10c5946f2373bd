"""The batched mutation engine (insert / delete-repair / merge prune paths).

Three contracts:
  1. kernel-vs-oracle bit-parity — every mutation caller (build, insert,
     consolidation, StreamingMerge in both distance flavors) produces the
     SAME graph with ``use_kernel=True`` (fused Pallas launches, interpret
     mode on CPU) as with the jnp oracle path;
  2. the Delta append path never duplicates an edge (degree-budget burn
     regression);
  3. alpha-RNG post-conditions: repaired rows satisfy the prune invariant
     (``prune.check_alpha_rng``) after ``consolidate_deletes`` and the
     StreamingMerge delete phase.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import index as mem
from repro.core.config import IndexConfig, PQConfig
from repro.core.delete import consolidate_deletes, delete
from repro.core.distance import INVALID
from repro.core.insert import apply_back_edges
from repro.core.lti import build_lti
from repro.core.merge import MergeStats, streaming_merge
from repro.core.prune import check_alpha_rng

from conftest import DIM


def _cfg(use_kernel, **kw):
    base = dict(capacity=1024, dim=DIM, R=16, L_build=24, L_search=32,
                alpha=1.2, use_kernel=use_kernel)
    base.update(kw)
    return IndexConfig(**base)


def _pq():
    return PQConfig(dim=DIM, m=8, ksub=32, kmeans_iters=4)


# ---------------------------------------------------------------------------
# 1. kernel-path bit-parity through every mutation caller
# ---------------------------------------------------------------------------

def test_build_and_insert_parity(points):
    """index.build (LTI build path) + batched insert: fused prune kernels
    vs the jnp oracle, identical adjacency."""
    g_j = mem.build(points[:300], _cfg(False), batch=64)
    g_k = mem.build(points[:300], _cfg(True), batch=64)
    np.testing.assert_array_equal(np.asarray(g_j.adjacency),
                                  np.asarray(g_k.adjacency))
    slots = jnp.arange(300, 332, dtype=jnp.int32)
    vecs = jnp.asarray(points[300:332])
    i_j = mem.insert(g_j, slots, vecs, _cfg(False))
    i_k = mem.insert(g_k, slots, vecs, _cfg(True))
    np.testing.assert_array_equal(np.asarray(i_j.adjacency),
                                  np.asarray(i_k.adjacency))


def test_consolidate_parity(points):
    g = mem.build(points[:300], _cfg(False), batch=64)
    victims = jnp.arange(0, 300, 9)
    c_j = consolidate_deletes(delete(g, victims), _cfg(False))
    c_k = consolidate_deletes(delete(g, victims), _cfg(True))
    np.testing.assert_array_equal(np.asarray(c_j.adjacency),
                                  np.asarray(c_k.adjacency))
    np.testing.assert_array_equal(np.asarray(c_j.active),
                                  np.asarray(c_k.active))


@pytest.mark.parametrize("use_sdc", [False, True])
def test_streaming_merge_parity(points, use_sdc):
    """All three merge phases (delete repair, insert-phase prune, Delta
    patch) ride the engine: kernel and jnp paths produce the same LTI."""
    lti = build_lti(points[:300], _cfg(False), _pq(), batch=64)
    newv = jnp.asarray(points[300:400])
    valid = jnp.ones((100,), bool)
    dmask = jnp.zeros((1024,), bool).at[jnp.arange(0, 300, 11)].set(True)
    out = {}
    for uk in (False, True):
        cfg = _cfg(uk)
        merged, stats = streaming_merge(lti, newv, valid, dmask, cfg, _pq(),
                                        insert_chunk=32, block=256,
                                        use_sdc=use_sdc)
        out[uk] = (merged, stats)
    np.testing.assert_array_equal(np.asarray(out[False][0].graph.adjacency),
                                  np.asarray(out[True][0].graph.adjacency))
    np.testing.assert_array_equal(np.asarray(out[False][1].slots),
                                  np.asarray(out[True][1].slots))


# ---------------------------------------------------------------------------
# 2. Delta append-path dedupe (degree-budget burn regression)
# ---------------------------------------------------------------------------

def _tiny_graph(n=12, R=4, seed=0):
    rng = np.random.default_rng(seed)
    vecs = jnp.asarray(rng.standard_normal((n, DIM)).astype(np.float32))
    adj = jnp.full((n, R), INVALID, jnp.int32)
    usable = jnp.ones((n,), bool)
    return vecs, adj, usable, R


def test_back_edge_already_present_not_duplicated():
    """A source p already in N_out(j) must leave the row unchanged — the
    old append path burned a degree slot on the duplicate."""
    vecs, adj, usable, R = _tiny_graph()
    adj = adj.at[1, 0].set(2)                     # j=1 already links p=2
    pairs_j = jnp.asarray([1], jnp.int32)
    pairs_p = jnp.asarray([2], jnp.int32)
    for uk in (False, True):
        out = apply_back_edges(adj, vecs, usable, pairs_j, pairs_p,
                               alpha=1.2, R=R, use_kernel=uk)
        np.testing.assert_array_equal(np.asarray(out[1]), [2, -1, -1, -1])


def test_duplicate_pairs_append_once():
    """The same (j, p) pair listed twice appends p exactly once."""
    vecs, adj, usable, R = _tiny_graph()
    adj = adj.at[1, 0].set(3)
    pairs_j = jnp.asarray([1, 1], jnp.int32)
    pairs_p = jnp.asarray([5, 5], jnp.int32)
    for uk in (False, True):
        out = apply_back_edges(adj, vecs, usable, pairs_j, pairs_p,
                               alpha=1.2, R=R, use_kernel=uk)
        row = np.asarray(out[1])
        np.testing.assert_array_equal(np.sort(row[:2]), [3, 5])
        np.testing.assert_array_equal(row[2:], [-1, -1])


def test_dedupe_avoids_spurious_reprune():
    """Duplicates must not inflate the degree-budget test: a row with
    R-1 edges + one duplicate source stays on the append path (the true
    union fits), rather than burning the last slot or re-pruning."""
    vecs, adj, usable, R = _tiny_graph()
    adj = adj.at[1].set(jnp.asarray([2, 4, 6, INVALID], jnp.int32))
    pairs_j = jnp.asarray([1, 1], jnp.int32)
    pairs_p = jnp.asarray([2, 8], jnp.int32)      # 2 is a dup, 8 is new
    out = apply_back_edges(adj, vecs, usable, pairs_j, pairs_p,
                           alpha=1.2, R=R, use_kernel=False)
    np.testing.assert_array_equal(np.sort(np.asarray(out[1])), [2, 4, 6, 8])


# ---------------------------------------------------------------------------
# 3. alpha-RNG post-conditions over the repair passes
# ---------------------------------------------------------------------------

def _alpha_ok_fraction(state, rows_of, table, alpha):
    oks = [bool(check_alpha_rng(state.adjacency[p], table[p], table, alpha))
           for p in rows_of]
    return np.mean(oks) if oks else 1.0


def test_consolidate_rows_satisfy_alpha_rng(points):
    """Every row repaired by Algorithm 4 is a fresh RobustPrune output and
    must satisfy the alpha-RNG invariant against the prune table."""
    cfg = _cfg(False)
    g = mem.build(points[:300], cfg, batch=64)
    victims = jnp.arange(0, 300, 7)
    gd = delete(g, victims)
    safe = jnp.maximum(gd.adjacency, 0)
    had_del = ((gd.adjacency >= 0) & gd.deleted[safe]).any(axis=1)
    repaired = np.nonzero(
        np.asarray(had_del & gd.active & ~gd.deleted))[0][:40]
    out = consolidate_deletes(gd, cfg)
    frac = _alpha_ok_fraction(out, repaired, out.vectors, cfg.alpha)
    assert frac == 1.0


def test_merge_delete_phase_satisfies_alpha_rng(points):
    """A pure-delete StreamingMerge changes rows only through the delete
    phase's RobustPrune — every changed row must satisfy the invariant
    against the PQ-decoded table the prune actually ran on."""
    from repro.core import pq as pqm
    cfg, pq_cfg = _cfg(False), _pq()
    lti = build_lti(points[:300], cfg, pq_cfg, batch=64)
    dmask = jnp.zeros((1024,), bool).at[jnp.arange(0, 300, 6)].set(True)
    none = jnp.zeros((1, DIM), jnp.float32)
    merged, _ = streaming_merge(lti, none, jnp.zeros((1,), bool), dmask,
                                cfg, pq_cfg, insert_chunk=32, block=256)
    decoded = pqm.decode(merged.codebook, merged.codes, pq_cfg)
    changed = np.nonzero(np.asarray(
        (merged.graph.adjacency != lti.graph.adjacency).any(axis=1)
        & merged.graph.active))[0][:40]
    frac = _alpha_ok_fraction(merged.graph, changed, decoded, cfg.alpha)
    assert frac == 1.0


def test_full_merge_improves_alpha_rng_on_decoded_table(points):
    """With staged inserts the Patch phase may legally append (no prune),
    so not every live row satisfies the invariant — but every row the merge
    *does* prune is pruned on decoded-code distances, so the decoded-table
    invariant fraction must not regress vs the pre-merge graph (whose rows
    were built on exact vectors and mostly violate it)."""
    from repro.core import pq as pqm
    cfg, pq_cfg = _cfg(False), _pq()
    lti = build_lti(points[:300], cfg, pq_cfg, batch=64)
    newv = jnp.asarray(points[300:380])
    dmask = jnp.zeros((1024,), bool).at[jnp.arange(0, 300, 9)].set(True)
    pre_decoded = pqm.decode(lti.codebook, lti.codes, pq_cfg)
    pre_live = np.nonzero(np.asarray(lti.graph.active))[0]
    pre = _alpha_ok_fraction(lti.graph, pre_live, pre_decoded, cfg.alpha)
    merged, _ = streaming_merge(lti, newv, jnp.ones((80,), bool), dmask,
                                cfg, pq_cfg, insert_chunk=32, block=256)
    decoded = pqm.decode(merged.codebook, merged.codes, pq_cfg)
    live = np.nonzero(np.asarray(merged.graph.active))[0]
    post = _alpha_ok_fraction(merged.graph, live, decoded, cfg.alpha)
    assert post >= pre, (pre, post)


# ---------------------------------------------------------------------------
# 4. localized delete repair + the delete-path bugfix sweep
# ---------------------------------------------------------------------------

def test_localized_consolidate_bit_parity(points):
    """mode="local" gathers/repairs/scatters only the affected rows and
    must reproduce the global sweep bit-for-bit — whole adjacency, flags,
    and entry point, on both engine paths."""
    from repro.core.delete import affected_mask
    for uk in (False, True):
        cfg = _cfg(uk)
        g = mem.build(points[:300], cfg, batch=64)
        gd = delete(g, jnp.arange(0, 300, 13))
        aff = int(affected_mask(gd.adjacency, gd.deleted,
                                gd.active & ~gd.deleted).sum())
        assert 0 < aff < 300          # genuinely partial coverage
        a = consolidate_deletes(gd, cfg, mode="global")
        b = consolidate_deletes(gd, cfg, mode="local")
        np.testing.assert_array_equal(np.asarray(a.adjacency),
                                      np.asarray(b.adjacency))
        np.testing.assert_array_equal(np.asarray(a.active),
                                      np.asarray(b.active))
        assert int(a.start) == int(b.start)


def test_localized_consolidate_codes_bit_parity(points):
    """SDC flavor of the same contract (capped expansion, PQ distances)."""
    from repro.core import pq as pqm
    from repro.core.delete import consolidate_deletes_codes
    lti = build_lti(points[:300], _cfg(False), _pq(), batch=64)
    tables = pqm.sdc_tables(lti.codebook)
    for uk in (False, True):
        cfg = _cfg(uk)
        gd = delete(lti.graph, jnp.arange(0, 300, 13))
        a = consolidate_deletes_codes(gd, cfg, lti.codes, tables,
                                      block=256, mode="global")
        b = consolidate_deletes_codes(gd, cfg, lti.codes, tables,
                                      block=256, mode="local")
        np.testing.assert_array_equal(np.asarray(a.adjacency),
                                      np.asarray(b.adjacency))
        assert int(a.start) == int(b.start)


def _n_affected(lti, dmask):
    """Rows the merge's Delete phase repairs for the DeleteList ``dmask``."""
    from repro.core.delete import affected_mask
    g = lti.graph
    deleted = g.deleted | (dmask & g.active)
    return int(affected_mask(g.adjacency, deleted,
                             g.active & ~deleted).sum())


def _local_merge_slices(n_aff, block, capacity, n_new, insert_chunk, R,
                        use_sdc):
    """The device programs a local merge runs (core/merge.py's doc): the
    opening one; the affected-set ranges and, in full precision, as many
    decode ranges; one repair and one write per block of affected rows;
    the reclamation; the allocation; one per insert chunk; the grouping;
    one per patch block."""
    from repro.core.delete import RANGE_ROWS
    ranges = -(-capacity // min(RANGE_ROWS, capacity))
    chunks = -(-n_new // insert_chunk)
    targets = min(chunks * insert_chunk * R, capacity)
    return (1 + ranges * (1 if use_sdc else 2) + 2 * -(-n_aff // block)
            + 1 + 1 + chunks + 1 + -(-targets // min(targets, block)))


def _exact_block(n):
    """A block size that ``n`` fills exactly, in two slices or more where
    ``n`` has a divisor."""
    d = next((d for d in range(2, n) if n % d == 0), 1)
    return n // d


# (use_sdc, which deletes, block, rows per range slice): the first two are
# the original cases, whose affected rows leave their one slice partial.
# 1,000-row ranges cover the 1,024 rows in two slices that overlap.
LOCAL_CASES = {
    "False": (False, "some", 256, None), "True": (True, "some", 256, None),
    "False-none": (False, "none", 256, None),
    "True-none": (True, "none", 256, None),
    "False-exact": (False, "some", "exact", None),
    "True-exact": (True, "some", "exact", None),
    "False-partial3": (False, "some", 64, None),
    "True-partial3": (True, "some", 64, None),
    "False-ranges": (False, "some", 64, 1000),
    "True-ranges": (True, "some", 64, 1000),
}


@pytest.mark.parametrize("use_sdc, deletes, block, range_rows",
                         LOCAL_CASES.values(), ids=LOCAL_CASES.keys())
def test_streaming_merge_local_parity(points, monkeypatch, use_sdc, deletes,
                                      block, range_rows):
    """A localized merge (bounded device slices) must be bit-identical to
    the fused global merge — graph, codes, slots and every MergeStats
    count — with no affected rows, a count that fills its last repair
    slice exactly, one that leaves it partial, and range slices that
    overlap; and it runs the expected number of slices, where the fused
    merge runs one program."""
    if range_rows:
        monkeypatch.setattr("repro.core.delete.RANGE_ROWS", range_rows)
    cfg, pq_cfg = _cfg(False), _pq()
    lti = build_lti(points[:300], cfg, pq_cfg, batch=64)
    newv = jnp.asarray(points[300:400])
    valid = jnp.ones((100,), bool)
    dmask = jnp.zeros((1024,), bool)
    if deletes == "some":
        dmask = dmask.at[jnp.arange(0, 300, 11)].set(True)
    n_aff = _n_affected(lti, dmask)
    assert (n_aff > 0) == (deletes == "some")
    if block == "exact":
        block = _exact_block(n_aff)
        assert n_aff % block == 0
    elif n_aff:
        assert n_aff % block != 0
    m_g, s_g = streaming_merge(lti, newv, valid, dmask, cfg, pq_cfg,
                               insert_chunk=32, block=block, use_sdc=use_sdc,
                               repair_mode="global")
    m_l, s_l = streaming_merge(lti, newv, valid, dmask, cfg, pq_cfg,
                               insert_chunk=32, block=block, use_sdc=use_sdc,
                               repair_mode="local")
    for a, b in zip(jax.tree.leaves(m_g), jax.tree.leaves(m_l)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for f in MergeStats._fields[:-1]:
        np.testing.assert_array_equal(np.asarray(getattr(s_g, f)),
                                      np.asarray(getattr(s_l, f)), f)
    assert int(s_g.slices) == 1
    assert s_l.slices == _local_merge_slices(n_aff, block, 1024, 100, 32,
                                             cfg.R, use_sdc)


@pytest.mark.parametrize("use_sdc", [False, True])
def test_local_merge_compiles_its_slices_once(points, use_sdc):
    """Every slice of the local merge has a fixed shape: a second merge
    with another affected-row count (and so another number of repair and
    write slices) adds no entry to any slice program's jit cache."""
    from repro.core import delete as dl
    from repro.core import merge as mg
    cfg, pq_cfg = _cfg(False), _pq()
    lti = build_lti(points[:300], cfg, pq_cfg, batch=64)
    newv = jnp.asarray(points[300:364])
    valid = jnp.ones((64,), bool)
    programs = (mg._open_delete, dl._affected_range, mg._decode_range,
                dl._repair_slice_fp, dl._repair_slice_codes, dl._reclaim,
                dl._write_rows, mg._allocate_slice, mg._insert_slice,
                mg._group_slice, mg._patch_slice)
    counts, sizes = [], []
    for step in (29, 5):
        dmask = jnp.zeros((1024,), bool).at[jnp.arange(0, 300, step)].set(
            True)
        counts.append(_n_affected(lti, dmask))
        _, stats = streaming_merge(lti, newv, valid, dmask, cfg, pq_cfg,
                                   insert_chunk=32, block=32,
                                   use_sdc=use_sdc, repair_mode="local")
        sizes.append([p._cache_size() for p in programs])
    assert -(-counts[0] // 32) < -(-counts[1] // 32)
    assert sizes[0] == sizes[1]


def test_system_merge_counts_its_slices(points):
    """``SystemStats.merge_slices`` adds up the merges' slices: a system's
    local-repair merge runs the count its sizes give."""
    from repro.core.config import SystemConfig
    from repro.core.system import bootstrap_system
    scfg = SystemConfig(index=_cfg(False), pq=_pq(), ro_snapshot_points=32,
                        merge_threshold=10**6, temp_capacity=256,
                        insert_batch=32, merge_block=64,
                        local_repair_threshold=0.5)
    sys_ = bootstrap_system(points[:300], np.arange(300), scfg, batch=64)
    for e in range(0, 300, 13):
        sys_.delete(e)
    for i in range(64):
        sys_.insert(1000 + i, points[300 + i])
    lti = sys_.lti
    dmask = jnp.asarray(np.isin(sys_.lti_ext_ids, list(sys_.deleted_ext))
                        & np.asarray(lti.graph.active))
    staged = sum(t.n for t in sys_.ro)
    n_aff = _n_affected(lti, dmask)
    assert staged > 0 and n_aff > 64
    sys_.merge()
    assert (sys_.stats.merges, sys_.stats.local_repairs) == (1, 1)
    assert sys_.stats.merge_slices == _local_merge_slices(
        n_aff, 64, 1024, staged, 32, scfg.index.R, False)


def test_harness_local_repair_warm_up_runs(points):
    """The benchmark's set-up compiles the localized Delete phase through
    ``bench/harness/window.py::Cell.warm_local_repair``, which calls
    ``delete._repair_blocks_fp`` and ``delete._scatter_repaired`` by
    name: it must keep running on a tiny system."""
    import os
    import sys
    from repro.core.config import SystemConfig
    from repro.core.system import bootstrap_system
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness.window import Cell
    scfg = SystemConfig(index=_cfg(False), pq=_pq(), merge_block=64)
    cell = Cell(dict(k=5, merge_block=64, R=scfg.index.R,
                     alpha=scfg.index.alpha), None, lambda m: None)
    cell.sys = bootstrap_system(points[:300], np.arange(300), scfg)
    cell.merge_deleted = np.arange(0, 300, 7)
    cell.warm_local_repair()


def test_localized_rows_satisfy_alpha_rng(points):
    """Post-condition: every row the localized pass repaired is a fresh
    RobustPrune output and satisfies the alpha-RNG invariant."""
    from repro.core.delete import affected_mask
    from repro.core.prune import check_alpha_rng_rows
    cfg = _cfg(False)
    g = mem.build(points[:300], cfg, batch=64)
    gd = delete(g, jnp.arange(0, 300, 7))
    aff = np.nonzero(np.asarray(affected_mask(
        gd.adjacency, gd.deleted, gd.active & ~gd.deleted)))[0]
    out = consolidate_deletes(gd, cfg, mode="local")
    oks = np.asarray(check_alpha_rng_rows(
        out.adjacency, jnp.asarray(aff.astype(np.int32)), out.vectors,
        cfg.alpha))
    assert oks.all()


def test_affected_mask_covers_changed_rows(points):
    """The rows the global sweep changes are exactly a subset of
    affected-set ∪ deleted — the localized mode's coverage guarantee."""
    from repro.core.delete import affected_mask
    cfg = _cfg(False)
    g = mem.build(points[:300], cfg, batch=64)
    gd = delete(g, jnp.arange(0, 300, 13))
    cover = np.asarray(affected_mask(
        gd.adjacency, gd.deleted, gd.active & ~gd.deleted)) \
        | np.asarray(gd.deleted)
    out = consolidate_deletes(gd, cfg, mode="global")
    changed = np.asarray((out.adjacency != gd.adjacency).any(axis=1))
    assert not (changed & ~cover).any()


def test_policy_a_repicks_inactive_start(points):
    """Regression: an already-inactive (not deleted) start slot must be
    re-picked by Policy A, not survive to seed searches from a dead node."""
    from repro.core.delete import consolidate_policy_a
    cfg = _cfg(False)
    g = mem.build(points[:300], cfg, batch=64)
    g = g._replace(active=g.active.at[g.start].set(False))
    out = consolidate_policy_a(g)
    assert int(out.start) != int(g.start)
    assert bool(out.active[out.start])


def test_delete_everything_then_reinsert(points):
    """Deleting 100%% of the points must leave the sentinel start (no
    garbage medoid of an all-false mask), searches must come back empty,
    and the next insert must re-seed the entry point."""
    cfg = _cfg(False)
    g = mem.build(points[:300], cfg, batch=64)
    gd = delete(g, jnp.arange(300, dtype=jnp.int32))
    for mode in ("global", "local"):
        out = consolidate_deletes(gd, cfg, mode=mode)
        assert int(out.start) == int(INVALID)
        assert not bool(out.active.any())
        ids, _, _, _ = mem.search(out, jnp.asarray(points[:4]), cfg,
                                  k=5, L=32)
        assert (np.asarray(ids) < 0).all()
    # re-insert into the emptied index: start re-seeds to the first slot
    out = consolidate_deletes(gd, cfg, mode="local")
    slots = jnp.arange(16, dtype=jnp.int32)
    st = mem.insert(out, slots, jnp.asarray(points[:16]), cfg)
    assert int(st.start) >= 0 and bool(st.active[st.start])
    ids, _, _, _ = mem.search(st, jnp.asarray(points[:4]), cfg, k=3, L=32)
    assert (np.asarray(ids)[:, 0] >= 0).all()


def test_merge_delete_everything_then_reinsert(points):
    """The merge Delete phase hitting 100%% of the LTI must hand phases
    2/3 the sentinel start, which then re-seeds from the first inserted
    slot — the merged LTI serves its new points."""
    cfg, pq_cfg = _cfg(False), _pq()
    lti = build_lti(points[:300], cfg, pq_cfg, batch=64)
    dmask = jnp.zeros((1024,), bool).at[jnp.arange(300)].set(True)
    newv = jnp.asarray(points[300:364])
    valid = jnp.ones((64,), bool)
    for mode in ("global", "local"):
        merged, stats = streaming_merge(lti, newv, valid, dmask, cfg,
                                        pq_cfg, insert_chunk=32, block=256,
                                        repair_mode=mode)
        g = merged.graph
        assert int(stats.n_deleted) == 300
        assert int(stats.n_inserted) == 64
        assert int(g.start) >= 0 and bool(g.active[g.start])
        assert int(g.active.sum()) == 64


def test_repair_cap_overflow_counter(points, monkeypatch):
    """A node with more deleted out-neighbors than the SDC expansion cap
    must fire the overflow counter, and its repaired row must still shed
    every deleted edge (the keep-mask is uncapped)."""
    from repro.core import pq as pqm
    from repro.core.delete import (consolidate_deletes_codes,
                                   repair_cap_overflow)
    from repro.core.merge import SDC_REPAIR_CAP
    cfg = _cfg(False)
    lti = build_lti(points[:300], cfg, _pq(), batch=64)
    g = lti.graph
    # delete SDC_REPAIR_CAP+2 of one node's out-neighbors
    p = int(jnp.argmax((g.adjacency >= 0).sum(axis=1)))
    row = np.asarray(g.adjacency[p])
    victims = row[row >= 0][:SDC_REPAIR_CAP + 2].astype(np.int32)
    assert len(victims) == SDC_REPAIR_CAP + 2
    gd = delete(g, jnp.asarray(victims))
    usable = gd.active & ~gd.deleted
    n_over = int(repair_cap_overflow(gd.adjacency, gd.deleted, usable,
                                     SDC_REPAIR_CAP))
    assert n_over >= 1
    tables = pqm.sdc_tables(lti.codebook)
    out = consolidate_deletes_codes(gd, cfg, lti.codes, tables,
                                    block=256, cap=SDC_REPAIR_CAP)
    new_row = np.asarray(out.adjacency[p])
    assert not np.isin(new_row[new_row >= 0], victims).any()
    # ... and a pure-delete SDC merge surfaces the count in MergeStats
    dmask = jnp.zeros((1024,), bool).at[jnp.asarray(victims)].set(True)
    none = jnp.zeros((1, DIM), jnp.float32)
    _, stats = streaming_merge(lti, none, jnp.zeros((1,), bool), dmask,
                               cfg, _pq(), insert_chunk=32, block=256,
                               use_sdc=True)
    assert int(stats.repair_cap_overflows) == n_over
    # ... and so does a local one whose range slices overlap (1,000-row
    # slices over 1,024 rows: the second starts at row 24), for a node
    # both slices cover: it counts once.
    monkeypatch.setattr("repro.core.delete.RANGE_ROWS", 1000)
    deg = np.asarray((g.adjacency >= 0).sum(axis=1))
    p = 24 + int(np.argmax(deg[24:]))
    row = np.asarray(g.adjacency[p])
    victims = row[row >= 0][:SDC_REPAIR_CAP + 2].astype(np.int32)
    gd = delete(g, jnp.asarray(victims))
    n_over = int(repair_cap_overflow(gd.adjacency, gd.deleted,
                                     gd.active & ~gd.deleted,
                                     SDC_REPAIR_CAP))
    assert n_over >= 1
    dmask = jnp.zeros((1024,), bool).at[jnp.asarray(victims)].set(True)
    _, stats = streaming_merge(lti, none, jnp.zeros((1,), bool), dmask,
                               cfg, _pq(), insert_chunk=32, block=256,
                               use_sdc=True, repair_mode="local")
    assert int(stats.repair_cap_overflows) == n_over
