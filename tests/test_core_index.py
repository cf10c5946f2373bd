"""FreshVamana core: build quality, insert/delete correctness, counters."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import IndexConfig
from repro.core.delete import consolidate_deletes, delete
from repro.core.graph import degree_stats
from repro.core.index import brute_force, build, insert, recall_at_k, search
from repro.core.prune import check_alpha_rng

from conftest import DIM, N


def _recall(state, cfg, queries, k=5, L=None):
    ids, d, hops, cmps = search(state, jnp.asarray(queries), cfg,
                                k=k, L=L or cfg.L_search)
    mask = state.active & ~state.deleted
    gt = brute_force(state.vectors, mask, jnp.asarray(queries), k)
    return float(recall_at_k(ids, gt)), hops, cmps


@pytest.mark.parametrize("chunk", [7, 100, 512])
def test_brute_force_chunked_matches_one_topk(points, queries, chunk):
    """The chunked exact reference equals one top_k over every row, ties
    (duplicate vectors) and masked rows included."""
    import jax
    from repro.core.distance import l2_sq_batch
    x = jnp.asarray(points).at[700].set(jnp.asarray(points[3]))
    q = jnp.asarray(queries).at[0].set(jnp.asarray(points[3]))
    mask = jnp.asarray(np.arange(len(points)) % 5 != 1)
    d = jnp.where(mask[None, :], l2_sq_batch(q, x), jnp.inf)
    want = jax.lax.top_k(-d, 5)[1]
    got = brute_force(x, mask, q, 5, chunk=chunk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert {3, 700} <= set(np.asarray(got[0]).tolist())


def _exact_subspace_d2(x, cent):
    """[N, m, ksub] squared distance of every subvector to every centroid,
    in float64 straight from the differences."""
    m, ksub, dsub = cent.shape
    xs = x.astype(np.float64).reshape(len(x), m, 1, dsub)
    return ((xs - cent.astype(np.float64)[None]) ** 2).sum(-1)


def test_pq_encode_picks_the_nearest_centroid():
    """Every code is the nearest centroid of its subvector (within f32
    rounding of near-ties)."""
    from repro.core import pq as pqm
    from repro.core.config import PQConfig
    cfg = PQConfig(dim=32, m=8, ksub=16)
    n = 3000
    rng = np.random.default_rng(n)
    cent = rng.normal(size=(8, 16, 4)).astype(np.float32)
    x = rng.normal(size=(n, 32)).astype(np.float32)
    codes = np.asarray(pqm.encode(pqm.PQCodebook(jnp.asarray(cent)),
                                  jnp.asarray(x), cfg)).astype(np.int64)
    assert codes.shape == (n, 8)
    d2 = _exact_subspace_d2(x, cent)
    got = np.take_along_axis(d2, codes[..., None], axis=-1)[..., 0]
    best = d2.min(-1)
    np.testing.assert_allclose(got, best, rtol=1e-5, atol=1e-5)
    assert np.mean(codes == d2.argmin(-1)) > 0.999


def test_pq_train_step_is_the_mean_of_assigned_points():
    """One more Lloyd step moves each used centroid to the mean of the
    subvectors assigned to it by the previous codebook; unused centroids
    stay put."""
    import dataclasses
    from repro.core import pq as pqm
    from repro.core.config import PQConfig
    cfg1 = PQConfig(dim=32, m=8, ksub=16, kmeans_iters=1)
    cfg2 = dataclasses.replace(cfg1, kmeans_iters=2)
    x = np.random.default_rng(5).normal(size=(600, 32)).astype(np.float32)
    c1 = np.asarray(pqm.train_pq(jnp.asarray(x), cfg1).centroids)
    c2 = np.asarray(pqm.train_pq(jnp.asarray(x), cfg2).centroids)
    codes = _exact_subspace_d2(x, c1).argmin(-1)          # [N, m]
    xs = x.reshape(600, 8, 4)
    for a in range(8):
        for k in range(16):
            rows = xs[codes[:, a] == k, a]
            want = rows.mean(0) if len(rows) else c1[a, k]
            np.testing.assert_allclose(c2[a, k], want, rtol=1e-5,
                                       atol=1e-5)


def test_build_recall(built_index, index_cfg, queries):
    rec, hops, cmps = _recall(built_index, index_cfg, queries)
    assert rec >= 0.90, f"build recall too low: {rec}"


def test_search_counters_bounded(built_index, index_cfg, queries):
    _, hops, cmps = _recall(built_index, index_cfg, queries)
    # paper §6.2: IO (hops) is about the candidate-list size, not O(N)
    assert float(hops.mean()) < 2.5 * index_cfg.L_search
    assert float(cmps.mean()) < N  # tiny fraction of brute force


def test_degrees_bounded(built_index, index_cfg):
    st = degree_stats(built_index)
    assert float(st["max_degree"]) <= index_cfg.R
    assert float(st["avg_degree"]) > 4


def test_alpha_rng_property_after_prune(built_index, index_cfg):
    """Rows satisfy the alpha-RNG invariant immediately after RobustPrune.

    (Raw graph rows may legitimately violate it: Algorithm 2 APPENDS
    back-edges without pruning while the degree budget allows — only
    pruned rows carry the invariant, which is what we check here.)
    """
    from repro.core.prune import prune_node
    vecs = built_index.vectors
    usable = built_index.active & ~built_index.deleted
    for p in range(0, N, 97):
        row = built_index.adjacency[p]
        res = prune_node(vecs, jnp.int32(p), row, usable,
                         index_cfg.alpha, index_cfg.R)
        assert bool(check_alpha_rng(res.ids, vecs[p], vecs,
                                    index_cfg.alpha)), p


def test_insert_new_points_searchable(built_index, index_cfg, points, rng):
    new = (points[:32] + 0.01).astype(np.float32)
    slots = jnp.arange(N, N + 32, dtype=jnp.int32)
    st = insert(built_index, slots, jnp.asarray(new), index_cfg)
    ids, d, _, _ = search(st, jnp.asarray(new), index_cfg, k=1, L=48)
    found = np.asarray(ids[:, 0])
    # the nearest neighbor of an inserted point should be itself (or its
    # near-duplicate source point)
    ok = (found == np.arange(N, N + 32)) | (found == np.arange(32))
    assert ok.mean() >= 0.9


def test_lazy_delete_filters_results(built_index, index_cfg, points):
    q = points[:8]
    ids0, *_ = search(built_index, jnp.asarray(q), index_cfg, k=1, L=48)
    victims = ids0[:, 0]
    st = delete(built_index, victims)
    ids1, *_ = search(st, jnp.asarray(q), index_cfg, k=5, L=48)
    assert not bool((ids1 == victims[:, None]).any())


def test_consolidate_removes_edges_and_reclaims(built_index, index_cfg, rng):
    victims = jnp.asarray(rng.choice(N, 100, replace=False).astype(np.int32))
    st = delete(built_index, victims)
    st = consolidate_deletes(st, index_cfg, block=256)
    adj = np.asarray(st.adjacency)
    vic = np.asarray(victims)
    live_rows = adj[np.setdiff1d(np.arange(N), vic)]
    assert not np.isin(live_rows[live_rows >= 0], vic).any()
    assert not bool(st.active[victims].any())
    assert not bool(st.deleted.any())


def test_consolidated_recall_holds(built_index, index_cfg, queries, rng):
    victims = jnp.asarray(rng.choice(N, 120, replace=False).astype(np.int32))
    st = consolidate_deletes(delete(built_index, victims), index_cfg)
    rec, *_ = _recall(st, index_cfg, queries)
    assert rec >= 0.88, rec


def test_masked_insert_lanes_noop(built_index, index_cfg, points):
    slots = jnp.asarray([N, -1, N + 1, -1], dtype=jnp.int32)
    st = insert(built_index, slots, jnp.asarray(points[:4]), index_cfg)
    assert int(st.active.sum()) == N + 2
