"""Beam-width search engine: W=1 parity against the legacy single-expansion
engine, recall-vs-beamwidth monotonicity, and kernel-vs-reference equality of
the batched distance path (``use_kernel`` on/off through ``kernels.ops``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pq as pqm
from repro.core.config import IndexConfig, PQConfig
from repro.core.distance import INVALID, gather_l2
from repro.core.index import brute_force, build, recall_at_k, search
from repro.core.lti import build_lti, search_lti
from repro.core.search import (FullPrecisionBackend, PQBackend,
                               batch_distances, beam_search)

from conftest import DIM, N


# --------------------------------------------------------------------------
# Legacy engine (the pre-beam implementation, verbatim): expands exactly one
# node per while-loop iteration.  Kept here as the W=1 parity oracle.
# --------------------------------------------------------------------------
def _legacy_search_one(adjacency, navigable, start, dist_fn, L, max_visits):
    R = adjacency.shape[1]
    cand_ids = jnp.full((L,), INVALID, jnp.int32).at[0].set(
        start.astype(jnp.int32))
    d0 = dist_fn(cand_ids[:1])[0]
    cand_d = jnp.full((L,), jnp.inf, jnp.float32).at[0].set(d0)
    cand_exp = jnp.zeros((L,), bool)
    vis_ids = jnp.full((max_visits,), INVALID, jnp.int32)
    vis_d = jnp.full((max_visits,), jnp.inf, jnp.float32)
    state = (cand_ids, cand_d, cand_exp, vis_ids, vis_d,
             jnp.int32(0), jnp.int32(0))

    def cond(s):
        cand_ids, cand_d, cand_exp, _, _, vis_cnt, _ = s
        open_ = (cand_ids >= 0) & ~cand_exp & jnp.isfinite(cand_d)
        return jnp.any(open_) & (vis_cnt < max_visits)

    def body(s):
        cand_ids, cand_d, cand_exp, vis_ids, vis_d, vis_cnt, n_cmps = s
        open_ = (cand_ids >= 0) & ~cand_exp
        sel = jnp.argmin(jnp.where(open_, cand_d, jnp.inf))
        p = cand_ids[sel]
        cand_exp = cand_exp.at[sel].set(True)
        vis_ids = vis_ids.at[vis_cnt].set(p)
        vis_d = vis_d.at[vis_cnt].set(cand_d[sel])
        vis_cnt = vis_cnt + 1
        nbrs = adjacency[jnp.maximum(p, 0)]
        ok = (nbrs >= 0) & navigable[jnp.maximum(nbrs, 0)]
        in_list = (nbrs[:, None] == cand_ids[None, :]).any(axis=1)
        in_vis = (nbrs[:, None] == vis_ids[None, :]).any(axis=1)
        new = ok & ~in_list & ~in_vis
        nd = dist_fn(jnp.where(new, nbrs, INVALID))
        n_cmps = n_cmps + new.sum(dtype=jnp.int32)
        all_ids = jnp.concatenate([cand_ids, jnp.where(new, nbrs, INVALID)])
        all_d = jnp.concatenate([cand_d, nd])
        all_exp = jnp.concatenate([cand_exp, jnp.zeros((R,), bool)])
        order = jnp.argsort(all_d)[:L]
        return (all_ids[order], all_d[order], all_exp[order],
                vis_ids, vis_d, vis_cnt, n_cmps)

    cand_ids, cand_d, _, vis_ids, vis_d, vis_cnt, n_cmps = (
        jax.lax.while_loop(cond, body, state))
    return cand_ids, cand_d, vis_ids, vis_d, vis_cnt, n_cmps


def _legacy_search(adjacency, navigable, start, queries, vectors, L,
                   max_visits):
    def one(q):
        return _legacy_search_one(
            adjacency, navigable, start,
            lambda ids: gather_l2(q, vectors, ids), L, max_visits)

    return jax.vmap(one)(queries)


def test_w1_parity_with_legacy_engine(built_index, index_cfg, queries):
    """beam_width=1 + reference path reproduces the old engine bit-for-bit."""
    st = built_index
    L = index_cfg.L_search
    mv = index_cfg.visits_bound(L)
    q = jnp.asarray(queries)
    old_ids, old_d, old_vis, old_vis_d, old_cnt, old_cmps = _legacy_search(
        st.adjacency, st.active, st.start, q, st.vectors, L, mv)
    res = beam_search(st.adjacency, st.active, st.start, q,
                      FullPrecisionBackend(st.vectors),
                      L=L, max_visits=mv, beam_width=1, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(old_ids), np.asarray(res.ids))
    np.testing.assert_array_equal(np.asarray(old_d), np.asarray(res.dists))
    np.testing.assert_array_equal(np.asarray(old_vis),
                                  np.asarray(res.visited))
    np.testing.assert_array_equal(np.asarray(old_cnt),
                                  np.asarray(res.n_reads))
    np.testing.assert_array_equal(np.asarray(old_cnt),
                                  np.asarray(res.n_hops))  # 1 read per round
    np.testing.assert_array_equal(np.asarray(old_cmps),
                                  np.asarray(res.n_cmps))


def test_recall_monotone_and_hops_drop_with_beam(built_index, index_cfg,
                                                 queries):
    """W in {1, 2, 4}: recall holds within 1% while IO rounds drop >= 2x."""
    st = built_index
    mask = st.active & ~st.deleted
    gt = brute_force(st.vectors, mask, jnp.asarray(queries), 5)
    recalls, hops, reads = {}, {}, {}
    for W in (1, 2, 4):
        ids, d, h, _ = search(st, jnp.asarray(queries), index_cfg, k=5,
                              L=index_cfg.L_search, beam_width=W)
        recalls[W] = float(recall_at_k(ids, gt))
        hops[W] = float(h.mean())
    for W in (2, 4):
        assert recalls[W] >= recalls[1] - 0.01, (W, recalls)
        assert hops[W] < hops[W // 2], (W, hops)
    assert hops[4] <= hops[1] / 2.0, hops
    assert recalls[1] >= 0.9, recalls


def test_beam_lti_hops_drop(points, index_cfg, pq_cfg, queries):
    """The acceptance config: PQ-navigated search_lti, W=4 vs W=1."""
    lti = build_lti(points, index_cfg, pq_cfg, batch=128)
    out = {}
    for W in (1, 4):
        ids, d, h, _ = search_lti(lti, jnp.asarray(queries), index_cfg,
                                  k=5, L=index_cfg.L_search, beam_width=W)
        mask = lti.graph.active & ~lti.graph.deleted
        gt = brute_force(lti.graph.vectors, mask, jnp.asarray(queries), 5)
        out[W] = (float(recall_at_k(ids, gt)), float(h.mean()))
    assert out[4][1] <= out[1][1] / 2.0, out
    assert out[4][0] >= out[1][0] - 0.01, out


def test_backend_kernel_matches_reference(built_index, rng):
    """The batched distance path: kernels.ops vs jnp reference, both backends."""
    st = built_index
    B, K = 8, 96
    qs = jnp.asarray(rng.standard_normal((B, DIM)).astype(np.float32))
    ids = rng.integers(0, N, (B, K)).astype(np.int32)
    ids[:, -7:] = INVALID                     # masked lanes -> +inf
    ids = jnp.asarray(ids)

    fp = FullPrecisionBackend(st.vectors)
    d_ref = batch_distances(fp, qs, ids, use_kernel=False)
    d_ker = batch_distances(fp, qs, ids, use_kernel=True)
    assert bool(jnp.isinf(d_ref[:, -7:]).all())
    np.testing.assert_allclose(np.asarray(d_ker), np.asarray(d_ref),
                               rtol=1e-4, atol=1e-3)

    pq_cfg = PQConfig(dim=DIM, m=8, ksub=32, kmeans_iters=4)
    cb = pqm.train_pq(st.vectors[:512], pq_cfg)
    codes = pqm.encode(cb, st.vectors, pq_cfg)
    pq = PQBackend(codes, cb)
    d_ref = batch_distances(pq, qs, ids, use_kernel=False)
    d_ker = batch_distances(pq, qs, ids, use_kernel=True)
    assert bool(jnp.isinf(d_ref[:, -7:]).all())
    np.testing.assert_allclose(np.asarray(d_ker), np.asarray(d_ref),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("W", [1, 4])
def test_end_to_end_kernel_path(built_index, index_cfg, queries, W):
    """Full search through the Pallas ops layer (interpret mode): same
    candidates as the reference path up to distance-tie reordering."""
    st = built_index
    q = jnp.asarray(queries[:8])
    L = 32
    mv = index_cfg.visits_bound(L)
    ref = beam_search(st.adjacency, st.active, st.start, q,
                      FullPrecisionBackend(st.vectors),
                      L=L, max_visits=mv, beam_width=W, use_kernel=False)
    ker = beam_search(st.adjacency, st.active, st.start, q,
                      FullPrecisionBackend(st.vectors),
                      L=L, max_visits=mv, beam_width=W, use_kernel=True)
    np.testing.assert_allclose(np.asarray(ker.dists), np.asarray(ref.dists),
                               rtol=1e-3, atol=1e-2)
    overlap = (np.asarray(ker.ids)[:, :, None]
               == np.asarray(ref.ids)[:, None, :]).any(axis=2).mean()
    assert overlap >= 0.95, overlap
