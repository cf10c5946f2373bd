"""Per-kernel shape/dtype sweeps: Pallas (interpret mode on CPU) vs the
pure-jnp oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

rng = np.random.default_rng(0)


@pytest.mark.parametrize("n,m,ksub,q", [
    (64, 8, 16, 1), (200, 8, 64, 5), (128, 16, 256, 3),
    (1000, 32, 256, 2), (37, 4, 16, 9),
])
def test_adc_matches_ref(n, m, ksub, q):
    codes = jnp.asarray(rng.integers(0, ksub, (n, m)).astype(np.uint8))
    luts = jnp.asarray(
        rng.standard_normal((q, m, ksub)).astype(np.float32)) ** 2
    got = ops.adc_distances(codes, luts)
    want = jax.vmap(lambda t: ref.adc_distances_ref(codes, t))(luts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("q,n,d", [
    (1, 128, 32), (37, 190, 48), (128, 256, 128), (5, 1000, 17),
    (64, 64, 256),
])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_l2_matches_ref(q, n, d, dtype):
    qq = jnp.asarray(rng.standard_normal((q, d)).astype(dtype))
    xx = jnp.asarray(rng.standard_normal((n, d)).astype(dtype))
    got = ops.l2_distances(qq, xx)
    want = ref.l2_distances_ref(qq, xx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("q,n,k", [
    (1, 300, 10), (7, 300, 10), (8, 512, 1), (3, 1024, 64), (9, 77, 5),
])
def test_topk_matches_ref(q, n, k):
    d = jnp.asarray(rng.standard_normal((q, n)).astype(np.float32))
    ids = jnp.arange(n, dtype=jnp.int32)
    gd, gi = ops.block_topk(d, ids, k)
    wd, wi = ref.block_topk_ref(d, ids, k)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd), atol=1e-6)
    assert (np.asarray(gi) == np.asarray(wi)).all()


def test_topk_with_inf_padding():
    d = jnp.asarray([[1.0, jnp.inf, 0.5, jnp.inf, 2.0]])
    ids = jnp.asarray([10, 11, 12, 13, 14], jnp.int32)
    gd, gi = ops.block_topk(d, ids, 4)
    assert list(np.asarray(gi[0])[:3]) == [12, 10, 14]
    assert np.asarray(gi[0])[3] == -1   # inf -> id -1


def _frontier_case(seed, L, K, V, W, nvis_frac=0.5):
    """A random but engine-consistent frontier_select input: sorted candidate
    list with an INVALID tail, fresh neighbors with masked lanes, a visited
    set that is a subset of the candidate ids, vis_cnt == occupancy."""
    r = np.random.default_rng(seed)
    ncand = int(r.integers(1, L + 1))
    nnew = int(r.integers(0, K + 1))
    pool = r.permutation(10_000)[:ncand + nnew].astype(np.int32)
    cand_ids = np.full(L, -1, np.int32)
    cand_d = np.full(L, np.inf, np.float32)
    cand_ids[:ncand] = pool[:ncand]
    cand_d[:ncand] = np.sort(r.random(ncand).astype(np.float32))
    new_ids = np.full(K, -1, np.int32)
    new_d = np.full(K, np.inf, np.float32)
    new_ids[:nnew] = pool[ncand:]
    new_d[:nnew] = r.random(nnew).astype(np.float32)
    vis_ids = np.full(V, -1, np.int32)
    vis_d = np.full(V, np.inf, np.float32)
    nvis = min(int(ncand * nvis_frac), V - 1)
    taken = r.permutation(ncand)[:nvis]
    vis_ids[:nvis] = cand_ids[taken]
    vis_d[:nvis] = cand_d[taken]
    args = tuple(jnp.asarray(x) for x in
                 (cand_ids, cand_d, new_ids, new_d, vis_ids, vis_d))
    return args + (jnp.int32(nvis),)


@pytest.mark.parametrize("W", [1, 4, 16])       # 16 == L: full-width beam
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_frontier_select_matches_ref(seed, W):
    """Fused kernel vs jnp reference: bit-identical merged list, frontier,
    and visited arrays — including INVALID-padded candidate/neighbor lanes."""
    L, K, V = 16, 24, 30
    args = _frontier_case(seed, L, K, V, W)
    want = ops.frontier_select(*args, W=W, max_visits=V, use_kernel=False)
    got = ops.frontier_select(*args, W=W, max_visits=V, use_kernel=True)
    names = ["m_ids", "m_d", "f_ids", "f_d", "vis_ids", "vis_d", "vis_cnt"]
    for w, g, name in zip(want, got, names):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g),
                                      err_msg=f"{name} (W={W}, seed={seed})")


def test_frontier_select_visit_budget():
    """The frontier never exceeds the remaining visit budget, and a full
    visited set yields an empty frontier (the loop's stop condition)."""
    L, K, V, W = 8, 8, 6, 4
    args = _frontier_case(7, L, K, V, W, nvis_frac=0.0)
    # Exhaust the budget: visited occupancy == max_visits.
    full_vis = jnp.asarray(np.arange(20_000, 20_000 + V, dtype=np.int32))
    full_vd = jnp.zeros((V,), jnp.float32)
    for use_kernel in (False, True):
        out = ops.frontier_select(args[0], args[1], args[2], args[3],
                                  full_vis, full_vd, jnp.int32(V),
                                  W=W, max_visits=V, use_kernel=use_kernel)
        assert (np.asarray(out[2]) == -1).all()      # empty frontier
        assert int(out[6]) == V                      # count unchanged


def test_frontier_select_under_vmap():
    """The engine calls frontier_select inside jax.vmap over query lanes."""
    L, K, V, W = 12, 16, 20, 3
    batched = [jnp.stack(x) for x in zip(*[
        _frontier_case(100 + i, L, K, V, W) for i in range(5)])]

    def run(use_kernel):
        return jax.vmap(lambda *a: ops.frontier_select(
            *a, W=W, max_visits=V, use_kernel=use_kernel))(*batched)

    for w, g in zip(run(False), run(True)):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def _prune_case(seed, C, d, with_dups=False):
    """A random prune-engine input: candidate ids (optionally duplicated),
    usability mask, anchor distances from a real anchor vector."""
    r = np.random.default_rng(seed)
    vecs = r.standard_normal((C, d)).astype(np.float32)
    ids = r.permutation(10_000)[:C].astype(np.int32)
    if with_dups:
        ids[C // 2:] = ids[:C - C // 2]
    ids[r.random(C) < 0.1] = -1
    ok = (ids >= 0) & (r.random(C) > 0.25)
    anchor = r.standard_normal(d).astype(np.float32)
    diff = anchor[None] - vecs
    d_p = (diff * diff).sum(-1)
    return (jnp.asarray(d_p), jnp.asarray(vecs), jnp.asarray(ids),
            jnp.asarray(ok))


@pytest.mark.parametrize("alpha", [1.0, 1.2])
@pytest.mark.parametrize("seed,C,d,R", [
    (0, 40, 16, 8), (1, 130, 24, 12), (2, 7, 8, 16), (3, 260, 32, 4),
])
def test_robust_prune_fp_matches_ref(seed, C, d, R, alpha):
    """Fused prune kernel vs the jnp contract: bit-identical selected ids
    and counts, including INVALID lanes, masked lanes, and duplicates."""
    args = [jnp.stack(x) for x in zip(
        _prune_case(seed, C, d, with_dups=seed % 2 == 1),
        _prune_case(seed + 100, C, d))]
    w_ids, w_cnt = ops.robust_prune_fp(*args, alpha=alpha, R=R,
                                       use_kernel=False)
    g_ids, g_cnt = ops.robust_prune_fp(*args, alpha=alpha, R=R,
                                       use_kernel=True)
    np.testing.assert_array_equal(np.asarray(w_ids), np.asarray(g_ids))
    np.testing.assert_array_equal(np.asarray(w_cnt), np.asarray(g_cnt))


def _sdc_case(seed, C, m, ksub):
    r = np.random.default_rng(seed)
    cent = r.standard_normal((m, ksub, 3)).astype(np.float32)
    diff = cent[:, :, None, :] - cent[:, None, :, :]
    tables = jnp.asarray((diff * diff).sum(-1))
    codes = r.integers(0, ksub, (C, m)).astype(np.int32)
    ids = r.permutation(10_000)[:C].astype(np.int32)
    ids[r.random(C) < 0.1] = -1
    ok = (ids >= 0) & (r.random(C) > 0.25)
    lut = np.asarray(tables)[np.arange(m), codes[0]]
    d_p = lut[np.arange(m)[None, :], codes].sum(-1)
    return (jnp.asarray(d_p), jnp.asarray(codes), tables,
            jnp.asarray(ids), jnp.asarray(ok))


@pytest.mark.parametrize("seed,C,m,ksub,R", [
    (0, 40, 8, 16, 8), (1, 130, 8, 64, 12), (2, 60, 16, 32, 6),
])
def test_robust_prune_sdc_matches_ref(seed, C, m, ksub, R):
    d_p, codes, tables, ids, ok = _sdc_case(seed, C, m, ksub)
    args = (d_p[None], codes[None], tables, ids[None], ok[None])
    w_ids, w_cnt = ops.robust_prune_sdc(*args, alpha=1.2, R=R,
                                        use_kernel=False)
    g_ids, g_cnt = ops.robust_prune_sdc(*args, alpha=1.2, R=R,
                                        use_kernel=True)
    np.testing.assert_array_equal(np.asarray(w_ids), np.asarray(g_ids))
    np.testing.assert_array_equal(np.asarray(w_cnt), np.asarray(g_cnt))


def test_robust_prune_block_matches_per_row():
    """One block launch over B rows == B independent single-row launches
    (rows must not leak into each other through the block batching)."""
    cases = [_prune_case(50 + i, 48, 16) for i in range(6)]
    batched = [jnp.stack(x) for x in zip(*cases)]
    g_ids, g_cnt = ops.robust_prune_fp(*batched, alpha=1.2, R=8,
                                       use_kernel=True)
    for b, case in enumerate(cases):
        one_ids, one_cnt = ops.robust_prune_fp(
            *[x[None] for x in case], alpha=1.2, R=8, use_kernel=True)
        np.testing.assert_array_equal(np.asarray(g_ids[b]),
                                      np.asarray(one_ids[0]))
        assert int(g_cnt[b]) == int(one_cnt[0])


@pytest.mark.parametrize("flavor", ["fp", "sdc"])
@pytest.mark.parametrize("rows_per_step", [1, 3, 8])
def test_robust_prune_row_groups_match_one_step(flavor, rows_per_step):
    """The compiled path grids the block into groups of G rows (padding the
    last group with inert rows); any grouping must equal the one-step
    launch the interpreter uses."""
    from repro.kernels.robust_prune import prune_call
    B, C, R = 10, 40, 8
    if flavor == "fp":
        cases = [_prune_case(70 + i, C, 16) for i in range(B)]
        d_p, payload, ids, ok = [jnp.stack(x) for x in zip(*cases)]
        tables = None
    else:
        cases = [_sdc_case(80 + i, C, 8, 16) for i in range(B)]
        d_p, payload, tables, ids, ok = [
            jnp.stack(x) for x in zip(*cases)]
        tables = tables[0]
    dm = jnp.where(ok, d_p, jnp.inf)
    ids = ids.astype(jnp.int32)
    kw = dict(alpha=1.2, R=R, interpret=True, tables=tables)
    want = prune_call(dm, payload, ids, **kw)
    got = prune_call(dm, payload, ids, rows_per_step=rows_per_step, **kw)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def _repair_case(seed, N, R, d, cap=None):
    """An Algorithm-4 node repair input over a small random graph."""
    r = np.random.default_rng(seed)
    vecs = jnp.asarray(r.standard_normal((N, d)).astype(np.float32))
    adj = jnp.asarray(r.integers(-1, N, (N, R)).astype(np.int32))
    deleted = jnp.asarray(r.random(N) < 0.2)
    usable = ~deleted
    p = jnp.int32(int(r.integers(0, N)))
    row = adj[p]
    safe = jnp.maximum(row, 0)
    nbr_del = (row >= 0) & deleted[safe]
    if cap is None:
        exp, exp_ok = adj[safe], nbr_del
    else:
        take, idx = jax.lax.top_k(nbr_del.astype(jnp.int32), cap)
        exp = adj[jnp.where(take > 0, row[idx], 0)]
        exp_ok = take > 0
    raw = jnp.concatenate([row, exp.reshape(-1)])
    safe_raw = jnp.maximum(raw, 0)
    dd = vecs[p][None] - vecs[safe_raw]
    d_p = jnp.sum(dd * dd, -1)
    return (row, nbr_del, exp, exp_ok, usable[safe_raw], d_p,
            vecs[safe_raw], p, usable[p], vecs, safe_raw)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_delete_repair_fp_matches_ref(seed):
    """Fused repair kernel vs the jnp contract on engine-shaped inputs
    (a block of two nodes per launch)."""
    args = [jnp.stack(x) for x in zip(_repair_case(seed, 90, 12, 16)[:9],
                                      _repair_case(seed + 50, 90, 12,
                                                   16)[:9])]
    w = ops.delete_repair_fp(*args, alpha=1.2, R=12, use_kernel=False)
    g = ops.delete_repair_fp(*args, alpha=1.2, R=12, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_delete_repair_sdc_matches_ref(seed):
    """Capped SDC repair: kernel vs ref, codes/tables path."""
    from repro.core import pq as pqm
    from repro.core.config import PQConfig
    N, R, d, cap = 90, 12, 16, 4
    (row, nbr_del, exp, exp_ok, usable_c, _, _, p, live, vecs,
     safe_raw) = _repair_case(seed, N, R, d, cap=cap)
    pq_cfg = PQConfig(dim=d, m=4, ksub=16, kmeans_iters=3)
    cb = pqm.train_pq(vecs, pq_cfg)
    codes = pqm.encode(cb, vecs, pq_cfg)
    tables = pqm.sdc_tables(cb)
    d_p = pqm.adc(codes[safe_raw], pqm.sdc_lut(tables, codes[p]))
    cand_codes = codes[safe_raw].astype(jnp.int32)
    args = [x[None] for x in (row, nbr_del, exp, exp_ok, usable_c, d_p,
                              cand_codes)] + [tables, p[None], live[None]]
    w = ops.delete_repair_sdc(*args, alpha=1.2, R=R, use_kernel=False)
    g = ops.delete_repair_sdc(*args, alpha=1.2, R=R, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def test_robust_prune_padding_lanes_inert():
    """The compiled path pads the candidate axis to a 128 multiple with
    (+inf, -1, zero) lanes; a padded launch must match the unpadded one
    (on CPU only the unpadded branch runs, so exercise padding directly)."""
    from repro.kernels.ops import _pad_to
    from repro.kernels.robust_prune import robust_prune_fp_kernel
    d_p, vecs, ids, ok = _prune_case(3, 60, 16)
    dm = jnp.where(ok, d_p, jnp.inf)[None]
    ids = ids[None].astype(jnp.int32)
    unp = robust_prune_fp_kernel(dm, vecs[None], ids, alpha=1.2, R=8,
                                 interpret=True)
    pad = robust_prune_fp_kernel(
        _pad_to(dm, 1, 128, jnp.inf), _pad_to(vecs[None], 1, 128, 0.0),
        _pad_to(ids, 1, 128, -1), alpha=1.2, R=8, interpret=True)
    for u, p in zip(unp, pad):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(p))


def test_delete_repair_padding_lanes_inert():
    """Same contract for the repair kernel: the wrapper's padded operand
    layout (expansion lanes -1/0, +inf distances) must be inert."""
    from repro.kernels.ops import _pad_payload, _repair_operands
    from repro.kernels.delete_repair import delete_repair_fp_kernel
    case = [x[None] for x in _repair_case(5, 90, 12, 16)[:9]]
    row, nbr_del, exp, exp_ok, usable_c, d_p, cand_vecs, p, live = case
    outs = []
    for pad in (False, True):
        r, nd, e, eok, us, dp, pp, lv = _repair_operands(
            row, nbr_del, exp, exp_ok, usable_c, d_p, p, live,
            pad_lanes=pad)
        vecs = _pad_payload(cand_vecs.astype(jnp.float32), pad)
        outs.append(delete_repair_fp_kernel(
            r, nd, e, eok, us, dp, vecs, pp, lv, alpha=1.2, R=12,
            interpret=True))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))


def test_batch_distances_kernel_parity_both_backends():
    """batch_distances: kernels.ops vs jnp reference on FullPrecision and PQ
    backends, with INVALID-masked id lanes -> +inf on both paths."""
    from repro.core import pq as pqm
    from repro.core.config import PQConfig
    from repro.core.search import (FullPrecisionBackend, PQBackend,
                                   batch_distances)
    dim, n, B, K = 32, 400, 6, 40
    vecs = jnp.asarray(rng.standard_normal((n, dim)).astype(np.float32))
    qs = jnp.asarray(rng.standard_normal((B, dim)).astype(np.float32))
    ids = rng.integers(0, n, (B, K)).astype(np.int32)
    ids[:, -5:] = -1
    ids = jnp.asarray(ids)

    fp = FullPrecisionBackend(vecs)
    d_ref = batch_distances(fp, qs, ids, use_kernel=False)
    d_ker = batch_distances(fp, qs, ids, use_kernel=True)
    assert bool(jnp.isinf(d_ref[:, -5:]).all())
    assert bool(jnp.isinf(d_ker[:, -5:]).all())
    np.testing.assert_allclose(np.asarray(d_ker), np.asarray(d_ref),
                               rtol=1e-4, atol=1e-3)

    pq_cfg = PQConfig(dim=dim, m=8, ksub=32, kmeans_iters=3)
    cb = pqm.train_pq(vecs, pq_cfg)
    codes = pqm.encode(cb, vecs, pq_cfg)
    pq = PQBackend(codes, cb)
    d_ref = batch_distances(pq, qs, ids, use_kernel=False)
    d_ker = batch_distances(pq, qs, ids, use_kernel=True)
    assert bool(jnp.isinf(d_ref[:, -5:]).all())
    assert bool(jnp.isinf(d_ker[:, -5:]).all())
    np.testing.assert_allclose(np.asarray(d_ker), np.asarray(d_ref),
                               rtol=1e-4, atol=1e-3)


def test_adc_is_used_equivalently_in_core():
    """core.pq.adc == kernel adc (the wiring contract)."""
    from repro.core import pq as pqm
    from repro.core.config import PQConfig
    cfg = PQConfig(dim=32, m=8, ksub=32, kmeans_iters=3)
    data = jnp.asarray(rng.standard_normal((256, 32)).astype(np.float32))
    cb = pqm.train_pq(data, cfg)
    codes = pqm.encode(cb, data, cfg)
    qv = data[7]
    table = pqm.lut(cb, qv)
    want = pqm.adc(codes, table)
    got = ops.adc_distances(codes, table[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
