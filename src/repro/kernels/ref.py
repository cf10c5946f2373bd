"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics the kernels must match (asserted by the per-kernel
allclose sweeps in ``tests/test_kernels.py``).  They are also the CPU
fallback used when a kernel is disabled.  docs/KERNELS.md tabulates each
contract: reference function, shape/dtype/padding invariants, and the
bit-parity test that enforces it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def adc_distances_ref(codes: jax.Array, lut: jax.Array) -> jax.Array:
    """ADC: ``out[n] = sum_m lut[m, codes[n, m]]``.

    codes: uint8/int32 [N, m]; lut: f32 [m, ksub] -> f32 [N].
    """
    c = codes.astype(jnp.int32)
    m = lut.shape[0]
    return jnp.sum(lut[jnp.arange(m)[None, :], c], axis=-1).astype(jnp.float32)


def l2_distances_ref(queries: jax.Array, points: jax.Array) -> jax.Array:
    """[Q, d] x [N, d] -> [Q, N] squared L2 (exact, f32 accumulation)."""
    q = queries.astype(jnp.float32)
    x = points.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=-1, keepdims=True)
    xn = jnp.sum(x * x, axis=-1)
    cross = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(qn - 2.0 * cross + xn[None, :], 0.0)


def frontier_select_ref(cand_ids: jax.Array, cand_d: jax.Array,
                        new_ids: jax.Array, new_d: jax.Array,
                        vis_ids: jax.Array, vis_d: jax.Array,
                        vis_cnt: jax.Array, *, W: int,
                        max_visits: int | None = None):
    """One fused beam-search round step (single query lane).

    Merges the freshly scored neighbors ``(new_ids, new_d)`` into the sorted
    candidate list ``(cand_ids, cand_d)`` (stable top-L over the [L + K]
    concatenation), computes which merged entries are still *open* (valid,
    finite, and not a member of the visited set), picks the next frontier —
    the first ``min(W, max_visits - vis_cnt)`` open entries in ascending
    distance order — and appends it to the visited arrays.

    Returns ``(merged_ids [L], merged_d [L], frontier_ids [W],
    frontier_d [W], vis_ids', vis_d', vis_cnt')``; unused frontier lanes are
    INVALID/+inf.  ``max_visits`` defaults to ``len(vis_ids)`` (callers pass
    the true bound explicitly when the visited arrays are padded).
    """
    L = cand_ids.shape[0]
    if max_visits is None:
        max_visits = vis_ids.shape[0]
    all_ids = jnp.concatenate([cand_ids, new_ids])
    all_d = jnp.concatenate([cand_d, new_d])
    order = jnp.argsort(all_d, stable=True)[:L]
    m_ids, m_d = all_ids[order], all_d[order]
    # Non-finite lanes are reported as INVALID (the engine only ever produces
    # +inf on INVALID lanes, so this is a normalization, not a change).
    m_ids = jnp.where(jnp.isfinite(m_d), m_ids, -1)

    in_vis = (m_ids[:, None] == vis_ids[None, :]).any(axis=1)
    open_ = (m_ids >= 0) & jnp.isfinite(m_d) & ~in_vis
    allowed = jnp.minimum(W, max_visits - vis_cnt)
    rank = jnp.cumsum(open_.astype(jnp.int32)) - 1
    take = open_ & (rank < allowed)
    n_take = take.sum(dtype=jnp.int32)

    fpos = jnp.argsort(~take, stable=True)[:W]        # taken slots first
    fvalid = take[fpos]
    f_ids = jnp.where(fvalid, m_ids[fpos], -1)
    f_d = jnp.where(fvalid, m_d[fpos], jnp.inf)

    wpos = jnp.where(fvalid, vis_cnt + jnp.arange(W, dtype=jnp.int32),
                     vis_ids.shape[0])
    vis_ids = vis_ids.at[wpos].set(f_ids, mode="drop")
    vis_d = vis_d.at[wpos].set(f_d, mode="drop")
    return m_ids, m_d, f_ids, f_d, vis_ids, vis_d, vis_cnt + n_take


def frontier_select_batch_ref(cand_ids: jax.Array, cand_d: jax.Array,
                              new_ids: jax.Array, new_d: jax.Array,
                              vis_ids: jax.Array, vis_d: jax.Array,
                              vis_cnt: jax.Array, *, W: int,
                              max_visits: int | None = None):
    """The query-batched contract: ``frontier_select_ref`` vmapped over a
    leading [B] axis — each query row's round step is independent, so the
    batched kernel (one grid point per row) must match this bit-for-bit.
    """
    import functools
    return jax.vmap(functools.partial(
        frontier_select_ref, W=W, max_visits=max_visits))(
        cand_ids, cand_d, new_ids, new_d, vis_ids, vis_d, vis_cnt)


def _sdc_cover_row(tables: jax.Array, codes: jax.Array, star: jax.Array
                   ) -> jax.Array:
    """SDC distances from candidate ``star`` to every candidate.

    ``tables`` [m, ksub, ksub] centroid-pair squared distances
    (``pq.sdc_tables``), ``codes`` [C, m] int32.  Op-for-op identical to
    ``pq.adc(codes, pq.sdc_lut(tables, codes[star]))`` — the gather order and
    the final sum over the m-axis must not drift, they are the bit-parity
    contract the Pallas kernel reproduces with one-hot contractions.
    """
    m = tables.shape[0]
    lut = tables[jnp.arange(m), codes[star]]                 # [m, ksub]
    gathered = lut[jnp.arange(m)[None, :], codes]            # [C, m]
    return jnp.sum(gathered, axis=-1)


def robust_prune_fp_ref(d_p: jax.Array, vecs: jax.Array, ids: jax.Array,
                        ok: jax.Array, *, alpha: float, R: int
                        ) -> tuple[jax.Array, jax.Array]:
    """RobustPrune (Algorithm 3) rounds over one candidate row, full precision.

    d_p [C] raw anchor->candidate distances (masked to +inf where ``~ok``),
    vecs [C, d] candidate vectors (garbage on masked lanes — never selected),
    ids [C] int32 candidate ids.  Runs exactly R rounds: masked argmin picks
    the closest alive candidate, its id is emitted, and every candidate it
    alpha-covers (``alpha * d(star, c) <= d(p, c)``) is retired.  Returns
    (out_ids [R] INVALID-padded, count scalar int32).

    This is the mutation-engine oracle: ``core.prune.robust_prune`` delegates
    here, and the fused Pallas kernel must match it bit-for-bit.
    """
    C = ids.shape[0]
    vecs = vecs.astype(jnp.float32)
    d_p = jnp.where(ok, d_p.astype(jnp.float32), jnp.inf)

    def body(i, s):
        alive, out_ids, cnt = s
        masked = jnp.where(alive, d_p, jnp.inf)
        star = jnp.argmin(masked)
        okr = jnp.isfinite(masked[star])
        out_ids = out_ids.at[i].set(jnp.where(okr, ids[star], -1))
        cnt = cnt + okr.astype(jnp.int32)
        diff = vecs[star][None, :] - vecs
        d_star = jnp.sum(diff * diff, axis=-1)               # [C]
        covered = alpha * d_star <= d_p
        alive = alive & ~covered & (jnp.arange(C) != star)
        alive = jnp.where(okr, alive, jnp.zeros_like(alive))
        return alive, out_ids, cnt

    alive0 = ok & jnp.isfinite(d_p)
    out0 = jnp.full((R,), -1, jnp.int32)
    _, out_ids, cnt = jax.lax.fori_loop(0, R, body,
                                        (alive0, out0, jnp.int32(0)))
    return out_ids, cnt


def robust_prune_sdc_ref(d_p: jax.Array, codes: jax.Array, tables: jax.Array,
                         ids: jax.Array, ok: jax.Array, *, alpha: float,
                         R: int) -> tuple[jax.Array, jax.Array]:
    """RobustPrune rounds with candidate-candidate distances from PQ codes.

    Same round structure as ``robust_prune_fp_ref`` but every coverage
    distance is symmetric-distance-computed from ``codes`` [C, m] int32 via
    ``tables`` [m, ksub, ksub] — the StreamingMerge operating point (one byte
    per subspace per candidate per round instead of dsub*4).
    """
    C = ids.shape[0]
    codes = codes.astype(jnp.int32)
    d_p = jnp.where(ok, d_p.astype(jnp.float32), jnp.inf)

    def body(i, s):
        alive, out_ids, cnt = s
        masked = jnp.where(alive, d_p, jnp.inf)
        star = jnp.argmin(masked)
        okr = jnp.isfinite(masked[star])
        out_ids = out_ids.at[i].set(jnp.where(okr, ids[star], -1))
        cnt = cnt + okr.astype(jnp.int32)
        d_star = _sdc_cover_row(tables, codes, star)
        covered = alpha * d_star <= d_p
        alive = alive & ~covered & (jnp.arange(C) != star)
        alive = jnp.where(okr, alive, jnp.zeros_like(alive))
        return alive, out_ids, cnt

    alive0 = ok & jnp.isfinite(d_p)
    out0 = jnp.full((R,), -1, jnp.int32)
    _, out_ids, cnt = jax.lax.fori_loop(0, R, body,
                                        (alive0, out0, jnp.int32(0)))
    return out_ids, cnt


def delete_repair_assemble_ref(row: jax.Array, nbr_del: jax.Array,
                               exp: jax.Array, exp_ok: jax.Array,
                               usable_c: jax.Array, p: jax.Array
                               ) -> tuple[jax.Array, jax.Array]:
    """Algorithm-4 candidate assembly for one node (shared contract half).

    row [R] out-neighbors, nbr_del [R] bool (neighbor is deleted), exp
    [E_par, R] neighbor-of-deleted-neighbor rows, exp_ok [E_par] bool (the
    expansion parent is a valid deleted neighbor), usable_c [C] bool gathered
    usability of the raw concatenated candidates, p scalar node id.  Returns
    (cand_ids [C] with INVALID on masked lanes, ok [C]) where
    C = R + E_par * R: kept-edge lanes are valid when the edge exists and its
    target is NOT deleted; expansion lanes when their parent IS deleted.
    """
    valid = row >= 0
    keep_ok = valid & ~nbr_del
    exp_flat = exp.reshape(-1)
    exp_flat_ok = jnp.repeat(exp_ok, exp.shape[1]) & (exp_flat >= 0)
    raw = jnp.concatenate([row, exp_flat])
    src_ok = jnp.concatenate([keep_ok, exp_flat_ok])
    ok = src_ok & usable_c & (raw != p)
    return jnp.where(src_ok, raw, -1), ok


def delete_repair_fp_ref(row, nbr_del, exp, exp_ok, usable_c, d_p, vecs,
                         p, live, *, alpha: float, R: int) -> jax.Array:
    """Fused Algorithm-4 block step for one node, full precision.

    Assembles the repair candidate set (kept live edges + neighbors of
    deleted neighbors), RobustPrunes it, and emits the new adjacency row —
    unchanged when the node is dead or had no deleted neighbor (the
    Algorithm-4 loop set).  Inputs are pre-gathered by the ops wrapper
    (vecs/d_p/usable_c follow the *raw* concat(row, exp) candidate order;
    masked lanes carry garbage and are inert).  Returns the new row [R].
    """
    cand_ids, ok = delete_repair_assemble_ref(row, nbr_del, exp, exp_ok,
                                              usable_c, p)
    new_row, _ = robust_prune_fp_ref(d_p, vecs, cand_ids, ok,
                                     alpha=alpha, R=R)
    changed = live & (nbr_del & (row >= 0)).any()
    return jnp.where(changed, new_row, row)


def delete_repair_sdc_ref(row, nbr_del, exp, exp_ok, usable_c, d_p, codes,
                          tables, p, live, *, alpha: float, R: int
                          ) -> jax.Array:
    """``delete_repair_fp_ref`` with SDC coverage distances from PQ codes."""
    cand_ids, ok = delete_repair_assemble_ref(row, nbr_del, exp, exp_ok,
                                              usable_c, p)
    new_row, _ = robust_prune_sdc_ref(d_p, codes, tables, cand_ids, ok,
                                      alpha=alpha, R=R)
    changed = live & (nbr_del & (row >= 0)).any()
    return jnp.where(changed, new_row, row)


def block_topk_ref(dists: jax.Array, ids: jax.Array, k: int
                   ) -> tuple[jax.Array, jax.Array]:
    """Top-k smallest distances with their ids.

    dists: f32 [Q, N]; ids: int32 [N] -> (f32 [Q, k], int32 [Q, k]) sorted
    ascending.  +inf distances lose to everything; ties broken by id order
    as produced by a stable sort on distance.
    """
    order = jnp.argsort(dists, axis=-1, stable=True)[:, :k]
    d = jnp.take_along_axis(dists, order, axis=-1)
    i = jnp.take(ids, order)
    return d, i
