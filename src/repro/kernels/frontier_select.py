"""Pallas TPU kernel: fused beam-search round step ("frontier select").

One launch per IO round replaces the three separate device steps the search
loop used to pay (candidate-list merge via ``block_topk``, open-mask
recompute, frontier pick via ``argsort``):

  1. **merge** — stable top-L selection over the concatenation of the sorted
     candidate list (L lanes) and the freshly scored neighbors (K lanes),
     by L rounds of (min, first-column, mask) — the same VPU-only scheme as
     ``block_topk``.
  2. **open mask** — membership test of every merged entry against the
     visited set (one [L, V] broadcast compare).
  3. **frontier pick** — the first ``min(W, max_visits - vis_cnt)`` open
     entries in ascending-distance order (the merged list is sorted, so rank
     = cumsum of the open mask).
  4. **visited update** — the frontier is appended to the visited arrays at
     positions ``vis_cnt ..`` (a vectorized one-hot scatter).

Every step is a select/compare/reduce over iota masks — no gathers, no
dynamic slices, no scans — which is what Mosaic compiles.  Steps 3 and 4
rank the merged list along SUBLANES: the merged lane row is copied into a
sublane column (a diagonal select summed along lanes), the open mask is
computed on the column, its prefix count (the cumsum) is a lower-triangle
masked sum, and the frontier / visited-append one-hots are [L, W] / [L, V]
tiles reduced over sublanes back into lane rows.  All values are exact
selections (one nonzero per sum, +inf rows aside), so the kernel stays
bit-identical to the reference.

``vis_cnt`` is *derived* from visited-array occupancy (the count of valid
ids): the engine appends only valid ids contiguously from slot 0, so
occupancy == vis_cnt by construction, and the kernel needs no scalar operand
(which keeps it trivially vmappable over query lanes).

All rows are [1, N] lane vectors padded to 128 multiples by the ops wrapper
(the L- and W-wide outputs are padded to 128 lanes in-kernel and sliced
back); padding lanes carry (INVALID, +inf) and are inert in every step
above.  The launch carries a leading QUERY-BATCH grid axis — one grid point
per query row, each block a [1, N] row of a [B, 1, N] view — so a B-query
serving batch is one launch whether it arrives as an explicit [B, ...] call
(``ops.frontier_select_batch``) or as a ``jax.vmap`` over the engine's
per-query step (both lower to the same grid).

Contract: ``ref.frontier_select_ref`` (see docs/KERNELS.md); parity
enforced by ``tests/test_kernels.py::test_frontier_select_matches_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _frontier_kernel(d_ref, i_ref, vis_i_ref, vis_d_ref,
                     m_d_ref, m_i_ref, f_d_ref, f_i_ref,
                     ov_i_ref, ov_d_ref, *, L: int, W: int, max_visits: int):
    all_d = d_ref[...].astype(jnp.float32)          # [1, M]
    all_i = i_ref[...]                              # [1, M]
    M = all_d.shape[1]
    Lp, Wp = m_d_ref.shape[-1], f_d_ref.shape[-1]
    cols = _iota((1, M), 1)
    lrow, lcol = _iota((1, Lp), 1), _iota((Lp, 1), 0)

    # -- 1. stable top-L merge: L rounds of (min, first column, retire) ----
    def select(j, carry):
        cd, out_d, out_i = carry
        m = jnp.min(cd, axis=1, keepdims=True)                  # [1, 1]
        col = jnp.min(jnp.where(cd == m, cols, M), axis=1, keepdims=True)
        sel = cols == col
        picked = jnp.sum(jnp.where(sel, all_i, 0), axis=1, keepdims=True)
        picked = jnp.where(jnp.isfinite(m), picked, -1)
        out_d = jnp.where(lrow == j, m, out_d)
        out_i = jnp.where(lrow == j, picked, out_i)
        return jnp.where(sel, jnp.inf, cd), out_d, out_i

    init = (all_d, jnp.full((1, Lp), jnp.inf, jnp.float32),
            jnp.full((1, Lp), -1, jnp.int32))
    _, m_d, m_i = jax.lax.fori_loop(0, L, select, init)
    m_d_ref[...] = m_d
    m_i_ref[...] = m_i
    # Column copies of the merged list (the diagonal of a [Lp, Lp] select,
    # summed along lanes) — steps 3-4 rank along sublanes.
    diag = lcol == lrow
    c_d = jnp.sum(jnp.where(diag, m_d, 0.0), axis=1, keepdims=True)
    c_i = jnp.sum(jnp.where(diag, m_i, 0), axis=1, keepdims=True)

    # -- 2. open mask: merged entry valid, finite, and not yet visited ------
    vis_i = vis_i_ref[...]                          # [1, Vp]
    vis_d = vis_d_ref[...]
    Vp = vis_i.shape[1]
    in_vis = jnp.max(jnp.where(c_i == vis_i, 1, 0), axis=1,
                     keepdims=True)                             # [Lp, 1]
    open_c = (c_i >= 0) & jnp.isfinite(c_d) & (in_vis == 0)    # [Lp, 1]

    # -- 3. frontier: first `allowed` open entries (list is sorted) ---------
    vis_cnt = jnp.sum(jnp.where(vis_i >= 0, 1, 0), axis=1, keepdims=True)
    allowed = jnp.minimum(W, max_visits - vis_cnt)              # [1, 1]
    # The open mask as a lane row (diagonal of a [Lp, Lp] select), then
    # rank[l] = #open entries at positions <= l, minus one.
    open_r = jnp.sum(jnp.where((lcol == lrow) & open_c, 1, 0), axis=0,
                     keepdims=True)                             # [1, Lp]
    rank = jnp.sum(jnp.where((lrow <= lcol) & (open_r != 0), 1, 0),
                   axis=1, keepdims=True) - 1                   # [Lp, 1]
    take = open_c & (rank < allowed)                            # [Lp, 1]
    fm = take & (rank == _iota((Lp, Wp), 1))                    # [Lp, Wp]
    fvalid = jnp.max(jnp.where(fm, 1, 0), axis=0, keepdims=True) != 0
    f_i_ref[...] = jnp.where(
        fvalid, jnp.sum(jnp.where(fm, c_i, 0), axis=0, keepdims=True), -1)
    f_d_ref[...] = jnp.where(
        fvalid, jnp.sum(jnp.where(fm, c_d, 0.0), axis=0, keepdims=True),
        jnp.inf)

    # -- 4. visited append: one-hot scatter at slots vis_cnt.. --------------
    match = take & (vis_cnt + rank == _iota((Lp, Vp), 1))      # [Lp, Vp]
    written = jnp.max(jnp.where(match, 1, 0), axis=0, keepdims=True) != 0
    add_i = jnp.sum(jnp.where(match, c_i, 0), axis=0, keepdims=True)
    add_d = jnp.sum(jnp.where(match, c_d, 0.0), axis=0, keepdims=True)
    ov_i_ref[...] = jnp.where(written, add_i, vis_i)
    ov_d_ref[...] = jnp.where(written, add_d, vis_d)


@functools.partial(
    jax.jit, static_argnames=("L", "W", "max_visits", "interpret"))
def frontier_select_kernel(all_d: jax.Array, all_i: jax.Array,
                           vis_i: jax.Array, vis_d: jax.Array, *,
                           L: int, W: int, max_visits: int,
                           interpret: bool = False):
    """all_d/all_i [B, M] merged-input lanes, vis_i/vis_d [B, Vp] visited.

    The leading axis is the QUERY-BATCH axis: one grid point per query row,
    each running the fused round step above on its own [1, ...] row block
    of a [B, 1, ...] view — exactly the layout a ``jax.vmap`` over the
    single-row call lowers to, made explicit so a B-query serving batch is
    one launch by construction (``ops.frontier_select_batch``).  B=1 is
    the classic single-lane call.

    Returns (merged_d [B, L], merged_i [B, L], frontier_d [B, W],
    frontier_i [B, W], new_vis_i [B, Vp], new_vis_d [B, Vp]).
    """
    B, M = all_d.shape
    _, Vp = vis_i.shape
    assert all_i.shape == (B, M) and vis_d.shape == (B, Vp)
    Lp, Wp = -(-L // 128) * 128, -(-W // 128) * 128
    row = lambda n: pl.BlockSpec((None, 1, n), lambda b: (b, 0, 0))
    outs = pl.pallas_call(
        functools.partial(_frontier_kernel, L=L, W=W, max_visits=max_visits),
        grid=(B,),
        in_specs=[row(M), row(M), row(Vp), row(Vp)],
        out_specs=[row(Lp), row(Lp), row(Wp), row(Wp), row(Vp), row(Vp)],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, Lp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, Lp), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, Wp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, Wp), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, Vp), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, Vp), jnp.float32),
        ],
        interpret=interpret,
    )(*(x.reshape(B, 1, -1) for x in (all_d, all_i, vis_i, vis_d)))
    m_d, m_i, f_d, f_i, ov_i, ov_d = (x[:, 0] for x in outs)
    return m_d[:, :L], m_i[:, :L], f_d[:, :W], f_i[:, :W], ov_i, ov_d
