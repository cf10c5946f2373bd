"""Public jit'd wrappers for the Pallas kernels.

Responsibilities: pad inputs to block multiples, pick interpret mode on CPU
(the CPU backend validates kernels with ``interpret=True``; on TPU the same
code compiles to Mosaic — ``tests/test_tpu_compile.py`` compiles every
main-path kernel for a v5e chip), and slice padding back off.  Every wrapper is
numerically interchangeable with its ``ref.py`` oracle — the per-kernel
contracts (reference, shape/dtype/padding invariants, parity tests) are
tabulated in docs/KERNELS.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .block_topk import block_topk_kernel
from .delete_repair import delete_repair_fp_kernel, delete_repair_sdc_kernel
from .frontier_select import frontier_select_kernel
from .l2_distance import l2_distances_kernel
from .pq_adc import adc_distances_kernel
from .robust_prune import robust_prune_fp_kernel, robust_prune_sdc_kernel


def _interpret() -> bool:
    """Interpret mode exactly when the backend is the CPU: a TPU always
    runs the compiled Mosaic kernels."""
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, axis: int, mult: int, fill) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


@functools.partial(jax.jit, static_argnames=("block_n", "block_q",
                                             "use_kernel"))
def adc_distances(codes: jax.Array, luts: jax.Array, *,
                  block_n: int = 128, block_q: int = 8,
                  use_kernel: bool = True) -> jax.Array:
    """codes [N, m] uint8, luts [Q, m, ksub] -> [Q, N] f32 ADC distances."""
    if not use_kernel:
        return jax.vmap(lambda t: ref.adc_distances_ref(codes, t))(luts)
    N, Q = codes.shape[0], luts.shape[0]
    c = _pad_to(codes, 0, block_n, 0)
    t = _pad_to(luts, 0, block_q, 0.0)
    out = adc_distances_kernel(c, t, block_n=block_n, block_q=block_q,
                               interpret=_interpret())
    return out[:Q, :N]


@functools.partial(jax.jit, static_argnames=("block_q", "block_n", "block_d",
                                             "use_kernel"))
def l2_distances(queries: jax.Array, points: jax.Array, *,
                 block_q: int = 128, block_n: int = 256, block_d: int = 128,
                 use_kernel: bool = True) -> jax.Array:
    """[Q, d] x [N, d] -> [Q, N] squared L2."""
    if not use_kernel:
        return ref.l2_distances_ref(queries, points)
    Q, d = queries.shape
    N = points.shape[0]
    bq = min(block_q, _ceil_mult(Q, 8))
    bn = min(block_n, _ceil_mult(N, 128))
    bd = min(block_d, d)
    q = _pad_to(_pad_to(queries, 0, bq, 0.0), 1, bd, 0.0)
    x = _pad_to(_pad_to(points, 0, bn, 0.0), 1, bd, 0.0)
    out = l2_distances_kernel(q, x, block_q=bq, block_n=bn, block_d=bd,
                              interpret=_interpret())
    return out[:Q, :N]


def _ceil_mult(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(jax.jit, static_argnames=("W", "max_visits", "use_kernel"))
def frontier_select(cand_ids: jax.Array, cand_d: jax.Array,
                    new_ids: jax.Array, new_d: jax.Array,
                    vis_ids: jax.Array, vis_d: jax.Array,
                    vis_cnt: jax.Array, *, W: int,
                    max_visits: int | None = None, use_kernel: bool = True):
    """Fused beam-search round step (single query lane; vmap over queries).

    Semantics are ``ref.frontier_select_ref``: merge the K fresh neighbors
    into the sorted L-entry candidate list, pick the next W-wide open
    frontier, and append it to the visited arrays — one kernel launch instead
    of the block_topk + membership + argsort sequence.

    Contract: ``vis_cnt`` must equal the number of valid (>= 0) ids in
    ``vis_ids`` — the engine maintains this by construction and the Pallas
    kernel re-derives the count from occupancy instead of taking a scalar
    operand.  Returns (merged_ids [L], merged_d [L], frontier_ids [W],
    frontier_d [W], vis_ids', vis_d', vis_cnt').
    """
    if max_visits is None:
        max_visits = vis_ids.shape[0]
    if not use_kernel:
        return ref.frontier_select_ref(cand_ids, cand_d, new_ids, new_d,
                                       vis_ids, vis_d, vis_cnt,
                                       W=W, max_visits=max_visits)
    L, V = cand_ids.shape[0], vis_ids.shape[0]
    all_d = _pad_to(jnp.concatenate([cand_d, new_d])[None, :].astype(
        jnp.float32), 1, 128, jnp.inf)
    all_i = _pad_to(jnp.concatenate([cand_ids, new_ids])[None, :], 1, 128, -1)
    vis_ip = _pad_to(vis_ids[None, :], 1, 128, -1)
    vis_dp = _pad_to(vis_d[None, :].astype(jnp.float32), 1, 128, jnp.inf)
    m_d, m_i, f_d, f_i, ov_i, ov_d = frontier_select_kernel(
        all_d, all_i, vis_ip, vis_dp, L=L, W=W, max_visits=max_visits,
        interpret=_interpret())
    n_take = jnp.sum((f_i[0] >= 0).astype(jnp.int32))
    return (m_i[0], m_d[0], f_i[0], f_d[0],
            ov_i[0, :V], ov_d[0, :V], vis_cnt + n_take)


@functools.partial(jax.jit, static_argnames=("W", "max_visits", "use_kernel"))
def frontier_select_batch(cand_ids: jax.Array, cand_d: jax.Array,
                          new_ids: jax.Array, new_d: jax.Array,
                          vis_ids: jax.Array, vis_d: jax.Array,
                          vis_cnt: jax.Array, *, W: int,
                          max_visits: int | None = None,
                          use_kernel: bool = True):
    """``frontier_select`` with an explicit query-batch leading axis.

    All operands carry a leading [B] axis (``cand_ids`` [B, L], ``new_ids``
    [B, K], ``vis_ids`` [B, V], ``vis_cnt`` [B]); the whole serving batch's
    round step is ONE kernel launch, gridded one query row per grid point —
    the same grid a ``jax.vmap`` over the single-row call lowers to, made
    explicit.  Contract: ``ref.frontier_select_batch_ref`` (the vmapped
    single-row reference); per-row results are bit-identical to B separate
    ``frontier_select`` calls.
    """
    if max_visits is None:
        max_visits = vis_ids.shape[1]
    if not use_kernel:
        return ref.frontier_select_batch_ref(
            cand_ids, cand_d, new_ids, new_d, vis_ids, vis_d, vis_cnt,
            W=W, max_visits=max_visits)
    L, V = cand_ids.shape[1], vis_ids.shape[1]
    all_d = _pad_to(jnp.concatenate(
        [cand_d, new_d], axis=1).astype(jnp.float32), 1, 128, jnp.inf)
    all_i = _pad_to(jnp.concatenate([cand_ids, new_ids], axis=1), 1, 128, -1)
    vis_ip = _pad_to(vis_ids, 1, 128, -1)
    vis_dp = _pad_to(vis_d.astype(jnp.float32), 1, 128, jnp.inf)
    m_d, m_i, f_d, f_i, ov_i, ov_d = frontier_select_kernel(
        all_d, all_i, vis_ip, vis_dp, L=L, W=W, max_visits=max_visits,
        interpret=_interpret())
    n_take = jnp.sum((f_i >= 0).astype(jnp.int32), axis=1)
    return (m_i, m_d, f_i, f_d, ov_i[:, :V], ov_d[:, :V], vis_cnt + n_take)


@functools.partial(jax.jit, static_argnames=("alpha", "R", "use_kernel"))
def robust_prune_fp(d_p: jax.Array, vecs: jax.Array, ids: jax.Array,
                    ok: jax.Array, *, alpha: float, R: int,
                    use_kernel: bool = True):
    """Fused RobustPrune rounds over a [B, C] block of nodes, full precision.

    d_p [B, C] raw anchor distances, vecs [B, C, d] candidate vectors,
    ids [B, C] int32, ok [B, C] bool -> (out_ids [B, R] INVALID-padded,
    counts [B]).  ONE kernel launch per block
    (``core.prune.robust_prune_batch``).  The candidate axis is padded to a
    128 multiple with (+inf, id -1) inert lanes; the feature axis stays
    unpadded so the per-round coverage reduction is bit-identical to the
    oracle's.
    """
    if not use_kernel:
        out, cnt = jax.vmap(lambda dp, v, i, o: ref.robust_prune_fp_ref(
            dp, v, i, o, alpha=alpha, R=R))(d_p, vecs, ids, ok)
        return out, cnt
    interp = _interpret()
    dm = jnp.where(ok, d_p.astype(jnp.float32), jnp.inf)
    vp = vecs.astype(jnp.float32)
    idsp = ids.astype(jnp.int32)
    if not interp:
        # Mosaic wants 128-multiple lanes; the interpreter does not, and
        # the pad copies are pure overhead there.  Padding lanes carry
        # (+inf, id -1, zero vectors) and are provably inert.
        dm = _pad_to(dm, 1, 128, jnp.inf)
        idsp = _pad_to(idsp, 1, 128, -1)
        vp = _pad_to(vp, 1, 128, 0.0)
    out, cnt = robust_prune_fp_kernel(dm, vp, idsp, alpha=alpha, R=R,
                                      interpret=interp)
    return out, cnt[:, 0]


@functools.partial(jax.jit, static_argnames=("alpha", "R", "use_kernel"))
def robust_prune_sdc(d_p: jax.Array, codes: jax.Array, tables: jax.Array,
                     ids: jax.Array, ok: jax.Array, *, alpha: float, R: int,
                     use_kernel: bool = True):
    """Fused RobustPrune rounds over a [B, C] block, SDC coverage.

    d_p [B, C] raw anchor distances (any source: SDC for code anchors, ADC
    for vector anchors), codes [B, C, m] candidate PQ codes,
    tables [m, ksub, ksub] from ``pq.sdc_tables`` ->
    (out_ids [B, R], counts [B]).
    """
    if not use_kernel:
        out, cnt = jax.vmap(lambda dp, c, i, o: ref.robust_prune_sdc_ref(
            dp, c, tables, i, o, alpha=alpha, R=R))(d_p, codes, ids, ok)
        return out, cnt
    interp = _interpret()
    dm = jnp.where(ok, d_p.astype(jnp.float32), jnp.inf)
    cp = codes.astype(jnp.int32)
    idsp = ids.astype(jnp.int32)
    if not interp:
        dm = _pad_to(dm, 1, 128, jnp.inf)
        idsp = _pad_to(idsp, 1, 128, -1)
        cp = _pad_to(cp, 1, 128, 0)
    out, cnt = robust_prune_sdc_kernel(dm, cp, tables.astype(jnp.float32),
                                       idsp, alpha=alpha, R=R,
                                       interpret=interp)
    return out, cnt[:, 0]


def _repair_operands(row, nbr_del, exp, exp_ok, usable_c, d_p, p, live,
                     pad_lanes: bool):
    """Engine-shaped repair inputs -> kernel lane layout (i32 flags).

    The per-parent ``exp_ok`` is flattened to per-lane so the candidate
    axis can pad to a 128 multiple for Mosaic (``pad_lanes``, compiled
    path only): padding lanes carry (exp -1, exp_ok 0, usable 0, +inf) and
    are inert through assembly and every prune round.
    """
    B, R = row.shape[:2]
    e = exp.reshape(B, -1).astype(jnp.int32)
    eok = jnp.repeat(exp_ok.astype(jnp.int32), R, axis=1)
    us = usable_c.astype(jnp.int32)
    dp = d_p.astype(jnp.float32)
    if pad_lanes:
        # C = R + E: pad the expansion lanes so C lands on a 128 multiple.
        pad = (-(R + e.shape[1])) % 128
        widths = ((0, 0), (0, pad))
        e = jnp.pad(e, widths, constant_values=-1)
        eok = jnp.pad(eok, widths, constant_values=0)
        us = jnp.pad(us, widths, constant_values=0)
        dp = jnp.pad(dp, widths, constant_values=jnp.inf)
    return (row.astype(jnp.int32), nbr_del.astype(jnp.int32), e, eok, us,
            dp, p.reshape(B, 1).astype(jnp.int32),
            live.reshape(B, 1).astype(jnp.int32))


def _pad_payload(x, pad_lanes: bool):
    """Pad a [B, C, f] candidate payload to match `_repair_operands`."""
    if not pad_lanes:
        return x
    return _pad_to(x, 1, 128, 0)


@functools.partial(jax.jit, static_argnames=("alpha", "R", "use_kernel"))
def delete_repair_fp(row, nbr_del, exp, exp_ok, usable_c, d_p, vecs, p,
                     live, *, alpha: float, R: int, use_kernel: bool = True):
    """A block's fused Algorithm-4 repair step, full precision.

    row [B, R] int32, nbr_del [B, R] bool, exp [B, E_par, R] int32
    pre-gathered expansion rows, exp_ok [B, E_par] bool, usable_c [B, C]
    bool, d_p [B, C] raw anchor distances, vecs [B, C, d] (raw
    concat(row, exp) candidate order), p [B] node ids, live [B] bool ->
    new rows [B, R].  Candidate assembly, prune rounds, and the final
    changed-row select are ONE launch per block
    (``core.delete.consolidate_deletes``).

    The contract is strictly per-row: each output row is a pure function
    of its own operand slice, never of its neighbors in the block.  That
    is what lets the localized repair mode feed GATHERED blocks — an
    arbitrary (even duplicated, for padding) set of node ids per launch —
    and still be bit-identical to the global sweep's aligned blocks
    (``core.delete`` module doc, "local" mode).
    """
    if not use_kernel:
        return jax.vmap(lambda *a: ref.delete_repair_fp_ref(
            *a, alpha=alpha, R=R))(row, nbr_del, exp, exp_ok, usable_c,
                                   d_p, vecs, p, live)
    interp = _interpret()
    r, nd, e, eok, us, dp, pp, lv = _repair_operands(
        row, nbr_del, exp, exp_ok, usable_c, d_p, p, live,
        pad_lanes=not interp)
    return delete_repair_fp_kernel(r, nd, e, eok, us, dp,
                                   _pad_payload(vecs.astype(jnp.float32),
                                                not interp), pp, lv,
                                   alpha=alpha, R=R, interpret=interp)


@functools.partial(jax.jit, static_argnames=("alpha", "R", "use_kernel"))
def delete_repair_sdc(row, nbr_del, exp, exp_ok, usable_c, d_p, codes,
                      tables, p, live, *, alpha: float, R: int,
                      use_kernel: bool = True):
    """``delete_repair_fp`` with SDC coverage (codes [B, C, m], sdc
    tables)."""
    if not use_kernel:
        return jax.vmap(lambda r_, nd, e, eok, us, dp, c, pp, lv:
                        ref.delete_repair_sdc_ref(
                            r_, nd, e, eok, us, dp, c, tables, pp, lv,
                            alpha=alpha, R=R))(
            row, nbr_del, exp, exp_ok, usable_c, d_p, codes, p, live)
    interp = _interpret()
    r, nd, e, eok, us, dp, pp, lv = _repair_operands(
        row, nbr_del, exp, exp_ok, usable_c, d_p, p, live,
        pad_lanes=not interp)
    return delete_repair_sdc_kernel(r, nd, e, eok, us, dp,
                                    _pad_payload(codes.astype(jnp.int32),
                                                 not interp),
                                    tables.astype(jnp.float32), pp, lv,
                                    alpha=alpha, R=R, interpret=interp)


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_n",
                                             "use_kernel"))
def block_topk(dists: jax.Array, ids: jax.Array, k: int, *,
               block_q: int = 8, block_n: int = 512,
               use_kernel: bool = True) -> tuple[jax.Array, jax.Array]:
    """Top-k smallest of [Q, N] with global ids [N]; returns ([Q,k], [Q,k])."""
    if not use_kernel:
        return ref.block_topk_ref(dists, ids, k)
    Q, N = dists.shape
    bn = min(block_n, _ceil_mult(N, 128))
    bq = min(block_q, _ceil_mult(Q, 8))
    d = _pad_to(_pad_to(dists, 0, bq, jnp.inf), 1, bn, jnp.inf)
    i = _pad_to(ids, 0, bn, -1)
    od, oi = block_topk_kernel(d, i, k=k, block_q=bq, block_n=bn,
                               interpret=_interpret())
    return od[:Q], oi[:Q]
