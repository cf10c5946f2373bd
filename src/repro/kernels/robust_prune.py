"""Pallas TPU kernel: fused RobustPrune rounds (the mutation-engine hot loop).

Algorithm 3 selects up to R out-neighbors by R sequential rounds of
(masked argmin over the anchor distances) -> (emit the winner) ->
(retire every candidate the winner alpha-covers).  The jnp engine pays the
round loop as R separate XLA steps per node; this kernel fuses all R rounds
— argmin, the winner's candidate<->candidate distance row, and the
alpha-coverage mask update — into ONE launch for a whole [B, C] block of
nodes (``core.prune.robust_prune_batch``).

Layout.  The launch grids over groups of G rows: each grid step holds a
(G, C) tile of anchor distances / ids (rows on sublanes, candidates on
lanes) and the matching payload tile — (G, d, C) vectors, candidates on
lanes like the distances, or (G, C, m) codes — so a block's payload
streams HBM->VMEM one row group at a time and never has to fit VMEM whole.
G is 8 (a full sublane tile) when the payload tile stays within
``_TILE_BYTES``, and drops toward 1 for wide candidate lists (the delete
repair's C = R + R^2).  Operands are viewed as [B/G, G, ...] so every block
spans the full extent of its last two axes, which Mosaic accepts for any G.
In interpret mode the whole block is one grid step (G = B): the interpreter
runs grid steps one after another, and rows never interact, so the row
grouping changes nothing but speed.

Two flavors share the round loop (``_prune_rounds``):

  ``robust_prune_fp_kernel``   coverage distances recomputed per round from
                               full-precision candidate vectors
                               (sum((v_star - v)^2) over the feature axis —
                               exactly the ``l2_sq`` the jnp oracle uses).
  ``robust_prune_sdc_kernel``  coverage distances from PQ codes via the
                               symmetric-distance tables, one subspace at a
                               time: the winner's code, its LUT row and each
                               candidate's lookup are one-hot selections of
                               exactly one f32, and the final sum runs over
                               the same [.., m] axis as ``pq.adc``.

No gathers and no dynamic slices: the winner's column, id, vector and code
row are all extracted with one-hot (iota compare + select + sum) reductions,
which select exactly one element and are therefore exact.  Winner selection
is the (min, first-column) scheme shared with ``frontier_select`` —
identical tie-breaking to ``jnp.argmin``.  Anchor distances arrive
pre-masked (+inf on unusable lanes), so the alive set needs no separate mask
operand; candidate-lane padding carries (+inf, id -1) and is inert.  Only
the candidate axis is ever padded, so every coverage reduction runs over the
same feature axis as the oracle's and stays bit-identical to it.

Contracts: ``ref.robust_prune_fp_ref`` / ``ref.robust_prune_sdc_ref``
(see docs/KERNELS.md); parity enforced by
``tests/test_kernels.py::test_robust_prune_fp_matches_ref`` /
``test_robust_prune_sdc_matches_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Largest per-step payload tile (bytes) before the row group shrinks; with
# double buffering and the per-round temporaries this keeps a step well
# inside the raised scoped-VMEM limit below.
_TILE_BYTES = 4 << 20
_VMEM_LIMIT = 64 << 20


def _group_rows(row_bytes: int) -> int:
    """Rows per grid step: 8, halved until the payload tile fits."""
    g = 8
    while g > 1 and g * row_bytes > _TILE_BYTES:
        g //= 2
    return g


def _prune_rounds(d_p, ids, cover_fn, *, alpha: float, R: int, r_pad: int):
    """R fused RobustPrune rounds over a (G, C) tile of candidate rows.

    d_p [G, C] f32 anchor distances, pre-masked (+inf on dead lanes);
    ids [G, C] int32; ``cover_fn(col)`` maps the winners' column indices
    [G, 1] to their distances to every candidate [G, C].  Returns
    (out_ids [G, r_pad] with lanes >= R at -1, counts [G, 1]).  The alive
    set rides the loop as int32: Mosaic cannot carry i1 vectors.
    """
    G, C = d_p.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (G, C), 1)
    rcols = jax.lax.broadcasted_iota(jnp.int32, (G, r_pad), 1)

    def body(i, s):
        alive, out_i, cnt = s
        masked = jnp.where(alive != 0, d_p, jnp.inf)
        m = jnp.min(masked, axis=1, keepdims=True)               # [G, 1]
        col = jnp.min(jnp.where(masked == m, cols, C - 1), axis=1,
                      keepdims=True)                             # [G, 1]
        okr = jnp.isfinite(m)                                    # [G, 1]
        sel = cols == col
        picked = jnp.sum(jnp.where(sel, ids, 0), axis=1, keepdims=True)
        out_i = jnp.where(rcols == i, jnp.where(okr, picked, -1), out_i)
        cnt = cnt + okr.astype(jnp.int32)
        covered = alpha * cover_fn(col) <= d_p
        # No winner -> the row is retired.
        alive = jnp.where(covered | sel | ~okr, 0, alive)
        return alive, out_i, cnt

    alive0 = jnp.isfinite(d_p).astype(jnp.int32)
    out0 = jnp.full((G, r_pad), -1, jnp.int32)
    _, out_i, cnt = jax.lax.fori_loop(
        0, R, body, (alive0, out0, jnp.zeros((G, 1), jnp.int32)))
    return out_i, cnt


def _fp_cover(vecs_t):
    """Full-precision coverage: d_star[g, c] = sum_d (v_star_g - v_gc)^2.

    vecs_t [G, d, C] f32 — the payload TRANSPOSED, candidates on lanes, so
    the per-round reduction over the feature axis is a sublane sum that
    lands directly in the [G, C] row layout of the anchor distances (a lane
    reduction per candidate would cost a cross-lane reduce per vreg every
    round).  The winner's vector is a one-hot sum over the candidate lanes
    (exact); the squared differences are summed over the same d values as
    the oracle's ``l2_sq`` — bit-identical on the CPU backend, where the
    interpreter runs this body (``tests/test_kernels.py`` parity).
    """
    G, d, C = vecs_t.shape
    cidx = jax.lax.broadcasted_iota(jnp.int32, (G, 1, C), 2)

    def cover(col):
        v_star = jnp.sum(jnp.where(cidx == col[:, :, None], vecs_t, 0.0),
                         axis=2, keepdims=True)                  # [G, d, 1]
        diff = v_star - vecs_t                                   # [G, d, C]
        return jnp.sum(diff * diff, axis=1)

    return cover


def _sdc_cover(codes, t_ref):
    """SDC coverage from PQ codes: d_star[g, c] = sum_m T[m, cs_m, cc_m].

    codes [G, C, m] int32; ``t_ref`` the [m, ksub, ksub] f32 tables ref.
    Per subspace (a loop over m): the winner's code and each candidate's
    code are one-hot lane extractions, the winner's LUT row a one-hot row
    selection of T[m], and each candidate's lookup a one-hot selection of
    that row — every step selects exactly one value.  The per-subspace
    values are assembled into [G, C, m] and summed over the same last axis
    as ``pq.adc`` — bit-identical to the reference.
    """
    G, C, m = codes.shape
    ksub = t_ref.shape[1]
    cidx = jax.lax.broadcasted_iota(jnp.int32, (G, C, m), 1)
    midx = jax.lax.broadcasted_iota(jnp.int32, (G, C, m), 2)
    mrow = jax.lax.broadcasted_iota(jnp.int32, (G, m), 1)
    krow = jax.lax.broadcasted_iota(jnp.int32, (G, ksub, ksub), 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (G, C, ksub), 2)

    def cover(col):
        c_star = jnp.sum(jnp.where(cidx == col[:, :, None], codes, 0),
                         axis=1)                                 # [G, m]

        def subspace(mm, gathered):
            cs = jnp.sum(jnp.where(mrow == mm, c_star, 0), axis=1,
                         keepdims=True)                          # [G, 1]
            t_m = t_ref[mm]                                      # [k, k]
            lut = jnp.sum(jnp.where(krow == cs[:, :, None], t_m[None], 0.0),
                          axis=1)                                # [G, k]
            cc = jnp.sum(jnp.where(midx == mm, codes, 0), axis=2,
                         keepdims=True)                          # [G, C, 1]
            g = jnp.sum(jnp.where(kcol == cc, lut[:, None, :], 0.0),
                        axis=2, keepdims=True)                   # [G, C, 1]
            return jnp.where(midx == mm, g, gathered)

        gathered = jax.lax.fori_loop(
            0, m, subspace, jnp.zeros((G, C, m), jnp.float32))
        return jnp.sum(gathered, axis=-1)

    return cover


def _fp_kernel(d_ref, v_ref, i_ref, out_ref, cnt_ref, *, alpha, R):
    out, cnt = _prune_rounds(d_ref[...], i_ref[...],
                             _fp_cover(v_ref[...].astype(jnp.float32)),
                             alpha=alpha, R=R, r_pad=out_ref.shape[-1])
    out_ref[...] = out
    cnt_ref[...] = cnt


def _sdc_kernel(d_ref, c_ref, t_ref, i_ref, out_ref, cnt_ref, *, alpha, R):
    out, cnt = _prune_rounds(d_ref[...], i_ref[...],
                             _sdc_cover(c_ref[...], t_ref),
                             alpha=alpha, R=R, r_pad=out_ref.shape[-1])
    out_ref[...] = out
    cnt_ref[...] = cnt


def prune_call(d_p, payload, ids, *, alpha: float, R: int, interpret: bool,
               tables=None, rows_per_step: int | None = None):
    """One gridded prune launch over [B, C] rows (shared by the prune and
    delete-repair kernels).

    d_p [B, C] pre-masked f32, payload [B, C, f] (f32 vectors for the fp
    flavor — transposed to [B, d, C] here, see ``_fp_cover`` — or int32
    codes with ``tables`` [m, ksub, ksub] for the SDC one), ids [B, C]
    int32 -> (out_ids [B, R] int32, counts [B, 1] int32).
    Rows are padded to the row-group size with inert (+inf, -1) rows;
    ``rows_per_step`` overrides that size (tests exercise the grouping in
    interpret mode with it).
    """
    B, C = d_p.shape
    f = payload.shape[2]
    if tables is None:
        payload = jnp.swapaxes(payload, 1, 2)        # [B, d, C], see _fp_cover
    if rows_per_step is not None:
        G = rows_per_step
    elif interpret:
        G = B
    else:
        # The SDC round also materializes a [C, ksub] one-hot per row.
        width = f if tables is None else max(f, tables.shape[1])
        G = _group_rows(C * width * 4)
    Bp = -(-B // G) * G
    if Bp != B:
        pad = ((0, Bp - B), (0, 0))
        d_p = jnp.pad(d_p, pad, constant_values=jnp.inf)
        ids = jnp.pad(ids, pad, constant_values=-1)
        payload = jnp.pad(payload, pad + ((0, 0),))
    nb = Bp // G
    r_pad = -(-R // 128) * 128
    row = lambda n: pl.BlockSpec((None, G, n), lambda b: (b, 0, 0))
    tile = payload.shape[1:]                         # (C, m) or (d, C)
    pay = pl.BlockSpec((None, G) + tile, lambda b: (b, 0, 0, 0))
    args = [d_p.reshape(nb, G, C), payload.reshape((nb, G) + tile)]
    specs = [row(C), pay]
    if tables is None:
        kernel = functools.partial(_fp_kernel, alpha=alpha, R=R)
    else:
        kernel = functools.partial(_sdc_kernel, alpha=alpha, R=R)
        args.append(tables)
        # Whole tables resident once in VMEM (not double-buffered).
        specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))
    args.append(ids.reshape(nb, G, C))
    specs.append(row(C))
    out, cnt = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=specs,
        out_specs=[row(r_pad), row(1)],
        out_shape=[
            jax.ShapeDtypeStruct((nb, G, r_pad), jnp.int32),
            jax.ShapeDtypeStruct((nb, G, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*args)
    return out.reshape(Bp, r_pad)[:B, :R], cnt.reshape(Bp, 1)[:B]


@functools.partial(jax.jit, static_argnames=("alpha", "R", "interpret"))
def robust_prune_fp_kernel(d_p: jax.Array, vecs: jax.Array, ids: jax.Array,
                           *, alpha: float, R: int,
                           interpret: bool = False):
    """d_p [B, C] pre-masked f32, vecs [B, C, d] f32, ids [B, C] int32 ->
    (out_ids [B, R] int32, counts [B, 1] int32)."""
    B, C = d_p.shape
    assert ids.shape == (B, C) and vecs.shape[:2] == (B, C)
    return prune_call(d_p, vecs, ids, alpha=alpha, R=R, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("alpha", "R", "interpret"))
def robust_prune_sdc_kernel(d_p: jax.Array, codes: jax.Array,
                            tables: jax.Array, ids: jax.Array,
                            *, alpha: float, R: int,
                            interpret: bool = False):
    """d_p [B, C] pre-masked f32, codes [B, C, m] int32,
    tables [m, ksub, ksub] f32, ids [B, C] int32 ->
    (out_ids [B, R] int32, counts [B, 1] int32)."""
    B, C = d_p.shape
    assert ids.shape == (B, C) and codes.shape[:2] == (B, C)
    return prune_call(d_p, codes, ids, alpha=alpha, R=R, interpret=interpret,
                      tables=tables)
