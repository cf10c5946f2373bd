r"""Deletion: lazy DeleteList + consolidation (Algorithm 4).

``delete`` only marks nodes (paper §4.2 — deletes return in ~0.1us; deleted
nodes stay navigable but are filtered from results).  ``consolidate_deletes``
is the batched graph repair: every live node p with deleted out-neighbors gets

    C  <-  (N_out(p) u  U_{v in N_out(p) n D} N_out(v)) \ D \ {p}
    N_out(p)  <-  RobustPrune(p, C, alpha, R)

The pass is blocked (``lax.map`` over node blocks) — the TPU rendition of the
paper's sequential block-by-block SSD scan: one block of adjacency rows is
streamed HBM->VMEM, repaired in parallel, written back.  Under
``IndexConfig.use_kernel`` each block's repair is ONE fused Pallas launch
(``kernels.delete_repair``: candidate assembly + all R prune rounds +
changed-row select, vectorized across the block's rows); the pre-engine
jnp blocks are kept verbatim as the bit-parity oracle.

Two sweep modes (``IndexConfig.repair_mode``, overridable per call):

- ``"global"`` — the paper's full scan: every ``capacity/block`` block is
  repaired, affected or not.  Cost is independent of the delete rate.
- ``"local"`` — Algorithm 4's loop set is exactly the *affected set*
  (live nodes with >=1 deleted out-neighbor; ``affected_mask``).  The
  localized sweep finds those rows with one O(N*R) gather/compare pass,
  gathers them into fixed-shape padded blocks, repairs the blocks through
  the SAME per-block engine (one fused launch per block under the
  kernels), and scatters the repaired rows back.  Row repair is
  independent row-to-row, so the result is bit-identical to the global
  sweep while touching ~``|affected|/capacity`` of the blocks — an
  order of magnitude cheaper at low delete rates.  The affected ids are
  materialized on the host (data-dependent size), so the localized mode
  cannot run under an enclosing ``jit`` — ``streaming_merge`` dispatches
  around it.

The localized sweep runs as bounded device slices (``SliceRunner``): the
affected-set scan over fixed ranges of ``RANGE_ROWS`` rows, one repair
program per block of affected rows, the reclamation, and one small write
per block, each launched only after the one before it has finished.  A
TPU cannot preempt a running program, so a search that arrives meanwhile
waits out one slice, not the whole sweep.  Every slice has a fixed shape,
so the sweep compiles the same programs whatever the number of affected
rows.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels import ops
from .config import IndexConfig
from .distance import INVALID, l2_sq
from .graph import GraphState, medoid
from .prune import prune_node


def delete(state: GraphState, slots: jax.Array) -> GraphState:
    """Lazy delete: add to DeleteList (no graph edits)."""
    ok = slots >= 0
    safe = jnp.where(ok, slots, 0)
    deleted = state.deleted.at[safe].set(
        jnp.where(ok, True, state.deleted[safe]))
    return state._replace(deleted=deleted)


def affected_mask(adjacency: jax.Array, deleted: jax.Array,
                  usable: jax.Array) -> jax.Array:
    """Algorithm 4's loop set: live nodes with >=1 deleted out-neighbor.

    One O(N*R) gather/compare over the adjacency — the reverse-edge pass
    (in-neighbors of D) and D's own out-balls collapse into this forward
    scan because an edge p->v with v in D *is* p being an in-neighbor of
    D.  Rows outside the mask are untouched by the repair (their
    ``nbr_del.any()`` select keeps the old row), so repairing only the
    masked rows is bit-identical to the global sweep.  ``adjacency`` and
    ``usable`` may be any range of rows; ``deleted`` is the whole mask.
    """
    safe = jnp.maximum(adjacency, 0)
    nbr_del = (adjacency >= 0) & deleted[safe]
    return usable & nbr_del.any(axis=1)


def repair_cap_overflow(adjacency: jax.Array, deleted: jax.Array,
                        usable: jax.Array, cap: int) -> jax.Array:
    """Count live nodes whose deleted out-neighbors exceed the SDC
    expansion cap — each such node's repair silently dropped >=1
    expansion ball (``_repair_block_codes``).  Surfaced as
    ``SystemStats.repair_cap_overflows``; deleted edges are still pruned
    from the kept set regardless (the keep-mask is uncapped)."""
    safe = jnp.maximum(adjacency, 0)
    nbr_del = (adjacency >= 0) & deleted[safe]
    return jnp.sum((jnp.sum(nbr_del, axis=1) > cap) & usable)


# Rows per slice of the localized sweep's passes over every row (the
# affected-set scan here, the merge's decode of its prune table): each such
# pass over a 2^21-row shard takes 1-2 s on a v5e, so 2^16 rows keep a
# slice well under one repair block's ~0.25 s.
RANGE_ROWS = 1 << 16


class SliceRunner:
    """Runs device programs one at a time and counts them.

    Each call launches one jitted program and waits for it to finish
    before returning, so at most one of the caller's programs sits in the
    device queue: a program another thread enqueues meanwhile (a search)
    runs after the current slice, not after all of them."""

    def __init__(self):
        self.count = 0

    def __call__(self, program, *args, **kwargs):
        out = jax.block_until_ready(program(*args, **kwargs))
        self.count += 1
        return out


def row_ranges(n: int) -> tuple[list, int]:
    """(start rows, rows per slice) of the ``RANGE_ROWS``-row slices that
    cover ``n`` rows.  The last start is pulled back so every slice has
    one shape; rows covered twice are computed twice, identically."""
    rows = min(RANGE_ROWS, n)
    return [min(lo, n - rows) for lo in range(0, n, rows)], rows


@partial(jax.jit, static_argnames=("rows", "cap"))
def _affected_range(adjacency, deleted, usable, lo, first, *, rows, cap):
    """``affected_mask`` of rows [lo, lo + rows), and (``cap`` set) how
    many of those rows at or past ``first`` overflow the SDC cap."""
    adj = jax.lax.dynamic_slice_in_dim(adjacency, lo, rows)
    use = jax.lax.dynamic_slice_in_dim(usable, lo, rows)
    mask = affected_mask(adj, deleted, use)
    if cap is None:
        return mask, jnp.int32(0)
    fresh = lo + jnp.arange(rows) >= first
    return mask, repair_cap_overflow(adj, deleted, use & fresh, cap)


def affected_rows(adjacency, deleted, usable, run: SliceRunner,
                  cap: int | None = None) -> tuple[np.ndarray, int]:
    """The affected set's ids (ascending, on the host) and, with ``cap``,
    the ``repair_cap_overflow`` count, one ``RANGE_ROWS`` slice at a
    time."""
    n = adjacency.shape[0]
    starts, rows = row_ranges(n)
    mask = np.zeros(n, bool)
    over = 0
    for i, lo in enumerate(starts):
        part, n_over = run(_affected_range, adjacency, deleted, usable, lo,
                           i * rows, rows=rows, cap=cap)
        mask[lo:lo + rows] = np.asarray(part)
        over += int(n_over)
    return np.nonzero(mask)[0], over


def repair_sliced(state: GraphState, aff: np.ndarray, block: int,
                  repair_slice, run: SliceRunner) -> GraphState:
    """Localized sweep body: repair the affected ids ``aff`` one block per
    slice — ``repair_slice(ids)`` returns block ``ids``'s repaired rows,
    read against the pre-repair ``state.adjacency`` — then reclaim
    ``state.deleted`` and write each block's rows into the reclaimed
    adjacency.  Reclaiming after the repair keeps the reclaimed copy out
    of the repair slices' memory.  The last block is padded with
    ``aff[0]``: a duplicate id writes the same repaired row again."""
    repaired = []
    if len(aff):
        n_blocks = -(-len(aff) // block)
        padded = np.full(n_blocks * block, aff[0], dtype=np.int32)
        padded[:len(aff)] = aff
        for ids in padded.reshape(n_blocks, block):
            ids = jnp.asarray(ids)
            repaired.append((ids, run(repair_slice, ids)))
    adjacency, active, deleted, start = run(_reclaim, state)
    for ids, rows in repaired:
        adjacency = run(_write_rows, adjacency, ids, rows)
    return state._replace(adjacency=adjacency, active=active,
                          deleted=deleted, start=start)


def _finish_consolidate(state: GraphState, adjacency: jax.Array) -> GraphState:
    """Shared consolidation tail: slot reclamation + entry-point upkeep.

    The start is re-picked when the current one is deleted, inactive, or
    already the empty sentinel; when NO live point remains the start
    becomes INVALID (searches then return empty instead of seeding from a
    garbage medoid of an all-false mask) and the next insert re-seeds it.
    Repaired rows are live, so reclamation may run before or after the
    repair writes them.
    """
    adjacency = jnp.where(state.deleted[:, None], INVALID, adjacency)
    active = state.active & ~state.deleted
    stale = (state.start < 0) | state.deleted[state.start] \
        | ~state.active[state.start]
    start = jnp.where(
        active.any(),
        jnp.where(stale, medoid(state.vectors, active), state.start),
        INVALID).astype(jnp.int32)
    return state._replace(
        adjacency=adjacency, active=active,
        deleted=jnp.zeros_like(state.deleted), start=start)


@jax.jit
def _reclaim(state: GraphState):
    """``_finish_consolidate`` of ``state``'s own adjacency as one program,
    returning only the fields it rewrites (adjacency, active, deleted,
    start): a field a jit returns unchanged is copied."""
    out = _finish_consolidate(state, state.adjacency)
    return out.adjacency, out.active, out.deleted, out.start


@partial(jax.jit, donate_argnums=(0,))
def _write_rows(adjacency, ids, rows):
    """Write one block of repaired rows into ``adjacency`` (donated, so
    the blocks share one copy)."""
    return adjacency.at[ids].set(rows)


def _consolidate_local(state: GraphState, usable, block: int,
                       repair_slice) -> GraphState:
    """The localized sweep as slices: the affected-set ranges, then
    ``repair_sliced``."""
    run = SliceRunner()
    aff, _ = affected_rows(state.adjacency, state.deleted, usable, run)
    return repair_sliced(state, aff, block, repair_slice, run)


def _scatter_repaired(adjacency, rows_fn, aff: np.ndarray, block: int, R):
    """One-program form of the localized scatter (``repair_sliced`` is the
    sweep's own): pad the affected ids to a block multiple, repair them
    all (``rows_fn`` maps [n_blocks, block] ids -> repaired rows), and
    scatter the rows back.  The benchmark's warm-up
    (``bench/harness/window.py``) calls it."""
    n_blocks = -(-len(aff) // block)
    padded = np.full(n_blocks * block, aff[0], dtype=np.int32)
    padded[:len(aff)] = aff
    ids = jnp.asarray(padded).reshape(n_blocks, block)
    rows = rows_fn(ids)
    return adjacency.at[ids.reshape(-1)].set(rows.reshape(-1, R))


def _repair_block(adjacency, prune_table, deleted, usable, node_ids, alpha, R):
    """Repair one block of nodes; returns new adjacency rows for the block.

    The jnp oracle path (``use_kernel=False``): per-node candidate assembly
    + ``prune_node`` (R sequential rounds as XLA loop steps)."""

    def one(p):
        row = adjacency[p]                                        # [R]
        safe = jnp.maximum(row, 0)
        valid = row >= 0
        nbr_del = valid & deleted[safe]
        keep = jnp.where(valid & ~nbr_del, row, INVALID)
        # neighbors of deleted neighbors
        exp = adjacency[safe]                                     # [R, R]
        exp = jnp.where(nbr_del[:, None], exp, INVALID)
        cand = jnp.concatenate([keep, exp.reshape(-1)])           # [R + R*R]
        new_row = prune_node(prune_table, p, cand, usable, alpha, R).ids
        # Only live nodes with >=1 deleted neighbor change (Alg. 4 loop set).
        live = usable[p]
        return jnp.where(live & nbr_del.any(), new_row, row)

    return jax.vmap(one)(node_ids)


def _repair_block_kernel(adjacency, prune_table, deleted, usable, node_ids,
                         alpha, R):
    """Kernel path: gathers stay in XLA; masks + R prune rounds + the
    changed-row select fuse into ONE ``delete_repair_fp`` launch for the
    whole block.  Bit-identical to ``_repair_block``."""
    rows = adjacency[node_ids]                                   # [B, R]
    safe = jnp.maximum(rows, 0)
    nbr_del = (rows >= 0) & deleted[safe]
    exp = adjacency[safe]                                        # [B, R, R]
    B = rows.shape[0]
    raw = jnp.concatenate([rows, exp.reshape(B, -1)], axis=1)
    safe_raw = jnp.maximum(raw, 0)
    cand_vecs = prune_table[safe_raw].astype(jnp.float32)        # [B, C, d]
    d_p = l2_sq(prune_table[node_ids][:, None, :].astype(jnp.float32),
                cand_vecs)
    return ops.delete_repair_fp(
        rows, nbr_del, exp, nbr_del, usable[safe_raw], d_p, cand_vecs,
        node_ids, usable[node_ids], alpha=alpha, R=R, use_kernel=True)


@partial(jax.jit, static_argnames=("alpha", "R", "kernel"))
def _repair_blocks_fp(adjacency, table, deleted, usable, ids, alpha, R,
                      kernel):
    """Blocked full-precision repair sweep over [n_blocks, block] ids,
    jitted ONCE per (shape, alpha, R, engine) so repeated global
    consolidations reuse the compiled program.  Nested jit inlines, so the
    fused merge program is unchanged."""
    repair = _repair_block_kernel if kernel else _repair_block

    def run(b):
        return repair(adjacency, table, deleted, usable, b, alpha, R)

    return jax.lax.map(run, ids)


@partial(jax.jit, static_argnames=("alpha", "R", "kernel"))
def _repair_slice_fp(adjacency, table, deleted, usable, ids, alpha, R,
                     kernel):
    """One slice of the localized full-precision sweep: the repaired rows
    of the block ``ids``."""
    repair = _repair_block_kernel if kernel else _repair_block
    return repair(adjacency, table, deleted, usable, ids, alpha, R)


def consolidate_deletes(state: GraphState, cfg: IndexConfig,
                        block: int = 256,
                        prune_table: jax.Array | None = None,
                        mode: str | None = None) -> GraphState:
    """Algorithm 4 (global or localized sweep), then slot reclamation.

    prune_table: distance table for RobustPrune — full-precision vectors by
    default; the StreamingMerge delete phase passes PQ-decoded vectors instead
    (paper §5.3 Delete Phase).
    mode: ``"global"`` | ``"local"`` (None -> ``cfg.repair_mode``).  The
    localized sweep repairs only the affected rows — bit-identical output,
    but the affected ids round-trip through the host so it must not be
    called under an enclosing ``jit``.
    """
    N = state.capacity
    table = state.vectors if prune_table is None else prune_table
    usable = state.active & ~state.deleted

    if (cfg.repair_mode if mode is None else mode) == "local":
        return _consolidate_local(
            state, usable, block, lambda ids: _repair_slice_fp(
                state.adjacency, table, state.deleted, usable, ids,
                cfg.alpha, cfg.R, cfg.kernel_enabled()))
    n_blocks = -(-N // block)
    pad = n_blocks * block
    ids = jnp.arange(pad, dtype=jnp.int32).clip(0, N - 1).reshape(
        n_blocks, block)
    adjacency = _repair_blocks_fp(state.adjacency, table, state.deleted,
                                  usable, ids, cfg.alpha, cfg.R,
                                  cfg.kernel_enabled())
    # Reclaim: deleted slots become free (edges cleared, flags reset).
    return _finish_consolidate(state, adjacency.reshape(pad, cfg.R)[:N])


def _repair_block_codes(adjacency, codes, tables, deleted, usable, node_ids,
                        alpha, R, cap):
    """SDC repair: distances from PQ codes; at most ``cap`` deleted
    neighbors expanded per node (candidate width R + cap*R instead of
    R + R^2 — random deletes of 5-10%% make >cap deleted neighbors
    vanishingly rare, and overflow only costs a few candidate edges)."""
    from .prune import prune_node_codes

    def one(p):
        row = adjacency[p]                                    # [R]
        safe = jnp.maximum(row, 0)
        valid = row >= 0
        nbr_del = valid & deleted[safe]
        keep = jnp.where(valid & ~nbr_del, row, INVALID)
        take, idx = jax.lax.top_k(nbr_del.astype(jnp.int32), cap)
        dn = jnp.where(take > 0, row[idx], 0)
        exp = adjacency[dn]                                   # [cap, R]
        exp = jnp.where((take > 0)[:, None], exp, INVALID)
        cand = jnp.concatenate([keep, exp.reshape(-1)])       # [R + cap*R]
        new_row = prune_node_codes(codes, tables, p, cand, usable,
                                   alpha, R).ids
        live = usable[p]
        return jnp.where(live & nbr_del.any(), new_row, row)

    return jax.vmap(one)(node_ids)


def _repair_block_codes_kernel(adjacency, codes, tables, deleted, usable,
                               node_ids, alpha, R, cap):
    """Kernel path of the capped SDC repair — one fused
    ``delete_repair_sdc`` launch for the whole block.  Bit-identical to
    ``_repair_block_codes``."""
    from . import pq as pqm

    rows = adjacency[node_ids]                                   # [B, R]
    safe = jnp.maximum(rows, 0)
    nbr_del = (rows >= 0) & deleted[safe]
    take, idx = jax.lax.top_k(nbr_del.astype(jnp.int32), cap)    # [B, cap]
    dn = jnp.where(take > 0, jnp.take_along_axis(rows, idx, axis=1), 0)
    exp = adjacency[dn]                                          # [B, cap, R]
    B = rows.shape[0]
    raw = jnp.concatenate([rows, exp.reshape(B, -1)], axis=1)
    safe_raw = jnp.maximum(raw, 0)
    cand_codes = codes[safe_raw].astype(jnp.int32)               # [B, C, m]
    d_p = jax.vmap(lambda sr, p: pqm.adc(codes[sr],
                                         pqm.sdc_lut(tables, codes[p])))(
        safe_raw, node_ids)
    return ops.delete_repair_sdc(
        rows, nbr_del, exp, take > 0, usable[safe_raw], d_p, cand_codes,
        tables, node_ids, usable[node_ids], alpha=alpha, R=R,
        use_kernel=True)


@partial(jax.jit, static_argnames=("alpha", "R", "cap", "kernel"))
def _repair_blocks_codes(adjacency, codes, tables, deleted, usable, ids,
                         alpha, R, cap, kernel):
    """SDC twin of ``_repair_blocks_fp`` — same jit-cache rationale."""
    repair = (_repair_block_codes_kernel if kernel
              else _repair_block_codes)

    def run(b):
        return repair(adjacency, codes, tables, deleted, usable, b,
                      alpha, R, cap)

    return jax.lax.map(run, ids)


@partial(jax.jit, static_argnames=("alpha", "R", "cap", "kernel"))
def _repair_slice_codes(adjacency, codes, tables, deleted, usable, ids,
                        alpha, R, cap, kernel):
    """SDC twin of ``_repair_slice_fp``."""
    repair = (_repair_block_codes_kernel if kernel
              else _repair_block_codes)
    return repair(adjacency, codes, tables, deleted, usable, ids, alpha, R,
                  cap)


def consolidate_deletes_codes(state: GraphState, cfg: IndexConfig,
                              codes: jax.Array, tables: jax.Array,
                              block: int = 1024,
                              cap: int = 8,
                              mode: str | None = None) -> GraphState:
    """Algorithm 4 with SDC distances (StreamingMerge delete phase at its
    traffic-optimal operating point — see EXPERIMENTS.md §Perf)."""
    N = state.capacity
    usable = state.active & ~state.deleted

    if (cfg.repair_mode if mode is None else mode) == "local":
        return _consolidate_local(
            state, usable, block, lambda ids: _repair_slice_codes(
                state.adjacency, codes, tables, state.deleted, usable, ids,
                cfg.alpha, cfg.R, cap, cfg.kernel_enabled()))
    n_blocks = -(-N // block)
    pad = n_blocks * block
    ids = jnp.arange(pad, dtype=jnp.int32).clip(0, N - 1).reshape(
        n_blocks, block)
    adjacency = _repair_blocks_codes(state.adjacency, codes, tables,
                                     state.deleted, usable, ids, cfg.alpha,
                                     cfg.R, cap, cfg.kernel_enabled())
    return _finish_consolidate(state, adjacency.reshape(pad, cfg.R)[:N])


# ----------------------------------------------------------------------------
# Naive baselines from §3.3 — used to reproduce Figure 1 (quality collapse).
# ----------------------------------------------------------------------------

def consolidate_policy_a(state: GraphState) -> GraphState:
    """Delete Policy A: drop all edges incident to deleted nodes, add nothing.

    Entry-point upkeep is the shared ``_finish_consolidate`` tail — the
    predicate matches ``consolidate_deletes`` (deleted OR already-inactive
    start is re-picked; an inactive start used to survive Policy A and
    seed searches from a dead node)."""
    safe = jnp.maximum(state.adjacency, 0)
    nbr_del = (state.adjacency >= 0) & state.deleted[safe]
    adjacency = jnp.where(nbr_del, INVALID, state.adjacency)
    return _finish_consolidate(state, adjacency)


def consolidate_policy_b(state: GraphState, cfg: IndexConfig,
                         block: int = 256) -> GraphState:
    """Delete Policy B: local patching with the aggressive alpha=1 prune."""
    cfg1 = IndexConfig(**{**cfg.__dict__, "alpha": 1.0})
    return consolidate_deletes(state, cfg1, block=block)
