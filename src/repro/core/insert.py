"""Insert (Algorithm 2) — batched, with the paper's Delta back-edge structure.

A batch of B new points is inserted in three fixed-shape stages:

  1. candidate generation: GreedySearch(s, p, 1, L) per new point against the
     *current* graph (vmapped);
  2. RobustPrune over the visited set -> the new point's out-neighbors;
  3. back-edges: the (target j, source p) pairs are the paper's Delta
     structure.  They are grouped by target with a sort + segment-position
     trick, then every affected node either appends (if still under the degree
     budget R) or re-prunes N_out(j) + {p...} — exactly Algorithm 2's branch.

Every prune in stages 2 and 3 rides the batched prune engine
(``prune.robust_prune_batch``): one fused Pallas launch per node block under
``use_kernel``, the vmapped jnp oracle otherwise — bit-identical either way.
The Delta combine is deduplicated before the append-or-prune branch: a
source p already present in N_out(j) (or appearing twice in the pair list)
must not be appended again, silently burning degree budget.

Points inside one batch do not see each other (the paper's concurrent inserts
under fine-grained locking have the same quiescent-consistency window).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .distance import INVALID
from .prune import (FullPrecisionPrune, SDCPrune, prune_node_batch,
                    robust_prune_batch)
from .search import DistanceBackend, SearchResult, beam_search


class InsertEdges(NamedTuple):
    new_adj: jax.Array   # [B, R] out-neighbors for the new points
    pairs_j: jax.Array   # [B*R] back-edge targets (INVALID padded)
    pairs_p: jax.Array   # [B*R] back-edge sources
    search: SearchResult


def compute_insert_edges(
    adjacency: jax.Array,
    navigable: jax.Array,      # bool[N] traversable (active; incl. lazy-deleted)
    usable: jax.Array,         # bool[N] candidate-eligible (active & !deleted)
    start: jax.Array,
    prune_table: jax.Array,    # [N, d] vectors used for prune distances
    new_slots: jax.Array,      # [B] slot ids of the new points (already stored)
    new_vecs: jax.Array,       # [B, d]
    backend: DistanceBackend,
    *,
    L: int,
    max_visits: int,
    alpha: float,
    R: int,
    beam_width: int = 1,
    use_kernel: bool = False,
) -> InsertEdges:
    """Stages 1+2: search & prune.  Graph arrays are pre-insert (new points
    are stored but have no in-edges, so searches cannot reach them).
    ``use_kernel`` routes BOTH the search hot loop and the batched prune
    through the Pallas ops layer."""
    res = beam_search(adjacency, navigable, start, new_vecs, backend,
                      L=L, max_visits=max_visits, beam_width=beam_width,
                      use_kernel=use_kernel)
    # Candidate pool: V union final list (Alg. 2 uses V; the list adds only
    # closer nodes, strictly improving the pool).
    cand = jnp.concatenate([res.visited, res.ids], axis=1)          # [B, V+L]

    safe = jnp.maximum(cand, 0)
    ok = (cand >= 0) & usable[safe] & (cand != new_slots[:, None])
    pb = FullPrecisionPrune(prune_table)
    d_p = pb.anchor_dists(new_vecs.astype(jnp.float32), cand)
    new_adj = robust_prune_batch(pb, cand, ok, alpha=alpha, R=R,
                                 use_kernel=use_kernel, d_p=d_p).ids
    B = new_slots.shape[0]
    pairs_j = new_adj.reshape(B * R)
    pairs_p = jnp.broadcast_to(new_slots[:, None], (B, R)).reshape(B * R)
    pairs_p = jnp.where(pairs_j >= 0, pairs_p, INVALID)
    return InsertEdges(new_adj, pairs_j, pairs_p, res)


def group_pairs(pairs_j: jax.Array, pairs_p: jax.Array, n_slots: int,
                d_max: int) -> tuple[jax.Array, jax.Array]:
    """Group back-edge pairs by target: Delta buffer [N, d_max] + counts [N].

    Sort by target, compute the position-within-group via searchsorted, then a
    single scatter.  Overflow beyond d_max is dropped (counted by callers via
    the returned counts, capped at d_max on read).
    """
    P = pairs_j.shape[0]
    key = jnp.where(pairs_j >= 0, pairs_j, jnp.int32(n_slots))  # invalid last
    order = jnp.argsort(key)
    sj, sp = key[order], pairs_p[order]
    first = jnp.searchsorted(sj, sj, side="left")
    slot = jnp.arange(P, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = (sj < n_slots) & (slot < d_max)
    buf = jnp.full((n_slots, d_max), INVALID, jnp.int32)
    buf = buf.at[jnp.where(keep, sj, n_slots), jnp.where(keep, slot, 0)].set(
        sp, mode="drop")
    cnt = jnp.zeros((n_slots,), jnp.int32).at[key].add(
        (key < n_slots).astype(jnp.int32), mode="drop")
    return buf, cnt


def _dedupe_combine(combine: jax.Array) -> jax.Array:
    """Mask later duplicates to INVALID, keeping the first occurrence.

    The Delta append path would otherwise append a source already present in
    N_out(j) (or listed twice in the pair buffer) a second time — a silent
    degree-budget burn.  Prune outputs are unaffected (a duplicate of the
    selected candidate is alpha-covered at distance 0 and retired anyway),
    so deduping changes only the append branch and its budget test.
    """
    Ct = combine.shape[-1]
    iota = jnp.arange(Ct)
    eq = combine[..., :, None] == combine[..., None, :]       # [.., i, j]
    dup = ((eq & (iota[None, :] < iota[:, None])).any(axis=-1)
           & (combine >= 0))
    return jnp.where(dup, INVALID, combine)


def patch_plan(pairs_j: jax.Array, pairs_p: jax.Array, n_slots: int, *,
               d_max: int, chunk: int, affected_cap: int | None = None
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Delta grouped for the Patch phase's block pass: the affected targets
    as [n_blocks, block] ids (padded with ``n_slots``), each target's Delta
    sources [n_blocks, block, d_max], and whether it has any.

    Every affected node appears (<= P of them); top_k over the 0/1
    indicator returns lowest-index ties first, so all 1s are captured when
    P <= a_max.  ``affected_cap`` (static) bounds the rows below the worst
    case min(P, N).  The locality paths measure the DISTINCT back-edge
    target count D on the host and pass a power-of-two bucket >= D
    (``locality.next_bucket``), so a proximity-ordered batch whose pairs
    collide onto few targets launches a proportionally smaller prune — the
    fixed-shape program cannot shrink dynamically otherwise.  Correctness
    requires cap >= D.  None = worst case."""
    buf, cnt = group_pairs(pairs_j, pairs_p, n_slots, d_max)
    a_max = min(pairs_j.shape[0], n_slots)
    if affected_cap is not None:
        a_max = max(1, min(a_max, int(affected_cap)))
    _, affected = jax.lax.top_k((cnt > 0).astype(jnp.int32), a_max)
    bs = min(a_max, chunk)
    n_blocks = -(-a_max // bs)
    js = jnp.concatenate(
        [affected.astype(jnp.int32),
         jnp.full((n_blocks * bs - a_max,), n_slots, jnp.int32)]
    ).reshape(n_blocks, bs)
    safe = jnp.minimum(js, n_slots - 1)
    return js, buf[safe], cnt[safe] > 0


def patch_block(adjacency, backend, usable, js, extra, has, *, alpha, R,
                use_kernel):
    """One block of the Patch phase: each target in ``js`` with Delta
    sources (``has``) appends ``extra`` to its row, or re-prunes the union
    once it passes R (Alg. 2).  Pad ids (>= N) write nothing."""
    N = adjacency.shape[0]
    safe = jnp.minimum(js, N - 1)
    rows = adjacency[safe]
    combine = _dedupe_combine(jnp.concatenate([rows, extra], axis=1))
    total = (combine >= 0).sum(axis=1)
    app_order = jnp.argsort(~(combine >= 0), axis=1)
    appended = jnp.take_along_axis(combine, app_order, axis=1)[:, :R]
    pruned = prune_node_batch(backend, safe, combine, usable,
                              alpha=alpha, R=R, use_kernel=use_kernel).ids
    new_rows = jnp.where((total > R)[:, None], pruned, appended)
    new_rows = jnp.where(has[:, None], new_rows, rows)
    return adjacency.at[jnp.where(js < N, js, N)].set(new_rows, mode="drop")


def _apply_back_edges_impl(adjacency, backend, usable, pairs_j, pairs_p, *,
                           alpha, R, d_max, chunk, use_kernel,
                           affected_cap=None):
    """Shared Delta application (stage 3 / StreamingMerge Patch phase).

    Affected nodes are processed in blocks via ``lax.scan`` — the Patch-phase
    block pass of StreamingMerge (one block of rows streamed, patched, written
    back) and a memory bound for plain batched inserts alike
    (``patch_plan``, ``patch_block``).
    """
    js, extra, has = patch_plan(pairs_j, pairs_p, adjacency.shape[0],
                                d_max=d_max, chunk=chunk,
                                affected_cap=affected_cap)

    def block(adj, blk):
        return patch_block(adj, backend, usable, *blk, alpha=alpha, R=R,
                           use_kernel=use_kernel), None

    if js.shape[0] == 1:
        return block(adjacency, (js[0], extra[0], has[0]))[0]
    adjacency, _ = jax.lax.scan(block, adjacency, (js, extra, has))
    return adjacency


def apply_back_edges_codes(
    adjacency: jax.Array,
    codes: jax.Array,        # [N, m] PQ codes
    tables: jax.Array,       # [m, ksub, ksub] sdc tables
    usable: jax.Array,
    pairs_j: jax.Array,
    pairs_p: jax.Array,
    *,
    alpha: float,
    R: int,
    d_max: int | None = None,
    chunk: int = 1024,
    use_kernel: bool = False,
    affected_cap: int | None = None,
) -> jax.Array:
    """Patch phase with SDC distances (see apply_back_edges)."""
    d_max = d_max if d_max is not None else R
    return _apply_back_edges_impl(
        adjacency, SDCPrune(codes, tables), usable, pairs_j, pairs_p,
        alpha=alpha, R=R, d_max=d_max, chunk=chunk, use_kernel=use_kernel,
        affected_cap=affected_cap)


def apply_back_edges(
    adjacency: jax.Array,
    prune_table: jax.Array,
    usable: jax.Array,
    pairs_j: jax.Array,
    pairs_p: jax.Array,
    *,
    alpha: float,
    R: int,
    d_max: int | None = None,
    chunk: int = 1024,
    use_kernel: bool = False,
    affected_cap: int | None = None,
) -> jax.Array:
    """Stage 3: apply Delta.  Affected nodes append or re-prune (Alg. 2)."""
    d_max = d_max if d_max is not None else R
    return _apply_back_edges_impl(
        adjacency, FullPrecisionPrune(prune_table), usable, pairs_j, pairs_p,
        alpha=alpha, R=R, d_max=d_max, chunk=chunk, use_kernel=use_kernel,
        affected_cap=affected_cap)
