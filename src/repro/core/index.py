"""FreshVamana — the in-memory index (paper §4): build, insert, delete,
consolidate, search.  Functional core over ``GraphState``; every entry point
jit-compiles with static shapes.

``unified_search`` is the one-program §5.2 fan-out every stage of which is
vmapped over the query axis — the device half of the batched serving engine
(``system.search_batch``; serving guide: docs/SERVING.md).  Under
``SystemConfig.shard_lti`` the same program shape runs with the LTI lane
mesh-sharded (``serving.steps.make_sharded_unified_step``), reusing
``search_lanes`` / ``lanes_to_ext`` / ``fanout_merge`` from here.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import pq as pqm
from .config import IndexConfig
from .distance import INVALID
from .graph import GraphState, LaneStack, empty_graph, medoid
from .insert import apply_back_edges, compute_insert_edges
from .search import (FullPrecisionBackend, PQBackend, batch_distances,
                     beam_search, rerank_candidates, topk_results)


@functools.partial(jax.jit, static_argnames=("cfg", "L", "reprune"))
def insert(state: GraphState, slots: jax.Array, vecs: jax.Array,
           cfg: IndexConfig, L: Optional[int] = None,
           reprune: bool = False) -> GraphState:
    """Insert a batch (Algorithm 2).  ``slots`` may contain INVALID (masked
    lanes — used by the distributed routed insert).  With ``reprune`` the
    points may already be in the graph (second build pass): their out-rows are
    recomputed rather than appended."""
    L = L or cfg.L_build
    valid = slots >= 0
    wslots = jnp.where(valid, slots, state.capacity)  # OOB -> dropped scatter
    vectors = state.vectors.at[wslots].set(
        vecs.astype(state.vectors.dtype), mode="drop")
    active = state.active.at[wslots].set(True, mode="drop")
    deleted = state.deleted.at[wslots].set(False, mode="drop")
    # Re-seed the entry point when it is the empty sentinel (a consolidate
    # that deleted every live point leaves start=INVALID): the first valid
    # inserted slot becomes the new start so this batch's edge searches —
    # and every later search — have a live seed again.
    first_valid = jnp.where(valid.any(),
                            slots[jnp.argmax(valid)], state.start)
    start = jnp.where(state.start < 0, first_valid,
                      state.start).astype(jnp.int32)
    st = state._replace(
        vectors=vectors, active=active, deleted=deleted, start=start,
        n_total=jnp.maximum(state.n_total,
                            jnp.max(jnp.where(valid, slots, -1)) + 1))
    usable = st.active & ~st.deleted
    edges = compute_insert_edges(
        state.adjacency if not reprune else st.adjacency,
        st.active, usable, st.start, st.vectors,
        jnp.where(valid, slots, INVALID), vecs,
        FullPrecisionBackend(st.vectors),
        L=L, max_visits=cfg.visits_bound(L), alpha=cfg.alpha, R=cfg.R,
        beam_width=cfg.beam_width, use_kernel=cfg.kernel_enabled())
    new_adj = jnp.where(valid[:, None], edges.new_adj, INVALID)
    adjacency = st.adjacency.at[wslots].set(new_adj, mode="drop")
    pairs_j = jnp.where(valid[:, None], edges.new_adj, INVALID).reshape(-1)
    adjacency = apply_back_edges(
        adjacency, st.vectors, usable, pairs_j, edges.pairs_p,
        alpha=cfg.alpha, R=cfg.R, use_kernel=cfg.kernel_enabled())
    return st._replace(adjacency=adjacency)


@functools.partial(jax.jit, static_argnames=("cfg", "L", "reprune"))
def insert_edges_stage(state: GraphState, slots: jax.Array, vecs: jax.Array,
                       cfg: IndexConfig, L: Optional[int] = None,
                       reprune: bool = False):
    """Stages 1+2 of ``insert`` as a standalone program: store the batch,
    search + prune its out-edges, scatter the new rows — returning the
    staged state plus the Delta pair list *without* applying it.

    ``insert_edges_stage`` followed by ``insert_apply_delta`` (with
    ``affected_cap=None``) is bit-identical to one ``insert`` call
    (tests/test_locality.py pins this).  The locality-ordered flush uses
    the split so it can measure the chunk's DISTINCT back-edge target count
    on the host between the stages and size the Delta prune launch to a
    matching power-of-two bucket instead of the worst case.
    """
    L = L or cfg.L_build
    valid = slots >= 0
    wslots = jnp.where(valid, slots, state.capacity)
    vectors = state.vectors.at[wslots].set(
        vecs.astype(state.vectors.dtype), mode="drop")
    active = state.active.at[wslots].set(True, mode="drop")
    deleted = state.deleted.at[wslots].set(False, mode="drop")
    first_valid = jnp.where(valid.any(),
                            slots[jnp.argmax(valid)], state.start)
    start = jnp.where(state.start < 0, first_valid,
                      state.start).astype(jnp.int32)
    st = state._replace(
        vectors=vectors, active=active, deleted=deleted, start=start,
        n_total=jnp.maximum(state.n_total,
                            jnp.max(jnp.where(valid, slots, -1)) + 1))
    usable = st.active & ~st.deleted
    edges = compute_insert_edges(
        state.adjacency if not reprune else st.adjacency,
        st.active, usable, st.start, st.vectors,
        jnp.where(valid, slots, INVALID), vecs,
        FullPrecisionBackend(st.vectors),
        L=L, max_visits=cfg.visits_bound(L), alpha=cfg.alpha, R=cfg.R,
        beam_width=cfg.beam_width, use_kernel=cfg.kernel_enabled())
    new_adj = jnp.where(valid[:, None], edges.new_adj, INVALID)
    adjacency = st.adjacency.at[wslots].set(new_adj, mode="drop")
    pairs_j = new_adj.reshape(-1)
    return st._replace(adjacency=adjacency), pairs_j, edges.pairs_p


@functools.partial(jax.jit, static_argnames=("cfg", "affected_cap"))
def insert_apply_delta(state: GraphState, pairs_j: jax.Array,
                       pairs_p: jax.Array, cfg: IndexConfig,
                       affected_cap: Optional[int] = None) -> GraphState:
    """Stage 3 of ``insert``: apply the staged Delta pair list.

    ``affected_cap`` (static) sizes the grouped prune launch; the caller
    must guarantee cap >= distinct(pairs_j) or affected rows are silently
    dropped (``insert._apply_back_edges_impl``).  None = worst case,
    completing the bit-identical replication of ``insert``.
    """
    usable = state.active & ~state.deleted
    adjacency = apply_back_edges(
        state.adjacency, state.vectors, usable, pairs_j, pairs_p,
        alpha=cfg.alpha, R=cfg.R, use_kernel=cfg.kernel_enabled(),
        affected_cap=affected_cap)
    return state._replace(adjacency=adjacency)


def _search_impl(state: GraphState, queries: jax.Array, cfg: IndexConfig,
                 *, k: int, L: int, beam_width: Optional[int]):
    res = beam_search(state.adjacency, state.active, state.start, queries,
                      FullPrecisionBackend(state.vectors),
                      L=L, max_visits=cfg.visits_bound(L),
                      beam_width=beam_width or cfg.beam_width,
                      use_kernel=cfg.kernel_enabled())
    ids, d = topk_results(res, k, state.active & ~state.deleted)
    return ids, d, res.n_hops, res.n_cmps


@functools.partial(jax.jit, static_argnames=("cfg", "k", "L", "beam_width"))
def search(state: GraphState, queries: jax.Array, cfg: IndexConfig,
           *, k: int, L: int, beam_width: Optional[int] = None):
    """Batched search; returns (ids [B,k], dists [B,k], hops [B], cmps [B]).

    ``hops`` counts IO rounds: with ``beam_width`` W each round expands up to
    W frontier nodes, so hops drop ~W-fold vs the W=1 classic search.
    """
    return _search_impl(state, queries, cfg, k=k, L=L, beam_width=beam_width)


@functools.partial(jax.jit, static_argnames=("cfg", "k", "L", "beam_width"))
def search_tiers(states: GraphState, queries: jax.Array, cfg: IndexConfig,
                 *, k: int, L: int, beam_width: Optional[int] = None):
    """Multi-tier fan-out: one vmapped search over T stacked graphs.

    ``states`` is a GraphState pytree with [T, ...] leaves (from
    ``graph.stack_graphs``); every tier is searched with the same query
    batch in a single device step, so wall-clock no longer scales linearly
    in the number of RO snapshots.  Returns (ids [T,B,k], dists [T,B,k],
    hops [T,B], cmps [T,B]) — per-lane results bit-identical to running
    ``search`` tier by tier.
    """
    def one(st):
        return _search_impl(st, queries, cfg, k=k, L=L,
                            beam_width=beam_width)

    return jax.vmap(one)(states)


@functools.partial(jax.jit, static_argnames=("cfg", "k", "L", "beam_width",
                                             "rerank"))
def search_lanes(stack: LaneStack, queries: jax.Array, cfg: IndexConfig,
                 *, k: int, L: int, beam_width: Optional[int] = None,
                 rerank: bool = True):
    """Heterogeneous-lane fan-out: every live tier in one device program.

    The temp group runs as one vmapped exact-L2 search over the [Tt, ...]
    stack; the LTI lane (if present) runs PQ-ADC navigation at its own
    capacity in the same program.  With ``rerank`` the LTI lane's final
    candidate list gets the exact full-precision rerank *in-program*
    (DeleteList members masked before the gather, matching the
    ``search_lti`` contract).  Returns (ids [T,B,k], dists [T,B,k],
    hops [T,B], cmps [T,B]) with the LTI as the LAST lane — lane t
    bit-identical to running the dedicated engine (``search`` /
    ``search_lti``) on tier t alone.
    """
    use_kernel = cfg.kernel_enabled()
    outs = []
    if stack.temps is not None:
        def one(g: GraphState):
            return _search_impl(g, queries, cfg, k=k, L=L,
                                beam_width=beam_width)

        outs.append(jax.vmap(one)(stack.temps))
    if stack.lti is not None:
        g = stack.lti
        res = beam_search(g.adjacency, g.active, g.start, queries,
                          PQBackend(stack.codes, pqm.PQCodebook(
                              stack.codebook)),
                          L=L, max_visits=cfg.visits_bound(L),
                          beam_width=beam_width or cfg.beam_width,
                          use_kernel=use_kernel)
        reportable = g.active & ~g.deleted
        if rerank:
            exact = batch_distances(
                FullPrecisionBackend(g.vectors), queries,
                rerank_candidates(res.ids, reportable),
                use_kernel=use_kernel)
            res = res._replace(dists=exact)
        ids, d = topk_results(res, k, reportable)
        outs.append(tuple(x[None] for x in (ids, d, res.n_hops,
                                            res.n_cmps)))
    if not outs:
        raise ValueError("search_lanes: empty LaneStack")
    return tuple(jnp.concatenate(parts, axis=0) for parts in zip(*outs))


def lanes_to_ext(tables: jax.Array, drop: jax.Array, slot_ids: jax.Array,
                 dists: jax.Array):
    """Slot->external-id map + DeleteList mask for one lane group.

    tables [G, capacity] int32/int64, drop [G, capacity] bool,
    slot_ids/dists [G, B, C] -> (ext [G, B, C], dists with DeleteList
    members inf'd out).  The device half of the §5.2 aggregation that
    depends on a lane's capacity; groups of different capacities map
    separately and meet in ``fanout_merge``.
    """

    def one(tab, dr, sl, d):
        s = jnp.maximum(sl, 0)
        ext = jnp.where(sl >= 0, tab[s], -1)
        dead = (sl >= 0) & dr[s]
        return ext, jnp.where(dead, jnp.inf, d)

    return jax.vmap(one)(tables, drop, slot_ids, dists)


def fanout_merge(ids: jax.Array, ds: jax.Array, *, k: int):
    """On-device cross-tier merge (the device half of §5.2 aggregation).

    ids/ds [B, M] — every lane's externally-mapped candidates concatenated
    (``lanes_to_ext`` output, flattened lane-major).  Dedupes cross-tier
    copies keeping the closest instance and returns the global top-k per
    query: (ext_ids [B, k], dists [B, k] f32) with (-1, +inf) padding.
    Bit-identical to the host-side ``FreshDiskANN._aggregate`` on the same
    per-lane inputs; ids may be int32 or int64 (``jax_enable_x64``).
    """
    ds = jnp.where(ids < 0, jnp.inf, ds.astype(jnp.float32))
    # Dedupe keeping the closest copy of each id, then rank by distance —
    # the same lexsort / dup-mask / stable-argsort sequence as _aggregate.
    order = jnp.lexsort((ds, ids))
    sid = jnp.take_along_axis(ids, order, axis=1)
    sd = jnp.take_along_axis(ds, order, axis=1)
    dup = jnp.zeros(sid.shape, bool).at[:, 1:].set(
        (sid[:, 1:] == sid[:, :-1]) & (sid[:, 1:] >= 0))
    sd = jnp.where(dup, jnp.inf, sd)
    top = jnp.argsort(sd, axis=1, stable=True)[:, :k]
    rd = jnp.take_along_axis(sd, top, axis=1)
    ri = jnp.where(jnp.isfinite(rd),
                   jnp.take_along_axis(sid, top, axis=1), -1)
    return ri, jnp.where(jnp.isfinite(rd), rd, jnp.inf)


@functools.partial(jax.jit, static_argnames=("cfg", "k", "k_lane", "L",
                                             "beam_width", "rerank"))
def unified_search(stack: LaneStack, temp_tables: Optional[jax.Array],
                   lti_table: Optional[jax.Array],
                   temp_drop: Optional[jax.Array],
                   lti_drop: Optional[jax.Array],
                   queries: jax.Array, cfg: IndexConfig, *, k: int,
                   k_lane: int, L: int, beam_width: Optional[int] = None,
                   rerank: bool = True):
    """The whole §5.2 steady-state query as ONE jitted device program.

    Beam-searches every lane (TempIndex tiers on exact L2, vmapped at temp
    capacity; the LTI lane on PQ ADC at its own capacity), exact-reranks
    the LTI lane's candidates, takes the per-lane top-``k_lane``, maps each
    group's slots to external ids against its own table
    (``temp_tables`` [Tt, temp_cap], ``lti_table`` [lti_cap]), filters the
    DeleteList (``temp_drop``/``lti_drop``), and merges to the global
    top-``k`` — all on-device, one dispatch per query batch however many
    tiers are live.  Returns (ext_ids [B, k], dists [B, k], hops [T, B],
    cmps [T, B]); the per-lane counters feed the beam-width autotuner's
    unified cost model.
    """
    ids, d, hops, cmps = search_lanes(stack, queries, cfg, k=k_lane, L=L,
                                      beam_width=beam_width, rerank=rerank)
    B = queries.shape[0]
    Tt = stack.n_temp_lanes
    parts_i, parts_d = [], []
    if stack.temps is not None:
        ext, dd = lanes_to_ext(temp_tables, temp_drop, ids[:Tt], d[:Tt])
        parts_i.append(jnp.transpose(ext, (1, 0, 2)).reshape(B, -1))
        parts_d.append(jnp.transpose(dd, (1, 0, 2)).reshape(B, -1))
    if stack.lti is not None:
        ext, dd = lanes_to_ext(lti_table[None], lti_drop[None],
                               ids[Tt:], d[Tt:])
        parts_i.append(ext[0])
        parts_d.append(dd[0])
    mi, md = fanout_merge(jnp.concatenate(parts_i, axis=1),
                          jnp.concatenate(parts_d, axis=1), k=k)
    return mi, md, hops, cmps


def build(vectors: np.ndarray | jax.Array, cfg: IndexConfig,
          batch: int = 256, passes: int = 1, seed: int = 0,
          shuffle: bool = True) -> GraphState:
    """Static build = streamed FreshVamana inserts (paper App. B: this is the
    *FreshVamana build*; ``passes=2`` adds the Vamana-style refinement pass).

    The batch size is capped at n//8: points inside one batch cannot see
    each other (quiescent-consistency window), so a single-batch build
    would degenerate to a star around the medoid."""
    n, d = vectors.shape
    assert n <= cfg.capacity and d == cfg.dim
    batch = max(16, min(batch, n // 8)) if n >= 32 else max(1, n // 2)
    vecs = jnp.asarray(vectors)
    state = empty_graph(cfg)
    state = state._replace(
        vectors=state.vectors.at[:n].set(vecs.astype(state.vectors.dtype)))
    # Entry point = medoid of the build set (active yet or not — vectors are
    # stored; medoid over the first n rows).
    mask = jnp.zeros((cfg.capacity,), bool).at[:n].set(True)
    start = medoid(state.vectors, mask)
    # Seed: the medoid point is active with no edges.
    state = state._replace(
        active=state.active.at[start].set(True),
        start=start, n_total=jnp.int32(n))

    rng = np.random.default_rng(seed)
    order = rng.permutation(n) if shuffle else np.arange(n)
    for pass_i in range(passes):
        reprune = pass_i > 0
        for lo in range(0, n, batch):
            sl = order[lo:lo + batch]
            pad = batch - len(sl)
            slots = np.concatenate([sl, np.full(pad, INVALID)]).astype(np.int32)
            bv = np.zeros((batch, d), np.float32)
            bv[:len(sl)] = np.asarray(vectors)[sl]
            state = insert(state, jnp.asarray(slots), jnp.asarray(bv), cfg,
                           reprune=reprune)
    return state


def _masked_topk(vectors: jax.Array, mask: jax.Array, queries: jax.Array,
                 k: int):
    from .distance import l2_sq_batch
    d = l2_sq_batch(queries, vectors)
    return jax.lax.top_k(jnp.where(mask[None, :], -d, -jnp.inf),
                         min(k, vectors.shape[0]))


def brute_force(vectors: jax.Array, mask: jax.Array, queries: jax.Array,
                k: int, chunk: int = 65536) -> jax.Array:
    """Exact k-NN over masked rows — ground truth for every recall number.

    Distances are full f32 (``l2_sq_batch`` asks for HIGHEST precision).
    Rows are scanned ``chunk`` at a time with a running top-k, so a large
    corpus never materializes the whole [Q, N] distance matrix.  Ties go to
    the lower row index, exactly as one ``top_k`` over all rows: the running
    list precedes each new chunk in the merge.
    """
    n = vectors.shape[0]
    best_v, best_i = _masked_topk(vectors[:chunk], mask[:chunk], queries, k)
    for lo in range(chunk, n, chunk):
        v, i = _masked_topk(vectors[lo:lo + chunk], mask[lo:lo + chunk],
                            queries, k)
        merged_v = jnp.concatenate([best_v, v], axis=1)
        merged_i = jnp.concatenate([best_i, i + lo], axis=1)
        best_v, pos = jax.lax.top_k(merged_v, min(k, merged_v.shape[1]))
        best_i = jnp.take_along_axis(merged_i, pos, axis=1)
    return best_i


def recall_at_k(found_ids: jax.Array, true_ids: jax.Array) -> jax.Array:
    """k-recall@k (Definition 1.1): |X ∩ G| / k averaged over queries."""
    k = true_ids.shape[1]
    eq = found_ids[:, :, None] == true_ids[:, None, :]
    inter = eq.any(axis=2) & (found_ids >= 0)
    return inter.sum(axis=1).mean() / k
