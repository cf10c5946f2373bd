"""Serve-step factories: LM prefill/decode, recsys scoring, retrieval — and
the ANN serving engine's mesh-sharded LTI lane (docs/SERVING.md).

Decode steps take and return KV caches so the launch layer can donate the
cache buffers (in-place update on device, no copy per token).

The ANN half implements ``SystemConfig.shard_lti``: the LTI's per-point
arrays (vectors, adjacency, PQ codes, flags) are row-partitioned over a
1-axis ``data`` mesh (``graph.shard_lti`` + ``distributed.sharding``), and
``make_sharded_unified_step`` builds the ONE jitted program that serves a
query batch against it — temp lanes replicated, the LTI lane under
``shard_map``.  Inside the lane the beam-search *state* (candidate list,
frontier, visited set) is replicated on every shard while each row access
is **owner-computed**: the shard owning a slot contributes the gathered
adjacency row / navigability flag / distance, all others contribute the
additive identity, and one ``psum`` recombines — so every shard steps the
identical beam loop and the lane is bit-identical to the single-device
lane for ANY shard count (the invariance contract
``tests/test_serving.py`` enforces on 1/2/4 fake CPU devices).

``make_disk_lti_lane`` is the storage-tier sibling: the same LTI lane with
its adjacency rows streamed from a decoupled on-disk layout through the
block cache + async prefetch pipeline (``repro.storage``, docs/STORAGE.md).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import index as mem
from ..core import pq as pqm
from ..core.config import IndexConfig
from ..core.distance import INVALID
from ..core.graph import LaneStack
from ..core.search import (FullPrecisionBackend, PQBackend,
                           batch_distances, beam_search, topk_masked)
from ..distributed.sharding import lti_lane_specs
from ..models import recsys as rec
from ..models import transformer as tf


def make_lm_prefill_step(cfg: tf.TransformerConfig) -> Callable:
    def prefill(params, tokens):
        logits, _, caches = tf.forward(params, tokens, cfg,
                                       collect_cache=True, last_only=True)
        return logits[:, -1], caches

    return prefill


def make_lm_decode_step(cfg: tf.TransformerConfig) -> Callable:
    def decode(params, caches, tokens, pos):
        logits, new_caches = tf.decode_step(params, caches, tokens, pos, cfg)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, new_caches

    return decode


def make_recsys_serve_step(cfg: rec.RecsysConfig) -> Callable:
    def serve(params, ids):
        return jax.nn.sigmoid(rec.recsys_forward(params, ids, cfg))

    return serve


# ---------------------------------------------------------------------------
# ANN: the mesh-sharded LTI lane (owner-computes row access, psum combine).
# ---------------------------------------------------------------------------

def _owned(ids: jax.Array, offset, n_local: int):
    """(owned mask, clipped local index) for global slot ids on this shard."""
    loc = ids - offset
    own = (ids >= 0) & (loc >= 0) & (loc < n_local)
    return own, jnp.clip(loc, 0, n_local - 1)


def shard_gather_mask(local_mask: jax.Array, ids: jax.Array, offset,
                      axis: str) -> jax.Array:
    """Gather a row-sharded bool array at global ids: owner contributes its
    flag, everyone else 0, one psum recombines.  ids < 0 -> False (exactly
    the dense ``(ids >= 0) & mask[max(ids, 0)]``)."""
    own, loc = _owned(ids, offset, local_mask.shape[0])
    hit = jnp.where(own, local_mask[loc].astype(jnp.int32), 0)
    return jax.lax.psum(hit, axis) > 0


class ShardedRows:
    """Owner-computes ``search.GraphSource`` over row-sharded graph arrays.

    Bit-parity with ``search.DenseSource``: the owner contributes exactly
    the dense gather's value and every other shard the additive identity,
    so the psum reproduces the unsharded result bit-for-bit (integer adds
    are exact; ids with no owner — INVALID frontier slots — sum to the
    INVALID row, matching the dense path's explicit mask).
    """

    def __init__(self, adjacency: jax.Array, active: jax.Array, offset,
                 axis: str):
        self.adjacency = adjacency          # [n_local, R]
        self.active = active                # [n_local]
        self.offset = offset
        self.axis = axis

    def rows(self, ids: jax.Array) -> jax.Array:
        own, loc = _owned(ids, self.offset, self.active.shape[0])
        contrib = jnp.where(own[:, None], self.adjacency[loc] - INVALID, 0)
        return jax.lax.psum(contrib, self.axis) + INVALID

    def node_ok(self, ids: jax.Array) -> jax.Array:
        return shard_gather_mask(self.active, ids, self.offset, self.axis)


class ShardedADC:
    """Owner-computes PQ asymmetric distances (the sharded ``PQBackend``).

    The owner evaluates the dense ``PQBackend`` on its local code rows — the
    same routine (``pq.adc``, or the ``adc_distances`` kernel under
    ``use_kernel``), hence the same f32 bits — and the psum adds exact
    zeros from every other shard (x + 0.0 == x for the non-negative finite
    distances ADC produces).
    """

    def __init__(self, codes: jax.Array, codebook: jax.Array, offset,
                 axis: str):
        self.codes = codes                  # [n_local, m] uint8
        self.codebook = codebook            # [m, ksub, dsub] f32 (replicated)
        self.offset = offset
        self.axis = axis

    def prepare(self, query: jax.Array) -> jax.Array:
        return pqm.lut(pqm.PQCodebook(self.codebook), query)

    def distances(self, ctx: jax.Array, ids: jax.Array, *,
                  use_kernel: bool = False) -> jax.Array:
        own, loc = _owned(ids, self.offset, self.codes.shape[0])
        d = PQBackend(self.codes, None).distances(ctx, loc,
                                                  use_kernel=use_kernel)
        d = jax.lax.psum(jnp.where(own, d, 0.0), self.axis)
        return jnp.where(ids >= 0, d, jnp.inf)


class ShardedExact:
    """Owner-computes exact squared-L2 (the sharded ``FullPrecisionBackend``)
    — used for the LTI lane's in-program full-precision rerank, whose
    vector rows live sharded.  The owner runs the dense
    ``FullPrecisionBackend`` on its local rows, so its value carries the
    same bits."""

    def __init__(self, vectors: jax.Array, offset, axis: str):
        self.vectors = vectors              # [n_local, d]
        self.offset = offset
        self.axis = axis

    def prepare(self, query: jax.Array) -> jax.Array:
        return query.astype(jnp.float32)

    def distances(self, ctx: jax.Array, ids: jax.Array, *,
                  use_kernel: bool = False) -> jax.Array:
        own, loc = _owned(ids, self.offset, self.vectors.shape[0])
        d = FullPrecisionBackend(self.vectors).distances(
            ctx, loc, use_kernel=use_kernel)
        d = jax.lax.psum(jnp.where(own, d, 0.0), self.axis)
        return jnp.where(ids >= 0, d, jnp.inf)


def make_sharded_lti_lane(mesh, cfg: IndexConfig, *, k_lane: int, L: int,
                          beam_width: Optional[int] = None,
                          rerank: bool = True, axis: str = "data"):
    """The LTI lane as a ``shard_map``: PQ-navigated beam search + exact
    rerank + per-lane top-k over row-sharded LTI arrays.

    Returns a callable ``(graph, codes, codebook, queries) -> (slot_ids
    [B, k_lane], dists, hops [B], cmps [B])`` whose outputs are replicated
    and bit-identical to the unsharded lane of ``index.search_lanes`` —
    counters included — for any shard count.  The lane routes through the
    same engine as the unsharded one (``cfg.kernel_enabled()``): the ADC
    and exact-distance kernels do not round like the jnp formulas, so a
    lane on the other engine would drift from the unsharded program.
    """
    W = beam_width or cfg.beam_width
    use_kernel = cfg.kernel_enabled()
    gspecs, cspec = lti_lane_specs(axis)

    def local(g, codes, codebook, queries):
        n_local = g.active.shape[0]
        offset = jax.lax.axis_index(axis).astype(jnp.int32) * n_local
        src = ShardedRows(g.adjacency, g.active, offset, axis)
        res = beam_search(g.adjacency, g.active, g.start, queries,
                          ShardedADC(codes, codebook, offset, axis),
                          L=L, max_visits=cfg.visits_bound(L),
                          beam_width=W, use_kernel=use_kernel, source=src)
        ok = shard_gather_mask(g.active & ~g.deleted, res.ids, offset, axis)
        dists = res.dists
        if rerank:
            # DeleteList members masked BEFORE the gather (the
            # ``rerank_candidates`` contract), on the precomputed ok mask.
            dists = batch_distances(
                ShardedExact(g.vectors, offset, axis), queries,
                jnp.where(ok, res.ids, INVALID), use_kernel=use_kernel)
        ids, d = topk_masked(res.ids, dists, ok, k_lane)
        return ids, d, res.n_hops, res.n_cmps

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(gspecs, cspec, P(), P()),
                         out_specs=(P(), P(), P(), P()),
                         check_vma=False)


def make_sharded_unified_step(mesh, cfg: IndexConfig, *, k: int, k_lane: int,
                              L: int, beam_width: Optional[int] = None,
                              rerank: bool = True,
                              axis: str = "data") -> Callable:
    """The unified §5.2 fan-out with the LTI lane mesh-sharded — still ONE
    jitted device program per query batch.

    Mirrors ``index.unified_search`` exactly (temp lanes vmapped at temp
    capacity, per-group slot->ext mapping, DeleteList filter, cross-tier
    dedupe/top-k), with the LTI lane dispatched through
    ``make_sharded_lti_lane``.  The returned step takes
    ``(stack, t_tabs, l_tab, t_drop, l_drop, queries)`` where
    ``stack.lti``/``stack.codes`` hold the ``graph.shard_lti`` layout; its
    (ids, dists) are bit-identical to the unsharded program's.
    """
    lane = make_sharded_lti_lane(mesh, cfg, k_lane=k_lane, L=L,
                                 beam_width=beam_width, rerank=rerank,
                                 axis=axis)
    # The temp lanes run replicated, but inside a shard_map all the same:
    # the program spans the mesh, and XLA cannot partition a Mosaic kernel
    # on its own.
    temp_lanes = jax.shard_map(
        lambda temps, queries: mem.search_lanes(
            LaneStack(temps, None, None, None), queries, cfg, k=k_lane, L=L,
            beam_width=beam_width),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P(), P(), P()),
        check_vma=False)

    @jax.jit
    def step(stack: LaneStack, t_tabs, l_tab, t_drop, l_drop, queries):
        B = queries.shape[0]
        parts_i, parts_d, hops, cmps = [], [], [], []
        if stack.temps is not None:
            tids, td, th, tc = temp_lanes(stack.temps, queries)
            ext, dd = mem.lanes_to_ext(t_tabs, t_drop, tids, td)
            parts_i.append(jnp.transpose(ext, (1, 0, 2)).reshape(B, -1))
            parts_d.append(jnp.transpose(dd, (1, 0, 2)).reshape(B, -1))
            hops.append(th)
            cmps.append(tc)
        lids, ld, lh, lc = lane(stack.lti, stack.codes, stack.codebook,
                                queries)
        ext, dd = mem.lanes_to_ext(l_tab[None], l_drop[None],
                                   lids[None], ld[None])
        parts_i.append(ext[0])
        parts_d.append(dd[0])
        hops.append(lh[None])
        cmps.append(lc[None])
        mi, md = mem.fanout_merge(jnp.concatenate(parts_i, axis=1),
                                  jnp.concatenate(parts_d, axis=1), k=k)
        return mi, md, jnp.concatenate(hops), jnp.concatenate(cmps)

    return step


def make_disk_lti_lane(layout, cfg: IndexConfig, *, k_lane: int, L: int,
                       beam_width: Optional[int] = None, rerank: bool = True,
                       cache_mb: int = 0, prefetch_depth: int = 1,
                       latency_us: float = 0.0) -> Callable:
    """The LTI lane served off a decoupled on-disk layout (docs/STORAGE.md):
    PQ navigation on in-memory codes, adjacency rows streamed from
    ``topology.bin`` through the block cache + async prefetch pipeline, and
    the exact rerank gathered from ``data.bin``.

    Returns a callable ``(queries) -> (slot_ids [B, k_lane], dists, hops,
    cmps, reads)`` — the sharded lane's tuple plus per-query disk reads.
    With the cache off its outputs are bit-identical to the in-memory lane
    at any prefetch depth.  The lane owns a ``DiskLTISearcher`` exposed as
    ``lane.searcher`` (IO stats via ``lane.searcher.stats``; call
    ``lane.close()`` to stop the prefetch thread).
    """
    from ..storage.source import DiskLTISearcher
    searcher = DiskLTISearcher(layout, cfg, cache_mb=cache_mb,
                               prefetch_depth=prefetch_depth,
                               latency_us=latency_us)
    W = beam_width or cfg.beam_width

    def lane(queries):
        return searcher.search(queries, k=k_lane, L=L, beam_width=W,
                               rerank=rerank)

    lane.searcher = searcher
    lane.close = searcher.close
    return lane


def make_retrieval_step(cfg: rec.RecsysConfig, k: int = 100) -> Callable:
    """Exact candidate-scoring baseline for the retrieval_cand shape.

    For FM-family models the query embedding is the summed field embedding
    (the factorized part); items are rows of a candidate table.  The ANN
    path swaps this for a FreshDiskANN search (examples/sasrec_retrieval.py).
    """
    def retrieve(params, user_ids, item_table):
        if cfg.kind == "sasrec":
            q = rec.sasrec_user_embedding(params, user_ids, cfg)
        else:
            emb = rec.field_lookup(params["V"], user_ids, cfg)
            q = emb.sum(axis=-2)
        return rec.retrieval_topk(q, item_table, k)

    return retrieve
