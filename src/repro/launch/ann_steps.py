"""Distributed FreshDiskANN steps over the production mesh.

The paper's own distribution design (§1): every chip hosts an independent
sub-index ("a thousand machines host a billion points each"); queries are
broadcast to all shards and results top-k-merged; updates are routed to one
shard by id hash; StreamingMerge is fully shard-local (zero ICI bytes — the
SSD-write-amplification discipline re-expressed as collective-byte
discipline on the pod).

Implemented with ``shard_map`` over every mesh axis: the global LTI arrays
carry a leading [n_shards * capacity] point axis; each shard's local block
is one FreshVamana/LTI instance.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..core import pq as pqm
from ..core.config import IndexConfig, PQConfig
from ..core.graph import GraphState
from ..core.index import insert as mem_insert
from ..core.lti import LTIState
from ..core.merge import streaming_merge
from ..core.search import (FullPrecisionBackend, PQBackend, batch_distances,
                           beam_search, topk_results)


def _all_axes(mesh: Mesh) -> tuple:
    return tuple(mesh.axis_names)


def shard_specs(mesh: Mesh):
    """(in_specs pytree for LTIState, codebook spec, n_shards)."""
    ax = _all_axes(mesh)
    graph = GraphState(
        vectors=P(ax, None), adjacency=P(ax, None), active=P(ax),
        deleted=P(ax), start=P(ax), n_total=P(ax))
    lti = LTIState(graph=graph, codes=P(ax, None), codebook=None)
    n = 1
    for a in ax:
        n *= mesh.shape[a]
    return lti, P(), n


def abstract_lti(cfg: IndexConfig, pq_cfg: PQConfig, mesh: Mesh,
                 dtype=jnp.float32):
    """Global ShapeDtypeStructs for the sharded LTI (no allocation)."""
    n = len(mesh.devices.flat)
    ax = _all_axes(mesh)
    cap = cfg.capacity * n

    def sds(shape, dt, spec):
        return jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, spec))

    graph = GraphState(
        vectors=sds((cap, cfg.dim), dtype, P(ax, None)),
        adjacency=sds((cap, cfg.R), jnp.int32, P(ax, None)),
        active=sds((cap,), jnp.bool_, P(ax)),
        deleted=sds((cap,), jnp.bool_, P(ax)),
        start=sds((n,), jnp.int32, P(ax)),
        n_total=sds((n,), jnp.int32, P(ax)),
    )
    codebook = pqm.PQCodebook(
        sds((pq_cfg.m, pq_cfg.ksub, pq_cfg.dsub), jnp.float32, P()))
    return LTIState(graph=graph,
                    codes=sds((cap, pq_cfg.m), jnp.uint8, P(ax, None)),
                    codebook=codebook)


def _shard_index(mesh: Mesh):
    """Flat shard id inside shard_map."""
    ax = _all_axes(mesh)
    idx = jnp.int32(0)
    for a in ax:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def make_distributed_search(mesh: Mesh, cfg: IndexConfig, *, k: int,
                            L: int | None = None,
                            beam_width: int | None = None) -> Callable:
    """(lti_global, queries[Q, d] replicated) -> (ids [Q, k], dists [Q, k]).

    Local PQ-navigated beam search on every shard (paper: broadcast), then a
    global top-k merge (all_gather of k candidates per shard — the only
    collective in the read path).
    """
    L = L or cfg.L_search
    W = beam_width or cfg.beam_width
    lti_specs, _, n_shards = shard_specs(mesh)
    ax = _all_axes(mesh)

    def local(lti: LTIState, queries):
        g = lti.graph
        start = g.start[0]
        use_kernel = cfg.kernel_enabled()
        res = beam_search(
            g.adjacency, g.active, start, queries,
            PQBackend(lti.codes, lti.codebook),
            L=L, max_visits=cfg.visits_bound(L), beam_width=W,
            use_kernel=use_kernel)
        reportable = g.active & ~g.deleted
        # exact rerank of the candidate list (paper §5.2: full-precision
        # vectors fetched from the capacity tier re-rank the ADC results —
        # essential when merging coarse ADC distances across shards)
        exact = batch_distances(FullPrecisionBackend(g.vectors), queries,
                                res.ids, use_kernel=use_kernel)
        ids, d = topk_results(res._replace(dists=exact), k, reportable)
        # globalize ids: shard offset into the flat point axis
        offset = _shard_index(mesh) * cfg.capacity
        ids = jnp.where(ids >= 0, ids + offset, ids)
        # merge across shards: gather [n_shards, Q, k] then local top-k
        all_ids = jax.lax.all_gather(ids, ax)      # [s0, s1(, s2), Q, k]
        all_d = jax.lax.all_gather(d, ax)
        Q = queries.shape[0]
        flat_ids = all_ids.reshape(-1, Q, k).transpose(1, 0, 2).reshape(Q, -1)
        flat_d = all_d.reshape(-1, Q, k).transpose(1, 0, 2).reshape(Q, -1)
        order = jnp.argsort(flat_d, axis=1)[:, :k]
        return (jnp.take_along_axis(flat_ids, order, axis=1),
                jnp.take_along_axis(flat_d, order, axis=1))

    lti_specs = LTIState(graph=lti_specs.graph, codes=lti_specs.codes,
                         codebook=pqm.PQCodebook(P()))
    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(lti_specs, P()),
        out_specs=(P(), P()), check_vma=False))


def make_distributed_insert(mesh: Mesh, cfg: IndexConfig,
                            per_shard: int = 32) -> Callable:
    """(lti, new_vecs [B, d] replicated) -> lti with hash-routed inserts.

    Each shard picks the rows hashed to it (up to ``per_shard``), allocates
    free local slots, and runs the in-memory Algorithm 2 against its
    sub-index using full-precision vectors + PQ code updates.  No
    collectives at all — the paper's "updates are routed" path.
    """
    lti_specs, _, n_shards = shard_specs(mesh)
    pq_m = None  # resolved from codes shape at trace time

    def local(lti: LTIState, new_vecs):
        g = lti.graph
        B, dim = new_vecs.shape
        me = _shard_index(mesh)
        owner = ((jnp.arange(B, dtype=jnp.uint32)
                  * jnp.uint32(2654435761)) % n_shards).astype(jnp.int32)
        mine = owner == me
        # select up to per_shard of my rows (top_k over the 0/1 indicator)
        take, rows = jax.lax.top_k(mine.astype(jnp.int32), per_shard)
        rows = jnp.where(take > 0, rows, -1)
        vecs = jnp.where((rows >= 0)[:, None],
                         new_vecs[jnp.maximum(rows, 0)], 0.0)
        # allocate local free slots
        free = ~g.active
        _, slots = jax.lax.top_k(free.astype(jnp.int32), per_shard)
        slots = jnp.where((take > 0) & free[slots], slots, -1)
        new_graph = mem_insert(g._replace(start=g.start[0],
                                          n_total=g.n_total[0]),
                               slots, vecs, cfg)
        codes = pqm.encode(lti.codebook, vecs,
                           PQConfig(dim=dim, m=lti.codes.shape[1],
                                    ksub=lti.codebook.centroids.shape[1]))
        wslots = jnp.where(slots >= 0, slots, g.capacity)
        new_codes = lti.codes.at[wslots].set(codes, mode="drop")
        ng = new_graph._replace(start=new_graph.start[None],
                                n_total=new_graph.n_total[None])
        return LTIState(ng, new_codes, lti.codebook)

    lti_in = LTIState(graph=lti_specs.graph, codes=lti_specs.codes,
                      codebook=pqm.PQCodebook(P()))
    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(lti_in, P()), out_specs=lti_in,
        check_vma=False),
        donate_argnums=(0,))


def make_distributed_merge(mesh: Mesh, cfg: IndexConfig, pq_cfg: PQConfig,
                           *, insert_chunk: int = 256,
                           block: int = 1024,
                           use_sdc: bool = False) -> Callable:
    """(lti, new_vecs [B, d] repl, new_valid [B], delete_mask global)
    -> merged lti.  StreamingMerge runs fully shard-local: each shard
    processes its hash-share of inserts and its slice of the DeleteList.
    Zero collective bytes — merge bandwidth scales linearly with shards.
    """
    lti_specs, _, n_shards = shard_specs(mesh)
    ax = _all_axes(mesh)

    def local(lti: LTIState, new_vecs, new_valid, delete_mask):
        g = lti.graph
        B = new_vecs.shape[0]
        per_shard = max(B // n_shards * 4, 8)
        me = _shard_index(mesh)
        owner = ((jnp.arange(B, dtype=jnp.uint32)
                  * jnp.uint32(2654435761)) % n_shards).astype(jnp.int32)
        mine = (owner == me) & new_valid
        take, rows = jax.lax.top_k(mine.astype(jnp.int32), per_shard)
        rows = jnp.where(take > 0, rows, -1)
        vecs = jnp.where((rows >= 0)[:, None],
                         new_vecs[jnp.maximum(rows, 0)], 0.0)
        local_lti = LTIState(
            g._replace(start=g.start[0], n_total=g.n_total[0]),
            lti.codes, lti.codebook)
        merged, _stats = streaming_merge(
            local_lti, vecs, take > 0, delete_mask, cfg, pq_cfg,
            insert_chunk=min(insert_chunk, per_shard), block=block,
            use_sdc=use_sdc)
        mg = merged.graph
        mg = mg._replace(start=mg.start[None], n_total=mg.n_total[None])
        return LTIState(mg, merged.codes, merged.codebook)

    lti_in = LTIState(graph=lti_specs.graph, codes=lti_specs.codes,
                      codebook=pqm.PQCodebook(P()))
    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(lti_in, P(), P(), lti_specs.graph.deleted),
        out_specs=lti_in, check_vma=False),
        donate_argnums=(0,))
