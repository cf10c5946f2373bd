"""StreamingMerge (paper §5.3) — fold staged inserts + the DeleteList into the
LTI in three phases, all distances from PQ codes.

  Delete phase — Algorithm 4 over the LTI, block-by-block (sequential pass 1).
  Insert phase — GreedySearch on the intermediate LTI per new point (PQ
      navigation), RobustPrune for its out-edges, back-edges staged as the
      Delta pair list of size O(|N|·R).
  Patch phase — Delta grouped by target and applied block-wise with the
      append-or-prune rule (sequential pass 2).

Faithfulness notes: every distance below is computed from the PQ codes
(decoded centroids), never from full-precision vectors — this is what produces
the paper's small steady-state recall dip (Fig. 4), which our tests reproduce.
In the paper phases 1/3 are sequential SSD passes; here they are block
streams (HBM->VMEM).  Insert-phase searches are vmapped chunks: new points
have no in-edges until the Patch phase, so chunked execution is
order-equivalent to the paper's sequential inserts.

All three phases ride the batched mutation engine: the Delete phase repairs
each block through the ``delete`` module's repair blocks (fused
``delete_repair`` kernel under ``IndexConfig.use_kernel``), the Insert
phase prunes each chunk with ONE ``prune.robust_prune_batch`` call, and the
Patch phase applies Delta through ``insert.patch_block`` — kernel- and
jnp-path outputs are bit-identical (docs/ARCHITECTURE.md, "Mutation
engine").

Delete-phase sweep modes (``repair_mode`` kwarg, None -> ``cfg.repair_mode``):

- ``"global"`` — the whole merge stays ONE jitted device program (the
  historical shape): Algorithm 4 scans every block.
- ``"local"`` — the merge runs as bounded device slices, launched one at a
  time by a ``delete.SliceRunner``, each waited out before the next: a
  TPU cannot preempt a running program, so a search that arrives during
  the merge waits out one slice, not the merge.  The slices, in order:
  one program that marks the DeleteList; the affected-set scan and (full
  precision) the decode of the prune table, over ranges of
  ``delete.RANGE_ROWS`` rows; one repair per ``block`` of affected rows;
  the reclamation of the deleted slots, then one small write of each
  block's repaired rows; one program that allocates slots and stores the
  staged rows; one Insert-phase program per ``insert_chunk``; one that
  groups Delta; one Patch-phase program per ``block`` of targets.  The
  allocation, insert-chunk and patch-block steps are the same traced
  functions the fused path runs inside its scans, every slice has a fixed
  shape (the number of affected rows changes only how many repair and
  write slices run), and outputs are bit-identical to the global merge.
  ``MergeStats.slices`` counts the programs.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import pq as pqm
from .config import IndexConfig, PQConfig
from .delete import (SliceRunner, _repair_slice_codes, _repair_slice_fp,
                     affected_rows, consolidate_deletes,
                     consolidate_deletes_codes, repair_cap_overflow,
                     repair_sliced, row_ranges)
from .distance import INVALID
from .graph import GraphState
from .insert import (apply_back_edges, apply_back_edges_codes,
                     compute_insert_edges, patch_block, patch_plan)
from .locality import locality_order, next_bucket
from .lti import LTIState
from .prune import FullPrecisionPrune, SDCPrune, robust_prune_batch
from .search import PQBackend, beam_search

# Expansion cap of the SDC delete repair (candidate width R + cap*R);
# overflows — nodes with more deleted out-neighbors than the cap — are
# counted into MergeStats.repair_cap_overflows.
SDC_REPAIR_CAP = 8

# Mirrors ``storage.layout.BLOCK_BYTES`` (the 4KB SSD-sector granularity of
# topology.bin) without importing the storage tier into core: the locality
# merge's slot placement groups new rows by this block size so the delta
# patch dirties as few blocks as possible.
TOPOLOGY_BLOCK_BYTES = 4096


class MergeStats(NamedTuple):
    n_deleted: jax.Array
    n_inserted: jax.Array
    n_backedge_pairs: jax.Array
    slots: jax.Array            # [Nn] slot assigned per staged row (INVALID ok)
    repair_cap_overflows: jax.Array  # nodes whose SDC repair dropped >=1
    #   expansion ball (deleted out-neighbors > SDC_REPAIR_CAP); always 0
    #   on the full-precision (use_sdc=False) path, whose expansion is
    #   uncapped.
    n_backedge_targets: jax.Array    # DISTINCT Delta targets the Patch phase
    #   touched (rows with real work; <= n_backedge_pairs)
    n_prune_rows: jax.Array          # prune-engine rows the Patch phase
    #   LAUNCHED: the fixed-shape worst case min(P, N) on the arrival-order
    #   paths, the sum of measured power-of-two buckets on the locality
    #   path — the number the locality ordering exists to shrink.
    slices: int = 1                  # device programs the merge launched
    #   through its SliceRunner, each waited out before the next; the fused
    #   merge is one program.


class DeletePhase(NamedTuple):
    """The LTI after the Delete phase, and what the later phases need."""
    graph: GraphState          # repaired, deleted slots reclaimed
    n_deleted: jax.Array
    overflow: jax.Array        # MergeStats.repair_cap_overflows
    decoded: Optional[jax.Array]   # [capacity, dim] prune table (not use_sdc)
    tables: Optional[jax.Array]    # SDC tables (use_sdc)


def streaming_merge(
    lti: LTIState,
    new_vecs: jax.Array,        # [Nn, d] staged TempIndex points (rows may be
    new_valid: jax.Array,       # [Nn] bool  masked — fixed-shape staging)
    delete_mask: jax.Array,     # [capacity] bool — DeleteList membership
    cfg: IndexConfig,
    pq_cfg: PQConfig,
    *,
    insert_chunk: int = 256,
    block: int = 1024,
    use_sdc: bool = False,
    repair_mode: str | None = None,
    locality: bool = False,
    locality_seed: int = 0,
) -> tuple[LTIState, MergeStats]:
    """With ``use_sdc`` every prune distance comes straight from the PQ
    codes via symmetric-distance tables (numerically identical to pruning
    on decoded reconstructions, ~16x less HBM traffic, no decoded-table
    buffer) — EXPERIMENTS.md §Perf iteration 1 on the merge cell.

    ``locality=True`` runs Phase 2 on the locality schedule
    (``_streaming_merge_ordered``): staged rows are proximity-ordered by
    ``core.locality.locality_order`` (seeded by ``locality_seed``) and
    inserted as EAGER cluster-ordered chunks — each chunk's Delta is applied
    before the next chunk searches, so cluster mates wire to each other and
    the back-edge patch concentrates onto the just-inserted rows.  Slot
    assignment and topology legitimately differ from the arrival-order
    merge; the contract is recall equivalence + bit-determinism for a fixed
    (inputs, seed), not bit-parity (docs/ARCHITECTURE.md, "Update-path
    locality")."""
    mode = cfg.repair_mode if repair_mode is None else repair_mode
    if locality:
        return _streaming_merge_ordered(
            lti, new_vecs, new_valid, delete_mask, cfg, pq_cfg,
            insert_chunk=insert_chunk, block=block, use_sdc=use_sdc,
            mode=mode, seed=locality_seed)
    if mode == "local":
        return _streaming_merge_local(
            lti, new_vecs, new_valid, delete_mask, cfg, pq_cfg,
            insert_chunk=insert_chunk, block=block, use_sdc=use_sdc)
    return _streaming_merge_fused(
        lti, new_vecs, new_valid, delete_mask, cfg, pq_cfg,
        insert_chunk=insert_chunk, block=block, use_sdc=use_sdc)


@functools.partial(jax.jit, static_argnames=("cfg", "pq_cfg", "insert_chunk",
                                              "block", "use_sdc"))
def _streaming_merge_fused(lti, new_vecs, new_valid, delete_mask, cfg, pq_cfg,
                           *, insert_chunk, block, use_sdc):
    """The historical one-program merge: global Delete phase + phases 2/3,
    all inside a single jit."""
    d = _delete_global(lti, delete_mask, cfg, pq_cfg, block=block,
                       use_sdc=use_sdc)
    return _insert_patch_phases(
        d.graph, lti.codes, lti.codebook, d.decoded, new_vecs, new_valid,
        d.n_deleted, d.overflow, cfg, pq_cfg, insert_chunk=insert_chunk,
        block=block, use_sdc=use_sdc)


def _delete_global(lti, delete_mask, cfg, pq_cfg, *, block, use_sdc):
    """Phase 1 as one traced sweep over every block (Algorithm 4).  Prune
    distances use PQ codes only (paper: "we use the compressed PQ vectors
    ... to calculate the approximate distances")."""
    g = lti.graph
    n_del = (g.active & delete_mask).sum()
    g = g._replace(deleted=g.deleted | (delete_mask & g.active))
    overflow = jnp.int32(0)
    decoded = tables = None
    if use_sdc:
        tables = pqm.sdc_tables(lti.codebook)
        overflow = repair_cap_overflow(
            g.adjacency, g.deleted, g.active & ~g.deleted, SDC_REPAIR_CAP)
        g = consolidate_deletes_codes(g, cfg, lti.codes, tables,
                                      block=block, cap=SDC_REPAIR_CAP,
                                      mode="global")
    else:
        decoded = pqm.decode(lti.codebook, lti.codes, pq_cfg).astype(
            jnp.float32)
        g = consolidate_deletes(g, cfg, block=block, prune_table=decoded,
                                mode="global")
    return DeletePhase(g, n_del, overflow, decoded, tables)


@functools.partial(jax.jit, static_argnames=("use_sdc",))
def _open_delete(g, delete_mask, codebook, *, use_sdc):
    """First slice of the local Delete phase: mark the DeleteList and make
    the prune table (the SDC tables, or the full-precision table the
    decode slices fill).  Returns (deleted, usable, n_del, table)."""
    deleted = g.deleted | (delete_mask & g.active)
    table = (pqm.sdc_tables(codebook) if use_sdc
             else jnp.zeros(g.vectors.shape, jnp.float32))
    return (deleted, g.active & ~deleted, (g.active & delete_mask).sum(),
            table)


@functools.partial(jax.jit, static_argnames=("pq_cfg", "rows"),
                   donate_argnums=(0,))
def _decode_range(table, codebook, codes, lo, *, pq_cfg, rows):
    """Decode rows [lo, lo + rows) of the prune table into ``table``
    (donated: the slices fill one buffer)."""
    part = pqm.decode(codebook, jax.lax.dynamic_slice_in_dim(codes, lo, rows),
                      pq_cfg).astype(jnp.float32)
    return jax.lax.dynamic_update_slice_in_dim(table, part, lo, axis=0)


def _delete_local(lti, delete_mask, cfg, pq_cfg, *, block, use_sdc,
                  run: SliceRunner) -> DeletePhase:
    """Phase 1 as bounded slices (module doc): the localized Algorithm 4,
    bit-identical to ``_delete_global``."""
    g0 = lti.graph
    deleted, usable, n_del, table = run(
        _open_delete, g0, delete_mask, lti.codebook, use_sdc=use_sdc)
    aff, overflow = affected_rows(g0.adjacency, deleted, usable, run,
                                  cap=SDC_REPAIR_CAP if use_sdc else None)
    kernel = cfg.kernel_enabled()
    if use_sdc:
        decoded, tables = None, table

        def repair(ids):
            return _repair_slice_codes(g0.adjacency, lti.codes, tables,
                                       deleted, usable, ids, cfg.alpha,
                                       cfg.R, SDC_REPAIR_CAP, kernel)
    else:
        starts, rows = row_ranges(g0.capacity)
        for lo in starts:
            table = run(_decode_range, table, lti.codebook, lti.codes, lo,
                        pq_cfg=pq_cfg, rows=rows)
        decoded, tables = table, None

        def repair(ids):
            return _repair_slice_fp(g0.adjacency, decoded, deleted, usable,
                                    ids, cfg.alpha, cfg.R, kernel)
    g = repair_sliced(g0._replace(deleted=deleted), aff, block, repair, run)
    return DeletePhase(g, n_del, jnp.int32(overflow), decoded, tables)


def _streaming_merge_local(lti, new_vecs, new_valid, delete_mask, cfg, pq_cfg,
                           *, insert_chunk, block, use_sdc):
    """Localized merge as bounded device slices (module doc); bit-identical
    to the fused global merge."""
    run = SliceRunner()
    d = _delete_local(lti, delete_mask, cfg, pq_cfg, block=block,
                      use_sdc=use_sdc, run=run)
    # The adjacency stays out of the allocation program: returned
    # unchanged it would be copied.
    g, codes, decoded, slots, usable, c_slots, c_vecs = run(
        _allocate_slice, d.graph._replace(adjacency=None), lti.codes,
        lti.codebook, d.decoded, new_vecs, new_valid, pq_cfg,
        insert_chunk=insert_chunk, use_sdc=use_sdc)
    adjacency = d.graph.adjacency
    pairs_j, pairs_p = [], []
    for c in range(c_slots.shape[0]):
        adjacency, pj, pp = run(
            _insert_slice, adjacency, g.active, g.start, usable, codes,
            lti.codebook, d.tables, decoded, c_slots, c_vecs, c, cfg,
            use_sdc=use_sdc)
        pairs_j.append(pj)
        pairs_p.append(pp)
    js, extra, has, n_ins, n_pairs, distinct = run(
        _group_slice, pairs_j, pairs_p, slots, n_slots=g.capacity, R=cfg.R,
        block=block)
    for b in range(js.shape[0]):
        adjacency = run(_patch_slice, adjacency, codes, d.tables, decoded,
                        usable, js, extra, has, b, cfg, use_sdc=use_sdc)
    g = g._replace(adjacency=adjacency)
    P = len(pairs_j) * pairs_j[0].shape[0]
    stats = MergeStats(d.n_deleted, n_ins, n_pairs, slots, d.overflow,
                       distinct, jnp.int32(min(P, g.capacity)),
                       slices=run.count)
    return LTIState(g, codes, lti.codebook), stats


def _allocate(g, old_codes, codebook, decoded, new_vecs, new_valid, pq_cfg,
              *, insert_chunk, use_sdc):
    """Phase 2's opening: free slots for the staged rows, their codes,
    vectors and flags stored, and the rows cut into insert chunks.
    Returns (g, codes, decoded, slots, usable, c_slots, c_vecs)."""
    Nn = new_vecs.shape[0]
    # Allocate free slots for the new points (top_k over the free indicator
    # yields distinct, lowest-first free slots).
    free = ~g.active
    _, slots = jax.lax.top_k(free.astype(jnp.int32), Nn)
    slots = jnp.where(new_valid & (free[slots]), slots, INVALID)
    wslots = jnp.where(slots >= 0, slots, g.capacity)

    new_codes = pqm.encode(codebook, new_vecs, pq_cfg)
    codes = old_codes.at[wslots].set(new_codes, mode="drop")
    vectors = g.vectors.at[wslots].set(
        new_vecs.astype(g.vectors.dtype), mode="drop")
    active = g.active.at[wslots].set(True, mode="drop")
    if not use_sdc:
        decoded = decoded.at[wslots].set(
            pqm.decode(codebook, new_codes, pq_cfg), mode="drop")
    # Re-seed the entry point when the Delete phase emptied the index
    # (start=INVALID sentinel): the first allocated slot seeds this
    # merge's insert searches and every search after the swap.
    first_new = jnp.where((slots >= 0).any(),
                          slots[jnp.argmax(slots >= 0)], INVALID)
    start = jnp.where(g.start < 0, first_new, g.start).astype(jnp.int32)
    g = g._replace(vectors=vectors, active=active, start=start,
                   n_total=jnp.maximum(g.n_total,
                                       jnp.max(jnp.where(slots >= 0, slots, -1)) + 1))
    usable = g.active & ~g.deleted

    n_chunks = max(1, -(-Nn // insert_chunk))
    pad = n_chunks * insert_chunk - Nn
    c_slots = jnp.concatenate([slots, jnp.full((pad,), INVALID, jnp.int32)])
    c_vecs = jnp.concatenate(
        [new_vecs.astype(jnp.float32),
         jnp.zeros((pad, new_vecs.shape[1]), jnp.float32)])
    c_slots = c_slots.reshape(n_chunks, insert_chunk)
    c_vecs = c_vecs.reshape(n_chunks, insert_chunk, -1)
    return g, codes, decoded, slots, usable, c_slots, c_vecs


_allocate_slice = jax.jit(
    _allocate, static_argnames=("pq_cfg", "insert_chunk", "use_sdc"),
    donate_argnums=(3,))


def _insert_chunk(adjacency, active, start, usable, codes, codebook, tables,
                  decoded, sl, vv, cfg, *, use_sdc):
    """One Insert-phase chunk: search + prune + scatter the new rows
    ``sl`` (INVALID pads), returning the adjacency and the chunk's Delta
    pair list."""
    backend = PQBackend(codes, codebook)
    use_kernel = cfg.kernel_enabled()
    N = adjacency.shape[0]
    if use_sdc:
        # search via ADC; prune with d_p = exact-vector ADC and
        # candidate-candidate distances via SDC on codes — one batched
        # prune-engine call per insert chunk (fused kernel under
        # use_kernel).
        res = beam_search(adjacency, active, start, vv, backend,
                          L=cfg.L_build,
                          max_visits=cfg.visits_bound(cfg.L_build),
                          beam_width=cfg.beam_width, use_kernel=use_kernel)
        cand = jnp.concatenate([res.visited, res.ids], axis=1)
        safe = jnp.maximum(cand, 0)
        ok = (cand >= 0) & usable[safe] & (cand != sl[:, None])
        d_p = jax.vmap(
            lambda c, vec: pqm.adc(codes[c], pqm.lut(codebook, vec))
        )(safe, vv)
        new_adj = robust_prune_batch(
            SDCPrune(codes, tables), cand, ok, alpha=cfg.alpha,
            R=cfg.R, use_kernel=use_kernel, d_p=d_p).ids
        src = jnp.broadcast_to(sl[:, None], new_adj.shape).reshape(-1)
    else:
        edges = compute_insert_edges(
            adjacency, active, usable, start, decoded, sl, vv, backend,
            L=cfg.L_build, max_visits=cfg.visits_bound(cfg.L_build),
            alpha=cfg.alpha, R=cfg.R, beam_width=cfg.beam_width,
            use_kernel=use_kernel)
        new_adj = edges.new_adj
        src = edges.pairs_p
    valid = sl >= 0
    new_adj = jnp.where(valid[:, None], new_adj, INVALID)
    adjacency = adjacency.at[jnp.where(valid, sl, N)].set(
        new_adj, mode="drop")
    pj = new_adj.reshape(-1)
    pp = jnp.where(pj >= 0, src, INVALID)
    return adjacency, pj, pp


@functools.partial(jax.jit, static_argnames=("cfg", "use_sdc"),
                   donate_argnums=(0,))
def _insert_slice(adjacency, active, start, usable, codes, codebook, tables,
                  decoded, c_slots, c_vecs, c, cfg, *, use_sdc):
    """Insert chunk ``c`` of ``c_slots``/``c_vecs`` as one program, the
    adjacency donated from chunk to chunk."""
    return _insert_chunk(adjacency, active, start, usable, codes, codebook,
                         tables, decoded, c_slots[c], c_vecs[c], cfg,
                         use_sdc=use_sdc)


def _delta_counts(slots, pairs_j, n_slots):
    """(inserted rows, Delta pairs, DISTINCT Delta targets).  The
    arrival-order Patch launches the fixed-shape worst case min(P, N)
    prune rows regardless of how many targets actually collide — the gap
    between the two numbers is the headroom the locality path cashes in."""
    skey = jnp.sort(jnp.where(pairs_j >= 0, pairs_j, jnp.int32(n_slots)))
    live = skey < n_slots
    distinct = (live & jnp.concatenate(
        [jnp.ones((1,), bool), skey[1:] != skey[:-1]])).sum()
    return ((slots >= 0).sum(), (pairs_j >= 0).sum(),
            distinct.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("n_slots", "R", "block"))
def _group_slice(pairs_j, pairs_p, slots, *, n_slots, R, block):
    """Delta grouped for the patch slices (``insert.patch_plan``), with
    the merge's counts (``_delta_counts``)."""
    pj, pp = jnp.concatenate(pairs_j), jnp.concatenate(pairs_p)
    js, extra, has = patch_plan(pj, pp, n_slots, d_max=R, chunk=block)
    return (js, extra, has) + _delta_counts(slots, pj, n_slots)


@functools.partial(jax.jit, static_argnames=("cfg", "use_sdc"),
                   donate_argnums=(0,))
def _patch_slice(adjacency, codes, tables, decoded, usable, js, extra, has, b,
                 cfg, *, use_sdc):
    """Patch-phase block ``b`` as one program, the adjacency donated from
    block to block."""
    backend = (SDCPrune(codes, tables) if use_sdc
               else FullPrecisionPrune(decoded))
    return patch_block(adjacency, backend, usable, js[b], extra[b], has[b],
                       alpha=cfg.alpha, R=cfg.R,
                       use_kernel=cfg.kernel_enabled())


@functools.partial(jax.jit, static_argnames=("cfg", "pq_cfg", "insert_chunk",
                                              "block", "use_sdc"))
def _insert_patch_phases(g, old_codes, codebook, decoded, new_vecs, new_valid,
                         n_del, overflow, cfg, pq_cfg, *, insert_chunk, block,
                         use_sdc):
    """Phases 2 (Insert) + 3 (Patch) as one program: the fused merge's
    tail, scanning the same step functions the local merge launches one
    slice at a time."""
    tables = pqm.sdc_tables(codebook) if use_sdc else None
    g, codes, decoded, slots, usable, c_slots, c_vecs = _allocate(
        g, old_codes, codebook, decoded, new_vecs, new_valid, pq_cfg,
        insert_chunk=insert_chunk, use_sdc=use_sdc)

    # ---- Phase 2: Insert (random reads against the intermediate LTI) ------
    def insert_block(adjacency, inp):
        adjacency, pj, pp = _insert_chunk(
            adjacency, g.active, g.start, usable, codes, codebook, tables,
            decoded, *inp, cfg, use_sdc=use_sdc)
        return adjacency, (pj, pp)

    adjacency, (pairs_j, pairs_p) = jax.lax.scan(
        insert_block, g.adjacency, (c_slots, c_vecs))
    pairs_j = pairs_j.reshape(-1)   # O(|N|*R) Delta pair list
    pairs_p = pairs_p.reshape(-1)

    # ---- Phase 3: Patch (sequential block pass applying Delta) ------------
    use_kernel = cfg.kernel_enabled()
    if use_sdc:
        adjacency = apply_back_edges_codes(
            adjacency, codes, tables, usable, pairs_j, pairs_p,
            alpha=cfg.alpha, R=cfg.R, chunk=block, use_kernel=use_kernel)
    else:
        adjacency = apply_back_edges(
            adjacency, decoded, usable, pairs_j, pairs_p,
            alpha=cfg.alpha, R=cfg.R, chunk=block, use_kernel=use_kernel)

    g = g._replace(adjacency=adjacency)
    n_ins, n_pairs, distinct = _delta_counts(slots, pairs_j, g.capacity)
    stats = MergeStats(n_del, n_ins, n_pairs, slots,
                       jnp.asarray(overflow, jnp.int32), distinct,
                       jnp.int32(min(pairs_j.shape[0], g.capacity)))
    return LTIState(g, codes, codebook), stats


def _streaming_merge_ordered(lti, new_vecs, new_valid, delete_mask, cfg,
                             pq_cfg, *, insert_chunk, block, use_sdc, mode,
                             seed):
    """Locality-schedule merge: Phase 1 (honoring ``mode``), then
    Phase 2 as cluster-ordered chunks inserted EAGERLY — each chunk's Delta
    is applied before the next chunk searches.

    Why eager: on the arrival-order paths new points have no in-edges until
    the single Phase-3 patch, so chunks cannot see each other and reordering
    alone changes nothing but slot labels.  With per-chunk patching, a
    chunk's searches DO reach its earlier-inserted cluster mates, so its
    out-edges and back-edges land on the new rows (being rewritten anyway)
    instead of scattering across the old graph — ``adjacency_delta_mask``
    shrinks, and ``patch_layout`` rewrites measurably fewer rows/bytes.
    Each chunk's Delta prune also launches at a measured power-of-two
    bucket (host-counted distinct targets -> ``affected_cap``) instead of
    the fixed-shape worst case.

    The host round-trip per chunk (distinct-target count) is the price of
    the dynamic launch size; ``MergeStats.n_prune_rows`` records the
    realized total so benchmarks can weigh it against the arrival-order
    worst case.  Deterministic for fixed (inputs, seed): the ordering is
    seeded, chunking is sequential, and every launch size is a pure
    function of the data."""
    codebook = lti.codebook
    run = SliceRunner()

    # ---- Phase 1: Delete (identical to the arrival-order paths) -----------
    if mode == "local":
        d = _delete_local(lti, delete_mask, cfg, pq_cfg, block=block,
                          use_sdc=use_sdc, run=run)
    else:
        d = _delete_global(lti, delete_mask, cfg, pq_cfg, block=block,
                           use_sdc=use_sdc)
    g, tables = d.graph, d.tables

    # ---- Phase 2a: order + allocate + store (one jitted stage) ------------
    # Rows the Delete phase already rewrote (freed slots go all-INVALID,
    # repaired neighbors change) mark their 4KB topology blocks dirty for
    # this merge's patch: placing new points there costs ZERO extra block
    # writes (the DGAI placement observation).
    phase1_dirty = run(adjacency_delta_mask, lti.graph.adjacency,
                       g.adjacency)
    g, codes, decoded, slots_ord, ord_vecs, perm = run(
        _locality_stage, g, lti.codes, codebook, d.decoded, new_vecs,
        new_valid, phase1_dirty, jax.random.PRNGKey(seed), cfg, pq_cfg,
        use_sdc=use_sdc)
    usable = g.active & ~g.deleted

    Nn = new_vecs.shape[0]
    n_chunks = max(1, -(-Nn // insert_chunk))
    pad = n_chunks * insert_chunk - Nn
    c_slots = jnp.concatenate(
        [slots_ord, jnp.full((pad,), INVALID, jnp.int32)]
    ).reshape(n_chunks, insert_chunk)
    c_vecs = jnp.concatenate(
        [ord_vecs.astype(jnp.float32),
         jnp.zeros((pad, new_vecs.shape[1]), jnp.float32)]
    ).reshape(n_chunks, insert_chunk, -1)

    # ---- Phase 2b/3: eager chunk loop, per-chunk Delta patch --------------
    adjacency = g.adjacency
    n_pairs = n_targets = n_rows = 0
    cap_max = min(insert_chunk * cfg.R, g.capacity)
    for c in range(n_chunks):
        adjacency, pj, pp = run(
            _insert_slice, adjacency, g.active, g.start, usable, codes,
            codebook, tables, decoded, c_slots, c_vecs, c, cfg,
            use_sdc=use_sdc)
        pj_h = np.asarray(pj)
        d_c = int(np.unique(pj_h[pj_h >= 0]).size)
        n_pairs += int((pj_h >= 0).sum())
        if d_c == 0:
            continue
        bucket = next_bucket(d_c, cap=cap_max)
        n_targets += d_c
        n_rows += bucket
        adjacency = run(
            _ordered_patch, adjacency, codes, tables, decoded, usable, pj,
            pp, cfg, bucket=bucket, block=block, use_sdc=use_sdc)
    g = g._replace(adjacency=adjacency)

    # Report slots in ORIGINAL row order (perm is a permutation, so the
    # scatter covers every entry): staged row i landed in slot
    # slots_orig[i], whatever position the ordering gave it.
    slots_orig = jnp.full((Nn,), INVALID, jnp.int32).at[perm].set(slots_ord)
    stats = MergeStats(d.n_deleted, (slots_ord >= 0).sum(),
                       jnp.int32(n_pairs), slots_orig,
                       jnp.asarray(d.overflow, jnp.int32),
                       jnp.int32(n_targets), jnp.int32(n_rows),
                       slices=run.count)
    return LTIState(g, codes, codebook), stats


@functools.partial(jax.jit, static_argnames=("cfg", "pq_cfg", "use_sdc"))
def _locality_stage(g, old_codes, codebook, decoded, new_vecs, new_valid,
                    phase1_dirty, key, cfg, pq_cfg, *, use_sdc):
    """Proximity-order the staged rows, allocate slots along the ordering,
    and store vectors/codes/flags — the ordered twin of ``_allocate``.

    Slot placement is locality-aware (two DGAI-style effects on the delta
    patch): free slots inside ALREADY-DIRTY 4KB topology blocks
    (``phase1_dirty`` rows — this merge's delete repairs and freed slots)
    are consumed first, since new rows there ride block writes the patch
    must issue anyway; the remainder fills fresh blocks in ascending order.
    Rows consume slots in cluster order, so cluster mates land CONTIGUOUS —
    which a LATER merge inserting near the same clusters cashes in, its
    back-edge targets then occupying few distinct blocks.  The count of
    allocated slots (and validity masking) matches the arrival-order merge;
    only the placement differs."""
    k = cfg.locality_clusters or 16
    perm = locality_order(new_vecs.astype(jnp.float32), new_valid,
                          n_clusters=k, key=key)
    ord_vecs = new_vecs[perm]
    ord_valid = new_valid[perm]
    Nn = ord_vecs.shape[0]
    cap = g.capacity
    free = ~g.active
    rpb = max(1, TOPOLOGY_BLOCK_BYTES // (cfg.R * 4))
    blk = jnp.arange(cap, dtype=jnp.int32) // rpb
    n_blocks = -(-cap // rpb)
    block_dirty = jnp.zeros((n_blocks,), jnp.int32).at[blk].add(
        phase1_dirty.astype(jnp.int32)) > 0
    # Rank: free slots in dirty blocks ascending, then free slots in clean
    # blocks ascending, then occupied slots (never taken — masked below).
    arange = jnp.arange(cap, dtype=jnp.int32)
    rank = jnp.where(block_dirty[blk], arange, cap + arange)
    rank = jnp.where(free, rank, 2 * cap)
    slots = jnp.argsort(rank)[:Nn].astype(jnp.int32)
    slots = jnp.where(ord_valid & free[slots], slots, INVALID)
    wslots = jnp.where(slots >= 0, slots, g.capacity)
    new_codes = pqm.encode(codebook, ord_vecs, pq_cfg)
    codes = old_codes.at[wslots].set(new_codes, mode="drop")
    vectors = g.vectors.at[wslots].set(
        ord_vecs.astype(g.vectors.dtype), mode="drop")
    active = g.active.at[wslots].set(True, mode="drop")
    if not use_sdc:
        decoded = decoded.at[wslots].set(
            pqm.decode(codebook, new_codes, pq_cfg), mode="drop")
    first_new = jnp.where((slots >= 0).any(),
                          slots[jnp.argmax(slots >= 0)], INVALID)
    start = jnp.where(g.start < 0, first_new, g.start).astype(jnp.int32)
    g = g._replace(vectors=vectors, active=active, start=start,
                   n_total=jnp.maximum(
                       g.n_total,
                       jnp.max(jnp.where(slots >= 0, slots, -1)) + 1))
    return g, codes, decoded, slots, ord_vecs, perm


@functools.partial(jax.jit, static_argnames=("cfg", "bucket", "block",
                                             "use_sdc"))
def _ordered_patch(adjacency, codes, tables, decoded, usable, pj, pp, cfg, *,
                   bucket, block, use_sdc):
    """Per-chunk Delta application at a measured launch size: ``bucket``
    (static, power of two, >= the chunk's distinct target count) bounds the
    grouped prune to the rows that actually have work."""
    use_kernel = cfg.kernel_enabled()
    if use_sdc:
        return apply_back_edges_codes(
            adjacency, codes, tables, usable, pj, pp,
            alpha=cfg.alpha, R=cfg.R, chunk=block, use_kernel=use_kernel,
            affected_cap=bucket)
    return apply_back_edges(
        adjacency, decoded, usable, pj, pp,
        alpha=cfg.alpha, R=cfg.R, chunk=block, use_kernel=use_kernel,
        affected_cap=bucket)


@jax.jit
def adjacency_delta_mask(old_adj: jax.Array, new_adj: jax.Array) -> jax.Array:
    """[capacity] bool — rows the merge actually rewrote.

    A StreamingMerge touches only the delete-repaired, inserted, and
    back-edge-patched rows; everything else is bit-identical to the old
    adjacency.  The mask computes on device (one elementwise compare +
    row-reduce over the arrays the merge already holds) and drives the
    DGAI-style delta topology patch (``storage.layout.patch_layout``): only
    masked rows are rewritten in ``topology.bin``, and the vector file is
    untouched for surviving points."""
    return jnp.any(old_adj != new_adj, axis=1)
