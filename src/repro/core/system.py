"""The FreshDiskANN system (paper §5): LTI + RW/RO-TempIndex + DeleteList +
WAL, with the StreamingMerge cycle and optional background merging.

JAX's functional state makes the paper's trickiest concurrency concern —
searching while a merge is underway — safe by construction: a merge produces a
*new* LTI value while searches keep reading the old immutable arrays; the swap
is a single reference assignment (the paper needs careful SSD double-buffering
for the same effect).  The (LTI, external-id table) pair is swapped as ONE
tuple so a concurrent search never pairs a new graph with a stale table, and
the RO snapshots being merged stay searchable until that swap lands — a
search during a merge sees every point in exactly one consistent place (or
transiently in two, which the cross-tier dedupe in ``_aggregate`` resolves).

Query fan-out (§5.2): a query must consult the LTI *and* every TempIndex.
``search_batch`` serves a whole query batch: all live tiers — the RW tier,
every frozen RO snapshot, AND the PQ-navigated LTI — are folded into one
heterogeneous ``LaneStack`` (``graph.stack_lanes``) and the B queries ride
ONE jitted device program (``index.unified_search``): the temp tiers as a
vmapped exact-L2 group padded to the largest TEMP capacity, the LTI lane at
its own capacity on PQ ADC, then the LTI's exact rerank, the per-group
slot->external-id mapping, the DeleteList filter, and the cross-tier top-k
merge all on-device, every stage vmapped over the query axis.  The stack
and the DeleteList drop-mask are cached between mutations, so a pure query
workload pays one dispatch per micro-batch however many snapshots
accumulate (``SystemConfig.batch_queries`` fixes the micro-batch width;
``SystemConfig.shard_lti`` row-shards the LTI lane over the mesh data axis
with bit-identical results — serving guide: docs/SERVING.md).
``SystemConfig.batch_fanout=False`` restores the fully sequential per-tier
loop + host-side aggregation (the bit-parity oracle for tests): both paths
return bit-identical (ids, dists).  See docs/ARCHITECTURE.md for the full
picture.

External ids are user-provided int64s; the system maps them to (tier, slot).
"""
from __future__ import annotations

import ctypes
import dataclasses
import os
import pickle
import threading
import time
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import autotune
from . import index as mem
from . import pq as pqm
from .config import IndexConfig, PQConfig, SystemConfig
from .distance import INVALID
from .graph import (NO_TENANT, FilterSpec, GraphState, LabelTable,
                    empty_graph, filter_match, pack_labels, pad_graph,
                    stack_lanes)
from .locality import locality_order, next_bucket
from .lti import LTIState, build_lti, search_lti
from .merge import streaming_merge
from .reach import unreachable_fraction
from .wal import WriteAheadLog, log_epoch, replay


@dataclass
class _Temp:
    """One TempIndex instance + its slot<->external-id maps."""
    state: GraphState
    ext_ids: np.ndarray           # [capacity] int64, -1 free
    n: int = 0
    labels: Optional[LabelTable] = None  # per-slot label bitsets + tenant
    #   ids, row-parallel to ext_ids (filtered/multi-tenant search)


LATENCY_RESERVOIR = 1024

# The serving micro-batch being dispatched: ``BatchScheduler.dispatch`` sets
# its sequence number for the duration of the serve call, and every search
# span of that call carries it as ``batch`` (-1 outside the scheduler).
# docs/SERVING.md, "Tracing", lists the spans.
serving_batch: ContextVar[int] = ContextVar("serving_batch", default=-1)


def lane_hops_metadata(span: TraceAnnotation, hops) -> None:
    """Put the LTI lane's hop counts (the last row of a unified search's
    ``hops`` [T, B]) on a ``fdann.search.device`` span: ``max_hops`` is the
    trip count of the vmapped beam loop.  Reads a device value, so callers
    run it only under ``TraceAnnotation.is_enabled()``."""
    h = np.asarray(hops)[-1]
    span.set_metadata(max_hops=int(h.max()), p50_hops=float(np.median(h)))


def name_os_thread(name: str) -> None:
    """Name the calling thread at the OS level (Linux ``PR_SET_NAME``, at
    most 15 bytes), so a profiler trace shows its spans on a line of that
    name; Python's ``threading`` names only its own object.  A no-op where
    the C library has no ``prctl``."""
    try:
        prctl = ctypes.CDLL(None).prctl
    except (AttributeError, OSError):
        return
    prctl.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_ulong,
                      ctypes.c_ulong, ctypes.c_ulong]
    prctl.restype = ctypes.c_int
    prctl(15, name.encode()[:15], 0, 0, 0)      # PR_SET_NAME


class Reservoir:
    """Fixed-size uniform sample of an unbounded stream (Vitter's
    algorithm R) with percentile snapshots over the retained sample.

    Every element of the stream has probability ``size / seen`` of being
    in the sample at any point, so ``percentile`` is an unbiased estimate
    of the stream percentile in O(size) memory however long we run — and
    EXACT while ``seen <= size`` (the sample is then the whole stream).
    The serving-latency contract tests live in ``tests/test_scheduler.py``.
    """

    def __init__(self, size: int = LATENCY_RESERVOIR, seed: int = 0):
        self.size = size
        self.sample: list = []
        self.seen = 0
        self._rng = np.random.default_rng(seed)

    def record(self, x: float) -> None:
        self.seen += 1
        if len(self.sample) < self.size:
            self.sample.append(x)
        else:
            j = int(self._rng.integers(self.seen))
            if j < self.size:
                self.sample[j] = x

    def percentile(self, p: float) -> float:
        """Linear-interpolated percentile of the sample (NaN when empty)."""
        if not self.sample:
            return float("nan")
        return float(np.percentile(self.sample, p))

    def snapshot(self) -> dict:
        """{p50, p99, n} — the reservoir-backed percentile snapshot the
        serving benchmarks and the stats surface report."""
        return {"p50": self.percentile(50.0), "p99": self.percentile(99.0),
                "n": self.seen}


@dataclass
class SystemStats:
    inserts: int = 0
    deletes: int = 0
    searches: int = 0
    merges: int = 0
    snapshots: int = 0
    merge_seconds: float = 0.0
    merge_slices: int = 0       # device programs the merges ran one at a
    #   time, each waited out before the next (MergeStats.slices)
    # Jitted device programs launched by the query path (the §5.2 fan-out's
    # serving-cost metric).  Contract under batching: B queries served in
    # one launch count ONE dispatch — the unified path pays 1 per
    # micro-batch (ceil(B / batch_queries) per request batch when
    # micro-batching is on, else 1), the sequential oracle pays 1 per live
    # tier per micro-batch.  `searches` counts queries; dispatches count
    # programs — divide for dispatches-per-query (benchmarks report both).
    # Flush/autotune dispatches are not counted — this tracks the
    # steady-state query path only.
    search_dispatches: int = 0
    # Storage-tier IO accounting (``cfg.storage_dir`` — docs/STORAGE.md).
    # Rows obey the conservation law of core/search.py's counter contract:
    # io_rows_read + io_cache_hits == rows the engine requested.
    io_rows_read: int = 0       # adjacency rows fetched off topology.bin
    #   (demand reads + prefetch-staged reads — the engine's n_reads)
    io_cache_hits: int = 0      # rows served by the block cache, no file IO
    io_prefetch_hits: int = 0   # ... of io_rows_read, staged ahead by the
    #   prefetch pipeline (IO overlapped off the critical path)
    io_bytes_read: int = 0      # topology.bin bytes read (whole blocks)
    storage_rows_patched: int = 0    # adjacency rows rewritten by the
    #   DGAI-style delta patches StreamingMerge issues
    storage_blocks_patched: int = 0  # DISTINCT 4KB topology blocks those
    #   rows live in — the real SSD write granularity; what the locality
    #   merge's dirty-block-first slot placement shrinks
    storage_bytes_written: int = 0   # bytes those patches (and full layout
    #   writes) put on disk
    # Localized delete repair + reachability monitor (docs/ARCHITECTURE.md,
    # "Localized delete repair").
    local_repairs: int = 0      # Delete phases run as the localized
    #   affected-set sweep (delete rate <= cfg.local_repair_threshold)
    global_repairs: int = 0     # Delete phases run as the global sweep
    consolidations: int = 0     # standalone consolidate() calls (Algorithm 4
    #   on the LTI outside a merge)
    repair_cap_overflows: int = 0  # nodes whose SDC delete repair had more
    #   deleted out-neighbors than the expansion cap (merge.SDC_REPAIR_CAP)
    #   — each dropped >=1 expansion ball; deleted edges are still pruned.
    reach_probes: int = 0       # reachability probes run (sampled self-search
    #   of live LTI points after merges/consolidations)
    repair_escalations: int = 0 # localized repairs whose probe exceeded
    #   cfg.reach_escalate_frac, forcing the next Delete phase global
    unreachable_frac: float = 0.0  # gauge: latest probe's estimate of the
    #   unreachable-live-point fraction (0.0 until the first probe)
    # Update-path locality (core/locality.py — docs/ARCHITECTURE.md,
    # "Update-path locality").  Counters accumulate whether
    # cfg.locality_order is on or off, so on/off runs are directly
    # comparable: targets counts DISTINCT back-edge rows with real work,
    # prune_rows counts rows the grouped Delta prune actually LAUNCHED
    # (worst-case min(P, N) on the arrival-order paths, measured
    # power-of-two buckets on the locality paths).
    flushes: int = 0                 # RW-tier buffer flushes
    flush_backedge_targets: int = 0  # distinct Delta targets across flushes
    flush_prune_rows: int = 0        # prune rows launched by flush Deltas
    merge_backedge_targets: int = 0  # distinct Delta targets across merges
    merge_prune_rows: int = 0        # prune rows launched by merge Patches
    # Continuous-batching serving front end (serving/scheduler.py —
    # docs/SERVING.md, "The serving loop").  Counters are owned here so one
    # stats surface covers queue, batch and dispatch behavior; the
    # scheduler updates them under its own lock.
    scheduled_requests: int = 0  # requests admitted to the serving queue
    shed_requests: int = 0       # requests REJECTED by queue backpressure
    #   (queue at cfg.serve_queue_capacity) — the bounded-queue contract:
    #   overload sheds explicitly instead of growing latency without bound
    batches_dispatched: int = 0  # micro-batches the scheduler closed and
    #   served (each is >= 1 and <= cfg.batch_queries requests)
    deadline_misses: int = 0     # requests completing after arrival +
    #   cfg.slo_ms (deadline-aware close aims the dispatch estimate at
    #   making this 0; late polls and underestimates land here)
    queue_depth: int = 0         # gauge: pending requests after the last
    #   scheduler submit/close (the backpressure observable)
    batch_occupancy: float = 0.0  # gauge: fill fraction (n / batch_queries)
    #   of the last dispatched micro-batch — 1.0 when batches close full,
    #   lower when the deadline closes them early
    # Filtered & multi-tenant search (docs/ARCHITECTURE.md, "Filtered &
    # multi-tenant search").
    filtered_searches: int = 0   # queries served under a non-empty
    #   FilterSpec (label predicate and/or tenant restriction)
    tenant_searches: dict = field(default_factory=dict)  # tenant id ->
    #   queries served under that tenant's mandatory filter
    tenant_sheds: dict = field(default_factory=dict)     # tenant id ->
    #   submissions SHED by the per-tenant quota (cfg.tenant_quota);
    #   every one also counts in shed_requests (the global total)
    # Latency reservoirs (Vitter's algorithm R, see ``Reservoir``): uniform
    # samples in O(LATENCY_RESERVOIR) memory however long we run, each
    # reporting p50/p99 via ``.snapshot()``.
    #   insert_latency  — per insert() call (WAL append + buffer append
    #                     ONLY; the amortized flush is sampled separately,
    #                     so per-insert p99 reflects the steady-state cost)
    #   flush_latency   — per buffer flush (device-side insert of one
    #                     drained buffer), sampled once per flush
    #   search_latency  — per dispatched search micro-batch (device program
    #                     wall time, recorded inside _search_dispatch)
    #   serve_latency   — per scheduled request, arrival -> completion on
    #                     the scheduler's clock (queue wait + dispatch)
    insert_latency: Reservoir = field(default_factory=Reservoir, repr=False)
    search_latency: Reservoir = field(
        default_factory=lambda: Reservoir(seed=1), repr=False)
    serve_latency: Reservoir = field(
        default_factory=lambda: Reservoir(seed=2), repr=False)
    flush_latency: Reservoir = field(
        default_factory=lambda: Reservoir(seed=3), repr=False)

    def serving_snapshot(self) -> dict:
        """One structured view of the serving surface: p50/p99 for each
        latency reservoir plus the queue/batch counters — what the serving
        benchmarks emit as machine-readable fields."""
        return {
            "search": self.search_latency.snapshot(),
            "serve": self.serve_latency.snapshot(),
            "insert": self.insert_latency.snapshot(),
            "flush": self.flush_latency.snapshot(),
            "flushes": self.flushes,
            "scheduled_requests": self.scheduled_requests,
            "shed_requests": self.shed_requests,
            "batches_dispatched": self.batches_dispatched,
            "deadline_misses": self.deadline_misses,
            "queue_depth": self.queue_depth,
            "batch_occupancy": self.batch_occupancy,
            "filtered_searches": self.filtered_searches,
            "tenant_searches": dict(self.tenant_searches),
            "tenant_sheds": dict(self.tenant_sheds),
        }


class FreshDiskANN:
    def __init__(self, cfg: SystemConfig, lti: Optional[LTIState] = None,
                 lti_ext_ids: Optional[np.ndarray] = None):
        self.cfg = cfg
        icfg = cfg.index
        # Everything except capacity mirrors the LTI's config: the unified
        # fan-out searches temp lanes and the LTI lane with ONE IndexConfig
        # (visit bounds, dtype, kernel routing), so any field that diverged
        # here would break the bit-parity contract with the sequential
        # oracle (which searches temp tiers with THIS config).
        self.temp_cfg = dataclasses.replace(icfg, capacity=cfg.temp_capacity)
        if lti is None:
            g = empty_graph(icfg)
            cb = pqm.PQCodebook(jnp.zeros(
                (cfg.pq.m, cfg.pq.ksub, cfg.pq.dsub), jnp.float32))
            lti = LTIState(g, jnp.zeros((icfg.capacity, cfg.pq.m), jnp.uint8), cb)
        # The LTI, its external-id table AND its label table are
        # read/swapped as ONE tuple so a search concurrent with a merge
        # never mixes generations.
        self._n_label_words = cfg.filter_words
        self._lti_pair: tuple[LTIState, np.ndarray, LabelTable] = (
            lti, lti_ext_ids if lti_ext_ids is not None
            else np.full(icfg.capacity, -1, np.int64),
            LabelTable(icfg.capacity, cfg.filter_words))
        self.rw = self._new_temp()
        self.ro: list[_Temp] = []
        self.deleted_ext: set[int] = set()
        self._ext_loc: dict[int, tuple] = {}
        if lti_ext_ids is not None:
            for slot, e in enumerate(lti_ext_ids):
                if e >= 0:
                    self._ext_loc[int(e)] = ("lti", slot)
        self._insert_buf_v: list[np.ndarray] = []
        self._insert_buf_id: list[int] = []
        self._insert_buf_bits: list[np.ndarray] = []   # packed label rows
        self._insert_buf_tenant: list[int] = []        # NO_TENANT default
        self._wal_offset: Optional[int] = None  # WAL bytes a snapshot covers
        self._wal_epoch: Optional[int] = None   # ... and of which log epoch
        self.stats = SystemStats()
        self._merge_lock = threading.Lock()
        self._ro_lock = threading.Lock()     # guards self.ro mutations
        # Guards the insert buffer and RW-tier BOOKKEEPING (buffer append /
        # swap, DeleteList edits, ext-id maps).  The device-side flush
        # compute runs OUTSIDE it (under _flush_lock only), so concurrent
        # insert/delete/search calls are never blocked for a whole flush.
        # RLock: save -> _flush_inserts nests under it.
        self._insert_lock = threading.RLock()
        # Serializes flushes end to end: buffer swap + device compute +
        # RW-tier publish.  Anything that must observe a QUIESCED flush
        # path (save/snapshot, rollover freeze) takes it first.  Canonical
        # lock order everywhere: _flush_lock -> _insert_lock -> _ro_lock —
        # never acquire a lock to the LEFT of one you hold.  RLock:
        # rollover/save -> _flush_inserts nest.
        self._flush_lock = threading.RLock()
        self._flush_seq = 0                  # locality-order seed per flush
        self._merge_inflight = 0             # staged points being merged now
        self._merge_thread: Optional[threading.Thread] = None
        self._merge_error: Optional[BaseException] = None  # from the thread
        self._force_global_repair = False    # set when a reachability probe
        #   after a localized repair degrades past cfg.reach_escalate_frac
        #   above the baseline; the next Delete phase then runs the global
        #   sweep and clears it.
        self._reach_baseline: Optional[float] = None  # probe estimate after
        #   the last global sweep (or the first probe ever) — what a
        #   localized repair's probe is compared against.
        self._tuned_w: Optional[int] = None  # cached autotuned beam width
        # Unified-fan-out caches: the LaneStack + ext-id tables (keyed by
        # tier-state identity — states are immutable values, so a flush /
        # rollover / merge replaces them and misses the cache) and the
        # DeleteList drop-mask (additionally keyed by _delete_epoch, bumped
        # on every DeleteList mutation the tier states don't witness).
        self._fanout_cache: Optional[tuple] = None
        self._frozen_cache: Optional[tuple] = None
        self._drop_cache: Optional[tuple] = None
        # Filtered drop-masks: (key, epoch, {FilterSpec: drop}) — one dict
        # of per-spec masks per (lane census, delete epoch); any tier or
        # DeleteList mutation retires the whole dict.
        self._filter_cache: Optional[tuple] = None
        self._delete_epoch = 0
        self._int32_warned = False
        # Sharded-LTI-lane caches (cfg.shard_lti — see _sharded_program).
        self._shard_mesh = None
        self._shard_mesh_n = 0
        self._shard_place: Optional[tuple] = None
        self._shard_steps: dict = {}
        self.wal: Optional[WriteAheadLog] = None
        if cfg.wal_dir:
            os.makedirs(cfg.wal_dir, exist_ok=True)
            self.wal = WriteAheadLog(
                os.path.join(cfg.wal_dir, "wal.bin"), icfg.dim)
        # Decoupled storage tier (cfg.storage_dir — docs/STORAGE.md): the
        # live layout mirrors the LTI, the searcher over it is cached per
        # layout generation (a sync closes it; reopened lazily).
        self._disk_searcher = None
        if cfg.storage_dir:
            self._sync_storage()

    # The pair is the source of truth; the individual attributes remain for
    # the non-concurrent paths (init, load, recover) and for inspection.
    @property
    def lti(self) -> LTIState:
        return self._lti_pair[0]

    @lti.setter
    def lti(self, value: LTIState) -> None:
        self._lti_pair = (value, self._lti_pair[1], self._lti_pair[2])

    @property
    def lti_ext_ids(self) -> np.ndarray:
        return self._lti_pair[1]

    @lti_ext_ids.setter
    def lti_ext_ids(self, value: np.ndarray) -> None:
        self._lti_pair = (self._lti_pair[0], value, self._lti_pair[2])

    @property
    def lti_labels(self) -> LabelTable:
        return self._lti_pair[2]

    @lti_labels.setter
    def lti_labels(self, value: LabelTable) -> None:
        self._lti_pair = (self._lti_pair[0], self._lti_pair[1], value)

    # ------------------------------------------------------------------ API
    def insert(self, ext_id: int, vec: np.ndarray, labels=None,
               tenant: Optional[int] = None) -> None:
        """Route to the RW-TempIndex (paper §5.2); batched flush.

        ``labels`` is an optional iterable of label bit indices (packed
        into ``cfg.filter_words`` uint32 words — filtered search matches
        against them); ``tenant`` tags the point with an owning tenant id
        (a mandatory filter under multi-tenancy).  Both ride the WAL as a
        labeled-insert record, the insert buffer, and every tier's label
        table, so they follow the point across its whole lifecycle.

        The lock hold covers only the WAL append + buffer append; the
        device-side flush (when this insert fills the batch) runs after the
        lock is RELEASED, under ``_flush_lock``, so concurrent
        insert/delete/search calls are not blocked for a whole flush.
        ``insert_latency`` therefore samples the bookkeeping cost only —
        the amortized flush lands in ``flush_latency``, once per flush.
        """
        bits = (pack_labels(labels, self._n_label_words)
                if labels else None)
        ten = NO_TENANT if tenant is None else int(tenant)
        t0 = time.perf_counter()
        with self._insert_lock:
            if self.wal:
                if bits is not None or ten != NO_TENANT:
                    self.wal.log_insert_labeled(
                        ext_id, vec, ten,
                        bits if bits is not None else
                        np.zeros(self._n_label_words, np.uint32))
                else:
                    self.wal.log_insert(ext_id, vec)
            self._insert_buf_id.append(int(ext_id))
            self._insert_buf_v.append(np.asarray(vec, np.float32))
            self._insert_buf_bits.append(
                bits if bits is not None else
                np.zeros(self._n_label_words, np.uint32))
            self._insert_buf_tenant.append(ten)
            # Re-insert revives the id immediately (not just at flush time),
            # so `size` and the DeleteList agree while the point is buffered.
            if int(ext_id) in self.deleted_ext:
                self.deleted_ext.discard(int(ext_id))
                self._delete_epoch += 1  # drop-mask caches must see the revive
            full = len(self._insert_buf_id) >= self.cfg.insert_batch
        self.stats.inserts += 1
        self.stats.insert_latency.record(time.perf_counter() - t0)
        if full:
            self._flush_inserts()
        self._maybe_rollover()

    def delete(self, ext_id: int) -> None:
        """DeleteList append — O(1), no graph edits (paper §4.2)."""
        with self._insert_lock:
            if self.wal:
                self.wal.log_delete(ext_id)
            e = int(ext_id)
            if e in self._insert_buf_id:
                # The point only exists in the insert buffer: drop it there,
                # or the next flush would revive the id (flush discards the
                # delete to implement re-insert-after-delete) and invert the
                # op order.
                keep = [i for i, x in enumerate(self._insert_buf_id)
                        if x != e]
                self._insert_buf_id = [self._insert_buf_id[i] for i in keep]
                self._insert_buf_v = [self._insert_buf_v[i] for i in keep]
                self._insert_buf_bits = [self._insert_buf_bits[i]
                                         for i in keep]
                self._insert_buf_tenant = [self._insert_buf_tenant[i]
                                           for i in keep]
            self.deleted_ext.add(e)
            self._delete_epoch += 1    # invalidate cached drop-masks
        self.stats.deletes += 1

    def search(self, queries: np.ndarray, k: int, L: Optional[int] = None,
               beam_width: Optional[int] = None
               ) -> tuple[np.ndarray, np.ndarray]:
        """Compatibility alias for ``search_batch`` (the canonical serving
        entry point since the batched engine landed — see docs/SERVING.md)."""
        return self.search_batch(queries, k, L=L, beam_width=beam_width)

    def search_batch(self, queries: np.ndarray, k: int,
                     L: Optional[int] = None,
                     beam_width: Optional[int] = None,
                     filter: Optional[FilterSpec] = None
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Serve a whole query batch: LTI + every TempIndex, aggregate,
        filter DeleteList (§5.2).  Returns (ext_ids [B, k], dists [B, k]).

        The B queries ride the unified fan-out as ONE jitted device
        program (per micro-batch — see below): every lane's beam search is
        vmapped over the query axis, so B queries in one launch cost one
        dispatch, not B.  Per-query results are bit-identical to serving
        each query alone (vmap semantics; the per-query / sequential-tier
        oracle suite is ``tests/test_serving.py``).

        ``cfg.batch_queries`` micro-batches the request: N > 0 serves the
        batch in fixed-shape chunks of N queries (tail chunk zero-padded,
        pad rows sliced off), so one compiled program serves any request
        size; ``SystemStats.search_dispatches`` then counts ceil(B/N)
        programs.  ``cfg.shard_lti`` additionally row-shards the LTI
        lane's arrays over the mesh data axis — same results, each device
        searching only its row block (docs/SERVING.md has the recipe and
        the capacity caveats).

        ``beam_width`` overrides the configured W for every lane in the
        fan-out; with ``cfg.autotune_beam`` and no override, W comes from
        the cached hop/cmp calibration (see ``core.autotune``).

        ``cfg.batch_fanout=False`` runs the sequential per-tier loop with
        host-side aggregation — the bit-parity oracle: both paths return
        bit-identical (ids, dists).

        ``filter`` restricts results to points matching a ``FilterSpec``
        (label predicate and/or tenant id).  The predicate folds into the
        cached DeleteList drop-mask — applied POST-search, exactly where
        deletes already are — so the beam search itself is untouched: a
        filter that matches everything returns bit-identical (ids, dists)
        to the unfiltered call, and hops/cmps never change.
        """
        self._search_flush()
        fspec = filter if filter is not None and not filter.is_empty \
            else None
        L = L or self.cfg.index.L_search
        if k > L:
            raise ValueError(
                f"search(k={k}, L={L}): k must be <= L — the candidate list "
                f"holds only L entries, so more than L results cannot be "
                f"returned; raise L or lower k")
        W = beam_width or self._beam_width(queries)
        # Over-fetch so DeleteList filtering + cross-tier dedupe still leave k.
        kk = min(max(k * 2, k + 8), L)
        q = np.asarray(queries, np.float32)
        B = q.shape[0]
        self.stats.searches += B        # queries served, not programs
        if fspec is not None:
            self.stats.filtered_searches += B
            if fspec.tenant is not None:
                self.stats.tenant_searches[fspec.tenant] = (
                    self.stats.tenant_searches.get(fspec.tenant, 0) + B)
        if B == 0:                      # a no-op request is not a program
            return (np.zeros((0, k), np.int64),
                    np.zeros((0, k), np.float32))
        bq = self.cfg.batch_queries
        if not bq or B == bq:
            return self._search_dispatch(q, k, kk, L, W, fspec)
        outs = []
        for lo in range(0, B, bq):      # fixed-shape chunks, tail padded
            chunk = q[lo:lo + bq]
            n = len(chunk)
            if n < bq:                  # pad up to the compiled width
                qp = np.zeros((bq, q.shape[1]), np.float32)
                qp[:n] = chunk
                chunk = qp
            ids, d = self._search_dispatch(chunk, k, kk, L, W, fspec)
            outs.append((ids[:n], d[:n]))
        return (np.concatenate([o[0] for o in outs]),
                np.concatenate([o[1] for o in outs]))

    def _search_dispatch(self, queries: np.ndarray, k: int, kk: int,
                         L: int, W: int,
                         fspec: Optional[FilterSpec] = None
                         ) -> tuple[np.ndarray, np.ndarray]:
        """Timed wrapper: every dispatched micro-batch samples its wall
        time into ``stats.search_latency`` (the reservoir behind the
        serving benches' p50/p99 rows) — lane-less no-op calls, which
        launch no program, are not samples."""
        d0 = self.stats.search_dispatches
        t0 = time.perf_counter()
        out = self._search_dispatch_impl(queries, k, kk, L, W, fspec)
        if self.stats.search_dispatches > d0:
            self.stats.search_latency.record(time.perf_counter() - t0)
        return out

    def _search_dispatch_impl(self, queries: np.ndarray, k: int, kk: int,
                              L: int, W: int,
                              fspec: Optional[FilterSpec] = None
                              ) -> tuple[np.ndarray, np.ndarray]:
        """Serve ONE fixed-shape micro-batch (all query-count accounting
        already done by ``search_batch``)."""
        q = jnp.asarray(queries, jnp.float32)
        nq = queries.shape[0]
        (rw_t, ro_temps, lti_entry), bundle, drop = self._prepare_batch(fspec)
        if rw_t is None and not ro_temps and lti_entry is None:
            return self._aggregate([], k, nq)
        if bundle is not None:
            _, stack, t_tabs, l_tab, _, _ = bundle
            t_drop, l_drop = drop
            # rerank only matters to the PQ lane; with no LTI lane it would
            # be dead compute.
            do_rerank = self.cfg.rerank and lti_entry is not None
            with TraceAnnotation("fdann.search.device",
                                 batch=serving_batch.get()) as span:
                if lti_entry is not None and self._shard_count():
                    step, sstack = self._sharded_program(
                        stack, k=k, kk=kk, L=L, W=W, rerank=do_rerank)
                    ids, d, hops, _ = step(sstack, t_tabs, l_tab, t_drop,
                                           l_drop, q)
                else:
                    ids, d, hops, _ = mem.unified_search(
                        stack, t_tabs, l_tab, t_drop, l_drop, q,
                        self.cfg.index, k=k, k_lane=kk, L=L, beam_width=W,
                        rerank=do_rerank)
                self.stats.search_dispatches += 1
                out = (np.asarray(ids).astype(np.int64),
                       np.asarray(d).astype(np.float32))
                if lti_entry is not None and span.is_enabled():
                    lane_hops_metadata(span, hops)
            return out
        # Sequential oracle: one device program per tier + host aggregation.
        cands: list[tuple[np.ndarray, np.ndarray]] = []   # (ext_ids, dists)
        if lti_entry is not None:
            lti, lti_table = lti_entry[0], lti_entry[1]
            ids, d, _, _ = search_lti(lti, q, self.cfg.index, k=kk, L=L,
                                      beam_width=W, rerank=self.cfg.rerank)
            self.stats.search_dispatches += 1
            ids = np.asarray(ids)
            cands.append((self._map_ext(ids, lti_table),
                          self._slot_filter(ids, np.asarray(d),
                                            lti_entry[2], fspec)))
        for t in ([rw_t] if rw_t is not None else []) + ro_temps:
            ids, d, _, _ = mem.search(t.state, q, self.temp_cfg, k=kk,
                                      L=L, beam_width=W)
            self.stats.search_dispatches += 1
            ids = np.asarray(ids)
            cands.append((self._map_ext(ids, t.ext_ids),
                          self._slot_filter(ids, np.asarray(d),
                                            t.labels, fspec)))
        return self._aggregate(cands, k, nq)

    def _search_flush(self) -> None:
        """A search's leading flush of the insert buffer, its wait for the
        flush lock included."""
        with TraceAnnotation("fdann.search.flush",
                             batch=serving_batch.get()) as span:
            span.set_metadata(points=self._flush_inserts())

    def _prepare_batch(self, fspec: Optional[FilterSpec]):
        """Host preparation of one micro-batch: the lane capture, and on the
        unified path the lane bundle and its drop masks.  Returns (captured
        lanes, bundle or None, (temp drop, LTI drop) or None)."""
        with TraceAnnotation("fdann.search.prepare",
                             batch=serving_batch.get()) as span:
            lanes = self._capture_lanes()
            rw_t, ro_temps, lti_entry = lanes
            span.set_metadata(lanes=(rw_t is not None) + len(ro_temps)
                              + (lti_entry is not None))
            if not self.cfg.batch_fanout or (
                    rw_t is None and not ro_temps and lti_entry is None):
                return lanes, None, None
            bundle = self._lane_bundle(rw_t, ro_temps, lti_entry)
            if bundle is None:
                return lanes, None, None
            key, _, _, _, tables_np, label_tabs = bundle
            drop = (self._drop_mask(key, tables_np) if fspec is None else
                    self._filter_drop(key, tables_np, label_tabs, fspec))
            return lanes, bundle, drop

    @staticmethod
    def _slot_filter(slot_ids: np.ndarray, dists: np.ndarray,
                     labels: Optional[LabelTable],
                     fspec: Optional[FilterSpec]) -> np.ndarray:
        """Host half of the filtered drop for the per-tier paths: inf-out
        candidates whose slot fails ``fspec`` — the same post-search point
        where ``lanes_to_ext`` applies the on-device mask, so the
        sequential oracle and the unified fan-out stay bit-identical with
        filters on.  A missing label table drops everything (a tier that
        never saw a labeled insert has no matching points)."""
        if fspec is None:
            return dists
        d = dists.copy()
        ok = slot_ids >= 0
        if labels is None:
            d[ok] = np.inf
            return d
        m = filter_match(labels, fspec)
        dead = np.zeros(slot_ids.shape, bool)
        dead[ok] = ~m[slot_ids[ok]]
        d[dead] = np.inf
        return d

    # ------------------------------------------------- sharded LTI lane
    @property
    def lti_shards(self) -> int:
        """Effective LTI-lane shard count: ``cfg.shard_lti`` capped at the
        device census (0 = unsharded).  Public mirror of the serving
        engine's routing decision — see docs/SERVING.md."""
        return self._shard_count()

    def _shard_count(self) -> int:
        n = self.cfg.shard_lti
        if n <= 0:
            return 0
        return min(n, len(jax.devices()))

    def _sharded_program(self, stack, *, k, kk, L, W, rerank):
        """(step, stack-with-sharded-LTI) for the mesh-sharded fan-out.

        Three caches: the 1-axis data mesh (per shard count), the
        ``graph.shard_lti`` placement (keyed by LTI graph/codes identity —
        a merge swaps them and misses), and the jitted step per (index
        config, k, kk, L, W, rerank) — the config decides the engine.
        """
        from ..distributed.sharding import data_mesh
        from ..serving.steps import make_sharded_unified_step
        from .graph import LaneStack, shard_lti
        n = self._shard_count()
        if self._shard_mesh is None or self._shard_mesh_n != n:
            self._shard_mesh = data_mesh(n)
            self._shard_mesh_n = n
            self._shard_place = None
            self._shard_steps = {}
        place = self._shard_place
        if (place is None or place[0] is not stack.lti
                or place[1] is not stack.codes):
            sg, sc = shard_lti(stack.lti, stack.codes, n,
                               mesh=self._shard_mesh)
            place = (stack.lti, stack.codes, sg, sc)
            self._shard_place = place
        key = (self.cfg.index, k, kk, L, W, rerank)
        step = self._shard_steps.get(key)
        if step is None:
            step = make_sharded_unified_step(
                self._shard_mesh, self.cfg.index, k=k, k_lane=kk, L=L,
                beam_width=W, rerank=rerank)
            self._shard_steps[key] = step
        return step, LaneStack(stack.temps, place[2], place[3],
                               stack.codebook)

    def _beam_width(self, queries: np.ndarray) -> int:
        """Resolve W: autotuned (and cached until the next merge) or static."""
        if not self.cfg.autotune_beam:
            return self.cfg.index.beam_width
        if self._tuned_w is None:
            tuned = self._calibrate_beam(queries)
            if tuned is None:          # no representative tier yet: don't
                return self.cfg.index.beam_width   # cache the fallback
            self._tuned_w = tuned
        return self._tuned_w

    def _calibrate_beam(self, queries: np.ndarray) -> Optional[int]:
        """Probe the serving configuration at each candidate W; pick by
        hop/cmp cost.

        With ``batch_fanout`` the probe runs the SAME unified device program
        queries pay for, so the tuner costs what serving costs: per-query
        IO rounds are the max over lanes (lanes run concurrently, latency
        follows the slowest lane — the LTI in steady state) and distance
        computations are summed across lanes (total work).  Without it the
        probe falls back to the largest single tier, as before.

        Returns None when no tier is big enough for the hop/cmp profile to
        be representative (a handful of points terminates in 1-2 hops at
        any W) — the caller then keeps using the static width WITHOUT
        caching, so calibration re-runs once the index has grown.
        """
        L = self.cfg.index.L_search
        probe = jnp.asarray(queries[:8], jnp.float32)
        rw_t, ro_temps, lti_entry = self._capture_lanes()
        sizes = ([rw_t.n] if rw_t is not None else []) \
            + [t.n for t in ro_temps] \
            + ([int(lti_entry[0].graph.n_total)] if lti_entry else [])
        if not sizes or max(sizes) < L:
            return None
        run = None
        if self.cfg.batch_fanout:
            bundle = self._lane_bundle(rw_t, ro_temps, lti_entry)
            if bundle is not None:
                key, stack, t_tabs, l_tab, tables_np, _ = bundle
                t_drop, l_drop = self._drop_mask(key, tables_np)

                def run(W):
                    _, _, hops, cmps = mem.unified_search(
                        stack, t_tabs, l_tab, t_drop, l_drop, probe,
                        self.cfg.index, k=1, k_lane=1, L=L, beam_width=W,
                        rerank=self.cfg.rerank and lti_entry is not None)
                    return (np.asarray(hops).max(axis=0),
                            np.asarray(cmps).sum(axis=0))
        if run is None:
            lti = self._lti_pair[0]
            if int(lti.graph.n_total) >= L:
                def run(W):
                    _, _, hops, cmps = search_lti(lti, probe, self.cfg.index,
                                                  k=1, L=L, beam_width=W)
                    return hops, cmps
            elif self.rw.n >= L:
                def run(W):
                    _, _, hops, cmps = mem.search(self.rw.state, probe,
                                                  self.temp_cfg, k=1, L=L,
                                                  beam_width=W)
                    return hops, cmps
            else:
                return None
        points = autotune.measure_widths(run, self.cfg.beam_width_candidates)
        return autotune.pick_beam_width(points)

    # ------------------------------------------------------------- plumbing
    def _capture_lanes(self):
        """One consistent capture of every searchable tier.

        Capture order matters: RW before RO before LTI.  A concurrent
        rollover moves RW -> RO, and a concurrent merge moves RO -> LTI, so
        capturing each tier BEFORE its points' destination means an
        interleaved move lands the points in BOTH captures (the cross-tier
        dedupe resolves that) rather than in neither (a gap).
        """
        rw = self.rw                             # single read
        rw_t = rw if rw.n > 0 else None
        with self._ro_lock:
            ro_temps = [t for t in self.ro if t.n > 0]
        lti, lti_table, lti_labels = self._lti_pair  # one generation
        lti_entry = ((lti, lti_table, lti_labels)
                     if int(lti.graph.n_total) > 0 else None)
        return rw_t, ro_temps, lti_entry

    @staticmethod
    def _key_hits(cached_key, key) -> bool:
        return (cached_key is not None and len(cached_key) == len(key)
                and all(a is b for a, b in zip(cached_key, key)))

    @staticmethod
    def _fits_int32(a: np.ndarray) -> bool:
        return (a.max(initial=-1) <= np.iinfo(np.int32).max
                and a.min(initial=0) >= np.iinfo(np.int32).min)

    def _lane_bundle(self, rw_t, ro_temps, lti_entry):
        """(key, LaneStack, temp tables [Tt, temp_cap] device, LTI table
        [lti_cap] device, tables np) for the unified fan-out — cached by
        tier-state identity (states are immutable values: a flush /
        rollover / merge replaces them, which misses the cache).

        Temp lanes are padded to the largest TEMP capacity only; the LTI
        lane rides at its own capacity (the stack is O(Tt x temp_cap)
        instead of O(T x LTI_cap)).  External ids travel as int32 when they
        fit; with ``jax_enable_x64`` set they widen to int64 pairs instead,
        and only when neither holds does the system warn once and fall back
        to the sequential per-tier path (bundle None, verdict cached).

        Two cache levels: the full bundle (missed by any tier mutation),
        and a frozen sub-cache of the RO lanes' padded graphs, the RO + LTI
        table rows, and the id-range verdict — those only change on
        rollover/merge, so the RW flushes that dominate a steady-state
        insert+search stream re-pad and re-scan ONLY the RW lane (the
        final [Tt, ...] device stack is still rebuilt: that copy is what
        buys the single dispatch).
        """
        fp = ([rw_t] if rw_t is not None else []) + ro_temps
        key = tuple(t.state for t in fp) + (
            (lti_entry[0],) if lti_entry is not None else ())
        cached = self._fanout_cache
        if cached is not None and self._key_hits(cached[0], key):
            return cached[1]

        tcap = max((t.state.capacity for t in fp), default=0)

        fkey = (tuple(t.state for t in ro_temps)
                + ((lti_entry[0],) if lti_entry is not None else ()))
        fcached = self._frozen_cache
        if (fcached is not None and fcached[1] == tcap
                and self._key_hits(fcached[0], fkey)):
            ro_states, ro_tabs, froz_ok = fcached[2:]
        else:
            ro_states = [pad_graph(t.state, tcap) for t in ro_temps]
            ro_tabs = np.full((len(ro_temps), tcap), -1, np.int64)
            for fi, t in enumerate(ro_temps):
                ro_tabs[fi, :len(t.ext_ids)] = t.ext_ids
            froz_ok = self._fits_int32(ro_tabs) and (
                lti_entry is None or self._fits_int32(lti_entry[1]))
            self._frozen_cache = (fkey, tcap, ro_states, ro_tabs, froz_ok)

        n_rw = 1 if rw_t is not None else 0
        rw_tabs = np.full((n_rw, tcap), -1, np.int64)
        if n_rw:
            rw_tabs[0, :len(rw_t.ext_ids)] = rw_t.ext_ids
        temp_tabs_np = np.concatenate([rw_tabs, ro_tabs])
        lti_tab_np = lti_entry[1] if lti_entry is not None else None
        if froz_ok and self._fits_int32(rw_tabs):
            id_dtype = np.int32
        elif jax.config.jax_enable_x64:
            id_dtype = np.int64     # billion-scale id spaces ride as i64
        else:
            if not self._int32_warned:
                self._int32_warned = True
                import warnings
                warnings.warn(
                    "external ids exceed int32: the on-device unified "
                    "fan-out is disabled, searches use the sequential "
                    "per-tier path (enable jax_enable_x64 to carry ids "
                    "as int64 instead)")
            self._fanout_cache = (key, None)
            return None
        lanes = ([pad_graph(rw_t.state, tcap)] if n_rw else []) + ro_states
        lti_graph = codes = codebook = None
        if lti_entry is not None:
            lti_graph = lti_entry[0].graph
            codes = lti_entry[0].codes
            codebook = lti_entry[0].codebook.centroids
        stack = stack_lanes(lanes, lti=lti_graph, codes=codes,
                            codebook=codebook)
        t_tabs = (jnp.asarray(temp_tabs_np.astype(id_dtype))
                  if lanes else None)
        l_tab = (jnp.asarray(lti_tab_np.astype(id_dtype))
                 if lti_entry is not None else None)
        # Label tables ride the bundle lane-ordered ([RW?] + RO, LTI) so
        # the filtered drop-mask aligns with the stacked lanes.
        label_tabs = ([t.labels for t in fp],
                      lti_entry[2] if lti_entry is not None else None)
        bundle = (key, stack, t_tabs, l_tab, (temp_tabs_np, lti_tab_np),
                  label_tabs)
        self._fanout_cache = (key, bundle)
        return bundle

    def _drop_mask(self, key: tuple, tables_np: tuple):
        """Per-group [.., cap] bool DeleteList membership masks for the
        on-device filter — (temp [Tt, temp_cap], lti [lti_cap] or None).
        Cached by (lane key, delete epoch): tier mutations change the key;
        DeleteList mutations the states don't witness (delete of an LTI/RO
        resident, re-insert revival) bump ``_delete_epoch``."""
        epoch = self._delete_epoch
        cached = self._drop_cache
        if (cached is not None and cached[1] == epoch
                and self._key_hits(cached[0], key)):
            return cached[2]
        t_mask, l_mask = self._delete_masks_np(tables_np)
        drop = (jnp.asarray(t_mask) if t_mask.shape[0] else None,
                jnp.asarray(l_mask) if l_mask is not None else None)
        self._drop_cache = (key, epoch, drop)
        return drop

    def _delete_masks_np(self, tables_np: tuple
                         ) -> tuple[np.ndarray, Optional[np.ndarray]]:
        """Host-side DeleteList membership masks over the lane tables —
        the shared base of ``_drop_mask`` and ``_filter_drop``."""
        temp_np, lti_np = tables_np
        deleted = self.deleted_ext.copy()        # GIL-atomic vs bg merge
        if deleted:
            dl = np.fromiter(deleted, np.int64, len(deleted))
            t_mask = np.isin(temp_np, dl)
            l_mask = np.isin(lti_np, dl) if lti_np is not None else None
        else:
            t_mask = np.zeros(temp_np.shape, bool)
            l_mask = (np.zeros(lti_np.shape, bool)
                      if lti_np is not None else None)
        return t_mask, l_mask

    def _filter_drop(self, key: tuple, tables_np: tuple, label_tabs: tuple,
                     fspec: FilterSpec):
        """Filtered drop masks: the DeleteList base ORed with ``~match`` of
        ``fspec`` against each lane's label table — one extra AND per
        candidate at the same post-search point deletes already pay, so the
        beam search itself (hops/cmps) is untouched.  Cached per
        (lane key, delete epoch) as a dict of per-spec masks; any tier or
        DeleteList mutation retires the whole dict."""
        epoch = self._delete_epoch
        cached = self._filter_cache
        if (cached is not None and cached[1] == epoch
                and self._key_hits(cached[0], key)):
            specs = cached[2]
        else:
            specs = {}
            self._filter_cache = (key, epoch, specs)
        drop = specs.get(fspec)
        if drop is not None:
            return drop
        t_mask, l_mask = self._delete_masks_np(tables_np)
        temp_labels, lti_labels = label_tabs
        for i, lt in enumerate(temp_labels):
            if lt is None:              # no labels ever seen: nothing matches
                t_mask[i] = True
                continue
            m = filter_match(lt, fspec)
            t_mask[i, :m.size] |= ~m
            t_mask[i, m.size:] = True   # lane padding can't match
        if l_mask is not None:
            if lti_labels is None:
                l_mask[:] = True
            else:
                l_mask |= ~filter_match(lti_labels, fspec)
        drop = (jnp.asarray(t_mask) if t_mask.shape[0] else None,
                jnp.asarray(l_mask) if l_mask is not None else None)
        specs[fspec] = drop
        return drop

    def _new_temp(self) -> _Temp:
        return _Temp(empty_graph(self.temp_cfg),
                     np.full(self.cfg.temp_capacity, -1, np.int64),
                     labels=LabelTable(self.cfg.temp_capacity,
                                       self._n_label_words))

    def _map_ext(self, slot_ids: np.ndarray, table: np.ndarray) -> np.ndarray:
        out = np.full(slot_ids.shape, -1, np.int64)
        ok = slot_ids >= 0
        out[ok] = table[slot_ids[ok]]
        return out

    def _aggregate(self, cands, k, nq):
        if not cands:
            return (np.full((nq, k), -1, np.int64),
                    np.full((nq, k), np.inf, np.float32))
        ids = np.concatenate([c[0] for c in cands], axis=1)
        ds = np.concatenate([c[1] for c in cands], axis=1).astype(np.float32)
        # filter DeleteList + invalid lanes (vectorized; no python loops).
        # .copy() is atomic under the GIL — a concurrent background merge
        # (deleted_ext -= consumed) must not race the iteration below.
        deleted = self.deleted_ext.copy()
        bad = ids < 0
        if deleted:
            dl = np.fromiter(deleted, np.int64, len(deleted))
            bad |= np.isin(ids, dl)
        ds[bad] = np.inf
        # dedupe keeping the closest instance of each id (an id may
        # transiently exist in LTI and a TempIndex after re-insertion): sort
        # each row by (id, dist), mask all but the first copy of every id,
        # then rank by distance and slice k.
        order = np.lexsort((ds, ids), axis=1)
        sid = np.take_along_axis(ids, order, axis=1)
        sd = np.take_along_axis(ds, order, axis=1)
        dup = np.zeros_like(sid, bool)
        dup[:, 1:] = (sid[:, 1:] == sid[:, :-1]) & (sid[:, 1:] >= 0)
        sd[dup] = np.inf
        top = np.argsort(sd, axis=1, kind="stable")[:, :k]
        res_d = np.take_along_axis(sd, top, axis=1)
        res_i = np.where(np.isfinite(res_d),
                         np.take_along_axis(sid, top, axis=1), -1)
        res_d = np.where(np.isfinite(res_d), res_d, np.inf)
        if res_i.shape[1] < k:     # fewer candidates than k: pad, as before
            pad = k - res_i.shape[1]
            res_i = np.pad(res_i, ((0, 0), (0, pad)), constant_values=-1)
            res_d = np.pad(res_d, ((0, 0), (0, pad)),
                           constant_values=np.inf)
        return res_i.astype(np.int64), res_d.astype(np.float32)

    def _flush_inserts(self) -> int:
        """Land the insert buffer in the RW tier; returns the points landed
        (0 when the buffer was empty).

        Locking: the buffer swap is the only step under ``_insert_lock``;
        the device-side compute + publish run under ``_flush_lock`` alone
        (canonical order flush -> insert), so a flush in flight never
        blocks concurrent insert/delete/search bookkeeping.  The unlocked
        emptiness peek is benign: a concurrently appended point is landed
        by ITS OWN insert's flush (or the next rendezvous), and the swap
        re-checks under the lock.

        Delete-vs-flight invariant: a buffered id is never in
        ``deleted_ext`` (``insert`` revives at append time, ``delete``
        drops buffered copies), so the publish loop below must NOT touch
        the DeleteList — a ``delete`` issued while the flush is in flight
        lands in ``deleted_ext`` and has to STAY there, masking the row
        this flush publishes (tests/test_system.py pins it).
        """
        if not self._insert_buf_id:
            return 0
        with self._flush_lock:
            with self._insert_lock:
                ids = self._insert_buf_id
                vecs = self._insert_buf_v
                bits = self._insert_buf_bits
                tens = self._insert_buf_tenant
                if not ids:
                    return 0
                self._insert_buf_id, self._insert_buf_v = [], []
                self._insert_buf_bits, self._insert_buf_tenant = [], []
            t0 = time.perf_counter()
            with TraceAnnotation("fdann.flush", points=len(ids),
                                 chunks=-(-len(ids) // self.cfg.insert_batch)):
                self._flush_compute(ids, vecs, bits, tens)
            self.stats.flushes += 1
            self.stats.flush_latency.record(time.perf_counter() - t0)
        return len(ids)

    def _flush_compute(self, ids: list, vecs: list, bits: list,
                       tens: list) -> None:
        """Device-side flush of one drained buffer (caller holds
        ``_flush_lock``; ``_insert_lock`` must NOT be required here).

        With ``cfg.locality_order`` the whole drained buffer is
        proximity-ordered first (seeded per flush), then every chunk runs
        the split insert (``mem.insert_edges_stage`` +
        ``mem.insert_apply_delta``): cluster mates share search frontiers
        and their back-edge pairs collide onto few DISTINCT targets, so the
        Delta prune launches at a measured power-of-two bucket instead of
        the worst case.  Arrival order runs the same split with
        ``affected_cap=None`` — bit-identical to the historical fused
        ``mem.insert`` (tests/test_locality.py) — so the
        targets-vs-launched counters accumulate comparably either way.

        Publish order per chunk: ext-id rows BEFORE the state swap, so a
        search capturing ``t.state`` mid-flush never maps a live row
        through a stale -1 entry.
        """
        B = self.cfg.insert_batch
        if self.cfg.locality_order and len(ids) > 1:
            perm = np.asarray(locality_order(
                jnp.asarray(np.stack(vecs)),
                n_clusters=self.cfg.index.locality_clusters or 16,
                seed=self._flush_seq))
            ids = [ids[i] for i in perm]
            vecs = [vecs[i] for i in perm]
            bits = [bits[i] for i in perm]
            tens = [tens[i] for i in perm]
        self._flush_seq += 1
        t = self.rw
        for lo in range(0, len(ids), B):
            chunk_i = ids[lo:lo + B]
            chunk_v = vecs[lo:lo + B]
            chunk_b = bits[lo:lo + B]
            chunk_t = tens[lo:lo + B]
            slots = np.arange(t.n, t.n + len(chunk_i), dtype=np.int32)
            if t.n == 0:
                # Seed the empty temp graph: first point becomes the start.
                st = t.state
                v0 = jnp.asarray(chunk_v[0], st.vectors.dtype)
                t.ext_ids[0] = chunk_i[0]
                t.labels.set_row(0, chunk_b[0], chunk_t[0])
                t.state = st._replace(
                    vectors=st.vectors.at[0].set(v0),
                    active=st.active.at[0].set(True),
                    start=jnp.int32(0), n_total=jnp.int32(1))
                self._ext_loc[chunk_i[0]] = ("rw", 0)
                chunk_i, chunk_v, slots = chunk_i[1:], chunk_v[1:], slots[1:] + 0
                chunk_b, chunk_t = chunk_b[1:], chunk_t[1:]
                t.n = 1
                if not chunk_i:
                    continue
            pad = B - len(chunk_i)
            pslots = np.concatenate(
                [slots, np.full(pad, INVALID, np.int32)])
            pvecs = np.zeros((B, self.cfg.index.dim), np.float32)
            pvecs[:len(chunk_v)] = np.stack(chunk_v)
            st, pj, pp = mem.insert_edges_stage(
                t.state, jnp.asarray(pslots), jnp.asarray(pvecs),
                self.temp_cfg)
            pj_h = np.asarray(pj)
            d_c = int(np.unique(pj_h[pj_h >= 0]).size)
            self.stats.flush_backedge_targets += d_c
            if self.cfg.locality_order:
                if d_c:
                    bucket = next_bucket(
                        d_c, cap=min(pj_h.size, self.cfg.temp_capacity))
                    self.stats.flush_prune_rows += bucket
                    st = mem.insert_apply_delta(st, pj, pp, self.temp_cfg,
                                                affected_cap=bucket)
            else:
                self.stats.flush_prune_rows += min(
                    pj_h.size, self.cfg.temp_capacity)
                st = mem.insert_apply_delta(st, pj, pp, self.temp_cfg)
            for j, (s, e) in enumerate(zip(slots, chunk_i)):
                t.ext_ids[s] = e
                t.labels.set_row(int(s), chunk_b[j], chunk_t[j])
            t.state = st
            for s, e in zip(slots, chunk_i):
                self._ext_loc[e] = ("rw", int(s))
            t.n += len(chunk_i)

    def _maybe_rollover(self) -> None:
        # flush_lock first (canonical order): the freeze must observe a
        # quiesced flush path, or the RW tier could be swapped out from
        # under an in-flight flush's publish loop.
        with self._flush_lock, self._insert_lock:
            if self.rw.n >= self.cfg.ro_snapshot_points:
                self._flush_inserts()
                frozen = self.rw
                with self._ro_lock:
                    self.ro.append(frozen)
                self.rw = self._new_temp()
                # The frozen snapshot's points are now RO-resident: retag so
                # the location map always names the tier a point lives in.
                for slot in np.nonzero(frozen.ext_ids >= 0)[0]:
                    e = int(frozen.ext_ids[slot])
                    if self._ext_loc.get(e) == ("rw", int(slot)):
                        self._ext_loc[e] = ("ro", int(slot))
                self.stats.snapshots += 1
            # Points already being consumed by an in-flight background merge
            # do not count toward the next threshold (they still sit in
            # self.ro so searches see them, but a second merge must not
            # re-stage them).  Read the RO list and the in-flight count
            # together under _ro_lock — the merge updates them atomically
            # under the same lock, and tearing the pair here would see the
            # pre-trim list with a zeroed count and launch a spurious merge.
            with self._ro_lock:
                staged = sum(t.n for t in self.ro) - self._merge_inflight
        # The merge itself runs OUTSIDE the insert lock (a foreground merge
        # holding it would deadlock against a background merge's snapshot).
        if staged >= self.cfg.merge_threshold:
            # With background_merge the insert path never stalls on the
            # StreamingMerge (paper §5.3's "merge runs concurrently").
            self.merge(background=self.cfg.background_merge)

    # -------------------------------------------------------------- merging
    def merge(self, background: bool = False) -> None:
        """StreamingMerge the RO-TempIndex points + DeleteList into the LTI."""
        if background:
            if self._merge_thread and self._merge_thread.is_alive():
                return
            self._merge_thread = threading.Thread(target=self._merge_worker,
                                                  name="fdann-merge")
            self._merge_thread.start()
        else:
            self._merge_impl()

    def _merge_worker(self) -> None:
        name_os_thread("fdann-merge")
        try:
            self._merge_impl()
        except BaseException as e:
            self._merge_error = e

    def wait_merge(self) -> None:
        """Join the background merge; re-raise the exception it died of."""
        if self._merge_thread:
            self._merge_thread.join()
        err, self._merge_error = self._merge_error, None
        if err is not None:
            raise err

    def _merge_impl(self) -> None:
        with self._merge_lock, TraceAnnotation(
                "fdann.merge", merge=self.stats.merges) as span:
            t0 = time.perf_counter()
            # Snapshot the RO list but KEEP it searchable while the merge
            # runs: its points leave self.ro only after the new LTI (which
            # contains them) has been swapped in, so a concurrent search
            # never observes a gap.  The brief window where a point exists in
            # both the new LTI and an RO tier is resolved by the cross-tier
            # dedupe in _aggregate.
            with self._ro_lock:
                ro = list(self.ro)
                self._merge_inflight = sum(t.n for t in ro)
            span.set_metadata(staged=self._merge_inflight)
            try:
                deletes, repair_mode, slices = self._merge_body(ro, t0)
                span.set_metadata(deletes=deletes, repair_mode=repair_mode,
                                  slices=slices)
            finally:
                # A failed merge must not leave the in-flight count set, or
                # every future threshold check would under-count and no
                # merge would ever run again.
                self._merge_inflight = 0

    def _merge_body(self, ro: list, t0: float) -> tuple[int, str, int]:
        """One StreamingMerge of the snapshotted RO tiers ``ro``; returns
        the DeleteList size it consumed against, its Delete phase's repair
        mode and the device programs it ran, for the ``fdann.merge``
        span."""
        staged = sum(t.n for t in ro)
        icfg = self.cfg.index
        merge = self.stats.merges       # every phase span carries it
        with TraceAnnotation("fdann.merge.stage", merge=merge):
            # The pre-merge adjacency anchors the delta patch: the live
            # layout is in sync with it, so rows that survive the merge
            # unchanged need no disk write (storage.layout.patch_layout).
            old_adj = (self.lti.graph.adjacency if self.cfg.storage_dir
                       else None)
            # Stage vectors + ids from the RO snapshots (skip re-deleted).
            del_snapshot = set(self.deleted_ext)
            vecs = np.zeros((max(staged, 1), icfg.dim), np.float32)
            exts = np.full(max(staged, 1), -1, np.int64)
            sbits = np.zeros((max(staged, 1), self._n_label_words),
                             np.uint32)
            sten = np.full(max(staged, 1), NO_TENANT, np.int32)
            w = 0
            for t in ro:
                sl = np.nonzero(t.ext_ids >= 0)[0][:t.n]
                v = np.asarray(t.state.vectors)[sl]
                for s, row in zip(sl, v):
                    e = int(t.ext_ids[s])
                    if e in del_snapshot:
                        continue
                    vecs[w], exts[w] = row, e
                    if t.labels is not None:   # labels follow the point
                        sbits[w] = t.labels.bits[s]
                        sten[w] = t.labels.tenant[s]
                    w += 1
            valid = np.zeros(max(staged, 1), bool)
            valid[:w] = True
            # Remove from the LTI: DeleteList members AND rows superseded by
            # a staged re-insert — after delete(e) + insert(e, v2), e's old
            # LTI row still holds the pre-delete vector; without this it
            # would survive the merge as a stale duplicate and searches
            # could return e ranked by the OLD vector.
            dmask = np.zeros(icfg.capacity, bool)
            lti_ids = self.lti_ext_ids
            if del_snapshot:
                dl = np.asarray(sorted(del_snapshot), np.int64)
                dmask[np.isin(lti_ids, dl)] = True
            if w:
                dmask[np.isin(lti_ids, exts[:w])] = True
            repair_mode = self._pick_repair_mode(dmask)
        with TraceAnnotation("fdann.merge.launch", merge=merge):
            new_lti, stats = streaming_merge(
                self.lti, jnp.asarray(vecs), jnp.asarray(valid),
                jnp.asarray(dmask), icfg, self.cfg.pq,
                insert_chunk=self.cfg.insert_batch,
                block=self.cfg.merge_block, repair_mode=repair_mode,
                # Locality merge (docs/ARCHITECTURE.md, "Update-path
                # locality"): seeded by the merge ordinal so every merge is
                # deterministic for its inputs yet successive merges don't
                # reuse one medoid sample.
                locality=self.cfg.locality_order, locality_seed=merge)
        with TraceAnnotation("fdann.merge.wait", merge=merge):
            jax.block_until_ready(new_lti.graph.adjacency)
        with TraceAnnotation("fdann.merge.publish", merge=merge):
            self.stats.repair_cap_overflows += int(
                stats.repair_cap_overflows)
            self.stats.merge_backedge_targets += int(stats.n_backedge_targets)
            self.stats.merge_prune_rows += int(stats.n_prune_rows)
            self.stats.merge_slices += int(stats.slices)
            if repair_mode == "local":
                self.stats.local_repairs += 1
            else:
                self.stats.global_repairs += 1
                self._force_global_repair = False  # the escalation is served
            # Rebuild the external-id table: deleted rows out, new rows in
            # (the merge reports the slot it assigned to each staged row).
            new_ids = self.lti_ext_ids.copy()
            for e in new_ids[dmask]:
                e = int(e)
                if e >= 0 and self._ext_loc.get(e, ("?",))[0] == "lti":
                    del self._ext_loc[e]     # removed from the LTI this cycle
            new_ids[dmask] = -1
            # Labels follow the same deleted-rows-out / staged-rows-in
            # rebuild as the ext-id table, scattered at the merge-assigned
            # slots.
            new_labels = self.lti_labels.copy()
            new_labels.clear_rows(dmask)
            slots = np.asarray(stats.slots)
            ok = valid & (slots >= 0)
            for i, (s, e) in zip(np.nonzero(ok)[0],
                                 zip(slots[ok], exts[ok])):
                new_ids[s] = e
                new_labels.bits[s] = sbits[i]
                new_labels.tenant[s] = sten[i]
                self._ext_loc[e] = ("lti", int(s))
            # One-shot generation swap (graph + ext table + labels
            # together), then retire exactly the RO snapshots this merge
            # consumed — anything appended by a concurrent rollover stays.
            self._lti_pair = (new_lti, new_ids, new_labels)
            with self._ro_lock:
                self.ro = self.ro[len(ro):]
                self._merge_inflight = 0
            self._tuned_w = None       # the graph changed: re-calibrate W
            self._fanout_cache = None  # retired RO stacks must not stay
            self._frozen_cache = None  # resident
            self._drop_cache = None
            self._filter_cache = None
            self._shard_place = None   # the old LTI's sharded copy likewise
        if self.cfg.storage_dir:
            # Delta-patch the live layout: only the adjacency rows this
            # merge rewrote touch topology.bin; surviving points' vector
            # bytes stay put (the DGAI decoupling win, measured in
            # storage_bytes_written).
            from .merge import adjacency_delta_mask
            self._sync_storage(
                adj_changed=np.asarray(adjacency_delta_mask(
                    old_adj, new_lti.graph.adjacency)))
        with TraceAnnotation("fdann.merge.retire", merge=merge):
            # A delete may leave the DeleteList only when NO copy of the id
            # survives the merge anywhere — LTI residents left via the dmask
            # pass and merged-RO residents were skipped at staging, but a
            # delete of a point still living in the RW tier (or an RO
            # snapshot that rolled over after this merge began, or the
            # insert buffer) must SURVIVE, or the live copy would be
            # revived.
            alive = self._live_ext_ids()
            dl = np.fromiter(del_snapshot, np.int64, len(del_snapshot))
            self.deleted_ext -= set(dl[~np.isin(dl, alive)].tolist())
            self._delete_epoch += 1
        if self.wal:
            if self.cfg.snapshot_dir:
                # Durability invariant (§5.6): snapshot BEFORE truncate, so
                # snapshot + log-suffix always covers the full state.  One
                # _insert_lock hold makes the pair atomic against concurrent
                # WAL writers — a record logged between the snapshot and the
                # truncation would otherwise be durable nowhere.  Restart
                # goes THROUGH the live handle: truncating the file under an
                # open positional handle would leave a zero-hole at its
                # stale offset on the next append.  _flush_lock is taken
                # FIRST (canonical order: flush -> insert) because the
                # snapshot's own flush nests under it.
                with self._flush_lock, self._insert_lock:
                    self._save_locked(
                        os.path.join(self.cfg.snapshot_dir,
                                     f"merge_{self.stats.merges + 1}"))
                    self.wal.restart(self.stats.merges + 1)
            # else: keep the whole log — with no snapshot covering the
            # pre-merge records, truncating would lose them on crash.
        self.stats.merges += 1
        self.stats.merge_seconds += time.perf_counter() - t0
        with TraceAnnotation("fdann.merge.probe", merge=merge):
            self._probe_reachability(repair_mode)
        return len(del_snapshot), repair_mode, int(stats.slices)

    def _pick_repair_mode(self, dmask: np.ndarray) -> str:
        """Route the merge's Delete phase: the localized affected-set sweep
        when the LTI's delete rate is at or below
        ``cfg.local_repair_threshold`` (and no reachability escalation is
        pending), the global Algorithm-4 sweep otherwise.  Both produce
        bit-identical graphs — this picks wall-clock, not semantics."""
        if self._force_global_repair:
            return "global"
        if self.cfg.index.repair_mode == "local":
            return "local"     # explicit user routing wins below escalation
        thr = self.cfg.local_repair_threshold
        if thr <= 0:
            return "global"
        active = np.asarray(self.lti.graph.active)
        n_live = int(active.sum())
        n_del = int(np.count_nonzero(dmask & active))
        return "local" if n_del <= thr * max(n_live, 1) else "global"

    def _probe_reachability(self, repair_mode: str) -> None:
        """Sampled self-search probe of the LTI after a Delete phase; sets
        the ``unreachable_frac`` gauge and arms the global-sweep escalation
        when a localized repair left too many live points stranded.

        Escalation compares against a BASELINE — the estimate recorded
        after the last global sweep (or the first probe) — because a few
        percent of points are unreachable on a freshly built graph already
        (batched inserts whose back-edges all lost the prune); the monitor
        guards against *repair-induced* degradation on top of that."""
        n = self.cfg.reach_probe_samples
        if n <= 0:
            return
        lti = self._lti_pair[0]
        frac = unreachable_fraction(lti.graph, self.cfg.index, samples=n,
                                    seed=self.stats.reach_probes)
        self.stats.unreachable_frac = frac
        self.stats.reach_probes += 1
        if repair_mode != "local" or self._reach_baseline is None:
            self._reach_baseline = frac
        elif frac > self._reach_baseline + self.cfg.reach_escalate_frac:
            self.stats.repair_escalations += 1
            self._force_global_repair = True

    def consolidate(self, mode: str = "local") -> int:
        """Standalone Algorithm 4 on the LTI — repair DeleteList residents
        without waiting for (or paying) a full StreamingMerge.

        The localized default makes this cheap at low delete rates: only
        the affected rows (plus the reclaimed deleted rows) change, and
        when ``cfg.storage_dir`` is set exactly that affected-union-deleted
        row set is delta-patched into the on-disk layout.  Returns the
        number of LTI points consolidated away.  Ids whose only copy was
        the LTI leave the DeleteList; copies in temp tiers keep their
        delete pending, exactly as a merge would."""
        from .delete import affected_mask, consolidate_deletes

        with self._merge_lock:
            icfg = self.cfg.index
            lti, table, labels = self._lti_pair
            del_snapshot = set(self.deleted_ext)
            dmask = np.zeros(icfg.capacity, bool)
            if del_snapshot:
                dl = np.asarray(sorted(del_snapshot), np.int64)
                dmask[np.isin(self.lti_ext_ids, dl)] = True
            dmask &= np.asarray(lti.graph.active)
            n_del = int(dmask.sum())
            if n_del == 0:
                return 0
            g = lti.graph
            g = g._replace(deleted=g.deleted | jnp.asarray(dmask))
            # The changed-row set is known a priori: affected rows get
            # repaired, deleted rows get cleared.  It anchors the storage
            # delta patch below — no post-hoc row compare needed.
            changed = np.asarray(affected_mask(
                g.adjacency, g.deleted, g.active & ~g.deleted)) | dmask
            decoded = pqm.decode(
                lti.codebook, lti.codes, self.cfg.pq).astype(jnp.float32)
            new_g = consolidate_deletes(g, icfg, block=self.cfg.merge_block,
                                        prune_table=decoded, mode=mode)
            jax.block_until_ready(new_g.adjacency)
            if mode == "local":
                self.stats.local_repairs += 1
            else:
                self.stats.global_repairs += 1
                self._force_global_repair = False
            # Retire the consolidated rows from the ext table, swap the
            # (LTI, table) pair as one generation, drop derived caches.
            new_ids = table.copy()
            for e in new_ids[dmask]:
                e = int(e)
                if e >= 0 and self._ext_loc.get(e, ("?",))[0] == "lti":
                    del self._ext_loc[e]
            new_ids[dmask] = -1
            new_labels = labels.copy()
            new_labels.clear_rows(dmask)
            self._lti_pair = (LTIState(new_g, lti.codes, lti.codebook),
                              new_ids, new_labels)
            self._tuned_w = None
            self._fanout_cache = None
            self._drop_cache = None
            self._filter_cache = None
            self._shard_place = None
            if self.cfg.storage_dir:
                self._sync_storage(adj_changed=changed)
            alive = self._live_ext_ids()
            dl = np.fromiter(del_snapshot, np.int64, len(del_snapshot))
            self.deleted_ext -= set(dl[~np.isin(dl, alive)].tolist())
            self._delete_epoch += 1
            self.stats.consolidations += 1
            self._probe_reachability(mode)
            return n_del

    # ------------------------------------------------------- storage tier
    def _storage_path(self) -> str:
        return os.path.join(self.cfg.storage_dir, "lti")

    def _sync_storage(self, adj_changed: Optional[np.ndarray] = None) -> None:
        """Mirror the live (LTI, ext-table) pair to the decoupled layout at
        ``cfg.storage_dir`` — a full write the first time, a DGAI-style
        delta patch afterwards (``adj_changed`` from the merge's device-side
        row compare when available).  Any open disk searcher is closed
        first: its in-memory header tables would go stale."""
        from ..storage import layout as slay
        self.close_storage()
        path = self._storage_path()
        os.makedirs(self.cfg.storage_dir, exist_ok=True)
        lti, table, labels = self._lti_pair
        if slay.is_layout(path):
            ps = slay.patch_layout(path, lti.graph, codes=lti.codes,
                                   ext_ids=table, adj_changed=adj_changed,
                                   label_bits=labels.bits,
                                   label_tenant=labels.tenant)
            self.stats.storage_rows_patched += ps.adj_rows
            self.stats.storage_blocks_patched += ps.adj_blocks
            self.stats.storage_bytes_written += ps.bytes_written
        else:
            lay = slay.write_layout(path, lti.graph, codes=lti.codes,
                                    codebook=lti.codebook, ext_ids=table,
                                    label_bits=labels.bits,
                                    label_tenant=labels.tenant)
            self.stats.storage_bytes_written += (
                lay.capacity * (lay.row_bytes + lay.dim * 4 + lay.m))
            lay.close()

    def _disk_searcher_get(self):
        """The cached ``DiskLTISearcher`` over the live layout (reopened
        after every sync, so it always serves the current generation)."""
        if self._disk_searcher is None:
            from ..storage import DiskLTISearcher, open_layout
            self._disk_searcher = DiskLTISearcher(
                open_layout(self._storage_path()), self.cfg.index,
                cache_mb=self.cfg.adjacency_cache_mb,
                prefetch_depth=self.cfg.prefetch_depth,
                latency_us=self.cfg.io_latency_us)
        return self._disk_searcher

    def close_storage(self) -> None:
        """Stop the prefetch thread and drop the layout mmaps (no-op when
        no disk searcher is open)."""
        if self._disk_searcher is not None:
            s, self._disk_searcher = self._disk_searcher, None
            s.close()
            s.layout.close()

    def search_disk(self, queries: np.ndarray, k: int,
                    L: Optional[int] = None,
                    beam_width: Optional[int] = None,
                    filter: Optional[FilterSpec] = None
                    ) -> tuple[np.ndarray, np.ndarray]:
        """The §5.2 fan-out with the LTI lane served OFF THE LAYOUT: PQ
        navigation on in-memory codes, adjacency rows streamed from
        ``topology.bin`` through the block cache + prefetch pipeline
        (``cfg.prefetch_depth`` / ``cfg.adjacency_cache_mb``), exact rerank
        from ``data.bin``.  Temp tiers are memory-resident by design (the
        paper's RW/RO TempIndices) and ride the sequential per-tier loop.

        With the cache off this returns bit-identical (ids, dists) to
        ``search_batch`` with ``batch_fanout=False``; reader IO deltas are
        folded into ``SystemStats`` (io_rows_read / io_cache_hits /
        io_prefetch_hits / io_bytes_read) after every call.
        """
        if not self.cfg.storage_dir:
            raise ValueError("search_disk needs SystemConfig.storage_dir")
        self._flush_inserts()
        fspec = filter if filter is not None and not filter.is_empty \
            else None
        L = L or self.cfg.index.L_search
        if k > L:
            raise ValueError(f"search(k={k}, L={L}): k must be <= L")
        W = beam_width or self.cfg.index.beam_width
        kk = min(max(k * 2, k + 8), L)
        q = np.asarray(queries, np.float32)
        B = q.shape[0]
        self.stats.searches += B
        if fspec is not None:
            self.stats.filtered_searches += B
            if fspec.tenant is not None:
                self.stats.tenant_searches[fspec.tenant] = (
                    self.stats.tenant_searches.get(fspec.tenant, 0) + B)
        if B == 0:
            return (np.zeros((0, k), np.int64),
                    np.zeros((0, k), np.float32))
        rw_t, ro_temps, lti_entry = self._capture_lanes()
        cands: list[tuple[np.ndarray, np.ndarray]] = []
        if lti_entry is not None:
            s = self._disk_searcher_get()
            before = s.stats.snapshot()
            ids, d, _, _, _ = s.search(q, k=kk, L=L, beam_width=W,
                                       rerank=self.cfg.rerank)
            # Dispatch is async — materialize before snapshotting, or the
            # IO counters are read mid-flight and the fold undercounts.
            ids, d = np.asarray(ids), np.asarray(d)
            self.stats.search_dispatches += 1
            after = s.stats.snapshot()

            def delta(key):
                return after[key] - before[key]

            self.stats.io_rows_read += (delta("demand_reads")
                                        + delta("prefetch_hits"))
            self.stats.io_cache_hits += delta("cache_hits")
            self.stats.io_prefetch_hits += delta("prefetch_hits")
            self.stats.io_bytes_read += delta("bytes_read")
            # Filter against the LAYOUT's own label side tables (the
            # generation this lane searched), not the live in-memory pair.
            lay_labels = None
            if s.layout.label_tenant is not None:
                lay_labels = LabelTable(
                    s.layout.capacity,
                    0 if s.layout.label_bits is None
                    else s.layout.label_bits.shape[1],
                    s.layout.label_bits, s.layout.label_tenant)
            cands.append((self._map_ext(ids, s.layout.ext_ids),
                          self._slot_filter(ids, d, lay_labels, fspec)))
        for t in ([rw_t] if rw_t is not None else []) + ro_temps:
            ids, d, _, _ = mem.search(t.state, q, self.temp_cfg, k=kk,
                                      L=L, beam_width=W)
            self.stats.search_dispatches += 1
            ids = np.asarray(ids)
            cands.append((self._map_ext(ids, t.ext_ids),
                          self._slot_filter(ids, np.asarray(d),
                                            t.labels, fspec)))
        return self._aggregate(cands, k, B)

    # ------------------------------------------------------------ snapshots
    def save(self, path: str) -> None:
        # Freeze the whole update path while we snapshot: flush first
        # (canonical order) so no flush is in flight, then the buffer/RW
        # bookkeeping.
        with self._flush_lock, self._insert_lock:
            self._save_locked(path)

    def _save_locked(self, path: str) -> None:
        # Caller holds _flush_lock + _insert_lock; both are RLocks, so the
        # nested flush re-enters them.
        self._flush_inserts()  # buffered inserts must land in temps
        os.makedirs(path, exist_ok=True)
        if self.cfg.storage_dir:
            # Decoupled snapshot: the LTI lands as a storage layout
            # (topology.bin + data.bin + side tables) instead of a
            # monolithic npz — the same files the live tier serves from,
            # so recovery reopens it with zero format conversion.
            from ..storage.layout import write_layout
            lay = write_layout(os.path.join(path, "layout"),
                               self.lti.graph, codes=self.lti.codes,
                               codebook=self.lti.codebook,
                               ext_ids=self.lti_ext_ids,
                               generation=self.stats.merges,
                               label_bits=self.lti_labels.bits,
                               label_tenant=self.lti_labels.tenant)
            lay.close()
        else:
            np.savez_compressed(
                os.path.join(path, "lti.npz"),
                **{f"g_{k}": np.asarray(v) for k, v in
                   self.lti.graph._asdict().items()},
                codes=np.asarray(self.lti.codes),
                centroids=np.asarray(self.lti.codebook.centroids),
                ext_ids=self.lti_ext_ids,
                label_bits=self.lti_labels.bits,
                label_tenant=self.lti_labels.tenant)
        # Temp entries are 5-tuples since labels landed; load() still
        # accepts the historical 3-tuples (label-free snapshots).
        ro_blob = [(t.state, t.ext_ids, t.n, t.labels)
                   for t in self.ro + [self.rw]]
        with open(os.path.join(path, "temps.pkl"), "wb") as f:
            pickle.dump([(jax.tree.map(np.asarray, s), e, n,
                          None if lb is None else lb.bits,
                          None if lb is None else lb.tenant)
                         for s, e, n, lb in ro_blob], f)
        # Record how much of the WAL (and which log epoch) this snapshot
        # already covers, so recovery replays only the suffix (no
        # double-apply).
        wal_offset = wal_epoch = None
        if self.wal and os.path.exists(self.wal.path):
            wal_offset = os.path.getsize(self.wal.path)
            wal_epoch = log_epoch(self.wal.path)
        with open(os.path.join(path, "meta.pkl"), "wb") as f:
            pickle.dump({"deleted": self.deleted_ext, "cfg": self.cfg,
                         "wal_offset": wal_offset,
                         "wal_epoch": wal_epoch}, f)

    @classmethod
    def load(cls, path: str, cfg: SystemConfig) -> "FreshDiskANN":
        from ..storage.layout import is_layout, open_layout
        lay_path = os.path.join(path, "layout")
        lti_label_bits = lti_label_tenant = None
        if is_layout(lay_path):
            # Decoupled snapshot (saved with cfg.storage_dir set): the LTI
            # comes back from the layout files; construction re-syncs the
            # live layout under the new storage_dir.
            lay = open_layout(lay_path)
            lti = lay.lti_state()
            ext_ids = lay.ext_ids.copy()
            lti_label_bits = lay.label_bits
            lti_label_tenant = lay.label_tenant
            lay.close()
        else:
            z = np.load(os.path.join(path, "lti.npz"))
            g = GraphState(*[jnp.asarray(z[f"g_{k}"])
                             for k in GraphState._fields])
            lti = LTIState(g, jnp.asarray(z["codes"]),
                           pqm.PQCodebook(jnp.asarray(z["centroids"])))
            ext_ids = z["ext_ids"].copy()
            if "label_tenant" in z.files:   # label-free snapshots lack these
                lti_label_bits = z["label_bits"]
                lti_label_tenant = z["label_tenant"]
        sys = cls(cfg, lti=lti, lti_ext_ids=ext_ids)
        if lti_label_tenant is not None:
            lb = sys.lti_labels
            lb.tenant[:] = lti_label_tenant
            if lti_label_bits is not None and lti_label_bits.size:
                w = min(lb.n_words, lti_label_bits.shape[1])
                lb.bits[:, :w] = lti_label_bits[:, :w]
        with open(os.path.join(path, "temps.pkl"), "rb") as f:
            temps = pickle.load(f)
        for i, entry in enumerate(temps):
            s, e, n = entry[:3]
            t = _Temp(GraphState(*[jnp.asarray(x) for x in s]), e.copy(), n,
                      labels=LabelTable(len(e), cfg.filter_words))
            if len(entry) >= 5 and entry[4] is not None:
                t.labels.tenant[:] = entry[4]
                if entry[3] is not None and entry[3].size:
                    w = min(t.labels.n_words, entry[3].shape[1])
                    t.labels.bits[:, :w] = entry[3][:, :w]
            # Last snapshot entry is the RW index, earlier ones are frozen RO
            # snapshots — tag them apart, matching the live-system tags.
            is_rw = i == len(temps) - 1
            if is_rw:
                sys.rw = t
            else:
                sys.ro.append(t)
            tag = "rw" if is_rw else "ro"
            for slot, ext in enumerate(e):
                if ext >= 0:
                    sys._ext_loc[int(ext)] = (tag, slot)
        with open(os.path.join(path, "meta.pkl"), "rb") as f:
            meta = pickle.load(f)
        sys.deleted_ext = set(meta["deleted"])
        sys._wal_offset = meta.get("wal_offset")
        sys._wal_epoch = meta.get("wal_epoch")
        return sys

    def latest_snapshot(self) -> Optional[str]:
        """The most recent merge snapshot under ``cfg.snapshot_dir``."""
        d = self.cfg.snapshot_dir
        if not d or not os.path.isdir(d):
            return None
        snaps = [s for s in os.listdir(d) if s.startswith("merge_")]
        if not snaps:
            return None
        return os.path.join(d, max(snaps, key=lambda s: int(s.split("_")[1])))

    def recover(self, snapshot_path: Optional[str] = None) -> int:
        """Crash recovery (§5.6): restore the latest snapshot (when given,
        else the newest merge snapshot under ``cfg.snapshot_dir``), then
        replay the WAL over it.  Returns the number of records replayed."""
        start = None
        if snapshot_path is None:
            snapshot_path = self.latest_snapshot()
        if snapshot_path:
            restored = FreshDiskANN.load(snapshot_path, self.cfg)
            if restored.wal:              # keep only our own WAL handle open
                restored.wal.close()
            self.lti = restored.lti
            self.lti_ext_ids = restored.lti_ext_ids
            self.lti_labels = restored.lti_labels
            self.rw = restored.rw
            self.ro = restored.ro
            self.deleted_ext = restored.deleted_ext
            self._ext_loc = restored._ext_loc
            self._insert_buf_v, self._insert_buf_id = [], []
            # The restored instance's construction already re-synced the
            # live layout under cfg.storage_dir; drop any searcher still
            # open over the pre-crash generation so the next search_disk
            # reopens against the restored one.
            self.close_storage()
            start = restored._wal_offset
            epoch = restored._wal_epoch
        n = 0
        wal_path = self.wal.path if self.wal else None
        if wal_path and os.path.exists(wal_path):
            # Replay only the suffix the snapshot doesn't already cover.  If
            # the log epoch changed since the snapshot (post-merge truncate)
            # everything in the current log postdates it: replay all of it.
            if start is not None and (start > os.path.getsize(wal_path)
                                      or epoch != log_epoch(wal_path)):
                start = None
            # Materialize before applying, and suppress re-logging while we
            # replay: the records are already in the log, and appending to
            # the file being iterated would never reach EOF.
            records = list(replay(wal_path, start))
            wal, self.wal = self.wal, None
            try:
                from .graph import unpack_labels
                from .wal import OP_DELETE, OP_INSERT
                for op, ext_id, vec in records:
                    if op == OP_INSERT:
                        self.insert(ext_id, vec)
                    elif op == OP_DELETE:
                        self.delete(ext_id)
                    else:       # labeled insert: (vec, tenant, bits)
                        self.insert(
                            ext_id, vec.vec,
                            labels=unpack_labels(vec.bits),
                            tenant=(None if vec.tenant == NO_TENANT
                                    else vec.tenant))
                    n += 1
                self._flush_inserts()
            finally:
                self.wal = wal
        return n

    # -------------------------------------------------------------- helpers
    @property
    def size(self) -> int:
        """Number of DISTINCT live external ids.

        Counts ids, not copies: after a delete + re-insert an id may
        transiently exist in the LTI *and* a TempIndex (or twice in one
        tier) until a merge retires the stale copy — searches dedupe those,
        and so does this accounting.
        """
        uniq = self._live_ext_ids()
        # .copy() is atomic under the GIL — a background merge shrinking the
        # set between len() and iteration would otherwise break fromiter.
        deleted = self.deleted_ext.copy()
        if not deleted:
            return len(uniq)
        dl = np.fromiter(deleted, np.int64, len(deleted))
        return int(len(uniq) - np.isin(uniq, dl).sum())

    def _live_ext_ids(self) -> np.ndarray:
        """Sorted unique external ids with a copy in ANY tier or the insert
        buffer (before DeleteList filtering).  Shared by ``size`` and the
        merge's delete-retirement check so the two always agree.  Stays in
        numpy end to end — no per-id Python object churn at scale."""
        parts = [self.lti_ext_ids] + [t.ext_ids for t in [self.rw] + self.ro]
        buf = list(self._insert_buf_id)      # atomic snapshot vs. inserts
        if buf:                              # not yet flushed to the RW index
            parts.append(np.asarray(buf, np.int64))
        arr = np.concatenate(parts)
        return np.unique(arr[arr >= 0])


def bootstrap_system(vectors: np.ndarray, ext_ids: np.ndarray,
                     cfg: SystemConfig, labels=None, tenants=None,
                     **build_kw) -> FreshDiskANN:
    """Build the initial static LTI (paper: start from a DiskANN build).

    ``labels`` (per-point iterables of label bit indices) and ``tenants``
    (per-point tenant ids) optionally tag the bootstrap points — the build
    assigns slots densely in input order, so row i's labels land in slot i.
    """
    lti = build_lti(vectors, cfg.index, cfg.pq, **build_kw)
    table = np.full(cfg.index.capacity, -1, np.int64)
    table[:len(ext_ids)] = ext_ids
    sys = FreshDiskANN(cfg, lti=lti, lti_ext_ids=table)
    if labels is not None:
        lb = sys.lti_labels
        for i, ls in enumerate(labels):
            lb.bits[i] = pack_labels(ls, lb.n_words)
    if tenants is not None:
        sys.lti_labels.tenant[:len(tenants)] = np.asarray(tenants, np.int32)
    return sys
