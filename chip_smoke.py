#!/usr/bin/env python3
"""Bring-up smoke of the streaming index on a TPU: one pass of the served path.

    python3 chip_smoke.py                 # one chip (the default)
    python3 chip_smoke.py --four-chips    # shard / replica parity on four

It runs the per-chip shard of ``configs/freshdiskann_1b.py::FULL`` (dim 128,
R=64, L=75/100, alpha=1.2, W=4, PQ 32x256, capacity 2,097,152) through the
entry points a user calls:

  bootstrap   ``bootstrap_system`` over ``--points`` Gaussian-mixture vectors
              (default ``POINTS``) from
              ``data.pipelines.vector_stream(seed=--seed)``, then a check that
              the PQ codes are the nearest centroids (computed on the host);
  stream      inserts interleaved with as many deletes of random live ids
              (the paper's equal-rate steady state), with RW->RO snapshots and
              a BACKGROUND StreamingMerge (Delete, Insert and Patch phases).
              Sizes keep the deployment's proportions (``stream_shape``): at
              2^20 points, 40,960 inserts, snapshots every 8,192 points and a
              merge at 32,768 staged points (about the paper's 0.5% / 3% of
              the LTI);
  serve       1,024 queries through ``BatchScheduler`` (64-query micro-batches)
              submitted while that merge runs;
  check       after ``wait_merge()``: the exact brute-force reference over
              the live set against the host (float64), 5-recall@5 of the
              kernel path against that reference (``RECALL_FLOOR``), the same
              final state searched through the jnp reference engines
              (``use_kernel=False``), which the kernel path must match to
              within ``RECALL_TOLERANCE``, and an exact-distance search of
              the merged LTI graph alone (``GRAPH_RECALL_FLOOR``).

Any failed phase, assertion, or worker-thread exception exits non-zero; so
does a run that finds no TPU (the CPU is not a stand-in for the chip).  The
last line of standard output is the device record, as one JSON object.
``--four-chips`` builds the same kind of state and then runs only the two
multi-chip checks: ``search_batch`` with ``shard_lti=4`` and a 4-replica
``ReplicaSet``, each bit-identical to the one-chip program, with each chip
holding its own quarter of the sharded LTI rows.
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Bootstrap size.  The deployment's target is 2^20 (half the shard's
# capacity).  On a v5e the build runs at ~500 points/s with compilation
# (498-557 points/s measured, 394 on the four-chip host), so 2^20 needs
# ~2,100 s of the 1200-s limit; 2^17 is the size measured to fit (see
# CHANGES.md for the readings and the 2^18 estimate).
POINTS = 1 << 17
FOUR_CHIP_POINTS = 1 << 14  # the four-chip parity run's bootstrap size
# The deployment's stream at 2^20 bootstrap points; a smaller bootstrap
# scales all three by the same factor (stream_shape).
DEPLOY_POINTS = 1 << 20
DEPLOY_STREAM_OPS = 40_960      # inserts, and as many deletes
DEPLOY_RO_SNAPSHOT = 8_192
DEPLOY_MERGE_THRESHOLD = 32_768
MIN_POINTS = DEPLOY_POINTS // 64  # smallest bootstrap: 640 ops, 128-point
#   snapshots (a 256-insert flush rolls over at once), a merge at 512
N_QUERIES = 1_024
BATCH_QUERIES = 64
K = 5
# 5-recall@5 floor of the served path (PQ navigation, exact rerank of the
# L=100 list) against the exact reference, and how far the kernel path may
# sit from the jnp reference engines on the same state.  Basis: PQ bounds
# it on this data.  Within a mixture component the 128-d Gaussian points
# are all at nearly the same distance from a query, so 32-byte codes cannot
# rank the true 5 into the top 100: an exhaustive PQ scan plus exact rerank
# of its top 100 reaches 0.965 at 16,384 points, 0.795 at 2^16 and 0.703 at
# 2^17 (CPU, 128 queries).  The v5e served path measured 0.778 at 2^16 and
# 0.694 at 2^17 (CHANGES.md).  The floor sits below that ceiling by the
# spread of the estimate and catches a collapse (0.175 before the PQ
# repair).
RECALL_FLOOR = 0.60
RECALL_TOLERANCE = 0.01
# 5-recall@5 floor of an exact-distance search of the final LTI graph alone
# against the exact reference over the points it holds: the graph the
# stream and the merge maintain, free of the PQ bound above.  Basis: the
# paper's >0.95; the v5e measured 0.998 at 2^16, after the bootstrap and
# after a merge alike (CHANGES.md).
GRAPH_RECALL_FLOOR = 0.95
# Share of sampled PQ code entries that must equal the nearest centroid
# computed on the host (float64); the rest may be f32 near-ties.
PQ_AGREEMENT_FLOOR = 0.99


def stream_shape(n_points: int) -> tuple[int, int, int]:
    """(stream ops, RO snapshot points, merge threshold) for a bootstrap of
    ``n_points``: the deployment's numbers scaled by n_points / 2^20."""
    return (DEPLOY_STREAM_OPS * n_points // DEPLOY_POINTS,
            DEPLOY_RO_SNAPSHOT * n_points // DEPLOY_POINTS,
            DEPLOY_MERGE_THRESHOLD * n_points // DEPLOY_POINTS)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, what) -> None:
    """A failed check ends the run non-zero (``assert`` would vanish
    under ``python -O``)."""
    if not cond:
        raise RuntimeError(f"check failed: {what}")


class CompileMeter:
    """Sums backend compile time and counts persistent-cache hits/misses
    through JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        return (f"compile: {self.compiles} programs, {self.seconds:.2f} s, "
                f"persistent cache hits={self.hits} misses={self.misses}")


METER: CompileMeter | None = None  # set by main(); read on a failed run


def system_config(index_cfg, pq_cfg, n_points: int):
    from repro.core.config import SystemConfig
    _, snapshot, threshold = stream_shape(n_points)
    return SystemConfig(
        index=index_cfg, pq=pq_cfg,
        ro_snapshot_points=snapshot,
        merge_threshold=threshold,
        # A search flushes a partial insert buffer, so a tier can overshoot
        # the snapshot size by up to one insert batch: leave headroom.
        temp_capacity=2 * snapshot,
        insert_batch=256, merge_block=1024,
        background_merge=True, batch_queries=BATCH_QUERIES)


def make_data(n_points: int, seed: int):
    """Corpus, insert stream and queries from one seeded mixture stream."""
    import numpy as np
    from repro.data.pipelines import vector_stream
    ops = stream_shape(n_points)[0]
    step = 1 << 16
    stream = vector_stream(step, 128, seed=seed)
    need = n_points + ops + N_QUERIES
    data = np.concatenate([next(stream) for _ in range(-(-need // step))])
    return (data[:n_points], data[n_points:n_points + ops],
            data[n_points + ops:need])


def pq_agreement(lti, vectors, n_points: int, seed: int) -> float:
    """Share of sampled code entries equal to the nearest centroid of the
    subvector, computed on the host in float64 from the differences."""
    import numpy as np
    rows = np.random.default_rng(seed).choice(n_points, min(n_points, 4096),
                                              replace=False)
    cent = np.asarray(lti.codebook.centroids).astype(np.float64)
    m, _, dsub = cent.shape
    xs = vectors[rows].astype(np.float64).reshape(len(rows), m, 1, dsub)
    want = ((xs - cent[None]) ** 2).sum(-1).argmin(-1)
    return float(np.mean(np.asarray(lti.codes)[rows] == want))


def run_stream(sys_, inserts, victims, n_points, sched, queries):
    """Interleave inserts and deletes; once the background merge is running,
    submit the queries to the scheduler in bursts as the stream goes on."""
    tickets = []
    per_burst = BATCH_QUERIES
    merging_at = None
    for i, vec in enumerate(inserts):
        sys_.insert(n_points + i, vec)
        sys_.delete(int(victims[i]))
        if merging_at is None and sys_._merge_thread is not None:
            merging_at = i
            left = len(inserts) - i
            every = max(1, left * per_burst // max(len(queries), 1))
        if (merging_at is not None and sched is not None
                and (i - merging_at) % every == 0
                and len(tickets) < len(queries)):
            for q in queries[len(tickets):len(tickets) + per_burst]:
                t = sched.submit(q)
                check(t is not None, "scheduler shed a smoke query")
                tickets.append(t)
    if sched is not None:
        for q in queries[len(tickets):]:
            tickets.append(sched.submit(q))
    return tickets, merging_at


def recall(found, truth) -> float:
    import numpy as np
    hits = [len(set(f[f >= 0].tolist()) & set(t.tolist()))
            for f, t in zip(found, truth)]
    return float(np.mean(hits)) / truth.shape[1]


def graph_recall(sys_, corpus, queries) -> float:
    """5-recall@5 of the LTI graph searched on exact distances, against the
    exact reference over the points the graph holds."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import index as mem
    g = sys_.lti.graph
    ext = sys_.lti_ext_ids
    held = np.asarray(g.active & ~g.deleted) & (ext >= 0)
    mask = np.zeros(len(corpus), bool)
    mask[ext[held]] = True
    truth = np.asarray(mem.brute_force(jnp.asarray(corpus), jnp.asarray(mask),
                                       jnp.asarray(queries), K))
    slots = np.asarray(mem.search(g, jnp.asarray(queries), sys_.cfg.index,
                                  k=K, L=sys_.cfg.index.L_search)[0])
    found = np.where(slots >= 0, ext[np.maximum(slots, 0)], -1)
    return recall(found, truth)


def reference_gap(corpus, live, queries, truth) -> float:
    """Largest relative gap between the squared distances of the device
    reference's neighbors and the true k nearest, computed on the host in
    float64 (0 when the reference is exact)."""
    import numpy as np
    x = corpus.astype(np.float64)
    worst = 0.0
    for q, row in zip(queries.astype(np.float64), truth):
        d2 = np.where(live, ((x - q) ** 2).sum(1), np.inf)
        best = np.sort(d2)[:len(row)]
        got = np.sort(d2[row])
        worst = max(worst, float(np.max((got - best) / np.maximum(best, 1e-12))))
    return worst


def custom_call_in_programs(sys_, queries):
    """Lower + compile the system's own unified search step and the merge's
    Delete-phase and Insert/Patch programs; each must hold a Mosaic kernel."""
    import jax
    import jax.numpy as jnp
    from repro.core import index as mem
    from repro.core.delete import _repair_blocks_fp
    from repro.core.merge import _insert_patch_phases
    cfg = sys_.cfg
    rw_t, ro_temps, lti_entry = sys_._capture_lanes()
    key, stack, t_tabs, l_tab, tables_np, _ = sys_._lane_bundle(
        rw_t, ro_temps, lti_entry)
    t_drop, l_drop = sys_._drop_mask(key, tables_np)
    q = jnp.asarray(queries[:BATCH_QUERIES])
    kk = min(max(K * 2, K + 8), cfg.index.L_search)
    texts = {"search": mem.unified_search.lower(
        stack, t_tabs, l_tab, t_drop, l_drop, q, cfg.index, k=K, k_lane=kk,
        L=cfg.index.L_search, beam_width=cfg.index.beam_width,
        rerank=True).compile().as_text()}
    g = sys_.lti.graph
    cap, dim = g.vectors.shape
    f32 = jax.ShapeDtypeStruct((cap, dim), jnp.float32)
    texts["merge_delete"] = _repair_blocks_fp.lower(
        g.adjacency, f32, g.deleted, g.active,
        jax.ShapeDtypeStruct((1, cfg.merge_block), jnp.int32),
        cfg.index.alpha, cfg.index.R, True).compile().as_text()
    n_staged = cfg.merge_threshold
    staged = jax.ShapeDtypeStruct((n_staged, dim), jnp.float32)
    texts["merge_insert_patch"] = _insert_patch_phases.lower(
        g, sys_.lti.codes, sys_.lti.codebook, f32, staged,
        jax.ShapeDtypeStruct((n_staged,), jnp.bool_),
        jnp.int32(0), jnp.int32(0), cfg.index, cfg.pq,
        insert_chunk=cfg.insert_batch, block=cfg.merge_block,
        use_sdc=False).compile().as_text()
    return {name: "tpu_custom_call" in t for name, t in texts.items()}


def four_chip_checks(sys_, queries):
    """shard_lti=4 and a 4-replica ReplicaSet vs the one-chip program."""
    import jax
    import numpy as np
    from repro.serving import ReplicaSet
    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    want_ids, want_d = sys_.search_batch(queries, K)

    sharded = copy.copy(sys_)
    sharded.cfg = dataclasses.replace(sys_.cfg, shard_lti=4)
    got_ids, got_d = sharded.search_batch(queries, K)
    check(sharded.lti_shards == 4, f"lti_shards={sharded.lti_shards}")
    same_ids = bool(np.array_equal(got_ids, want_ids))
    same_d = bool(np.array_equal(got_d, want_d))
    log(f"shard_lti=4 vs unsharded: ids identical={same_ids} "
        f"dists identical={same_d}")
    check(same_ids and same_d, "shard_lti=4 results differ from one chip")

    sg = sharded._shard_place[2]
    cap = sys_.lti.graph.vectors.shape[0]
    for name in ("vectors", "adjacency"):
        arr = getattr(sg, name)
        rows = {s.device.id: (s.index[0].start, s.index[0].stop)
                for s in arr.addressable_shards}
        log(f"sharded LTI {name}: rows per device {rows}")
        check(len(rows) == 4, f"{name} rows per device {rows}")
        check(all(b - a == cap // 4 for a, b in rows.values()),
              f"{name} is not split in quarters: {rows}")
    for d in devs:
        stats = d.memory_stats() or {}
        log(f"device {d.id}: bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")

    rs = ReplicaSet(sys_, 4)
    check(rs.n_replicas == 4, f"n_replicas={rs.n_replicas}")
    r_ids, r_d = rs.search_batch(queries, K)
    same_ids = bool(np.array_equal(r_ids, want_ids))
    same_d = bool(np.array_equal(r_d, want_d))
    log(f"ReplicaSet(4) vs direct search_batch: ids identical={same_ids} "
        f"dists identical={same_d} dispatches={rs.dispatches}")
    check(same_ids and same_d, "4-replica results differ from direct search")
    check(all(n > 0 for n in rs.dispatches),
          f"a replica served nothing: {rs.dispatches}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--points", type=int, default=None,
                    help=f"bootstrap points (default {POINTS}; "
                         f"{FOUR_CHIP_POINTS} with --four-chips)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard_lti=4 / 4-replica parity checks")
    args = ap.parse_args()
    n_points = args.points or (FOUR_CHIP_POINTS if args.four_chips
                               else POINTS)
    if not MIN_POINTS <= n_points <= DEPLOY_POINTS:
        ap.error(f"--points must lie in [{MIN_POINTS}, {DEPLOY_POINTS}]")
    n_ops, snapshot, threshold = stream_shape(n_points)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform is {dev.platform!r}); "
              f"this smoke runs only on the chip", file=sys.stderr)
        return 2
    log(f"device kind={dev.device_kind!r} count={len(devs)} "
        f"seed={args.seed} points={n_points}")

    import numpy as np
    import jax.numpy as jnp
    from repro.configs.freshdiskann_1b import FULL
    from repro.core.index import brute_force
    from repro.core.system import bootstrap_system
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import BatchScheduler

    log(f"compile cache: {enable_compile_cache()}")
    global METER
    meter = METER = CompileMeter()
    check(not ops._interpret(), "kernels would run in interpret mode")
    cfg = system_config(FULL.index, FULL.pq, n_points)
    check(cfg.index.kernel_enabled(), "kernels are off on the TPU")
    phase = {}

    t0 = time.perf_counter()
    base, inserts, queries = make_data(n_points, args.seed)
    phase["data"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    sys_ = bootstrap_system(base, np.arange(n_points), cfg)
    jax.block_until_ready(sys_.lti.graph.adjacency)
    phase["bootstrap"] = time.perf_counter() - t0
    log(f"bootstrap: {n_points} points in {phase['bootstrap']:.2f} s "
        f"({n_points / phase['bootstrap']:.1f} points/s, compile included)")
    agree = pq_agreement(sys_.lti, base, n_points, args.seed)
    log(f"PQ codes equal to the host's nearest centroid: {agree:.4f}")
    check(agree >= PQ_AGREEMENT_FLOOR,
          f"PQ codes agree with the host on {agree} < {PQ_AGREEMENT_FLOOR}")

    rng = np.random.default_rng(args.seed)
    victims = rng.choice(n_points, n_ops, replace=False)
    sched = None
    if not args.four_chips:
        sched = BatchScheduler(sys_, k=K)
        sched.start()
    served_while_merging = []
    if sched is not None:
        inner = sched._serve

        def serve(*a, **kw):
            th = sys_._merge_thread
            served_while_merging.append(th is not None and th.is_alive())
            return inner(*a, **kw)

        sched._serve = serve
    t0 = time.perf_counter()
    tickets, merging_at = run_stream(sys_, inserts, victims, n_points, sched,
                                     queries)
    phase["stream"] = time.perf_counter() - t0
    check(merging_at is not None, "the stream never triggered a merge")
    log(f"stream: {n_ops} inserts + {n_ops} deletes in "
        f"{phase['stream']:.2f} s (RO snapshots every {snapshot}, merge at "
        f"{threshold} staged); background merge started after insert "
        f"{merging_at}")

    t0 = time.perf_counter()
    if sched is not None:
        served = [t.result(timeout=1800) for t in tickets]
        sched.stop()
    phase["serve_drain"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    sys_.wait_merge()
    phase["merge_wait"] = time.perf_counter() - t0
    st = sys_.stats
    check(st.merges >= 1, "no StreamingMerge completed")
    check(st.local_repairs + st.global_repairs >= 1, "no Delete phase ran")
    log(f"merge: {st.merges} StreamingMerge(s), {st.merge_seconds:.2f} s "
        f"inside the merge thread; repair local={st.local_repairs} "
        f"global={st.global_repairs}; unreachable_frac="
        f"{st.unreachable_frac}; waited {phase['merge_wait']:.2f} s after "
        f"the stream")
    lti_ids = sys_.lti_ext_ids
    check(not np.isin(victims[:merging_at], lti_ids).any(),
          "deletes issued before the merge still sit in the LTI")

    if args.four_chips:
        four_chip_checks(sys_, queries[:4 * BATCH_QUERIES])
    else:
        n_bm = sum(served_while_merging)
        log(f"serve: {len(served)} queries in {len(served_while_merging)} "
            f"micro-batches, {n_bm} dispatched while the merge ran; "
            f"drain {phase['serve_drain']:.2f} s after the stream; "
            f"p50={st.serve_latency.percentile(50) * 1e3:.2f} ms "
            f"p99={st.serve_latency.percentile(99) * 1e3:.2f} ms")
        check(n_bm >= 1, "no query was served while the merge ran")
        check(all(ids.shape == (K,) for ids, _ in served),
              "a served row is not k wide")

        live = np.ones(n_points + n_ops, bool)
        live[victims] = False
        corpus = np.concatenate([base, inserts])
        t0 = time.perf_counter()
        truth = np.asarray(brute_force(jnp.asarray(corpus), jnp.asarray(live),
                                       jnp.asarray(queries), K))
        phase["reference"] = time.perf_counter() - t0
        gap = reference_gap(corpus, live, queries[:16], truth[:16])
        log(f"exact reference vs host float64 on 16 queries: largest "
            f"relative distance gap {gap:.2e}")
        check(gap <= 1e-4, f"the device reference is not exact: gap {gap}")

        t0 = time.perf_counter()
        k_ids, _ = sys_.search_batch(queries, K)
        phase["search_kernel"] = time.perf_counter() - t0
        jnp_sys = copy.copy(sys_)
        jnp_sys.cfg = dataclasses.replace(
            cfg, index=dataclasses.replace(cfg.index, use_kernel=False))
        jnp_sys.temp_cfg = dataclasses.replace(sys_.temp_cfg,
                                               use_kernel=False)
        t0 = time.perf_counter()
        r_ids, _ = jnp_sys.search_batch(queries, K)
        phase["search_jnp"] = time.perf_counter() - t0
        check(not np.isin(k_ids, victims).any(), "a deleted id was returned")
        rec_k, rec_r = recall(k_ids, truth), recall(r_ids, truth)
        log(f"5-recall@5 vs exact reference: kernel path={rec_k:.4f} "
            f"jnp path={rec_r:.4f}; ids bit-identical="
            f"{bool(np.array_equal(k_ids, r_ids))} "
            f"(rows equal {float(np.mean((k_ids == r_ids).all(1))):.4f})")
        check(abs(rec_k - rec_r) <= RECALL_TOLERANCE,
              f"kernel recall {rec_k} vs jnp recall {rec_r}")
        check(rec_k >= RECALL_FLOOR,
              f"kernel recall {rec_k} < floor {RECALL_FLOOR}")
        t0 = time.perf_counter()
        rec_g = graph_recall(sys_, corpus, queries)
        phase["graph_search"] = time.perf_counter() - t0
        log(f"5-recall@5 of an exact-distance search of the LTI graph: "
            f"{rec_g:.4f}")
        check(rec_g >= GRAPH_RECALL_FLOOR,
              f"graph recall {rec_g} < floor {GRAPH_RECALL_FLOOR}")

        has = custom_call_in_programs(sys_, queries)
        log(f"tpu_custom_call in compiled programs: {has}")
        check(all(has.values()), f"programs without a kernel: {has}")

    log("phases (s): " + ", ".join(f"{k}={v:.2f}" for k, v in phase.items()))
    log(meter.line())
    for d in devs:
        stats = d.memory_stats() or {}
        log(f"device {d.id} peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:
        traceback.print_exc()
        sys.stderr.flush()
        if METER is not None:
            log(METER.line())
        # A stuck worker thread must not keep a failed run alive.
        os._exit(1)
    sys.exit(code)
