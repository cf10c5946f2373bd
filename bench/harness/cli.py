"""One run of one cell: set-up, the measured window, the checks, and the
result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``); the last lines of standard error are
the numbers compared, each beside its limit.  Without a TPU, or with fewer
chips than the cell asks for, the run exits 3 and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Optional

import numpy as np

from . import reference as ref
from . import spec as specs
from . import trace as tr
from .peaks import peaks_for
from .state import state_checks
from .traffic import make_plan
from .window import Cell


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileMeter:
    """Counts backend compilations and persistent-cache hits through JAX's
    monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def enable_cache() -> None:
    """JAX's persistent compilation cache, holding every program, through
    the program's own helper: ``JAX_COMPILATION_CACHE_DIR`` where it is set,
    else a fixed ``.jax_cache`` in the checkout, so only a checkout's first
    run compiles."""
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compilation cache: {enable_compile_cache()}")


@dataclasses.dataclass
class ReadContext:
    """What a metric reader may read."""
    config: dict
    traffic: dict
    plan: object
    rec: object                 # window.WindowRecord
    setup_s: float
    memory_peak_bytes: int
    recall: float
    events: Optional[list]      # trace events (--trace 1), else None
    peaks: Optional[dict]
    drain_end: float
    numbers: dict               # the numbers compared, by name
    device: dict                # the result line's "device" record
    merge_staged: np.ndarray    # the inserts the window's merge took in


def pq_ceiling(sys_, plan, queries: np.ndarray, k: int) -> float:
    """k-recall@k of an exhaustive PQ scan of the bootstrap set plus an
    exact rerank of its best 100: the most that PQ navigation can give on
    this data.  Diagnostic only; read from the bootstrap LTI on the host."""
    n = plan.n_base
    codes = np.asarray(sys_.lti.codes[:n]).astype(np.int64)
    cent = np.asarray(sys_.lti.codebook.centroids, np.float64)
    m, _, dsub = cent.shape
    x = plan.vectors[:n].astype(np.float64)
    hits = 0
    for q in queries.astype(np.float64):
        lut = ((q.reshape(m, 1, dsub) - cent) ** 2).sum(-1)      # [m, ksub]
        adc = lut[np.arange(m), codes].sum(1)
        top = np.argpartition(adc, 100)[:100]
        exact = ((x - q) ** 2).sum(1)
        truth = np.argsort(exact)[:k]
        got = top[np.argsort(exact[top])[:k]]
        hits += len(set(got.tolist()) & set(truth.tolist()))
    return hits / (k * len(queries))


def run_cell_ctx(cell: specs.CellSpec, seed: int, seconds: float,
                 trace: bool, *, patch=None) -> tuple:
    """Set-up, window, checks and metrics: (result object, the
    ``ReadContext`` the metrics were read from)."""
    ctx = measure(cell, seed, seconds, trace, patch=patch)
    return result(cell, ctx, trace), ctx


def measure(cell: specs.CellSpec, seed: int, seconds: float, trace: bool,
            *, patch=None) -> ReadContext:
    """Set-up, window and checks.  ``patch(cell)`` is called after set-up
    (tests break the timed path there).  The caller has checked the
    devices."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    config = cell.config
    k = config["k"]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    log(f"device kind={dev.device_kind!r} platform={dev.platform} "
        f"count={len(devs)} workload={cell.name} seed={seed} "
        f"seconds={seconds} trace={int(trace)}")
    meter = CompileMeter()

    t_setup = time.perf_counter()
    plan = make_plan(config, cell.traffic, seed, seconds)
    run = Cell(config, plan, log)
    ceiling_s = [0.0]

    def after_bootstrap():
        t = time.perf_counter()
        c = pq_ceiling(run.sys, plan, plan.queries[:64], k)
        ceiling_s[0] = time.perf_counter() - t
        log(f"PQ-scan ceiling (exhaustive PQ scan + exact rerank of the best "
            f"100, {min(64, plan.n_searches)} queries, bootstrap set): "
            f"{k}-recall@{k} {c:.4f}")

    run.setup(after_bootstrap=after_bootstrap)
    setup_s = time.perf_counter() - t_setup - ceiling_s[0]
    log(f"set-up {setup_s:.2f} s; compiles so far {meter.compiles} "
        f"({meter.seconds:.2f} s), persistent cache hits {meter.hits}")
    if patch is not None:
        patch(run)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        rec = run.window(trace_dir, compile_count=lambda: meter.compiles)
        drain_end = time.monotonic()
        late = np.nanmax(rec.search_submit - rec.search_due,
                         initial=0.0) if plan.n_searches else 0.0
        log(f"window closed: {plan.n_searches} searches, "
            f"{len(plan.update_times)} updates; generator at most "
            f"{late * 1e3:.2f} ms late; compiles in the window "
            f"{rec.compiles_in_window}; merges {rec.after.merges - rec.before.merges}")
        events = None
        if trace:
            t = time.perf_counter()
            events = tr.load_events(trace_dir)
            log(f"trace: {len(events)} events read in "
                f"{time.perf_counter() - t:.2f} s")
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    peak = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[:cell.chips]))
    run.sys.search_batch(plan.warm_queries, k)     # lands any buffered insert
    setup_deleted = np.concatenate(
        [plan.stage_deletes] + [d for _, d, _ in plan.rounds])
    numbers = {"lost_requests": int(rec.lost.sum()),
               "compiles_in_window": int(rec.compiles_in_window)}
    numbers.update(state_checks(run.sys, plan, run.merge_staged,
                                setup_deleted))
    run.close()
    run.sys = None
    gc.collect()

    # The reference, once the window has closed and the system is freed.
    t = time.perf_counter()
    idx, ids, dists = ref.served_answers(rec, k)
    intervals = ref.id_intervals(plan, rec, len(plan.vectors))
    sub, done = rec.search_submit[idx], rec.search_done[idx]
    q = plan.queries[idx]
    dead = ref.dead_at(intervals, ids, sub, done)
    numbers.update(ref.answer_checks(plan.vectors, q, ids, dists, dead, k))
    truth = ref.exact_truth(plan.vectors, q, intervals, sub, done, k)
    rec_k = ref.recall(ids, truth)
    lti_time = ref.lti_from(plan, run.merge_staged, rec.lti_old_until,
                            len(plan.vectors))
    share, pairs = ref.temp_misses(ids, truth,
                                   ref.temp_only(lti_time, truth, done))
    numbers["temp_miss_share"] = share
    log(f"reference: {len(idx)} answers checked in "
        f"{time.perf_counter() - t:.2f} s; {k}-recall@{k} {rec_k:.4f}; "
        f"{pairs} exact neighbours only in a temp tier, "
        f"{share:.4f} of them missed")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if trace:
        lo, hi = rec.trace_span
        device["busy_s"] = tr.busy_seconds(events)
        device["window_s"] = hi - lo
    return ReadContext(config, cell.traffic, plan, rec, setup_s, peak,
                       rec_k, events, peaks, drain_end, numbers, device,
                       run.merge_staged)


def result(cell: specs.CellSpec, ctx: ReadContext, trace: bool) -> dict:
    """The result line: the cell's end-to-end metrics (untraced) or its
    per-layer metrics (traced), each read by its own reader."""
    rec, plan = ctx.rec, ctx.plan
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = specs.load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": ref.verdict(ctx.numbers),
           "attempted": int(plan.n_searches + len(plan.update_times)),
           "failed": int(rec.shed.sum() + rec.lost.sum()
                         + np.isnan(rec.update_ack).sum()),
           "metrics": metrics, "device": ctx.device}
    if trace:
        out["breakdown"] = {"device_ops": tr.top_ops(ctx.events),
                            "idle_gaps": tr.idle_gaps(ctx.events)}
    out["checks"] = {name: {"value": ctx.numbers[name],
                            "limit": ref.LIMITS[name]}
                     for name in ref.LIMITS}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = specs.load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 3
    enable_cache()
    out, _ = run_cell_ctx(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
