"""Set-up and the measured window of one cell, on the system's own entry
points: ``bootstrap_system``, ``FreshDiskANN.insert/delete``,
``BatchScheduler.submit`` and the background StreamingMerge.

Threads while the window is open: this one submits searches at their due
times, one more applies inserts and deletes at theirs, the scheduler's
worker serves micro-batches, and the system's merge thread merges.  Every
request is timed on ``time.monotonic`` (the scheduler's own clock) from the
time it was due, so a stall counts against every request it delays.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Optional

import numpy as np

from .traffic import INSERT, Plan

DRAIN_SECONDS = 120.0   # how long past the close a due request may take


@dataclasses.dataclass
class StatsView:
    """The SystemStats fields the metric readers use, at one instant."""
    searches: int
    batches: int
    shed: int
    flushes: int
    merges: int
    merge_seconds: float
    search_seen: int
    flush_seen: int

    @classmethod
    def of(cls, st) -> "StatsView":
        return cls(st.searches, st.batches_dispatched, st.shed_requests,
                   st.flushes, st.merges, st.merge_seconds,
                   st.search_latency.seen, st.flush_latency.seen)


@dataclasses.dataclass
class WindowRecord:
    """What the harness saw in the window (all times on time.monotonic)."""
    t0: float
    seconds: float
    search_due: np.ndarray        # [n_s] absolute due times
    search_submit: np.ndarray     # [n_s] when submit() was called
    search_done: np.ndarray       # [n_s] completion (nan: shed or lost)
    shed: np.ndarray              # [n_s] bool
    lost: np.ndarray              # [n_s] bool: admitted, never answered
    results: list                 # [n_s] (ids [k], dists [k]) or None
    update_due: np.ndarray        # [n_u]
    update_start: np.ndarray      # [n_u] call start
    update_ack: np.ndarray        # [n_u] call return (nan: never returned)
    before: StatsView = None
    after: StatsView = None
    search_samples: list = None   # search_batch wall times in the window
    flush_samples: list = None    # flush wall times in the window
    compiles_in_window: int = 0
    trace_span: tuple = None      # (start, end) monotonic of the trace
    lti_old_until: float = np.nan  # last time the LTI was seen without
                                   # the window's merged inserts


def system_config(config: dict):
    from repro.core.config import IndexConfig, PQConfig, SystemConfig
    index = IndexConfig(
        capacity=config["capacity"], dim=config["dim"], R=config["R"],
        L_build=config["L_build"], L_search=config["L_search"],
        alpha=config["alpha"], beam_width=config["beam_width"],
        dtype=config["dtype"])
    pq = PQConfig(dim=config["dim"], m=config["pq_m"], ksub=config["pq_ksub"])
    return SystemConfig(
        index=index, pq=pq,
        ro_snapshot_points=config["ro_snapshot_points"],
        merge_threshold=config["merge_threshold"],
        temp_capacity=config["temp_capacity"],
        insert_batch=config["insert_batch"],
        merge_block=config["merge_block"],
        batch_queries=config["batch_queries"],
        serve_queue_capacity=config["serve_queue_capacity"],
        slo_ms=config["slo_ms"],
        local_repair_threshold=config["local_repair_threshold"],
        background_merge=True)


def _reservoir_window(res, seen_before: int, seen_after: int) -> list:
    """Samples recorded between two ``seen`` counts; exact while the
    reservoir has not wrapped, else its whole (uniform) sample."""
    if seen_after <= res.size:
        return list(res.sample[seen_before:seen_after])
    return list(res.sample)


class Cell:
    """One run of one cell: set-up, window, and the handles the checks
    need afterwards."""

    def __init__(self, config: dict, plan: Plan, log: Callable[[str], None]):
        self.config = config
        self.plan = plan
        self.log = log
        self.k = config["k"]
        self.sys = None
        self.sched = None
        self.merge_staged: np.ndarray = np.zeros(0, np.int64)
        self.merge_deleted: np.ndarray = np.zeros(0, np.int64)
        self._inserted = 0            # inserts made in set-up

    # ----------------------------------------------------------------- set-up
    def _warm(self) -> None:
        """One micro-batch through the system's own search entry point: it
        compiles the unified search program for the current lane count."""
        self.sys.search_batch(self.plan.warm_queries, self.k)

    def _insert(self, ext_id: int) -> None:
        self.sys.insert(int(ext_id), self.plan.vectors[ext_id])
        self._inserted += 1
        if self._inserted % self.config["insert_batch"] == 0:
            self._warm()       # buffer empty: a search flushes nothing

    def setup(self, after_bootstrap: Optional[Callable[[], None]] = None
              ) -> None:
        from repro.core.system import bootstrap_system
        from repro.serving import BatchScheduler
        import jax

        cfg = system_config(self.config)
        plan = self.plan
        base = plan.vectors[:plan.n_base]
        t = time.perf_counter()
        self.sys = bootstrap_system(
            base, np.arange(plan.n_base), cfg,
            batch=self.config["build_batch"])
        jax.block_until_ready(self.sys.lti.graph.adjacency)
        self.log(f"bootstrap: {plan.n_base} points in "
                 f"{time.perf_counter() - t:.2f} s")
        if after_bootstrap is not None:
            after_bootstrap()
        self._warm()
        for ins, dels, under in plan.rounds:
            t = time.perf_counter()
            merges = self.sys.stats.merges
            for e, x in zip(dels, ins):
                self.sys.delete(int(e))
                self._insert(x)
            for x in under:
                self._insert(x)
            self.sys.wait_merge()
            if self.sys.stats.merges != merges + 1:
                raise RuntimeError("a set-up round did not run one merge")
            self._warm()
            self.log(f"set-up merge round: {len(ins)} inserts, {len(dels)} "
                     f"deletes, {len(under)} inserts under the merge, "
                     f"{time.perf_counter() - t:.2f} s")
        done = len(plan.rounds[-1][2]) if plan.rounds else 0
        ins, dels = plan.stage_inserts[done:], plan.stage_deletes
        for j in range(max(len(ins), len(dels))):
            if j < len(dels):
                self.sys.delete(int(dels[j]))
            if j < len(ins):
                self._insert(ins[j])
        if len(plan.stage_inserts):
            # The window's first insert completes the staged set.
            self.merge_staged = np.append(plan.stage_inserts,
                                          plan.update_ids[0])
            self.merge_deleted = np.asarray(plan.stage_deletes)
            self.warm_local_repair()
        self.sched = BatchScheduler(self.sys, k=self.k)
        self.sched.start()

    def warm_local_repair(self) -> None:
        """Compile, without running them, the localized Delete-phase programs
        the window's merge will launch.  Their shapes follow the number of
        affected rows (live rows with an edge to a deleted one), which the
        staged deletes fix: this computes it and compiles the repair for
        that many 1,024-row blocks and one more.  Where the program's
        internals moved, the warm-up raises and the
        run fails.  The program has no public entry point that compiles
        these without running a merge (PERF.md, Open questions)."""
        import jax
        import jax.numpy as jnp
        from repro.core import delete as dl
        g = self.sys.lti.graph
        table = np.asarray(self.sys.lti_ext_ids)
        dmask = jnp.asarray(np.isin(table, self.merge_deleted))
        usable = g.active & ~g.deleted & ~dmask
        adj = g.adjacency
        hit = (adj >= 0) & dmask[jnp.maximum(adj, 0)]
        n_aff = int((usable & hit.any(axis=1)).sum())
        block = self.config["merge_block"]
        R = self.config["R"]
        cap, dim = g.vectors.shape
        n_blocks = -(-n_aff // block)
        f32 = jax.ShapeDtypeStruct((cap, dim), jnp.float32)
        for nb in (n_blocks, n_blocks + 1):
            if nb == 0:
                continue
            ids = jax.ShapeDtypeStruct((nb, block), jnp.int32)
            dl._repair_blocks_fp.lower(
                adj, f32, g.deleted, usable, ids, self.config["alpha"],
                R, self.sys.cfg.index.kernel_enabled()).compile()
            aff = np.arange(nb * block - block // 2, dtype=np.int64)
            dl._scatter_repaired(
                adj, lambda i: jnp.zeros(i.shape + (R,), jnp.int32),
                aff, block, R).block_until_ready()
        self.log(f"local repair warmed: {n_aff} affected rows, "
                 f"{n_blocks} and {n_blocks + 1} blocks")

    # ----------------------------------------------------------------- window
    def window(self, trace_dir: Optional[str] = None,
               compile_count: Callable[[], int] = lambda: 0) -> WindowRecord:
        import jax
        plan = self.plan
        sys_, sched = self.sys, self.sched
        n_s, n_u = plan.n_searches, len(plan.update_times)
        rec = WindowRecord(
            t0=0.0, seconds=plan.seconds,
            search_due=np.zeros(n_s), search_submit=np.full(n_s, np.nan),
            search_done=np.full(n_s, np.nan), shed=np.zeros(n_s, bool),
            lost=np.zeros(n_s, bool), results=[None] * n_s,
            update_due=np.zeros(n_u), update_start=np.full(n_u, np.nan),
            update_ack=np.full(n_u, np.nan))
        errors: list = []
        probe = int(self.merge_staged[0]) if len(self.merge_staged) else None
        table = [None, probe is None]     # last LTI id table seen; has probe

        def see_merge() -> None:
            """Note the time while the LTI does not hold the staged inserts
            yet (its id table is replaced whole when the merge lands)."""
            if table[1]:
                return
            now = time.monotonic()
            t = sys_.lti_ext_ids
            if t is not table[0]:
                table[0], table[1] = t, bool(np.any(t == probe))
            if not table[1]:
                rec.lti_old_until = now

        def updater():
            try:
                for j in range(n_u):
                    due = rec.t0 + plan.update_times[j]
                    wait = due - time.monotonic()
                    if wait > 0:
                        time.sleep(wait)
                    e = int(plan.update_ids[j])
                    rec.update_start[j] = time.monotonic()
                    if plan.update_kinds[j] == INSERT:
                        sys_.insert(e, plan.vectors[e])
                    else:
                        sys_.delete(e)
                    rec.update_ack[j] = time.monotonic()
                    see_merge()
            except Exception as ex:   # reported after the window
                errors.append(ex)

        before = StatsView.of(sys_.stats)
        c0 = compile_count()
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir)
        trace_start = time.monotonic()
        rec.t0 = t0 = time.monotonic() + 0.05
        rec.search_due[:] = t0 + plan.search_times
        rec.update_due[:] = t0 + plan.update_times
        upd = threading.Thread(target=updater, name="bench-updater")
        upd.start()
        tickets = [None] * n_s
        for j in range(n_s):
            wait = rec.search_due[j] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            rec.search_submit[j] = time.monotonic()
            t = sched.submit(plan.queries[j])
            if t is None:
                rec.shed[j] = True
            tickets[j] = t
            see_merge()
        close = t0 + plan.seconds
        if time.monotonic() < close:
            time.sleep(close - time.monotonic())
        deadline = close + DRAIN_SECONDS
        for j, t in enumerate(tickets):
            if t is None:
                continue
            if not t.done.wait(max(deadline - time.monotonic(), 0.0)) \
                    or t.error is not None:
                rec.lost[j] = True
                continue
            rec.search_done[j] = t.completion
            rec.results[j] = (np.asarray(t.ids), np.asarray(t.dists))
        upd.join(max(deadline - time.monotonic(), 0.0))
        if upd.is_alive():
            raise RuntimeError("updates due in the window did not finish "
                               f"within {DRAIN_SECONDS} s of its close")
        if errors:
            raise errors[0]
        sys_.wait_merge()
        see_merge()
        trace_end = time.monotonic()
        if trace_dir is not None:
            jax.profiler.stop_trace()
        rec.trace_span = (trace_start, trace_end)
        rec.compiles_in_window = compile_count() - c0
        st = sys_.stats
        rec.before, rec.after = before, StatsView.of(st)
        rec.search_samples = _reservoir_window(
            st.search_latency, before.search_seen, rec.after.search_seen)
        rec.flush_samples = _reservoir_window(
            st.flush_latency, before.flush_seen, rec.after.flush_seen)
        return rec

    def close(self) -> None:
        """Stop the scheduler; the caller drops ``self.sys`` to free it."""
        if self.sched is not None:
            self.sched.stop()
            self.sched = None
