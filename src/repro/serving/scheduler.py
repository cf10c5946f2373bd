"""Continuous-batching serving front end (docs/SERVING.md, "The serving
loop"): a bounded request queue + deadline-aware scheduler that packs
ragged live arrivals into the fixed-shape micro-batches ``search_batch``
serves.

The paper's headline system claim (§6.3) is thousands of *concurrent*
real-time inserts, deletes and searches per second; ``search_batch`` +
``SystemConfig.batch_queries`` give the fixed-shape micro-batch engine, and
this module is the piece that coalesces live traffic into it — the
continuous-batching pattern from LLM serving applied to the unified §5.2
fan-out.  A batch closes when it fills to ``batch_queries`` OR when the
oldest queued request's deadline budget (``SystemConfig.slo_ms`` minus a
measured dispatch estimate) would otherwise be violated, whichever comes
first; results are de-interleaved back to callers row by row, bit-identical
to calling ``search_batch`` directly (the batch IS one ``search_batch``
call, and per-query bit-parity is the serving engine's standing contract).

Determinism is a design seam, not an afterthought: every policy decision —
admit vs shed, close vs wait, miss vs meet — consults ONLY the injected
``Clock`` (``SystemConfig.clock``), never the wall.  The policy core
(``submit`` / ``poll`` / ``dispatch`` / ``next_close_time``) is fully
synchronous, so a test driving it with a ``VirtualClock`` reproduces every
decision bit-for-bit (``tests/test_scheduler.py``); ``start()`` wraps the
same core in a worker thread against the wall clock for production use,
where background threshold merges (``SystemConfig.background_merge``)
overlap the serving loop by construction (the merge thread swaps immutable
generations; searches never block on it).

Queue growth under overload is bounded: submissions past
``SystemConfig.serve_queue_capacity`` are SHED — ``submit`` returns None
and ``SystemStats.shed_requests`` counts them — so saturation surfaces as
explicit rejections instead of unbounded latency.

Filtered and multi-tenant traffic rides the same queue: ``submit`` takes an
optional ``FilterSpec`` and a closed micro-batch contains only tickets that
share the OLDEST queued ticket's spec (a batch is ONE ``search_batch``
call, and the filter is a per-call argument — mixing specs in one batch is
impossible, so the scheduler de-interleaves them while preserving per-spec
FIFO order).  When ``SystemConfig.tenant_quota`` > 0, a tenant may hold at
most that many queued tickets; submissions past the quota are shed with
the same explicit contract (``submit`` returns None) and counted per
tenant in ``SystemStats.tenant_sheds`` as well as ``shed_requests`` — one
tenant's burst cannot crowd every other tenant out of the bounded queue.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Optional, Protocol, runtime_checkable

import numpy as np
from jax.profiler import TraceAnnotation

from ..core.system import name_os_thread, serving_batch


@runtime_checkable
class Clock(Protocol):
    """What the scheduler needs from time: a monotonic ``now()``."""

    def now(self) -> float:
        ...


class WallClock:
    """Production clock: ``time.monotonic`` seconds."""

    def now(self) -> float:
        return time.monotonic()


class VirtualClock:
    """A manually-advanced clock: ``now()`` returns exactly what the test
    set, so every scheduler decision derived from it is deterministic.
    Picklable (it rides inside ``SystemConfig``)."""

    def __init__(self, start: float = 0.0):
        self._t = float(start)

    def now(self) -> float:
        return self._t

    def advance(self, seconds: float) -> float:
        if seconds < 0:
            raise ValueError(f"VirtualClock.advance({seconds}): time only "
                             f"moves forward")
        self._t += float(seconds)
        return self._t


class Ticket:
    """One in-flight request: the caller's handle to its (ids, dists) row.

    ``result()`` blocks (wall-clock deployments); under a virtual clock the
    test drives the scheduler itself, so ``done`` is already set when it
    reads the fields.  ``latency`` is completion - arrival on the
    scheduler's clock; ``missed`` is the deadline verdict recorded at
    completion."""

    __slots__ = ("query", "arrival", "deadline", "fspec", "ids", "dists",
                 "completion", "missed", "done", "error")

    def __init__(self, query: np.ndarray, arrival: float, deadline: float,
                 fspec=None):
        self.query = query
        self.arrival = arrival
        self.deadline = deadline
        self.fspec = fspec
        self.ids: Optional[np.ndarray] = None
        self.dists: Optional[np.ndarray] = None
        self.completion: Optional[float] = None
        self.missed = False
        self.done = threading.Event()
        self.error: Optional[BaseException] = None

    @property
    def latency(self) -> Optional[float]:
        if self.completion is None:
            return None
        return self.completion - self.arrival

    def result(self, timeout: Optional[float] = None
               ) -> tuple[np.ndarray, np.ndarray]:
        if not self.done.wait(timeout):
            raise TimeoutError("request not served within timeout")
        if self.error is not None:
            raise self.error
        return self.ids, self.dists


class BatchScheduler:
    """The deadline-aware continuous-batching scheduler.

    Policy (all against ``clock.now()``):

      * ``submit(query)`` — admit to the FIFO queue, or SHED (return None)
        when the queue is at ``cfg.serve_queue_capacity``.
      * ``poll()`` — close a micro-batch when (a) the queue holds
        ``cfg.batch_queries`` requests (full close), or (b) ``cfg.slo_ms``
        is set and ``now + dispatch_estimate`` has reached the OLDEST
        request's deadline (deadline close: waiting any longer would blow
        its budget).  An empty queue never closes a batch.
      * ``dispatch(batch)`` — one ``serve`` call on the stacked queries
        (default ``system.search_batch``; a ``ReplicaSet.route`` plugs in
        here for multi-replica serving), rows de-interleaved back to the
        tickets in arrival order, per-request latency recorded into
        ``stats.serve_latency`` and late completions into
        ``stats.deadline_misses``.

    The dispatch estimate is an EWMA of measured dispatch wall time on the
    scheduler's clock, seeded by ``cfg.dispatch_estimate_ms``; under a
    virtual clock the measurement is whatever the test advances (usually
    0), so the estimate — and hence every close decision — stays
    deterministic.

    ``run_once``/``flush`` drive the core synchronously; ``start``/``stop``
    run it on a worker thread (wall-clock deployments only — a virtual
    clock never moves on its own, so the thread would sleep forever).
    """

    def __init__(self, system, k: int, *, L: Optional[int] = None,
                 beam_width: Optional[int] = None,
                 serve: Optional[Callable] = None,
                 clock: Optional[Clock] = None):
        cfg = system.cfg
        if cfg.batch_queries <= 0:
            raise ValueError(
                "BatchScheduler needs SystemConfig.batch_queries > 0 — the "
                "micro-batch width is the shape batches are packed to")
        self.system = system
        self.stats = system.stats
        self.k = k
        self.L = L
        self.beam_width = beam_width
        self.batch_queries = cfg.batch_queries
        self.capacity = cfg.serve_queue_capacity
        self.slo = cfg.slo_ms / 1e3 if cfg.slo_ms > 0 else None
        self.tenant_quota = max(cfg.tenant_quota, 0)
        self._queued_by_tenant: dict = {}
        self.clock: Clock = clock or cfg.clock or WallClock()
        self.dispatch_estimate = max(cfg.dispatch_estimate_ms, 0.0) / 1e3
        self._serve = serve or system.search_batch
        self._queue: deque[Ticket] = deque()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self._error: Optional[BaseException] = None  # worker's failure
        # Occupancy accounting beyond the last-batch gauge: mean fill over
        # the scheduler's lifetime (benchmarks report it per run).
        self._occupancy_sum = 0.0
        self._batches = 0

    # ------------------------------------------------------------- requests
    def submit(self, query: np.ndarray, filter=None) -> Optional[Ticket]:
        """Admit one query (shape [dim]) or shed it.

        ``filter`` is an optional ``FilterSpec`` carried on the ticket and
        applied to the micro-batch that serves it.  Returns the caller's
        ``Ticket``, or None when the bounded queue is full OR the ticket's
        tenant already holds ``cfg.tenant_quota`` queued tickets — every
        shed is counted (``shed_requests``; quota sheds additionally in
        ``tenant_sheds[tenant]``), never silently dropped."""
        q = np.asarray(query, np.float32)
        fspec = filter if filter is not None and not filter.is_empty \
            else None
        tenant = fspec.tenant if fspec is not None else None
        with self._cond:
            if self._error is not None:
                # The worker died: nothing would ever serve this ticket.
                raise RuntimeError("scheduler worker failed") \
                    from self._error
            if len(self._queue) >= self.capacity:
                self.stats.shed_requests += 1
                return None
            if (self.tenant_quota and tenant is not None
                    and self._queued_by_tenant.get(tenant, 0)
                    >= self.tenant_quota):
                self.stats.shed_requests += 1
                self.stats.tenant_sheds[tenant] = (
                    self.stats.tenant_sheds.get(tenant, 0) + 1)
                return None
            now = self.clock.now()
            deadline = now + self.slo if self.slo is not None else np.inf
            t = Ticket(q, now, deadline, fspec)
            if tenant is not None:
                self._queued_by_tenant[tenant] = (
                    self._queued_by_tenant.get(tenant, 0) + 1)
            self._queue.append(t)
            self.stats.scheduled_requests += 1
            self.stats.queue_depth = len(self._queue)
            self._cond.notify()
        return t

    @property
    def pending(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def mean_occupancy(self) -> float:
        """Lifetime mean fill fraction of dispatched micro-batches."""
        if self._batches == 0:
            return 0.0
        return self._occupancy_sum / self._batches

    # --------------------------------------------------------------- policy
    def next_close_time(self) -> Optional[float]:
        """The clock time at which the current queue must close: now when
        already full, the oldest deadline minus the dispatch estimate under
        an SLO, None when empty (or when no SLO bounds a partial batch —
        it then closes only on fill or ``flush``).  The worker thread (and
        a deterministic test driver) sleeps exactly until this."""
        with self._lock:
            return self._next_close_locked()

    def _next_close_locked(self) -> Optional[float]:
        if not self._queue:
            return None
        if len(self._queue) >= self.batch_queries:
            return self.clock.now()
        if self.slo is None:
            return None
        return self._queue[0].deadline - self.dispatch_estimate

    def poll(self) -> Optional[list[Ticket]]:
        """Close a micro-batch if policy says so at ``clock.now()``; the
        caller dispatches it.  Returns None when no close is due."""
        with self._lock:
            close_at = self._next_close_locked()
            if close_at is None or self.clock.now() < close_at:
                return None
            return self._take_locked()

    def _take_locked(self) -> list[Ticket]:
        """Pop the next micro-batch: up to ``batch_queries`` tickets that
        share the OLDEST queued ticket's filter spec, in FIFO order.  A
        batch is one ``search_batch`` call and the filter is a per-call
        argument, so mixed-spec arrivals de-interleave into same-spec
        batches; tickets with other specs keep their queue positions."""
        if not self._queue:
            return []
        spec = self._queue[0].fspec
        batch: list[Ticket] = []
        rest: list[Ticket] = []
        while self._queue and len(batch) < self.batch_queries:
            t = self._queue.popleft()
            if t.fspec == spec:
                batch.append(t)
            else:
                rest.append(t)
        for t in reversed(rest):
            self._queue.appendleft(t)
        for t in batch:
            if t.fspec is not None and t.fspec.tenant is not None:
                left = self._queued_by_tenant.get(t.fspec.tenant, 0) - 1
                if left > 0:
                    self._queued_by_tenant[t.fspec.tenant] = left
                else:
                    self._queued_by_tenant.pop(t.fspec.tenant, None)
        self.stats.queue_depth = len(self._queue)
        return batch

    # ------------------------------------------------------------- dispatch
    def dispatch(self, batch: list[Ticket]) -> None:
        """Serve one closed micro-batch and de-interleave the rows back.

        The batch rides ONE ``serve`` call on the stacked queries — with
        ``batch_queries`` set on the system, a partial batch is zero-padded
        by ``search_batch`` itself, so scheduled results are bit-identical
        to the caller invoking ``search_batch`` directly (per-query
        bit-parity is the engine's contract; this layer only stacks and
        slices rows in arrival order)."""
        if not batch:
            return
        qs = np.stack([t.query for t in batch])
        t0 = self.clock.now()
        kw = {}
        if batch[0].fspec is not None:
            # Filter rides as a kwarg only when set, so label-free serve
            # callables (and pre-filter test doubles) keep their signature.
            kw["filter"] = batch[0].fspec
        # The batch sequence number rides the serve call: every search span
        # under it carries it (docs/SERVING.md, "Tracing").
        token = serving_batch.set(self._batches)
        try:
            with TraceAnnotation("fdann.serve.batch", batch=self._batches,
                                 n=len(batch), oldest_wait_ms=(
                                     t0 - batch[0].arrival) * 1e3):
                ids, dists = self._serve(qs, self.k, L=self.L,
                                         beam_width=self.beam_width, **kw)
        finally:
            serving_batch.reset(token)
        t1 = self.clock.now()
        # EWMA toward the measured dispatch; on a virtual clock the
        # measurement is the test's advance (0 unless it models compute),
        # so the estimate trajectory is deterministic too.
        self.dispatch_estimate = (0.8 * self.dispatch_estimate
                                  + 0.2 * (t1 - t0))
        occupancy = len(batch) / self.batch_queries
        self.stats.batches_dispatched += 1
        self.stats.batch_occupancy = occupancy
        self._occupancy_sum += occupancy
        self._batches += 1
        for i, t in enumerate(batch):
            t.ids, t.dists = ids[i], dists[i]
            t.completion = t1
            self.stats.serve_latency.record(t1 - t.arrival)
            if t1 > t.deadline:
                t.missed = True
                self.stats.deadline_misses += 1
            t.done.set()

    def run_once(self) -> int:
        """One synchronous scheduler turn: poll, dispatch if a batch
        closed.  Returns the number of requests served (0 = nothing due).
        This is the deterministic drive path — tests advance the virtual
        clock and call this at the times ``next_close_time`` names."""
        batch = self.poll()
        if batch is None:
            return 0
        self.dispatch(batch)
        return len(batch)

    def flush(self) -> int:
        """Drain the queue unconditionally (shutdown path): close batches
        of at most ``batch_queries`` until empty, deadlines or not."""
        served = 0
        while True:
            with self._lock:
                batch = self._take_locked()
            if not batch:
                return served
            self.dispatch(batch)
            served += len(batch)

    # ------------------------------------------------------- threaded loop
    def start(self) -> None:
        """Run the loop on a worker thread (wall-clock only): wake on
        arrivals, sleep until ``next_close_time``, dispatch outside the
        lock so submissions never block on a device program."""
        if self._thread and self._thread.is_alive():
            return
        self._running = True
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="fdann-serve")
        self._thread.start()

    def stop(self, flush: bool = True) -> None:
        """Stop the worker; by default serve whatever is still queued.

        A dispatch that raised on the worker thread ended the loop; its
        exception is re-raised here (before any flush), so a failed
        device program never passes for a clean shutdown."""
        self._running = False
        with self._cond:
            self._cond.notify_all()
        if self._thread:
            self._thread.join()
            self._thread = None
        err, self._error = self._error, None
        if err is not None:
            raise err
        if flush:
            self.flush()

    def _loop(self) -> None:
        name_os_thread("fdann-serve")
        while True:
            with self._cond:
                if not self._running:
                    return
                close_at = self._next_close_locked()
                now = self.clock.now()
                if close_at is None:
                    self._cond.wait(timeout=0.05)
                    continue
                if now < close_at:
                    # New arrivals can only move the close EARLIER (a full
                    # queue) — the notify wakes us to re-evaluate.
                    self._cond.wait(timeout=close_at - now)
                    continue
                batch = self._take_locked()
            try:
                self.dispatch(batch)   # outside the lock: submits proceed
            except BaseException as e:
                # The batch's callers, and every caller still queued, see
                # the failure from result(); later submits raise it, and
                # stop() re-raises it to whoever owns the scheduler.
                with self._cond:
                    self._error = e
                    self._running = False
                    failed = batch + list(self._queue)
                    self._queue.clear()
                    self._queued_by_tenant.clear()
                    self.stats.queue_depth = 0
                for t in failed:
                    t.error = e
                    t.done.set()
                return
