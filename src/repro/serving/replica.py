"""Multi-replica data-parallel serving (docs/SERVING.md, "Multi-replica
routing").

``ReplicaSet`` replicates the unified fan-out across device groups on a
2-axis ``[replica, data]`` mesh (``distributed.sharding.replica_mesh``):
each row of the grid is one replica serving whole micro-batches against a
full copy of the index, and the columns are the within-replica
``shard_lti`` row shards — the existing owner-computed + psum'd sharded
program (``serving.steps.make_sharded_unified_step``) runs unchanged on
each replica's 1-axis group mesh (``replica_groups``), so the two axes
compose instead of interacting.

Micro-batches are routed round-robin across replicas.  Every replica
serves from the SAME immutable lane bundle the system's own serving path
uses (``_lane_bundle`` — tier states are immutable values, swapped by
flush/rollover/merge), and the sharded lane is bit-identical to the
unsharded one by the PR-5 contract, so per-query results are bit-identical
across replica counts and to ``system.search_batch`` directly.  Placement
caches are keyed by LTI graph/codes identity exactly like the system's
single-mesh cache: a background merge swaps the LTI, every replica's next
dispatch misses its placement cache and re-places the new generation —
routing survives merges by the same mechanism that makes single-replica
serving survive them.

When fewer devices exist than ``replicas x shards`` the set DEGRADES
rather than raises — shards cap at the census, then replicas at
``census // shards`` (always >= 1) — so the same config runs on a laptop
and a pod (census-capping mirrors ``SystemConfig.shard_lti``).
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.system import lane_hops_metadata, serving_batch


class ReplicaSet:
    """Round-robin router over N data-parallel serving replicas.

    ``search_batch`` mirrors ``system.search_batch`` (same signature, same
    micro-batch chunking, bit-identical results), routing each fixed-shape
    micro-batch to the next replica; pass it to ``BatchScheduler`` as the
    ``serve`` callable to put the scheduler in front of the replicas.
    ``dispatches[r]`` counts the micro-batches each replica served (the
    round-robin accounting contract in ``tests/test_serving.py``).
    """

    def __init__(self, system, n_replicas: int, *,
                 n_shards: Optional[int] = None):
        from ..distributed.sharding import replica_groups, replica_mesh
        if n_replicas < 1:
            raise ValueError(f"ReplicaSet: n_replicas={n_replicas} must "
                             f"be >= 1")
        if n_shards is None:
            n_shards = max(1, system.cfg.shard_lti)
        ndev = len(jax.devices())
        self.n_shards = min(max(1, n_shards), ndev)
        self.n_replicas = max(1, min(n_replicas, ndev // self.n_shards))
        self.system = system
        self.mesh = replica_mesh(self.n_replicas, self.n_shards)
        self.groups = replica_groups(self.mesh)
        self.dispatches = [0] * self.n_replicas
        self._rr = 0
        # Per-replica program caches, mirroring ``system._sharded_program``:
        # placement keyed by LTI graph/codes identity (a merge swaps them
        # and misses), jitted step per static (k, kk, L, W, rerank).
        self._place: list = [None] * self.n_replicas
        self._steps: list = [dict() for _ in range(self.n_replicas)]

    # ---------------------------------------------------------------- route
    def search_batch(self, queries: np.ndarray, k: int,
                     L: Optional[int] = None,
                     beam_width: Optional[int] = None,
                     replica: Optional[int] = None,
                     filter=None
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Serve a query batch through the replica set.

        Identical contract to ``system.search_batch`` — same L/W/kk
        resolution, same ``batch_queries`` fixed-shape chunking with a
        zero-padded tail, same ``filter`` (FilterSpec) semantics,
        bit-identical per-query results — except each micro-batch is
        dispatched to a replica: round-robin by default, or pinned with
        ``replica=r``."""
        sys_ = self.system
        sys_._search_flush()
        fspec = filter if filter is not None and not filter.is_empty \
            else None
        L = L or sys_.cfg.index.L_search
        if k > L:
            raise ValueError(
                f"search(k={k}, L={L}): k must be <= L — the candidate "
                f"list holds only L entries, so more than L results cannot "
                f"be returned; raise L or lower k")
        W = beam_width or sys_._beam_width(queries)
        kk = min(max(k * 2, k + 8), L)
        q = np.asarray(queries, np.float32)
        B = q.shape[0]
        sys_.stats.searches += B
        if fspec is not None:
            sys_.stats.filtered_searches += B
            if fspec.tenant is not None:
                sys_.stats.tenant_searches[fspec.tenant] = (
                    sys_.stats.tenant_searches.get(fspec.tenant, 0) + B)
        if B == 0:
            return (np.zeros((0, k), np.int64),
                    np.zeros((0, k), np.float32))
        bq = sys_.cfg.batch_queries
        if not bq or B <= bq:
            return self._dispatch_sliced(q, bq, k, kk, L, W, replica, fspec)
        outs = []
        for lo in range(0, B, bq):
            chunk = q[lo:lo + bq]
            outs.append(self._dispatch_sliced(chunk, bq, k, kk, L, W,
                                              replica, fspec))
        return (np.concatenate([o[0] for o in outs]),
                np.concatenate([o[1] for o in outs]))

    def _dispatch_sliced(self, chunk, bq, k, kk, L, W, replica, fspec=None):
        """Pad one chunk to the compiled width, dispatch, slice pads off."""
        n = len(chunk)
        if bq and n < bq:
            qp = np.zeros((bq, chunk.shape[1]), np.float32)
            qp[:n] = chunk
            chunk = qp
        ids, d = self._dispatch(chunk, k, kk, L, W, replica, fspec)
        return ids[:n], d[:n]

    def _next_replica(self) -> int:
        r = self._rr
        self._rr = (self._rr + 1) % self.n_replicas
        return r

    # ------------------------------------------------------------- dispatch
    def _dispatch(self, queries, k, kk, L, W, replica, fspec=None):
        """Serve ONE fixed-shape micro-batch on one replica's device group.

        Mirrors ``system._search_dispatch``: same lane capture, same
        bundle, same drop masks, same spans, and the same per-dispatch
        latency sample, timed from the lane capture to the results on the
        host — only the mesh the sharded program runs on differs.  Falls
        back to the system's own dispatch when there is no LTI lane to
        place (the bundle-less warm-up regime, or ``batch_fanout=False``):
        the replica axis only exists once an LTI generation is live."""
        import jax.numpy as jnp
        sys_ = self.system
        r = replica if replica is not None else self._next_replica()
        if not 0 <= r < self.n_replicas:
            raise ValueError(f"replica={r} out of range "
                             f"[0, {self.n_replicas})")
        t0 = time.perf_counter()
        (rw_t, ro_temps, lti_entry), bundle, drop = sys_._prepare_batch(fspec)
        if rw_t is None and not ro_temps and lti_entry is None:
            return sys_._aggregate([], k, queries.shape[0])
        if bundle is None or lti_entry is None:
            self.dispatches[r] += 1     # routed, served on the system path
            return sys_._search_dispatch(queries, k, kk, L, W, fspec)
        _, stack, t_tabs, l_tab, _, _ = bundle
        t_drop, l_drop = drop
        with TraceAnnotation("fdann.search.device",
                             batch=serving_batch.get()) as span:
            step, sstack = self._replica_program(
                r, stack, k=k, kk=kk, L=L, W=W, rerank=sys_.cfg.rerank)
            ids, d, hops, _ = step(sstack, t_tabs, l_tab, t_drop, l_drop,
                                   jnp.asarray(queries, jnp.float32))
            out = (np.asarray(ids).astype(np.int64),
                   np.asarray(d).astype(np.float32))
            if span.is_enabled():
                lane_hops_metadata(span, hops)
        sys_.stats.search_latency.record(time.perf_counter() - t0)
        sys_.stats.search_dispatches += 1
        self.dispatches[r] += 1
        return out

    def _replica_program(self, r, stack, *, k, kk, L, W, rerank):
        """(step, stack-with-placed-LTI) for replica ``r``'s group mesh.

        Cache discipline is ``system._sharded_program``'s, per replica:
        placement re-keys on LTI graph/codes identity (merge survival),
        steps on the index config + static shape tuple."""
        from ..core.graph import LaneStack, shard_lti
        from .steps import make_sharded_unified_step
        mesh = self.groups[r]
        place = self._place[r]
        if (place is None or place[0] is not stack.lti
                or place[1] is not stack.codes):
            sg, sc = shard_lti(stack.lti, stack.codes, self.n_shards,
                               mesh=mesh)
            place = (stack.lti, stack.codes, sg, sc)
            self._place[r] = place
        key = (self.system.cfg.index, k, kk, L, W, rerank)
        step = self._steps[r].get(key)
        if step is None:
            step = make_sharded_unified_step(
                mesh, self.system.cfg.index, k=k, k_lane=kk, L=L,
                beam_width=W, rerank=rerank)
            self._steps[r][key] = step
        return step, LaneStack(stack.temps, place[2], place[3],
                               stack.codebook)
