"""Pallas TPU kernel: fused Algorithm-4 repair step for a block of nodes.

Deletion repair visits every live node p with a deleted out-neighbor and
rebuilds its row from

    C  <-  (N_out(p) \\ D)  u  ( U_{v in N_out(p) n D} N_out(v) )

followed by RobustPrune (paper Algorithm 4).  The jnp engine materializes
the masked candidate list, gathers, and then pays R separate prune rounds
per node; here the whole block step is one jitted program around ONE prune
launch: the neighbor-of-deleted-neighbor candidate assembly (kept-edge and
expansion masks) and the anchor-distance masking are elementwise XLA
prologue, all R prune rounds run in the shared gridded prune launch
(``robust_prune.prune_call``, one row group per grid step), and the
changed-row select (untouched nodes — dead, or no deleted neighbor — keep
their row) is the epilogue.  One launch per block is the same HBM->VMEM
streaming unit as the paper's sequential SSD block pass.

The HBM gathers stay OUTSIDE the kernel (XLA gathers in the engine): the
kernel receives each node's row, its neighbors' deleted flags, the
pre-gathered expansion rows, and the candidate payloads in *raw*
``concat(row, exp)`` order.  Masked lanes carry garbage payloads and are
provably inert (their anchor distance is forced to +inf before the rounds,
and the winner one-hot never lands on them).

Flavors mirror the prune kernel: ``delete_repair_fp_kernel``
(full-precision coverage) and ``delete_repair_sdc_kernel`` (PQ-code SDC
coverage, the StreamingMerge delete-phase operating point with a capped
expansion width).

Contracts: ``ref.delete_repair_fp_ref`` / ``ref.delete_repair_sdc_ref``
(see docs/KERNELS.md); parity enforced by
``tests/test_kernels.py::test_delete_repair_fp_matches_ref`` /
``test_delete_repair_sdc_matches_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .robust_prune import prune_call


def _assemble(row, nbr_del, exp, exp_ok, usable_c, d_p, p):
    """Candidate assembly + anchor-distance masking.

    row [B, R], nbr_del [B, R] i32, exp [B, E] i32 (pre-gathered expansion
    rows, parent-major flattened, INVALID-padded past the real E_par * R
    lanes), exp_ok [B, E] i32 (per-LANE expansion validity — the parent
    flag repeated R times, zero on padding lanes), usable_c [B, C] i32,
    d_p [B, C] f32 raw, p [B, 1] i32.
    Returns (raw_ids [B, C], d_p_masked [B, C], changed [B, 1] bool).
    """
    nd = nbr_del != 0
    keep_ok = (row >= 0) & ~nd
    raw = jnp.concatenate([row, exp], axis=1)                    # [B, C]
    src_ok = jnp.concatenate([keep_ok, (exp_ok != 0) & (exp >= 0)], axis=1)
    ok = src_ok & (usable_c != 0) & (raw != p)
    d_pm = jnp.where(ok, d_p, jnp.inf)
    changed = jnp.any(nd & (row >= 0), axis=1, keepdims=True)
    return raw, d_pm, changed


def _repair(row, nbr_del, exp, exp_ok, usable_c, d_p, payload, p, live, *,
            alpha, R, interpret, tables=None):
    raw, d_pm, changed = _assemble(row, nbr_del, exp, exp_ok, usable_c,
                                   d_p, p)
    out, _ = prune_call(d_pm, payload, raw, alpha=alpha, R=R,
                        interpret=interpret, tables=tables)
    return jnp.where(changed & (live != 0), out, row)


@functools.partial(jax.jit, static_argnames=("alpha", "R", "interpret"))
def delete_repair_fp_kernel(row, nbr_del, exp, exp_ok, usable_c, d_p, vecs,
                            p, live, *, alpha: float, R: int,
                            interpret: bool = False):
    """One block's fused repair step, full-precision coverage.

    row [B, R] i32, nbr_del [B, R] i32, exp [B, E] i32,
    exp_ok [B, E] i32 (per-lane validity, see ``_assemble``),
    usable_c [B, C] i32 with C = R + E, d_p [B, C] f32 raw,
    vecs [B, C, d] f32 (raw candidate order), p [B, 1] i32, live [B, 1]
    i32 -> new rows [B, R] i32.
    """
    B, C = d_p.shape
    assert vecs.shape[:2] == (B, C) and usable_c.shape == (B, C)
    return _repair(row, nbr_del, exp, exp_ok, usable_c, d_p,
                   vecs.astype(jnp.float32), p, live, alpha=alpha, R=R,
                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("alpha", "R", "interpret"))
def delete_repair_sdc_kernel(row, nbr_del, exp, exp_ok, usable_c, d_p,
                             codes, tables, p, live, *, alpha: float,
                             R: int, interpret: bool = False):
    """One block's fused repair step, SDC coverage from PQ codes.

    Same operands as the fp kernel with (codes [B, C, m] i32,
    tables [m, ksub, ksub] f32) replacing vecs -> new rows [B, R] i32.
    """
    B, C = d_p.shape
    assert codes.shape[:2] == (B, C) and usable_c.shape == (B, C)
    return _repair(row, nbr_del, exp, exp_ok, usable_c, d_p,
                   codes.astype(jnp.int32), p, live, alpha=alpha, R=R,
                   interpret=interpret, tables=tables.astype(jnp.float32))
