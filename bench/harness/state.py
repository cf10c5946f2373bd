"""Checks of the index's state after the window, against what the harness
acknowledged: read through the system's public attributes once every
request has been answered and the merge has ended."""
from __future__ import annotations

import numpy as np


def live_ids(sys_) -> np.ndarray:
    """External ids live in any tier, less the DeleteList."""
    parts = [np.asarray(sys_.lti_ext_ids)]
    parts += [t.ext_ids for t in [sys_.rw] + list(sys_.ro)]
    ids = np.concatenate(parts)
    ids = np.unique(ids[ids >= 0])
    gone = np.fromiter(sys_.deleted_ext.copy(), np.int64)
    return ids[~np.isin(ids, gone)]


def expected_live(plan) -> np.ndarray:
    """Bootstrap plus every insert, less every delete, all acknowledged."""
    from .traffic import INSERT
    ins = [np.arange(plan.n_base), plan.stage_inserts]
    dels = [plan.stage_deletes]
    for i, d, u in plan.rounds:
        ins += [i, u]
        dels.append(d)
    win = plan.update_kinds == INSERT
    ins.append(plan.update_ids[win])
    dels.append(plan.update_ids[~win])
    return np.setdiff1d(np.concatenate(ins), np.concatenate(dels))


def state_checks(sys_, plan, merge_staged: np.ndarray,
                 setup_deleted: np.ndarray) -> dict:
    """state_errors, lti_errors and dangling_edges (see reference.py)."""
    import jax.numpy as jnp
    have = live_ids(sys_)
    want = expected_live(plan)
    state_errors = (len(np.setdiff1d(want, have))
                    + len(np.setdiff1d(have, want)))

    table = np.asarray(sys_.lti_ext_ids)
    slots = np.nonzero(table >= 0)[0]
    in_lti = table[slots]
    g = sys_.lti.graph
    missing = len(np.setdiff1d(merge_staged, in_lti))
    kept = int(np.isin(in_lti, setup_deleted).sum())
    rows = np.asarray(jnp.take(g.vectors, jnp.asarray(slots), axis=0))
    wrong = int(np.any(rows != plan.vectors[in_lti], axis=1).sum())

    usable = g.active & ~g.deleted
    adj = g.adjacency
    bad = (adj >= 0) & ~usable[jnp.maximum(adj, 0)] & usable[:, None]
    return {"state_errors": int(state_errors),
            "lti_errors": int(missing + kept + wrong),
            "dangling_edges": int(bad.sum())}
