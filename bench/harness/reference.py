"""The plain reference and the comparison that decides ``correct``.

The reference is exact k-nearest-neighbour search by brute force over the
set that was live when each search was submitted, written here in plain
``jax.numpy`` and NumPy: it imports nothing of the program and takes none of
its state.  Candidates come from a float32 scan at HIGHEST precision on the
device; the final order is decided in float64 on the host.

Per search, an id is *certainly live* when its insert was acknowledged
before the search was submitted and its delete (if any) had not started by
the time the search completed; *certainly dead* when it was not yet being
inserted by completion, or its delete was acknowledged before submission.
Ids whose state changed while the search was in flight are neither, and
are left out of both the truth and the checks.

Numbers compared, each against its limit (see ``LIMITS``):

  lost_requests    admitted searches never answered, or answered with an
                   error, within ``window.DRAIN_SECONDS`` of the close;
  stale_results    returned ids certainly dead at submission (a delete
                   acknowledged before it, or never inserted);
  short_rows       answers with fewer than k ids;
  dist_rel_err     largest relative gap between a reported distance and the
                   float64 squared distance of the id it reports;
  temp_miss_share  of the exact neighbours that sat only in a temp tier (RW
                   or RO) when their search was submitted, the share the
                   answer left out: the visibility guarantee for
                   acknowledged inserts that the LTI does not hold yet;
  compiles_in_window  programs compiled while the window was open;
  state_errors     acknowledged inserts missing from the index after the
                   window, plus acknowledged deletes still live in it;
  lti_errors       after the window's merge: staged inserts missing from the
                   LTI, set-up deletes still in it, and LTI rows whose vector
                   differs from the one inserted;
  dangling_edges   edges of live LTI rows to deleted or free slots.
"""
from __future__ import annotations

import numpy as np

# Limits, each set from readings (PERF.md, "How correct is decided").
# dist_rel_err: sound runs read at most 6.13e-5 over the seeds read; the
# control, the reference in the program's place in bfloat16, reads 1.0 with
# expanded-form distances and 1.08e-2 to 1.19e-2 with direct-form ones.
# temp_miss_share: sound runs (32-point flushes) read 0; the control reads
# 0.77 to 0.96 and the reference with its temp tiers left out 1.0.  With
# 256-point flushes the program's temp graphs lose points and runs read up
# to 0.536 (PERF.md, Open questions).
LIMITS = {
    "lost_requests": 0,
    "stale_results": 0,
    "short_rows": 0,
    "dist_rel_err": 2e-3,
    "temp_miss_share": 0.5,
    "state_errors": 0,
    "lti_errors": 0,
    "dangling_edges": 0,
    "compiles_in_window": 0,
}
MARGIN = 32         # extra candidates the float32 scan keeps for the f64 sort
CHUNK = 128         # queries per device scan


def id_intervals(plan, rec, n_points: int):
    """Per id: (insert start, insert ack, delete start, delete ack) on the
    window's clock; -inf for what happened in set-up, +inf for never."""
    from .traffic import INSERT
    ins_s = np.full(n_points, np.inf)
    ins_a = np.full(n_points, np.inf)
    del_s = np.full(n_points, np.inf)
    del_a = np.full(n_points, np.inf)
    ins_s[:plan.n_base] = ins_a[:plan.n_base] = -np.inf
    setup_ins = [plan.stage_inserts] + [np.concatenate([i, u])
                                        for i, _, u in plan.rounds]
    setup_del = [plan.stage_deletes] + [d for _, d, _ in plan.rounds]
    for ids in setup_ins:
        ins_s[ids] = ins_a[ids] = -np.inf
    for ids in setup_del:
        del_s[ids] = del_a[ids] = -np.inf
    ack = np.where(np.isnan(rec.update_ack), np.inf, rec.update_ack)
    start = np.where(np.isnan(rec.update_start), np.inf, rec.update_start)
    ins = plan.update_kinds == INSERT
    ins_s[plan.update_ids[ins]] = start[ins]
    ins_a[plan.update_ids[ins]] = ack[ins]
    del_s[plan.update_ids[~ins]] = start[~ins]
    del_a[plan.update_ids[~ins]] = ack[~ins]
    return ins_s, ins_a, del_s, del_a


def live_mask(intervals, submit: np.ndarray, done: np.ndarray,
              lti_time=None) -> np.ndarray:
    """[Q, N] certainly-live mask for searches submitted and completed at
    the given times; with ``lti_time``, less what sat only in a temp tier."""
    _, ins_a, del_s, _ = intervals
    live = (ins_a[None] < submit[:, None]) & (del_s[None] > done[:, None])
    if lti_time is not None:
        live &= lti_time[None] < done[:, None]
    return live


def dead_at(intervals, ids: np.ndarray, submit: np.ndarray,
            done: np.ndarray) -> np.ndarray:
    """[Q, k] whether each id is certainly dead for its search."""
    ins_s, _, _, del_a = intervals
    safe = np.maximum(ids, 0)
    return ((ins_s[safe] > done[:, None]) | (del_a[safe] < submit[:, None]))


def _scan(vectors, queries, live, k: int, bf16: bool):
    """Masked brute-force top-k on the device: (ids, distances)."""
    import jax
    import jax.numpy as jnp
    x = jnp.asarray(vectors)
    q = jnp.asarray(queries)
    if bf16:
        x, q = x.astype(jnp.bfloat16), q.astype(jnp.bfloat16)
        prec = jax.lax.Precision.DEFAULT
    else:
        prec = jax.lax.Precision.HIGHEST
    xx = jnp.sum(x * x, axis=1)
    qq = jnp.sum(q * q, axis=1)
    d = qq[:, None] + xx[None] - 2 * jnp.dot(q, x.T, precision=prec)
    d = jnp.where(jnp.asarray(live), d, jnp.inf)
    neg, ids = jax.lax.top_k(-d, k)
    return np.asarray(ids), -np.asarray(neg.astype(jnp.float32))


def exact_truth(vectors: np.ndarray, queries: np.ndarray, intervals,
                submit: np.ndarray, done: np.ndarray, k: int,
                lti_time=None) -> np.ndarray:
    """[Q, k] ids of the exact k nearest certainly-live points (float64);
    with ``lti_time``, of those the LTI held (see ``live_mask``)."""
    out = np.empty((len(queries), k), np.int64)
    x64 = vectors.astype(np.float64)
    for lo in range(0, len(queries), CHUNK):
        sl = slice(lo, lo + CHUNK)
        live = live_mask(intervals, submit[sl], done[sl],
                         lti_time)
        cand, _ = _scan(vectors, queries[sl], live, k + MARGIN, False)
        for j, (qv, row) in enumerate(zip(queries[sl].astype(np.float64),
                                          cand)):
            row = row[live[j, row]]
            d = ((x64[row] - qv) ** 2).sum(1)
            out[lo + j] = row[np.lexsort((row, d))[:k]]
    return out


def control_answers(vectors: np.ndarray, queries: np.ndarray, intervals,
                    submit: np.ndarray, done: np.ndarray, k: int,
                    direct: bool = False):
    """The reference in the program's place, in bfloat16: (ids, dists).
    Distances come from the expanded form of the scan, or with ``direct``
    from a rerank of the chosen ids that squares bfloat16 differences."""
    ids, ds = [], []
    for lo in range(0, len(queries), CHUNK):
        sl = slice(lo, lo + CHUNK)
        i, d = _scan(vectors, queries[sl],
                     live_mask(intervals, submit[sl], done[sl]), k, True)
        ids.append(i)
        ds.append(_rerank_bf16(vectors, queries[sl], i) if direct else d)
    return np.concatenate(ids), np.concatenate(ds)


def _rerank_bf16(vectors, queries, ids) -> np.ndarray:
    """sum((x - q)^2) over bfloat16 operands, accumulated in float32."""
    import jax.numpy as jnp
    x = jnp.asarray(vectors[ids], jnp.bfloat16)               # [Q, k, d]
    q = jnp.asarray(queries, jnp.bfloat16)[:, None]
    return np.asarray(jnp.sum(jnp.square(x - q), axis=-1,
                              dtype=jnp.float32))


def lti_from(plan, merge_staged: np.ndarray, lti_old_until: float,
             n_points: int) -> np.ndarray:
    """Per id, a time up to which the LTI certainly did not hold it: -inf
    for the bootstrap and what set-up merged; for the inserts the window's
    merge takes in, the last time the harness saw the LTI without them;
    +inf for the window's own inserts, which no merge takes in (a window
    holds one merge).  A search that completed by then found such an id in
    a temp tier or not at all."""
    out = np.full(n_points, np.inf)
    out[:plan.n_base] = -np.inf
    for ins, _, _ in plan.rounds:
        out[ins] = -np.inf
    out[merge_staged] = (lti_old_until if np.isfinite(lti_old_until)
                         else -np.inf)
    return out


def temp_only(lti_time: np.ndarray, ids: np.ndarray,
              done: np.ndarray) -> np.ndarray:
    """[Q, k] whether each id sat only in a temp tier for the whole of its
    search (completed at ``done``)."""
    return lti_time[np.maximum(ids, 0)] >= done[:, None]


def temp_misses(ids: np.ndarray, truth: np.ndarray,
                only_temp: np.ndarray) -> tuple:
    """(share, pairs): of the exact neighbours marked ``only_temp`` [Q, k],
    the share the answers left out, and how many there were (share 0 when
    there were none)."""
    got = np.array([np.isin(t, a[a >= 0]) for a, t in zip(ids, truth)],
                   bool).reshape(truth.shape)
    pairs = int(only_temp.sum())
    missed = int((only_temp & ~got).sum())
    return (missed / pairs if pairs else 0.0), pairs


def answer_checks(vectors, queries, ids, dists, dead, k: int) -> dict:
    """stale_results, short_rows and dist_rel_err over served answers;
    ``dead`` [Q, k] marks the ids certainly dead for their search."""
    valid = ids >= 0
    safe = np.where(valid, ids, 0)
    stale = int(np.sum(valid & dead))
    short = int(np.sum(valid.sum(1) < k))
    x = vectors.astype(np.float64)[safe]                     # [Q, k, d]
    true = ((x - queries.astype(np.float64)[:, None]) ** 2).sum(-1)
    err = np.abs(dists.astype(np.float64) - true) / np.maximum(true, 1e-12)
    worst = float(np.max(np.where(valid, err, 0.0), initial=0.0))
    return {"stale_results": stale, "short_rows": short,
            "dist_rel_err": worst}


def recall(ids: np.ndarray, truth: np.ndarray) -> float:
    """Mean k-recall@k of answers against the exact truth."""
    k = truth.shape[1]
    hits = [len(set(a[a >= 0].tolist()) & set(t.tolist()))
            for a, t in zip(ids, truth)]
    return float(np.mean(hits)) / k if hits else float("nan")


def served_answers(rec, k: int):
    """Answered searches: (indices, ids [Q, k], dists [Q, k])."""
    idx = np.array([j for j, r in enumerate(rec.results) if r is not None],
                   np.int64)
    if len(idx) == 0:
        return idx, np.zeros((0, k), np.int64), np.zeros((0, k), np.float32)
    ids = np.stack([rec.results[j][0] for j in idx]).astype(np.int64)
    ds = np.stack([rec.results[j][1] for j in idx]).astype(np.float32)
    return idx, ids, ds


def verdict(numbers: dict) -> bool:
    """Every number given within its limit."""
    return all(numbers[name] <= LIMITS[name] for name in numbers)


def control_numbers(plan, rec, merge_staged: np.ndarray, k: int) -> dict:
    """The answer checks with a stand-in in the program's place, for every
    answered search, by stand-in: ``bf16`` and ``bf16_direct`` (the
    bfloat16 reference, expanded-form or direct-form distances) and
    ``no_temp_tiers`` (the float32 reference with every point that sat only
    in a temp tier left out)."""
    idx, _, _ = served_answers(rec, k)
    intervals = id_intervals(plan, rec, len(plan.vectors))
    sub, done = rec.search_submit[idx], rec.search_done[idx]
    q = plan.queries[idx]
    truth = exact_truth(plan.vectors, q, intervals, sub, done, k)
    lti_time = lti_from(plan, merge_staged, rec.lti_old_until,
                        len(plan.vectors))
    only_temp = temp_only(lti_time, truth, done)

    def numbers(ids, dists):
        out = answer_checks(plan.vectors, q, ids, dists,
                            dead_at(intervals, ids, sub, done), k)
        out["temp_miss_share"] = temp_misses(ids, truth, only_temp)[0]
        return out

    no_temp = exact_truth(plan.vectors, q, intervals, sub, done, k, lti_time)
    x = plan.vectors.astype(np.float64)[no_temp]
    no_temp_d = ((x - q.astype(np.float64)[:, None]) ** 2).sum(-1)
    return {
        "bf16": numbers(*control_answers(plan.vectors, q, intervals, sub,
                                         done, k)),
        "bf16_direct": numbers(*control_answers(plan.vectors, q, intervals,
                                                sub, done, k, direct=True)),
        "no_temp_tiers": numbers(no_temp, no_temp_d.astype(np.float32)),
    }
