"""The one general traffic generator: a traffic file's parameters and a seed
give the whole plan of a run.

Every seed gets the same sizes and the same counts of operations; the seed
changes only which points, which ids and which arrival times.  Arrivals in
the window are a Poisson process conditioned on its count: ``n`` sorted
uniform times over the window, so a run's work does not swing with the seed.

Plan of a run, in order:

  bootstrap   ``bootstrap_points`` points, external ids 0..n-1;
  rounds      ``warmup_rounds`` whole merge rounds in set-up: one merge
              threshold of inserts and as many deletes (the last insert
              starts the merge), then ``1.5 * snapshot`` inserts while the
              merge runs (a flush and a snapshot ride under it), then a wait
              for the merge;
  stage       inserts and deletes up to ``stage_inserts`` / ``stage_deletes``,
              counting the inserts made while the last round's merge ran;
  window      the insert that completes the staged set at t = 0 (when any
              is staged), then Poisson searches, inserts and deletes at the
              file's rates.

Inserts take fresh points in arrival order (``insert_order``: i.i.d. over
the components, or one component after another); deletes take bootstrap
ids (``delete_order``: a random permutation, or oldest first).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .data import Mixture, components_for, rng_for

SEARCH, INSERT, DELETE = 0, 1, 2


@dataclasses.dataclass
class Plan:
    vectors: np.ndarray          # [n_points, dim] f32; row i is external id i
    n_base: int                  # ids below this are the bootstrap
    rounds: list                 # [(insert ids, delete ids, inserts under merge)]
    stage_inserts: np.ndarray    # ids inserted in the final staging
    stage_deletes: np.ndarray    # ids deleted in the final staging
    warm_queries: np.ndarray     # [batch, dim] queries for set-up warm-ups
    search_times: np.ndarray     # [n_s] seconds after the window opens
    queries: np.ndarray          # [n_s, dim]
    update_times: np.ndarray     # [n_u] sorted seconds
    update_kinds: np.ndarray     # [n_u] INSERT or DELETE
    update_ids: np.ndarray       # [n_u] external ids
    seconds: float

    @property
    def n_searches(self) -> int:
        return len(self.search_times)


def _uniform_times(n: int, seconds: float, r: np.random.Generator):
    return np.sort(r.uniform(0.0, seconds, n))


def make_plan(config: dict, traffic: dict, seed: int,
              seconds: float) -> Plan:
    data = config["data"]
    n_base = config["bootstrap_points"]
    threshold = config["merge_threshold"]
    under_merge = config["ro_snapshot_points"] + config["insert_batch"]
    rounds_n = traffic["warmup_rounds"]
    n_stage_i = traffic["stage_inserts"]
    n_stage_d = traffic["stage_deletes"]
    trigger = 1 if n_stage_i else 0
    n_win_i = int(round(traffic["inserts_per_s"] * seconds))
    n_win_d = int(round(traffic["deletes_per_s"] * seconds))
    n_s = int(round(traffic["searches_per_s"] * seconds))
    if rounds_n and n_stage_i < under_merge:
        raise ValueError("stage_inserts must cover the inserts made while a "
                         "set-up merge runs")
    n_ins = rounds_n * threshold + n_stage_i + trigger + n_win_i
    n_del = rounds_n * threshold + n_stage_d + n_win_d
    if n_del > n_base:
        raise ValueError(f"{n_del} deletes exceed the {n_base} bootstrap ids")

    mix = Mixture.from_config(config, seed)
    r = rng_for(seed, 1)
    order = traffic["insert_order"]
    cp = data["cluster_points"]
    comps = components_for(n_base + n_ins, data["components"], order, cp, r)
    vectors = mix.sample(comps, r)

    if traffic["delete_order"] == "random":
        victims = rng_for(seed, 2).permutation(n_base)[:n_del]
    elif traffic["delete_order"] == "oldest_first":
        victims = np.arange(n_del)
    else:
        raise ValueError(f"unknown delete order {traffic['delete_order']!r}")

    ins = np.arange(n_base, n_base + n_ins)
    rounds, i, d = [], 0, 0
    for _ in range(rounds_n):
        rounds.append((ins[i:i + threshold], victims[d:d + threshold],
                       ins[i + threshold:i + threshold + under_merge]))
        i += threshold
        d += threshold
    stage_i = ins[i:i + n_stage_i]
    stage_d = victims[d:d + n_stage_d]
    i += n_stage_i
    d += n_stage_d

    rq = rng_for(seed, 3)
    batch = config["batch_queries"]
    q_comps = components_for(n_s + batch, data["components"],
                             traffic["query_order"], cp, rq)
    qs = mix.sample(q_comps, rq)

    rt = rng_for(seed, 4)
    t_i = np.concatenate([np.zeros(trigger), _uniform_times(n_win_i, seconds, rt)])
    t_d = _uniform_times(n_win_d, seconds, rt)
    times = np.concatenate([t_i, t_d])
    kinds = np.concatenate([np.full(len(t_i), INSERT), np.full(n_win_d, DELETE)])
    ids = np.concatenate([ins[i:i + trigger + n_win_i], victims[d:d + n_win_d]])
    order_u = np.argsort(times, kind="stable")
    return Plan(
        vectors=vectors, n_base=n_base, rounds=rounds,
        stage_inserts=stage_i, stage_deletes=stage_d,
        warm_queries=qs[n_s:], search_times=_uniform_times(n_s, seconds, rt),
        queries=qs[:n_s], update_times=times[order_u],
        update_kinds=kinds[order_u], update_ids=ids[order_u],
        seconds=float(seconds))
