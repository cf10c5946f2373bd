#!/usr/bin/env python3
"""Find the highest search rate a configuration sustains, once, on the chip.

    python3 bench/tools/sweep.py --config sift1b_shard \
        --seed 1 --rates 150,200,250,300,350 --seconds 15 \
        [--build-batches 256,1024]

Bootstraps the configuration's index once, then offers open-loop Poisson
searches alone (no updates, no merge) at each rate for ``--seconds`` on the same index and prints one JSON line
per rate: p50/p99 latency from the due time, shed searches, batch
occupancy, and how far the last answer trailed the last due time (a queue
that grows through the window shows as a lag that grows with the rate).
``--build-batches`` first times the bootstrap at each build batch and
reports the graph's own recall on exact distances, so a larger build batch
can be judged on graph quality and set-up time.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import numpy as np  # noqa: E402

from harness import cli, reference as ref, spec as specs  # noqa: E402
from harness.traffic import make_plan  # noqa: E402
from harness.window import Cell, system_config  # noqa: E402


def graph_recall(sys_, plan, queries, k):
    """k-recall@k of an exact-distance beam search of the LTI graph alone."""
    import jax.numpy as jnp
    from repro.core import index as mem
    g = sys_.lti.graph
    cfg = sys_.cfg.index
    slots = np.asarray(mem.search(g, jnp.asarray(queries), cfg, k=k,
                                  L=cfg.L_search)[0])
    ext = np.asarray(sys_.lti_ext_ids)
    found = np.where(slots >= 0, ext[np.maximum(slots, 0)], -1)
    n = plan.n_base
    inf = np.full(len(plan.vectors), np.inf)
    inf[:n] = -np.inf
    intervals = (inf, inf, np.full(len(plan.vectors), np.inf), None)
    t = np.zeros(len(queries))
    truth = ref.exact_truth(plan.vectors, queries, intervals, t, t, k)
    return ref.recall(found, truth)


SEARCH_ONLY = {"insert_order": "shuffled", "delete_order": "random",
               "query_order": "shuffled", "warmup_rounds": 0,
               "stage_inserts": 0, "stage_deletes": 0, "searches_per_s": 0,
               "inserts_per_s": 0, "deletes_per_s": 0}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15)
    ap.add_argument("--build-batches", default="")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    cli.enable_cache()
    with open(os.path.join(specs.BENCH, "configs", args.config + ".json")) as f:
        config = json.load(f)
    traffic = SEARCH_ONLY
    k = config["k"]
    plan = make_plan(config, traffic, args.seed, args.seconds)
    for bb in [int(b) for b in args.build_batches.split(",") if b]:
        from repro.core.system import bootstrap_system
        t = time.perf_counter()
        s = bootstrap_system(plan.vectors[:plan.n_base],
                             np.arange(plan.n_base), system_config(config),
                             batch=bb)
        jax.block_until_ready(s.lti.graph.adjacency)
        dt = time.perf_counter() - t
        rec = graph_recall(s, plan, plan.queries[:256], k)
        print(json.dumps({"build_batch": bb, "bootstrap_s": dt,
                          "graph_recall": rec}), flush=True)
        del s
    run = Cell(config, plan, cli.log)
    run.setup()
    meter = cli.CompileMeter()
    for rate in [float(r) for r in args.rates.split(",")]:
        run.plan = make_plan(config, dict(traffic, searches_per_s=rate),
                             args.seed, args.seconds)
        c0 = meter.compiles
        rec = run.window()
        lat = np.where(np.isnan(rec.search_done), np.inf,
                       rec.search_done - rec.search_due)
        b, a = rec.before, rec.after
        print(json.dumps({
            "rate": rate, "n": len(lat),
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3,
            "shed": int(rec.shed.sum()), "lost": int(rec.lost.sum()),
            "occupancy": (a.searches - b.searches)
            / max(a.batches - b.batches, 1) / config["batch_queries"],
            "search_batch_ms": float(np.median(rec.search_samples)) * 1e3
            if rec.search_samples else None,
            "tail_lag_ms": float(np.nanmax(rec.search_done)
                                 - rec.search_due[-1]) * 1e3,
            "compiles": meter.compiles - c0}), flush=True)
    run.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
