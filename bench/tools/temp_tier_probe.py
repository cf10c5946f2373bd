#!/usr/bin/env python3
"""Self-probes of the temp tiers: how many of the points held in the RW and
RO tiers a search for their own vector returns, per flush size.

    JAX_PLATFORMS=cpu python3 bench/tools/temp_tier_probe.py \
        --config sift1b_shard --seeds 5,6,7 --insert-batches 256,32

Bootstraps a small LTI (capacity 4,096, 1,024 points) at the configuration's
widths, inserts 1,124 points through ``FreshDiskANN.insert`` (two RO
snapshots of 512 and 100 points in the RW tier, no merge), then searches for
every eighth inserted point's own vector through ``search_batch``.  Prints
one JSON line per (seed, flush size): the share found, and the temp graphs'
mean out-degree.  Runs on the CPU or the chip.
"""
import argparse
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import numpy as np  # noqa: E402

from harness import spec as specs  # noqa: E402
from harness.data import Mixture, rng_for  # noqa: E402
from harness.window import system_config  # noqa: E402

N_BASE, N_NEW = 1024, 1124


def probe(config: dict, seed: int, insert_batch: int) -> dict:
    from repro.core.system import bootstrap_system
    mix = Mixture.from_config(config, seed)
    r = rng_for(seed, 1)
    x = mix.sample(r.integers(0, mix.components, N_BASE + N_NEW), r)
    cfg = dataclasses.replace(
        system_config(dict(config, capacity=4096)), background_merge=False,
        merge_threshold=10 ** 6, insert_batch=insert_batch)
    s = bootstrap_system(x[:N_BASE], np.arange(N_BASE), cfg, batch=256)
    new = np.arange(N_BASE, N_BASE + N_NEW)
    for e in new:
        s.insert(int(e), x[e])
    s.search_batch(x[:config["batch_queries"]], config["k"])  # lands the rest
    ids, _ = s.search_batch(x[new[::8]], config["k"])
    found = [e in row for e, row in zip(new[::8], np.asarray(ids))]
    degree = [float((np.asarray(t.state.adjacency)[:t.n] >= 0).sum(1).mean())
              for t in s.ro]
    return {"seed": seed, "insert_batch": insert_batch,
            "found": float(np.mean(found)), "probes": len(found),
            "ro_sizes": [t.n for t in s.ro], "rw_size": s.rw.n,
            "ro_mean_degree": degree}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--insert-batches", default="256,32")
    args = ap.parse_args()
    with open(os.path.join(specs.BENCH, "configs", args.config + ".json")) as f:
        config = json.load(f)
    for seed in [int(v) for v in args.seeds.split(",")]:
        for ib in [int(v) for v in args.insert_batches.split(",")]:
            print(json.dumps(probe(config, seed, ib)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
