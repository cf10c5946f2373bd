#!/usr/bin/env python3
"""Run a cell as the benchmark does, then read the stand-ins on the same
searches: the reference in the program's place in bfloat16 (the control,
with expanded-form and with direct-form distances), and the float32
reference with the temp tiers left out (a fault).

    python3 bench/tools/control.py --workload <cell> --seeds 1,2,3 --seconds 30 \
        [--config <configuration file name>] [--traffic <traffic file name>]

For each seed prints the run's result line, then one line with each
stand-in's numbers and verdict beside the program's (``correct`` must come
out false for each stand-in).  These readings set the limits in
reference.LIMITS.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

from harness import cli, reference as ref, spec as specs  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--config", default="",
                    help="another configuration file of bench/configs/ in "
                         "place of the cell's own")
    ap.add_argument("--traffic", default="",
                    help="another traffic file of bench/traffic/ in place "
                         "of the cell's own")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        return 3
    cli.enable_cache()
    cell = specs.load_cell(args.workload)
    for kind, name in (("configs", args.config), ("traffic", args.traffic)):
        if name:
            with open(os.path.join(specs.BENCH, kind, name + ".json")) as f:
                setattr(cell, "config" if kind == "configs" else kind,
                        json.load(f))
    for seed in [int(s) for s in args.seeds.split(",")]:
        out, ctx = cli.run_cell_ctx(cell, seed, args.seconds,
                                    bool(args.trace))
        print(json.dumps(out), flush=True)
        ctl = ref.control_numbers(ctx.plan, ctx.rec, ctx.merge_staged,
                                  ctx.config["k"])
        print(json.dumps({
            "seed": seed, "program_correct": out["correct"],
            "program": {n: c["value"] for n, c in out["checks"].items()},
            "stand_ins": ctl,
            "stand_ins_correct": {n: ref.verdict(v) for n, v in ctl.items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
