#!/usr/bin/env python3
"""Run one benchmark cell once on the chip(s) of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See ``bench/harness/cli.py`` for what it prints, and ``BENCHMARK.json`` at
the root of the checkout for the cells and metrics.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

if __name__ == "__main__":
    from harness.cli import main
    sys.exit(main())
