"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

  configuration  the file ``configs[].file`` names (JSON);
  traffic        ``<bench>/traffic/<traffic>.json``;
  metric reader  ``<bench>/metrics/<metric name>.py``, a module with
                 ``read(ctx)`` returning a number, or None where it finds
                 nothing to read.

A later cell, configuration or metric is files and entries: nothing here
names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@dataclasses.dataclass
class CellSpec:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list          # metric entries this cell reports untraced
    per_layer: list           # metric entries this cell reports traced
    benchmark: dict


def load_benchmark(root: str = CHECKOUT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = CHECKOUT,
              bench_dir: str = BENCH) -> CellSpec:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return CellSpec(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        benchmark=bench)


def load_reader(metric: str, bench_dir: str = BENCH):
    """The ``read`` function of a per-layer metric's own module."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_names(bench: dict) -> list:
    """Names in BENCHMARK.json that break the naming rule."""
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    for c in bench["configs"]:
        names += c["reduced"]
    return [n for n in names if not NAME.match(n)]
