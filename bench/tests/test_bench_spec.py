"""Every configuration, cell and metric of BENCHMARK.json loads by name,
and a new one needs files and entries only."""
import json
import os
import shutil

import numpy as np
import pytest

import _paths  # noqa: F401
from harness import spec as specs
from harness.traffic import make_plan

BENCH = specs.BENCH
CHECKOUT = specs.CHECKOUT
BENCHMARK = specs.load_benchmark()
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
METRICS = [m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]]
CONFIG_KEYS = ("dim", "dtype", "capacity", "R", "L_build", "L_search",
               "alpha", "beam_width", "pq_m", "pq_ksub", "k",
               "bootstrap_points", "build_batch", "ro_snapshot_points",
               "merge_threshold", "temp_capacity", "insert_batch",
               "merge_block", "batch_queries", "serve_queue_capacity",
               "slo_ms", "local_repair_threshold", "data", "guarantees")
CONFIG_FILES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "configs"))
                      if f.endswith(".json"))
TRAFFIC_FILES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
                       if f.endswith(".json"))


def test_names_follow_the_rule():
    assert specs.check_names(BENCHMARK) == []


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads(cell):
    c = specs.load_cell(cell)
    assert c.chips in (1, 4)
    for key in CONFIG_KEYS:
        assert key in c.config, key
    assert c.config["dim"] % c.config["pq_m"] == 0
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


@pytest.mark.parametrize("metric", METRICS)
def test_metric_reader_loads(metric):
    assert callable(specs.load_reader(metric))


@pytest.mark.parametrize("config", BENCHMARK["configs"],
                         ids=lambda c: c["name"])
def test_config_file_is_under_paths_and_lists_its_cuts(config):
    assert config["file"].startswith(tuple(p + "/" for p in
                                           BENCHMARK["paths"]))
    with open(os.path.join(CHECKOUT, config["file"])) as f:
        body = json.load(f)
    assert body["name"] == config["name"]
    assert sorted(body["reduced"]) == sorted(config["reduced"])


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_every_config_file_holds_the_keys(name):
    """Also a configuration that no cell uses yet, kept for a later cell."""
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        body = json.load(f)
    assert body["name"] == name
    for key in CONFIG_KEYS:
        assert key in body, key
    assert body["dim"] % body["pq_m"] == 0


@pytest.mark.parametrize("name", TRAFFIC_FILES)
def test_every_traffic_file_plans_the_same_work_for_every_seed(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        traffic = json.load(f)
    config = dict(dim=4, bootstrap_points=8192, merge_threshold=2048,
                  ro_snapshot_points=512, insert_batch=256,
                  batch_queries=64, k=5,
                  data=dict(components=8, center_scale=3.0, noise_scale=1.0,
                            spectrum_decay=2.0, cluster_points=512))
    a = make_plan(config, traffic, seed=2**31 + 12345, seconds=4.0)
    b = make_plan(config, traffic, seed=7, seconds=4.0)
    assert a.vectors.shape == b.vectors.shape
    assert a.n_searches == b.n_searches == traffic["searches_per_s"] * 4
    assert np.array_equal(np.sort(a.update_kinds), np.sort(b.update_kinds))
    assert len(a.stage_inserts) == traffic["stage_inserts"]
    assert len(a.rounds) == traffic["warmup_rounds"]


def test_a_new_cell_config_and_metric_need_no_edit(tmp_path):
    """Files and entries in a copy of the benchmark are found by name."""
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    b = json.loads(json.dumps(BENCHMARK))
    with open(os.path.join(CHECKOUT, b["configs"][0]["file"])) as f:
        cfg = json.load(f)
    cfg["name"] = "new_config"
    (bench / "configs" / "new_config.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "new_mix.json").write_text(json.dumps(
        {"insert_order": "shuffled", "delete_order": "random",
         "query_order": "shuffled", "warmup_rounds": 0, "stage_inserts": 0,
         "stage_deletes": 0, "searches_per_s": 10, "inserts_per_s": 0,
         "deletes_per_s": 0}))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    b["configs"].append(dict(b["configs"][0], name="new_config",
                             file="bench/configs/new_config.json"))
    b["workloads"].append({"name": "new.cell", "config": "new_config",
                           "traffic": "new_mix", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "new_metric", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "device", "moves": "setup_s",
                           "workloads": ["new.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = specs.load_cell("new.cell", root=str(root), bench_dir=str(bench))
    assert cell.config["name"] == "new_config"
    assert cell.traffic["searches_per_s"] == 10
    assert "new_metric" in [m["name"] for m in cell.per_layer]
    read = specs.load_reader("new_metric", bench_dir=str(bench))
    assert read(None) == 42.0
    assert specs.check_names(b) == []
