"""The whole run at a tiny size on the CPU (the harness's look for a chip
skipped): a sound run is correct, and the control and each fault the cells
can have make ``correct`` come out false."""
import json
import os

import numpy as np
import pytest

import _paths  # noqa: F401
from harness import cli, reference as ref, spec as specs

TINY = dict(
    name="tiny", dim=16, dtype="float32", capacity=4096, R=16, L_build=24,
    L_search=32, alpha=1.2, beam_width=2, pq_m=4, pq_ksub=16, k=5,
    bootstrap_points=1024, build_batch=64, ro_snapshot_points=64,
    merge_threshold=256, temp_capacity=128, insert_batch=8, merge_block=128,
    batch_queries=8, serve_queue_capacity=1024, slo_ms=100,
    local_repair_threshold=0.05,
    data=dict(components=8, center_scale=3.0, noise_scale=2.0,
              spectrum_decay=4.0, cluster_points=128))
SECONDS = 2.0


def tiny_cell():
    with open(os.path.join(specs.BENCH, "traffic", "merge_window.json")) as f:
        traffic = json.load(f)
    traffic.update(stage_inserts=255, stage_deletes=256, searches_per_s=40,
                   inserts_per_s=40, deletes_per_s=10)
    b = specs.load_benchmark()
    return specs.CellSpec("tiny", 1, TINY, traffic, b["end_to_end"],
                          b["per_layer"], b)


@pytest.fixture(scope="module")
def sound():
    return cli.run_cell_ctx(tiny_cell(), 20261018, SECONDS, False)


def test_a_sound_run_is_correct(sound):
    out, ctx = sound
    assert out["correct"], {n: c for n, c in out["checks"].items()
                            if c["value"] > c["limit"]}
    assert out["failed"] == 0
    assert set(out["metrics"]) >= {"search_p99_ms", "setup_s", "recall_at_k"}
    assert list(out)[-1] == "checks"
    assert ctx.rec.compiles_in_window == 0
    assert ctx.rec.after.merges == ctx.rec.before.merges + 1


@pytest.mark.parametrize("stand_in, number", [
    ("bf16", "dist_rel_err"), ("bf16_direct", "dist_rel_err"),
    ("no_temp_tiers", "temp_miss_share")])
def test_the_control_is_not_correct(sound, stand_in, number):
    _, ctx = sound
    numbers = ref.control_numbers(ctx.plan, ctx.rec, ctx.merge_staged,
                                  ctx.config["k"])[stand_in]
    assert numbers[number] > ref.LIMITS[number]
    assert not ref.verdict(numbers)


def test_a_sound_run_checks_points_in_the_temp_tiers(sound):
    _, ctx = sound
    idx, ids, _ = ref.served_answers(ctx.rec, ctx.config["k"])
    done = ctx.rec.search_done[idx]
    truth = ref.exact_truth(
        ctx.plan.vectors, ctx.plan.queries[idx],
        ref.id_intervals(ctx.plan, ctx.rec, len(ctx.plan.vectors)),
        ctx.rec.search_submit[idx], done, ctx.config["k"])
    lti_time = ref.lti_from(ctx.plan, ctx.merge_staged,
                            ctx.rec.lti_old_until, len(ctx.plan.vectors))
    share, pairs = ref.temp_misses(ids, truth,
                                   ref.temp_only(lti_time, truth, done))
    assert pairs >= 10
    assert share == ctx.numbers["temp_miss_share"]
    assert share <= ref.LIMITS["temp_miss_share"]


def state_unchanged(run):
    """A flush that returns the RW tier as it was."""
    run.sys._flush_compute = lambda *a, **kw: None


def half_batch(run):
    """Half of every micro-batch served, the rest left out."""
    serve = run.sched._serve

    def half(qs, k, **kw):
        n = max(len(qs) // 2, 1)
        ids, d = serve(qs[:n], k, **kw)
        pad = len(qs) - n
        return (np.concatenate([ids, np.full((pad, k), -1, ids.dtype)]),
                np.concatenate([d, np.full((pad, k), np.inf, d.dtype)]))
    run.sched._serve = half


def temp_tiers_skipped(run):
    """Searches that read the LTI alone, leaving out the RW and RO tiers."""
    capture = run.sys._capture_lanes

    def lti_only():
        _, _, lti = capture()
        return None, [], lti
    run.sys._capture_lanes = lti_only


def answer_altered(run):
    """Every answer's first id replaced where it is produced."""
    serve = run.sched._serve

    def altered(qs, k, **kw):
        ids, d = serve(qs, k, **kw)
        ids = ids.copy()
        ids[:, 0] = (ids[:, 0] + 1) % 1024
        return ids, d
    run.sched._serve = altered


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   temp_tiers_skipped, answer_altered],
                         ids=lambda f: f.__name__)
def test_a_fault_is_not_correct(fault, sound):
    out, _ = cli.run_cell_ctx(tiny_cell(), 7, SECONDS, False, patch=fault)
    assert not out["correct"], out["checks"]
