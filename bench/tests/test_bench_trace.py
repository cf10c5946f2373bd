"""The trace reduction on made-up events and on a trace recorded here."""
import json
import os

import pytest

import _paths
from harness import counts, trace as tr
from harness.trace import Event

DEV = "/device:TPU:0"


def ev(start, dur, name="fusion.1", line=tr.OPS_LINE, plane=DEV, **stats):
    return Event(plane, line, name, start, dur, stats)


def test_busy_intervals_merge_overlaps_and_keep_gaps():
    events = [ev(0, 10), ev(5, 10), ev(20, 10), ev(25, 1)]
    assert tr.busy_intervals(events, DEV) == [[0, 15], [20, 30]]
    assert tr.busy_intervals([], DEV) == []


def test_busy_and_idle_gaps():
    events = [ev(0, 100), ev(50, 100), ev(400, 100), ev(1000, 50),
              Event("/host:CPU", "python", "bench.submit", 160, 200, {}),
              Event("/host:CPU", "python", "wait", 500, 500, {})]
    assert tr.busy_seconds(events) == pytest.approx(300e-9)
    gaps = tr.idle_gaps(events)
    assert [g[1] for g in gaps] == pytest.approx([500e-9, 250e-9])
    assert gaps[0][0] == "wait"          # covers [500, 1000)
    assert gaps[1][0] == "bench.submit"  # 200 of [150, 400)


def test_busy_averages_over_chips():
    events = [ev(0, 100), ev(0, 300, plane="/device:TPU:1")]
    assert tr.busy_seconds(events) == pytest.approx(200e-9)


def test_top_ops_groups_numbered_names():
    events = [ev(0, 10, "fusion.1"), ev(10, 30, "fusion.2"),
              ev(40, 25, "custom-call.7")]
    assert tr.top_ops(events) == [["/fusion", 40e-9],
                                  ["/custom-call", 25e-9]]


def test_matching_reads_the_instruction_name():
    adc = ev(0, 5, "%adc_distances_kernel.6 = f32[64,8,256]{2,1,0} "
                   "custom-call(u8[64,256,32]{2,1,0} %a, "
                   "f32[64,8,32,256]{3,2,1,0} %b)")
    user = ev(5, 5, "%fusion.3 = f32[64,256]{1,0} fusion("
                    "f32[64,8,256]{2,1,0} %adc_distances_kernel.6)")
    assert tr.op_name(adc) == "adc_distances_kernel"
    assert tr.op_name(user) == "fusion"
    assert tr.matching([adc, user], ["adc_distances_kernel"]) == [adc]
    assert tr.operand_shapes(tr.long_name(adc))[:3] == [
        ("f32", (64, 8, 256)), ("u8", (64, 256, 32)),
        ("f32", (64, 8, 32, 256))]


def test_ops_are_labelled_by_their_program():
    m = ev(0, 100, "jit_unified_search(3)", line=tr.MODULES_LINE)
    ops = [ev(10, 5, "%fusion.1 = f32[2]{0} fusion()"),
           ev(20, 50, "%while.2 = (s32[]) while()"), ev(200, 7)]
    assert tr.module_events([m] + ops) == [m]
    assert tr.programs_of(ops, [m]) == ["unified_search", "unified_search",
                                       ""]
    assert tr.top_ops([m] + ops) == [["/fusion", 7e-9],
                                     ["unified_search/fusion", 5e-9]]


def recorded():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "v5e_trace_excerpt.json")) as f:
        d = json.load(f)
    return ([Event(*e, {}) for e in d["events"]],
            [Event(*e, {}) for e in d["kernels"]])


def test_a_recorded_v5e_trace_reduces():
    events, _ = recorded()
    lo = min(e.start_ns for e in events)
    hi = max(e.start_ns + e.dur_ns for e in events)
    busy = tr.busy_seconds(events)
    assert 0 < busy <= (hi - lo) / 1e9
    assert busy <= sum(e.dur_ns for e in events) / 1e9
    top = tr.top_ops(events)
    assert 0 < len(top) <= 10
    assert all(not name.split("/")[-1].startswith("while")
               for name, _ in top)


@pytest.mark.parametrize("config,kernel", [
    ("sift1b_shard", "adc_distances_kernel"),
    ("sift1b_shard", "robust_prune_fp_kernel"),
    ("sift1b_shard", "delete_repair_fp_kernel")])
def test_recorded_kernel_launches_are_counted(config, kernel):
    _, kernels = recorded()
    launches = tr.matching(kernels, [kernel])
    assert launches
    with open(os.path.join(_paths.BENCH, "configs", config + ".json")) as f:
        c = json.load(f)
    for e in launches:
        shapes = tr.operand_shapes(tr.long_name(e))
        if kernel == "adc_distances_kernel":
            flops, nbytes = counts.pq_adc(shapes, m=c["pq_m"],
                                             ksub=c["pq_ksub"])
            rows, n = shapes[0][1][0], shapes[0][1][-1]
            assert flops == 2 * rows * n * c["pq_m"]
        else:
            flops, nbytes = counts.robust_prune(shapes, R=c["R"])
            assert shapes[3][1][-2] == c["dim"]
        assert flops > 0 and nbytes > 0


def test_a_recorded_trace_loads(tmp_path):
    import jax
    import jax.numpy as jnp
    x = jnp.ones((256, 256))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.recorded"):
        (x @ x).block_until_ready()
    jax.profiler.stop_trace()
    events = tr.load_events(str(tmp_path))
    assert any(e.name == "bench.recorded" for e in events)
    assert all(e.dur_ns >= 0 for e in events)
    # The CPU backend has no device plane: nothing reads as device time.
    assert tr.busy_seconds(events) == 0.0
