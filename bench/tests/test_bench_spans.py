"""The readers of the program's own spans, on a made-up trace whose numbers
are worked out by hand, and on a trace with no program spans."""
from types import SimpleNamespace

import pytest

import _paths  # noqa: F401
from harness import spans, spec as specs, trace as tr
from harness.trace import Event

HOST, DEV = "/host:CPU", "/device:TPU:0"
US = 1000                       # the made-up times are in microseconds
READERS = ("search_flush_ms", "search_device_wait_ms",
           "merge_program_max_ms", "merge_host_s", "batch_max_hops",
           "idle_unspanned_share")


def host(line, name, start, end, **stats):
    return Event(HOST, line, name, start * US, (end - start) * US, stats)


def program(name, start, end):
    """One execution of a program on the device: its module event and one
    operation over the same interval."""
    return [Event(DEV, tr.MODULES_LINE, f"jit_{name}(7)", start * US,
                  (end - start) * US, {}),
            Event(DEV, tr.OPS_LINE, "%fusion.1 = f32[8]{0} fusion()",
                  start * US, (end - start) * US, {})]


def search(batch, start, flush_end, device_start, end, hops):
    return [host("fdann-serve", "fdann.serve.batch", start, end, batch=batch),
            host("fdann-serve", "fdann.search.flush", start, flush_end,
                 batch=batch, points=0),
            host("fdann-serve", "fdann.search.prepare", flush_end,
                 device_start, batch=batch, lanes=3),
            host("fdann-serve", "fdann.search.device", device_start, end,
                 batch=batch, max_hops=hops, p50_hops=hops // 2),
            host("fdann-serve", "PjitFunction(unified_search)",
                 device_start + 5, device_start + 10)]


def made_up():
    m = "fdann-merge"
    return (
        search(0, 0, 100, 200, 1000, 12)           # flush 100, wait 100
        + search(1, 1990, 2290, 2400, 5100, 20)    # flush 300, wait 1000
        + search(2, 6100, 6300, 6300, 6900, 15)    # flush 200, wait 50
        + [host(m, "fdann.merge", 1000, 4000, merge=0, staged=2048),
           host(m, "fdann.merge.stage", 1000, 1200, merge=0),     # 200
           host(m, "fdann.merge.launch", 1200, 1300, merge=0),
           host(m, "PjitFunction(decode)", 1210, 1220),
           host(m, "PjitFunction(_repair)", 1230, 1240),
           host(m, "PjitFunction(shared)", 1250, 1260),
           host(m, "fdann.merge.wait", 1300, 3500, merge=0),
           host(m, "fdann.merge.publish", 3500, 3700, merge=0),   # 200
           host(m, "fdann.merge.retire", 3700, 3800, merge=0),    # 100
           host(m, "fdann.merge.probe", 3800, 3990, merge=0),
           host(m, "PjitFunction(_probe)", 3810, 3820),
           # The updater's flush, in the merge's time but on its own line.
           host("python", "fdann.flush", 1490, 1600, points=32, chunks=1),
           host("python", "PjitFunction(insert_edges_stage)", 1500, 1510),
           host("python", "PjitFunction(shared)", 5000, 5010)]
        + program("unified_search", 300, 800)
        + program("decode", 1220, 1800)
        + program("insert_edges_stage", 1800, 1850)
        + program("_repair", 1850, 2850)            # the merge's longest
        + program("unified_search", 3400, 4900)
        + program("_probe", 4900, 4950)
        + program("shared", 5200, 6300)             # two layers: not merge
        + program("unified_search", 6350, 6700))


# Idle on the device over [0, 6900): [0, 300) 300, [800, 1220) 420,
# [2850, 3400) 550, [4950, 5200) 250, [6300, 6350) 50, [6700, 6900) 200:
# 1,770 in all.  Spans cover [0, 5100) and [6100, 6900), so only
# [5100, 5200) of it, 100, is unspanned.
EXPECTED = {
    "search_flush_ms": 0.2,             # (100 + 300 + 200) / 3 us
    "search_device_wait_ms": 1.0,       # max(100, 1000, 50) us
    "merge_program_max_ms": 1.0,        # _repair; shared is excluded
    "merge_host_s": 500e-6,             # stage 200 + publish 200 + retire 100
    "batch_max_hops": 15,               # median(12, 20, 15)
    "idle_unspanned_share": 100 * 100 / 1770,
}


def read(name, events):
    return specs.load_reader(name)(SimpleNamespace(events=events))


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_hand_computed_value(name):
    assert read(name, made_up()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_program_spans(name):
    bare = [e for e in made_up() if not e.name.startswith(spans.PREFIX)]
    assert read(name, bare) is None
    assert read(name, None) is None


def test_a_name_launched_from_two_layers_is_logged(capsys):
    assert spans.merge_programs(made_up()) == {"decode", "_repair",
                                               "_probe"}
    assert "['shared']" in capsys.readouterr().err


def test_device_wait_refuses_a_join_of_unequal_counts(capsys):
    events = [e for e in made_up() if not (
        e.plane == DEV and e.start_ns == 6350 * US)]
    assert spans.device_waits_ns(events) is None
    assert "3 fdann.search.device spans but 2" in capsys.readouterr().err


def test_overlap_of_two_unions():
    a = spans.merged([(0, 10), (5, 20), (30, 40)])
    assert a == [[0, 20], [30, 40]]
    assert spans.overlap_ns(a, [[15, 35], [38, 50]]) == 5 + 5 + 2
    assert spans.overlap_ns(a, []) == 0
