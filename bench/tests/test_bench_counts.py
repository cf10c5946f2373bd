"""Operation and byte counts of the pq_adc and robust_prune launches at
both configurations' shapes."""
import json
import os

import pytest

import _paths  # noqa: F401
from harness import counts
from harness.peaks import PEAKS, peaks_for

BENCH = _paths.BENCH


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["sift1b_shard", "msturing30m_shard"])
def test_pq_adc_counts(name):
    c = config(name)
    m, ksub, B = c["pq_m"], c["pq_ksub"], c["batch_queries"]
    n = c["beam_width"] * c["R"]          # candidates scored per hop
    shapes = [("f32", (B, 1, n)), ("u8", (B, n, m)),
              ("f32", (B, 1, m, ksub))]
    flops, nbytes = counts.pq_adc(shapes, m=m, ksub=ksub)
    assert flops == 2 * B * n * m
    assert nbytes == B * (n * m + m * ksub * 4 + n * 4)
    t, which = counts.roofline_seconds(flops, nbytes, peaks_for("TPU v5 lite"))
    assert which == "memory" and t == pytest.approx(nbytes / 819e9)


@pytest.mark.parametrize("name", ["sift1b_shard", "msturing30m_shard"])
@pytest.mark.parametrize("C", [200, 4224])
def test_robust_prune_counts(name, C):
    c = config(name)
    d, R, G, groups = c["dim"], c["R"], 8, 16
    shapes = [("s32", (groups, G, 128)), ("s32", (groups, G, 1)),
              ("f32", (groups, G, C)), ("f32", (groups, G, d, C)),
              ("s32", (groups, G, C))]
    flops, nbytes = counts.robust_prune(shapes, R=R)
    rows = groups * G
    assert flops == rows * R * C * (3 * d + 2)
    assert nbytes == rows * (8 * C + 4 * C * d + 4 * R)
    # ~R * 3/4 flops per byte: below the v5e's 240 flops/byte ridge.
    assert counts.roofline_seconds(flops, nbytes, PEAKS["TPU v5 lite"]
                                   )[1] == "memory"


def test_unknown_device_is_an_error():
    assert "TPU v5 lite" in PEAKS
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
