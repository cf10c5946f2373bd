"""The reference and the answer checks against a toy brute force."""
import numpy as np
import pytest

import _paths  # noqa: F401
from harness import reference as ref


def toy(n=300, d=8, nq=20, seed=0):
    r = np.random.default_rng(seed)
    return (r.standard_normal((n, d)).astype(np.float32),
            r.standard_normal((nq, d)).astype(np.float32))


def all_live(n):
    inf = np.full(n, np.inf)
    return (np.full(n, -np.inf), np.full(n, -np.inf), inf, inf)


def brute(x, q, live, k):
    d = ((x[None].astype(np.float64) - q[:, None]) ** 2).sum(-1)
    d = np.where(live, d, np.inf)
    return np.argsort(d, axis=1, kind="stable")[:, :k]


def test_exact_truth_matches_numpy_brute_force():
    x, q = toy()
    iv = all_live(len(x))
    t = np.zeros(len(q))
    truth = ref.exact_truth(x, q, iv, t, t, 5)
    assert np.array_equal(np.sort(truth, 1),
                          np.sort(brute(x, q, np.ones(len(x), bool), 5), 1))


def test_truth_follows_what_was_live_at_submission():
    x, q = toy()
    n = len(x)
    ins_s, ins_a, del_s, del_a = all_live(n)
    ins_s, ins_a = ins_s.copy(), ins_a.copy()
    del_s, del_a = del_s.copy(), del_a.copy()
    ins_s[:50], ins_a[:50] = 9.0, 10.0      # inserted at t=10
    del_s[50:100], del_a[50:100] = 4.0, 5.0  # deleted at t=5
    iv = (ins_s, ins_a, del_s, del_a)
    sub = np.array([1.0] * 10 + [11.0] * 10)
    done = sub + 0.5
    truth = ref.exact_truth(x, q, iv, sub, done, 5)
    live_early = np.ones(n, bool)
    live_early[:50] = False
    live_late = np.ones(n, bool)
    live_late[50:100] = False
    want = np.concatenate([brute(x, q[:10], live_early, 5),
                           brute(x, q[10:], live_late, 5)])
    assert np.array_equal(np.sort(truth, 1), np.sort(want, 1))
    # An id changing while its search is in flight is neither live nor dead.
    mid = ref.live_mask(iv, np.array([9.5]), np.array([10.5]))
    assert not mid[0, :50].any()
    assert not ref.dead_at(iv, np.arange(50)[None], np.array([9.5]),
                           np.array([10.5])).any()


def test_recall_arithmetic():
    truth = np.array([[1, 2, 3, 4], [5, 6, 7, 8]])
    got = np.array([[1, 2, 9, -1], [8, 7, 6, 5]])
    assert ref.recall(got, truth) == pytest.approx((2 + 4) / 8)


def test_answer_checks_catch_stale_short_and_wrong_distances():
    x, q = toy()
    k = 5
    truth = brute(x, q, np.ones(len(x), bool), k)
    d = ((x[truth].astype(np.float64) - q[:, None]) ** 2).sum(-1)
    dead = np.zeros_like(truth, bool)
    good = ref.answer_checks(x, q, truth, d.astype(np.float32), dead, k)
    assert good["stale_results"] == 0 and good["short_rows"] == 0
    assert good["dist_rel_err"] < 1e-6
    dead[0, 0] = True
    ids = truth.copy()
    ids[1, -1] = -1
    dist = d.astype(np.float32).copy()
    dist[2, 0] *= 1.01
    bad = ref.answer_checks(x, q, ids, dist, dead, k)
    assert bad["stale_results"] == 1 and bad["short_rows"] == 1
    assert bad["dist_rel_err"] == pytest.approx(0.01, rel=1e-3)
    assert not ref.verdict(bad)


def test_the_bf16_control_fails_the_distance_check():
    # Points near far-off centres, as in the cells: neighbours lie close
    # together against large norms.
    r = np.random.default_rng(1)
    centres = 3 * r.standard_normal((8, 128))
    x = (centres[r.integers(0, 8, 2000)]
         + 0.5 * r.standard_normal((2000, 128))).astype(np.float32)
    q = (centres[r.integers(0, 8, 64)]
         + 0.5 * r.standard_normal((64, 128))).astype(np.float32)
    iv = all_live(len(x))
    t = np.zeros(len(q))
    ids, dists = ref.control_answers(x, q, iv, t, t, 5)
    numbers = ref.answer_checks(x, q, ids, dists,
                                np.zeros_like(ids, bool), 5)
    assert numbers["dist_rel_err"] > ref.LIMITS["dist_rel_err"]
    assert not ref.verdict(numbers)


def test_the_direct_form_bf16_control_fails_the_distance_check():
    r = np.random.default_rng(2)
    centres = 3 * r.standard_normal((8, 128))
    x = (centres[r.integers(0, 8, 2000)]
         + 0.5 * r.standard_normal((2000, 128))).astype(np.float32)
    q = (centres[r.integers(0, 8, 64)]
         + 0.5 * r.standard_normal((64, 128))).astype(np.float32)
    iv = all_live(len(x))
    t = np.zeros(len(q))
    ids, dists = ref.control_answers(x, q, iv, t, t, 5, direct=True)
    numbers = ref.answer_checks(x, q, ids, dists,
                                np.zeros_like(ids, bool), 5)
    assert 1e-4 < numbers["dist_rel_err"] < 1.0
    assert numbers["dist_rel_err"] > ref.LIMITS["dist_rel_err"]


def test_temp_misses_count_only_neighbours_the_lti_did_not_hold():
    truth = np.array([[1, 2, 3], [4, 5, 6]])
    got = np.array([[1, 9, 3], [4, 5, -1]])
    lti_time = np.full(10, -np.inf)
    lti_time[[2, 3]] = np.inf          # inserted after the window's merge
    lti_time[6] = 5.0                  # merged: the LTI lacked it until t=5
    done = np.array([1.0, 6.0])
    only = ref.temp_only(lti_time, truth, done)
    assert only.tolist() == [[False, True, True], [False, False, False]]
    share, pairs = ref.temp_misses(got, truth, only)
    assert (share, pairs) == (0.5, 2)
    done_early = np.array([1.0, 4.0])
    only = ref.temp_only(lti_time, truth, done_early)
    assert ref.temp_misses(got, truth, only) == (pytest.approx(2 / 3), 3)
    assert ref.temp_misses(got, truth, np.zeros_like(only)) == (0.0, 0)


def test_truth_without_the_temp_tiers_leaves_them_out():
    x, q = toy()
    iv = all_live(len(x))
    t = np.zeros(len(q))
    lti_time = np.full(len(x), -np.inf)
    lti_time[:150] = np.inf
    truth = ref.exact_truth(x, q, iv, t, t, 5, lti_time)
    live = np.ones(len(x), bool)
    live[:150] = False
    assert np.array_equal(np.sort(truth, 1), np.sort(brute(x, q, live, 5), 1))
