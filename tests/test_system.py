"""End-to-end FreshDiskANN system behaviour (paper §5): API semantics,
RW->RO rollover, background merge, crash recovery, persistence."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import IndexConfig, PQConfig, SystemConfig
from repro.core.index import brute_force, recall_at_k
from repro.core.system import FreshDiskANN, bootstrap_system

from conftest import DIM


def _sys_cfg(tmp=None):
    return SystemConfig(
        index=IndexConfig(capacity=2048, dim=DIM, R=24, L_build=32,
                          L_search=64, alpha=1.2),
        pq=PQConfig(dim=DIM, m=8, ksub=32, kmeans_iters=4),
        ro_snapshot_points=128, merge_threshold=256,
        temp_capacity=512, insert_batch=64,
        wal_dir=str(tmp) if tmp else None)


@pytest.fixture(scope="module")
def booted(points):
    return bootstrap_system(points[:800], np.arange(800), _sys_cfg()), points


def _gt_search(live_map, queries, k):
    keys = np.asarray(sorted(live_map))
    mat = np.stack([live_map[kk] for kk in keys])
    gt = brute_force(jnp.asarray(mat), jnp.ones(len(keys), bool),
                     jnp.asarray(queries), k)
    return keys[np.asarray(gt)]


def test_search_after_bootstrap(booted, queries):
    sys_, points = booted
    ids, d = sys_.search(queries, k=5)
    live = dict(enumerate(points[:800]))
    gt = _gt_search(live, queries, 5)
    rec = float(recall_at_k(jnp.asarray(ids), jnp.asarray(gt)))
    assert rec >= 0.85, rec


def test_fresh_inserts_immediately_searchable(points, queries):
    sys_ = bootstrap_system(points[:400], np.arange(400), _sys_cfg())
    for i in range(50):
        sys_.insert(1000 + i, points[400 + i])
    q = points[400:410]
    ids, _ = sys_.search(q, k=1)
    assert (np.asarray(ids[:, 0]) == np.arange(1000, 1010)).mean() >= 0.8


def test_deletes_reflected_without_merge(points):
    sys_ = bootstrap_system(points[:400], np.arange(400), _sys_cfg())
    q = points[:5]
    ids0, _ = sys_.search(q, k=1)
    for e in np.asarray(ids0[:, 0]):
        sys_.delete(int(e))
    ids1, _ = sys_.search(q, k=5)
    assert not np.isin(np.asarray(ids0[:, 0]), np.asarray(ids1)).any()


def test_rollover_and_merge_threshold(points):
    sys_ = bootstrap_system(points[:400], np.arange(400), _sys_cfg())
    for i in range(300):                       # > merge_threshold staged
        sys_.insert(2000 + i, points[500 + i])
    assert sys_.stats.snapshots >= 2
    assert sys_.stats.merges >= 1
    # merged points must remain searchable via the LTI
    q = points[500:520]
    ids, _ = sys_.search(q, k=1)
    assert (np.asarray(ids[:, 0]) >= 2000).mean() >= 0.8


def test_reinsert_after_delete_revives(points):
    sys_ = bootstrap_system(points[:300], np.arange(300), _sys_cfg())
    sys_.delete(7)
    sys_.insert(7, points[7])
    ids, _ = sys_.search(points[7:8], k=1)
    assert int(ids[0, 0]) == 7


def test_size_accounting(points):
    sys_ = bootstrap_system(points[:300], np.arange(300), _sys_cfg())
    for i in range(40):
        sys_.insert(5000 + i, points[300 + i])
    for e in range(20):
        sys_.delete(e)
    assert sys_.size == 300 + 40 - 20


def test_save_load_roundtrip(tmp_path, points, queries):
    sys_ = bootstrap_system(points[:400], np.arange(400), _sys_cfg())
    for i in range(60):
        sys_.insert(3000 + i, points[400 + i])
    sys_.delete(3)
    ids0, d0 = sys_.search(queries[:8], k=5)
    sys_.save(str(tmp_path / "snap"))
    restored = FreshDiskANN.load(str(tmp_path / "snap"), _sys_cfg())
    ids1, d1 = restored.search(queries[:8], k=5)
    assert (np.asarray(ids0) == np.asarray(ids1)).mean() > 0.9


def test_wal_crash_recovery(tmp_path, points):
    cfg = _sys_cfg(tmp_path / "wal")
    sys_ = bootstrap_system(points[:300], np.arange(300), cfg)
    for i in range(40):
        sys_.insert(4000 + i, points[300 + i])
    sys_.delete(5)
    # "crash": rebuild a fresh system from the same base, replay the WAL
    sys2 = bootstrap_system(points[:300], np.arange(300), _sys_cfg())
    sys2.wal = None
    n = 0
    from repro.core.wal import replay
    for op, ext_id, vec in replay(os.path.join(str(tmp_path / "wal"),
                                               "wal.bin")):
        if op == 0:
            sys2.insert(ext_id, vec)
        else:
            sys2.delete(ext_id)
        n += 1
    assert n == 41
    ids, _ = sys2.search(points[300:305], k=1)
    assert (np.asarray(ids[:, 0]) == np.arange(4000, 4005)).mean() >= 0.8
    assert 5 in sys2.deleted_ext


def test_recover_loads_snapshot_before_wal(tmp_path, points):
    """recover(snapshot_path) restores the snapshot, then replays only the
    WAL suffix the snapshot doesn't already cover (no double-apply)."""
    cfg = _sys_cfg(tmp_path / "wal")
    sys_ = bootstrap_system(points[:300], np.arange(300), cfg)
    for i in range(20):                 # WAL-logged AND inside the snapshot
        sys_.insert(7000 + i, points[280 + i])
    sys_.save(str(tmp_path / "snap"))
    size_at_save = sys_.size
    # post-snapshot traffic lands only in the WAL suffix we replay
    for i in range(30):
        sys_.insert(8000 + i, points[300 + i])
    sys_.delete(9)
    # "crash": a fresh empty system with the same WAL recovers everything
    crashed = FreshDiskANN(cfg)
    n = crashed.recover(str(tmp_path / "snap"))
    assert n == 31                      # pre-save records are not re-applied
    assert crashed.size == size_at_save + 30 - 1
    ids, _ = crashed.search(points[300:305], k=1)
    assert (np.asarray(ids[:, 0]) == np.arange(8000, 8005)).mean() >= 0.8
    ids2, _ = crashed.search(points[10:12], k=1)   # snapshot points present
    assert (np.asarray(ids2[:, 0]) == np.arange(10, 12)).mean() >= 0.5
    assert 9 in crashed.deleted_ext


def test_ext_loc_tags_unified(tmp_path, points):
    """Location-map tags name real tiers (lti/rw/ro) after save/load."""
    sys_ = bootstrap_system(points[:300], np.arange(300), _sys_cfg())
    for i in range(200):                      # forces an RW->RO rollover
        sys_.insert(9000 + i, points[400 + i])
    sys_.save(str(tmp_path / "snap"))
    restored = FreshDiskANN.load(str(tmp_path / "snap"), _sys_cfg())
    for s in (sys_, restored):
        tags = {loc[0] for loc in s._ext_loc.values()}
        assert tags <= {"lti", "rw", "ro"}, tags
        assert "ro" in tags  # the rolled-over snapshot is tagged as RO


def test_background_merge_concurrent_search(points, queries):
    sys_ = bootstrap_system(points[:400], np.arange(400), _sys_cfg())
    for i in range(200):
        sys_.insert(6000 + i, points[500 + i])
    sys_.ro.append(sys_.rw)
    sys_.rw = sys_._new_temp()
    sys_.merge(background=True)
    ids, _ = sys_.search(queries[:4], k=5)   # search while merging
    sys_.wait_merge()
    assert sys_.stats.merges >= 1
    assert (np.asarray(ids) >= -1).all()


def test_background_merge_failure_surfaces_in_wait_merge(points,
                                                         monkeypatch):
    """An exception inside the background merge thread is re-raised by
    wait_merge() (once), and the system keeps serving the pre-merge
    state: a failed merge on the device must not pass for a clean run."""
    sys_ = bootstrap_system(points[:300], np.arange(300), _sys_cfg())
    for i in range(150):
        sys_.insert(7000 + i, points[300 + i])
    sys_.ro.append(sys_.rw)
    sys_.rw = sys_._new_temp()

    def boom(ro, t0):
        raise RuntimeError("merge failed on the device")

    monkeypatch.setattr(sys_, "_merge_body", boom)
    sys_.merge(background=True)
    with pytest.raises(RuntimeError, match="merge failed on the device"):
        sys_.wait_merge()
    sys_.wait_merge()                       # the error is reported once
    assert sys_.stats.merges == 0
    ids, _ = sys_.search(points[300:301], k=1)
    assert int(ids[0, 0]) == 7000


# --------------------------------------------------- flush-path concurrency
# The narrowed _insert_lock critical section (insert() holds it only for
# WAL + buffer bookkeeping; the device-side flush runs under _flush_lock
# after release) and the split insert/flush latency accounting.

def test_delete_during_inflight_flush_sticks(points, monkeypatch):
    """A delete issued while its point's flush is in flight must STICK:
    the flush publish loop may not touch the DeleteList (the buffered id
    was revived at append time, so any deleted_ext entry it would discard
    belongs to a LATER delete)."""
    import threading
    sys_ = bootstrap_system(points[:300], np.arange(300), _sys_cfg())
    started, release = threading.Event(), threading.Event()
    inner = sys_._flush_compute

    def gated(ids, vecs, bits, tens):
        started.set()
        assert release.wait(timeout=30)
        inner(ids, vecs, bits, tens)

    monkeypatch.setattr(sys_, "_flush_compute", gated)
    victim = 3000

    def filler():                       # fills the batch -> triggers flush
        for i in range(sys_.cfg.insert_batch):
            sys_.insert(victim + i, points[300 + i])

    t = threading.Thread(target=filler)
    t.start()
    assert started.wait(timeout=30)
    # Flush is mid-compute; insert()/delete() bookkeeping must not block on
    # it (the narrowed lock), and the delete must survive the publish.
    sys_.delete(victim)
    release.set()
    t.join(timeout=30)
    assert not t.is_alive()
    assert victim in sys_.deleted_ext
    ids, _ = sys_.search(points[300:301], k=3)
    assert victim not in np.asarray(ids)
    ids2, _ = sys_.search(points[301:302], k=3)
    assert victim + 1 in np.asarray(ids2)  # the rest of the batch flushed


def test_flush_latency_sampled_once_per_flush(points, monkeypatch):
    """insert_latency samples bookkeeping per insert; flush_latency samples
    the amortized device flush once per flush, and the slow part never
    bleeds into the per-insert numbers."""
    import time as _time
    sys_ = bootstrap_system(points[:300], np.arange(300), _sys_cfg())
    inner = sys_._flush_compute
    monkeypatch.setattr(
        sys_, "_flush_compute",
        lambda ids, vecs, bits, tens: (_time.sleep(0.25), inner(ids, vecs, bits, tens)))
    n = sys_.cfg.insert_batch * 2
    for i in range(n):
        sys_.insert(4000 + i, points[300 + i])
    snap = sys_.stats.serving_snapshot()
    assert sys_.stats.flushes == 2
    assert snap["flush"]["n"] == 2
    assert snap["flush"]["p50"] >= 0.25
    assert sys_.stats.insert_latency.seen == n
    assert max(sys_.stats.insert_latency.sample) < 0.25


def test_concurrent_insert_delete_search_no_deadlock(points):
    """Mixed traffic across threads with the narrowed locks: everything
    completes (no flush->insert->ro lock inversion) and accounting adds
    up."""
    import threading
    sys_ = bootstrap_system(points[:300], np.arange(300), _sys_cfg())
    errs = []

    def worker(base):
        try:
            for i in range(40):
                sys_.insert(base + i, points[(base + i) % 900])
                if i % 7 == 0:
                    sys_.delete(base + i)
                if i % 11 == 0:
                    sys_.search(points[i:i + 2], k=3)
        except Exception as e:                       # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(5000 + 100 * w,))
          for w in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert not errs and all(not t.is_alive() for t in ts)
    assert sys_.stats.inserts == 160 and sys_.stats.deletes == 24
    sys_._flush_inserts()
    assert sys_.size == 300 + 160 - 24
