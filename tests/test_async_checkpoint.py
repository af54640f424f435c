"""Async checkpoint engine + device-side tracker selection tests.

Covers the PR's acceptance contract: crash consistency (a fence before any
restore observes every enqueued save), byte-accounting parity with the
synchronous store, and exact equivalence of the Pallas ``tracker_select``
kernel (CPU interpret mode) with the numpy MFU reference.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (AsyncCheckpointWriter, CheckpointStore, CPRManager,
                        EmbShardSpec, FailureEvent, SystemParams)
from repro.core import trackers as trk
from repro.kernels import ops, ref

SIZES = (40, 17, 5)


def make_state(sizes=SIZES, d=8, seed=0):
    rng = np.random.default_rng(seed)
    tables = [rng.normal(size=(n, d)).astype(np.float32) for n in sizes]
    accs = [np.zeros(n, np.float32) for n in sizes]
    return tables, accs


def make_stores(directory=None):
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 4)
    sync = CheckpointStore([t.copy() for t in tables],
                           [a.copy() for a in accs], spec)
    astore = CheckpointStore([t.copy() for t in tables],
                             [a.copy() for a in accs], spec,
                             directory=directory)
    return tables, accs, spec, sync, AsyncCheckpointWriter(astore)


# ------------------------------------------------------------ writer core --
def test_async_byte_accounting_parity():
    """The async writer reports the same per-event bytes as the sync store,
    and after a fence the store's cumulative count matches exactly."""
    tables, accs, spec, sync, writer = make_stores()
    nb_sync = sync.save_full([t + 1 for t in tables], [a + 1 for a in accs],
                             step=1)
    nb_async = writer.save_full([t + 1 for t in tables],
                                [a + 1 for a in accs], step=1)
    assert nb_async == nb_sync
    rows = np.array([0, 3, 39, 99])            # 99 is out of range -> dropped
    vals = np.zeros((4, 8), np.float32)
    av = np.zeros(4, np.float32)
    nb_sync = sync.save_rows(0, rows, vals, av, step=2)
    nb_async = writer.save_rows(0, rows, vals, av, step=2)
    assert nb_async == nb_sync
    writer.fence()
    assert writer.store.bytes_written == sync.bytes_written
    assert writer.store.save_events == sync.save_events
    writer.close()


def test_fence_before_restore_observes_all_saves():
    """Crash consistency: every save enqueued before the fence is visible
    to a subsequent restore, in submission order (later saves win)."""
    tables, accs, spec, _, writer = make_stores()
    for k in range(1, 6):                      # 5 overlapping generations
        writer.save_full([t + k for t in tables], [a + k for a in accs],
                         step=k)
    hot = np.array([1, 2])
    writer.save_rows(0, hot, tables[0][hot] + 99.0, accs[0][hot] + 99.0,
                     step=6)
    writer.fence()
    out_t, out_a = writer.store.restore_shards(
        [t * 0 for t in tables], [a * 0 for a in accs], shard_ids=[0, 1, 2, 3])
    np.testing.assert_array_equal(out_t[1], tables[1] + 5)     # last full
    np.testing.assert_array_equal(out_t[0][hot], tables[0][hot] + 99.0)
    np.testing.assert_array_equal(out_a[0][hot], accs[0][hot] + 99.0)
    writer.close()


def test_snapshot_isolation_from_caller_mutation():
    """The writer snapshots inputs on the caller thread: mutating the
    source arrays after enqueue must not corrupt the checkpoint image."""
    tables, accs, spec, _, writer = make_stores()
    src_t = [t + 7 for t in tables]
    src_a = [a + 7 for a in accs]
    writer.save_full(src_t, src_a, step=1)
    for t in src_t:
        t[...] = -1.0                          # mutate after enqueue
    writer.fence()
    np.testing.assert_array_equal(writer.store.image_tables[0], tables[0] + 7)
    writer.close()


def test_worker_errors_are_fail_stop():
    """After a queued apply fails, later saves are discarded (not applied
    around the hole) and the error stays latched on every subsequent call."""
    tables, accs, spec, _, writer = make_stores()
    # enqueue an apply that will fail in the worker (bad table index)
    writer._submit(writer.store.save_rows, 99, np.array([0]),
                   np.zeros((1, 8), np.float32), np.zeros(1, np.float32), 0)
    with pytest.raises(RuntimeError):
        writer.fence()
    with pytest.raises(RuntimeError):          # still latched
        writer.save_full(tables, accs, step=1)
    with pytest.raises(RuntimeError):
        writer.fence()
    assert writer.store.save_events == 0       # nothing applied post-failure
    writer.close()                             # best-effort, does not raise


def test_writer_close_is_idempotent():
    *_, writer = make_stores()
    writer.close()
    writer.close()


# -------------------------------------------------------- manager wiring ---
@pytest.mark.parametrize("mode", ["cpr", "cpr-mfu"])
def test_async_manager_image_matches_sync(mode):
    """Driving identical save/failure sequences through a sync and an async
    manager yields bit-identical checkpoint images, bytes, and restores."""
    p = SystemParams(N_emb=4)
    mgrs = []
    for async_save in (False, True):
        mgr = CPRManager(mode, p, SIZES, target_pls=0.1,
                         async_save=async_save, tracker_backend="pallas")
        tables, accs = make_state()
        mgr.attach_store(tables, accs)
        mgr.set_total_samples(10_000)
        mgrs.append((mgr, tables, accs))
    rng = np.random.default_rng(5)
    for step in range(6):
        drift_t = [t + rng.normal() for t in mgrs[0][1]]
        drift_a = [a + abs(rng.normal()) for a in mgrs[0][2]]
        results = []
        for mgr, tables, accs in mgrs:
            tracker = (mgr.tracker_init(drift_t) if step == 0 and
                       mgr.is_priority else getattr(mgr, "_tt", {}))
            if mgr.is_priority and step == 0:
                tracker = {t: trk.mfu_update(tracker[t],
                                             jnp.arange(5, dtype=jnp.int32))
                           for t in tracker}
            tracker = mgr.run_save(mgr.save_interval * (step + 1),
                                   drift_t, drift_a, tracker, step=step)
            mgr._tt = tracker
            if step == 3:
                out = mgr.on_failure(
                    FailureEvent(mgr.save_interval * (step + 1) + 0.01,
                                 (1, 2), 0.5), drift_t, drift_a)
                results.append(out)
        if results:
            np.testing.assert_array_equal(results[0][0][0], results[1][0][0])
    sync_mgr, async_mgr = mgrs[0][0], mgrs[1][0]
    async_mgr.fence()
    for t in range(len(SIZES)):
        np.testing.assert_array_equal(sync_mgr.store.image_tables[t],
                                      async_mgr.store.image_tables[t])
        np.testing.assert_array_equal(sync_mgr.store.image_accs[t],
                                      async_mgr.store.image_accs[t])
    assert sync_mgr.store.bytes_written == async_mgr.store.bytes_written
    assert sync_mgr.ledger.save == pytest.approx(async_mgr.ledger.save)
    assert async_mgr.ledger.save_blocked_s > 0.0
    async_mgr.close()


def test_async_disk_roundtrip(tmp_path):
    """Disk persistence happens off-thread but load_latest sees a complete,
    ordered image after fence."""
    tables, accs = make_state()
    spec = EmbShardSpec(SIZES, 4)
    store = CheckpointStore([t.copy() for t in tables],
                            [a.copy() for a in accs], spec,
                            directory=str(tmp_path))
    writer = AsyncCheckpointWriter(store)
    writer.save_full([t + 1.5 for t in tables], [a + 2 for a in accs], step=5)
    writer.save_rows(0, np.array([1, 2]), tables[0][[1, 2]] + 9.0,
                     accs[0][[1, 2]] + 9.0, step=7)
    writer.fence()
    loaded = CheckpointStore.load_latest(str(tmp_path), tables, accs, spec)
    np.testing.assert_array_equal(loaded.image_tables[1], tables[1] + 1.5)
    np.testing.assert_array_equal(loaded.image_tables[0][[1, 2]],
                                  tables[0][[1, 2]] + 9.0)
    writer.close()


# ------------------------------------------------- tracker_select kernel ---
_SELECT_CASES = [
    (1000, 300, 25, 256, False),    # multi-segment
    (7, 3, 2, 512, False),          # single tiny segment
    (512, 0, 10, 128, False),       # no pending ids
    (513, 11, 4, 256, False),       # ragged last segment (padding picks)
    (100, 50, 100, 512, False),     # k > live rows
    # several blocks of segments, n_seg (1172) not a multiple of the block
    (300_001, 500, 3, 256, False),
    # ragged last segment inside a block padded with whole segments
    (4658, 200, 5, 128, False),
    (20_000, 300, 1, 512, False),   # k = 1
    (1000, 100, 128, 128, False),   # k = seg: every row of every segment
    (50_000, 1000, 8, 512, False),  # a table smaller than one block
    # pending ids past the end and negative, folded by the scatter
    (4658, 200, 5, 128, True),
]


@pytest.mark.parametrize(
    "N,M,k,seg,stray", _SELECT_CASES,
    ids=["-".join(map(str, c[:4])) + ("-stray" if c[4] else "")
         for c in _SELECT_CASES])
def test_tracker_select_matches_numpy_ref(N, M, k, seg, stray):
    rng = np.random.default_rng(N + M + k)
    counts = rng.integers(0, 50, size=N).astype(np.int32)
    idx = rng.integers(0, N, size=M).astype(np.int32)
    if stray:
        idx = np.concatenate([idx, np.array(
            [N, N + 7, -1, -N, -(N + 3), np.iinfo(np.int32).max,
             np.iinfo(np.int32).min], np.int32)])
        rng.shuffle(idx)
    got_i, got_c = ops.tracker_select(jnp.asarray(counts), jnp.asarray(idx),
                                      k, seg_size=seg)
    want_i, want_c = ref.tracker_select(counts, idx, k, seg_size=seg)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)   # exact
    np.testing.assert_array_equal(np.asarray(got_c), want_c)


def test_tracker_select_tie_breaking_matches_ref():
    """All-equal counts: both implementations pick the lowest row ids."""
    counts = np.full(64, 3, np.int32)
    got_i, got_c = ops.tracker_select(jnp.asarray(counts),
                                      jnp.zeros((0,), jnp.int32), 4,
                                      seg_size=32)
    want_i, want_c = ref.tracker_select(counts, np.zeros(0, np.int64), 4,
                                        seg_size=32)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)
    np.testing.assert_array_equal(np.asarray(got_i), [0, 1, 2, 3,
                                                      32, 33, 34, 35])
    np.testing.assert_array_equal(np.asarray(got_c), want_c)


def test_tracker_select_ignores_out_of_range_pending_ids():
    """Regression: pending ids in [N, n_seg*seg) or negative must match
    nothing — they'd otherwise inflate padding-row counters and displace
    live rows from the selection (diverging from the numpy oracle)."""
    counts = np.zeros(10, np.int32)
    counts[0], counts[1] = 5, 4
    idx = np.array([12, 12, 12, -3], np.int32)     # all invalid for N=10
    got_i, got_c = ops.tracker_select(jnp.asarray(counts), jnp.asarray(idx),
                                      2, seg_size=8)
    want_i, want_c = ref.tracker_select(counts, idx, 2, seg_size=8)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)
    np.testing.assert_array_equal(np.asarray(got_c), want_c)
    live = np.asarray(got_i)[np.asarray(got_i) < 10]
    assert 8 in live or 9 in live      # ragged segment still picks live rows


def test_tracker_select_fused_update_counts():
    """Pending ids are folded in before selection and survive in new_counts
    for unselected rows."""
    counts = np.zeros(16, np.int32)
    idx = np.array([3, 3, 3, 9, 9, 1], np.int32)
    got_i, got_c = ops.tracker_select(jnp.asarray(counts), jnp.asarray(idx),
                                      2, seg_size=16)
    assert set(np.asarray(got_i).tolist()) == {3, 9}
    got_c = np.asarray(got_c)
    assert got_c[3] == 0 and got_c[9] == 0     # selected -> cleared
    assert got_c[1] == 1                       # unselected survives


def test_mfu_select_segmented_matches_global_topk_single_segment():
    """For tables within one segment the segmented selection is the global
    MFU top-k (same selected set, counters cleared identically)."""
    counts = jnp.asarray(np.random.default_rng(2).integers(
        0, 1000, size=300).astype(np.int32))
    rn = 40
    gi, gc = trk.mfu_select_segmented(counts, rn, seg_size=512)
    hi, hc = trk.mfu_select(counts, rn)
    assert set(np.asarray(gi).tolist()) == set(np.asarray(hi).tolist())
    np.testing.assert_array_equal(np.asarray(gc), np.asarray(hc))
