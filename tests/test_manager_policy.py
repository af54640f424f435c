"""CPRManager policy + PLS-accounting properties, and the serve driver."""
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import CPRManager, FailureEvent, SystemParams
from repro.core.manager import PRIORITY_MODES


def make_mgr(mode="cpr", n_emb=8, **kw):
    p = SystemParams(N_emb=n_emb)
    sizes = (100, 40, 7)
    mgr = CPRManager(mode, p, sizes, **kw)
    tables = [np.zeros((n, 4), np.float32) for n in sizes]
    accs = [np.zeros(n, np.float32) for n in sizes]
    mgr.attach_store(tables, accs)
    mgr.set_total_samples(10_000)
    return mgr, tables, accs


def test_priority_modes_use_subintervals():
    for mode in PRIORITY_MODES:
        mgr, *_ = make_mgr(mode)
        assert mgr.save_interval == pytest.approx(mgr.T_save / 8)
    mgr, *_ = make_mgr("cpr")
    assert mgr.save_interval == mgr.T_save


def test_big_table_selection_covers_99pct():
    mgr, *_ = make_mgr("cpr-mfu")
    covered = sum(mgr.table_sizes[t] for t in mgr.big_tables)
    assert covered / sum(mgr.table_sizes) >= 0.9


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4), st.integers(100, 9000))
def test_pls_increment_matches_eq3(n_shards_failed, samples):
    """Eq. 3: one failure adds k/N · (S_i − S_last)/S_total to PLS."""
    mgr, tables, accs = make_mgr("cpr", n_emb=8)
    mgr.samples_seen = samples
    ids = tuple(range(n_shards_failed))
    ev = FailureEvent(time=1.0, shard_ids=ids, fraction=n_shards_failed / 8)
    _, _, info = mgr.on_failure(ev, tables, accs)
    want = n_shards_failed * samples / 10_000 / 8
    assert mgr.pls == pytest.approx(want)
    # second failure of the same shards right away adds ~nothing
    mgr.on_failure(FailureEvent(1.1, ids, ev.fraction), tables, accs)
    assert mgr.pls == pytest.approx(want)


def test_full_recovery_accrues_no_pls():
    mgr, tables, accs = make_mgr("full")
    mgr.samples_seen = 5000
    mgr.on_failure(FailureEvent(1.0, (0, 1), 0.25), tables, accs)
    assert mgr.pls == 0.0
    assert mgr.ledger.lost > 0.0


def test_due_saves_monotone_and_complete():
    mgr, *_ = make_mgr("cpr")
    evs = mgr.due_saves(mgr.T_save * 3.5)
    assert len(evs) == 3
    assert evs == sorted(evs)
    assert mgr.due_saves(mgr.T_save * 3.6) == []


def test_serve_driver_end_to_end():
    from repro.configs import get_config
    from repro.launch.serve import make_requests, serve
    cfg = get_config("gemma2-2b").reduced()
    reqs = make_requests(5, 8, cfg.vocab_size)
    done, stats = serve(cfg, reqs, batch=2, gen=4)
    assert set(done) == set(range(5))
    assert all(len(v) == 4 for v in done.values())
    assert stats["refills"] == 3


@pytest.mark.parametrize("async_save", [False, True], ids=["sync", "async"])
def test_priority_saves_gather_into_kept_buffers(async_save):
    """A priority save gathers each big table's picks into host buffers
    that the manager keeps from save to save; the store takes the rows
    before the next save overwrites them, so the image holds every save's
    own values."""
    _check_kept_buffer_saves(async_save, "C")


@pytest.mark.parametrize("async_save", [False, True], ids=["sync", "async"])
def test_priority_saves_gather_column_major_tables(async_save):
    """A column-major table (how the TPU keeps, and the host fetches, a
    narrow table) is gathered in its own order, into column-major kept
    buffers, and the image still holds every save's own values."""
    _check_kept_buffer_saves(async_save, "F")


def _check_kept_buffer_saves(async_save, order):
    import jax.numpy as jnp

    from repro.core import trackers as trk
    p = SystemParams(N_emb=4)
    sizes = (100, 40, 7)
    mgr = CPRManager("cpr-mfu", p, sizes, async_save=async_save)
    rng = np.random.default_rng(3)
    tables = [np.asarray(rng.normal(size=(n, 4)), np.float32, order=order)
              for n in sizes]
    accs = [rng.random(n).astype(np.float32) for n in sizes]
    mgr.attach_store(tables, accs)
    mgr.set_total_samples(10_000)
    picks, select = [], mgr._select

    def recording_select(*a, **kw):
        rows, state = select(*a, **kw)
        picks.append((a[0], rows.copy()))
        return rows, state
    mgr._select = recording_select
    tracker = mgr.tracker_init(tables)
    saved, bufs = [], []
    for i in range(3):
        tracker = {t: trk.mfu_update(c, jnp.asarray(
            rng.integers(0, sizes[t], 30))) for t, c in tracker.items()}
        tables = [np.asarray(x + 1.0, order=order) for x in tables]
        accs = [a + 0.5 for a in accs]
        picks.clear()
        tracker = mgr.run_save(mgr.save_interval * (i + 1), tables, accs,
                               tracker, step=i)
        saved.append([(t, r, tables[t][r], accs[t][r]) for t, r in picks])
        bufs.append({t: b[1][0] for t, b in mgr._gather_bufs.items()})
    assert set(bufs[0]) == set(mgr.big_tables)
    for t in mgr.big_tables:
        assert bufs[1][t] is bufs[0][t] and bufs[2][t] is bufs[0][t]
        assert bufs[0][t].flags[f"{order}_CONTIGUOUS"]
    mgr.fence()
    # replay the saves in order: the image holds each row's latest save
    latest = {}
    for per_save in saved:
        for t, r, v, a in per_save:
            assert r.size
            latest.update({(t, row): (vv, aa) for row, vv, aa in zip(r, v, a)})
    for (t, row), (v, a) in latest.items():
        np.testing.assert_array_equal(mgr.store.image_tables[t][row], v)
        assert mgr.store.image_accs[t][row] == a
    mgr.close()
