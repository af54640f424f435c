"""The CPR path's own spans and transfer counters (``repro.core.tracing``).

A tiny cpr-mfu ``Emulator.run`` with saves and a failure is recorded by
``jax.profiler`` on the CPU: its ``cpr.*`` spans must nest as the save and
the restore do, with their ``save``, ``table`` and ``failure`` metadata.
The manager's counters must equal the ``nbytes`` of what was copied.
"""
import glob
import os
import time

import jax
import pytest

from repro.configs.dlrm import DLRM_KAGGLE, scaled
from repro.core import CPRManager, Emulator, FailureInjector, SystemParams
from repro.data.synthetic import ClickLogDataset

STEPS = 24
SAVE_CHILDREN = ("cpr.save.fetch", "cpr.save.select", "cpr.save.gather",
                 "cpr.save.store", "cpr.save.small", "cpr.save.fence")
TABLE_SPANS = ("cpr.save.fetch", "cpr.save.select", "cpr.save.gather",
               "cpr.save.store")
RESTORE_CHILDREN = ("cpr.restore.fetch", "cpr.restore.apply",
                    "cpr.restore.put")


@pytest.fixture(scope="module")
def setup():
    cfg = scaled(DLRM_KAGGLE, max_rows=2000)
    ds = ClickLogDataset(cfg.table_sizes, num_samples=8000, seed=3)
    return cfg, ds


def _run(cfg, ds, mode="cpr-mfu", **kw):
    p = SystemParams()
    mgr = CPRManager(mode, p, cfg.table_sizes, **kw)
    # one failure, 60 % through the span, after some saves
    inj = FailureInjector(1, 0.25, p.N_emb, p.T_total,
                          times=(0.6 * p.T_total,))
    emu = Emulator(cfg, ds, mgr, inj, batch_size=256)
    res = emu.run(max_steps=STEPS)
    return mgr, emu, res


@pytest.fixture(scope="module")
def traced(setup, tmp_path_factory):
    """(manager, emulator, [(name, start_ns, end_ns, stats)] of cpr.*)."""
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        mgr, emu, _ = _run(*setup, async_save=True)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              {k: v for k, v in e.stats})
             for pl in data.planes for ln in pl.lines for e in ln.events
             if e.name.startswith("cpr.")]
    return mgr, emu, spans


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_trace_holds_every_span_of_the_path(traced):
    mgr, _, spans = traced
    assert mgr.is_priority and mgr.n_saves > 1 and mgr.n_failures == 1
    names = {s[0] for s in spans}
    assert names == {"cpr.step", "cpr.save", "cpr.restore"} | set(
        SAVE_CHILDREN) | set(RESTORE_CHILDREN)
    steps = _named(spans, "cpr.step")
    assert sorted(int(s[3]["step_num"]) for s in steps) == list(range(STEPS))
    assert len(_named(spans, "cpr.save")) == mgr.n_saves
    assert len(_named(spans, "cpr.restore")) == 1


def test_save_spans_nest_and_share_the_save_id(traced):
    mgr, _, spans = traced
    saves = {int(s[3]["save"]): s for s in _named(spans, "cpr.save")}
    assert sorted(saves) == list(range(mgr.n_saves))
    boundaries = [int(s[3]["boundary"]) for s in saves.values()]
    assert 0 < sum(boundaries) < len(boundaries)
    for name in SAVE_CHILDREN:
        for child in _named(spans, name):
            assert _inside(child, saves[int(child[3]["save"])])
    for name in TABLE_SPANS:
        per_save = {}
        for child in _named(spans, name):
            assert int(child[3]["table"]) in mgr.big_tables
            per_save.setdefault(int(child[3]["save"]), []).append(
                int(child[3]["table"]))
        assert all(sorted(t) == sorted(mgr.big_tables)
                   for t in per_save.values())
    # small tables and the fence only at a cycle boundary
    for name in ("cpr.save.small", "cpr.save.fence"):
        assert sorted(int(c[3]["save"]) for c in _named(spans, name)) == \
            sorted(i for i, s in saves.items() if int(s[3]["boundary"]))
    # the fetch of a table comes before its pick, the pick before the store
    for sid in saves:
        for t in mgr.big_tables:
            f, sel, g, st = (
                [c for c in _named(spans, n) if int(c[3]["save"]) == sid
                 and int(c[3]["table"]) == t][0] for n in TABLE_SPANS)
            assert f[2] <= sel[1] and sel[2] <= g[1] and g[2] <= st[1]


def test_restore_spans_nest_and_share_the_failure_id(traced):
    _, _, spans = traced
    (restore,) = _named(spans, "cpr.restore")
    assert int(restore[3]["failure"]) == 0 and restore[3]["shards"]
    children = [c for n in RESTORE_CHILDREN for c in _named(spans, n)]
    assert len(children) == 3
    for c in children:
        assert _inside(c, restore) and int(c[3]["failure"]) == 0
    fetch, apply, put = children
    assert fetch[2] <= apply[1] and apply[2] <= put[1]


def _state_bytes(emu):
    tabs = emu.final_params["tables"]
    accs = emu.final_ostate["acc"]["tables"]
    return [t.nbytes for t in tabs], [a.nbytes for a in accs]


@pytest.mark.parametrize("backend", ["host", "pallas"])
def test_counters_equal_the_nbytes_arithmetic(setup, backend):
    mgr, emu, res = _run(*setup, tracker_backend=backend)
    tab_b, acc_b = _state_bytes(emu)
    trainer = sum(x.nbytes for x in jax.tree.leaves(
        {"bottom": emu.final_params["bottom"],
         "top": emu.final_params["top"]}))
    saves = [h for h in mgr.history if h["event"] == "save"]
    n_boundary = sum(h["boundary"] for h in saves)
    per_save = 0
    for t in mgr.big_tables:
        n = mgr.table_sizes[t]
        rn = max(1, int(mgr.r * n))
        ids = rn
        if backend == "pallas":         # the padded pick: n_seg x k ids
            seg = min(mgr.seg_size, n)
            n_seg = -(-n // seg)
            ids = n_seg * max(1, min(-(-rn // n_seg), seg))
        per_save += tab_b[t] + acc_b[t] + 4 * ids
    small = sum(tab_b[t] + acc_b[t] for t in mgr.small_tables)
    whole = sum(tab_b) + sum(acc_b)
    assert len(saves) > 1 and 0 < n_boundary < len(saves)
    assert mgr.counters == {
        "save_fetch_bytes": len(saves) * per_save +
        n_boundary * (small + trainer),
        "restore_fetch_bytes": mgr.n_failures * whole,
        "restore_put_bytes": mgr.n_failures * whole}
    assert res.report["transfers"] == mgr.counters


def test_full_recovery_save_counts_every_array(setup):
    mgr, emu, res = _run(*setup, mode="full")
    tab_b, acc_b = _state_bytes(emu)
    trainer = sum(x.nbytes for x in jax.tree.leaves(
        {"bottom": emu.final_params["bottom"],
         "top": emu.final_params["top"]}))
    saves = [h for h in mgr.history if h["event"] == "save"]
    assert saves and mgr.n_saves == len(saves)
    assert res.report["transfers"]["save_fetch_bytes"] == len(saves) * (
        sum(tab_b) + sum(acc_b) + trainer)


# the model's own scopes inside ``forward``, forward and backward
MODEL_SCOPES = tuple(f"jit(step)/{d}/{s}/"
                     for d in ("jvp(forward)", "transpose(jvp(forward))")
                     for s in ("embedding", "interaction"))


def _step_text(cfg, ds):
    p = SystemParams()
    mgr = CPRManager("cpr-mfu", p, cfg.table_sizes)
    emu = Emulator(cfg, ds, mgr, FailureInjector(0, 0.25, p.N_emb, p.T_total))
    step, opt = emu._build_step()
    from repro.models import dlrm as D
    params = D.init_dlrm(cfg, jax.random.PRNGKey(0))
    batch = next(iter(ds.batches(64, 0, 64)))
    return step.lower(params, opt.init(params),
                      mgr.tracker_init(params["tables"]),
                      batch).compile().as_text()


def test_step_hlo_carries_the_scopes(setup):
    """The compiled step's op names carry forward, its transpose (the
    backward pass), optimizer and tracker, and inside forward the embedding
    bags and the (dot) interaction."""
    text = _step_text(*setup)
    for path in ("jit(step)/jvp(forward)/",
                 "jit(step)/transpose(jvp(forward))/",
                 "jit(step)/optimizer/", "jit(step)/tracker/") + MODEL_SCOPES:
        assert f'op_name="{path}' in text, path


def test_dcn_step_hlo_carries_the_model_scopes():
    """The same scopes around DLRM-DCNv2's multi-hot bags and cross layers."""
    from repro.models.dlrm import DLRMConfig
    cfg = DLRMConfig(name="dcn-small", num_dense=13,
                     table_sizes=(300, 40, 7, 250), emb_dim=8,
                     bottom_mlp=(16, 8), top_mlp=(32, 1), multi_hot=2,
                     interaction="dcn", dcn_num_layers=2, dcn_low_rank_dim=4)
    ds = ClickLogDataset(cfg.table_sizes, num_samples=128, multi_hot=2,
                         seed=3)
    text = _step_text(cfg, ds)
    for path in MODEL_SCOPES:
        assert f'op_name="{path}' in text, path


def test_spans_cost_nothing_without_a_session():
    """With no profiler session, a span is entered and left for about a
    microsecond; the counters hold only ints."""
    from repro.core.tracing import span, step_span
    t0 = time.perf_counter()
    for i in range(1000):
        with span("cpr.save.fetch", save=i, table=3):
            pass
        with step_span(i):
            pass
    assert time.perf_counter() - t0 < 0.5
    mgr = CPRManager("cpr-mfu", SystemParams(), (10, 20))
    assert all(isinstance(v, int) and v == 0 for v in mgr.counters.values())
