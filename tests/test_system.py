"""End-to-end behaviour tests: the paper's headline claims hold on the
emulation framework (reduced scale), and the launch driver runs with
failures + partial recovery on a real transformer."""
import numpy as np

from repro.configs.dlrm import DLRM_KAGGLE, scaled
from repro.core import CPRManager, Emulator, FailureInjector, SystemParams
from repro.data.synthetic import ClickLogDataset


def test_headline_claim_overhead_reduction_and_accuracy():
    """Paper Fig. 7: CPR cuts checkpoint overhead by >80% vs full recovery
    while keeping AUC within 0.01 (reduced-scale emulation)."""
    cfg = scaled(DLRM_KAGGLE, max_rows=2000)
    ds = ClickLogDataset(cfg.table_sizes, num_samples=8000, seed=3)
    p = SystemParams()
    results = {}
    for mode in ("full", "cpr-mfu"):
        mgr = CPRManager(mode, p, cfg.table_sizes, target_pls=0.1)
        inj = FailureInjector(2, 0.25, p.N_emb, p.T_total, seed=11)
        results[mode] = Emulator(cfg, ds, mgr, inj, batch_size=256).run()
    of = results["full"].report["overheads"]["total"]
    oc = results["cpr-mfu"].report["overheads"]["total"]
    assert oc < 0.2 * of, (oc, of)
    assert results["cpr-mfu"].auc > results["full"].auc - 0.01


def test_lm_driver_with_partial_recovery():
    """The transformer launch driver survives failures and keeps training."""
    from examples.train_lm_with_cpr import CFG_100M
    import dataclasses
    from repro.launch.train import train
    cfg = dataclasses.replace(CFG_100M, num_layers=2, d_model=128,
                              num_heads=4, num_kv_heads=2, head_dim=32,
                              d_ff=256, vocab_size=512, sliding_window=32)
    _, hist = train(cfg, steps=24, batch=2, seq=32, mode="cpr-mfu",
                    n_failures=2, log_every=100)
    kinds = [e[0] for e in hist["events"]]
    assert "save" in kinds and "failure" in kinds
    assert np.isfinite(hist["loss"][-1][1])
    assert hist["report"]["measured_pls"] > 0


def test_compile_cache_leaves_env_var_choice_to_jax(monkeypatch, tmp_path):
    import jax
    from repro.launch import compile_cache as cc
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cc.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_defaults_to_fixed_ignored_checkout_path(monkeypatch):
    import os

    import jax
    from repro.launch import compile_cache as cc
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        got = cc.enable_compile_cache()
        assert got == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert cc.enable_compile_cache() == got     # the same path each time
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
