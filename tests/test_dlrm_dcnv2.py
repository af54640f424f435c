"""DLRM-DCNv2 (low-rank cross layers, per-table hotness) against the
benchmark's plain reference (``benchmarks/chip/references/dlrm_dcnv2.py``),
on the CPU at a tiny size with seeded random weights; the dot interaction
against digests of the program as it was before it took the cross layers;
and the cross layers through CPR's save and ``resume_from`` load."""
import dataclasses
import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.dlrm import (DLRM_DCNV2, DLRM_KAGGLE, MLPERF_DCNV2_HOTNESS,
                                scaled)
from repro.core import CPRManager, Emulator, FailureInjector, SystemParams
from repro.data.synthetic import ClickLogDataset
from repro.models import dlrm as D
from repro.optim.optimizers import apply_updates, rowwise_adagrad

REF_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip", "references",
    "dlrm_dcnv2.py")
B = 32
LR, EPS = 0.004, 1e-8
# float32 on both sides with the same operation order; what is left is the
# fusion XLA chooses for each program, a few ulps of the largest term
RTOL, ATOL = 2e-5, 1e-7


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("dcnv2_reference", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny(multi_hot):
    """MLPerf DCNv2's 26 tables cut to at most 400 rows, every width
    published but the table rows."""
    return dataclasses.replace(scaled(DLRM_DCNV2, max_rows=400),
                               multi_hot=multi_hot)


def ref_cfg(cfg):
    return {"num_dense": cfg.num_dense, "table_sizes": list(cfg.table_sizes),
            "emb_dim": cfg.emb_dim, "bottom_mlp": list(cfg.bottom_mlp),
            "top_mlp": list(cfg.top_mlp), "dcn_num_layers": cfg.dcn_num_layers,
            "dcn_low_rank_dim": cfg.dcn_low_rank_dim,
            "multi_hot": cfg.multi_hot}


def make_batch(cfg, seed):
    rng = np.random.default_rng(seed)
    hot = cfg.multi_hot
    if isinstance(hot, int):
        sparse = np.stack([rng.integers(0, n, (B, hot))
                           for n in cfg.table_sizes], axis=1)
    else:
        sparse = np.concatenate([rng.integers(0, n, (B, h))
                                 for n, h in zip(cfg.table_sizes, hot)],
                                axis=1)
    return {"dense": rng.standard_normal((B, cfg.num_dense), np.float32),
            "sparse": sparse.astype(np.int32),
            "label": (rng.random(B) < 0.3).astype(np.float32)}


def close(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=RTOL,
                                   atol=ATOL)


HOTNESS = pytest.mark.parametrize("multi_hot", [8, MLPERF_DCNV2_HOTNESS],
                                  ids=["int8", "mlperf"])


@HOTNESS
def test_forward_and_loss_match_the_reference(ref, multi_hot):
    cfg = tiny(multi_hot)
    arch = ref.Arch.of(ref_cfg(cfg))
    params = D.init_dlrm(cfg, jax.random.PRNGKey(3))
    want = ref.init_params(arch, jax.random.PRNGKey(3))
    # the same weights from the seed, bit for bit: the benchmark's check
    # makes the initial weights anew in the reference
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    batch = make_batch(cfg, 11)
    hot = None if isinstance(multi_hot, int) else multi_hot
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, b: D.dlrm_forward(p, b, cfg))(params, batch)
        exp = jax.jit(lambda p, b: ref.forward(p, b["dense"], b["sparse"],
                                               hot=hot))(params, batch)
        loss, _ = jax.jit(lambda p, b: D.dlrm_loss(p, b, cfg))(params, batch)
        ref_loss = ref.loss_fn(params, batch, hot=hot)
    assert got.shape == (B,)
    close(got, exp)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=RTOL)


@HOTNESS
def test_gradients_and_adagrad_step_match_the_reference(ref, multi_hot):
    cfg = tiny(multi_hot)
    params = D.init_dlrm(cfg, jax.random.PRNGKey(5))
    batch = make_batch(cfg, 12)
    hot = None if isinstance(multi_hot, int) else multi_hot
    opt = rowwise_adagrad(LR, EPS)

    @jax.jit
    def program(p, b):
        g = jax.grad(lambda q: D.dlrm_loss(q, b, cfg)[0])(p)
        updates, state = opt.update(g, opt.init(p), p)
        return g, apply_updates(p, updates), state["acc"]

    @jax.jit
    def reference(p, b):
        g = jax.grad(ref.loss_fn)(p, b, hot=hot)
        acc = jax.tree.map(lambda q: jnp.zeros(q.shape[:1] if q.ndim >= 2
                                               else q.shape), p)
        new, acc, _, _ = ref.adagrad_update(p, acc, b, LR, EPS, hot=hot)
        return g, new, acc

    with jax.default_matmul_precision("highest"):
        got, want = program(params, batch), reference(params, batch)
    assert set(got[0]) == {"tables", "bottom", "top", "cross"}
    for x, y in zip(got, want):
        close(x, y)
    # the cross layers train: every V, W and b has a gradient
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree.leaves(got[0]["cross"]))


def test_table_ids_give_the_same_bags_in_both_layouts():
    cfg = tiny(3)
    packed = dataclasses.replace(cfg, multi_hot=(3,) * cfg.num_sparse)
    batch = make_batch(cfg, 13)                        # (B, F, 3)
    flat = batch["sparse"].reshape(B, -1)              # (B, F * 3)
    assert packed.hot_offsets[:3] == (0, 3, 6)
    for t in range(cfg.num_sparse):
        np.testing.assert_array_equal(
            np.asarray(D.table_ids(cfg, batch["sparse"], t)),
            np.asarray(D.table_ids(packed, flat, t)))
    params = D.init_dlrm(cfg, jax.random.PRNGKey(0))
    a = D.dlrm_forward(params, batch, cfg)
    b = D.dlrm_forward(params, dict(batch, sparse=flat), packed)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_config_refuses_a_wrong_hotness_or_interaction():
    with pytest.raises(ValueError, match="hotnesses"):
        dataclasses.replace(tiny(8), multi_hot=(1, 2))
    with pytest.raises(ValueError, match="interaction"):
        dataclasses.replace(tiny(8), interaction="cat")
    assert tiny(8).interaction_dim == 27 * 128
    assert sum(MLPERF_DCNV2_HOTNESS) == 214


# ------------------------------------------- the dot interaction as before --
def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# the program before it took the cross layers, on this configuration, seed
# and batch: weights, forward, gradient, and the gradient's lowered program
# (StableHLO without location metadata, so scope names do not enter it)
DOT_DIGESTS = {
    "params": "40368ae0a55918400cfc1c85756c16cf5237d14b5601b117d5c56fa29fd818ca",
    "forward": "d42ad5da8332f351d9dc3d522f1c541b0e680024a6db5c3d1bdeb08dc8af9b56",
    "grad": "c9ea3c90703537b7bc77b9534805389970b7b71569a061cc8928e9768cfd171d",
    "grad_hlo": "33a9add5c2a8219a4ab091c44ad7c025244955d159b3087dda54de0c24776797",
    "forward_hlo":
        "c0300ea41866fca0caf6a58e1ef699fbc78f3db1390ac8414bcae14f278c219a",
}


def test_dot_interaction_is_bit_identical_to_before():
    cfg = D.DLRMConfig(name="dot-digest", num_dense=5,
                       table_sizes=(50, 7, 120, 33), emb_dim=8,
                       bottom_mlp=(16, 8), top_mlp=(32, 16, 1), multi_hot=3)
    rng = np.random.default_rng(20261018)
    batch = {"dense": rng.standard_normal((16, 5)).astype(np.float32),
             "sparse": np.stack([rng.integers(0, n, (16, 3))
                                 for n in cfg.table_sizes],
                                1).astype(np.int32),
             "label": (rng.random(16) < 0.4).astype(np.float32)}
    params = D.init_dlrm(cfg, jax.random.PRNGKey(7))
    fwd = jax.jit(lambda p, b: D.dlrm_forward(p, b, cfg))
    grad = jax.jit(jax.grad(lambda p, b: D.dlrm_loss(p, b, cfg)[0]))
    got = {
        "params": _digest(jax.tree.leaves(params)),
        "forward": _digest([fwd(params, batch)]),
        "grad": _digest(jax.tree.leaves(grad(params, batch))),
        "grad_hlo": hashlib.sha256(grad.lower(params, batch).as_text()
                                   .encode()).hexdigest(),
        "forward_hlo": hashlib.sha256(fwd.lower(params, batch).as_text()
                                      .encode()).hexdigest(),
    }
    assert got == DOT_DIGESTS
    assert set(params) == {"tables", "bottom", "top"}


# --------------------------------------------- through CPR's normal path ----
def small_dcn():
    """A DCNv2 small enough to train for a few steps on the CPU: every
    mechanism, narrow widths."""
    return D.DLRMConfig(name="dcn-small", num_dense=13,
                        table_sizes=(300, 40, 7, 250, 90, 3), emb_dim=8,
                        bottom_mlp=(16, 8), top_mlp=(32, 1), multi_hot=2,
                        interaction="dcn", dcn_num_layers=2,
                        dcn_low_rank_dim=4)


def test_save_and_resume_hold_the_cross_layers(tmp_path):
    cfg = small_dcn()
    ds = ClickLogDataset(cfg.table_sizes, num_samples=3000, multi_hot=2,
                         seed=3)
    p = SystemParams()
    mgr = CPRManager("full", p, cfg.table_sizes, directory=str(tmp_path))
    emu = Emulator(cfg, ds, mgr, FailureInjector(0, 0.25, p.N_emb,
                                                 p.T_total), batch_size=64)
    res = emu.run(max_steps=20)
    assert np.isfinite(res.final_loss) and mgr.n_saves >= 1
    image = mgr.store.trainer_image
    assert set(image) == {"bottom", "top", "cross"}
    assert [set(layer) for layer in image["cross"]] == [{"V", "W", "b"}] * 2
    # the newest save's image is the trained replica, not the initial one
    init = D.init_dlrm(cfg, jax.random.PRNGKey(0))
    assert not np.array_equal(image["cross"][0]["V"],
                              np.asarray(init["cross"][0]["V"]))

    # a fresh emulator from another seed, resumed from the directory, at a
    # learning rate of 0: its state after one step is what it loaded
    mgr2 = CPRManager("full", p, cfg.table_sizes)
    emu2 = Emulator(cfg, ds, mgr2, FailureInjector(0, 0.25, p.N_emb,
                                                   p.T_total),
                    batch_size=64, lr=0.0, seed=9)
    emu2.run(max_steps=1, resume_from=str(tmp_path))
    got = D.trainer_state(emu2.final_params)
    assert jax.tree.structure(got) == jax.tree.structure(image)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(image)):
        np.testing.assert_array_equal(np.asarray(x), y)


def test_trainer_state_is_every_leaf_but_the_tables():
    dot = D.init_dlrm(scaled(DLRM_KAGGLE, max_rows=20), jax.random.PRNGKey(0))
    assert set(D.trainer_state(dot)) == {"bottom", "top"}
    assert D.trainer_state(dot)["top"] is dot["top"]
    dcn = D.init_dlrm(small_dcn(), jax.random.PRNGKey(0))
    assert set(D.trainer_state(dcn)) == {"bottom", "top", "cross"}
