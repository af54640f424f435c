"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Shapes and dtypes are swept per the brief; hypothesis covers the
embedding-bag index space.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import ops, ref


# ------------------------------------------------------------ embedding ----
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,d,B,hot", [
    (64, 16, 8, 1), (128, 64, 4, 4), (1000, 32, 16, 3), (32, 512, 2, 2),
])
def test_embedding_bag_matches_ref(N, d, B, hot, dtype):
    key = jax.random.PRNGKey(0)
    table = jax.random.normal(key, (N, d), dtype)
    idx = jax.random.randint(jax.random.PRNGKey(1), (B, hot), 0, N)
    got = ops.embedding_bag(table, idx, block_d=min(512, d))
    want = ref.embedding_bag(table, idx)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 50), st.integers(1, 5), st.data())
def test_embedding_bag_property(N, hot, data):
    B = data.draw(st.integers(1, 8))
    idx = np.array(data.draw(st.lists(
        st.lists(st.integers(0, N - 1), min_size=hot, max_size=hot),
        min_size=B, max_size=B)), np.int32)
    table = np.random.default_rng(0).normal(size=(N, 16)).astype(np.float32)
    got = ops.embedding_bag(jnp.asarray(table), jnp.asarray(idx), block_d=16)
    want = table[idx].sum(1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------ flash attention ----
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,hd,causal,window,softcap", [
    (2, 4, 4, 128, 128, 32, True, 0, 0.0),
    (1, 8, 2, 128, 128, 64, True, 0, 0.0),       # GQA 4:1
    (2, 4, 1, 256, 256, 32, True, 64, 0.0),      # MQA + sliding window
    (1, 2, 2, 128, 128, 32, True, 0, 50.0),      # softcap (gemma2)
    (1, 4, 4, 128, 128, 32, False, 0, 0.0),      # encoder (hubert)
    (1, 4, 2, 128, 384, 32, True, 0, 0.0),       # Skv > Sq (decode-ish)
])
def test_flash_attention_matches_ref(B, Hq, Hkv, Sq, Skv, hd, causal,
                                     window, softcap, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, hd), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, hd), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, block_q=64, block_k=64)
    want = ref.flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                               v.swapaxes(1, 2), causal, window,
                               softcap).swapaxes(1, 2)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_model_chunked_attention():
    """The Pallas kernel and the model's online-softmax path agree."""
    from repro.models.layers import _chunked_sdpa
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, S, H, hd = 2, 256, 4, 32
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, 2, hd))
    v = jax.random.normal(ks[2], (B, S, 2, hd))
    pos = jnp.arange(S)
    want = _chunked_sdpa(q, k, v, pos, pos, True, 0, 0.0, block=64)
    got = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ rglru scan ----
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,w,bs,bw", [
    (2, 128, 64, 32, 64), (1, 256, 128, 256, 64), (3, 64, 32, 16, 32),
])
def test_rglru_scan_matches_ref(B, S, w, bs, bw, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    # decay in (0, 1) like real RG-LRU gates
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, w))).astype(dtype)
    b = (jax.random.normal(ks[1], (B, S, w)) * 0.1).astype(dtype)
    got = ops.rglru_scan(a, b, block_s=bs, block_w=bw)
    want = ref.rglru_scan(a, b)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.integers(1, 4), st.integers(1, 4))
def test_rglru_block_invariance(B, sblocks, wblocks):
    """Property: result is independent of the block decomposition."""
    S, w = 32 * sblocks, 32 * wblocks
    ks = jax.random.split(jax.random.PRNGKey(B * 100 + S + w), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, w)))
    b = jax.random.normal(ks[1], (B, S, w)) * 0.1
    full = ops.rglru_scan(a, b, block_s=S, block_w=w)
    blocked = ops.rglru_scan(a, b, block_s=32, block_w=32)
    np.testing.assert_allclose(np.asarray(full), np.asarray(blocked),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------ FNV-1a row hash ----
@pytest.mark.parametrize("n,d", [
    (0, 8),       # empty shard slice
    (1, 1), (7, 3), (257, 5), (1000, 16),
    (5, 0),       # zero-column values: rows hash the acc bytes only
])
def test_row_hash_kernel_bit_exact(n, d):
    """The Pallas FNV kernel is an exact-match port: uint64-for-uint64
    against both the numpy oracle and the checkpoint writer's host loop,
    on every shape class a shard slice can take."""
    from repro.core.sharded_checkpoint import row_hash as host_row_hash
    rng = np.random.default_rng(n * 31 + d)
    v = rng.normal(size=(n, d)).astype(np.float32)
    a = np.abs(rng.normal(size=n)).astype(np.float32)
    want = ref.row_hash(v, a)
    got = ops.row_hash(v, a)
    assert got.dtype == np.uint64 and got.shape == (n,)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, host_row_hash(v, a))


def test_row_hash_zero_byte_rows_hash_to_offset_basis():
    from repro.kernels.row_hash import FNV_OFFSET
    v = np.zeros((4, 0), np.float32)
    a = np.zeros((4, 0), np.float32)
    np.testing.assert_array_equal(ops.row_hash(v, a),
                                  np.full(4, FNV_OFFSET, np.uint64))


def test_row_hash_block_invariance():
    """Result is independent of the grid blocking (padding rows are cut)."""
    from repro.kernels import row_hash as rh
    rng = np.random.default_rng(11)
    v = rng.normal(size=(300, 9)).astype(np.float32)
    a = rng.normal(size=300).astype(np.float32)
    full = rh.row_hash(v, a, block_rows=1024)
    blocked = rh.row_hash(v, a, block_rows=64)   # 300 -> 5 blocks, padded
    np.testing.assert_array_equal(full, blocked)


# ------------------------------------------------------- SSU dedupe/evict ---
def test_ssu_dedupe_evict_matches_numpy_oracle():
    from repro.kernels.ssu_dedupe import EMPTY
    rng = np.random.default_rng(5)
    rn, nc = 16, 12
    buf = np.sort(rng.choice(1000, size=rn, replace=False)).astype(np.int32)
    buf[rn - 3:] = EMPTY                    # EMPTY-padded tail
    cand = np.full(nc, EMPTY, np.int32)
    cand[:6] = rng.choice(1000, size=6, replace=False)
    cand[0] = buf[0]                        # one duplicate to drop
    scores = rng.uniform(size=rn + nc).astype(np.float32)
    got = np.asarray(ops.ssu_dedupe_evict(buf, cand, scores))
    want = ref.ssu_dedupe_evict(buf, cand, scores)
    np.testing.assert_array_equal(got, want)


def test_ssu_update_backend_parity_bit_identical():
    """trackers.ssu_update draws the eviction scores before branching, so
    host and pallas backends walk the same PRNG stream and must agree bit
    for bit across rounds."""
    from repro.core import trackers as trk
    sh = trk.ssu_init(32, seed=3)
    sp = trk.ssu_init(32, seed=3)
    rng = np.random.default_rng(9)
    for k in range(6):
        idx = jnp.asarray(rng.integers(0, 200, size=40, dtype=np.int32))
        sh = trk.ssu_update(sh, idx, period=2, backend="host")
        sp = trk.ssu_update(sp, idx, period=2, backend="pallas")
        np.testing.assert_array_equal(np.asarray(sh["buf"]),
                                      np.asarray(sp["buf"]),
                                      err_msg=f"round {k}")
        np.testing.assert_array_equal(np.asarray(sh["key"]),
                                      np.asarray(sp["key"]))


# ---------------------------------------------- tracker_select lane guard ---
def test_tracker_select_rejects_misaligned_seg_on_mosaic_path():
    """A seg that is not a lane-width multiple can never compile through
    Mosaic — the guard fails fast at trace time instead of shipping a
    config that only works in interpret mode."""
    from repro.kernels import tracker_select as ts
    counts = jnp.zeros(1000, jnp.int32)
    idx = jnp.zeros(0, jnp.int32)
    with pytest.raises(ValueError, match="lane"):
        ts.tracker_select(counts, idx, 2, seg_size=100, interpret=False)
    # interpret mode has no layout constraint: any seg runs
    ids, nc = ts.tracker_select(counts, idx, 2, seg_size=100,
                                interpret=True)
    assert nc.shape == (1000,)


def test_autotune_seg_size_picks_lane_aligned_candidate():
    from repro.kernels import tracker_select as ts
    seg = ts.autotune_seg_size(4096, 8, candidates=(100, 128, 256),
                               trials=1)
    assert seg in (128, 256)
    with pytest.raises(ValueError):
        ts.autotune_seg_size(4096, 8, candidates=(100, 200), trials=1)


# ------------------------------------------- kernels the TPU refuses ------
@pytest.fixture
def on_tpu(monkeypatch):
    """Make ``ops._interpret()`` see a TPU backend (nothing compiles)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("kernel", sorted(ops.NO_TPU_LOWERING))
def test_kernel_without_tpu_lowering_refuses_on_tpu(kernel, on_tpu):
    with pytest.raises(NotImplementedError, match=kernel):
        ops.require_lowering(kernel)


def test_refused_kernel_wrappers_never_interpret_on_tpu(on_tpu):
    """On a TPU the wrappers pass interpret=False, and the kernels Mosaic
    refuses raise instead of running the interpreter on the chip."""
    ops.require_lowering("tracker_select")          # compiles: no refusal
    with pytest.raises(NotImplementedError, match="row_hash"):
        ops.row_hash(np.ones((4, 4), np.float32), np.ones((4, 1), np.float32))
    with pytest.raises(NotImplementedError, match="ssu_dedupe"):
        ops.ssu_dedupe_evict(jnp.zeros(4, jnp.int32), jnp.zeros(2, jnp.int32),
                             jnp.zeros(6))
    with pytest.raises(NotImplementedError, match="embedding_bag"):
        ops.embedding_bag(jnp.ones((8, 16)), jnp.zeros((2, 1), jnp.int32))


def test_refused_backends_raise_at_construction_on_tpu(on_tpu, tmp_path):
    from repro.configs.dlrm import DLRM_KAGGLE, scaled
    from repro.core import (CPRManager, EmbShardSpec, Emulator,
                            FailureInjector, ShardedCheckpointWriter,
                            SystemParams)
    p, sizes = SystemParams(), (64, 32)
    with pytest.raises(NotImplementedError, match="ssu_dedupe"):
        CPRManager("cpr-ssu", p, sizes, tracker_backend="pallas")
    with pytest.raises(NotImplementedError, match="row_hash"):
        CPRManager("cpr-mfu", p, sizes, hash_backend="pallas")
    tabs = [np.zeros((n, 4), np.float32) for n in sizes]
    accs = [np.zeros((n,), np.float32) for n in sizes]
    with pytest.raises(NotImplementedError, match="row_hash"):
        ShardedCheckpointWriter(tabs, accs, EmbShardSpec(sizes, 2),
                                directory=str(tmp_path),
                                hash_backend="pallas")
    assert not list(tmp_path.iterdir())     # refused before touching disk
    cfg = scaled(DLRM_KAGGLE, max_rows=200)
    mgr = CPRManager("cpr-mfu", p, cfg.table_sizes, tracker_backend="pallas")
    with pytest.raises(NotImplementedError, match="embedding_bag"):
        Emulator(cfg, None, mgr, FailureInjector(0, 0.25, p.N_emb,
                                                 p.T_total), use_kernel=True)
