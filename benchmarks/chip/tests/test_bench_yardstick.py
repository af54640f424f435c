"""The benchmark's yardstick: operation and byte counts, peaks, the pool,
and the reference's selection and shard arithmetic, against hand counts."""
import hashlib
import types

import numpy as np
import pytest

import tiny_cell  # noqa: F401  (puts the benchmark on the path)
import checks
import harness
import pool
import yardstick

KAGGLE = {"arch": "dlrm", "num_dense": 13, "table_sizes": [1] * 26,
          "emb_dim": 16, "bottom_mlp": [512, 256, 64, 16],
          "top_mlp": [512, 256, 1]}
TERABYTE = {"arch": "dlrm", "num_dense": 13, "table_sizes": [1] * 26,
            "emb_dim": 64, "bottom_mlp": [512, 256, 64],
            "top_mlp": [512, 512, 256, 1]}


def test_dlrm_flops_match_hand_counts():
    # Kaggle: bottom 13*512 + 512*256 + 256*64 + 64*16 = 155,136 MACs;
    # top (27*26/2 + 16 = 367) 367*512 + 512*256 + 256*1 = 319,232 MACs;
    # interaction 27*27*16 = 11,664 MACs.  2 ops a MAC, x3 for training.
    ref = harness.reference("dlrm")
    assert ref.forward_flops_per_sample(KAGGLE) == \
        2 * (155_136 + 319_232 + 11_664)
    assert yardstick.dlrm_train_flops_per_sample(KAGGLE) == 2_916_192
    # Terabyte: bottom 154,112; top (351 + 64 = 415) 605,952; inter 46,656
    assert yardstick.dlrm_train_flops_per_sample(TERABYTE) == 4_840_320


def test_kaggle_config_train_flops_unchanged():
    cfg = harness.load_config(harness.load_benchmark(), "dlrm-kaggle")
    assert yardstick.dlrm_train_flops_per_sample(cfg) == 2_916_192
    assert harness.reference(cfg["arch"]).train_flops_per_sample(cfg) == \
        2_916_192


def test_flops_silent_for_a_reference_without_a_count(monkeypatch):
    monkeypatch.setattr(harness, "reference",
                        lambda arch: types.SimpleNamespace())
    assert yardstick.dlrm_train_flops_per_sample(
        dict(KAGGLE, arch="another")) is None
    ctx = {"trace": {"modules": {"jit_step": {"count": 2, "s": 0.035}}},
           "peaks": {"bf16_flops_per_s": 197e12}, "yardstick": yardstick,
           "cfg": dict(KAGGLE, arch="another"), "counters": {"batch": 2048}}
    assert harness.metric_reader("train_mfu_pct").read(ctx) is None


def _digest(p):
    h = hashlib.sha256()
    for k in sorted(p):
        a = np.ascontiguousarray(p[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# digests of the one-hot pool as the harness drew it before it took hotness
@pytest.mark.parametrize("seed,zipf_a,want", [
    (7, 1.2,
     "4620f1a38cb3a09141985e353dad006f5108940c06f64ee778d655b48cd8ab18"),
    (2**31 + 5, 1.2,
     "3431571c837bade18db7d7b86500a6ea0d6735157db24ef951ed468b32923754"),
    (2**31 + 5, 1.05,
     "ed636c9f7b37d763c40e5d71bd7e745349e6a2cf779a9991a865f7d633d97d6e"),
])
def test_one_hot_pool_is_unchanged(seed, zipf_a, want):
    sizes = [3, 1000, 100_003, 26]
    assert _digest(pool.make_pool(sizes, 13, 512, seed,
                                  zipf_a=zipf_a)) == want
    assert _digest(pool.make_pool(sizes, 13, 512, seed, zipf_a=zipf_a,
                                  multi_hot=1)) == want


@pytest.mark.parametrize("multi_hot", [3, 2], ids=["uniform", "two"])
def test_multi_hot_pool_layout(multi_hot):
    sizes = [3, 1000, 100_003, 26]
    seed = 2**31 + 5
    one = pool.make_pool(sizes, 13, 512, seed)
    got = pool.make_pool(sizes, 13, 512, seed, multi_hot=multi_hot)
    assert got["sparse"].shape == (512, 4, multi_hot)
    np.testing.assert_array_equal(got["dense"], one["dense"])
    for t, n in enumerate(sizes):
        ids = got["sparse"][:, t, :]
        assert ids.min() >= 0 and ids.max() < n
        # slot 0 is the one-hot draw, from the same stream
        np.testing.assert_array_equal(ids[:, 0], one["sparse"][:, t, 0])
    # further slots are drawn anew, not copies of slot 0
    wide = got["sparse"][:, 2, :]
    assert (wide[:, 1] != wide[:, 0]).mean() > 0.5


def test_program_config_takes_every_model_key():
    from repro.models.dlrm import DLRMConfig
    cfg = harness.load_config(harness.load_benchmark(), "dlrm-kaggle")
    got = harness.program_config(cfg)
    # as the harness built it before it read the whole file
    old = DLRMConfig(
        name=cfg["name"], num_dense=cfg["num_dense"],
        table_sizes=tuple(cfg["table_sizes"]), emb_dim=cfg["emb_dim"],
        bottom_mlp=tuple(cfg["bottom_mlp"]),
        top_mlp=tuple(cfg["top_mlp"]), multi_hot=1)
    assert got == DLRMConfig(**dict(vars(old), source=cfg["source"]))
    assert harness.program_config(dict(cfg, multi_hot=2)).multi_hot == 2


def test_cell_refuses_an_unknown_model_key():
    bench = harness.load_benchmark()
    wl = harness.find(bench["workloads"], "kaggle-mfu", "workload")
    cfg, traffic = tiny_cell.tiny(harness.load_config(bench, wl["config"]),
                                  harness.load_traffic(wl["traffic"]))
    with pytest.raises(ValueError, match="cross_layers"):
        harness.Cell(dict(cfg, cross_layers=3), traffic, seed=1)


@pytest.mark.parametrize("n,rn,seg,want", [
    (10_131_227, 1_266_403, 512, (512, 64)),
    (1000, 125, 512, (512, 63)),
    (100, 12, 512, (100, 12)),
])
def test_segmented_k(n, rn, seg, want):
    assert yardstick.segmented_k(n, rn, seg) == want


def test_tracker_select_bytes_by_hand():
    # 1024 rows in 2 segments of 512, 64 picks each, 2048 pending ids:
    # counters read 4 KiB + written 4 KiB, 128 ids, 2048 pending
    assert yardstick.tracker_select_bytes(1024, 64, 512, 2048) == \
        4096 + 4096 + 4 * 128 + 4 * 2048


def test_peaks_known_and_unknown_kind():
    p = yardstick.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        yardstick.peaks("cpu")


def test_pool_is_seeded_and_in_range():
    sizes = [3, 1000, 10_000_019]
    a = pool.make_pool(sizes, 13, 4096, seed=2**31 + 7)
    b = pool.make_pool(sizes, 13, 4096, seed=2**31 + 7)
    c = pool.make_pool(sizes, 13, 4096, seed=2**31 + 8)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(a["sparse"], c["sparse"])
    assert a["sparse"].shape == (4096, 3, 1) and a["dense"].shape == (4096, 13)
    for f, n in enumerate(sizes):
        ids = a["sparse"][:, f, 0]
        assert ids.min() >= 0 and ids.max() < n
    # Zipf skew: the hottest id of the big table is far above uniform
    _, counts = np.unique(a["sparse"][:, 2, 0], return_counts=True)
    assert counts.max() > 100
    assert 0.0 < a["label"].mean() < 1.0


@pytest.mark.parametrize("n", [1, 2, 12, 97, 1 << 20])
def test_affine_bijection_is_a_permutation(n):
    a, b = pool.affine_bijection(np.random.default_rng(n), n)
    r = np.arange(min(n, 5000), dtype=np.int64)
    ids = (r * a + b) % n
    assert np.unique(ids).size == r.size


def test_segment_topk_matches_brute_force():
    ref = harness.reference("dlrm")
    rng = np.random.default_rng(0)
    for n, k, seg in [(1000, 7, 128), (1300, 64, 512), (77, 5, 512)]:
        counts = rng.integers(0, 3, n) * (rng.random(n) < 0.3)
        want = []
        for s in range(0, n, seg):
            c = counts[s:s + seg]
            order = sorted(range(len(c)), key=lambda i: (-c[i], i))
            want += [s + i for i in order[:k]]
        np.testing.assert_array_equal(ref.segment_topk(counts, k, seg),
                                      np.sort(want))


def test_shard_range_is_the_paper_split():
    ref = harness.reference("dlrm")
    n, N = 10_131_227, 8
    edges = np.floor(np.arange(N + 1) * n / N).astype(np.int64)
    for j in range(N):
        assert ref.shard_range(n, j, N) == (edges[j], edges[j + 1])


def test_big_tables_cover_99_percent():
    bench = harness.load_benchmark()
    kaggle = harness.load_config(bench, "dlrm-kaggle")["table_sizes"]
    assert len(checks.big_tables(kaggle)) == 6
    assert sum(kaggle) == 33_762_577
    # largest first until 99 %: 1000 alone is 49.9 %, with 990 it is 99.25 %
    assert checks.big_tables([10, 1000, 5, 990]) == [1, 3]


def test_full_save_bytes_by_hand():
    ref = harness.reference("dlrm")
    arch = ref.Arch(2, (3, 5), 4, (8, 4), (6, 1))
    # tables 8 rows x 4 + 8 accumulators; bottom 2*8+8 + 8*4+4;
    # top (3*2/2 + 4 = 7) 7*6+6 + 6*1+1
    dense = (16 + 8 + 32 + 4) + (42 + 6 + 6 + 1)
    assert ref.full_save_bytes(arch) == 4 * (8 * 4 + 8 + dense)
