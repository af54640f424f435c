"""The benchmark's yardstick: operation and byte counts, peaks, the pool,
and the reference's selection and shard arithmetic, against hand counts."""
import numpy as np
import pytest

import tiny_cell  # noqa: F401  (puts the benchmark on the path)
import checks
import harness
import pool
import yardstick

KAGGLE = {"num_dense": 13, "table_sizes": [1] * 26, "emb_dim": 16,
          "bottom_mlp": [512, 256, 64, 16], "top_mlp": [512, 256, 1]}
TERABYTE = {"num_dense": 13, "table_sizes": [1] * 26, "emb_dim": 64,
            "bottom_mlp": [512, 256, 64], "top_mlp": [512, 512, 256, 1]}


def test_dlrm_flops_match_hand_counts():
    # Kaggle: bottom 13*512 + 512*256 + 256*64 + 64*16 = 155,136 MACs;
    # top (27*26/2 + 16 = 367) 367*512 + 512*256 + 256*1 = 319,232 MACs;
    # interaction 27*27*16 = 11,664 MACs.  2 ops a MAC, x3 for training.
    assert yardstick.dlrm_forward_flops_per_sample(KAGGLE) == \
        2 * (155_136 + 319_232 + 11_664)
    assert yardstick.dlrm_train_flops_per_sample(KAGGLE) == 2_916_192
    # Terabyte: bottom 154,112; top (351 + 64 = 415) 605,952; inter 46,656
    assert yardstick.dlrm_train_flops_per_sample(TERABYTE) == 4_840_320


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
