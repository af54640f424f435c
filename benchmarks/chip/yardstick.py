"""Operations and bytes from shapes, and the table of chip peaks.

Kept with the benchmark so that a change to the program cannot move them.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def mlp_flops(sizes):
    """Forward multiply-adds of a dense stack, counted as 2 operations each."""
    return sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))


def dlrm_forward_flops_per_sample(cfg):
    """Bottom MLP, the pairwise dot-product interaction (the whole F x F
    batched product, as it is computed) and the top MLP.  Embedding sums
    and elementwise work are not counted."""
    F = len(cfg["table_sizes"]) + 1
    inter_dim = F * (F - 1) // 2 + cfg["emb_dim"]
    bottom = mlp_flops([cfg["num_dense"]] + list(cfg["bottom_mlp"]))
    top = mlp_flops([inter_dim] + list(cfg["top_mlp"]))
    return bottom + top + 2 * F * F * cfg["emb_dim"]


def dlrm_train_flops_per_sample(cfg):
    """Forward plus backward (twice the forward) model operations."""
    return 3 * dlrm_forward_flops_per_sample(cfg)


def segmented_k(n, rn, seg_size=512):
    """(segment width, rows picked per segment) of a segment-wise top-k
    covering ``rn`` of ``n`` rows."""
    seg = min(seg_size, max(n, 1))
    n_seg = -(-n // seg)
    return seg, max(1, min(-(-rn // n_seg), seg))


def tracker_select_bytes(n, k, seg, pending=0):
    """Bytes a fused count-update + segment-wise top-k must move: the int32
    counters read and written back, the picked ids written, and the pending
    ids read."""
    n_seg = -(-n // seg)
    return 4 * n + 4 * n + 4 * n_seg * k + 4 * pending
