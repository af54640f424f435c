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


def dlrm_train_flops_per_sample(cfg):
    """Model operations of one training sample (forward and backward), as
    the reference module of ``cfg["arch"]`` counts them
    (``train_flops_per_sample``); None where it has no count."""
    import harness
    count = getattr(harness.reference(cfg["arch"]), "train_flops_per_sample",
                    None)
    return None if count is None else count(cfg)


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
