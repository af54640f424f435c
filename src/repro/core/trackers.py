"""Priority trackers for partial checkpointing (paper §4.2, Table 1).

Given constrained checkpoint bandwidth, CPR saves the rows most likely to
have large accumulated updates first.  Three implementations:

  * SCAR   (Qiao et al. 2019): track actual per-row update magnitude via a
           shadow copy of the table at the last save.  Memory 100 %,
           time O(N log N) at save.
  * CPR-MFU: a 4-byte access counter per row (memory 0.78–6.25 % of the
           table for 64–512 B vectors); save the top r·N by count, clear
           saved counters.  Time O(N log N).
  * CPR-SSU: a fixed r·N-slot deduplicated list of sub-sampled accessed row
           ids with random eviction on overflow (memory r× MFU); the
           sub-sampling acts as a high-pass filter on access frequency.
           Time O(N) (no global sort over the table).

All ``update`` functions are pure and jit-compatible so they can live inside
the train step.  ``EMPTY`` (int32 max) marks unused SSU slots.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

EMPTY = jnp.iinfo(jnp.int32).max


# ------------------------------------------------------------------ MFU ----
def mfu_init(num_rows: int):
    return jnp.zeros((num_rows,), jnp.int32)


def mfu_update(counts, indices):
    """indices: any int array of accessed row ids."""
    return counts.at[indices.reshape(-1)].add(1)


def mfu_select(counts, rn: int):
    """Top r·N rows by access count -> (row_ids, cleared_counts)."""
    rn = min(rn, counts.shape[0])
    _, idx = jax.lax.top_k(counts, rn)
    return idx, counts.at[idx].set(0)


def segmented_k(n: int, rn: int, seg_size: int = 512):
    """(seg, k) plan for segment-wise selection: segment width and the
    per-segment quota covering rn rows total.  Shared by the selection
    wrapper and the benchmark's parity audit."""
    seg = min(seg_size, max(n, 1))
    n_seg = -(-n // seg)
    return seg, max(1, min(-(-rn // n_seg), seg))


def mfu_select_segmented(counts, rn: int, indices=None, seg_size: int = 512):
    """Device-side fused MFU update + segment-wise top-k (Pallas kernel).

    Replaces the global ``top_k`` over the full counter table with a
    per-segment top-``ceil(rn/n_seg)`` selection, so priority saves never
    round-trip the table through a host sort.  ``indices`` (optional
    pending accessed ids not yet counted; ids outside [0, N) are dropped)
    are folded in by a scatter-add in the same jitted program, before the
    kernel.  Returns (row_ids, new_counts) like ``mfu_select``: row_ids
    hold each segment's picks in descending count, ties to the lowest id,
    and may include padding picks >= N, which callers drop; new_counts
    hold the folded counts with the picked rows cleared.

    Caveat: the per-segment quota matches global top-k only when hot rows
    are spread across segments.  Ids clustered into few segments (e.g. raw
    un-permuted zipf ids) lose hot-set coverage to the quota — keep the
    manager's ``tracker_backend="host"`` default there, or permute ids.
    """
    from repro.kernels import ops
    seg, k = segmented_k(counts.shape[0], rn, seg_size)
    if indices is None:
        indices = jnp.zeros((0,), jnp.int32)
    return ops.tracker_select(counts, indices, k, seg_size=seg)


# ------------------------------------------------------------------ SSU ----
def ssu_init(rn: int, seed: int = 17):
    """``seed`` decorrelates eviction streams across tracker instances —
    with a shared key every table/trial evicts the same buffer positions,
    which systematically drops the hottest (lowest-position) ids."""
    return {"buf": jnp.full((rn,), EMPTY, jnp.int32),
            "key": jax.random.PRNGKey(seed)}


def ssu_update(state, indices, period: int = 2, backend: str = "host"):
    """Insert every ``period``-th accessed id; dedupe; random-evict overflow.

    Keeps the buffer sorted ascending with EMPTY slots at the end, so
    membership tests are O(log rN) via searchsorted.

    ``backend="pallas"`` runs the dedupe/merge/evict as one fused kernel
    (``kernels.ssu_dedupe``).  Both backends draw the keep-scores from
    the same PRNG stream *before* branching, so their results are
    bit-identical — the parity test asserts it.
    """
    buf, key = state["buf"], state["key"]
    rn = buf.shape[0]
    cand = indices.reshape(-1)[::period]
    cand = jnp.unique(cand, size=cand.shape[0], fill_value=EMPTY)
    key, sub = jax.random.split(key)
    # random keep of rn among valid entries (uniform eviction on overflow)
    scores = jax.random.uniform(sub, (rn + cand.shape[0],))
    if backend == "pallas":
        from repro.kernels import ops
        return {"buf": jnp.asarray(ops.ssu_dedupe_evict(buf, cand, scores)),
                "key": key}
    # drop candidates already present
    pos = jnp.searchsorted(buf, cand)
    present = buf[jnp.clip(pos, 0, rn - 1)] == cand
    cand = jnp.where(present, EMPTY, cand)
    combined = jnp.sort(jnp.concatenate([buf, cand]))
    score = jnp.where(combined != EMPTY, scores, jnp.inf)
    keep = jnp.argsort(score)[:rn]
    new_buf = jnp.sort(combined[keep])
    # if no overflow, keep everything valid (argsort path already does)
    return {"buf": new_buf, "key": key}


def ssu_select(state):
    """Rows to save -> (row_ids (padded with EMPTY), reset_state)."""
    return state["buf"], {"buf": jnp.full_like(state["buf"], EMPTY),
                          "key": state["key"]}


# ----------------------------------------------------------------- SCAR ----
def scar_init(table):
    return {"shadow": table.copy()}


def scar_select(state, table, rn: int):
    """Top r·N rows by L2 norm of change since last save."""
    rn = min(rn, table.shape[0])
    delta = jnp.sum(jnp.square(table - state["shadow"]), axis=-1)
    _, idx = jax.lax.top_k(delta, rn)
    new_shadow = state["shadow"].at[idx].set(table[idx])
    return idx, {"shadow": new_shadow}


# ------------------------------------------------- memory accounting -------
def tracker_memory_bytes(mode: str, num_rows: int, emb_bytes: int, r: float) -> int:
    """Table 1: tracker memory relative to the embedding table."""
    if mode == "scar":
        return num_rows * emb_bytes           # shadow copy: 100 %
    if mode == "mfu":
        return num_rows * 4                   # 4-byte counter per row
    if mode == "ssu":
        return int(num_rows * r) * 4          # r·N id slots
    return 0


# -------------------------------------- frequency/update correlation -------
def access_update_correlation(counts, table, table0):
    """Pearson correlation between access frequency and update L2 norm
    (paper Fig. 6 reports 0.983)."""
    c = np.asarray(counts, dtype=np.float64)
    upd = np.linalg.norm(np.asarray(table, np.float64) -
                         np.asarray(table0, np.float64), axis=-1)
    mask = np.ones_like(c, bool)
    if c.std() == 0 or upd.std() == 0:
        return float("nan")
    return float(np.corrcoef(c[mask], upd[mask])[0, 1])
