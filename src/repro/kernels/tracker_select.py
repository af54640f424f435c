"""Pallas-TPU fused MFU tracker update + segment-wise top-k row selection.

The CPR-MFU hot path at every priority-save sub-interval is: fold the
pending accessed-row ids into the per-row access counters, then pick the
r·N highest-count rows and clear their counters.  The host implementation
round-trips the full counter table through a global sort per sub-interval;
this kernel keeps everything on device and replaces the global sort with a
*segment-wise* top-k: the table is cut into fixed-size row segments and the
top ``k`` rows of each segment are selected.  For skewed (Zipf-like) access
distributions hot rows are spread across segments, so segment-wise
selection covers the same hot set while needing only an O(seg) scan per
grid step — no global argsort, no host round-trip.

Grid: one step per segment, over counters laid out (n_seg, 1, seg) with
the segment axis squeezed out of the block, so every block's last two
dims equal the array's (Mosaic's tiling rule).  Each step
  1. DMAs its (1, seg) counter block into VMEM,
  2. adds the pending-id histogram for its row range, comparing the
     pending id list against the segment's global row iota ``chunk`` ids
     at a time (a fixed-size VMEM compare buffer at any pending count),
  3. runs ``k`` max/argmin-of-tie iterations to emit the segment's top-k
     global row ids,
  4. writes back the updated counters with the selected rows cleared.

``interpret=True`` (off the TPU) runs the same kernel body as traced JAX
ops — bit-identical to the Mosaic path and to ``ref.tracker_select``.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_INT32_MIN = jnp.iinfo(jnp.int32).min

# TPU vector lane width: a Mosaic-lowered (1, seg) block lives in
# (sublane, lane) tiles, so ``seg`` must be a lane-width multiple or the
# compile fails with an opaque layout error.  interpret mode has no such
# constraint (any seg runs), which is exactly how a blind-tuned seg_size
# slips through CPU tests and breaks on hardware — hence the guard below.
LANE_WIDTH = 128

# pending ids folded per inner step: bounds the kernel's (chunk, seg)
# compare buffer in VMEM (512 x 512 int32 = 1 MiB) at any pending count
_PENDING_CHUNK = 512


def _kernel(idx_ref, cnt_ref, out_idx_ref, out_cnt_ref, *, seg: int, k: int,
            chunk: int):
    lo = pl.program_id(0) * seg
    col = jax.lax.broadcasted_iota(jnp.int32, (1, seg), 1)        # 0..seg-1
    gid = lo + col                                                # global ids
    # --- fused count update: histogram of pending ids over this segment,
    # folded ``chunk`` ids at a time so the (chunk, seg) compare buffer
    # stays a fixed VMEM size whatever the pending count ---
    def fold(c, hits):
        start = pl.multiple_of(c * chunk, chunk)
        ids = idx_ref[pl.ds(start, chunk), :]                     # (chunk, 1)
        return hits + jnp.sum((ids == gid).astype(jnp.int32), axis=0,
                              keepdims=True)                      # (1, seg)
    n_chunks = idx_ref.shape[0] // chunk
    counts = cnt_ref[...] + jax.lax.fori_loop(
        0, n_chunks, fold, jnp.zeros((1, seg), jnp.int32))

    # --- segment-wise top-k (ties -> lowest row id); a picked row's work
    # entry drops to INT32_MIN, which no live or padding count reaches ---
    kcol = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def body(j, carry):
        work, ids = carry
        m = jnp.max(work, axis=1, keepdims=True)                  # (1, 1)
        pos = jnp.min(jnp.where(work == m, col, seg), axis=1, keepdims=True)
        ids = jnp.where(kcol == j, lo + pos, ids)
        return jnp.where(col == pos, _INT32_MIN, work), ids

    work, ids = jax.lax.fori_loop(
        0, k, body, (counts, jnp.zeros((1, k), jnp.int32)))
    out_idx_ref[...] = ids
    out_cnt_ref[...] = jnp.where(work == _INT32_MIN, 0, counts)


@functools.partial(jax.jit,
                   static_argnames=("k", "seg_size", "interpret"))
def tracker_select(counts, indices, k: int, seg_size: int = 512,
                   interpret: bool = True):
    """Fused MFU update + segment-wise top-k.

    counts:  (N,) int32 per-row access counters.
    indices: int array of pending accessed row ids (any shape; may be empty)
             not yet folded into ``counts``.
    k:       rows to select per segment.

    Returns ``(row_ids, new_counts)``: ``row_ids`` is (n_seg * k,) int32
    global ids (entries >= N are padding-segment picks and must be dropped
    by the caller); ``new_counts`` is (N,) with pending ids folded in and
    the selected rows' counters cleared.
    """
    counts = jnp.asarray(counts, jnp.int32)
    (N,) = counts.shape
    seg = min(seg_size, max(int(N), 1))
    if not interpret and seg % LANE_WIDTH:
        raise ValueError(
            f"seg_size {seg_size} -> effective segment {seg} is not a "
            f"multiple of the {LANE_WIDTH}-wide TPU lane dim; pick a "
            f"lane-aligned seg_size (see autotune_seg_size)")
    n_seg = -(-N // seg)                      # ceil
    k = min(k, seg)
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    pad = n_seg * seg - N
    # padded rows get count -1 so any live row outranks them.  The grid
    # axis is a squeezed leading dim: each block's last two dims, (1, seg)
    # and (1, k), equal the array's, which is what Mosaic's tiling accepts
    cgrid = jnp.pad(counts, (0, pad), constant_values=-1).reshape(
        n_seg, 1, seg)
    flat = jnp.asarray(indices, jnp.int32).reshape(-1)
    # ids outside [0, N) must match nothing — N..n_seg*seg-1 would otherwise
    # inflate padding-row counters and displace live rows from the top-k
    flat = jnp.where((flat >= 0) & (flat < N), flat, -1)
    # pad the pending list with -1 (matches nothing) to whole chunks of
    # sublane-aligned rows; an empty list becomes one chunk of 8
    chunk = min(_PENDING_CHUNK, -(-max(flat.size, 1) // 8) * 8)
    n_pend = -(-max(flat.size, 1) // chunk) * chunk
    idx2d = jnp.pad(flat, (0, n_pend - flat.size),
                    constant_values=-1).reshape(n_pend, 1)

    ids, new_counts = pl.pallas_call(
        functools.partial(_kernel, seg=seg, k=k, chunk=chunk),
        grid=(n_seg,),
        in_specs=[
            pl.BlockSpec((n_pend, 1), lambda i: (0, 0)),
            pl.BlockSpec((None, 1, seg), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, seg), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_seg, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((n_seg, 1, seg), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="tracker_select",      # the kernel's name in traces and HLO
    )(idx2d, cgrid)
    return ids.reshape(-1), new_counts.reshape(-1)[:N]


def autotune_seg_size(n_rows: int, k: int,
                      candidates=(128, 256, 512, 1024, 2048),
                      pending: int = 512, trials: int = 3,
                      interpret: bool = True, seed: int = 0) -> int:
    """Pick ``seg_size`` by measurement instead of blind convention.

    Runs ``tracker_select`` on a representative ``(n_rows, k)`` workload
    for every **lane-aligned** candidate (misaligned candidates are
    skipped — they could never ship to Mosaic) and returns the one with
    the best min-over-``trials`` wall time.  Measurable today in
    interpret mode (relative ranking tracks the O(seg·k) scan cost) and
    the same harness times the Mosaic path on TPU unchanged.

    The chosen value is what ``CPRManager`` surfaces in ``report()`` when
    configured with ``seg_size="auto"``.
    """
    rng = np.random.default_rng(seed)
    n_rows = max(int(n_rows), 1)
    counts = jnp.asarray(rng.integers(0, 64, size=n_rows, dtype=np.int32))
    idx = jnp.asarray(
        rng.integers(0, n_rows, size=max(1, min(n_rows, pending)),
                     dtype=np.int32))
    best_seg, best_t = None, None
    for seg in candidates:
        if seg % LANE_WIDTH or (seg > n_rows and best_seg is not None):
            continue
        kk = max(1, min(int(k), seg))
        ids, nc = tracker_select(counts, idx, kk, seg_size=seg,
                                 interpret=interpret)
        jax.block_until_ready(nc)             # compile outside the clock
        t = None
        for _ in range(max(1, trials)):
            t0 = time.monotonic()
            ids, nc = tracker_select(counts, idx, kk, seg_size=seg,
                                     interpret=interpret)
            jax.block_until_ready(nc)
            dt = time.monotonic() - t0
            t = dt if t is None else min(t, dt)
        if best_t is None or t < best_t:
            best_seg, best_t = seg, t
    if best_seg is None:
        raise ValueError(f"no lane-aligned seg_size candidate in "
                         f"{tuple(candidates)}")
    return best_seg
