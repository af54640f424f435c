"""Pallas-TPU MFU tracker update + segment-wise top-k row selection.

The CPR-MFU hot path at every priority-save sub-interval is: fold the
pending accessed-row ids into the per-row access counters, then pick the
r·N highest-count rows and clear their counters.  The host implementation
round-trips the full counter table through a global sort per sub-interval;
this kernel keeps everything on device and replaces the global sort with a
*segment-wise* top-k: the table is cut into fixed-size row segments and the
top ``k`` rows of each segment are selected.  For skewed (Zipf-like) access
distributions hot rows are spread across segments, so segment-wise
selection covers the same hot set while needing only an O(seg) scan per
segment — no global argsort, no host round-trip.

Layout: the padded counters are viewed as ``(n_seg_p, seg)``, one segment
per row, and the grid runs over blocks of ``S`` segments stacked on
sublanes.  ``S`` is derived from the shape alone (``_block_segments``):
about ``_BLOCK_ROWS`` counters per block, a multiple of the 8 sublanes, and
no more segments than the table has.  ``n_seg`` is padded up to whole
blocks with segments of count -1, like the ragged last segment.  Each grid
step runs ``k`` max/argmin-of-tie iterations over its ``(S, seg)`` block,
so every iteration's row-wise reductions cover ``S`` independent segments
at once, emits the ``(S, k)`` top-k global row ids, and writes back the
counters with the selected rows cleared.

Pending ids are folded in by one scatter-add in the same jitted program,
before the ``pallas_call``; ids outside ``[0, N)`` are dropped.

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

# TPU vector lane width: a Mosaic-lowered (S, seg) block lives in
# (sublane, lane) tiles, so ``seg`` must be a lane-width multiple or the
# compile fails with an opaque layout error.  interpret mode has no such
# constraint (any seg runs), which is exactly how a blind-tuned seg_size
# slips through CPU tests and breaks on hardware — hence the guard below.
LANE_WIDTH = 128
SUBLANES = 8

# counters per grid step: 2**17 int32 is 128 dense vregs, enough
# independent segments that the row-wise reductions pipeline, and its
# double-buffered in/out blocks (~2 MiB) sit well inside scoped VMEM
_BLOCK_ROWS = 1 << 17


def _block_segments(n_seg: int, seg: int) -> int:
    """Segments per grid step: a sublane multiple near ``_BLOCK_ROWS``
    counters, and no more than ``n_seg`` rounded up to whole sublanes."""
    per_block = max(SUBLANES, _BLOCK_ROWS // seg // SUBLANES * SUBLANES)
    return min(-(-n_seg // SUBLANES) * SUBLANES, per_block)


def _kernel(cnt_ref, out_idx_ref, out_cnt_ref, *, k: int):
    s, seg = cnt_ref.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (s, seg), 1)       # 0..seg-1
    kcol = jax.lax.broadcasted_iota(jnp.int32, (s, k), 1)
    # first global row id of each of the block's segments
    lo = (pl.program_id(0) * s +
          jax.lax.broadcasted_iota(jnp.int32, (s, 1), 0)) * seg
    counts = cnt_ref[...]

    # --- segment-wise top-k (ties -> lowest row id), one segment per
    # row; a picked row's work entry drops to INT32_MIN, which no live or
    # padding count reaches ---
    def body(j, carry):
        work, ids = carry
        m = jnp.max(work, axis=1, keepdims=True)                  # (s, 1)
        pos = jnp.min(jnp.where(work == m, col, seg), axis=1, keepdims=True)
        ids = jnp.where(kcol == j, lo + pos, ids)
        return jnp.where(col == pos, _INT32_MIN, work), ids

    work, ids = jax.lax.fori_loop(
        0, k, body, (counts, jnp.zeros((s, k), jnp.int32)))
    out_idx_ref[...] = ids
    out_cnt_ref[...] = jnp.where(work == _INT32_MIN, 0, counts)


@functools.partial(jax.jit,
                   static_argnames=("k", "seg_size", "interpret"))
def tracker_select(counts, indices, k: int, seg_size: int = 512,
                   interpret: bool = True):
    """Fused MFU update + segment-wise top-k.

    counts:  (N,) int32 per-row access counters.
    indices: int array of pending accessed row ids (any shape; may be empty)
             not yet folded into ``counts``; ids outside [0, N) are dropped.
    k:       rows to select per segment.

    Returns ``(row_ids, new_counts)``: ``row_ids`` is (n_seg * k,) int32
    global ids, per segment in descending count with ties to the lowest
    id (entries >= N are padding-segment picks and must be dropped by the
    caller); ``new_counts`` is (N,) with pending ids folded in and the
    selected rows' counters cleared.
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
    flat = jnp.asarray(indices, jnp.int32).reshape(-1)
    if flat.size:
        # a negative id would wrap to the table's end: send every id
        # outside [0, N) to N, which the scatter drops
        flat = jnp.where((flat >= 0) & (flat < N), flat, N)
        counts = counts.at[flat].add(1, mode="drop")
    s = _block_segments(n_seg, seg)
    n_seg_p = -(-n_seg // s) * s
    # padded rows get count -1 so any live row outranks them
    cgrid = jnp.pad(counts, (0, n_seg_p * seg - N),
                    constant_values=-1).reshape(n_seg_p, seg)

    ids, new_counts = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(n_seg_p // s,),
        in_specs=[pl.BlockSpec((s, seg), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((s, k), lambda i: (i, 0)),
            pl.BlockSpec((s, seg), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_seg_p, k), jnp.int32),
            jax.ShapeDtypeStruct((n_seg_p, seg), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="tracker_select",      # the kernel's name in traces and HLO
    )(cgrid)
    return ids[:n_seg].reshape(-1), new_counts.reshape(-1)[:N]


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
