"""Pallas FNV-1a per-row hash — the delta-save changed-row detector.

``ShardedCheckpointWriter.save_rows`` ships only rows whose FNV-1a hash
changed since the last save; at fleet scale that hash is pure memory
bandwidth over every touched row (values + optimizer accumulators), and
the host numpy loop serializes word columns on the CPU.  This kernel
moves the word loop into Pallas: rows are blocked over the grid, each
step folds its block's ``m`` 64-bit words with the classic
``h = (h ^ w) * FNV_PRIME`` recurrence.

Staging stays on host (``ref.rows_to_words``): the raw row bytes are
zero-padded to 8-byte alignment and viewed as uint64 words — the same
preprocessing the numpy implementation does, so the kernel is bit-exact
against ``ref.row_hash`` and ``sharded_checkpoint.row_hash`` for every
dtype and row width, including zero-row and zero-column slices.

The kernel runs under a scoped ``jax.enable_x64(True)`` (uint64 lanes;
the global default stays 32-bit so nothing else in the process changes
dtype).  It runs in interpret mode only: a Mosaic lowering needs the
64-bit state split into 32-bit limbs (TPU has no 64-bit int lanes), so
``ops.row_hash`` refuses to run it on a TPU.  The interpret path is the
bit-exactness contract any limb split must keep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

FNV_OFFSET = np.uint64(14695981039346656037)
FNV_PRIME = np.uint64(1099511628211)


def _fnv_kernel(w_ref, out_ref, *, m: int):
    h = jnp.full(out_ref.shape, FNV_OFFSET, jnp.uint64)

    def body(i, h):
        return (h ^ w_ref[:, i]) * FNV_PRIME

    out_ref[:] = jax.lax.fori_loop(0, m, body, h)


def row_hash(values, acc_values, block_rows: int = 1024,
             interpret: bool = True) -> np.ndarray:
    """FNV-1a over each row's (values, accs) bytes -> (n,) uint64.

    Exact-match target: ``ref.row_hash``.  Zero rows return an empty
    array; zero-byte rows hash to the FNV offset basis (both without
    entering the kernel)."""
    values = np.asarray(values)
    n = values.shape[0]
    if n == 0:
        return np.full(0, FNV_OFFSET, np.uint64)
    w = _ref.rows_to_words(values, acc_values)
    m = w.shape[1]
    if m == 0:
        return np.full(n, FNV_OFFSET, np.uint64)
    bn = min(int(block_rows), n)
    n_blk = -(-n // bn)                   # ceil
    padded = n_blk * bn
    if padded != n:                       # padding rows hash and are cut
        w = np.pad(w, ((0, padded - n), (0, 0)))
    with jax.enable_x64(True):
        out = pl.pallas_call(
            functools.partial(_fnv_kernel, m=m),
            grid=(n_blk,),
            in_specs=[pl.BlockSpec((bn, m), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((padded,), jnp.uint64),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
        )(jnp.asarray(w))
        # np.array, not asarray: the zero-copy view of the device buffer
        # is read-only, and callers mutate the result in place (the
        # delta-save hash ledger advances row by row)
        res = np.array(out[:n], dtype=np.uint64)
    return res
