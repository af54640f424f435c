"""Pallas-TPU embedding-bag kernel: fused gather + sum-pool.

The Emb-PS hot spot of DLRM training.  TPU adaptation of the CPU/GPU
gather: lookup indices are *scalar-prefetched* (SMEM) so each grid step's
BlockSpec index_map selects the table row to DMA into VMEM — the gather
never materializes (B, hot, d); rows stream HBM->VMEM and accumulate into
the output block.

Grid: (B, hot, d_blocks); output block (1, bd) revisited across the ``hot``
dimension with accumulate-or-init (standard TPU reduction pattern).

Interpret mode only: Mosaic refuses the (1, bd) row block (the last two
block dims must be (8k, 128k) or the array's own).  Squeezing the row
axis of an (N, 1, d) view does compile, but at d=16 it relays the table
out with every row padded to 128 lanes (5.2 GB of temporaries for one
10M-row Kaggle table), so ``ops.embedding_bag`` refuses to run on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(idx_ref, table_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = table_ref[...]

    @pl.when(j > 0)
    def _acc():
        out_ref[...] += table_ref[...]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def embedding_bag(table, idx, block_d: int = 512, interpret: bool = True):
    """table: (N, d) f32; idx: (B, hot) i32 -> (B, d)."""
    N, d = table.shape
    B, hot = idx.shape
    bd = min(block_d, d)
    assert d % bd == 0, (d, bd)
    grid = (B, hot, d // bd)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # table block = one embedding row slab, chosen by the
                # prefetched index for (b, j)
                pl.BlockSpec((1, bd), lambda b, j, dblk, idx: (idx[b, j], dblk)),
            ],
            out_specs=pl.BlockSpec((1, bd), lambda b, j, dblk, idx: (b, dblk)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, d), table.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary",
                                             "parallel")),
    )(idx, table)
    return out
