"""Pallas-TPU blocked scan for the RG-LRU linear recurrence
h_t = a_t * h_{t-1} + b_t.

TPU adaptation: the time axis is blocked; the carry h lives in VMEM scratch
across sequential time blocks (grid dim marked "arbitrary"), and within a
block the recurrence closes with an associative scan over VREG data — a
log-depth composition instead of the GPU warp-shuffle prefix tricks.
Channels and batch are embarrassingly parallel grid dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(a_ref, b_ref, o_ref, h_ref, *, bs):
    t = pl.program_id(2)   # time is the innermost (sequential) grid dim

    @pl.when(t == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)       # (bs, bw)
    b = b_ref[0].astype(jnp.float32)

    def combine(x, y):
        ax, bx = x
        ay, by = y
        return ax * ay, ay * bx + by

    A, B = jax.lax.associative_scan(combine, (a, b), axis=0)
    h = A * h_ref[...] + B                  # close the recurrence with carry
    o_ref[0] = h.astype(o_ref.dtype)
    h_ref[...] = h[-1:]


@functools.partial(jax.jit, static_argnames=("block_s", "block_w",
                                             "interpret"))
def rglru_scan(a, b, block_s: int = 256, block_w: int = 512,
               interpret: bool = True):
    """a, b: (B, S, w) -> h: (B, S, w)."""
    B, S, w = a.shape
    bs = min(block_s, S)
    bw = min(block_w, w)
    assert S % bs == 0 and w % bw == 0
    grid = (B, w // bw, S // bs)   # time innermost: h carries across t
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda bb, c, t: (bb, t, c)),
            pl.BlockSpec((1, bs, bw), lambda bb, c, t: (bb, t, c)),
        ],
        out_specs=pl.BlockSpec((1, bs, bw), lambda bb, c, t: (bb, t, c)),
        out_shape=jax.ShapeDtypeStruct((B, S, w), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                             "arbitrary")),
    )(a, b)
