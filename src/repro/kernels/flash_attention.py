"""Pallas-TPU flash attention (forward) with causal / sliding-window masks,
GQA via BlockSpec index-mapping (no KV head expansion), and gemma2-style
attention-logit softcap.

Grid: (B, Hq, Sq/bq, Skv/bk) — the KV dimension is innermost ("arbitrary"
semantics); running (m, l, acc) live in VMEM scratch across KV steps and the
output block is finalized on the last KV step.  KV blocks entirely outside
the causal/window mask are skipped via ``pl.when`` (the DMA still happens —
a production variant would clamp the index_map; see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, softcap, bq, bk, skv, sq):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nkv = pl.num_programs(3)

    @pl.when(kj == 0)
    def _reset():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions (queries right-aligned to the KV tail)
    q_pos = qi * bq + jax.lax.iota(jnp.int32, bq) + (skv - sq)
    k_pos = kj * bk + jax.lax.iota(jnp.int32, bk)
    run = True
    if causal:
        run = jnp.max(q_pos) >= jnp.min(k_pos)
    if window:
        run = jnp.logical_and(
            run, jnp.min(q_pos) - jnp.max(k_pos) < window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, hd)
        s = q @ k.T                                     # (bq, bk)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + p @ v_ref[0, 0].astype(jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == nkv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, causal=True, window=0, softcap=0.0,
                    block_q=128, block_k=128, interpret: bool = True):
    """q: (B, Hq, Sq, hd); k/v: (B, Hkv, Skv, hd) -> (B, Hq, Sq, hd)."""
    B, Hq, Sq, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    grid = (B, Hq, Sq // bq, Skv // bk)
    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(hd), causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, skv=Skv, sq=Sq)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            # GQA: kv head = h // g, mapped in the BlockSpec (no expansion)
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum l
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                             "parallel", "arbitrary")),
    )(q, k, v)
