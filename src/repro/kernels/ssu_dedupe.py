"""Pallas SSU dedupe + random-evict — the cpr-ssu tracker hot loop.

``trackers.ssu_update`` maintains a sorted, EMPTY-padded reservoir of
sampled row ids: every update drops candidates already present, merges
the rest, and on overflow keeps a uniform-random subset.  The merge /
membership / evict sequence is the per-step host round-trip ROADMAP
item 4 names; this kernel runs it as one fused Pallas body.

Division of labor: the caller keeps ``jnp.unique`` (data-dependent
shapes) and the PRNG draw — the keep-score vector comes IN as an
argument, so the randomness stream is identical between the host and
kernel backends and results match bit for bit (``ref.ssu_dedupe_evict``
is the exact-match oracle, stable argsort on both sides).

Single-block kernel over jnp sort/argsort primitives.  Pallas has no TPU
lowering for sort, so the kernel runs in interpret mode only and
``ops.ssu_dedupe_evict`` refuses to run it on a TPU; there the ``host``
tracker backend (XLA's own sort inside the train step) is the path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

EMPTY = np.int32(np.iinfo(np.int32).max)


def _kernel(buf_ref, cand_ref, score_ref, out_ref, *, rn: int):
    buf = buf_ref[:]
    cand = cand_ref[:]
    # membership: broadcast equality against the (sorted) reservoir —
    # exactly searchsorted presence, without the gather
    present = jnp.any(cand[:, None] == buf[None, :], axis=1)
    cand = jnp.where(present, EMPTY, cand)
    combined = jnp.sort(jnp.concatenate([buf, cand]))
    score = jnp.where(combined != EMPTY, score_ref[:], jnp.inf)
    keep = jnp.argsort(score, stable=True)[:rn]
    out_ref[:] = jnp.sort(combined[keep])


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssu_dedupe_evict(buf, cand, scores, interpret: bool = True):
    """Fused SSU reservoir update -> new (rn,) sorted int32 buffer.

    buf:    (rn,) int32 sorted ascending, EMPTY-padded.
    cand:   (nc,) int32 deduped candidates (EMPTY-padded).
    scores: (rn + nc,) float keep-scores for the sorted union (lower
            survives; the caller draws them so eviction randomness stays
            outside the kernel).
    """
    buf = jnp.asarray(buf, jnp.int32)
    cand = jnp.asarray(cand, jnp.int32)
    scores = jnp.asarray(scores)
    rn = buf.shape[0]
    return pl.pallas_call(
        functools.partial(_kernel, rn=rn),
        out_shape=jax.ShapeDtypeStruct((rn,), jnp.int32),
        interpret=interpret,
    )(buf, cand, scores)
