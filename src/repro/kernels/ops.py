"""Public jit'd wrappers for the Pallas kernels.

Off the TPU the kernels execute in ``interpret=True`` mode (the kernel
body runs as traced JAX ops — bit-accurate semantics, no Mosaic); on a
TPU the same calls compile through Mosaic.  ``_interpret()`` is the one
switch.  Kernels Mosaic refuses (``NO_TPU_LOWERING``) raise on a TPU
instead of falling back to the interpreter, and constructors call
``require_lowering`` to refuse such a backend choice up front.
``flash_attention`` adapts the model-layer layout (B, S, H, hd) to the
kernel layout (B, H, S, hd).
"""
from __future__ import annotations

import jax

from repro.kernels import embedding_bag as _eb
from repro.kernels import flash_attention as _fa
from repro.kernels import rglru_scan as _rg
from repro.kernels import row_hash as _rh
from repro.kernels import ssu_dedupe as _sd
from repro.kernels import tracker_select as _ts


# kernel -> why the TPU's compiler refuses it
NO_TPU_LOWERING = {
    "embedding_bag": "its (1, d) row block breaks the TPU tiling rule",
    "row_hash": "its FNV state needs uint64 lanes, which the TPU lacks",
    "ssu_dedupe": "Pallas TPU cannot lower sort",
}


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def require_lowering(kernel: str) -> None:
    """Raise if ``kernel`` cannot run on this backend without the
    interpreter (i.e. it is in ``NO_TPU_LOWERING`` and this is a TPU)."""
    why = NO_TPU_LOWERING.get(kernel)
    if why is not None and not _interpret():
        raise NotImplementedError(
            f"the Pallas {kernel} kernel cannot run on a TPU: {why}; "
            f"choose the host/jnp path")


def embedding_bag(table, idx, block_d: int = 512):
    require_lowering("embedding_bag")
    return _eb.embedding_bag(table, idx, block_d=block_d,
                             interpret=_interpret())


def flash_attention(q, k, v, causal=True, window=0, softcap=0.0,
                    block_q=128, block_k=128):
    """Layer layout: q (B, Sq, Hq, hd), k/v (B, Skv, Hkv, hd)."""
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                              softcap=softcap, block_q=block_q,
                              block_k=block_k, interpret=_interpret())
    return out.swapaxes(1, 2)


def rglru_scan(a, b, block_s: int = 256, block_w: int = 512):
    return _rg.rglru_scan(a, b, block_s=block_s, block_w=block_w,
                          interpret=_interpret())


def tracker_select(counts, indices, k: int, seg_size: int = 512):
    """Fused MFU count-update + segment-wise top-k row selection."""
    return _ts.tracker_select(counts, indices, k, seg_size=seg_size,
                              interpret=_interpret())


def autotune_seg_size(n_rows: int, k: int, **kw) -> int:
    """Measured lane-aligned ``seg_size`` choice for ``tracker_select``."""
    return _ts.autotune_seg_size(n_rows, k, interpret=_interpret(), **kw)


def row_hash(values, acc_values) -> "np.ndarray":
    """FNV-1a per-row delta-save hash -> (n,) uint64 numpy array."""
    require_lowering("row_hash")
    return _rh.row_hash(values, acc_values, interpret=_interpret())


def ssu_dedupe_evict(buf, cand, scores):
    """Fused SSU reservoir dedupe + random-evict (sorted int32 buffer)."""
    require_lowering("ssu_dedupe")
    return _sd.ssu_dedupe_evict(buf, cand, scores,
                                interpret=_interpret())
