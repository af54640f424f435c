"""DLRM (Naumov et al., arXiv:1906.00091) — the paper's workload.

Dense features -> bottom MLP; sparse categorical features -> embedding-bag
lookups (sum pooling); feature interaction; top MLP -> CTR logit.  The
interaction is DLRM's pairwise dot product, or DCN V2's low-rank cross
layers (Wang et al., arXiv:2008.13535; MLPerf's DLRM-DCNv2) over the
concatenation of the bottom output and the pooled bags:
``x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l``.  Embedding tables are the
Emb-PS state CPR partially recovers; they are sharded over the "model" mesh
axis on the row dimension, exactly mirroring the paper's Emb PS row-range
partitioning.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Tuple, Union

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init

Array = jax.Array


@dataclass(frozen=True)
class DLRMConfig:
    name: str
    num_dense: int                       # continuous features (13 for Criteo)
    table_sizes: Tuple[int, ...]         # rows per sparse table (26 tables)
    emb_dim: int                         # embedding vector dim
    bottom_mlp: Tuple[int, ...]          # hidden sizes incl. output (= emb_dim)
    top_mlp: Tuple[int, ...]             # hidden sizes, final = 1
    # lookups per table per sample: one int for every table (a batch's
    # ``sparse`` is (B, F, h)), or one per table (``sparse`` (B, sum h),
    # table t in the columns ``hot_offsets[t]`` on, see ``table_ids``)
    multi_hot: Union[int, Tuple[int, ...]] = 1
    source: str = ""
    interaction: str = "dot"             # "dot" (pairwise) | "dcn" (cross)
    dcn_num_layers: int = 0              # low-rank cross layers ("dcn")
    dcn_low_rank_dim: int = 0            # their rank r: V is d x r, W r x d

    def __post_init__(self):
        if self.interaction not in ("dot", "dcn"):
            raise ValueError(f"interaction {self.interaction!r}: "
                             f"'dot' or 'dcn'")
        if not isinstance(self.multi_hot, int) and \
                len(self.multi_hot) != self.num_sparse:
            raise ValueError(f"multi_hot gives {len(self.multi_hot)} "
                             f"hotnesses for {self.num_sparse} tables")

    @property
    def num_sparse(self) -> int:
        return len(self.table_sizes)

    @property
    def interaction_dim(self) -> int:
        f = self.num_sparse + 1
        if self.interaction == "dcn":
            return f * self.emb_dim
        return f * (f - 1) // 2 + self.emb_dim

    @property
    def hot_offsets(self) -> Tuple[int, ...]:
        """First column of each table in a packed per-table ``sparse``."""
        out, off = [], 0
        for h in self.multi_hot:
            out.append(off)
            off += h
        return tuple(out)

    def total_emb_rows(self) -> int:
        return sum(self.table_sizes)


def init_mlp_stack(key, sizes):
    ws = []
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        k1, k2, key = jax.random.split(key, 3)
        ws.append({"w": dense_init(k1, (a, b)), "b": jnp.zeros((b,), jnp.float32)})
    return ws


def apply_mlp_stack(ws, x, final_act=True):
    for i, p in enumerate(ws):
        x = x @ p["w"] + p["b"]
        if i < len(ws) - 1 or final_act:
            x = jax.nn.relu(x)
    return x


def init_cross(key, d, r, n_layers):
    """Low-rank cross layers: V (d, r) and W (r, d) as ``dense_init`` makes
    them, a zero bias (d,); layer l keyed ``fold_in(key, l)``."""
    layers = []
    for i in range(n_layers):
        kv, kw = jax.random.split(jax.random.fold_in(key, i))
        layers.append({"V": dense_init(kv, (d, r)), "W": dense_init(kw, (r, d)),
                       "b": jnp.zeros((d,), jnp.float32)})
    return layers


def apply_cross(layers, x0):
    """x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l, no activation."""
    x = x0
    for p in layers:
        x = x0 * ((x @ p["V"]) @ p["W"] + p["b"]) + x
    return x


def init_dlrm(cfg: DLRMConfig, key) -> dict:
    k_emb, k_bot, k_top = jax.random.split(key, 3)
    tables = []
    for i, n in enumerate(cfg.table_sizes):
        ki = jax.random.fold_in(k_emb, i)
        scale = 1.0 / jnp.sqrt(jnp.float32(n))
        tables.append(jax.random.uniform(ki, (n, cfg.emb_dim), jnp.float32,
                                         -scale, scale))
    params = {
        "tables": tables,
        "bottom": init_mlp_stack(k_bot, (cfg.num_dense,) + cfg.bottom_mlp),
        "top": init_mlp_stack(k_top, (cfg.interaction_dim,) + cfg.top_mlp),
    }
    if cfg.interaction == "dcn":
        params["cross"] = init_cross(jax.random.fold_in(key, 3),
                                     cfg.interaction_dim,
                                     cfg.dcn_low_rank_dim, cfg.dcn_num_layers)
    return params


def trainer_state(params) -> dict:
    """The trainer replica: every parameter but the embedding tables."""
    return {k: v for k, v in params.items() if k != "tables"}


def table_ids(cfg: DLRMConfig, sparse: Array, t: int) -> Array:
    """Table ``t``'s ids of a batch -> (B, h_t)."""
    if isinstance(cfg.multi_hot, int):
        return sparse[:, t, :]
    off = cfg.hot_offsets[t]
    return sparse[:, off:off + cfg.multi_hot[t]]


def embedding_bag(table: Array, idx: Array, use_kernel: bool = False) -> Array:
    """Sum-pooled lookup.  idx: (B, h) -> (B, emb_dim)."""
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.embedding_bag(table, idx)
    return jnp.sum(table[idx], axis=1)


def dlrm_forward(params, batch, cfg: DLRMConfig, use_kernel=False) -> Array:
    """batch: dense (B, num_dense) f32; sparse i32, (B, num_sparse, h) or
    packed (B, sum h) (``table_ids``).  Returns CTR logits (B,)."""
    dense_out = apply_mlp_stack(params["bottom"], batch["dense"])  # (B, emb)
    with jax.named_scope("embedding"):
        embs = [embedding_bag(t, table_ids(cfg, batch["sparse"], i),
                              use_kernel)
                for i, t in enumerate(params["tables"])]
    with jax.named_scope("interaction"):
        if cfg.interaction == "dcn":
            z = apply_cross(params["cross"],
                            jnp.concatenate([dense_out] + embs, axis=-1))
        else:
            feats = jnp.stack([dense_out] + embs, axis=1)          # (B, F, emb)
            inter = jnp.einsum("bfe,bge->bfg", feats, feats)
            f = feats.shape[1]
            iu, ju = jnp.triu_indices(f, k=1)
            pairwise = inter[:, iu, ju]                            # (B, F(F-1)/2)
            z = jnp.concatenate([dense_out, pairwise], axis=-1)
    return apply_mlp_stack(params["top"], z, final_act=False)[:, 0]


def dlrm_loss(params, batch, cfg: DLRMConfig, use_kernel=False):
    logits = dlrm_forward(params, batch, cfg, use_kernel)
    y = batch["label"].astype(jnp.float32)
    # numerically-stable BCE with logits
    loss = jnp.mean(jnp.maximum(logits, 0) - logits * y +
                    jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return loss, logits


# Paper §5.1 configurations (MLPerf DLRM reference hyperparameters).
DLRM_KAGGLE = DLRMConfig(
    name="dlrm-kaggle",
    num_dense=13,
    table_sizes=tuple(),   # filled by dataset (Criteo Kaggle cardinalities)
    emb_dim=16,            # 64-byte fp32 vectors
    bottom_mlp=(512, 256, 64, 16),
    top_mlp=(512, 256, 1),
    source="MLPerf DLRM reference / arXiv:1906.00091, Kaggle hyperparams",
)

DLRM_TERABYTE = DLRMConfig(
    name="dlrm-terabyte",
    num_dense=13,
    table_sizes=tuple(),
    emb_dim=64,            # 256-byte fp32 vectors
    bottom_mlp=(512, 256, 64),
    top_mlp=(512, 512, 256, 1),
    source="MLPerf DLRM reference / arXiv:1906.00091, Terabyte hyperparams",
)

# MLPerf Training's DLRM-DCNv2 (recommendation_v2/torchrec_dlrm; DCN V2,
# arXiv:2008.13535): three rank-512 cross layers over the 27 x 128 features.
DLRM_DCNV2 = DLRMConfig(
    name="dlrm-dcnv2",
    num_dense=13,
    table_sizes=tuple(),   # filled by dataset (Criteo 1TB, capped at 40M)
    emb_dim=128,           # 512-byte fp32 vectors
    bottom_mlp=(512, 256, 128),
    top_mlp=(1024, 1024, 512, 256, 1),
    interaction="dcn",
    dcn_num_layers=3,
    dcn_low_rank_dim=512,
    source="MLPerf DLRM-DCNv2 (mlcommons/training recommendation_v2) / "
           "arXiv:2008.13535",
)
