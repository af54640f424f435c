"""Plain DLRM-DCNv2 reference (MLPerf Training's recommendation model:
mlcommons/training ``recommendation_v2/torchrec_dlrm``; DCN V2, Wang et al.,
arXiv:2008.13535) for the benchmark.

Straightforward ``jax.numpy`` in float32, imported from nothing of the
program: bottom MLP (ReLU on every layer), sum-pooled multi-hot embedding
bags, ``x0`` the concatenation of the bottom output and the bags, low-rank
cross layers ``x_{l+1} = x0 * ((x_l V_l) W_l + b_l) + x_l`` (V d x r, W
r x d, no activation), top MLP over the last ``x`` (no final activation),
binary cross-entropy with logits, and row-wise Adagrad (one accumulator per
row of a 2-D parameter: the mean of its squared gradient; one per element of
a 1-D parameter).

Hotness: an int ``h`` takes ``sparse (B, F, h)``; one int per table takes
the packed ``sparse (B, sum h)``, table t in the ``h_t`` columns after the
tables before it.

``train(precision="bf16_3x")`` is the check's control: every matmul, the
cross layers' included, as the three bfloat16 passes of the TPU's ``high``
precision (``dlrm.mm3``).

The weights come from the seed: tables U(-1/sqrt(rows), 1/sqrt(rows)) keyed
``fold_in(k_emb, table)``, dense layers U(-1/sqrt(fan_in), 1/sqrt(fan_in))
with zero biases, from the three-way split of ``PRNGKey(seed)`` into
embedding, bottom and top keys; cross layer l's V and W from the two-way
split of ``fold_in(fold_in(PRNGKey(seed), 3), l)``, each U(-1/sqrt(fan_in),
1/sqrt(fan_in)), with a zero bias.  TorchRec initialises V and W
xavier-normal: a departure, noted in the configuration's ``assumed``.

The interface is ``references/dlrm.py``'s (its docstring lists it).  The
helpers that do not depend on the model (``mm3``, ``segment_topk``,
``shard_range``, ``row_grad_norms``, ``leaf_values``) are that module's,
loaded by path.
"""
from __future__ import annotations

import functools
import importlib.util
import os
from typing import NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np


def _load_dlrm():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "dlrm.py")
    spec = importlib.util.spec_from_file_location("bench_reference_dlrm_base",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_dlrm = _load_dlrm()
mm3 = _dlrm.mm3
segment_topk = _dlrm.segment_topk
shard_range = _dlrm.shard_range
row_grad_norms = _dlrm.row_grad_norms
leaf_values = _dlrm.leaf_values


class Arch(NamedTuple):
    num_dense: int
    table_sizes: Tuple[int, ...]
    emb_dim: int
    bottom_mlp: Tuple[int, ...]
    top_mlp: Tuple[int, ...]
    num_layers: int
    low_rank_dim: int
    multi_hot: Union[int, Tuple[int, ...]]

    @classmethod
    def of(cls, cfg):
        hot = cfg.get("multi_hot", 1)
        return cls(int(cfg["num_dense"]), tuple(cfg["table_sizes"]),
                   int(cfg["emb_dim"]), tuple(cfg["bottom_mlp"]),
                   tuple(cfg["top_mlp"]), int(cfg["dcn_num_layers"]),
                   int(cfg["dcn_low_rank_dim"]),
                   int(hot) if isinstance(hot, int) else tuple(hot))

    @property
    def cross_dim(self):
        return (len(self.table_sizes) + 1) * self.emb_dim


def _uniform(key, shape):
    s = 1.0 / np.sqrt(shape[0])
    return jax.random.uniform(key, shape, jnp.float32, -s, s)


def _mlp_init(key, sizes):
    out = []
    for a, b in zip(sizes[:-1], sizes[1:]):
        k1, _, key = jax.random.split(key, 3)
        out.append({"w": _uniform(k1, (a, b)),
                    "b": jnp.zeros((b,), jnp.float32)})
    return out


def init_params(arch: Arch, key):
    k_emb, k_bot, k_top = jax.random.split(key, 3)
    tables = []
    for i, n in enumerate(arch.table_sizes):
        s = 1.0 / jnp.sqrt(jnp.float32(n))
        tables.append(jax.random.uniform(jax.random.fold_in(k_emb, i),
                                         (n, arch.emb_dim), jnp.float32,
                                         -s, s))
    d, r = arch.cross_dim, arch.low_rank_dim
    k_cross = jax.random.fold_in(key, 3)
    cross = []
    for i in range(arch.num_layers):
        kv, kw = jax.random.split(jax.random.fold_in(k_cross, i))
        cross.append({"V": _uniform(kv, (d, r)), "W": _uniform(kw, (r, d)),
                      "b": jnp.zeros((d,), jnp.float32)})
    return {"tables": tables,
            "bottom": _mlp_init(k_bot, (arch.num_dense,) + arch.bottom_mlp),
            "top": _mlp_init(k_top, (d,) + arch.top_mlp),
            "cross": cross}


def bags(tables, sparse, hot=None):
    """Sum-pooled lookups, one (B, emb) per table: ``sparse`` (B, F, h), or
    packed (B, sum hot) with ``hot`` the per-table hotness."""
    if sparse.ndim == 3:
        return [jnp.sum(t[sparse[:, i, :]], axis=1)
                for i, t in enumerate(tables)]
    out, off = [], 0
    for t, h in zip(tables, hot):
        out.append(jnp.sum(t[sparse[:, off:off + h]], axis=1))
        off += h
    return out


def forward(params, dense, sparse, mm=jnp.matmul, preacts=None, hot=None):
    """Logits; ``preacts``, a list, receives each ReLU's input."""
    def relu(a):
        if preacts is not None:
            preacts.append(a)
        return jax.nn.relu(a)
    x = dense
    for layer in params["bottom"]:
        x = relu(mm(x, layer["w"]) + layer["b"])
    x0 = jnp.concatenate([x] + bags(params["tables"], sparse, hot), axis=-1)
    z = x0
    for layer in params["cross"]:
        z = x0 * (mm(mm(z, layer["V"]), layer["W"]) + layer["b"]) + z
    top = params["top"]
    for j, layer in enumerate(top):
        z = mm(z, layer["w"]) + layer["b"]
        if j < len(top) - 1:
            z = relu(z)
    return z[:, 0]


def loss_fn(params, batch, mm=jnp.matmul, hot=None):
    logits = forward(params, batch["dense"], batch["sparse"], mm, hot=hot)
    y = batch["label"]
    return jnp.mean(jnp.maximum(logits, 0) - logits * y +
                    jnp.log1p(jnp.exp(-jnp.abs(logits))))


def _sq_mean(g):
    if g.ndim >= 2:
        return jnp.mean(jnp.square(g), axis=tuple(range(1, g.ndim)))
    return jnp.square(g)


def adagrad_update(params, acc, batch, lr, eps, mm=jnp.matmul, hot=None):
    """One step -> (params, acc, loss, per-leaf gradient norms)."""
    loss, grads = jax.value_and_grad(loss_fn)(params, batch, mm, hot)
    acc = jax.tree.map(lambda a, g: a + _sq_mean(g), acc, grads)

    def upd(p, g, a):
        scale = jax.lax.rsqrt(a + eps)
        return p + (-lr * g) * scale.reshape(scale.shape + (1,) * (g.ndim - 1))

    params = jax.tree.map(upd, params, grads, acc)
    norms = jax.tree.map(lambda g: jnp.sqrt(jnp.sum(jnp.square(g))), grads)
    return params, acc, loss, norms


@functools.partial(jax.jit, static_argnames=("lr", "eps", "three_pass",
                                             "hot"),
                   donate_argnums=(0, 1))
def adagrad_step(params, acc, batch, lr=0.004, eps=1e-8, three_pass=False,
                 hot=None):
    return adagrad_update(params, acc, batch, lr, eps,
                          mm3 if three_pass else jnp.matmul, hot)


_init_jit = jax.jit(init_params, static_argnums=(0,))


@functools.partial(jax.jit, static_argnums=(0,))
def change_norms(arch: Arch, key, params):
    """Per-leaf ||params - init(key)||, the initial weights made anew."""
    p0 = init_params(arch, key)
    return jax.tree.map(lambda p, q: jnp.sqrt(jnp.sum(jnp.square(p - q))),
                        params, p0)


@jax.jit
def _take_rows(tables, rows):
    return [jnp.take(t, r, axis=0) for t, r in zip(tables, rows)]


def train(arch: Arch, seed, batches, precision="highest", lr=0.004,
          eps=1e-8, rows=None, first_rows=None, lap=None):
    """The first ``len(batches)`` steps from the seed's weights, as
    ``references/dlrm.py``'s ``train`` reads them."""
    lap = lap or (lambda name: None)
    key = jax.random.PRNGKey(seed)
    hot = None if isinstance(arch.multi_hot, int) else arch.multi_hot
    out = {}
    three_pass = precision == "bf16_3x"
    with jax.default_matmul_precision("highest" if three_pass else precision):
        params = _init_jit(arch, key)
        jax.block_until_ready(params)
        lap("ref_init")
        if rows is not None:
            out["init_rows"] = [np.asarray(x) for x in _take_rows(
                params["tables"], [jnp.asarray(r, jnp.int32) for r in rows])]
            lap("ref_init_rows")
        acc = jax.tree.map(lambda p: jnp.zeros(p.shape[:1] if p.ndim >= 2
                                               else p.shape, jnp.float32),
                           params)
        losses, first = [], None
        for b in batches:
            params, acc, loss, norms = adagrad_step(
                params, acc, {k: jnp.asarray(v) for k, v in b.items()},
                lr=lr, eps=eps, three_pass=three_pass, hot=hot)
            losses.append(float(loss))
            if first is None:
                first = leaf_values(norms)
                if first_rows is not None:
                    out["row_norms"] = [np.asarray(x) for x in row_grad_norms(
                        acc, params, [jnp.asarray(r, jnp.int32)
                                      for r in first_rows])]
        lap("ref_steps")
        change = leaf_values(change_norms(arch, key, params))
        lap("ref_change")
    del params, acc
    return {"losses": losses, "grad_norms": first, "change_norms": change,
            **out}


def _dense_params(arch: Arch):
    d, r = arch.cross_dim, arch.low_rank_dim
    n = 0
    for sizes in ((arch.num_dense,) + arch.bottom_mlp, (d,) + arch.top_mlp):
        n += sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))
    return n + arch.num_layers * (d * r + r * d + d)


def full_save_bytes(arch: Arch):
    """Bytes of one full save: every table and its row accumulators in
    float32, and the dense layers, the cross layers' included (the trainer
    replica)."""
    n = sum(arch.table_sizes)
    return 4 * (n * arch.emb_dim + n + _dense_params(arch))


# ------------------------------------------------------------ operations ----
def forward_flops_per_sample(cfg):
    """Bottom MLP, the cross layers (two rank-r products a layer) and the top
    MLP, 2 operations a multiply-add.  Embedding sums and elementwise work
    are not counted."""
    d = (len(cfg["table_sizes"]) + 1) * cfg["emb_dim"]
    bottom = _dlrm.mlp_flops([cfg["num_dense"]] + list(cfg["bottom_mlp"]))
    top = _dlrm.mlp_flops([d] + list(cfg["top_mlp"]))
    cross = cfg["dcn_num_layers"] * 2 * (2 * d * cfg["dcn_low_rank_dim"])
    return bottom + cross + top


def train_flops_per_sample(cfg):
    """Forward plus backward (twice the forward) model operations."""
    return 3 * forward_flops_per_sample(cfg)
