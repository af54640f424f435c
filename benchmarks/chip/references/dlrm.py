"""Plain DLRM reference (Naumov et al., arXiv:1906.00091) for the benchmark.

Straightforward ``jax.numpy`` in float32, imported from nothing of the
program: bottom MLP (ReLU on every layer), one sum-pooled embedding lookup
per table, the pairwise dot-product interaction over the 27 features (upper
triangle, no diagonal) concatenated to the bottom output, top MLP (no final
activation), binary cross-entropy with logits, and row-wise Adagrad (one
accumulator per row of a 2-D parameter: the mean of its squared gradient;
one per element of a 1-D parameter).

``train(precision="bf16_3x")`` is the check's control: every matmul, in the
forward and the backward, as the three bfloat16 passes of the TPU's
``high`` precision (hi*hi + hi*lo + lo*hi, float32 sums, each part rounded
to bfloat16 on its bits), written out so that the CPU runs the same
arithmetic.

The weights come from the seed as the published model initialises them:
tables U(-1/sqrt(rows), 1/sqrt(rows)) keyed ``fold_in(k_emb, table)``,
dense layers U(-1/sqrt(fan_in), 1/sqrt(fan_in)) with zero biases, from the
three-way split of ``PRNGKey(seed)`` into embedding, bottom and top keys.

Beside it: the MFU count, segment-wise top-k and SSU set that a priority
save must select, the row ranges of the Emb-PS shards, the bytes of a full
save, and the model operations of a training sample.

The harness finds this module by a configuration's ``arch`` and calls, on
any reference module:

- ``Arch.of(cfg)``: the hashable model description from a configuration;
- ``init_params(arch, key)``: the weights from the seed;
- ``train(arch, seed, batches, precision, lr, eps, rows, first_rows, lap)``:
  the reference's first steps and readings (``checks.training_gaps``);
- ``change_norms(arch, key, params)``: per-leaf change from the initial
  weights, of the program's parameters;
- ``row_grad_norms(acc, params, rows)``: first-gradient row norms from a
  row-wise Adagrad state;
- ``segment_topk(counts, k, seg)`` and ``shard_range(n, shard, n_shards)``:
  what a priority save selects and a failed shard covers;
- ``full_save_bytes(arch)``: the bytes of one full save;
- ``train_flops_per_sample(cfg)``: model operations of one training sample
  (``train_mfu_pct``); a module without it leaves that metric silent.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Arch(NamedTuple):
    num_dense: int
    table_sizes: Tuple[int, ...]
    emb_dim: int
    bottom_mlp: Tuple[int, ...]
    top_mlp: Tuple[int, ...]

    @classmethod
    def of(cls, cfg):
        return cls(int(cfg["num_dense"]), tuple(cfg["table_sizes"]),
                   int(cfg["emb_dim"]), tuple(cfg["bottom_mlp"]),
                   tuple(cfg["top_mlp"]))


def _mlp_init(key, sizes):
    out = []
    for a, b in zip(sizes[:-1], sizes[1:]):
        k1, _, key = jax.random.split(key, 3)
        s = 1.0 / np.sqrt(a)
        out.append({"w": jax.random.uniform(k1, (a, b), jnp.float32, -s, s),
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
    F = len(arch.table_sizes) + 1
    inter = F * (F - 1) // 2 + arch.emb_dim
    return {"tables": tables,
            "bottom": _mlp_init(k_bot, (arch.num_dense,) + arch.bottom_mlp),
            "top": _mlp_init(k_top, (inter,) + arch.top_mlp)}


def _top16(x):
    """``x`` (float32, finite) rounded to the nearest bfloat16, ties to even,
    on its bits: a round trip through bfloat16 the compiler may fold away
    as excess precision, which leaves the low part at zero (one pass, not
    three)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _split(x):
    hi = _top16(x)
    return hi, _top16(x - hi)


@jax.custom_vjp
def mm3(a, b):
    """``a @ b`` (batched over leading axes) in three bfloat16 passes."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    dot = functools.partial(jnp.matmul, precision="highest")
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def _mm3_fwd(a, b):
    return mm3(a, b), (a, b)


def _mm3_bwd(res, g):
    a, b = res
    return mm3(g, jnp.swapaxes(b, -1, -2)), mm3(jnp.swapaxes(a, -1, -2), g)


mm3.defvjp(_mm3_fwd, _mm3_bwd)


def forward(params, dense, sparse, mm=jnp.matmul, preacts=None):
    """Logits; ``preacts``, a list, receives each ReLU's input."""
    def relu(a):
        if preacts is not None:
            preacts.append(a)
        return jax.nn.relu(a)
    x = dense
    for layer in params["bottom"]:
        x = relu(mm(x, layer["w"]) + layer["b"])
    embs = [jnp.sum(t[sparse[:, i, :]], axis=1)
            for i, t in enumerate(params["tables"])]
    feats = jnp.stack([x] + embs, axis=1)
    inter = mm(feats, jnp.swapaxes(feats, 1, 2))
    iu, ju = np.triu_indices(feats.shape[1], k=1)
    z = jnp.concatenate([x, inter[:, iu, ju]], axis=-1)
    top = params["top"]
    for j, layer in enumerate(top):
        z = mm(z, layer["w"]) + layer["b"]
        if j < len(top) - 1:
            z = relu(z)
    return z[:, 0]


def loss_fn(params, batch, mm=jnp.matmul):
    logits = forward(params, batch["dense"], batch["sparse"], mm)
    y = batch["label"]
    return jnp.mean(jnp.maximum(logits, 0) - logits * y +
                    jnp.log1p(jnp.exp(-jnp.abs(logits))))


def _sq_mean(g):
    if g.ndim >= 2:
        return jnp.mean(jnp.square(g), axis=tuple(range(1, g.ndim)))
    return jnp.square(g)


def adagrad_update(params, acc, batch, lr=0.02, eps=1e-8, mm=jnp.matmul):
    """One step -> (params, acc, loss, per-leaf gradient norms)."""
    loss, grads = jax.value_and_grad(loss_fn)(params, batch, mm)
    acc = jax.tree.map(lambda a, g: a + _sq_mean(g), acc, grads)

    def upd(p, g, a):
        scale = jax.lax.rsqrt(a + eps)
        return p + (-lr * g) * scale.reshape(scale.shape + (1,) * (g.ndim - 1))

    params = jax.tree.map(upd, params, grads, acc)
    norms = jax.tree.map(lambda g: jnp.sqrt(jnp.sum(jnp.square(g))), grads)
    return params, acc, loss, norms


@functools.partial(jax.jit, static_argnames=("lr", "eps", "three_pass"),
                   donate_argnums=(0, 1))
def adagrad_step(params, acc, batch, lr=0.02, eps=1e-8, three_pass=False):
    return adagrad_update(params, acc, batch, lr, eps,
                          mm3 if three_pass else jnp.matmul)


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


@jax.jit
def row_grad_norms(acc, params, rows):
    """Per-row first-gradient norms of the tables at ``rows`` (one id array
    per table), from a row-wise Adagrad state after one step: the row's
    accumulator is the mean of its squared gradient."""
    return [jnp.sqrt(jnp.take(a, r) * t.shape[1])
            for a, t, r in zip(acc["tables"], params["tables"], rows)]


def train(arch: Arch, seed, batches, precision="highest", lr=0.02, eps=1e-8,
          rows=None, first_rows=None, lap=None):
    """The first ``len(batches)`` steps from the seed's weights -> losses,
    per-leaf gradient norms of the first step, per-leaf change norms after
    the last step (all host numbers, leaves keyed by tree path), the
    initial values of ``rows`` and the first gradient's row norms at
    ``first_rows`` (one id array per table each), if given.  ``precision``
    is a matmul precision, or ``bf16_3x`` for the control."""
    lap = lap or (lambda name: None)
    key = jax.random.PRNGKey(seed)
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
                lr=lr, eps=eps, three_pass=three_pass)
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


def leaf_values(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


# ---------------------------------------------------- save and restore ----
def shard_range(n, shard, n_shards):
    """Row range of one Emb-PS shard: the paper's even split floor(j*n/N)."""
    return (n * shard) // n_shards, (n * (shard + 1)) // n_shards


def segment_topk(counts, k, seg):
    """Segment-wise top-k: per ``seg``-row segment, the ``k`` rows of highest
    count, ties to the lower row id; rows past the table end never win.
    Sorted row ids."""
    n = counts.shape[0]
    n_seg = -(-n // seg)
    c = np.full(n_seg * seg, -1, np.int64)
    c[:n] = counts
    c = c.reshape(n_seg, seg)
    # a segment of zero counts picks its first k rows; only the others sort
    ids = np.arange(n_seg, dtype=np.int64)[:, None] * seg + np.arange(k)
    hot = np.nonzero((c > 0).any(axis=1) | (c[:, :k] < 0).any(axis=1))[0]
    if hot.size:
        col = np.arange(seg, dtype=np.int64)
        key = c[hot] * (2 * seg) + (seg - 1 - col)
        part = np.argpartition(-key, k - 1, axis=1)[:, :k]
        ids[hot] = hot[:, None] * seg + part
    ids = ids.ravel()
    return np.sort(ids[ids < n])


def full_save_bytes(arch: Arch):
    """Bytes of one full save: every table and its row accumulators in
    float32, and the dense layers (the trainer replica)."""
    n = sum(arch.table_sizes)
    dense = 0
    F = len(arch.table_sizes) + 1
    for sizes in ((arch.num_dense,) + arch.bottom_mlp,
                  (F * (F - 1) // 2 + arch.emb_dim,) + arch.top_mlp):
        dense += sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))
    return 4 * (n * arch.emb_dim + n + dense)


# ------------------------------------------------------------ operations ----
def mlp_flops(sizes):
    """Forward multiply-adds of a dense stack, counted as 2 operations each."""
    return sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))


def forward_flops_per_sample(cfg):
    """Bottom MLP, the pairwise dot-product interaction (the whole F x F
    batched product, as it is computed) and the top MLP.  Embedding sums
    and elementwise work are not counted."""
    F = len(cfg["table_sizes"]) + 1
    inter_dim = F * (F - 1) // 2 + cfg["emb_dim"]
    bottom = mlp_flops([cfg["num_dense"]] + list(cfg["bottom_mlp"]))
    top = mlp_flops([inter_dim] + list(cfg["top_mlp"]))
    return bottom + top + 2 * F * F * cfg["emb_dim"]


def train_flops_per_sample(cfg):
    """Forward plus backward (twice the forward) model operations."""
    return 3 * forward_flops_per_sample(cfg)
