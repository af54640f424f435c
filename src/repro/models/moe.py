"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Scalable dispatch (no (T, E, C) one-hot tensors): tokens are flattened,
assignments sorted by expert id, scattered into an (E, C, d) buffer that is
expert-sharded over the "model" mesh axis (expert parallelism), and gathered
back with router weights.  Tokens beyond an expert's capacity are dropped
(standard capacity-factor semantics); a router aux loss balances load.

Supports Qwen-style shared experts computed densely alongside routed ones.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, init_mlp, apply_mlp
from repro.sharding.ctx import constrain


def init_moe(key, d_model, moe_cfg):
    m = moe_cfg
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d_model, m.num_experts)),
        # experts stacked on axis 0 -> shardable over "model"
        "w_gate": dense_init(ks[1], (m.num_experts, d_model, m.d_expert), in_axis=1),
        "w_up": dense_init(ks[2], (m.num_experts, d_model, m.d_expert), in_axis=1),
        "w_down": dense_init(ks[3], (m.num_experts, m.d_expert, d_model), in_axis=1),
    }
    if m.num_shared_experts:
        p["shared"] = init_mlp(ks[4], d_model, m.d_shared, act="silu")
        p["shared_gate"] = dense_init(ks[4], (d_model, 1))
    return p


def apply_moe_shard_map(p, x, moe_cfg, policy, capacity=None):
    """Expert-parallel MoE via shard_map: per-device sort-based dispatch,
    all_to_all into expert shards, local expert GEMMs, all_to_all back.

    Avoids the XLA SPMD auto-partitioning failure mode where the global
    (T·k, d) dispatch scatter all-gathers a broadcast index matrix (observed:
    a 64 GiB u32[8.4M, 2048] all-gather for qwen3-moe train_4k).  Inside
    shard_map every gather/scatter is shard-local; only the (E, C_loc, d)
    dispatch buffers cross the ICI, which is the theoretical minimum.
    """
    import jax.sharding as jsh
    P = jsh.PartitionSpec
    m = moe_cfg
    B, S, d = x.shape
    T = B * S
    mesh = policy["mesh"]
    dp = policy["dp"]
    dps, tps = policy["dp_size"], policy["tp_size"]
    ep = m.num_experts % tps == 0
    if not ep or T % dps != 0:
        return apply_moe(p, x, moe_cfg, capacity)   # SPMD fallback
    E_loc = m.num_experts // tps
    # token-shard over (data × model) jointly when divisible: the MoE input
    # is model-axis-replicated, and a dp-only dispatch would make all tp
    # columns redundantly dispatch/compute the SAME tokens (§Perf pair 1,
    # iteration 1: 16x wasted expert+router compute)
    two_d = T % (dps * tps) == 0
    tok_spec = (dp + ("model",)) if two_d else dp
    T_loc = T // (dps * tps) if two_d else T // dps
    if capacity is None:
        if S == 1:
            C_loc = T_loc
        else:
            C_loc = max(1, int(m.capacity_factor * T_loc * m.top_k /
                               m.num_experts))
    else:
        C_loc = capacity

    def local_fn(xt, rw, wg, wu, wd):
        # xt: (T_loc, d); rw: (d, E); wg/wu: (E_loc, d, f); wd: (E_loc, f, d)
        E = m.num_experts
        logits = (xt @ rw.astype(xt.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, m.top_k)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        frac_tokens = jnp.mean(
            jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
        aux = E * jnp.sum(frac_tokens * jnp.mean(probs, axis=0)) * \
            m.router_aux_weight
        aux = jax.lax.pmean(aux, tok_spec if len(tok_spec) > 1 else tok_spec[0])

        flat_e = top_e.reshape(-1)
        flat_w = top_w.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(T_loc), m.top_k)
        order = jnp.argsort(flat_e, stable=True)
        se, st, sw = flat_e[order], flat_tok[order], flat_w[order]
        pos = jnp.arange(T_loc * m.top_k)
        seg_start = jnp.searchsorted(se, jnp.arange(E))
        rank = pos - seg_start[se]
        keep = rank < C_loc
        slot = jnp.where(keep, se * C_loc + rank, E * C_loc)  # OOB -> dropped
        buf = jnp.zeros((E * C_loc, d), xt.dtype)
        buf = buf.at[slot].set(xt[st].astype(xt.dtype), mode="drop")
        # ---- expert parallel exchange ----
        recv = jax.lax.all_to_all(buf.reshape(E, C_loc, d), "model",
                                  split_axis=0, concat_axis=1, tiled=True)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv, wg.astype(xt.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", recv, wu.astype(xt.dtype))
        eo = jnp.einsum("ecf,efd->ecd", h, wd.astype(xt.dtype))
        send = jax.lax.all_to_all(eo, "model", split_axis=1, concat_axis=0,
                                  tiled=True)
        flat_out = send.reshape(E * C_loc, d)
        gathered = jnp.where(keep[:, None],
                             flat_out[jnp.clip(slot, 0, E * C_loc - 1)], 0.0)
        out = jnp.zeros((T_loc, d), xt.dtype).at[st].add(
            gathered * sw[:, None].astype(xt.dtype))
        return out, aux

    fn = jax.shard_map(
        local_fn, mesh=mesh, check_vma=False,
        in_specs=(P(tok_spec, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(tok_spec, None), P()))
    out, aux = fn(x.reshape(T, d), p["router"], p["w_gate"], p["w_up"],
                  p["w_down"])
    if "shared" in p:
        xt = x.reshape(T, d)
        sg = jax.nn.sigmoid(xt @ p["shared_gate"].astype(x.dtype))
        out = out + sg * apply_mlp(p["shared"], xt)
    return out.reshape(B, S, d), aux


def apply_moe_auto(p, x, moe_cfg, capacity=None):
    """Dispatch to the shard_map implementation when an activation-sharding
    policy (mesh) is installed, else the plain SPMD version (CPU tests)."""
    from repro.sharding.ctx import current_policy
    pol = current_policy()
    if pol is not None and pol["tp_size"] > 1:
        return apply_moe_shard_map(p, x, moe_cfg, pol, capacity)
    return apply_moe(p, x, moe_cfg, capacity)


def apply_moe(p, x, moe_cfg, capacity=None):
    """x: (B, S, d) -> (B, S, d), aux_loss (scalar)."""
    m = moe_cfg
    B, S, d = x.shape
    T = B * S
    xt = constrain(x.reshape(T, d), "tokens_flat")
    logits = (xt @ p["router"].astype(x.dtype)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)                     # (T, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # ---- aux load-balance loss (Switch-style) ----
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_e[:, 0], m.num_experts, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = m.num_experts * jnp.sum(frac_tokens * frac_probs) * m.router_aux_weight

    # ---- sort-based dispatch ----
    if capacity is None:
        if S == 1:  # decode: lossless dispatch (T = B is small)
            capacity = T
        else:
            capacity = int(m.capacity_factor * T * m.top_k / m.num_experts) or 1
    C = capacity
    flat_e = top_e.reshape(-1)                                   # (T*k,)
    flat_w = top_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), m.top_k)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_tok[order], flat_w[order]
    # rank within expert = index - start_of_expert_run
    pos = jnp.arange(T * m.top_k)
    seg_start = jnp.searchsorted(se, jnp.arange(m.num_experts))  # (E,)
    rank = pos - seg_start[se]
    keep = rank < C
    # overflow slots land out-of-bounds and are dropped by the scatter mode
    slot = jnp.where(keep, se * C + rank, m.num_experts * C)
    dispatch_src = constrain(xt[st].astype(x.dtype), "tokens_flat")  # (T*k, d)
    buf = jnp.zeros((m.num_experts * C, d), x.dtype)
    buf = constrain(buf, "moe_flat")
    buf = buf.at[slot].set(dispatch_src, mode="drop")
    buf = constrain(buf, "moe_flat")
    eb = buf.reshape(m.num_experts, C, d)                        # (E, C, d)
    eb = constrain(eb, "moe_dispatch")  # all-to-all into expert parallelism

    # ---- expert computation (sharded over E) ----
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", eb, p["w_gate"].astype(x.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", eb, p["w_up"].astype(x.dtype))
    eo = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype))

    # ---- combine ----
    flat_out = constrain(eo.reshape(m.num_experts * C, d), "moe_flat")
    gathered = jnp.where(keep[:, None], flat_out[jnp.clip(slot, 0, m.num_experts * C - 1)], 0.0)
    gathered = constrain(gathered, "tokens_flat")
    contrib = gathered * sw[:, None].astype(x.dtype)
    out = jnp.zeros((T, d), x.dtype).at[st].add(contrib)
    out = constrain(out, "tokens_flat")

    if "shared" in p:
        sg = jax.nn.sigmoid(xt @ p["shared_gate"].astype(x.dtype))
        out = out + sg * apply_mlp(p["shared"], xt)
    return out.reshape(B, S, d), aux
