"""Mixture-of-Experts layer: top-k router with capacity-based dispatch
(GShard/Switch-style one-hot einsum dispatch — the TPU-native formulation),
optional always-on shared experts (Qwen-MoE / Kimi-K2 style), and an
auxiliary load-balance loss surfaced to the training objective.

Expert weights carry a leading E axis so they shard naturally over the
``model`` mesh axis (expert parallelism); dispatch/combine einsums lower to
all-to-alls under pjit when tokens are data-sharded.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.obs import trace as obs_trace

PyTree = Any


def init_moe(cfg, key, dtype=jnp.float32):
    E, D, F = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    k_router, k_experts, k_shared = jax.random.split(key, 3)

    def one_expert(k):
        return init_expert_ffn(cfg, k, D, F, dtype)

    p = {
        "router": cm.dense_init(k_router, (D, E), dtype=jnp.float32),
        "experts": cm.stacked_init(one_expert, k_experts, E),
    }
    if cfg.num_shared_experts:
        p["shared"] = cm.init_mlp(
            cfg, k_shared, d_in=D, d_ff=cfg.shared_d_ff or cfg.num_shared_experts * F, dtype=dtype
        )
    return p


def init_expert_ffn(cfg, key, d, f, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "up": cm.dense_init(k1, (d, f), dtype=dtype),
        "down": cm.dense_init(k3, (f, d), dtype=dtype),
    }
    if cfg.mlp_type == "glu":
        p["gate"] = cm.dense_init(k2, (d, f), dtype=dtype)
    return p


def _expert_ffn(cfg, p, x):
    """x: (E, C, D) with per-expert stacked weights (E, ...)."""
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[cfg.act]
    up = jnp.einsum("ecd,edf->ecf", x, p["up"].astype(x.dtype))
    if cfg.mlp_type == "glu":
        up = up * act(jnp.einsum("ecd,edf->ecf", x, p["gate"].astype(x.dtype)))
    else:
        up = act(up)
    return jnp.einsum("ecf,efd->ecd", up, p["down"].astype(x.dtype))


MOE_GROUP = 1024  # tokens per dispatch group (GShard-style); bounds the
# one-hot dispatch tensor to (G, E, C) with C ~ k*G/E, so dispatch/combine
# einsum overhead stays ~O(G/6F) relative to expert FLOPs.


def _group_dispatch(cfg, probs_g, tokens_g, experts, capacity):
    """One dispatch group. probs_g: (G, E) f32; tokens_g: (G, D)."""

    G, E = probs_g.shape
    K = cfg.top_k
    gate_vals, expert_idx = jax.lax.top_k(probs_g, K)  # (G, K)
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    assign = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # (G,K,E)
    # choice-major flattening: all 1st choices get capacity slots before 2nd…
    flat = assign.transpose(1, 0, 2).reshape(K * G, E)
    pos = jnp.cumsum(flat, axis=0) - flat
    pos = jnp.sum(pos * flat, axis=-1)  # (K*G,)
    keep = (pos < capacity) & (jnp.sum(flat, -1) > 0)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32) * keep[:, None]
    # contract over the choice axis without materializing (K,G,E,C)
    flat_k = flat.reshape(K, G, E)
    pos_oh_k = pos_oh.reshape(K, G, capacity)
    dispatch = jnp.einsum("kge,kgc->gec", flat_k, pos_oh_k)  # (G,E,C) 0/1
    gates_k = gate_vals.transpose(1, 0)  # (K,G)
    combine = jnp.einsum("kge,kgc->gec", flat_k * gates_k[:, :, None], pos_oh_k)

    expert_in = jnp.einsum("gec,gd->ecd", dispatch.astype(tokens_g.dtype), tokens_g)
    expert_out = _expert_ffn(cfg, experts, expert_in)  # (E,C,D)
    out = jnp.einsum("gec,ecd->gd", combine.astype(tokens_g.dtype), expert_out)
    return out


def apply_moe(cfg, p: PyTree, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D). Returns (out, aux_load_balance_loss)."""

    # the expert layer is an mlp block; "moe" inside it names this module
    with obs_trace.block("mlp"), obs_trace.block("moe"):
        B, S, D = x.shape
        E, K = cfg.num_experts, cfg.top_k
        T = B * S
        group = min(MOE_GROUP, T)
        n_groups = T // group
        assert n_groups * group == T, f"token count {T} not divisible by group {group}"
        capacity = max(int(cfg.capacity_factor * K * group / E), 4)

        tokens = x.reshape(n_groups, group, D)
        router_logits = jnp.einsum(
            "ngd,de->nge", tokens.astype(jnp.float32), p["router"].astype(jnp.float32)
        )
        probs = jax.nn.softmax(router_logits, axis=-1)  # (n, G, E)

        out = jax.vmap(lambda pr, tk: _group_dispatch(cfg, pr, tk, p["experts"], capacity))(
            probs, tokens
        )

        flat_tokens = x.reshape(T, D)
        if cfg.num_shared_experts:
            out = out.reshape(T, D) + cm.apply_mlp(cfg, p["shared"], flat_tokens)

        # GShard aux loss: E * sum_e f_e * p_e over the whole batch
        probs_flat = probs.reshape(T, E)
        _, expert_idx = jax.lax.top_k(probs_flat, K)
        assign = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
        me = jnp.mean(probs_flat, axis=0)
        ce = jnp.mean(jnp.sum(assign, axis=1), axis=0)
        aux = E * jnp.sum(me * ce) * cfg.router_aux_coef

        return out.reshape(B, S, D), aux.astype(jnp.float32)
