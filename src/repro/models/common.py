"""Shared model building blocks: norms, MLPs, RoPE, initializers.

Everything is pure-functional over nested-dict params. Layer stacks are
*stacked* along a leading axis and executed with ``lax.scan`` so HLO size
(and compile time) is O(1) in depth — essential for the 100-layer dry-runs.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.obs import trace as obs_trace

PyTree = Any


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, scale: float = 1.0, dtype=jnp.float32):
    """Truncated-normal fan-in init."""
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale / jnp.sqrt(jnp.maximum(fan_in, 1)).astype(jnp.float32)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def stacked_init(init_fn: Callable, key, num: int) -> PyTree:
    """vmap an init over a leading layer axis."""
    keys = jax.random.split(key, num)
    return jax.vmap(init_fn)(keys)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(cfg, d=None):
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(cfg, p, x, eps=1e-6):
    with obs_trace.block("norm"):
        xf = x.astype(jnp.float32)
        if cfg.norm == "layernorm":
            mu = jnp.mean(xf, axis=-1, keepdims=True)
            var = jnp.var(xf, axis=-1, keepdims=True)
            out = (xf - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
        else:  # rmsnorm
            ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
            out = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
        return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP / GLU
# ---------------------------------------------------------------------------


def _act(name):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def init_mlp(cfg, key, d_in=None, d_ff=None, dtype=jnp.float32):
    d_in = d_in or cfg.d_model
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "up": dense_init(k1, (d_in, d_ff), dtype=dtype),
        "down": dense_init(k3, (d_ff, d_in), dtype=dtype),
    }
    if cfg.mlp_type == "glu":
        p["gate"] = dense_init(k2, (d_in, d_ff), dtype=dtype)
    return p


def apply_mlp(cfg, p, x):
    with obs_trace.block("mlp"):
        act = _act(cfg.act)
        up = x @ p["up"].astype(x.dtype)
        if cfg.mlp_type == "glu":
            up = up * act(x @ p["gate"].astype(x.dtype))
        else:
            up = act(up)
        return up @ p["down"].astype(x.dtype)


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # (..., S, 1, D/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(seq: int, d: int, dtype=jnp.float32):
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, dim / d)
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang))
    pe = pe.at[:, 1::2].set(jnp.cos(ang))
    return pe.astype(dtype)


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------


def softcap(x, cap: float):
    """Gemma-2 logit soft-capping."""
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def unstack_layer(params: PyTree, idx) -> PyTree:
    """Select one layer's params from a stacked pytree (used by decode loops
    and inspection utilities; scan does this implicitly)."""
    return jax.tree_util.tree_map(lambda x: x[idx], params)
