"""Attention variants: GQA self-attention (with sliding-window / global mix,
logit softcap), MLA (compressed-latent KV), and cross-attention — each with a
training path and a one-token decode path over an explicit KV cache.

Layout conventions:
  activations x: (B, S, D)
  q/k/v:        (B, S, H, Dh)
  KV cache:     {"k": (B, T, KV, Dh), "v": (B, T, KV, Dh)}  (T = cache length)
  MLA cache:    {"ckv": (B, T, r), "krope": (B, T, Dr)}      (compressed!)

``local_flag`` is a traced scalar bool so that heterogeneous local/global
patterns run inside a single lax.scan over stacked layer params.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.models import common as cm
from repro.obs import trace as obs_trace

PyTree = Any

#: ISSUE 9 dispatch seams: GQA self-attention routes through the kernel
#: registry (flash Pallas kernels on TPU / forced backends, the literal
#: pre-kernel jnp ops on the always-eligible ``ref`` path).
_flash_attention = dispatch.get_kernel("flash_attention")
_flash_decode = dispatch.get_kernel("flash_decode")


def make_mask(q_pos, kv_pos, *, causal=True, local_flag=None, window=0):
    """q_pos: (B,S) int; kv_pos: (T,) int. Returns (B,1,S,T) bool (True=keep)."""
    q = q_pos[:, :, None]  # (B,S,1)
    k = kv_pos[None, None, :]  # (1,1,T)
    mask = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    if causal:
        mask = k <= q
    if window and local_flag is not None:
        local = (q - k) < window
        mask = mask & jnp.where(local_flag, local, True)
    return mask[:, None]  # (B,1,S,T)


def _chunked_sdpa(q, k, v, q_pos, kv_pos, *, chunk, softcap=0.0, local_flag=None,
                  window=0, causal=True):
    """Blockwise online-softmax attention (flash-style, KV-chunked scan).

    Never materializes the (B, H, S, T) score tensor: each scan step holds
    one (B, H, S, chunk) block plus running (max, sum, acc) statistics. The
    body is checkpointed so the backward pass recomputes blocks instead of
    saving them. This is the §Perf memory-term optimization for long-sequence
    prefill/train; on TPU the block working set is VMEM-sized by chunk.
    """

    B, S, KV, G, Dh = q.shape
    T = k.shape[1]
    nc = -(-T // chunk)
    if nc * chunk != T:  # ragged T: pad KV with -1-position sentinel rows
        pad = nc * chunk - T
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=-1)
    k_c = jnp.moveaxis(k.reshape(B, nc, chunk, KV, Dh), 1, 0)
    v_c = jnp.moveaxis(v.reshape(B, nc, chunk, KV, Dh), 1, 0)
    pos_c = kv_pos.reshape(nc, chunk)
    scale = 1.0 / jnp.sqrt(Dh).astype(q.dtype)

    NEG = -1e30  # finite sentinel: keeps exp/max arithmetic nan-free when a
    # query's valid keys haven't appeared yet (e.g. sliding-window + early chunks)

    def body(carry, inp):
        m, l, acc = carry
        kc, vc, pc = inp
        s = jnp.einsum("bskgd,btkd->bkgst", q, kc) * scale  # (B,KV,G,S,C)
        s = cm.softcap(s.astype(jnp.float32), softcap)
        mask = make_mask(q_pos, pc, causal=causal, local_flag=local_flag, window=window)
        mask = mask & (pc >= 0)[None, None, None, :]  # drop padded sentinel rows
        mask_b = jnp.broadcast_to(mask[:, :, None], s.shape)  # (B,1,1,S,C)->(B,KV,G,S,C)
        s = jnp.where(mask_b, s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(mask_b, jnp.exp(s - m_new[..., None]), 0.0)
        scale_old = jnp.exp(jnp.minimum(m - m_new, 0.0))
        scale_old = jnp.where(m <= NEG, 0.0, scale_old)  # nothing accumulated yet
        l_new = l * scale_old + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgst,btkd->bskgd", p.astype(q.dtype), vc)
        acc_new = acc * jnp.moveaxis(scale_old, -1, 1)[..., None].astype(q.dtype) + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, S), NEG, jnp.float32)
    l0 = jnp.zeros((B, KV, G, S), jnp.float32)
    acc0 = jnp.zeros((B, S, KV, G, Dh), q.dtype)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), (m0, l0, acc0), (k_c, v_c, pos_c)
    )
    denom = jnp.moveaxis(jnp.maximum(l, 1e-30), -1, 1)[..., None]
    return (acc / denom.astype(q.dtype)).reshape(B, S, KV * G, Dh)


def _sdpa(q, k, v, mask, *, softcap=0.0):
    """Grouped scaled-dot-product attention.
    q: (B,S,H,Dh), k/v: (B,T,KV,Dh); H = KV * G."""

    B, S, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, S, KV, G, Dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k) / jnp.sqrt(Dh).astype(q.dtype)
    scores = cm.softcap(scores.astype(jnp.float32), softcap)
    if mask is not None:
        scores = jnp.where(mask[:, :, None], scores, -1e30)  # mask (B,1,S,T)->(B,1,1,S,T)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H, Dh)


# ---------------------------------------------------------------------------
# GQA self-attention
# ---------------------------------------------------------------------------


def init_self_attn(cfg, key, dtype=jnp.float32):
    H, KV, Dh, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": cm.dense_init(k1, (D, H * Dh), dtype=dtype),
        "wk": cm.dense_init(k2, (D, KV * Dh), dtype=dtype),
        "wv": cm.dense_init(k3, (D, KV * Dh), dtype=dtype),
        "wo": cm.dense_init(k4, (H * Dh, D), dtype=dtype),
    }


def self_attention(
    cfg,
    p: PyTree,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    local_flag=None,
    causal: bool = True,
    cache: Optional[Dict] = None,
    cache_pos=None,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    with obs_trace.block("attention"):
        B, S, D = x.shape
        H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, H, Dh)
        k = (x @ p["wk"].astype(x.dtype)).reshape(B, S, KV, Dh)
        v = (x @ p["wv"].astype(x.dtype)).reshape(B, S, KV, Dh)
        if cfg.use_rope:
            q = cm.apply_rope(q, positions, cfg.rope_theta)
            k = cm.apply_rope(k, positions, cfg.rope_theta)

        if cache is None:
            kv_pos = positions[0] if positions.ndim == 2 else positions
            q_pos = (positions if positions.ndim == 2
                     else jnp.broadcast_to(positions[None], (B, S)))
            # ISSUE 9: training/prefill attention dispatches through the kernel
            # registry. The ref backend reproduces the pre-kernel ops literally
            # (including the chunk-gated _sdpa/_chunked_sdpa selection), so the
            # default CPU path is unchanged; TPU / forced backends lower the
            # blockwise flash Pallas kernel with its recompute-based VJP.
            out = _flash_attention(
                q, k, v, q_pos, kv_pos, local_flag,
                softcap=cfg.attn_logit_softcap, window=cfg.sliding_window,
                causal=causal, chunk=cfg.attn_chunk,
            )
            new_cache = None
        else:
            # decode: insert the S new k/v rows at cache_pos, attend over the
            # cache. cache_pos is a scalar start (uniform batch — a contiguous
            # dynamic_update_slice) or a (B,) vector of per-lane starts
            # (continuous batching with staggered sequence lengths — a scatter).
            T = cache["k"].shape[1]
            if jnp.ndim(cache_pos) == 0:
                ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, cache_pos, 0, 0))
                cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, cache_pos, 0, 0))
            else:
                lane = jnp.arange(B)[:, None]
                idx = cache_pos[:, None] + jnp.arange(S)
                ck = cache["k"].at[lane, idx].set(k.astype(cache["k"].dtype))
                cv = cache["v"].at[lane, idx].set(v.astype(cache["v"].dtype))
            if S == 1:
                # one-token decode: the split-KV kernel consumes per-lane
                # positions directly (continuous batching's ragged lanes); the
                # ref backend is the exact make_mask + _sdpa ops from before.
                out = _flash_decode(
                    q, ck.astype(q.dtype), cv.astype(q.dtype), positions,
                    local_flag, softcap=cfg.attn_logit_softcap,
                    window=cfg.sliding_window,
                )
            else:
                kv_pos = jnp.arange(T)
                mask = make_mask(positions, kv_pos, causal=True, local_flag=local_flag, window=cfg.sliding_window)
                out = _sdpa(q, ck.astype(q.dtype), cv.astype(q.dtype), mask, softcap=cfg.attn_logit_softcap)
            new_cache = {"k": ck, "v": cv}

        out = out.reshape(B, S, H * Dh) @ p["wo"].astype(x.dtype)
        return out, new_cache


def init_kv_cache(cfg, batch: int, length: int, dtype=jnp.bfloat16):
    KV, Dh = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, length, KV, Dh), dtype),
        "v": jnp.zeros((batch, length, KV, Dh), dtype),
    }


# ---------------------------------------------------------------------------
# MLA (DeepSeek/MiniCPM3-style multi-head latent attention)
# ---------------------------------------------------------------------------


def init_mla(cfg, key, dtype=jnp.float32):
    D, H = cfg.d_model, cfg.num_heads
    r, rq = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wkv_a": cm.dense_init(ks[0], (D, r + dr), dtype=dtype),
        "kv_norm": jnp.ones((r,), jnp.float32),
        "wkv_b": cm.dense_init(ks[1], (r, H * (dn + dv)), dtype=dtype),
        "wo": cm.dense_init(ks[2], (H * dv, D), dtype=dtype),
    }
    if rq:
        p["wq_a"] = cm.dense_init(ks[3], (D, rq), dtype=dtype)
        p["q_norm"] = jnp.ones((rq,), jnp.float32)
        p["wq_b"] = cm.dense_init(ks[4], (rq, H * (dn + dr)), dtype=dtype)
    else:
        p["wq"] = cm.dense_init(ks[5], (D, H * (dn + dr)), dtype=dtype)
    return p


def _rmsnorm_vec(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf**2, -1, keepdims=True) + eps) * scale).astype(x.dtype)


def mla_attention(cfg, p, x, positions, *, cache=None, cache_pos=None):
    with obs_trace.block("attention"):
        B, S, D = x.shape
        H = cfg.num_heads
        r = cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

        if "wq_a" in p:
            q = _rmsnorm_vec(x @ p["wq_a"].astype(x.dtype), p["q_norm"]) @ p["wq_b"].astype(x.dtype)
        else:
            q = x @ p["wq"].astype(x.dtype)
        q = q.reshape(B, S, H, dn + dr)
        qn, qr = q[..., :dn], q[..., dn:]
        qr = cm.apply_rope(qr, positions, cfg.rope_theta)

        kv_a = x @ p["wkv_a"].astype(x.dtype)  # (B,S,r+dr)
        ckv, krope = kv_a[..., :r], kv_a[..., r:]
        krope = cm.apply_rope(krope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]  # shared head

        if cache is not None:
            if jnp.ndim(cache_pos) == 0:
                ckv = jax.lax.dynamic_update_slice(cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, cache_pos, 0))
                krope = jax.lax.dynamic_update_slice(
                    cache["krope"], krope.astype(cache["krope"].dtype), (0, cache_pos, 0)
                )
            else:  # per-lane starts (continuous batching): scatter rows
                lane = jnp.arange(B)[:, None]
                idx = cache_pos[:, None] + jnp.arange(S)
                ckv = cache["ckv"].at[lane, idx].set(ckv.astype(cache["ckv"].dtype))
                krope = cache["krope"].at[lane, idx].set(krope.astype(cache["krope"].dtype))
            new_cache = {"ckv": ckv, "krope": krope}
            T = ckv.shape[1]
            kv_pos = jnp.arange(T)
        else:
            new_cache = None
            T = S
            kv_pos = positions[0] if positions.ndim == 2 else positions

        kv = _rmsnorm_vec(ckv.astype(x.dtype), p["kv_norm"]) @ p["wkv_b"].astype(x.dtype)
        kv = kv.reshape(B, T, H, dn + dv)
        kn, v = kv[..., :dn], kv[..., dn:]

        scale = 1.0 / jnp.sqrt(dn + dr).astype(x.dtype)
        scores = (
            jnp.einsum("bshd,bthd->bhst", qn, kn)
            + jnp.einsum("bshd,btd->bhst", qr, krope.astype(x.dtype))
        ) * scale
        mask = make_mask(positions, kv_pos, causal=True)  # (B,1,S,T)
        scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhst,bthd->bshd", probs, v).reshape(B, S, H * dv)
        return out @ p["wo"].astype(x.dtype), new_cache


def init_mla_cache(cfg, batch: int, length: int, dtype=jnp.bfloat16):
    return {
        "ckv": jnp.zeros((batch, length, cfg.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, length, cfg.qk_rope_head_dim), dtype),
    }


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder, llama-3.2-vision layers)
# ---------------------------------------------------------------------------


def init_cross_attn(cfg, key, dtype=jnp.float32, kv_dim=None):
    H, KV, Dh, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    kv_dim = kv_dim or D
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": cm.dense_init(k1, (D, H * Dh), dtype=dtype),
        "wk": cm.dense_init(k2, (kv_dim, KV * Dh), dtype=dtype),
        "wv": cm.dense_init(k3, (kv_dim, KV * Dh), dtype=dtype),
        "wo": cm.dense_init(k4, (H * Dh, D), dtype=dtype),
    }


def cross_attention(cfg, p, x, *, memory=None, memory_kv=None):
    """memory: (B, M, D_mem) encoder/vision states, or precomputed memory_kv
    {"k","v"} (decode path — computed once at prefill)."""

    with obs_trace.block("cross_attention"):
        B, S, D = x.shape
        H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, H, Dh)
        if memory_kv is None:
            k = (memory @ p["wk"].astype(memory.dtype)).reshape(B, -1, KV, Dh).astype(x.dtype)
            v = (memory @ p["wv"].astype(memory.dtype)).reshape(B, -1, KV, Dh).astype(x.dtype)
        else:
            k, v = memory_kv["k"].astype(x.dtype), memory_kv["v"].astype(x.dtype)
        out = _sdpa(q, k, v, None)
        return out.reshape(B, S, H * Dh) @ p["wo"].astype(x.dtype)


def cross_kv(cfg, p, memory):
    with obs_trace.block("cross_attention"):
        B = memory.shape[0]
        KV, Dh = cfg.num_kv_heads, cfg.head_dim
        k = (memory @ p["wk"].astype(memory.dtype)).reshape(B, -1, KV, Dh)
        v = (memory @ p["wv"].astype(memory.dtype)).reshape(B, -1, KV, Dh)
        return {"k": k, "v": v}
