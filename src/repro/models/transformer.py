"""Architecture assembly: init / forward / one-token decode per family.

Families
  dense   — GQA or MLA attention + MLP, heterogeneous local/global patterns
            expressed as a per-layer flag array inside ONE lax.scan.
  moe     — leading dense layers + scanned MoE stack (aux loss accumulated
            in the scan carry).
  ssm     — RWKV6 blocks (time-mix + channel-mix).
  hybrid  — Zamba2: groups of k Mamba2 layers + ONE shared attention block
            applied after each group (shared weights = scan closure constant,
            per-application KV caches).
  audio   — Whisper: bidirectional encoder over (stubbed) frame embeddings +
            causal decoder with cross-attention.
  vlm     — Llama-3.2-Vision: groups of (k-1) self layers + 1 gated
            cross-attention layer over (stubbed) patch embeddings.
  encoder — BERT-style classifier (the paper's own base model for the
            WRENCH experiments).

All stacks are scanned, so HLO size is independent of depth. Decode caches
are pytrees whose leaves carry the stacked layer axis, so the same scan
pattern threads them.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common as cm
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.obs import trace as obs_trace

PyTree = Any


# ---------------------------------------------------------------------------
# shared block helpers
# ---------------------------------------------------------------------------


def _init_dense_layer(cfg, key, dtype):
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": cm.init_norm(cfg),
        "ln2": cm.init_norm(cfg),
        "mlp": cm.init_mlp(cfg, k2, dtype=dtype),
    }
    if cfg.use_mla:
        p["attn"] = attn.init_mla(cfg, k1, dtype=dtype)
    else:
        p["attn"] = attn.init_self_attn(cfg, k1, dtype=dtype)
    return p


def _dense_layer(cfg, p, x, positions, flag, cache=None, cache_pos=None, causal=True):
    h = cm.apply_norm(cfg, p["ln1"], x)
    if cfg.use_mla:
        out, new_cache = attn.mla_attention(cfg, p["attn"], h, positions, cache=cache, cache_pos=cache_pos)
    else:
        out, new_cache = attn.self_attention(
            cfg, p["attn"], h, positions, local_flag=flag, cache=cache, cache_pos=cache_pos,
            causal=causal,
        )
    x = x + out
    x = x + cm.apply_mlp(cfg, p["mlp"], cm.apply_norm(cfg, p["ln2"], x))
    return x, new_cache


def _init_moe_layer(cfg, key, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": cm.init_norm(cfg),
        "ln2": cm.init_norm(cfg),
        "attn": attn.init_self_attn(cfg, k1, dtype=dtype),
        "moe": moe_mod.init_moe(cfg, k2, dtype=dtype),
    }


def _moe_layer(cfg, p, x, positions, cache=None, cache_pos=None):
    h = cm.apply_norm(cfg, p["ln1"], x)
    out, new_cache = attn.self_attention(cfg, p["attn"], h, positions, cache=cache, cache_pos=cache_pos)
    x = x + out
    h2, aux = moe_mod.apply_moe(cfg, p["moe"], cm.apply_norm(cfg, p["ln2"], x))
    return x + h2, aux, new_cache


def _flags(cfg) -> jnp.ndarray:
    return jnp.asarray([k == "local" for k in cfg.layer_kinds], bool)


def _maybe_remat(cfg, body):
    """Checkpoint a scan body: activations inside a layer are recomputed in
    the backward pass, so live memory is O(1) in depth instead of O(L)."""
    return jax.checkpoint(body, prevent_cse=False) if cfg.remat else body


def _embed(cfg, params, tokens, dtype, positions=None):
    """positions: (B, S) absolute positions for learned pos-embed lookup;
    None means tokens start at position 0 (the train/prefill case). Decode
    MUST pass real positions — indexing ``pos_embed[:S]`` there would add
    the position-0 embedding to every generated token."""
    with obs_trace.block("embed"):
        x = params["embed"][tokens].astype(dtype) * jnp.sqrt(cfg.d_model).astype(dtype)
        if cfg.pos_embed == "learned":
            if positions is None:
                x = x + params["pos_embed"][: tokens.shape[1]].astype(dtype)
            else:
                x = x + params["pos_embed"][positions].astype(dtype)
        return x


def _unembed(cfg, params, x):
    with obs_trace.block("unembed"):
        logits = x @ params["embed"].T.astype(x.dtype)
        return cm.softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)


# ===========================================================================
# init
# ===========================================================================


def init_params(cfg, key) -> PyTree:
    dtype = cm.dtype_of(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": cm.dense_init(keys[0], (cfg.vocab_size, cfg.d_model), dtype=dtype),
        "final_norm": cm.init_norm(cfg),
    }
    if cfg.pos_embed == "learned":
        params["pos_embed"] = cm.dense_init(keys[6], (cfg.max_position, cfg.d_model), dtype=dtype)

    fam = cfg.family
    if fam in ("dense",):
        params["layers"] = cm.stacked_init(
            lambda k: _init_dense_layer(cfg, k, dtype), keys[1], cfg.num_layers
        )
    elif fam == "moe":
        nd = cfg.first_k_dense
        if nd:
            params["dense_layers"] = cm.stacked_init(
                lambda k: _init_dense_layer(cfg, k, dtype), keys[2], nd
            )
        params["layers"] = cm.stacked_init(
            lambda k: _init_moe_layer(cfg, k, dtype), keys[1], cfg.num_layers - nd
        )
    elif fam == "ssm":  # rwkv6
        def init_block(k):
            k1, k2 = jax.random.split(k)
            return {
                "ln1": cm.init_norm(cfg),
                "ln2": cm.init_norm(cfg),
                "tmix": ssm_mod.init_rwkv_time_mix(cfg, k1, dtype),
                "cmix": ssm_mod.init_rwkv_channel_mix(cfg, k2, dtype),
            }

        params["layers"] = cm.stacked_init(init_block, keys[1], cfg.num_layers)
    elif fam == "hybrid":  # zamba2
        k_grp = cfg.hybrid_attn_every
        n_extra = cfg.num_layers % k_grp
        n_groups = cfg.num_layers // k_grp

        def init_mamba_block(k):
            return {"ln1": cm.init_norm(cfg), "mamba": ssm_mod.init_mamba(cfg, k, dtype)}

        if n_extra:
            params["mamba_head"] = cm.stacked_init(init_mamba_block, keys[2], n_extra)
        params["mamba_groups"] = jax.vmap(
            lambda k: cm.stacked_init(init_mamba_block, k, k_grp)
        )(jax.random.split(keys[1], n_groups))
        params["shared_attn"] = _init_dense_layer(cfg, keys[3], dtype)
    elif fam == "audio":  # whisper
        def init_enc(k):
            return _init_dense_layer(cfg, k, dtype)

        def init_dec(k):
            k1, k2 = jax.random.split(k)
            p = _init_dense_layer(cfg, k1, dtype)
            p["ln_x"] = cm.init_norm(cfg)
            p["xattn"] = attn.init_cross_attn(cfg, k2, dtype=dtype)
            return p

        params["encoder"] = {
            "layers": cm.stacked_init(init_enc, keys[2], cfg.encoder_layers),
            "norm": cm.init_norm(cfg),
        }
        params["layers"] = cm.stacked_init(init_dec, keys[1], cfg.num_layers)
    elif fam == "vlm":  # llama-3.2-vision
        k_grp = cfg.cross_attn_every
        n_groups = cfg.num_layers // k_grp
        n_self = k_grp - 1

        def init_self_group(k):
            return cm.stacked_init(lambda kk: _init_dense_layer(cfg, kk, dtype), k, n_self)

        def init_cross(k):
            k1, k2 = jax.random.split(k)
            return {
                "ln1": cm.init_norm(cfg),
                "ln2": cm.init_norm(cfg),
                "xattn": attn.init_cross_attn(cfg, k1, dtype=dtype),
                "mlp": cm.init_mlp(cfg, k2, dtype=dtype),
                "gate_attn": jnp.zeros((), jnp.float32),
                "gate_mlp": jnp.zeros((), jnp.float32),
            }

        params["self_groups"] = jax.vmap(init_self_group)(jax.random.split(keys[1], n_groups))
        params["cross_layers"] = cm.stacked_init(init_cross, keys[2], n_groups)
        params["projector"] = cm.dense_init(keys[3], (cfg.vision_dim, cfg.d_model), dtype=dtype)
    elif fam == "encoder":  # bert-style classifier
        params["layers"] = cm.stacked_init(
            lambda k: _init_dense_layer(cfg, k, dtype), keys[1], cfg.num_layers
        )
        params["cls_head"] = {
            "w": cm.dense_init(keys[4], (cfg.d_model, cfg.num_labels), dtype=dtype),
            "b": jnp.zeros((cfg.num_labels,), jnp.float32),
        }
    else:
        raise ValueError(f"unknown family {fam}")
    return params


# ===========================================================================
# forward (train / prefill)
# ===========================================================================


def forward(cfg, params: PyTree, batch: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits, aux_loss). batch: tokens (B,S) [+ patches | frames]."""

    dtype = cm.dtype_of(cfg.dtype)
    fam = cfg.family
    aux = jnp.zeros((), jnp.float32)

    if fam == "audio":
        return _whisper_forward(cfg, params, batch)

    tokens = batch["tokens"]
    B, S = tokens.shape
    x = _embed(cfg, params, tokens, dtype)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    if fam in ("dense", "encoder"):
        flags = _flags(cfg)
        causal = fam != "encoder"  # BERT-style encoders are bidirectional

        def body(h, inp):
            lp, fl = inp
            h, _ = _dense_layer(cfg, lp, h, positions, fl, causal=causal)
            return h, None

        x, _ = jax.lax.scan(_maybe_remat(cfg, body), x, (params["layers"], flags))
        if fam == "encoder":
            x = cm.apply_norm(cfg, params["final_norm"], x)
            with obs_trace.block("unembed"):
                cls = x[:, 0]
                logits = cls @ params["cls_head"]["w"].astype(x.dtype) + params["cls_head"]["b"]
            return logits.astype(jnp.float32), aux

    elif fam == "moe":
        if cfg.first_k_dense:
            def dbody(h, lp):
                h, _ = _dense_layer(cfg, lp, h, positions, jnp.asarray(False))
                return h, None

            x, _ = jax.lax.scan(_maybe_remat(cfg, dbody), x, params["dense_layers"])

        def mbody(carry, lp):
            h, a = carry
            h, aux_l, _ = _moe_layer(cfg, lp, h, positions)
            return (h, a + aux_l), None

        (x, aux), _ = jax.lax.scan(_maybe_remat(cfg, mbody), (x, aux), params["layers"])

    elif fam == "ssm":
        def rbody(h, lp):
            h = h + ssm_mod.apply_rwkv_time_mix(cfg, lp["tmix"], cm.apply_norm(cfg, lp["ln1"], h))
            h = h + ssm_mod.apply_rwkv_channel_mix(cfg, lp["cmix"], cm.apply_norm(cfg, lp["ln2"], h))
            return h, None

        x, _ = jax.lax.scan(_maybe_remat(cfg, rbody), x, params["layers"])

    elif fam == "hybrid":
        def mamba_block(h, lp):
            h = h + ssm_mod.apply_mamba(cfg, lp["mamba"], cm.apply_norm(cfg, lp["ln1"], h))
            return h, None

        if "mamba_head" in params:
            x, _ = jax.lax.scan(_maybe_remat(cfg, mamba_block), x, params["mamba_head"])

        shared = params["shared_attn"]

        def gbody(h, grp):
            h, _ = jax.lax.scan(mamba_block, h, grp)
            h, _ = _dense_layer(cfg, shared, h, positions, jnp.asarray(False))
            return h, None

        x, _ = jax.lax.scan(_maybe_remat(cfg, gbody), x, params["mamba_groups"])

    elif fam == "vlm":
        memory = (batch["patches"].astype(dtype)) @ params["projector"].astype(dtype)

        def self_block(h, lp):
            h, _ = _dense_layer(cfg, lp, h, positions, jnp.asarray(False))
            return h, None

        def vgroup(h, inp):
            sg, cl = inp
            h, _ = jax.lax.scan(self_block, h, sg)
            a = attn.cross_attention(cfg, cl["xattn"], cm.apply_norm(cfg, cl["ln1"], h), memory=memory)
            h = h + jnp.tanh(cl["gate_attn"]).astype(h.dtype) * a
            m = cm.apply_mlp(cfg, cl["mlp"], cm.apply_norm(cfg, cl["ln2"], h))
            h = h + jnp.tanh(cl["gate_mlp"]).astype(h.dtype) * m
            return h, None

        x, _ = jax.lax.scan(_maybe_remat(cfg, vgroup), x, (params["self_groups"], params["cross_layers"]))

    else:
        raise ValueError(fam)

    x = cm.apply_norm(cfg, params["final_norm"], x)
    return _unembed(cfg, params, x), aux


def _whisper_forward(cfg, params, batch):
    dtype = cm.dtype_of(cfg.dtype)
    frames = batch["frames"].astype(dtype)  # (B, F, D) stubbed conv/mel output
    F = frames.shape[1]
    with obs_trace.block("encoder"):
        with obs_trace.block("embed"):
            enc = frames + cm.sinusoidal_pos(F, cfg.d_model, dtype)[None]
        enc_pos = jnp.broadcast_to(jnp.arange(F), (frames.shape[0], F))

        def ebody(h, lp):
            hh = cm.apply_norm(cfg, lp["ln1"], h)
            out, _ = attn.self_attention(cfg, lp["attn"], hh, enc_pos, causal=False)
            h = h + out
            h = h + cm.apply_mlp(cfg, lp["mlp"], cm.apply_norm(cfg, lp["ln2"], h))
            return h, None

        enc, _ = jax.lax.scan(_maybe_remat(cfg, ebody), enc, params["encoder"]["layers"])
        memory = cm.apply_norm(cfg, params["encoder"]["norm"], enc)

    tokens = batch["tokens"]
    B, S = tokens.shape
    with obs_trace.block("decoder"):
        x = _embed(cfg, params, tokens, dtype)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))

        def dbody(h, lp):
            h, _ = _dense_layer_with_cross(cfg, lp, h, positions, memory=memory)
            return h, None

        x, _ = jax.lax.scan(_maybe_remat(cfg, dbody), x, params["layers"])
        x = cm.apply_norm(cfg, params["final_norm"], x)
        logits = _unembed(cfg, params, x)
    return logits, jnp.zeros((), jnp.float32)


def _dense_layer_with_cross(cfg, p, x, positions, memory=None, memory_kv=None, cache=None, cache_pos=None):
    h = cm.apply_norm(cfg, p["ln1"], x)
    out, new_cache = attn.self_attention(cfg, p["attn"], h, positions, cache=cache, cache_pos=cache_pos)
    x = x + out
    x = x + attn.cross_attention(
        cfg, p["xattn"], cm.apply_norm(cfg, p["ln_x"], x), memory=memory, memory_kv=memory_kv
    )
    x = x + cm.apply_mlp(cfg, p["mlp"], cm.apply_norm(cfg, p["ln2"], x))
    return x, new_cache

# ===========================================================================
# decode (serve_step: ONE new token against a seq_len cache/state)
# ===========================================================================


def init_cache(cfg, batch: int, cache_len: int, dtype=jnp.bfloat16) -> PyTree:
    """Allocate the decode cache pytree (leaves stacked over layers)."""

    fam = cfg.family

    def kv(n_stack, length=cache_len, extra=()):
        base = attn.init_kv_cache(cfg, batch, length, dtype)
        return jax.tree_util.tree_map(
            lambda x: jnp.zeros(extra + (n_stack,) + x.shape if n_stack else x.shape, x.dtype), base
        )

    def stack(n, tree):
        return jax.tree_util.tree_map(lambda x: jnp.zeros((n,) + x.shape, x.dtype), tree)

    if fam == "dense":
        if cfg.use_mla:
            one = attn.init_mla_cache(cfg, batch, cache_len, dtype)
        else:
            one = attn.init_kv_cache(cfg, batch, cache_len, dtype)
        return {"kv": stack(cfg.num_layers, one)}
    if fam == "moe":
        one = attn.init_kv_cache(cfg, batch, cache_len, dtype)
        c = {"kv": stack(cfg.num_layers - cfg.first_k_dense, one)}
        if cfg.first_k_dense:
            c["dense_kv"] = stack(cfg.first_k_dense, one)
        return c
    if fam == "ssm":
        one = ssm_mod.init_rwkv_state(cfg, batch, dtype)
        return {"layers": stack(cfg.num_layers, one)}
    if fam == "hybrid":
        k_grp = cfg.hybrid_attn_every
        n_extra = cfg.num_layers % k_grp
        n_groups = cfg.num_layers // k_grp
        one = ssm_mod.init_mamba_state(cfg, batch, dtype)
        c = {
            "groups": stack(n_groups, stack(k_grp, one)),
            "attn_kv": stack(n_groups, attn.init_kv_cache(cfg, batch, cache_len, dtype)),
        }
        if n_extra:
            c["head"] = stack(n_extra, one)
        return c
    if fam == "audio":
        enc_kv = {
            "k": jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim), dtype),
        }
        return {
            "kv": stack(cfg.num_layers, attn.init_kv_cache(cfg, batch, cache_len, dtype)),
            "cross_kv": enc_kv,
        }
    if fam == "vlm":
        k_grp = cfg.cross_attn_every
        n_groups = cfg.num_layers // k_grp
        n_self = k_grp - 1
        one = attn.init_kv_cache(cfg, batch, cache_len, dtype)
        cross = {
            "k": jnp.zeros((n_groups, batch, cfg.vision_tokens, cfg.num_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((n_groups, batch, cfg.vision_tokens, cfg.num_kv_heads, cfg.head_dim), dtype),
        }
        return {"self_kv": stack(n_groups, stack(n_self, one)), "cross_kv": cross}
    raise ValueError(f"no decode cache for family {fam}")


def decode_step(cfg, params: PyTree, cache: PyTree, tokens: jnp.ndarray, pos) -> Tuple[jnp.ndarray, PyTree]:
    """tokens: (B, S) int32 — S = 1 for one-token decode, S > 1 for a
    chunked teacher-forced prefill block (attention-cache families only;
    the recurrent families advance their state one token per call).

    pos: int32 position of tokens[:, 0] — a scalar when every lane is at
    the same position, or a (B,) vector of per-lane positions (continuous
    batching over staggered sequences; K/V rows scatter per lane). Token j
    of the chunk lands at position pos + j.

    Returns (logits (B,S,V) f32, new_cache)."""

    dtype = cm.dtype_of(cfg.dtype)
    fam = cfg.family
    B, S = tokens.shape
    if S != 1 and fam in ("ssm", "hybrid"):
        raise ValueError(
            f"family {fam!r} is recurrent: decode_step advances one token per "
            "call (chunked prefill uses the token-scan path, repro.serve.prefill)"
        )
    pos_col = pos[:, None] if jnp.ndim(pos) else jnp.full((B, 1), pos, jnp.int32)
    positions = pos_col + jnp.arange(S, dtype=jnp.int32)[None]
    x = _embed(cfg, params, tokens, dtype, positions=positions)

    if fam == "dense":
        flags = _flags(cfg)

        def body(h, inp):
            lp, fl, lc = inp
            h, newc = _dense_layer(cfg, lp, h, positions, fl, cache=lc, cache_pos=pos)
            return h, newc

        x, new_kv = jax.lax.scan(body, x, (params["layers"], flags, cache["kv"]))
        new_cache = {"kv": new_kv}

    elif fam == "moe":
        new_cache = {}
        if cfg.first_k_dense:
            def dbody(h, inp):
                lp, lc = inp
                h, newc = _dense_layer(cfg, lp, h, positions, jnp.asarray(False), cache=lc, cache_pos=pos)
                return h, newc

            x, ndkv = jax.lax.scan(dbody, x, (params["dense_layers"], cache["dense_kv"]))
            new_cache["dense_kv"] = ndkv

        def mbody(h, inp):
            lp, lc = inp
            h, _, newc = _moe_layer(cfg, lp, h, positions, cache=lc, cache_pos=pos)
            return h, newc

        x, nkv = jax.lax.scan(mbody, x, (params["layers"], cache["kv"]))
        new_cache["kv"] = nkv

    elif fam == "ssm":
        def rbody(h, inp):
            lp, st = inp
            out, st_att = ssm_mod.rwkv_time_mix_decode(
                cfg, lp["tmix"], cm.apply_norm(cfg, lp["ln1"], h), st
            )
            h = h + out
            out, st_ffn = ssm_mod.rwkv_channel_mix_decode(
                cfg, lp["cmix"], cm.apply_norm(cfg, lp["ln2"], h), st
            )
            h = h + out
            return h, {**st_att, **st_ffn}

        x, new_states = jax.lax.scan(rbody, x, (params["layers"], cache["layers"]))
        new_cache = {"layers": new_states}

    elif fam == "hybrid":
        new_cache = {}

        def mdec(h, inp):
            lp, st = inp
            out, newst = ssm_mod.mamba_decode(cfg, lp["mamba"], cm.apply_norm(cfg, lp["ln1"], h), st)
            return h + out, newst

        if "mamba_head" in params:
            x, nh = jax.lax.scan(mdec, x, (params["mamba_head"], cache["head"]))
            new_cache["head"] = nh

        shared = params["shared_attn"]

        def gbody(h, inp):
            grp_params, grp_state, akv = inp
            h, new_states = jax.lax.scan(mdec, h, (grp_params, grp_state))
            h, new_akv = _dense_layer(
                cfg, shared, h, positions, jnp.asarray(False), cache=akv, cache_pos=pos
            )
            return h, (new_states, new_akv)

        x, (ngs, nakv) = jax.lax.scan(
            gbody, x, (params["mamba_groups"], cache["groups"], cache["attn_kv"])
        )
        new_cache["groups"] = ngs
        new_cache["attn_kv"] = nakv

    elif fam == "audio":
        def dbody(h, inp):
            lp, lc, xkv = inp
            h, newc = _dense_layer_with_cross(
                cfg, lp, h, positions, memory_kv=xkv, cache=lc, cache_pos=pos
            )
            return h, newc

        x, nkv = jax.lax.scan(dbody, x, (params["layers"], cache["kv"], cache["cross_kv"]))
        new_cache = {"kv": nkv, "cross_kv": cache["cross_kv"]}

    elif fam == "vlm":
        def self_block(h, inp):
            lp, lc = inp
            h, newc = _dense_layer(cfg, lp, h, positions, jnp.asarray(False), cache=lc, cache_pos=pos)
            return h, newc

        def vgroup(h, inp):
            sg, cl, skv, xkv = inp
            h, nskv = jax.lax.scan(self_block, h, (sg, skv))
            a = attn.cross_attention(cfg, cl["xattn"], cm.apply_norm(cfg, cl["ln1"], h), memory_kv=xkv)
            h = h + jnp.tanh(cl["gate_attn"]).astype(h.dtype) * a
            m = cm.apply_mlp(cfg, cl["mlp"], cm.apply_norm(cfg, cl["ln2"], h))
            h = h + jnp.tanh(cl["gate_mlp"]).astype(h.dtype) * m
            return h, nskv

        x, nskv = jax.lax.scan(
            vgroup, x, (params["self_groups"], params["cross_layers"], cache["self_kv"], cache["cross_kv"])
        )
        new_cache = {"self_kv": nskv, "cross_kv": cache["cross_kv"]}

    else:
        raise ValueError(f"no decode path for family {fam}")

    x = cm.apply_norm(cfg, params["final_norm"], x)
    return _unembed(cfg, params, x), new_cache
