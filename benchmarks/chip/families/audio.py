"""Encoder-decoder speech model (the registry's ``whisper-small``): an
encoder over frame embeddings with sinusoidal positions, a decoder with
learned positions, causal self-attention, cross-attention to the encoder
and a GELU MLP in pre-LayerNorm blocks, and an unembedding tied to the
token embedding. The loss of an example is the mean next-token
cross-entropy over its decoder tokens."""

import jax
import jax.numpy as jnp

import flops
from reference import (attention, dense_layer_shapes, embed, layer_norm, mlp, scan_layers,
                       sinusoidal)


def theta_shapes(c):
    D = c["d_model"]
    norm = {"bias": (D,), "scale": (D,)}
    enc = dict(c, num_layers=c["encoder_layers"])
    return {"embed": (c["vocab_size"], D), "final_norm": dict(norm),
            "pos_embed": (c["max_position"], D),
            "encoder": {"layers": dense_layer_shapes(enc, cross=False), "norm": dict(norm)},
            "layers": dense_layer_shapes(c, cross=True)}


def per_example_loss(ein, c, theta, batch):
    """Mean next-token cross-entropy of each example, (B,)."""
    frames = batch["frames"]

    def enc_body(h, lp):
        h = h + attention(ein, c, lp["attn"], layer_norm(lp["ln1"], h))
        return h + mlp(ein, lp["mlp"], layer_norm(lp["ln2"], h))

    enc = frames + sinusoidal(frames.shape[1], c["d_model"])[None]
    enc = scan_layers(enc_body, enc, theta["encoder"]["layers"])
    memory = layer_norm(theta["encoder"]["norm"], enc)

    def dec_body(h, lp):
        h = h + attention(ein, c, lp["attn"], layer_norm(lp["ln1"], h), causal=True)
        h = h + attention(ein, c, lp["xattn"], layer_norm(lp["ln_x"], h), memory=memory)
        return h + mlp(ein, lp["mlp"], layer_norm(lp["ln2"], h))

    tokens = batch["tokens"]
    x = scan_layers(dec_body, embed(c, theta, tokens), theta["layers"])
    x = layer_norm(theta["final_norm"], x)
    logits = ein("bsd,vd->bsv", x[:, :-1], theta["embed"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(ce, axis=-1)


def forward_flops(c, mix):
    """Model FLOPs of one forward pass over one example: the encoder over
    its frames, the decoder with cross-attention, and the unembedding of
    the positions whose next token is scored."""
    s, t = mix["inputs"]["tokens"]["shape"][0], c["encoder_seq"]
    d, f, hd = c["d_model"], c["d_ff"], c["num_heads"] * c["head_dim"]
    enc = c["encoder_layers"] * flops.block_flops(t, d, f, hd)
    cross = 2 * s * d * hd * 2 + 2 * t * d * hd * 2 + 2 * 2 * s * t * hd
    dec = c["num_layers"] * (flops.block_flops(s, d, f, hd) + cross)
    return enc + dec + 2 * (s - 1) * d * c["vocab_size"]
