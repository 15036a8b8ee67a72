"""Encoder classifier (the registry's ``bert-base``): learned positions,
pre-LayerNorm blocks of bidirectional multi-head attention and a GELU MLP,
a final norm, and a linear head on the first token."""

import jax
import jax.numpy as jnp

import flops
from reference import attention, dense_layer_shapes, embed, layer_norm, mlp, scan_layers


def theta_shapes(c):
    D = c["d_model"]
    return {"embed": (c["vocab_size"], D), "final_norm": {"bias": (D,), "scale": (D,)},
            "pos_embed": (c["max_position"], D), "layers": dense_layer_shapes(c, cross=False),
            "cls_head": {"w": (D, c["num_labels"]), "b": (c["num_labels"],)}}


def per_example_loss(ein, c, theta, batch):
    """Cross-entropy of each example's label, (B,)."""
    def body(h, lp):
        h = h + attention(ein, c, lp["attn"], layer_norm(lp["ln1"], h))
        return h + mlp(ein, lp["mlp"], layer_norm(lp["ln2"], h))

    x = scan_layers(body, embed(c, theta, batch["tokens"]), theta["layers"])
    cls = layer_norm(theta["final_norm"], x)[:, 0]
    logits = ein("bd,dl->bl", cls, theta["cls_head"]["w"]) + theta["cls_head"]["b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0]


def forward_flops(c, mix):
    """Model FLOPs of one forward pass over one example."""
    s = mix["inputs"]["tokens"]["shape"][0]
    hd = c["num_heads"] * c["head_dim"]
    return (c["num_layers"] * flops.block_flops(s, c["d_model"], c["d_ff"], hd)
            + 2 * c["d_model"] * c["num_labels"])
