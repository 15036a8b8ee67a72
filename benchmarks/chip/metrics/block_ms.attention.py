"""Device milliseconds per meta step in the ``attention`` block: the QKV and
output projections and the score, softmax and AV core, kernel or jnp, of
every self-attention layer (encoder and decoder; cross-attention is a block
of its own), forward and backward, over every phase, averaged over the
cell's chips (``blocks.py``)."""

import blocks


def read(ctx):
    return blocks.block_ms(ctx, "attention")
