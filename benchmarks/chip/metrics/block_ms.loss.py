"""Device milliseconds per meta step in the ``loss`` block: the per-token
cross-entropy of the base and meta losses (and the predictive entropy read
beside it), forward and backward, over every phase, averaged over the
cell's chips (``blocks.py``). The vocabulary projection before it is the
``unembed`` block, not this one."""

import blocks


def read(ctx):
    return blocks.block_ms(ctx, "loss")
