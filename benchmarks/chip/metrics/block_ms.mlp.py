"""Device milliseconds per meta step in the ``mlp`` block: the up (and gate)
and down projections and the activation of every layer, forward and
backward, over every phase, averaged over the cell's chips
(``blocks.py``)."""

import blocks


def read(ctx):
    return blocks.block_ms(ctx, "mlp")
