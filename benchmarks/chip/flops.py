"""Operations and bytes, counted from shapes.

``step_flops`` is the model FLOPs of one SAMA meta step: the matrix
products the algorithm needs, counted once (recomputation under remat is
not counted). One forward pass over an example costs ``forward_flops``,
which the model family's file gives; its backward pass twice that. A meta step runs

* ``unroll`` base passes, forward and backward, over the base batch;
* one meta pass, forward and backward, over the meta batch;
* two central-difference passes over the last base batch. They take the
  gradient with respect to lam only, which enters through the weight net
  on each example's (stopped) loss, so they are forward passes alone.

This corrects ``repro.roofline.analysis.step_flops`` for these cells: it
took a meta batch of B/8 (the learner is given B/2), one base pass
whatever the unroll, an unembedding for the 4-way classifier, and bf16
activations for its bytes.

``adam_adapt_cost`` is the fused Adam adaptation product's operations and
HBM bytes over every parameter leaf, with the kernel's padding.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax

from reference import family, theta_shapes


def block_flops(s: int, d: int, f: int, hd: int) -> int:
    """Self-attention (q, k, v, o projections and the two score products)
    and the MLP, over ``s`` positions."""
    return 2 * s * d * hd * 4 + 2 * 2 * s * s * hd + 2 * 2 * s * d * f


def forward_flops(c: Dict[str, Any], mix: Dict[str, Any]) -> int:
    """FLOPs of one forward pass over one example, from the model family's
    file (``families/<family>.py``)."""
    return family(c).forward_flops(c, mix)


def step_flops(c: Dict[str, Any], mix: Dict[str, Any], chips: int) -> int:
    """Model FLOPs of one meta step over all chips."""
    fwd = forward_flops(c, mix)
    b = mix["batch_per_chip"] * chips
    bm = mix["meta_batch_per_chip"] * chips
    return fwd * (3 * mix["unroll"] * b + 3 * bm + 2 * b)


LANES, SUBLANES, BLOCK_ROWS = 128, 8, 512
#: elementwise operations per element of the Adam adaptation product
ADAM_ADAPT_OPS = 20


def _padded(n: int) -> int:
    rows = -(-(-(-n // LANES)) // SUBLANES) * SUBLANES
    br = min(BLOCK_ROWS, rows)
    return -(-rows // br) * br * LANES


def adam_adapt_cost(c: Dict[str, Any]) -> Dict[str, float]:
    """Operations and HBM bytes of one meta step's adaptation product: per
    leaf, four f32 inputs read (g, m, v, g_meta) and one f32 output written
    over the padded length."""
    leaves = jax.tree_util.tree_leaves(theta_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    n = sum(_padded(math.prod(s)) for s in leaves)
    return {"flops": ADAM_ADAPT_OPS * n, "bytes": 5 * 4 * n, "calls": len(leaves)}
