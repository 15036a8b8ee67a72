"""Pallas TPU kernel: fused SAMA Adam-adaptation product.

SAMA's perturbation direction v = (du_adam/dg) .* g_meta (Eq. 4 + App. C)
touches four HBM-resident arrays (g, m, v, g_meta) and, written naively,
lowers to ~12 elementwise HLO ops with several HBM round-trips, plus a
separate reduction for eps = alpha/||v||_2. This kernel fuses the whole
chain into one pass: each (BLK,)-tile is read once, the adaptation diagonal
is computed in registers, and a per-tile partial sum of squares is emitted so
the norm needs no second pass over the data.

Layout, padding and the scalar inputs are ``kernels.flat``'s. The step
index ``t`` and learning rate ``lr`` are traced values in the hot path
(``state.count`` under jit, scheduled lr): the bias corrections are computed
from them outside the kernel and ride its SMEM scalar vector, so no step
forces a retrace.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flat import flat_product


def adam_adapt_product(
    g: jnp.ndarray,
    m: jnp.ndarray,
    v: jnp.ndarray,
    g_meta: jnp.ndarray,
    *,
    t,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    lr=1.0,
    interpret: bool = False,
):
    """Flat f32 arrays (N,). Returns (v_out (N,) f32, sumsq scalar f32).

    ``t`` and ``lr`` may be python numbers or traced scalars."""

    b1, b2, eps = float(b1), float(b2), float(eps)
    t = jnp.asarray(t, jnp.float32)
    bc1 = 1.0 - b1**t
    bc2 = 1.0 - b2**t

    def formula(s, g, m, v, gm):
        lr, bc1, bc2, a, b = s[0], s[1], s[2], s[3], s[4]
        m1 = b1 * m + (1.0 - b1) * g
        v1 = b2 * v + (1.0 - b2) * g * g
        mhat = m1 / bc1
        vhat = v1 / bc2
        sq = jnp.sqrt(vhat)
        denom = sq + eps
        diag = lr * (a / denom - mhat * b * g / (jnp.maximum(sq, 1e-15) * denom * denom))
        return diag * gm

    scalars = (lr, bc1, bc2, (1.0 - b1) / bc1, (1.0 - b2) / bc2)
    return flat_product(formula, scalars, (g, m, v, g_meta),
                        pad_values=(0.0, 0.0, 0.0, 0.0), interpret=interpret)
