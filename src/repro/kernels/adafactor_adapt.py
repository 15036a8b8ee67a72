"""Pallas TPU kernel: fused SAMA Adafactor-adaptation product.

Adafactor's factored second moment couples every element of a row/column, so
its exact du/dg is not diagonal; the repo's Adafactor optimizer declares the
frozen-statistics diagonal ``lr / (sqrt(vhat) + eps)`` (see
``optim.adafactor``'s docstring — exact in the b2 -> 1 limit where the
factored statistics move slowly). The factored reconstruction
``vhat = rhat cx chat / mean(rhat)`` is a cheap rank-1 outer product computed
by the caller; this kernel fuses the remaining elementwise chain — rsqrt,
scale, product against ``g_meta``, and the per-tile partial sum of squares
for eps = alpha/||v|| — into one pass over (vhat, g_meta).

Layout, padding and the scalar inputs (the traced lr) are
``kernels.flat``'s, as for ``adam_adapt``.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flat import flat_product


def adafactor_adapt_product(
    vhat: jnp.ndarray,
    g_meta: jnp.ndarray,
    *,
    lr=1.0,
    eps: float = 1e-8,
    interpret: bool = False,
):
    """Flat f32 arrays (N,). ``vhat`` must be the bias-corrected second
    moment (non-negative). Returns (v_out (N,) f32, sumsq scalar f32)."""

    eps = float(eps)

    def formula(s, vhat, gm):
        return s[0] / (jnp.sqrt(vhat) + eps) * gm

    # vhat pads with ones, not zeros: 1/(sqrt(0)+eps) would be huge; with
    # ones the padded products are exact zeros for any eps
    return flat_product(formula, (lr,), (vhat, g_meta),
                        pad_values=(1.0, 0.0), interpret=interpret)
