"""Pallas TPU kernel: fused SAMA Lion-adaptation product.

Lion's update direction is ``sign(c)`` with ``c = b1*m + (1-b1)*g``; the
exact derivative of ``sign`` is zero almost everywhere, which would make the
algorithmic-adaptation matrix vanish and reduce SAMA to SAMA-NA. Instead the
repo's Lion optimizer declares (see ``optim.lion``'s docstring) the smoothed
surrogate ``sign_d(c) = c / (|c| + delta)``, whose elementwise derivative

    du/dg = lr * (1-b1) * delta / (|c| + delta)^2

is the diagonal this kernel fuses against ``g_meta`` — one pass over
(g, m, g_meta) emitting the product tile plus a per-tile partial sum of
squares for the eps = alpha/||v|| step size (no second norm pass).

Layout, padding and the scalar inputs (the traced lr) are
``kernels.flat``'s, as for ``adam_adapt``.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flat import flat_product


def lion_adapt_product(
    g: jnp.ndarray,
    m: jnp.ndarray,
    g_meta: jnp.ndarray,
    *,
    lr=1.0,
    b1: float = 0.9,
    delta: float = 1e-3,
    interpret: bool = False,
):
    """Flat f32 arrays (N,). Returns (v_out (N,) f32, sumsq scalar f32)."""

    b1, delta = float(b1), float(delta)

    def formula(s, g, m, gm):
        c = b1 * m + (1.0 - b1) * g
        ad = jnp.abs(c) + delta
        diag = s[0] * (1.0 - b1) * delta / (ad * ad)
        return diag * gm

    return flat_product(formula, (lr,), (g, m, g_meta),
                        pad_values=(0.0, 0.0, 0.0), interpret=interpret)
