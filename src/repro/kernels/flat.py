"""Shared launcher for the flat SAMA adaptation-product kernels.

The adam/lion/adafactor adaptation products are one elementwise formula per
parameter, times the meta gradient, plus the sum of squares of the result
(SAMA's ``eps = alpha/||v||``). This module runs such a formula over flat
(N,) arrays in one pass:

* the arrays are viewed as (rows, 128) — lanes last, rows a multiple of the
  block height, which is a multiple of the f32 sublane tile (8) — and padded
  at the tail when N does not fill the last block;
* the scalars the formula needs (learning rate, bias corrections: traced
  values in the hot path, so never baked in as static parameters) ride one
  f32 vector in SMEM;
* the sum of squares accumulates into one (8, 128) output block that every
  grid step revisits (the grid is sequential), reduced to a scalar outside.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
#: rows per block: 512 x 128 f32 = 256 KiB per buffer, so four inputs and
#: one output, double-buffered, stay well inside the scoped VMEM budget
BLOCK_ROWS = 512


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def flat_product(
    formula: Callable[..., jnp.ndarray],
    scalars: Sequence,
    arrays: Sequence[jnp.ndarray],
    *,
    pad_values: Sequence[float],
    interpret: bool = False,
    block_rows: int = BLOCK_ROWS,
):
    """``formula(s, *blocks) -> out`` on f32 blocks (``s`` is the SMEM ref
    of ``scalars``); returns ``(out (N,) f32, sum(out**2) f32)``.

    ``pad_values[i]`` fills the tail of ``arrays[i]``; choose them so the
    formula stays finite and the padded outputs are zero."""

    (n,) = arrays[0].shape
    rows = _round_up(-(-n // LANES), SUBLANES)
    br = min(block_rows, rows)
    rows = _round_up(rows, br)
    pad = rows * LANES - n
    blocks = [
        (jnp.pad(x, (0, pad), constant_values=v) if pad else x).reshape(rows, LANES)
        for x, v in zip(arrays, pad_values)
    ]
    s = jnp.stack([jnp.asarray(x, jnp.float32) for x in scalars])

    def kernel(s_ref, *refs):
        *in_refs, out_ref, ss_ref = refs
        out = formula(s_ref, *(r[...].astype(jnp.float32) for r in in_refs))
        out_ref[...] = out

        @pl.when(pl.program_id(0) == 0)
        def _init():
            ss_ref[...] = jnp.zeros_like(ss_ref)

        sq = out * out
        ss_ref[...] += jnp.sum(sq.reshape(br // SUBLANES, SUBLANES, LANES), axis=0)

    tile = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    out, ss = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [tile] * len(blocks),
        out_specs=[tile, pl.BlockSpec((SUBLANES, LANES), lambda i: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((SUBLANES, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(s, *blocks)
    return out.reshape(-1)[:n], jnp.sum(ss)
