"""Pallas TPU kernel: blockwise cross-entropy over very large vocabularies.

The base-level loss of every data-optimization experiment in the paper is a
(sample-weighted) cross-entropy; with vocabularies up to 262 144 the logits
row does not fit VMEM, and a naive logsumexp materializes several (R, V)
temporaries in HBM. This kernel streams the vocabulary in (BR, BV) VMEM
blocks with an online max/sum-exp accumulator (flash-style), so each logit is
read exactly once for the forward and once for the backward.

Grid: (rows/BR, V/BV) — TPU iterates the last axis fastest, so the scratch
accumulators (m, l, target-logit) persist across a row-block's vocab sweep
and are finalized on the last vocab step.

Layout decisions (TPU): BV is a multiple of 128 (lane width), BR a multiple
of 8 (f32 sublanes). Per-row values (targets, lse, ce, the backward's row
weights) are (R, 1) columns, so their blocks are (BR, 1) tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ce_fwd_kernel(targets_ref, logits_ref, out_ce_ref, out_lse_ref, m_ref, l_ref, t_ref):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    block = logits_ref[...].astype(jnp.float32)  # (BR, BV)
    bv = block.shape[1]
    m_prev = m_ref[...]  # (BR, 1), as every per-row value
    m_cur = jnp.maximum(m_prev, jnp.max(block, axis=1, keepdims=True))
    scale = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * scale + jnp.sum(jnp.exp(block - m_cur), axis=1, keepdims=True)
    m_ref[...] = m_cur

    # pick out the target logit if it falls inside this vocab block
    local = targets_ref[...] - j * bv  # absolute ids -> block-local
    cols = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    t_ref[...] += jnp.sum(jnp.where(cols == local, block, 0.0), axis=1, keepdims=True)

    @pl.when(j == nv - 1)
    def _fin():
        lse = jnp.log(l_ref[...]) + m_ref[...]
        out_lse_ref[...] = lse
        out_ce_ref[...] = lse - t_ref[...]


def _ce_bwd_kernel(targets_ref, lse_ref, g_ref, logits_ref, dlogits_ref):
    j = pl.program_id(1)
    block = logits_ref[...].astype(jnp.float32)
    bv = block.shape[1]
    p = jnp.exp(block - lse_ref[...])
    local = targets_ref[...] - j * bv
    cols = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    onehot = (cols == local).astype(jnp.float32)
    dlogits_ref[...] = ((p - onehot) * g_ref[...]).astype(dlogits_ref.dtype)


def _pick_blocks(rows, v):
    # a row block that tiles f32 sublanes, else all rows (a block may span
    # a whole dimension); dispatch keeps the compiled path to rows that fit
    br = next((b for b in (128, 64, 32, 16, 8) if rows % b == 0), rows)
    bv = 2048 if v % 2048 == 0 else (512 if v % 512 == 0 else (128 if v % 128 == 0 else v))
    return br, bv


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray, interpret: bool = False):
    """logits: (R, V); targets: (R,) int32. Returns per-row CE (R,) f32."""
    ce, _ = _ce_fwd(logits, targets, interpret)
    return ce


def _ce_fwd(logits, targets, interpret):
    R, V = logits.shape
    BR, BV = _pick_blocks(R, V)
    grid = (R // BR, V // BV)
    row = pl.BlockSpec((BR, 1), lambda i, j: (i, 0))
    ce, lse = pl.pallas_call(
        _ce_fwd_kernel,
        grid=grid,
        in_specs=[row, pl.BlockSpec((BR, BV), lambda i, j: (i, j))],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((R, 1), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((BR, 1), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(targets.astype(jnp.int32).reshape(R, 1), logits)
    return ce[:, 0], lse


def _cross_entropy_fwd(logits, targets, interpret):
    ce, lse = _ce_fwd(logits, targets, interpret)
    return ce, (logits, targets, lse)


def _cross_entropy_bwd(interpret, res, g):
    logits, targets, lse = res
    R, V = logits.shape
    BR, BV = _pick_blocks(R, V)
    grid = (R // BR, V // BV)
    row = pl.BlockSpec((BR, 1), lambda i, j: (i, 0))
    tile = pl.BlockSpec((BR, BV), lambda i, j: (i, j))
    dlogits = pl.pallas_call(
        _ce_bwd_kernel,
        grid=grid,
        in_specs=[row, row, row, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((R, V), logits.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(targets.astype(jnp.int32).reshape(R, 1), lse,
      g.astype(jnp.float32).reshape(R, 1), logits)
    return dlogits, None


cross_entropy.defvjp(_cross_entropy_fwd, _cross_entropy_bwd)
