"""Blockwise flash attention + split-KV decode Pallas kernels (ISSUE 9).

Two kernels, both registered through :mod:`repro.kernels.dispatch`:

``flash_attention``
    Training forward/backward for GQA self-attention. The forward is the
    classic online-softmax blockwise scan over KV tiles (running max
    ``m``, running denominator ``l``, rescaled accumulator ``acc`` in
    VMEM scratch, finalized on the last KV block of each query tile).
    The backward is recompute-based: only ``(out, lse)`` are saved as
    residuals; score tiles are rebuilt from q/k in the dq and dk/dv
    kernels, so activation memory is O(B*S*H*Dh) instead of O(S*T).
    Supports causal masking, logit softcap (tanh), and sliding-window
    masking gated by a *traced* per-layer ``local_flag`` (the flag rides
    into the kernel as a tiny int32 SMEM input, so heterogeneous
    local/global layers inside a ``lax.scan`` over layers work without
    retracing).

GQA without grid races: q is laid out as ``(B*KV, G, S, Dh)`` so each
grid cell owns one (batch, kv-head) pair and its whole query group. The
kernels flatten the ``(G, block_q)`` rows into a single ``(G*block_q,
Dh)`` matmul operand, which means dk/dv accumulate contributions from
every query head of the group *inside* one grid cell — no revisited
output blocks across a parallel axis.

``flash_decode``
    Split-KV decode for the one-token path: stage 1 launches a grid of
    ``(B*KV, n_splits)`` cells, each producing a *normalized* partial
    output plus its log-sum-exp over one contiguous KV span; stage 2
    (:func:`merge_partials`, plain jnp) combines them with the standard
    log-sum-exp merge ``m* = max lse_i; out = sum_i exp(lse_i - m*) *
    o_i / sum_i exp(lse_i - m*)``. The split count comes from
    :func:`pick_splits`, an occupancy heuristic (enough grid cells to
    fill the cores, each split long enough to amortize the HBM DMA).
    Decode is inference-only: no VJP is defined.

Both kernels carry a ``ref`` twin that reproduces the existing
``models/attention.py`` ops *literally* (including the chunk-gate
selection between ``_sdpa`` and ``_chunked_sdpa``), so the default CPU
dispatch is bitwise-identical to the pre-kernel code and every tier-1
pin (scan-prefill bitwise equality, attribution FLOP bands) holds.

TPU layout: per-row values (query positions, ``m``, ``l``, ``lse``, the
backward's ``delta``) are (rows, 1) columns and key positions a (1, bk)
row, so a score tile's mask is one broadcast compare and every block's
last two dimensions tile (8, 128) or span the whole array dimension.

Masking convention shared with the ref path: padded positions are
``-1`` sentinels, masked scores are set to the finite ``NEG = -1e30``
(never ``-inf`` — fully-masked rows then produce ``l == 0`` and are
normalized by ``max(l, 1e-30)`` to exact zeros instead of NaN).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
_TINY = 1e-30

__all__ = [
    "flash_attention",
    "flash_attention_ref",
    "flash_decode",
    "flash_decode_ref",
    "merge_partials",
    "pick_splits",
]


def _pow2ceil(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def _pick_blocks(s: int, t: int) -> tuple[int, int]:
    """Query/KV tile sizes: 128 lanes when the problem affords it,
    shrunk (but >= 8 sublanes) for small shapes so padding stays cheap."""
    bq = max(8, min(128, _pow2ceil(s)))
    bk = max(8, min(128, _pow2ceil(t)))
    return bq, bk


def _pad_to(x, axis, mult, value):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _flag_array(local_flag, window: int):
    """Traced window-gate scalar as a (1,)-int32 kernel input."""
    if window <= 0 or local_flag is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.asarray(local_flag, jnp.int32).reshape(1)


def _tile_mask(qp, kp, *, causal: bool, window: int, use_window: bool, lf):
    """Validity of one score tile: ``qp`` is a (rows, 1) column and ``kp``
    a (1, bk) row of int32 positions; -1 marks padding. ``lf`` is the
    traced 0/1 gate."""
    valid = (kp >= 0) & (qp >= 0)
    if causal:
        valid &= kp <= qp
    if use_window:
        local = (qp - kp) < window
        valid &= jnp.where(lf != 0, local, True)
    return valid


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


# ---------------------------------------------------------------------------
# training forward
# ---------------------------------------------------------------------------


def _fwd_kernel(lf_ref, qp_ref, kp_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, softcap, window, causal, scale, g):
    j, nj = pl.program_id(2), pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bq, dh = q_ref.shape[2], q_ref.shape[3]
    rows = g * bq
    q = q_ref[0].astype(jnp.float32).reshape(rows, dh)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    valid = _tile_mask(qp_ref[0].reshape(rows, 1), kp_ref[...], causal=causal,
                       window=window, use_window=window > 0, lf=lf_ref[0])
    s = jnp.where(valid, s, NEG)

    m_prev = m_ref[...]                          # (rows, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_cur), 0.0)
    alpha = jnp.where(m_prev <= NEG, 0.0,
                      jnp.exp(jnp.minimum(m_prev - m_cur, 0.0)))
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_cur
    pv = jax.lax.dot_general(p, v_ref[0].astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_ref[...]
        out = acc_ref[...] / jnp.maximum(l, _TINY)
        o_ref[0] = out.reshape(g, bq, dh)
        lse = jnp.where(l > 0, m_ref[...] + jnp.log(jnp.maximum(l, _TINY)), NEG)
        lse_ref[0] = lse.reshape(g, bq, 1)


def _layouts(q, k, v, q_pos, kv_pos, bq, bk):
    """Fold GQA into per-(batch, kv-head) blocks and pad to tiles. Query
    positions are repeated per group member, (B, G, Sp, 1), so a q block's
    (G*bq) rows carry their own positions."""
    b, s, h, dh = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    q4 = q.reshape(b, s, kv, g, dh).transpose(0, 2, 3, 1, 4)
    q4 = _pad_to(q4.reshape(b * kv, g, s, dh), 2, bq, 0)
    k3 = _pad_to(k.transpose(0, 2, 1, 3).reshape(b * kv, t, dh), 1, bk, 0)
    v3 = _pad_to(v.transpose(0, 2, 1, 3).reshape(b * kv, t, dh), 1, bk, 0)
    qp = _pad_to(q_pos.astype(jnp.int32), 1, bq, -1)
    qp = jnp.broadcast_to(qp[:, None, :, None], (b, g, qp.shape[1], 1))
    kp = _pad_to(kv_pos.astype(jnp.int32).reshape(1, t), 1, bk, -1)
    return q4, k3, v3, qp, kp, (b, s, h, dh, t, kv, g)


def _fwd_impl(q, k, v, q_pos, kv_pos, lf, softcap, window, causal,
              interpret, bq, bk):
    q4, k3, v3, qp, kp, (b, s, h, dh, t, kv, g) = _layouts(
        q, k, v, q_pos, kv_pos, bq, bk)
    bh, sp, tp = q4.shape[0], q4.shape[2], k3.shape[1]
    grid = (bh, sp // bq, tp // bk)
    kernel = functools.partial(
        _fwd_kernel, softcap=float(softcap), window=int(window),
        causal=bool(causal), scale=1.0 / math.sqrt(dh), g=g)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _SMEM,
            pl.BlockSpec((1, g, bq, 1), lambda bb, i, j, kvh=kv: (bb // kvh, 0, i, 0)),
            pl.BlockSpec((1, bk), lambda bb, i, j: (0, j)),
            pl.BlockSpec((1, g, bq, dh), lambda bb, i, j: (bb, 0, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda bb, i, j: (bb, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda bb, i, j: (bb, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, g, bq, dh), lambda bb, i, j: (bb, 0, i, 0)),
            pl.BlockSpec((1, g, bq, 1), lambda bb, i, j: (bb, 0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, g, sp, dh), jnp.float32),
            jax.ShapeDtypeStruct((bh, g, sp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g * bq, 1), jnp.float32),
            pltpu.VMEM((g * bq, 1), jnp.float32),
            pltpu.VMEM((g * bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lf, qp, kp, q4, k3, v3)
    # (B*KV, G, Sp, Dh) -> (B, S, H, Dh)
    o = out[:, :, :s].reshape(b, kv, g, s, dh).transpose(0, 3, 1, 2, 4)
    return o.reshape(b, s, h, dh).astype(q.dtype), lse


# ---------------------------------------------------------------------------
# training backward (recompute)
# ---------------------------------------------------------------------------


def _bwd_tile(q, k, v, do, qp, kp, lf, lse, delta,
              *, softcap, window, causal, scale):
    """Recompute p/ds for one tile. q/do are (rows, Dh) row blocks, k/v
    (bk, Dh); qp/lse/delta are (rows, 1) columns, kp a (1, bk) row."""
    s_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
    if softcap:
        tt = jnp.tanh(s_raw / softcap)
        s = softcap * tt
        dcap = 1.0 - tt * tt
    else:
        s = s_raw
        dcap = 1.0
    valid = _tile_mask(qp, kp, causal=causal, window=window,
                       use_window=window > 0, lf=lf)
    # lse == NEG marks fully-masked/padded rows; exp would overflow to
    # +inf in the dead branch, so clamp the subtrahend first.
    lse_safe = jnp.where(lse <= NEG, 0.0, lse)
    p = jnp.where(valid, jnp.exp(s - lse_safe), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * dcap * scale
    return p, ds


def _tile_inputs(g, qp_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref):
    bq, dh = q_ref.shape[2], q_ref.shape[3]
    rows = g * bq
    return (q_ref[0].astype(jnp.float32).reshape(rows, dh),
            k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            do_ref[0].astype(jnp.float32).reshape(rows, dh),
            qp_ref[0].reshape(rows, 1), lse_ref[0].reshape(rows, 1),
            delta_ref[0].reshape(rows, 1))


def _dq_kernel(lf_ref, qp_ref, kp_ref, q_ref, k_ref, v_ref, do_ref,
               lse_ref, delta_ref, dq_ref, dq_acc,
               *, softcap, window, causal, scale, g):
    j, nj = pl.program_id(2), pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q, k, v, do, qp, lse, delta = _tile_inputs(
        g, qp_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref)
    _, ds = _bwd_tile(q, k, v, do, qp, kp_ref[...], lf_ref[0], lse, delta,
                      softcap=softcap, window=window, causal=causal, scale=scale)
    dq_acc[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].reshape(dq_ref.shape[1:])


def _dkv_kernel(lf_ref, qp_ref, kp_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                *, softcap, window, causal, scale, g):
    j, nj = pl.program_id(2), pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q, k, v, do, qp, lse, delta = _tile_inputs(
        g, qp_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref)
    p, ds = _bwd_tile(q, k, v, do, qp, kp_ref[...], lf_ref[0], lse, delta,
                      softcap=softcap, window=window, causal=causal, scale=scale)
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...]
        dv_ref[0] = dv_acc[...]


def _bwd_impl(q, k, v, q_pos, kv_pos, lf, out, lse, g_out,
              softcap, window, causal, interpret, bq, bk):
    q4, k3, v3, qp, kp, (b, s, h, dh, t, kv, g) = _layouts(
        q, k, v, q_pos, kv_pos, bq, bk)
    do4 = g_out.reshape(b, s, kv, g, dh).transpose(0, 2, 3, 1, 4)
    do4 = _pad_to(do4.reshape(b * kv, g, s, dh), 2, bq, 0)
    # delta = rowsum(dO * O), computed once in plain jnp (f32)
    delta = jnp.sum(g_out.astype(jnp.float32) * out.astype(jnp.float32), -1)
    delta = delta.reshape(b, s, kv, g).transpose(0, 2, 3, 1)
    delta = _pad_to(delta.reshape(b * kv, g, s), 2, bq, 0)[..., None]
    # lse from the forward is already padded (B*KV, G, Sp, 1)
    bh, sp, tp = q4.shape[0], q4.shape[2], k3.shape[1]
    nq, nk = sp // bq, tp // bk
    scale = 1.0 / math.sqrt(dh)
    common = dict(softcap=float(softcap), window=int(window),
                  causal=bool(causal), scale=scale, g=g)
    semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    def specs(qi, kj):
        """BlockSpecs of the nine inputs, given how the (bb, i, j) grid
        index picks the query tile (``qi``) and the KV tile (``kj``)."""
        return [
            _SMEM,                                                          # lf
            pl.BlockSpec((1, g, bq, 1),
                         lambda bb, i, j, kvh=kv: (bb // kvh, 0, qi(i, j), 0)),  # qp
            pl.BlockSpec((1, bk), lambda bb, i, j: (0, kj(i, j))),          # kp
            pl.BlockSpec((1, g, bq, dh), lambda bb, i, j: (bb, 0, qi(i, j), 0)),  # q
            pl.BlockSpec((1, bk, dh), lambda bb, i, j: (bb, kj(i, j), 0)),  # k
            pl.BlockSpec((1, bk, dh), lambda bb, i, j: (bb, kj(i, j), 0)),  # v
            pl.BlockSpec((1, g, bq, dh), lambda bb, i, j: (bb, 0, qi(i, j), 0)),  # do
            pl.BlockSpec((1, g, bq, 1), lambda bb, i, j: (bb, 0, qi(i, j), 0)),   # lse
            pl.BlockSpec((1, g, bq, 1), lambda bb, i, j: (bb, 0, qi(i, j), 0)),   # delta
        ]

    args = (lf, qp, kp, q4, k3, v3, do4, lse, delta)
    # dq: query tiles on the middle axis, KV tiles innermost
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(bh, nq, nk),
        in_specs=specs(lambda i, j: i, lambda i, j: j),
        out_specs=[pl.BlockSpec((1, g, bq, dh),
                                lambda bb, i, j: (bb, 0, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, g, sp, dh), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g * bq, dh), jnp.float32)],
        compiler_params=semantics,
        interpret=interpret,
    )(*args)[0]

    # dk/dv: KV tiles on the middle axis, query tiles innermost, so the
    # (bk, Dh) scratch accumulates over every query block of one KV tile
    # before finalizing.
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(bh, nk, nq),
        in_specs=specs(lambda i, j: j, lambda i, j: i),
        out_specs=[pl.BlockSpec((1, bk, dh), lambda bb, i, j: (bb, i, 0)),
                   pl.BlockSpec((1, bk, dh), lambda bb, i, j: (bb, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, tp, dh), jnp.float32),
                   jax.ShapeDtypeStruct((bh, tp, dh), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bk, dh), jnp.float32),
                        pltpu.VMEM((bk, dh), jnp.float32)],
        compiler_params=semantics,
        interpret=interpret,
    )(*args)

    dq = dq[:, :, :s].reshape(b, kv, g, s, dh).transpose(0, 3, 1, 2, 4)
    dq = dq.reshape(b, s, h, dh).astype(q.dtype)
    dk = dk[:, :t].reshape(b, kv, t, dh).transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv[:, :t].reshape(b, kv, t, dh).transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash(q, k, v, q_pos, kv_pos, lf, softcap, window, causal,
           interpret, bq, bk):
    out, _ = _fwd_impl(q, k, v, q_pos, kv_pos, lf, softcap, window, causal,
                       interpret, bq, bk)
    return out


def _flash_fwd(q, k, v, q_pos, kv_pos, lf, softcap, window, causal,
               interpret, bq, bk):
    out, lse = _fwd_impl(q, k, v, q_pos, kv_pos, lf, softcap, window, causal,
                         interpret, bq, bk)
    return out, (q, k, v, q_pos, kv_pos, lf, out, lse)


def _flash_bwd(softcap, window, causal, interpret, bq, bk, res, g_out):
    q, k, v, q_pos, kv_pos, lf, out, lse = res
    dq, dk, dv = _bwd_impl(q, k, v, q_pos, kv_pos, lf, out, lse, g_out,
                           softcap, window, causal, interpret, bq, bk)
    return dq, dk, dv, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, q_pos, kv_pos, local_flag=None, *,
                    softcap=0.0, window=0, causal=True, chunk=0,
                    interpret=False, block_q=None, block_k=None):
    """Pallas blockwise flash attention.

    q: (B, S, H, Dh); k/v: (B, T, KV, Dh) with H % KV == 0;
    q_pos: (B, S) int32; kv_pos: (T,) int32 (-1 = padding);
    local_flag: optional traced scalar bool gating the sliding window.
    ``chunk`` is accepted for call-convention parity with the ref
    backend and ignored — the kernel's own KV blocking subsumes it.
    Returns (B, S, H, Dh) in q.dtype.
    """
    del chunk
    b, s, h, dh = q.shape
    t = k.shape[1]
    bq, bk = _pick_blocks(s, t)
    if block_q:
        bq = block_q
    if block_k:
        bk = block_k
    use_window = window if (window and local_flag is not None) else 0
    lf = _flag_array(local_flag, use_window)
    return _flash(q, k, v, q_pos.astype(jnp.int32),
                  jnp.asarray(kv_pos, jnp.int32), lf,
                  float(softcap or 0.0), int(use_window), bool(causal),
                  bool(interpret), int(bq), int(bk))


def flash_attention_ref(q, k, v, q_pos, kv_pos, local_flag=None, *,
                        softcap=0.0, window=0, causal=True, chunk=0,
                        **_ignored):
    """Reference twin: literally the pre-kernel models/attention.py ops,
    including the chunk-gate selection — the default CPU path must stay
    bitwise-identical to the seed behavior."""
    from repro.models import attention as attn  # lazy: avoids import cycle

    b, s, h, dh = q.shape
    kv = k.shape[2]
    if chunk and s > chunk:
        return attn._chunked_sdpa(
            q.reshape(b, s, kv, h // kv, dh), k, v, q_pos, kv_pos,
            chunk=chunk, softcap=softcap, local_flag=local_flag,
            window=window, causal=causal)
    # a sliding window applies without causality too (as in _chunked_sdpa
    # and the kernel's _tile_mask); only the all-keys case needs no mask
    windowed = bool(window) and local_flag is not None
    mask = (attn.make_mask(q_pos, kv_pos, causal=causal,
                           local_flag=local_flag, window=window)
            if causal or windowed else None)
    return attn._sdpa(q, k, v, mask, softcap=softcap)


# ---------------------------------------------------------------------------
# split-KV decode
# ---------------------------------------------------------------------------


def pick_splits(t: int, bh: int, *, min_split: int = 128,
                target_cells: int = 64, max_splits: int = 16) -> int:
    """Occupancy heuristic for the decode KV split count.

    Enough ``(B*KV, n_splits)`` grid cells to occupy ``target_cells``
    cores, but never splits shorter than ``min_split`` tokens (the DMA
    would dominate) and never more than ``max_splits`` (stage-2 merge
    cost grows linearly).
    """
    by_len = max(1, math.ceil(t / min_split))
    want = max(1, math.ceil(target_cells / max(bh, 1)))
    return max(1, min(by_len, want, max_splits))


def merge_partials(o, lse):
    """Two-stage softmax combine: ``o`` is (..., n_splits, G, Dh) of
    *normalized* partial outputs, ``lse`` (..., n_splits, G) their
    log-sum-exps (NEG for empty splits). Returns (..., G, Dh)."""
    m = jnp.max(lse, axis=-2, keepdims=True)
    w = jnp.exp(lse - m)                       # empty splits: exp(NEG-m)->0
    denom = jnp.sum(w, axis=-2)
    out = jnp.sum(w[..., None] * o, axis=-3)
    return out / jnp.maximum(denom, _TINY)[..., None]


def _decode_kernel(pos_ref, lf_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   *, softcap, window, scale, t, split, kv):
    si = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)           # (G, Dh)
    k = k_ref[0].astype(jnp.float32)           # (split, Dh)
    v = v_ref[0].astype(jnp.float32)
    g = q.shape[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    pos = pos_ref[pl.program_id(0) // kv]
    idx = si * split + jax.lax.broadcasted_iota(jnp.int32, (g, split), 1)
    valid = (idx <= pos) & (idx < t)
    if window > 0:
        local = (pos - idx) < window
        valid &= jnp.where(lf_ref[0] != 0, local, True)
    s = jnp.where(valid, s, NEG)
    m = jnp.max(s, axis=1, keepdims=True)      # (G, 1)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0, 0] = o / jnp.maximum(l, _TINY)
    lse_ref[0, 0] = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, _TINY)), NEG)


def flash_decode(q, k, v, q_pos, local_flag=None, *, softcap=0.0, window=0,
                 interpret=False, n_splits=None):
    """Split-KV decode: q (B, 1, H, Dh), k/v (B, T, KV, Dh), q_pos (B, 1)
    per-lane positions. Inference-only (no VJP). Returns (B, 1, H, Dh)."""
    b, s, h, dh = q.shape
    assert s == 1, "flash_decode is the one-token path"
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    bh = b * kv
    if n_splits is None:
        n_splits = pick_splits(t, bh)
    # KV spans tile f32 sublanes; a span past T is masked (idx < t) and
    # merges as an empty split
    split = -(-math.ceil(t / n_splits) // 8) * 8
    tp = split * n_splits
    q3 = q[:, 0].reshape(b, kv, g, dh).reshape(bh, g, dh)
    k3 = _pad_to(k.transpose(0, 2, 1, 3).reshape(bh, t, dh), 1, split, 0)
    v3 = _pad_to(v.transpose(0, 2, 1, 3).reshape(bh, t, dh), 1, split, 0)
    pos = q_pos.astype(jnp.int32).reshape(b)
    use_window = window if (window and local_flag is not None) else 0
    lf = _flag_array(local_flag, use_window)
    kernel = functools.partial(
        _decode_kernel, softcap=float(softcap or 0.0), window=int(use_window),
        scale=1.0 / math.sqrt(dh), t=t, split=split, kv=kv)
    o_part, lse_part = pl.pallas_call(
        kernel,
        grid=(bh, n_splits),
        in_specs=[
            _SMEM,
            _SMEM,
            pl.BlockSpec((1, g, dh), lambda bb, si: (bb, 0, 0)),
            pl.BlockSpec((1, split, dh), lambda bb, si: (bb, si, 0)),
            pl.BlockSpec((1, split, dh), lambda bb, si: (bb, si, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda bb, si: (bb, si, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda bb, si: (bb, si, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_splits, g, dh), jnp.float32),
            jax.ShapeDtypeStruct((bh, n_splits, g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(pos, lf, q3, k3, v3)
    out = merge_partials(o_part, lse_part[..., 0])     # (B*KV, G, Dh)
    out = out.reshape(b, kv, g, dh).reshape(b, 1, h, dh)
    return out.astype(q.dtype)


def flash_decode_ref(q, k, v, q_pos, local_flag=None, *, softcap=0.0,
                     window=0, **_ignored):
    """Reference twin: exactly the pre-kernel decode ops
    (make_mask over arange(T) + _sdpa)."""
    from repro.models import attention as attn  # lazy: avoids import cycle

    t = k.shape[1]
    mask = attn.make_mask(q_pos, jnp.arange(t), causal=True,
                          local_flag=local_flag, window=window)
    return attn._sdpa(q, k, v, mask, softcap=softcap)
