"""Three-term roofline analysis for dry-run jobs.

    compute_s    = FLOPs_global / (chips * peak_flops)
    memory_s     = HBM_bytes_global / (chips * hbm_bw)
    collective_s = collective_bytes_per_device / ici_bw

Measurement methodology (see EXPERIMENTS.md §Method):

* XLA's ``compiled.cost_analysis()`` counts while-loop (lax.scan) bodies
  ONCE — verified empirically — so raw HLO flops/bytes undercount scanned
  layer stacks by ~num_layers. We therefore use **analytic accounting**
  (exact matmul/attention/scan/moe-dispatch terms from the architecture
  config — the standard MFU methodology) for compute and memory, and keep
  the raw HLO numbers in the record labeled ``hlo_*_body_once``.
* Collective bytes come from the partitioned HLO with **trip-count
  correction** (roofline.hlo_parse): every collective inside a scan body is
  scaled by the loop's known_trip_count. cost_analysis cannot see these at
  all. Transfer model: result bytes / one ICI link — a stated lower bound.
* compute/memory terms assume ideal sharding (global / chips); the HLO is
  the structural witness that the program actually partitions.

Hardware: per-chip peaks come from ``DEVICE_PEAKS``, keyed by
``jax.Device.device_kind``; the dry run plans for ``TARGET_DEVICE_KIND``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax

from repro.roofline import hlo_parse

@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    ici_bw: float  # interconnect bytes/s per link
    hbm_bytes: int  # HBM capacity per chip


#: Published per-chip peaks keyed by ``device_kind``. Source: Google Cloud
#: documentation, "TPU v5e" system architecture: 197 TFLOP/s bf16, 16 GB
#: HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect (4 links of
#: 50 GB/s each).
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                               hbm_bytes=16 * 2**30),
}

#: The chip the dry run's production mesh is made of.
TARGET_DEVICE_KIND = "TPU v5 lite"


def device_peaks(device_kind: str) -> DevicePeaks:
    """The published peaks of ``device_kind``; an unknown device raises."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(DEVICE_PEAKS)}") from None


ACT_BYTES = 2  # bf16 activations
LOGIT_BYTES = 4  # f32 logits
META_FRACTION = 8  # meta batch = base batch / 8 in the SAMA train job


def param_counts(param_shapes) -> Dict[str, int]:
    total = experts = embed = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(param_shapes)[0]:
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        key = jax.tree_util.keystr(path)
        if "experts" in key:
            experts += n
        if "embed" in key:  # embed + pos_embed: gathers, not matmuls
            embed += n
    return {"total": total, "experts": experts, "embed": embed}


# ---------------------------------------------------------------------------
# analytic FLOPs
# ---------------------------------------------------------------------------


def _attn_flops(cfg, batch, s_q, t_kv):
    """Self/cross attention score+AV flops for one forward pass, per layer."""
    if cfg.use_mla:
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        return 2 * batch * cfg.num_heads * s_q * t_kv * (dn + dr + dv)
    return 4 * batch * cfg.num_heads * s_q * t_kv * cfg.head_dim


def _moe_dispatch_flops(cfg, tokens):
    """GShard one-hot dispatch + combine einsums per MoE layer."""
    from repro.models.moe import MOE_GROUP

    g = min(MOE_GROUP, tokens)
    cap = max(int(cfg.capacity_factor * cfg.top_k * g / cfg.num_experts), 4)
    per_group = 2 * g * cfg.num_experts * cap * cfg.d_model * 2  # dispatch+combine
    return (tokens // g) * per_group


def _ssm_scan_flops(cfg, batch, seq):
    """Mamba2 SSD chunkwise flops per layer (intra matmuls + state updates)."""
    d_inner = cfg.ssm_expand * cfg.d_model
    n = cfg.ssm_state
    q = min(cfg.ssm_chunk, seq)
    intra = 2 * batch * seq * q * (n + d_inner)
    inter = 4 * batch * seq * d_inner * n
    return intra + inter


def _rwkv_scan_flops(cfg, batch, seq):
    d = cfg.d_model
    k = cfg.rwkv_head_dim
    q = min(cfg.ssm_chunk, seq)
    intra = 4 * batch * seq * q * d  # (i,j,channel) products
    inter = 4 * batch * seq * d * k
    return intra + inter


def forward_flops(cfg, counts, batch, s_q, t_kv=None) -> float:
    """One forward pass over (batch, s_q) query tokens (kv length t_kv)."""

    t_kv = t_kv if t_kv is not None else s_q
    tokens = batch * s_q

    n_matmul = counts["total"] - counts["embed"] - counts["experts"]
    n_matmul += cfg.vocab_size * cfg.d_model  # tied unembed
    if cfg.num_experts:
        n_matmul += counts["experts"] * cfg.top_k / cfg.num_experts
    total = 2.0 * tokens * n_matmul

    fam = cfg.family
    if fam in ("dense", "encoder", "moe"):
        n_attn_layers = cfg.num_layers
        kinds = cfg.layer_kinds
        for kind in kinds:
            t_eff = min(cfg.sliding_window, t_kv) if (kind == "local" and cfg.sliding_window) else t_kv
            total += _attn_flops(cfg, batch, s_q, t_eff)
        if fam == "moe":
            total += (cfg.num_layers - cfg.first_k_dense) * _moe_dispatch_flops(cfg, tokens)
    elif fam == "hybrid":
        n_groups = cfg.num_layers // cfg.hybrid_attn_every
        total += cfg.num_layers * _ssm_scan_flops(cfg, batch, s_q)
        total += n_groups * _attn_flops(cfg, batch, s_q, t_kv)
    elif fam == "ssm":
        total += cfg.num_layers * _rwkv_scan_flops(cfg, batch, s_q)
    elif fam == "vlm":
        n_groups = cfg.num_layers // cfg.cross_attn_every
        n_self = n_groups * (cfg.cross_attn_every - 1)
        total += n_self * _attn_flops(cfg, batch, s_q, t_kv)
        total += n_groups * _attn_flops(cfg, batch, s_q, cfg.vision_tokens)
    elif fam == "audio":
        f = cfg.encoder_seq
        total += cfg.encoder_layers * _attn_flops(cfg, batch, f, f)  # encoder (runs every fwd)
        total += cfg.num_layers * (_attn_flops(cfg, batch, s_q, t_kv) + _attn_flops(cfg, batch, s_q, f))
    return total


def step_flops(cfg, counts, shape, kind: str) -> float:
    """Whole-step analytic flops. Train = the SAMA bilevel step:
    base fwd+bwd (3x fwd) + meta pass (3x fwd, B/8) + 2 central-difference
    forwards (their lambda-backward is cut by the feature stop-gradient)."""

    b, s = shape.global_batch, shape.seq_len
    if kind == "train":
        f_base = forward_flops(cfg, counts, b, s)
        f_meta = forward_flops(cfg, counts, max(b // META_FRACTION, 1), s)
        return 3 * f_base + 3 * f_meta + 2 * f_base
    if kind == "prefill":
        return forward_flops(cfg, counts, b, s)
    # decode: one token against a cache of length seq_len
    if cfg.family == "audio":
        # decode does NOT rerun the encoder (cross-kv cached)
        f = forward_flops(cfg, counts, b, 1, t_kv=s)
        f -= cfg.encoder_layers * _attn_flops(cfg, b, cfg.encoder_seq, cfg.encoder_seq)
        return f
    return forward_flops(cfg, counts, b, 1, t_kv=s)


# ---------------------------------------------------------------------------
# analytic HBM traffic
# ---------------------------------------------------------------------------


def _activation_traffic(cfg, batch, s_q, t_kv) -> float:
    """Rough per-pass activation HBM traffic: ~8 read/writes of the residual
    stream per block plus attention score materialization (f32 read+write) —
    the latter is what flash/blockwise attention removes (see §Perf)."""

    tokens = batch * s_q
    blocks = cfg.num_layers + (cfg.encoder_layers if cfg.family == "audio" else 0)
    stream = 8.0 * tokens * cfg.d_model * ACT_BYTES * blocks
    scores = 0.0
    if cfg.family in ("dense", "encoder", "moe", "vlm", "audio"):
        for kind in cfg.layer_kinds:
            t_eff = min(cfg.sliding_window, t_kv) if (kind == "local" and cfg.sliding_window) else t_kv
            scores += 8.0 * batch * cfg.num_heads * s_q * t_eff  # f32 write+read
    logits = 0.0
    if cfg.family != "encoder":
        logits = tokens * cfg.vocab_size * LOGIT_BYTES
    return stream + scores + logits


def step_bytes(cfg, counts, shape, kind: str, cache_bytes: int = 0) -> float:
    b, s = shape.global_batch, shape.seq_len
    params_bytes = counts["total"] * ACT_BYTES  # bf16 params in the dry-run
    if kind == "train":
        # fwd reads W; bwd reads W + writes grad; x4 passes; optimizer reads/
        # writes f32-equiv moments (bf16 here) — ~8x params traffic total.
        t = 8.0 * params_bytes
        t += 3.0 * _activation_traffic(cfg, b, s, s)  # base fwd+bwd
        t += 3.0 * _activation_traffic(cfg, max(b // META_FRACTION, 1), s, s)
        t += 2.0 * _activation_traffic(cfg, b, s, s)  # central-difference fwds
        return t
    if kind == "prefill":
        return params_bytes + _activation_traffic(cfg, b, s, s)
    # decode: params once + cache read/write + small activations
    t = params_bytes + 2.0 * cache_bytes
    t += _activation_traffic(cfg, b, 1, s)
    return t


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Roofline:
    name: str
    flops_global: float
    bytes_global: float
    collective_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    useful_ratio: float  # model matmul flops / total analytic flops
    peak_memory_bytes: Optional[int]
    hlo_flops_body_once: float
    hlo_bytes_body_once: float
    collectives: Dict[str, Any]

    def as_dict(self):
        return dataclasses.asdict(self)


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` returns a dict on modern jax but a
    per-partition list of dicts on 0.4.x — normalize to one dict."""

    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost or {}


def analyze(name: str, compiled, hlo_text: str, cfg, shape, kind: str,
            param_shapes, n_devices: int, cache_shapes=None,
            device_kind: str = TARGET_DEVICE_KIND) -> Roofline:
    peaks = device_peaks(device_kind)
    counts = param_counts(param_shapes)
    cache_bytes = 0
    if cache_shapes is not None:
        for leaf in jax.tree_util.tree_leaves(cache_shapes):
            n = 1
            for d in leaf.shape:
                n *= d
            cache_bytes += n * leaf.dtype.itemsize

    flops = step_flops(cfg, counts, shape, kind)
    mem = step_bytes(cfg, counts, shape, kind, cache_bytes)
    coll = hlo_parse.collective_stats(hlo_text)

    compute_s = flops / (n_devices * peaks.flops)
    memory_s = mem / (n_devices * peaks.hbm_bw)
    collective_s = coll["total_bytes"] / peaks.ici_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)

    # "useful" = pure matmul-param flops (6ND-style) over everything the step does
    n_matmul = counts["total"] - counts["embed"] - counts["experts"] + cfg.vocab_size * cfg.d_model
    if cfg.num_experts:
        n_matmul += counts["experts"] * cfg.top_k / cfg.num_experts
    tokens = shape.global_batch * shape.seq_len
    if kind == "train":
        useful = (6 + 6 / META_FRACTION + 4) * n_matmul * tokens
    elif kind == "prefill":
        useful = 2 * n_matmul * tokens
    else:
        useful = 2 * n_matmul * shape.global_batch
    useful_ratio = useful / flops if flops else 0.0

    cost = cost_analysis_dict(compiled)
    peak_mem = None
    try:
        stats = compiled.memory_analysis()
        peak_mem = int(
            stats.argument_size_in_bytes + stats.output_size_in_bytes + stats.temp_size_in_bytes
        )
    except Exception:
        pass

    return Roofline(
        name=name,
        flops_global=flops,
        bytes_global=mem,
        collective_bytes_per_device=coll["total_bytes"],
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        useful_ratio=useful_ratio,
        peak_memory_bytes=peak_mem,
        hlo_flops_body_once=float(cost.get("flops", 0.0)),
        hlo_bytes_body_once=float(cost.get("bytes accessed", 0.0)),
        collectives=coll,
    )
