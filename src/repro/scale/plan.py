"""The HBM-budget memory planner (DESIGN.md §11).

``plan_microbatch`` answers the deployment question the paper's memory
claims raise: *given this model, this mesh and this much HBM per device,
how little accumulation can I get away with?* It binary-searches the
candidate microbatch counts (divisors of the batch's leading dims) for the
SMALLEST M — i.e. the largest fitting microbatch — whose compiled step
fits the budget, measuring each candidate with ``repro.perf.memory``:

* primary source: ``compiled.memory_analysis()`` of the lowered+compiled
  step (argument + output + temp - alias per device, the same peak
  composition every BENCH_*.json reports). Compilation happens on
  ShapeDtypeStructs — no device allocation, so planning a 90B config on a
  laptop works exactly like the dry-run harness.
* fallback (backends with no buffer assignment): aval arithmetic —
  argument + output bytes exactly, plus a COARSE activation-slab estimate
  ``batch_bytes / M * activation_multiplier`` (ALL leaves, so int32 token
  batches still register — each token expands to activations). Its job is
  not accuracy, it is strict monotonicity in M so the binary search still
  converges; the returned ``ExecPlan.source`` says which path produced
  the numbers, and callers gating real deployments should insist on
  ``memory_analysis``.

The search assumes peak memory is non-increasing in M (more accumulation
never costs memory) — true by construction for the scan-accumulated step
and verified empirically by ``benchmarks/bench_scale.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax

from repro.perf import memory as perf_memory
from repro.scale.policy import ScaleConfig

PyTree = Any

#: coarse activations-per-batch-byte multiplier for the aval fallback —
#: transformer backward passes hold O(10) activation copies of the token
#: stream; only monotonicity in M matters for the search (see module doc).
AVAL_ACTIVATION_MULTIPLIER = 12.0


@dataclasses.dataclass(frozen=True)
class ExecPlan:
    """The planner's verdict: run with ``scale`` (= the input ScaleConfig
    with ``microbatch`` replaced by the chosen M)."""

    microbatch: int
    scale: ScaleConfig
    peak_bytes: Optional[int]  # measured peak of the CHOSEN M
    hbm_budget: int
    fits: bool  # False: even the largest candidate M busts the budget
    source: str  # perf.memory source tag of the measurements
    #: every (M, peak_bytes) the search actually compiled/estimated —
    #: the audit trail benchmarks and tests assert monotonicity on
    candidates: Tuple[Tuple[int, Optional[int]], ...] = ()


def _batch_dims(base_batches, meta_batch) -> Tuple[int, int]:
    base_leaves = jax.tree_util.tree_leaves(base_batches)
    meta_leaves = jax.tree_util.tree_leaves(meta_batch)
    if not base_leaves or not meta_leaves:
        raise ValueError("plan_microbatch needs non-empty base and meta batches")
    return base_leaves[0].shape[1], meta_leaves[0].shape[0]  # (K, B, ...) / (B, ...)


def candidate_microbatches(base_batches, meta_batch,
                           max_microbatch: Optional[int] = None,
                           *, shard_divisor: int = 1) -> Tuple[int, ...]:
    """Ascending Ms that divide BOTH the per-step base batch and the meta
    batch (``split_batch`` requires exact divisibility).

    ``shard_divisor``: the data-parallel extent when the step runs under
    the manual schedule — ``split_batch`` there executes on the PER-DEVICE
    shard inside shard_map, so candidates must divide the shard
    (global/dp), not the global batch. 1 for pjit/single-device."""

    base_b, meta_b = _batch_dims(base_batches, meta_batch)
    if shard_divisor < 1 or base_b % shard_divisor or meta_b % shard_divisor:
        raise ValueError(
            f"batches (base {base_b}, meta {meta_b}) do not shard evenly "
            f"over {shard_divisor} data-parallel devices"
        )
    base_b //= shard_divisor
    meta_b //= shard_divisor
    ms = [m for m in range(1, min(base_b, meta_b) + 1)
          if base_b % m == 0 and meta_b % m == 0
          and (max_microbatch is None or m <= max_microbatch)]
    if not ms:
        raise ValueError(
            f"no common microbatch divisor for per-shard base batch {base_b} / "
            f"meta batch {meta_b} under max_microbatch={max_microbatch}"
        )
    return tuple(ms)


# the activation estimate counts EVERY leaf (perf.memory.tree_bytes) —
# int32 token batches included: for the repo's LM/encoder models the
# activation slab scales with the token COUNT (each token expands to
# d_model floats downstream), so a floats-only sum would be 0 for a token
# batch and break the fallback's monotonicity-in-M job.
_batch_bytes = perf_memory.tree_bytes


def measure_peak(spec, base_opt, meta_opt, engine_cfg, state, base_batches,
                 meta_batch, *, mesh=None, schedule: str = "pjit",
                 _dryrun: bool = False):
    """Compile ONE candidate step on example avals and return
    ``(peak_bytes, source)``. ``state`` / batches may be concrete arrays
    or ShapeDtypeStructs — only shapes/dtypes are consumed."""

    from repro.core.engine import make_meta_step  # lazy: engine imports scale

    if schedule == "single_sync":
        from repro.launch.distributed import make_manual_step

        if mesh is None:
            raise ValueError("schedule='single_sync' needs a mesh")
        step = make_manual_step(spec, base_opt, meta_opt, engine_cfg, mesh)
    else:
        step = make_meta_step(spec, base_opt, meta_opt, engine_cfg)

    def lower():  # donating, as MetaLearner's step: the state is aliased
        return jax.jit(step, donate_argnums=(0,)).lower(state, base_batches, meta_batch)

    if mesh is not None:
        with mesh:
            compiled = lower().compile()
    else:
        compiled = lower().compile()
    stats = perf_memory.compiled_memory(
        compiled, example_args=(state, base_batches, meta_batch))
    if stats.peak_bytes is not None:
        return int(stats.peak_bytes), stats.source
    # aval fallback: argument/output exact + monotone activation estimate
    m = engine_cfg.scale.microbatch
    act = int(_batch_bytes((base_batches, meta_batch))
              * AVAL_ACTIVATION_MULTIPLIER / max(m, 1))
    return stats.argument_bytes + stats.output_bytes + act, stats.source


def plan_microbatch(spec, base_opt, meta_opt, engine_cfg, state, base_batches,
                    meta_batch, *, hbm_budget: int, mesh=None,
                    schedule: str = "pjit",
                    max_microbatch: Optional[int] = None) -> ExecPlan:
    """Binary-search the smallest microbatch count M whose compiled step
    peak fits ``hbm_budget`` bytes per device. Returns an ``ExecPlan``
    whose ``scale`` is ``engine_cfg.scale`` with the chosen M — feed it
    back as ``dataclasses.replace(engine_cfg, scale=plan.scale)``.

    When even the LARGEST candidate M does not fit, ``fits=False`` and the
    plan carries that largest M (the least-bad configuration) — callers
    decide whether to run anyway or shrink the batch."""

    if hbm_budget <= 0:
        raise ValueError(f"hbm_budget must be > 0 bytes, got {hbm_budget}")
    # under the manual schedule split_batch runs on the PER-DEVICE shard
    # inside shard_map — candidates must divide the shard, not the global
    dp = 1
    if schedule == "single_sync" and mesh is not None:
        from repro.launch.mesh import data_axes  # lazy: launch sits above scale

        for axis in data_axes(mesh):
            dp *= mesh.shape[axis]
    cands = candidate_microbatches(base_batches, meta_batch, max_microbatch,
                                   shard_divisor=dp)
    tried = {}

    def peak_of(m: int):
        if m not in tried:
            cfg_m = dataclasses.replace(
                engine_cfg, scale=dataclasses.replace(engine_cfg.scale, microbatch=m))
            tried[m] = measure_peak(
                spec, base_opt, meta_opt, cfg_m, state, base_batches, meta_batch,
                mesh=mesh, schedule=schedule)
        return tried[m]

    # bisect the ascending candidate list: peak(M) is non-increasing, so
    # the fitting candidates form a suffix — find its first element.
    lo, hi = 0, len(cands) - 1
    best = None
    if peak_of(cands[hi])[0] <= hbm_budget:
        while lo < hi:
            mid = (lo + hi) // 2
            if peak_of(cands[mid])[0] <= hbm_budget:
                hi = mid
            else:
                lo = mid + 1
        best = cands[lo]

    chosen = best if best is not None else cands[-1]
    peak, source = tried[chosen]
    return ExecPlan(
        microbatch=chosen,
        scale=dataclasses.replace(engine_cfg.scale, microbatch=chosen),
        peak_bytes=peak,
        hbm_budget=int(hbm_budget),
        fits=best is not None,
        source=source,
        candidates=tuple((m, tried[m][0]) for m in sorted(tried)),
    )
