"""Mixed-precision policies for the SAMA hot path (DESIGN.md §11).

A ``PrecisionPolicy`` names three dtypes and (optionally) a dynamic loss
scale:

* ``param_dtype``  — the MASTER copy of the base parameters. EngineState
  keeps theta (and therefore the optimizer moments ``OptState`` derives
  from it) in this dtype; the paper's "exploit first-order machinery"
  memory claim rests on the usual f32-master / low-precision-compute
  split, so it is f32 in every built-in policy.
* ``compute_dtype`` — the dtype the loss (and its backward pass) runs in.
  ``apply_to_spec`` installs the cast boundary: theta's float leaves and
  the batch's float leaves are cast to ``compute_dtype`` on the way into
  ``BilevelSpec.base_loss`` / ``meta_loss``, and the scalar loss comes
  back f32. Because the cast is the first traced op, its VJP casts the
  low-precision cotangents back up — gradients w.r.t. the master params
  arrive in ``param_dtype`` with no extra bookkeeping. The SAME wrapped
  spec feeds the base unroll and the hypergradient path (SAMA's meta
  pass and both central-difference passes), so the cast boundary is
  uniform across both levels.
* ``accum_dtype``  — the dtype microbatch accumulators (``repro.scale.
  accum``) and reduction buffers run in; f32 everywhere built-in (bf16
  accumulation loses the benefit of bf16's range for no memory win on
  the accumulator, which is parameter-sized, not batch-sized).

``loss_scale > 0`` turns on DYNAMIC loss scaling (the f16 policy):
the base loss is multiplied by the live scale before the backward pass so
f16 cotangents stay representable, gradients are unscaled after
accumulation, and a non-finite unscaled gradient SKIPS that base update
(params + optimizer state untouched) and halves the scale; every
``growth_interval`` consecutive finite steps the scale doubles. bf16 has
f32's exponent range and ships unscaled (``loss_scale=0``).

lam (the meta parameters) stays in its native dtype: meta modules are
tiny (MWN is a 2-layer MLP), so down-casting them saves nothing and
perturbs the hypergradient for free.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

PyTree = Any


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Dtype triple + loss-scale knobs. ``jnp`` dtypes are stored as their
    canonical string names so the policy is hashable/JSON-able and safe as
    a static jit argument."""

    name: str = "f32"
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    accum_dtype: str = "float32"
    # 0.0 = no loss scaling; > 0 = initial DYNAMIC scale (doubles every
    # growth_interval finite steps, halves on a non-finite gradient).
    loss_scale: float = 0.0
    growth_interval: int = 200
    max_loss_scale: float = float(2 ** 24)
    min_loss_scale: float = 1.0

    @property
    def param_jnp(self):
        return jnp.dtype(self.param_dtype)

    @property
    def compute_jnp(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def accum_jnp(self):
        return jnp.dtype(self.accum_dtype)

    @property
    def dynamic_scaling(self) -> bool:
        return self.loss_scale > 0.0

    @property
    def is_identity(self) -> bool:
        """True when the policy changes nothing (the f32 default) — callers
        skip the spec wrapper entirely so paper-exact paths stay untouched."""
        return (self.compute_jnp == jnp.float32
                and self.param_jnp == jnp.float32
                and not self.dynamic_scaling)

    def matmul_context(self):
        """The ``jax.default_matmul_precision`` a meta step traces under.
        On a TPU, XLA's ``default`` multiplies f32 operands in one bf16
        pass, which rounds SAMA's central-difference perturbation
        theta +- eps*v away; an f32 compute dtype therefore asks for
        ``highest`` (true f32 products). 16-bit compute keeps ``default``:
        its operands are already 16-bit."""
        return jax.default_matmul_precision(
            "highest" if self.compute_jnp == jnp.float32 else "default")


#: the built-in policies (DESIGN.md §11): f32 master params everywhere;
#: bf16 computes unscaled (f32 exponent range), f16 computes under a
#: dynamic loss scale with skip-on-nonfinite. The f16 scale is CAPPED at
#: 2^15: the backward seed is the scale itself cast through the f16
#: boundary, and float16(2^16) == inf — growth past the cap would skip a
#: base step deterministically (model-independent) every growth_interval.
POLICIES = {
    "f32": PrecisionPolicy(name="f32"),
    "bf16": PrecisionPolicy(name="bf16", compute_dtype="bfloat16"),
    "f16": PrecisionPolicy(name="f16", compute_dtype="float16",
                           loss_scale=float(2 ** 15),
                           max_loss_scale=float(2 ** 15)),
}


def resolve_policy(policy: Union[str, PrecisionPolicy]) -> PrecisionPolicy:
    if isinstance(policy, PrecisionPolicy):
        return policy
    if isinstance(policy, str):
        if policy not in POLICIES:
            raise ValueError(
                f"unknown precision policy {policy!r}; built-ins: {sorted(POLICIES)}"
            )
        return POLICIES[policy]
    raise TypeError(
        f"policy must be a name or PrecisionPolicy, got {type(policy).__name__}"
    )


def cast_floats(tree: PyTree, dtype) -> PyTree:
    """Cast the inexact (float) leaves of ``tree`` to ``dtype``; integer /
    bool leaves (token ids, labels) pass through untouched."""

    def one(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.inexact):
            return x.astype(dtype)
        return x

    return jax.tree_util.tree_map(one, tree)


def apply_to_spec(spec: "Any", policy: PrecisionPolicy) -> "Any":
    """Install the policy's cast boundary on a BilevelSpec: theta and batch
    float leaves go down to ``compute_dtype`` on entry, the scalar loss
    comes back f32 (aux, when present, is passed through untouched). The
    identity policy returns ``spec`` itself."""

    # engine imports this module, so BilevelSpec must resolve lazily
    from repro.core.bilevel import BilevelSpec

    if policy.is_identity:
        return spec
    cdt = policy.compute_jnp

    def wrap(loss_fn):
        def wrapped(theta, lam, batch):
            out = loss_fn(cast_floats(theta, cdt), lam, cast_floats(batch, cdt))
            if spec.has_aux:
                return out[0].astype(jnp.float32), out[1]
            return out.astype(jnp.float32)

        return wrapped

    return BilevelSpec(base_loss=wrap(spec.base_loss),
                       meta_loss=wrap(spec.meta_loss),
                       has_aux=spec.has_aux)


# ---------------------------------------------------------------------------
# dynamic loss scaling
# ---------------------------------------------------------------------------


class LossScaleState(NamedTuple):
    """Carried in ``EngineState.scale`` when the policy scales losses."""

    scale: jnp.ndarray  # f32 scalar, the live multiplier
    good_steps: jnp.ndarray  # i32 scalar, consecutive finite base steps


def init_scale_state(policy: PrecisionPolicy) -> Optional[LossScaleState]:
    """The initial LossScaleState for a policy (None when the policy does
    not scale — the EngineState field then stays an empty subtree and old
    checkpoints keep restoring)."""

    if not policy.dynamic_scaling:
        return None
    return LossScaleState(scale=jnp.asarray(policy.loss_scale, jnp.float32),
                          good_steps=jnp.zeros([], jnp.int32))


def all_finite(tree: PyTree) -> jnp.ndarray:
    """Scalar bool: every float leaf of ``tree`` is finite."""

    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(x.dtype, jnp.inexact)]
    if not leaves:
        return jnp.asarray(True)
    finite = [jnp.all(jnp.isfinite(x)) for x in leaves]
    out = finite[0]
    for f in finite[1:]:
        out = jnp.logical_and(out, f)
    return out


def update_scale(state: LossScaleState, finite: jnp.ndarray,
                 policy: PrecisionPolicy) -> LossScaleState:
    """The standard dynamic-loss-scale automaton: halve on a non-finite
    step (and reset the streak), double after ``growth_interval``
    consecutive finite steps, clamped to [min_loss_scale, max_loss_scale]."""

    good = jnp.where(finite, state.good_steps + 1, 0)
    grow = jnp.logical_and(finite, good >= policy.growth_interval)
    scale = jnp.where(
        finite,
        jnp.where(grow, state.scale * 2.0, state.scale),
        state.scale * 0.5,
    )
    scale = jnp.clip(scale, policy.min_loss_scale, policy.max_loss_scale)
    good = jnp.where(grow, 0, good)
    return LossScaleState(scale=scale.astype(jnp.float32),
                          good_steps=good.astype(jnp.int32))


def backoff_on(state: LossScaleState, finite: jnp.ndarray,
               policy: PrecisionPolicy) -> LossScaleState:
    """Backoff-only automaton step: halve the scale and reset the growth
    streak when ``finite`` is False, identity otherwise. Used for events
    that should never GROW the scale (the hypergradient path's per-meta-
    step finiteness — growth streaks are counted in base steps only, so a
    meta event must not double-count them)."""

    scale = jnp.where(finite, state.scale,
                      jnp.clip(state.scale * 0.5, policy.min_loss_scale,
                               policy.max_loss_scale))
    good = jnp.where(finite, state.good_steps, 0)
    return LossScaleState(scale=scale.astype(jnp.float32),
                          good_steps=good.astype(jnp.int32))


def select_tree(pred: jnp.ndarray, on_true: PyTree, on_false: PyTree) -> PyTree:
    """Elementwise tree select on a scalar predicate (the skip-on-nonfinite
    update gate: params/moments keep their old values on a skipped step)."""

    return jax.tree_util.tree_map(
        lambda t, f: jnp.where(pred, t, f), on_true, on_false
    )


# ---------------------------------------------------------------------------
# the user-facing config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ScaleConfig:
    """The ``repro.scale`` knobs as they ride on ``EngineConfig`` (and so
    on ``MetaLearner`` / ``DataOptimizer`` scoring / ``launch.train``):

    ``policy``     — "f32" | "bf16" | "f16" or a PrecisionPolicy instance.
    ``microbatch`` — M: each base batch (and the meta/last batches the
      hypergradient stage consumes) is split into M microbatches that are
      accumulated shard-locally under ``lax.scan`` (repro.scale.accum), so
      activation memory is O(batch/M) while the distributed schedule still
      fires exactly ``unroll_steps + 1`` all-reduces. Batch leading dims
      must be divisible by M (``plan_microbatch`` only proposes divisors).
    """

    policy: Union[str, PrecisionPolicy] = "f32"
    microbatch: int = 1

    def __post_init__(self):
        resolve_policy(self.policy)  # fail at config time, not trace time
        if self.microbatch < 1:
            raise ValueError(f"microbatch must be >= 1, got {self.microbatch}")

    def resolve(self) -> PrecisionPolicy:
        return resolve_policy(self.policy)

    @property
    def is_identity(self) -> bool:
        return self.microbatch == 1 and self.resolve().is_identity
