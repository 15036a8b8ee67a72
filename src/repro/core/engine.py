"""The meta-training Engine.

One ``meta_step`` = K unrolled base optimizer steps + one meta update, with
the hypergradient estimator resolved through the ``repro.core.methods``
registry — the paper's whole ablation surface (Tables 8/9) behind one
config value, and open to third-party estimators via ``register_method``.

The Engine builds a *pure* step function (state, base_batches, meta_batch) ->
(state, metrics) so it can be jit'ed on one device (benchmarks, examples) or
handed to the launcher which wraps it in pjit/shard_map for the production
mesh. ``base_batches`` carries a leading unroll axis of length K.

The step is method-agnostic: unroll -> ``method.local_terms`` (shard-local
math) -> identity reduce (this is the single-device path) ->
``method.finalize`` (hypergradient + post-update hook). The distributed
single-sync schedule in ``launch.distributed`` drives the SAME protocol,
inserting its one bucketed all-reduce between stages 2 and 3.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import methods as methods_mod
from repro.core.bilevel import BilevelSpec
from repro.core.methods import HypergradMethod, MethodContext
from repro.core.sama import global_norm
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.optim import Optimizer, OptState, apply_updates
from repro.scale import accum as accum_mod
from repro.scale import policy as policy_mod
from repro.scale.policy import LossScaleState, ScaleConfig

PyTree = Any

#: The built-in estimators (kept for back-compat; the authoritative list is
#: ``methods.available_methods()``, which also includes custom registrations).
METHODS = ("sama", "sama_na", "t1t2", "neumann", "cg", "iterdiff")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """``method`` is a registry name or a HypergradMethod instance; the
    remaining per-method knobs feed the built-in factories. ``scale``
    carries the repro.scale knobs (precision policy + microbatch count,
    DESIGN.md §11) — the default is the identity (f32, no microbatching),
    i.e. the paper-exact step."""

    method: Union[str, HypergradMethod] = "sama"
    unroll_steps: int = 1
    alpha: float = 1.0  # SAMA perturbation scale
    base_nudge: bool = True
    adapt_clip: float = 0.0  # see SAMAConfig.adapt_clip
    # baseline-specific knobs
    neumann_terms: int = 5
    neumann_scale: float = 0.1
    cg_iters: int = 5
    cg_damping: float = 1e-3
    # precision policy + microbatch accumulation (repro.scale)
    scale: ScaleConfig = ScaleConfig()

    def __post_init__(self):
        if isinstance(self.method, str) and self.method not in methods_mod.available_methods():
            raise ValueError(
                f"method {self.method!r} not registered; have {methods_mod.available_methods()}"
            )

    def resolve(self) -> HypergradMethod:
        return methods_mod.resolve_method(self.method, self)


class EngineState(NamedTuple):
    theta: PyTree
    base_opt_state: OptState
    lam: PyTree
    meta_opt_state: OptState
    step: jnp.ndarray
    #: dynamic loss-scale automaton (repro.scale); None (an empty subtree,
    #: so old checkpoints keep restoring) unless the policy scales losses.
    scale: Optional[LossScaleState] = None


def init_state(theta: PyTree, lam: PyTree, base_opt: Optimizer, meta_opt: Optimizer,
               *, scale: Optional[ScaleConfig] = None) -> EngineState:
    """``scale``: the EngineConfig's ScaleConfig — needed so a
    loss-scaling policy (f16) gets its LossScaleState seeded; omitting it
    keeps the f32/bf16 default (no scale state)."""

    policy = (scale or ScaleConfig()).resolve()
    return EngineState(
        theta=theta,
        base_opt_state=base_opt.init(theta),
        lam=lam,
        meta_opt_state=meta_opt.init(lam),
        step=jnp.zeros([], jnp.int32),
        scale=policy_mod.init_scale_state(policy),
    )


def _unroll_base(spec: BilevelSpec, base_opt: Optimizer, theta, opt_state, lam,
                 base_batches, *, scale_cfg: Optional[ScaleConfig] = None,
                 scale_state: Optional[LossScaleState] = None, grad_reduce=None):
    """K base optimizer steps via lax.scan. Carries the last base gradient and
    the optimizer state *at which it was computed* — SAMA's adaptation matrix
    is evaluated there (paper footnote 2: no extra backward pass).

    repro.scale hooks (all default to the paper-exact path):
    ``scale_cfg.microbatch`` splits each base batch into M accumulated
    microbatches (collective-free inner scan); ``scale_state`` (with a
    loss-scaling policy) multiplies each microbatch loss by the live scale
    before its backward pass and SKIPS the update on a non-finite gradient
    (params, moments, and the carried (g, state-at-g) pair all keep their
    previous values) while the scale automaton backs off; ``grad_reduce``
    is the distributed schedule's per-step DDP pmean — it runs on the
    ACCUMULATED gradient, so the all-reduce count per base step stays one
    for every M.

    Returns ``(theta, opt_state, g_last, st_at_g, losses, scale_state,
    any_finite)`` — ``any_finite`` (scalar bool, always True without
    scaling) says whether ANY base step of this unroll applied; when every
    step skipped, ``g_last`` is still the zero init and the meta level
    must not consume it (SAMA's adaptation diagonal at a zero gradient and
    cold moments is the lr/eps pathology — finite but garbage), so the
    caller's meta-update guard ANDs this flag in.
    """

    cfg = scale_cfg or ScaleConfig()
    policy = cfg.resolve()
    if policy.dynamic_scaling and scale_state is None:
        raise ValueError(
            f"policy {policy.name!r} scales losses but the state carries no "
            "LossScaleState — build the state with "
            "init_state(..., scale=engine_cfg.scale)"
        )
    g0 = jax.tree_util.tree_map(jnp.zeros_like, theta)

    def step(carry, batch):
        th, st, g_prev, st_prev, ss, ok_prev = carry
        loss, g = accum_mod.microbatch_value_and_grad(
            spec.base_scalar, th, lam, batch, cfg.microbatch, policy.accum_jnp,
            scale=ss,
        )
        if grad_reduce is not None:
            g = grad_reduce(g)
        if ss is None:
            upd, st_new = base_opt.update(g, st, th)
            return (apply_updates(th, upd), st_new, g, st, ss, ok_prev), loss
        finite = policy_mod.all_finite(g)
        g_safe = jax.tree_util.tree_map(
            lambda x: jnp.where(finite, x, jnp.zeros_like(x)), g)
        upd, st_new = base_opt.update(g_safe, st, th)
        th_new = policy_mod.select_tree(finite, apply_updates(th, upd), th)
        st_new = policy_mod.select_tree(finite, st_new, st)
        # a skipped step contributes no usable gradient: keep the previous
        # (g, state-at-g) pair so SAMA's adaptation stays finite
        g_keep = policy_mod.select_tree(finite, g, g_prev)
        st_at_g = policy_mod.select_tree(finite, st, st_prev)
        ss = policy_mod.update_scale(ss, finite, policy)
        return (th_new, st_new, g_keep, st_at_g, ss, jnp.logical_or(ok_prev, finite)), loss

    any0 = jnp.asarray(scale_state is None)  # no scaling: vacuously True
    init = (theta, opt_state, g0, opt_state, scale_state, any0)
    (theta, opt_state, g_last, st_at_g, scale_state, any_finite), losses = jax.lax.scan(
        step, init, base_batches)
    return theta, opt_state, g_last, st_at_g, losses, scale_state, any_finite


def make_context(
    base_opt: Optimizer,
    state: EngineState,
    base_batches,
    meta_batch,
    *,
    theta,
    base_opt_state,
    g_base,
    loss_scale=None,
) -> MethodContext:
    """Assemble the MethodContext a hypergradient method consumes. Shared by
    the Engine step and the distributed schedule so both hand methods the
    exact same view of the unroll. ``loss_scale`` (the POST-unroll dynamic
    scale under an f16 policy) lets methods protect their own backward
    passes — see MethodContext.loss_scale."""

    return MethodContext(
        base_opt=base_opt,
        theta0=state.theta,
        theta=theta,
        lam=state.lam,
        g_base=g_base,
        base_opt_state=base_opt_state,
        base_batches=base_batches,
        last_batch=jax.tree_util.tree_map(lambda x: x[-1], base_batches),
        meta_batch=meta_batch,
        loss_scale=loss_scale,
    )


def step_metrics(method: HypergradMethod, terms, hyper, base_losses) -> Dict[str, jnp.ndarray]:
    """The uniform metric dict. ``eps`` is kept for every method (zero when
    the method has no step-size notion) so logs/benchmarks stay columnar."""

    metrics = {
        "base_loss": jnp.mean(base_losses),
        "meta_loss": terms["meta_loss"],
        "hypergrad_norm": global_norm(hyper),
        "eps": jnp.zeros([], jnp.float32),
    }
    for k, v in method.metrics(terms).items():
        metrics[k] = v
    return metrics


def guarded_meta_update(meta_opt: Optimizer, hyper, theta_post, state: EngineState,
                        *, theta_pre, guard: bool, base_ok=None):
    """The meta-level update, optionally gated on finiteness: under a
    loss-scaling policy the hypergradient path (low-precision CD passes)
    can overflow, and a single non-finite meta step would poison lam and
    the nudged theta permanently. With ``guard`` the whole meta update
    (lam, meta moments, AND the finalize post-update of theta) is skipped
    for that step — the meta-level analogue of the base unroll's
    skip-on-nonfinite. ``base_ok`` (the unroll's any-finite flag) is ANDed
    in: when EVERY base step skipped, g_base is the zero init and the
    hypergradient is finite garbage. Shared by the Engine step and the
    manual schedule so the semantics cannot diverge.

    Returns ``(lam, m_state, theta_post, finite)``; ``finite`` is None
    when unguarded, else the gate — callers feed it to
    ``policy.backoff_on`` so the loss-scale automaton OBSERVES
    hypergradient overflow (otherwise a persistently-overflowing meta
    path would skip forever with no backoff)."""

    upd, m_state = meta_opt.update(hyper, state.meta_opt_state, state.lam)
    lam = apply_updates(state.lam, upd)
    if not guard:
        return lam, m_state, theta_post, None
    finite = policy_mod.all_finite({"hyper": hyper, "theta": theta_post})
    if base_ok is not None:
        finite = jnp.logical_and(finite, base_ok)
    lam = policy_mod.select_tree(finite, lam, state.lam)
    m_state = policy_mod.select_tree(finite, m_state, state.meta_opt_state)
    theta_post = policy_mod.select_tree(finite, theta_post, theta_pre)
    return lam, m_state, theta_post, finite


def make_meta_step(
    spec: BilevelSpec,
    base_opt: Optimizer,
    meta_opt: Optimizer,
    cfg: EngineConfig = EngineConfig(),
) -> Callable[[EngineState, Any, Any], Tuple[EngineState, Dict[str, jnp.ndarray]]]:
    """Build the pure, method-agnostic meta-step function. ``cfg.scale``
    applies the precision policy's cast boundary to BOTH levels (the spec
    is wrapped once, so the unroll and the hypergradient path see the same
    boundary) and microbatch accumulation to every batch-sized backward
    pass (repro.scale.accum)."""

    method = cfg.resolve()
    policy = cfg.scale.resolve()
    spec = policy_mod.apply_to_spec(spec, policy)
    micro = cfg.scale.microbatch

    def meta_step(state: EngineState, base_batches, meta_batch):
        with policy.matmul_context():
            return _meta_step(state, base_batches, meta_batch)

    def _meta_step(state: EngineState, base_batches, meta_batch):
        # obs_trace.phase = unconditional jax.named_scope (identical HLO
        # with obs on or off) + a host span iff a Tracer is activated
        with obs_trace.phase("base_unroll"):
            (theta, b_state, g_base, st_at_g, base_losses, scale_state,
             base_ok) = _unroll_base(
                spec, base_opt, state.theta, state.base_opt_state, state.lam,
                base_batches, scale_cfg=cfg.scale, scale_state=state.scale,
            )
        ctx = make_context(
            base_opt, state, base_batches, meta_batch,
            theta=theta, base_opt_state=st_at_g, g_base=g_base,
            loss_scale=scale_state.scale if scale_state is not None else None,
        )
        # local_terms is the phase every method shares (attribution for the
        # baselines); SAMA's own meta_pass/cd_passes scopes nest inside it
        # and win the innermost-phase match in obs.profile
        with obs_trace.phase("local_terms"):
            terms = methods_mod.validate_terms(
                method, accum_mod.microbatch_local_terms(method, spec, ctx, micro,
                                                         policy.accum_jnp))
        # single-device / pjit path: identity reduce between stages 2 and 3
        with obs_trace.phase("finalize"):
            hyper, theta_post = method.finalize(terms, ctx)

        with obs_trace.phase("meta_update"):
            lam, m_state, theta_post, meta_ok = guarded_meta_update(
                meta_opt, hyper, theta_post, state,
                theta_pre=theta, guard=policy.dynamic_scaling, base_ok=base_ok,
            )
            if meta_ok is not None:  # hypergrad overflow must back the scale off
                scale_state = policy_mod.backoff_on(scale_state, meta_ok, policy)

        new_state = EngineState(
            theta=theta_post,
            base_opt_state=b_state,
            lam=lam,
            meta_opt_state=m_state,
            step=state.step + 1,
            scale=scale_state,
        )
        metrics = step_metrics(method, terms, hyper, base_losses)
        if meta_ok is not None:
            # expose the automaton to host-side observers: the post-step
            # scale and the gate verdict ride the existing metric outputs,
            # so obs needs no extra sync (and no obs-conditional tracing —
            # these are present whenever the policy scales, observed or not)
            metrics["loss_scale"] = scale_state.scale
            metrics["meta_skipped"] = 1.0 - meta_ok.astype(jnp.float32)
        return new_state, metrics

    return meta_step


def run_loop(step_fn, state, batch_iter, num_steps: int, log_every: int = 0,
             on_step=None, obs=None):
    """The shared training loop: drive ``step_fn`` over an iterator of
    (base_batches[K], meta_batch), collecting float-cast metric history at
    ``log_every`` cadence. Used by both Engine.run and MetaLearner.fit so
    the logging semantics cannot diverge. ``on_step(i, state)`` runs after
    every step (checkpoint hooks).

    Metric reads happen ONLY at the log cadence and fetch the whole dict
    in one ``jax.device_get`` (``obs.metrics.packed_read``) — one D2H
    transfer per logged step instead of one blocking ``float(v)`` per
    key. ``obs`` (a ``repro.obs.Obs``) receives the same host dict via
    ``observe_step`` at the same boundary, so observability adds no sync
    points to the hot loop; ``obs=None`` logs nothing extra.

    The host work between steps is named for ``jax.profiler``:
    ``next_batch`` around pulling the batches and ``log_read`` around the
    metric read."""

    history = []
    for i in range(num_steps):
        with jax.profiler.TraceAnnotation("next_batch"):
            base_batches, meta_batch = next(batch_iter)
        state, metrics = step_fn(state, base_batches, meta_batch)
        if log_every and (i % log_every == 0 or i == num_steps - 1):
            with jax.profiler.TraceAnnotation("log_read"):
                host = obs_metrics.packed_read(metrics)
            row = {k: float(v) for k, v in host.items()}
            history.append(row | {"step": i})
            if obs is not None and obs.enabled:
                obs.observe_step(i, row)
        if on_step is not None:
            on_step(i, state)
    return state, history


class Engine:
    """Convenience single-process driver around the pure step function."""

    def __init__(self, spec, base_opt, meta_opt, cfg: EngineConfig = EngineConfig(), jit: bool = True):
        self.spec = spec
        self.base_opt = base_opt
        self.meta_opt = meta_opt
        self.cfg = cfg
        step = make_meta_step(spec, base_opt, meta_opt, cfg)
        self.step_fn = jax.jit(step) if jit else step

    def init(self, theta, lam) -> EngineState:
        return init_state(theta, lam, self.base_opt, self.meta_opt,
                          scale=self.cfg.scale)

    def run(self, state: EngineState, batch_iter, num_meta_steps: int,
            log_every: int = 0, obs=None):
        """batch_iter yields (base_batches[K], meta_batch)."""

        return run_loop(self.step_fn, state, batch_iter, num_meta_steps,
                        log_every, obs=obs)
