"""The paper's distributed execution schedule (Fig. 2), generalized to any
registered HypergradMethod with a linear reduce contract.

Two implementations of the same meta step:

* ``make_pjit_step`` — "Betty-style DDP" baseline: the Engine's pure step
  under jit; XLA inserts a gradient synchronization wherever the math needs
  one. In particular the meta pass's theta-gradient (pass 1) gets a
  model-sized all-reduce of its own.

* ``make_manual_step`` — the paper's single-sync schedule via shard_map,
  manual over the data axes, auto over "model":
    ``method.local_terms`` runs on LOCAL shards with NO collective;
    ONE bucketed pmean carries exactly the terms the method's
    ``reduce_contract`` declares (SAMA: hypergrad, v, eps, meta_loss —
    the analogue of PyTorch's single overlapped bucketed all-reduce), plus
    the scalar base-loss metric so no second sync is needed for logging;
    ``method.finalize`` then consumes replica-consistent values (SAMA's
    base nudge). The base-level unroll keeps its standard per-step DDP
    pmean (that sync exists in the paper's base level too), so the lowered
    module carries exactly ``unroll_steps`` base all-reduces + ONE
    meta-level all-reduce — pinned by ``count_data_allreduces``.

  Statistically, the manual path averages per-shard local estimates; for a
  method with a LINEAR reduce contract (SAMA, SAMA-NA, T1-T2) the mean of
  mixed second-derivative terms equals the pjit estimator's expectation,
  and with identical per-device batches the two are exactly equal — what
  tests/test_distributed.py pins, along with the collective-count claim,
  by parsing the lowered HLO. Methods with nonlinear contracts (CG,
  Neumann, iterdiff solve/unroll on the shard) are refused unless
  ``allow_nonlinear=True`` opts into the local-solve approximation.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import methods as methods_mod
from repro.core.bilevel import BilevelSpec
from repro.core.engine import (
    EngineConfig,
    EngineState,
    _unroll_base,
    guarded_meta_update,
    make_context,
    make_meta_step,
    step_metrics,
)
from repro.launch.mesh import data_axes
from repro.obs import trace as obs_trace
from repro.optim import Optimizer
from repro.scale import accum as accum_mod
from repro.scale import policy as policy_mod

PyTree = Any

#: What the manual schedule emits per step (static for shard_map out_specs).
#: Under a dynamic-scaling policy the automaton scalars ride along too
#: (see make_manual_step's ``metric_keys``).
METRIC_KEYS = ("base_loss", "meta_loss", "hypergrad_norm", "eps")
SCALE_METRIC_KEYS = ("loss_scale", "meta_skipped")


def flat_pmean(tree: PyTree, axes) -> PyTree:
    """Mean-reduce a pytree over ``axes`` through ONE all-reduce: ravel every
    leaf into a single flat f32 buffer (PyTorch-DDP flat bucket), pmean it,
    and unravel. Relying on XLA's all-reduce combiner would make the paper's
    one-sync claim backend-dependent; the flat bucket makes it structural.
    Leaves must already share a dtype (callers cast to f32 for reduction
    accuracy).

    Only valid when no tensor-parallel auto axis is live: ravel/concat breaks
    per-leaf "model" sharding, which would make the partitioner all-gather
    model-sharded leaves into full-size reduce buffers. Callers pick this
    bucket for pure-DDP meshes and ``tree_pmean`` otherwise."""

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    flat = jax.lax.pmean(jnp.concatenate([x.reshape(-1) for x in leaves]), axes)
    offsets = [0]
    for x in leaves:
        offsets.append(offsets[-1] + x.size)
    pieces = [jax.lax.slice_in_dim(flat, a, b) for a, b in zip(offsets, offsets[1:])]
    # the barrier keeps the TPU compiler from rewriting slice-then-reshape
    # of a (768, 4) leaf as a reshape of the whole bucket to (N/4, 4),
    # whose tiled layout pads 4 lanes to 128: 32x the bucket in HBM
    pieces = jax.lax.optimization_barrier(pieces)
    out = [p.reshape(x.shape).astype(x.dtype) for p, x in zip(pieces, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def tree_pmean(tree: PyTree, axes) -> PyTree:
    """Per-leaf mean-reduce: keeps each leaf's auto-axis (tensor-parallel)
    sharding intact. Still ONE logical sync point per call — XLA may lower
    it as several fused all-reduce ops, which its combiner can overlap."""

    return jax.lax.pmean(tree, axes)


def cast_for_reduce(tree: PyTree) -> PyTree:
    """Promote ONLY sub-f32 float leaves (bf16/f16) to f32 before an
    all-reduce; f32/f64 and integer leaves pass through untouched (f32
    identity leaves keep their object identity — pinned by tests).

    Two reasons, both pinned by tests/test_scale_distributed.py:
    1. XLA's AllReducePromotion pass crashes on bf16 VARIADIC all-reduce
       on the CPU backend — a sub-f32 leaf in the reduce bucket must not
       reach the collective at its narrow dtype;
    2. reduction accuracy: accumulating a cross-replica mean in bf16 loses
       the benefit of the f32 master params (this is also what PyTorch DDP
       does for low-precision buckets).

    Callers cast the reduced result back per leaf where the consumer is
    dtype-sensitive."""

    def one(x):
        if jnp.issubdtype(x.dtype, jnp.inexact) and x.dtype.itemsize < 4:
            return x.astype(jnp.float32)
        return x

    return jax.tree_util.tree_map(one, tree)


def make_pjit_step(spec: BilevelSpec, base_opt, meta_opt, cfg: EngineConfig):
    """Naive DDP baseline: correctness by SPMD propagation."""
    return make_meta_step(spec, base_opt, meta_opt, cfg)


def make_manual_step(
    spec: BilevelSpec,
    base_opt: Optimizer,
    meta_opt: Optimizer,
    cfg: EngineConfig,
    mesh,
    axes=None,
    *,
    allow_nonlinear: bool = False,
):
    """The single-sync schedule for any method whose reduce contract is
    linear. Returns a shard_map'ed step with the same signature as the
    Engine step: (state, base_batches[K], meta_batch).

    ``axes``: mesh axes to be *manual* data-parallel over (default: the
    pod/data axes, leaving "model" to the auto partitioner). Passing ALL axes
    gives pure DDP — the right configuration for models that fit per-device
    (see §Perf pair 1).

    ``allow_nonlinear``: run a method whose contract declares
    ``linear=False`` anyway, as the average-of-local-solves approximation
    (each shard solves/unrolls on its own data; only the results are
    averaged). Off by default because that is a *different* estimator from
    the method's own global-batch definition.
    """

    dp = tuple(axes) if axes is not None else data_axes(mesh)
    # the flat single-op bucket is only safe when every non-manual mesh axis
    # is trivial (pure DDP): raveling would break "model" sharding and force
    # all-gathers. With live tensor parallelism, reduce per leaf instead —
    # same single logical sync point, sharding preserved.
    auto_extent = 1
    for a in mesh.axis_names:
        if a not in dp:
            auto_extent *= mesh.shape[a]
    bucket_pmean = flat_pmean if auto_extent == 1 else tree_pmean
    # with no live auto axis the region is manual over the whole mesh: a
    # Pallas (Mosaic) kernel cannot sit under an auto axis, even of size 1,
    # because the partitioner cannot split a custom call
    manual = set(mesh.axis_names) if auto_extent == 1 else set(dp)
    method = cfg.resolve()
    policy = cfg.scale.resolve()
    spec = policy_mod.apply_to_spec(spec, policy)
    micro = cfg.scale.microbatch
    # static metric set (shard_map out_specs): the quartet, plus the
    # loss-scale automaton scalars whenever the policy scales — a config
    # property, NOT an obs switch, so observability never changes the HLO
    metric_keys = METRIC_KEYS + (SCALE_METRIC_KEYS if policy.dynamic_scaling
                                 else ())
    contract = method.reduce_contract
    if not contract.linear and not allow_nonlinear:
        raise ValueError(
            f"hypergrad method {method.name!r} declares a nonlinear reduce contract: "
            "averaging its per-shard estimates is not the method's own estimator on "
            "the global batch. Pass allow_nonlinear=True to accept the "
            "local-solve approximation, or use the pjit path."
        )

    def ddp_grad_reduce(g_loc):
        """The per-base-step DDP sync: one bucketed pmean over the data
        axes, sub-f32 leaves promoted for the collective and restored
        after. With microbatch accumulation this runs on the ACCUMULATED
        gradient — one all-reduce per base step for every M. Named
        ``grad_sync`` in the compiled ops' metadata, as the meta bucket's
        exchange is ``allreduce_flat``."""

        with jax.named_scope("grad_sync"):
            g_red = bucket_pmean(cast_for_reduce(g_loc), dp)
        return jax.tree_util.tree_map(lambda r, gl: r.astype(gl.dtype), g_red, g_loc)

    def local_step(state: EngineState, base_batches, meta_batch):
        with policy.matmul_context():
            return _local_step(state, base_batches, meta_batch)

    def _local_step(state: EngineState, base_batches, meta_batch):
        lam = state.lam

        # ---- base unroll: standard DDP (one pmean per base step), shared
        # with the Engine path — microbatch accumulation, precision casts
        # and loss-scale skip semantics are engine._unroll_base's ----
        with obs_trace.phase("base_unroll"):
            (theta, b_state, g_base, st_at_g, losses, scale_state,
             base_ok) = _unroll_base(
                spec, base_opt, state.theta, state.base_opt_state, lam,
                base_batches, scale_cfg=cfg.scale, scale_state=state.scale,
                grad_reduce=ddp_grad_reduce,
            )

        # ---- method stage 1: strictly LOCAL terms (no collective) ----
        ctx = make_context(
            base_opt, state, base_batches, meta_batch,
            theta=theta, base_opt_state=st_at_g, g_base=g_base,
            loss_scale=scale_state.scale if scale_state is not None else None,
        )
        terms = methods_mod.validate_terms(
            method, accum_mod.microbatch_local_terms(method, spec, ctx, micro,
                                                     policy.accum_jnp))

        # ---- THE single synchronization point (one bucketed all-reduce) ----
        # Exactly the contract's terms ride the bucket, plus the scalar
        # base-loss metric so logging costs no extra sync. cast_for_reduce
        # promotes only sub-f32 leaves (see its docstring for why).
        bucket = {k: terms[k] for k in contract.terms}
        bucket["__base_loss__"] = jnp.mean(losses)
        with obs_trace.phase("allreduce_flat"):
            reduced = bucket_pmean(cast_for_reduce(bucket), dp)
        base_loss = reduced.pop("__base_loss__")
        terms = dict(terms, **reduced)

        # ---- method stage 3: finalize on replica-consistent terms ----
        with obs_trace.phase("finalize"):
            hyper, theta_post = method.finalize(terms, ctx)

        with obs_trace.phase("meta_update"):
            lam, m_state, theta_post, meta_ok = guarded_meta_update(
                meta_opt, hyper, theta_post, state,
                theta_pre=theta, guard=policy.dynamic_scaling, base_ok=base_ok,
            )
            if meta_ok is not None:  # hypergrad overflow must back the scale off
                scale_state = policy_mod.backoff_on(scale_state, meta_ok, policy)

        metrics = step_metrics(method, terms, hyper, losses)
        metrics["base_loss"] = base_loss
        if meta_ok is not None:  # see engine.make_meta_step: automaton scalars
            metrics["loss_scale"] = scale_state.scale
            metrics["meta_skipped"] = 1.0 - meta_ok.astype(jnp.float32)
        # the manual schedule reports a static metric set (its out_specs
        # are static); extra per-method metrics live on the Engine path
        metrics = {k: metrics[k] for k in metric_keys}
        new_state = EngineState(
            theta=theta_post, base_opt_state=b_state, lam=lam,
            meta_opt_state=m_state, step=state.step + 1, scale=scale_state,
        )
        return new_state, metrics

    def batch_spec(t):
        nd = len(t.shape)
        return P(*((None, dp) + (None,) * (nd - 2)))  # (K, B, ...) -> shard B

    def meta_spec(t):
        nd = len(t.shape)
        return P(*((dp,) + (None,) * (nd - 1)))

    def wrap(state, base_batches, meta_batch):
        in_specs = (
            jax.tree_util.tree_map(lambda _: P(), state),
            jax.tree_util.tree_map(batch_spec, base_batches),
            jax.tree_util.tree_map(meta_spec, meta_batch),
        )
        out_specs = (
            jax.tree_util.tree_map(lambda _: P(), state),
            {k: P() for k in metric_keys},
        )
        fn = jax.shard_map(
            local_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names=manual, check_vma=False,
        )
        return fn(state, base_batches, meta_batch)

    return wrap


def count_data_allreduces(hlo_text: str) -> int:
    """Number of all-reduce(-start) ops in a lowered module (structure audit)."""
    import re

    n = 0
    for line in hlo_text.splitlines():
        if re.search(r"=\s+\S.*\s+all-reduce(-start)?\(", line) and "all-reduce-done" not in line:
            n += 1
    return n
