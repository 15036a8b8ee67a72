"""The three-level public API (DESIGN.md §5), learn2learn-style.

Level 1 — ``repro.api.MetaLearner``: one object that owns the bilevel
  program end-to-end. Pick optimizers by name, a hypergradient method by
  registry name (or hand in a ``HypergradMethod`` instance), optionally a
  mesh + schedule, and you get ``init / step / fit / save / load`` with
  checkpointing wired in. Users never hand-assemble
  spec -> opt -> engine -> mesh again.

Level 2 — ``repro.core.Engine`` / ``make_meta_step`` and
  ``repro.launch.distributed.make_manual_step``: pure step-function
  builders over the ``HypergradMethod`` protocol, for people composing
  their own training loops or launchers.

Level 3 — ``repro.core.methods`` / ``repro.core.sama`` /
  ``repro.core.baselines``: the raw estimator math and the protocol
  itself, for people writing new estimators (``register_method``) or
  studying the algorithms.

Typical use::

    from repro import api, optim, scale
    from repro.core import problems

    learner = api.MetaLearner(
        spec,
        base_opt="adam", base_lr=1e-2,
        meta_opt="adam", meta_lr=1e-2,
        method="sama", unroll_steps=2,
        scale=scale.ScaleConfig(policy="bf16", microbatch=4),  # repro.scale
        checkpoint_dir="out/ck",
    )
    learner.init(theta0, lam0)
    history = learner.fit(batch_iter, steps=200, log_every=50)
    learner.save()
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys
from typing import Any, Dict, Iterator, List, Optional, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro import checkpoint, optim
from repro.core.bilevel import BilevelSpec
from repro.core.engine import EngineConfig, EngineState, init_state, make_meta_step, run_loop
from repro.core.methods import HypergradMethod

PyTree = Any

#: schedule choices: "auto" = single_sync when a mesh is given, else jit;
#: "pjit" = naive-DDP Engine step (XLA places the collectives);
#: "single_sync" = the paper's one-bucket shard_map schedule.
SCHEDULES = ("auto", "pjit", "single_sync")

_ENGINE_FIELDS = {f.name for f in dataclasses.fields(EngineConfig)}


class MetaLearner:
    """High-level facade over the bilevel Engine and the distributed
    schedules. Holds the (pure) step function plus the current EngineState;
    all mutation is confined to ``self.state``.

    The learner owns its state. ``init`` copies the arrays it is given, and
    the jitted step donates ``self.state`` to XLA, which writes the new state
    into the old one's buffers: a step holds one copy of theta and the
    optimizer moments, not two. The old state's arrays are deleted; a caller
    that still holds the state object (``before = learner.state``) keeps it,
    because ``step`` then donates a copy (docs/api.md)."""

    def __init__(
        self,
        spec: BilevelSpec,
        *,
        base_opt: Union[str, optim.Optimizer] = "adam",
        base_lr: float = 1e-3,
        meta_opt: Union[str, optim.Optimizer] = "adam",
        meta_lr: float = 1e-3,
        method: Union[str, HypergradMethod] = "sama",
        unroll_steps: int = 1,
        engine_config: Optional[EngineConfig] = None,
        mesh=None,
        schedule: str = "auto",
        allow_nonlinear: bool = False,
        jit: bool = True,
        checkpoint_dir: Optional[str] = None,
        obs=None,
        **method_knobs,
    ):
        if schedule not in SCHEDULES:
            raise ValueError(f"schedule {schedule!r} not in {SCHEDULES}")
        unknown = set(method_knobs) - _ENGINE_FIELDS
        if unknown:
            raise TypeError(f"unknown method knobs {sorted(unknown)}; "
                            f"EngineConfig accepts {sorted(_ENGINE_FIELDS)}")

        self.spec = spec
        self.base_opt = optim.get_optimizer(base_opt, base_lr) if isinstance(base_opt, str) else base_opt
        self.meta_opt = optim.get_optimizer(meta_opt, meta_lr) if isinstance(meta_opt, str) else meta_opt
        if engine_config is not None:
            if method != "sama" or unroll_steps != 1 or method_knobs:
                raise ValueError(
                    "pass either engine_config or method/unroll_steps/method knobs, "
                    "not both — the explicit knobs would be silently ignored"
                )
            self.cfg = engine_config
        else:
            self.cfg = EngineConfig(method=method, unroll_steps=unroll_steps, **method_knobs)
        self.method = self.cfg.resolve()
        self.mesh = mesh
        self.checkpoint_dir = checkpoint_dir
        self.state: Optional[EngineState] = None
        if obs is None:
            from repro.obs import NULL_OBS
            obs = NULL_OBS
        self.obs = obs

        if schedule == "auto":
            schedule = "single_sync" if mesh is not None else "pjit"
        if schedule == "single_sync":
            if mesh is None:
                raise ValueError("schedule='single_sync' needs a mesh")
            from repro.launch.distributed import make_manual_step

            step = make_manual_step(
                self.spec, self.base_opt, self.meta_opt, self.cfg, mesh,
                allow_nonlinear=allow_nonlinear,
            )
        else:
            step = make_meta_step(self.spec, self.base_opt, self.meta_opt, self.cfg)
        self.schedule = schedule
        self._step = step
        self.step_fn = jax.jit(step, donate_argnums=(0,)) if jit else step
        # host-side count of dispatched steps: the profiler's step marker
        # reads it, where reading state.step would sync with the device
        self._dispatched = 0

    # -- lifecycle ---------------------------------------------------------

    def _place(self, tree: PyTree, *, copy: bool = False) -> PyTree:
        """``tree`` where the step returns the state: whole on every device
        of the mesh, or where each leaf is without one. ``copy`` gives new
        buffers even to a leaf that is there already."""
        where = None if self.mesh is None else NamedSharding(self.mesh, PartitionSpec())
        tree = jax.device_put(tree, where)
        # copied in a second put: a copy made while moving a leaf onto the
        # mesh may share the source's buffer on the device it came from
        return jax.device_put(tree, where, may_alias=False) if copy else tree

    def init(self, theta: PyTree, lam: PyTree) -> EngineState:
        """Build the EngineState (both levels' params + optimizer moments;
        a loss-scaling precision policy additionally seeds its
        LossScaleState from ``cfg.scale``) from copies of ``theta`` and
        ``lam``, so that the donating step never deletes the caller's
        arrays. Every leaf starts where the step returns it, so the first
        step already donates it."""
        theta, lam = self._place((theta, lam), copy=True)
        self.state = self._place(init_state(theta, lam, self.base_opt, self.meta_opt,
                                            scale=self.cfg.scale))
        self._dispatched = 0
        return self.state

    def step(self, base_batches, meta_batch) -> Dict[str, Any]:
        """One meta step: K base updates + one meta update. Advances
        ``self.state`` and returns the metric dict (jax scalars).

        The dispatch runs under ``jax.profiler.StepTraceAnnotation(
        "meta_step", step_num=n)``, so a profile marks each step. ``n`` is
        counted on the host, from 0 at ``init`` or from the restored step
        at ``load``: nothing is read back from the device.

        The jitted step donates the old state, whose arrays are deleted.
        Where a caller still holds the old state object, the step donates a
        copy of it instead, and the caller's object stays valid."""

        if self.state is None:
            raise RuntimeError("call init(theta, lam) or load(...) before step()")
        state = self.state
        # three references: self.state, `state` and getrefcount's argument
        if self.step_fn is not self._step and sys.getrefcount(state) > 3:
            state = self._place(state, copy=True)
        n = self._dispatched
        self._dispatched += 1
        with jax.profiler.StepTraceAnnotation("meta_step", step_num=n):
            if self.mesh is not None:
                with self.mesh:
                    self.state, metrics = self.step_fn(state, base_batches, meta_batch)
            else:
                self.state, metrics = self.step_fn(state, base_batches, meta_batch)
        return metrics

    def fit(
        self,
        batch_iter: Iterator[Tuple[Any, Any]],
        steps: int,
        *,
        log_every: int = 0,
        save_every: int = 0,
        obs=None,
    ) -> List[Dict[str, float]]:
        """Run ``steps`` meta steps from an iterator of
        (base_batches[K], meta_batch). Checkpoints every ``save_every``
        steps when a checkpoint_dir is configured. ``obs`` (defaulting to
        the learner's own) receives metric/scale/gate events at the
        ``log_every`` boundary — observability shares the loop's existing
        sync points (see ``run_loop``)."""

        if save_every and self.checkpoint_dir is None:
            raise ValueError("fit(save_every=...) needs a checkpoint_dir")
        if self.state is None:
            raise RuntimeError("call init(theta, lam) or load(...) before fit()")

        # the loop carries no state: a reference to self.state held across
        # the step would make step() donate a copy of it
        def step_adapter(_, base_batches, meta_batch):
            return None, self.step(base_batches, meta_batch)  # advances self.state

        def on_step(i, _):
            if save_every and (i + 1) % save_every == 0:
                self.save()

        _, history = run_loop(step_adapter, None, batch_iter, steps,
                              log_every, on_step=on_step,
                              obs=obs if obs is not None else self.obs)
        return history

    # -- telemetry ---------------------------------------------------------

    def profile(self, base_batches, meta_batch, *, warmup: int = 2,
                repeats: int = 5, name: Optional[str] = None,
                attribution: bool = False, attribution_spans=None):
        """Measure this learner's step on example batches through
        ``repro.perf``: warmup/repeat/block run timing with the compile
        split, per-device memory breakdown, and the trip-scaled collective
        census of the compiled step. Returns a ``perf.PerfRecord``.

        ``attribution=True`` additionally partitions the compiled step's
        FLOPs/bytes/collectives by engine phase (``repro.obs.profile``)
        into the record's ``attribution`` section; ``attribution_spans``
        (measured ``repro.obs.Span`` objects or dicts named by phase)
        joins wall time and roofline utilization per phase.

        Always profiles the JIT-COMPILED step (memory/collective accounting
        needs the compiled executable) — for a ``jit=False`` learner these
        are the numbers ``fit`` would see after ``jax.jit``, not its eager
        per-call overhead. State advances are discarded: the probe runs a
        compile of the step that donates nothing, again and again on
        ``self.state``, which it leaves as it was. So its memory breakdown
        counts the state twice, where the training step aliases it."""

        from repro import perf

        if self.state is None:
            raise RuntimeError("call init(theta, lam) or load(...) before profile()")
        fn = jax.jit(self._step)
        args = (self.state, base_batches, meta_batch)
        rec_name = name or f"{self.method.name}_{self.schedule}"
        extra = {"method": self.method.name, "schedule": self.schedule,
                 "unroll_steps": self.cfg.unroll_steps,
                 "microbatch": self.cfg.scale.microbatch,
                 "policy": self.cfg.scale.resolve().name}
        if self.mesh is not None:
            with self.mesh:
                return perf.profile_step(rec_name, fn, *args, warmup=warmup,
                                         repeats=repeats, extra=extra,
                                         attribution=attribution,
                                         attribution_spans=attribution_spans)
        return perf.profile_step(rec_name, fn, *args, warmup=warmup,
                                 repeats=repeats, extra=extra,
                                 attribution=attribution,
                                 attribution_spans=attribution_spans)

    def verify_census(self, base_batches, meta_batch):
        """Compile the step on these example shapes and check the
        collective census against the pinned ``unroll+1`` all-reduces
        (``perf.verify_single_sync``). Returns the census dict; when the
        learner carries an enabled obs the verdict is emitted as a
        ``census`` event (a mismatch trips the census health monitor).

        Meaningful on the manual single-sync schedule — the pjit path
        lets XLA place collectives, so nothing is pinned there. Shares
        the jit cache with training when the shapes match."""

        from repro import perf

        if self.state is None:
            raise RuntimeError(
                "call init(theta, lam) or load(...) before verify_census()")
        fn = self.step_fn if hasattr(self.step_fn, "lower") else jax.jit(self.step_fn)
        args = (self.state, base_batches, meta_batch)
        if self.mesh is not None:
            with self.mesh:
                compiled = fn.lower(*args).compile()
        else:
            compiled = fn.lower(*args).compile()
        stats = perf.verify_single_sync(compiled, self.cfg.unroll_steps)
        if self.obs.enabled:
            self.obs.observe_census(stats.get("all-reduce_count", 0),
                                    stats["expected_all_reduces"],
                                    detail={"schedule": self.schedule})
        return stats

    # -- checkpointing -----------------------------------------------------

    @functools.partial(jax.profiler.annotate_function, name="checkpoint")
    def save(self, path: Optional[str] = None, *, meta: Optional[Dict[str, Any]] = None) -> str:
        """Checkpoint the full EngineState. Default path:
        ``{checkpoint_dir}/step_{NNNNNN}``. ``meta`` entries are merged into
        the manifest alongside the learner's own (method/unroll/schedule).
        A profile shows the save as the host span ``checkpoint``."""

        if self.state is None:
            raise RuntimeError("nothing to save: no state")
        step = int(self.state.step)
        if path is None:
            if self.checkpoint_dir is None:
                raise ValueError("no path given and no checkpoint_dir configured")
            path = os.path.join(self.checkpoint_dir, f"step_{step:06d}")
        manifest_meta = {"method": self.method.name,
                         "unroll_steps": self.cfg.unroll_steps,
                         "schedule": self.schedule}
        if meta:
            manifest_meta.update(meta)
        checkpoint.save(path, self.state, step=step, meta=manifest_meta)
        if self.obs.enabled:
            self.obs.emit("checkpoint", "save", step=step,
                          data={"path": path})
        return path

    def load(self, path: Optional[str] = None) -> EngineState:
        """Restore the EngineState saved by ``save``. With no ``path``, the
        newest ``step_*`` under ``checkpoint_dir``. Needs a template state
        (from ``init``) to validate structure against."""

        if self.state is None:
            raise RuntimeError("call init(theta, lam) first: restore validates "
                               "against the live state structure")
        if path is None:
            if self.checkpoint_dir is None:
                raise ValueError("no path given and no checkpoint_dir configured")
            path = checkpoint.latest_step(self.checkpoint_dir)
            if path is None:
                raise FileNotFoundError(f"no step_* checkpoints under {self.checkpoint_dir}")
        state, manifest = checkpoint.restore(path, self.state)
        # the EngineState structure is method-independent, so a structural
        # match alone would silently resume a different estimator's
        # trajectory — cross-check the manifest save() wrote.
        meta = manifest.get("meta", {})
        for key, mine in (("method", self.method.name),
                          ("unroll_steps", self.cfg.unroll_steps)):
            if key in meta and meta[key] != mine:
                raise ValueError(
                    f"checkpoint {path} was saved with {key}={meta[key]!r} but this "
                    f"learner uses {mine!r}; construct a matching MetaLearner "
                    "(or restore via repro.checkpoint directly to override)"
                )
        self.state = self._place(state)
        self._dispatched = int(state.step)
        if self.obs.enabled:
            self.obs.emit("checkpoint", "restore", step=int(state.step),
                          data={"path": path})
        return self.state
