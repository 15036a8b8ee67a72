"""End-to-end SAMA training driver, on the MetaLearner facade.

    PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --smoke \
        --steps 50 --method sama [--manual-collectives] [--ckpt out/ck] \
        [--precision bf16] [--microbatch 4 | --hbm-budget-gb 8]

Wires together: config registry -> synthetic noisy LM data -> Model ->
data-optimization BilevelSpec -> ``repro.api.MetaLearner`` (which owns the
Engine or the single-sync shard_map schedule + checkpointing). On the CPU
use --smoke; on a TPU the same script runs the full config (e.g.
``--arch bert-base --batch 32 --seq 128``). The mesh is data-parallel over
every device present, ``(data=len(jax.devices()), model=1)``.
``--method`` accepts any registered hypergradient method, including
third-party registrations.

repro.scale knobs: ``--precision`` picks the policy (f32/bf16/f16) and
the model's activation dtype with it, ``--microbatch`` forces an
accumulation factor, and ``--hbm-budget-gb`` asks the memory planner
(``repro.scale.plan_microbatch``) to pick the smallest M whose compiled
step fits that per-device budget instead.

``--profile-dir DIR`` writes a ``jax.profiler`` capture of three jitted
steps after the second (which may still compile): the device ops under
their phase and block scopes, each step's ``meta_step`` marker, and the
host spans ``next_batch`` and ``log_read``.

``main(argv)`` is also the library entry point: it returns the built
``Trainer`` and the logged metric rows (``chip_smoke.py`` drives it).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import api, configs, data, scale
from repro import obs as obs_mod
from repro.core import available_methods, problems
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import Model

#: the steps a --profile-dir capture holds: three after the first two
PROFILED = (2, 3, 4)


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--unroll", type=int, default=2)
    ap.add_argument("--method", default="sama", choices=list(available_methods()))
    ap.add_argument("--base-lr", type=float, default=1e-3)
    ap.add_argument("--meta-lr", type=float, default=1e-3)
    ap.add_argument("--manual-collectives", action="store_true",
                    help="use the paper's single-sync shard_map schedule")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--precision", default="f32", choices=sorted(scale.POLICIES),
                    help="repro.scale precision policy")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="accumulate each base batch as M microbatches")
    ap.add_argument("--hbm-budget-gb", type=float, default=None,
                    help="let repro.scale.plan_microbatch pick the smallest M "
                         "whose compiled step fits this per-device budget "
                         "(overrides --microbatch)")
    ap.add_argument("--obs-log", default=None, metavar="PATH",
                    help="append structured events (JSONL) for "
                         "`python -m repro.obs.report`")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="write a jax.profiler trace of steps "
                         f"{PROFILED[0]}-{PROFILED[-1]} (0-based) under DIR")
    args = ap.parse_args(argv)
    if args.profile_dir and args.steps <= PROFILED[-1]:
        ap.error(f"--profile-dir needs --steps > {PROFILED[-1]}")
    return args


@dataclasses.dataclass
class Trainer:
    """What ``build`` assembles: the config, mesh, model and learner (its
    state initialised from the fixed seeds), and the seeded batch maker."""

    cfg: Any
    mesh: Any
    model: Model
    learner: api.MetaLearner
    make_batch: Callable[..., Dict[str, jnp.ndarray]]
    n_params: int


def build(args: argparse.Namespace, obs) -> Trainer:
    scale_cfg = scale.ScaleConfig(policy=args.precision, microbatch=args.microbatch)
    cfg = configs.get_smoke_config(args.arch) if args.smoke else configs.get_config(args.arch)
    # the activations follow --precision: bf16/f16 policies compute in
    # 16 bits end to end (every shipped config sets f32 activations)
    cfg = cfg.replace(dtype=scale_cfg.resolve().compute_dtype)
    mesh = make_host_mesh()
    model = Model(cfg)

    spec = problems.make_data_optimization_spec(
        model.classifier_per_example if cfg.family == "encoder" else model.per_example,
        reweight=True,
    )
    learner_args = dict(
        base_opt="adam", base_lr=args.base_lr,
        meta_opt="adam", meta_lr=args.meta_lr,
        method=args.method, unroll_steps=args.unroll,
        mesh=mesh,
        schedule="single_sync" if args.manual_collectives else "pjit",
        checkpoint_dir=args.ckpt,
        obs=obs,
    )
    learner = api.MetaLearner(spec, scale=scale_cfg, **learner_args)

    theta = model.init(jax.random.PRNGKey(0))
    lam = problems.init_data_optimization_lam(jax.random.PRNGKey(1), reweight=True)
    learner.init(theta, lam)

    lm_cfg = data.LMStreamConfig(vocab_size=cfg.vocab_size, seq_len=args.seq)
    train_rng = np.random.default_rng(0)

    def make_batch(batch, unroll=None, rng=None):
        rng = rng if rng is not None else train_rng
        shape_batch = batch * (unroll or 1)
        b = data.lm_batch(lm_cfg, rng, shape_batch)
        toks = b["tokens"].reshape((unroll, batch, args.seq) if unroll else (batch, args.seq))
        out = {"tokens": jnp.asarray(toks)}
        if cfg.family == "vlm":
            shp = ((unroll, batch) if unroll else (batch,)) + (cfg.vision_tokens, cfg.vision_dim)
            out["patches"] = jnp.zeros(shp, jnp.float32)
        if cfg.family == "audio":
            shp = ((unroll, batch) if unroll else (batch,)) + (cfg.encoder_seq, cfg.d_model)
            out["frames"] = jnp.zeros(shp, jnp.float32)
        if cfg.family == "encoder":
            yshape = (unroll, batch) if unroll else (batch,)
            out["y"] = jnp.asarray(rng.integers(0, cfg.num_labels, size=yshape), jnp.int32)
        return out

    if args.hbm_budget_gb is not None:
        # plan on the learner's own batch SHAPES with a throwaway RNG so the
        # training data stream is identical to a --microbatch run (the
        # planner compiles candidates; nothing trains yet)
        plan_rng = np.random.default_rng(0)
        plan = scale.plan_microbatch(
            spec, learner.base_opt, learner.meta_opt, learner.cfg,
            learner.state, make_batch(args.batch, args.unroll, rng=plan_rng),
            make_batch(max(args.batch // 2, 1), rng=plan_rng),
            hbm_budget=int(args.hbm_budget_gb * 2 ** 30),
            mesh=mesh if args.manual_collectives else None,
            schedule="single_sync" if args.manual_collectives else "pjit",
        )
        peak_mb = plan.peak_bytes / 2 ** 20 if plan.peak_bytes is not None else float("nan")
        obs.log("planner",
                f"planner: microbatch={plan.microbatch} fits={plan.fits} "
                f"peak={peak_mb:.1f}MB budget={args.hbm_budget_gb}GB "
                f"source={plan.source}",
                microbatch=plan.microbatch, fits=plan.fits,
                peak_bytes=plan.peak_bytes, source=plan.source,
                budget_gb=args.hbm_budget_gb)
        if plan.microbatch != scale_cfg.microbatch:
            scale_cfg = plan.scale
            learner = api.MetaLearner(spec, scale=scale_cfg, **learner_args)
            learner.init(theta, lam)

    return Trainer(cfg=cfg, mesh=mesh, model=model, learner=learner,
                   make_batch=make_batch, n_params=model.num_params(theta))


def main(argv: Optional[Sequence[str]] = None) -> Tuple[Trainer, List[Dict[str, float]]]:
    """Parse ``argv``, build, train. Returns the Trainer and one host
    metric row per logged step (unrounded; ``step_s`` is that step's
    seconds from dispatch until its metrics are on the host)."""

    args = parse_args(argv)
    enable_compile_cache()
    # All reporting flows through one obs pipeline: the ConsoleSink keeps
    # stdout identical to the pre-obs prints; --obs-log adds the durable
    # JSONL the report CLI consumes.
    obs = obs_mod.make_obs(log_path=args.obs_log, console=True,
                           run_id=f"train-{args.arch}-{args.method}")
    obs_mod.set_default(obs)

    tr = build(args, obs)
    cfg, mesh, learner, make_batch = tr.cfg, tr.mesh, tr.learner, tr.make_batch
    n_params = tr.n_params
    microbatch = learner.cfg.scale.microbatch
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    obs.emit("run", "run_start", data={
        "cli": "train", "arch": cfg.name, "method": args.method,
        "steps": args.steps, "unroll": args.unroll, "params": n_params,
        "schedule": learner.schedule, "precision": args.precision,
        "microbatch": microbatch, "mesh": mesh_shape})
    obs.log("run_header",
            f"arch={cfg.name} params={n_params:,} method={args.method} "
            f"schedule={learner.schedule} precision={args.precision} "
            f"microbatch={microbatch} mesh={mesh_shape}")

    rows: List[Dict[str, float]] = []
    t0 = time.time()
    with contextlib.ExitStack() as capture:  # stops a capture cut short
        for i in range(args.steps):
            if args.profile_dir and i == PROFILED[0]:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # TraceMe spans, not every call
                capture.enter_context(jax.profiler.trace(
                    args.profile_dir, profiler_options=options))
            with jax.profiler.TraceAnnotation("next_batch"):
                base = make_batch(args.batch, args.unroll)
                meta = make_batch(max(args.batch // 2, 1))
            t_step = time.perf_counter()
            metrics = learner.step(base, meta)
            if i % args.log_every == 0 or i == args.steps - 1:
                # one packed D2H read for the whole metric dict, then the same
                # greppable JSON line the CLI always printed (ConsoleSink)
                with jax.profiler.TraceAnnotation("log_read"):
                    host = obs_mod.packed_read(metrics)
                rows.append(dict(host, step=i, step_s=time.perf_counter() - t_step))
                row = {k: round(v, 4) for k, v in host.items()}
                row["elapsed_s"] = round(time.time() - t0, 1)
                obs.observe_step(i, row)
            if args.profile_dir and i == PROFILED[-1]:
                jax.block_until_ready(metrics)
                capture.close()
                obs.log("profile", f"profile of steps {PROFILED[0]}-{i} written "
                        f"under {args.profile_dir}", path=args.profile_dir)

    if args.manual_collectives and args.obs_log:
        census = learner.verify_census(base, meta)
        obs.log("census",
                f"census: all_reduces={census.get('all-reduce_count', 0)} "
                f"expected={census['expected_all_reduces']} "
                f"ok={census['single_sync_ok']}")

    if args.ckpt:
        path = learner.save(meta={"arch": cfg.name})
        obs.log("checkpoint", f"checkpoint written to {path}", path=path)

    if args.obs_log:  # snapshot is for the report CLI, not the console
        obs.emit("metrics", "registry_snapshot", data=obs.metrics.snapshot())
    obs.emit("run", "run_end", data={
        "elapsed_s": round(time.time() - t0, 1), "steps": args.steps,
        "health": obs.health.status, "ring_dropped": obs.sink_dropped()})
    obs.close()
    return tr, rows


if __name__ == "__main__":
    main()
