"""repro.obs acceptance bench (ISSUE 7): the instrumented training loop
must cost no more than 3% median wall-time over the uninstrumented one,
and the single-sync collective census must stay exactly ``unroll + 1``
with observability fully enabled.

Three arms, all landing in PerfRecords (gated in CI against
``benchmarks/baselines/BENCH_obs.json``):

* ``obs_off_loop`` — ``run_loop`` over the jitted SAMA step on the
  WRENCH-analog mini-BERT task, obs disabled (NULL_OBS): the baseline.
* ``obs_on_loop``  — the SAME loop with a fully enabled pipeline (ring
  sink + health monitors + active span tracer + packed metric reads at
  log cadence). The bench HARD-ASSERTS ``median_on <= 1.03 * median_off``
  (fail loudly under --strict CI).
* ``obs_census``   — the manual single-sync schedule on 8 forced host
  devices (subprocess, same harness as bench_scale) lowered WITH the
  tracer active and a default obs installed: trip-scaled census +
  single_sync verdict — observability must not add a collective.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro import data, obs as obs_mod, optim, perf
from repro.core import EngineConfig, init_state, make_meta_step, problems
from repro.core.engine import run_loop
from repro.obs.events import RingSink

from benchmarks.common import emit, emit_record, mini_bert, wrench_task

BATCH, UNROLL = 48, 2  # paper's WRENCH global batch
OVERHEAD_LIMIT = 1.03  # ISSUE 7 acceptance: <= 3% median wall-time
LOG_EVERY = 5


def _problem():
    ccfg, train, meta, _ = wrench_task(seed=7)
    model = mini_bert(num_labels=ccfg.num_classes, d_model=128)
    spec = problems.make_data_optimization_spec(model.classifier_per_example,
                                                reweight=True)
    lam = problems.init_data_optimization_lam(jax.random.PRNGKey(1),
                                              reweight=True)
    theta = model.init(jax.random.PRNGKey(0))
    it = data.BatchIterator(train, meta, batch_size=BATCH, meta_batch_size=BATCH,
                            unroll=UNROLL, seed=0)
    base_b, meta_b = next(it)
    base_b = jax.tree_util.tree_map(jnp.asarray, base_b)
    meta_b = jax.tree_util.tree_map(jnp.asarray, meta_b)
    return spec, theta, lam, base_b, meta_b


def _loop_arm(name, step, state, base_b, meta_b, *, n_steps, obs, tracer,
              warmup, repeats):
    """Time run_loop (host driver — no lowering, run-phase stats only)."""

    def drive():
        batches = iter([(base_b, meta_b)] * n_steps)
        if tracer is not None:
            with obs_mod.activate(tracer):
                out, _ = run_loop(step, state, batches, n_steps,
                                  log_every=LOG_EVERY, obs=obs)
        else:
            out, _ = run_loop(step, state, batches, n_steps,
                              log_every=LOG_EVERY, obs=obs)
        return out.theta

    timing = perf.time_callable(drive, warmup=warmup, repeats=repeats)
    emit_record(perf.PerfRecord(
        name=name, us_per_step=timing.as_dict(),
        samples_per_s=BATCH * UNROLL * n_steps / (timing.median_us / 1e6),
        extra={"method": "sama", "batch": BATCH, "unroll": UNROLL,
               "loop_steps": n_steps, "log_every": LOG_EVERY,
               "obs": obs is not None and obs.enabled},
    ))
    emit(name, timing.median_us,
         f"loop_steps={n_steps};obs={'on' if obs is not None and obs.enabled else 'off'}")
    return timing.median_us


CENSUS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp

from repro import obs as obs_mod, optim, perf
from repro.core import EngineConfig, init_state, problems
from repro.launch import distributed as dist
from jax.sharding import AxisType
from benchmarks.common import mini_bert

UNROLL = 2
mesh = jax.make_mesh((8, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
model = mini_bert(num_labels=4, d_model=128)
spec = problems.make_data_optimization_spec(model.classifier_per_example, reweight=True)
lam = problems.init_data_optimization_lam(jax.random.PRNGKey(1), reweight=True)
theta = model.init(jax.random.PRNGKey(0))
base_opt, meta_opt = optim.adam(1e-3), optim.adam(1e-3)

K, B, S, MB = UNROLL, 64, 32, 32
bb = {"tokens": jnp.zeros((K, B, S), jnp.int32), "y": jnp.zeros((K, B), jnp.int32)}
mb = {"tokens": jnp.zeros((MB, S), jnp.int32), "y": jnp.zeros((MB,), jnp.int32)}

# a fully live pipeline during lowering: default obs + active span tracer
obs_mod.set_default(obs_mod.make_obs(ring=4096))
cfg = EngineConfig(method="sama", unroll_steps=UNROLL)
state = init_state(theta, lam, base_opt, meta_opt, scale=cfg.scale)
with mesh, obs_mod.activate(obs_mod.Tracer(obs=obs_mod.get_default())):
    manual = jax.jit(dist.make_manual_step(spec, base_opt, meta_opt, cfg, mesh))
    compiled = manual.lower(state, bb, mb).compile()
    census = perf.verify_single_sync(compiled, UNROLL)
print(json.dumps({"unroll": UNROLL, "census": census}))
"""


def _census_arm():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src:" + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    # a host-device census by design: never reach for a chip the parent holds
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", CENSUS_SCRIPT], capture_output=True,
                         text=True, env=env, cwd=root, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"obs census subprocess failed:\n{out.stderr[-2000:]}")
    r = json.loads(out.stdout.strip().splitlines()[-1])
    census = r["census"]
    if not census["single_sync_ok"]:
        raise RuntimeError(
            f"single-sync invariant BROKEN with obs enabled: "
            f"{census.get('all-reduce_count', 0)} all-reduces vs expected "
            f"{census['expected_all_reduces']}")
    emit_record(perf.PerfRecord(
        name="obs_census", collectives=census,
        extra={"schedule": "single_sync", "unroll_steps": r["unroll"],
               "devices": 8, "obs": True},
    ))
    emit("obs_census", 0.0,
         f"count={census.get('all-reduce_count', 0)};"
         f"single_sync_ok={census['single_sync_ok']}")


def main(fast: bool = True):
    warmup, repeats = (1, 3) if fast else (2, 5)
    n_steps = 10 if fast else 25
    spec, theta, lam, base_b, meta_b = _problem()
    base_opt, meta_opt = optim.adam(1e-3), optim.adam(1e-3)
    cfg = EngineConfig(method="sama", unroll_steps=UNROLL)
    state = init_state(theta, lam, base_opt, meta_opt, scale=cfg.scale)
    step = jax.jit(make_meta_step(spec, base_opt, meta_opt, cfg))

    off_us = _loop_arm("obs_off_loop", step, state, base_b, meta_b,
                       n_steps=n_steps, obs=None, tracer=None,
                       warmup=warmup, repeats=repeats)

    live = obs_mod.Obs(sink=RingSink(8192), monitor=True)
    on_us = _loop_arm("obs_on_loop", step, state, base_b, meta_b,
                      n_steps=n_steps, obs=live,
                      tracer=obs_mod.Tracer(obs=live),
                      warmup=warmup, repeats=repeats)

    ratio = on_us / off_us
    emit("obs_overhead_ratio", 0.0, f"ratio={ratio:.4f};limit={OVERHEAD_LIMIT}")
    if ratio > OVERHEAD_LIMIT:
        raise RuntimeError(
            f"obs overhead {100 * (ratio - 1):.2f}% exceeds the "
            f"{100 * (OVERHEAD_LIMIT - 1):.0f}% budget "
            f"(off={off_us:.0f}us, on={on_us:.0f}us)")

    _census_arm()


if __name__ == "__main__":
    main()
