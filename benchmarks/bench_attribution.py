"""repro.obs.profile acceptance bench (ISSUE 8): the per-phase cost
attribution must cover >= 90% of the compiled SAMA step's FLOPs, and the
attention module must be the top FLOP sink on the transformer config.
Both are hard-asserted (fail loudly under --strict CI) and the per-phase
FLOP counts are gated against ``benchmarks/baselines/BENCH_attribution.json``
(tight 1.10x band — the counts are deterministic under the jax pin, so a
band trip names the phase whose cost structure moved).

Arms:

* ``attribution_sama``   — the WRENCH-analog mini-BERT SAMA step (the
  bench_throughput_memory configuration): full ``perf.profile_step``
  with ``attribution=True`` plus measured per-phase wall times from one
  eager (un-jitted) step under the span tracer, so the
  record carries achieved-vs-roofline utilization per phase.
* ``attribution_manual`` — the manual single-sync schedule on 8 forced
  host devices (subprocess, same harness as bench_obs): attribution of
  the distributed step, asserting coverage >= 90% there too and that the
  ``allreduce_flat`` phase carries every all-reduce byte.
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
from repro.obs import profile as profile_mod

from benchmarks.common import emit, emit_record, mini_bert, wrench_task

BATCH, UNROLL = 48, 2          # paper's WRENCH global batch
COVERAGE_FLOOR = 0.90          # ISSUE 8 acceptance
TOP_MODULE = "attention.py"    # must dominate FLOPs on the transformer


def _problem():
    ccfg, train, meta, _ = wrench_task(seed=8)
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


def _sama_arm(fast: bool):
    warmup, repeats = (1, 3) if fast else (2, 5)
    spec, theta, lam, base_b, meta_b = _problem()
    base_opt, meta_opt = optim.adam(1e-3), optim.adam(1e-3)
    cfg = EngineConfig(method="sama", unroll_steps=UNROLL)
    state = init_state(theta, lam, base_opt, meta_opt, scale=cfg.scale)
    step = make_meta_step(spec, base_opt, meta_opt, cfg)

    # measured per-phase wall: one eager step under the span tracer
    # (state untouched; the jitted step below compiles independently)
    tracer = obs_mod.Tracer()
    with obs_mod.activate(tracer):
        out = step(state, base_b, meta_b)
        jax.block_until_ready(out)

    rec = perf.profile_step(
        "attribution_sama", jax.jit(step), state, base_b, meta_b,
        samples_per_step=BATCH * UNROLL, warmup=warmup, repeats=repeats,
        extra={"method": "sama", "batch": BATCH, "unroll": UNROLL},
        attribution=True, attribution_spans=tracer.runtime_spans(),
    )
    attr = rec.attribution
    assert attr is not None

    # acceptance: >= 90% of compiled-step FLOPs land on a named phase
    if attr["coverage"] < COVERAGE_FLOOR:
        raise RuntimeError(
            f"attribution coverage {attr['coverage']:.3f} below the "
            f"{COVERAGE_FLOOR} floor — phase scopes are not reaching the "
            "compiled HLO")
    # acceptance: attention is the top FLOP sink on the transformer config
    if attr["top_module"] != TOP_MODULE:
        raise RuntimeError(
            f"top FLOP sink is {attr['top_module']!r}, expected "
            f"{TOP_MODULE!r} — the FLOP model or source attribution moved")

    emit_record(rec)
    phases = attr["phases"]
    top_phase = next(iter(phases))
    emit("attribution_sama", rec.timing.median_us,
         f"coverage={attr['coverage']:.4f};top_phase={top_phase};"
         f"top_phase_frac={phases[top_phase]['flop_frac']:.3f};"
         f"top_module={attr['top_module']}")
    return rec


MANUAL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp

from repro import optim
from repro.core import EngineConfig, init_state, problems
from repro.launch import distributed as dist
from jax.sharding import AxisType
from repro.obs import profile as profile_mod
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

cfg = EngineConfig(method="sama", unroll_steps=UNROLL)
state = init_state(theta, lam, base_opt, meta_opt, scale=cfg.scale)
with mesh:
    manual = jax.jit(dist.make_manual_step(spec, base_opt, meta_opt, cfg, mesh))
    compiled = manual.lower(state, bb, mb).compile()
attr = profile_mod.attribute(compiled, n_devices=8)
print(json.dumps({"unroll": UNROLL, "attribution": attr}))
"""


def _manual_arm():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src:" + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    # a host-device census by design: never reach for a chip the parent holds
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", MANUAL_SCRIPT],
                         capture_output=True, text=True, env=env, cwd=root,
                         timeout=900)
    if out.returncode != 0:
        raise RuntimeError(
            f"attribution manual subprocess failed:\n{out.stderr[-2000:]}")
    r = json.loads(out.stdout.strip().splitlines()[-1])
    attr = r["attribution"]
    if attr["coverage"] < COVERAGE_FLOOR:
        raise RuntimeError(
            f"manual-schedule attribution coverage {attr['coverage']:.3f} "
            f"below the {COVERAGE_FLOOR} floor")
    # the single-sync schedule's pinned census is unroll+1 all-reduces:
    # one per base step (base_unroll) + ONE flat hypergrad bucket
    # (allreduce_flat). The meta/hypergrad phases must be collective-free
    # — a collective charged there means the bucketing (or the phase
    # scopes) broke.
    phases = attr["phases"]
    stray = sum(b["collective_count"] for ph, b in phases.items()
                if ph not in ("base_unroll", "allreduce_flat"))
    flat = phases.get("allreduce_flat", {}).get("collective_count", 0)
    if stray or flat != 1:
        raise RuntimeError(
            f"collective attribution broke the single-sync shape: "
            f"{stray} stray collectives in hypergrad phases, "
            f"{flat} on allreduce_flat (expected exactly 1)")
    total = attr["total"]["collective_count"]
    if total != r["unroll"] + 1:
        raise RuntimeError(
            f"{total} attributed collectives, expected unroll+1 = "
            f"{r['unroll'] + 1}")
    rec = perf.PerfRecord(
        name="attribution_manual", attribution=attr,
        extra={"schedule": "single_sync", "unroll_steps": r["unroll"],
               "devices": 8},
    )
    emit_record(rec)
    ar = attr["phases"].get("allreduce_flat", {})
    emit("attribution_manual", 0.0,
         f"coverage={attr['coverage']:.4f};"
         f"allreduce_bytes={ar.get('collective_bytes', 0):.3e};"
         f"allreduce_count={ar.get('collective_count', 0):.0f}")


def main(fast: bool = True):
    _sama_arm(fast)
    _manual_arm()


if __name__ == "__main__":
    main()
