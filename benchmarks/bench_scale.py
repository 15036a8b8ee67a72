"""repro.scale acceptance bench (ISSUE 5): peak per-device memory of the
SAMA step must STRICTLY DECREASE as the microbatch count M grows at fixed
global batch, and the single-sync collective census must stay exactly
``unroll_steps + 1`` with accumulation active.

Three arms, all landing in PerfRecords (gated in CI against
``benchmarks/baselines/BENCH_scale.json`` — the memory band and the EXACT
census both bite):

* ``scale_m{M}``      — the jitted Engine SAMA step at M in {1, 2, 4},
  fixed global batch: timing + compiled memory breakdown. The bench
  HARD-ASSERTS monotone peak decrease (fail loudly under --strict CI).
* ``scale_bf16_m4``   — the bf16 precision policy on top of M=4
  (f32 master params, bf16 compute): the memory point the paper's
  low-precision claim rests on.
* ``scale_census_m{M}`` — the manual single-sync schedule on 8 forced
  host devices (subprocess, same harness as bench_distributed):
  trip-scaled collective census + single_sync verdict for M=1 and M=4.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro import data, optim, perf
from repro.core import EngineConfig, init_state, make_meta_step, problems
from repro.scale import ScaleConfig

from benchmarks.common import emit, emit_record, mini_bert, wrench_task

MICROBATCHES = (1, 2, 4)
BATCH, UNROLL = 48, 2  # paper's WRENCH global batch


def _problem():
    ccfg, train, meta, _ = wrench_task(seed=4)
    model = mini_bert(num_labels=ccfg.num_classes, d_model=128)
    spec = problems.make_data_optimization_spec(model.classifier_per_example,
                                                reweight=True)
    lam = problems.init_data_optimization_lam(jax.random.PRNGKey(1), reweight=True)
    theta = model.init(jax.random.PRNGKey(0))
    it = data.BatchIterator(train, meta, batch_size=BATCH, meta_batch_size=BATCH,
                            unroll=UNROLL, seed=0)
    base_b, meta_b = next(it)
    base_b = jax.tree_util.tree_map(jnp.asarray, base_b)
    meta_b = jax.tree_util.tree_map(jnp.asarray, meta_b)
    return spec, theta, lam, base_b, meta_b


def _profile(spec, theta, lam, base_b, meta_b, *, name, policy, m,
             warmup, repeats):
    base_opt, meta_opt = optim.adam(1e-3), optim.adam(1e-3)
    cfg = EngineConfig(method="sama", unroll_steps=UNROLL,
                       scale=ScaleConfig(policy=policy, microbatch=m))
    state = init_state(theta, lam, base_opt, meta_opt, scale=cfg.scale)
    step = make_meta_step(spec, base_opt, meta_opt, cfg)
    rec = perf.profile_step(
        name, jax.jit(step), state, base_b, meta_b,
        samples_per_step=BATCH * UNROLL, warmup=warmup, repeats=repeats,
        extra={"method": "sama", "policy": policy, "microbatch": m,
               "batch": BATCH, "unroll": UNROLL},
    )
    emit_record(rec)
    peak = (rec.memory or {}).get("per_device", {}).get("peak_bytes")
    peak_mb = peak / 2**20 if peak is not None else float("nan")
    emit(name, rec.timing.median_us,
         f"peak_mb={peak_mb:.1f};microbatch={m};policy={policy}")
    return peak


CENSUS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp

from repro import optim, perf
from repro.core import EngineConfig, init_state, problems
from repro.launch import distributed as dist
from jax.sharding import AxisType
from repro.scale import ScaleConfig
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

out = {}
with mesh:
    for m in (1, 4):
        cfg = EngineConfig(method="sama", unroll_steps=UNROLL,
                           scale=ScaleConfig(microbatch=m))
        state = init_state(theta, lam, base_opt, meta_opt, scale=cfg.scale)
        manual = jax.jit(dist.make_manual_step(spec, base_opt, meta_opt, cfg, mesh))
        compiled = manual.lower(state, bb, mb).compile()
        out[m] = perf.verify_single_sync(compiled, UNROLL)
print(json.dumps({"unroll": UNROLL, "census": {str(k): v for k, v in out.items()}}))
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
        # raise so --strict CI fails loudly (a skipped census would pass the
        # gate as MISSING while the accumulation claim stops being measured)
        raise RuntimeError(f"scale census subprocess failed:\n{out.stderr[-2000:]}")
    r = json.loads(out.stdout.strip().splitlines()[-1])
    for m_str, census in r["census"].items():
        if not census["single_sync_ok"]:
            raise RuntimeError(
                f"single-sync invariant BROKEN at microbatch={m_str}: "
                f"{census['all-reduce_count']} all-reduces vs expected "
                f"{census['expected_all_reduces']}")
        emit_record(perf.PerfRecord(
            name=f"scale_census_m{m_str}", collectives=census,
            extra={"schedule": "single_sync", "unroll_steps": r["unroll"],
                   "microbatch": int(m_str), "devices": 8},
        ))
        emit(f"scale_census_m{m_str}", 0.0,
             f"count={census['all-reduce_count']};"
             f"single_sync_ok={census['single_sync_ok']}")


def main(fast: bool = True):
    warmup, repeats = (1, 3) if fast else (2, 5)
    spec, theta, lam, base_b, meta_b = _problem()

    peaks = {}
    for m in MICROBATCHES:
        peaks[m] = _profile(spec, theta, lam, base_b, meta_b,
                            name=f"scale_m{m}", policy="f32", m=m,
                            warmup=warmup, repeats=repeats)

    if all(p is not None for p in peaks.values()):
        for lo, hi in zip(MICROBATCHES, MICROBATCHES[1:]):
            if not peaks[hi] < peaks[lo]:
                raise RuntimeError(
                    f"peak memory NOT strictly decreasing: M={lo} -> "
                    f"{peaks[lo]} bytes, M={hi} -> {peaks[hi]} bytes")
        emit("scale_memory_ratio_m4_over_m1", 0.0,
             f"ratio={peaks[MICROBATCHES[-1]] / peaks[1]:.3f}")

    _profile(spec, theta, lam, base_b, meta_b, name="scale_bf16_m4",
             policy="bf16", m=4, warmup=warmup, repeats=repeats)

    _census_arm()


if __name__ == "__main__":
    main()
