"""Distributed pins for repro.scale (8 forced host devices, subprocess per
the dry-run isolation rule — same harness as tests/test_distributed.py):

1. CENSUS: the manual single-sync schedule with M-way microbatch
   accumulation still lowers to EXACTLY unroll_steps + 1 all-reduces — the
   accumulation scans are collective-free and the per-base-step DDP pmean
   fires on the accumulated gradient (ISSUE acceptance criterion).
2. EQUALITY: with identical per-device batches, the microbatched manual
   step equals the microbatched single-device Engine step (the linear
   reduce contract commutes with both the shard mean and the microbatch
   mean).
3. BUCKET DTYPES: the flat reduce bucket never carries sub-f32 leaves —
   with bf16 base params (grads, v and the SAMA bucket all bf16 at the
   source) the manual step still compiles and runs on the CPU backend,
   which crashes in XLA's AllReducePromotion on bf16 variadic all-reduce
   without ``cast_for_reduce``; and f32 buckets are NOT pointlessly
   round-tripped (the census bytes pin below would catch a double cast).
"""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.core import EngineConfig, init_state, make_meta_step, problems
from repro.launch import distributed as dist
from jax.sharding import AxisType
from repro.scale import ScaleConfig

mesh = jax.make_mesh((8, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)

def apply_fn(theta, x):
    return jnp.tanh(x @ theta["w1"]) @ theta["w2"]

per_ex = problems.softmax_per_example(apply_fn)
spec = problems.make_data_optimization_spec(per_ex, reweight=True)

d, h, C = 6, 16, 3
theta = {"w1": jax.random.normal(jax.random.PRNGKey(0), (d, h)) * 0.3,
         "w2": jax.random.normal(jax.random.PRNGKey(1), (h, C)) * 0.3}
lam = problems.init_data_optimization_lam(jax.random.PRNGKey(2), reweight=True)

base_opt = optim.adam(1e-2)
meta_opt = optim.adam(1e-2)
K, M = 2, 4
cfg = EngineConfig(method="sama", unroll_steps=K, scale=ScaleConfig(microbatch=M))
state = init_state(theta, lam, base_opt, meta_opt, scale=cfg.scale)

# per-shard batches sized so every shard splits into M microbatches
pb, pmb = 8, 8  # per-device base / meta batch (divisible by M=4)
kx = jax.random.PRNGKey(3)
x_shard = jax.random.normal(kx, (K, pb, d))
y_shard = jax.random.randint(jax.random.PRNGKey(4), (K, pb), 0, C)
mx_shard = jax.random.normal(jax.random.PRNGKey(5), (pmb, d))
my_shard = jax.random.randint(jax.random.PRNGKey(6), (pmb,), 0, C)

base_tiled = {"x": jnp.tile(x_shard, (1, 8, 1)), "y": jnp.tile(y_shard, (1, 8))}
meta_tiled = {"x": jnp.tile(mx_shard, (8, 1)), "y": jnp.tile(my_shard, (8,))}

engine_step = jax.jit(make_meta_step(spec, base_opt, meta_opt, cfg))
manual_step = jax.jit(dist.make_manual_step(spec, base_opt, meta_opt, cfg, mesh))

s_ref, m_ref = engine_step(state, {"x": x_shard, "y": y_shard},
                           {"x": mx_shard, "y": my_shard})
with mesh:
    s_man, m_man = manual_step(state, base_tiled, meta_tiled)

ok_equal = True
for a, b in zip(jax.tree_util.tree_leaves((s_ref.lam, s_ref.theta)),
                jax.tree_util.tree_leaves((s_man.lam, s_man.theta))):
    if not np.allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6):
        ok_equal = False

# census: the microbatched manual step on genuinely sharded batches
B, MB = 64, 64
xg = jax.random.normal(jax.random.PRNGKey(7), (K, B, d))
yg = jax.random.randint(jax.random.PRNGKey(8), (K, B), 0, C)
mxg = jax.random.normal(jax.random.PRNGKey(9), (MB, d))
myg = jax.random.randint(jax.random.PRNGKey(10), (MB,), 0, C)
from repro.roofline import hlo_parse
census = {}
with mesh:
    for m_count in (1, 4):
        cfg_m = EngineConfig(method="sama", unroll_steps=K,
                             scale=ScaleConfig(microbatch=m_count))
        hlo = jax.jit(dist.make_manual_step(spec, base_opt, meta_opt, cfg_m, mesh)) \
            .lower(state, {"x": xg, "y": yg}, {"x": mxg, "y": myg}).compile().as_text()
        census[m_count] = hlo_parse.collective_stats(hlo)

# bf16 params end-to-end: grads/v/bucket are bf16 at the source; without
# cast_for_reduce this CRASHES in XLA AllReducePromotion on CPU. (Raw
# bf16 MASTER params also hit the cold-state Adam adaptation pathology —
# eps can be NaN on step 0 regardless of schedule, pre-existing and the
# reason the PrecisionPolicy keeps masters f32 — so the numeric pin here
# is base_loss + dtype preservation, not the SAMA terms.)
theta16 = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), theta)
cfg16 = EngineConfig(method="sama", unroll_steps=K, scale=ScaleConfig(microbatch=M))
state16 = init_state(theta16, lam, base_opt, meta_opt, scale=cfg16.scale)
with mesh:
    s16, m16 = jax.jit(dist.make_manual_step(spec, base_opt, meta_opt, cfg16, mesh))(
        state16, base_tiled, meta_tiled)
bf16_ok = bool(np.isfinite(float(m16["base_loss"])))
bf16_dtypes_kept = all(
    a.dtype == b.dtype for a, b in zip(jax.tree_util.tree_leaves(state16.theta),
                                       jax.tree_util.tree_leaves(s16.theta)))

# the POLICY route (f32 masters, bf16 compute) is the supported way to run
# bf16 — every metric finite on the manual schedule
cfg_pol = EngineConfig(method="sama", unroll_steps=K,
                       scale=ScaleConfig(policy="bf16", microbatch=M))
state_pol = init_state(theta, lam, base_opt, meta_opt, scale=cfg_pol.scale)
with mesh:
    _, m_pol = jax.jit(dist.make_manual_step(spec, base_opt, meta_opt, cfg_pol, mesh))(
        state_pol, base_tiled, meta_tiled)
policy_bf16_finite = all(np.isfinite(float(v)) for v in m_pol.values())

# planner under the manual schedule: candidates must divide the PER-DEVICE
# shard (64/8 = 8), not the global batch — a global-batch candidate (e.g.
# 64) would crash split_batch inside shard_map at trace time
from repro.scale import plan_microbatch
plan = plan_microbatch(
    spec, base_opt, meta_opt, EngineConfig(method="sama", unroll_steps=K),
    state, {"x": xg, "y": yg}, {"x": mxg, "y": myg},
    hbm_budget=10**12, mesh=mesh, schedule="single_sync")
plan_info = {"microbatch": plan.microbatch, "fits": plan.fits,
             "max_tried": max(m for m, _ in plan.candidates)}

print(json.dumps({
    "plan": plan_info,
    "equal_under_tiling": ok_equal,
    "allreduce_m1": census[1]["all-reduce_count"],
    "allreduce_m4": census[4]["all-reduce_count"],
    "bytes_m1": census[1]["total_bytes"],
    "bytes_m4": census[4]["total_bytes"],
    "unroll": K,
    "bf16_ok": bf16_ok,
    "bf16_dtypes_kept": bf16_dtypes_kept,
    "policy_bf16_finite": policy_bf16_finite,
}))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_microbatched_manual_equals_engine_under_identical_shards(result):
    assert result["equal_under_tiling"]


def test_census_exactly_unroll_plus_one_under_accumulation(result):
    # the single-sync invariant survives microbatching: K base DDP
    # flat-bucket pmeans (on ACCUMULATED grads) + 1 meta bucket
    expected = result["unroll"] + 1
    assert result["allreduce_m1"] == expected, result
    assert result["allreduce_m4"] == expected, result


def test_census_bytes_unchanged_by_accumulation(result):
    # accumulation moves compute, not communication: same buckets, same
    # bytes (also pins that no extra f32 round-trip snuck into the bucket)
    assert result["bytes_m4"] == result["bytes_m1"], result


def test_bf16_bucket_compiles_and_trains(result):
    # the cast_for_reduce regression pin: bf16 leaves in the flat bucket
    # must be promoted before the variadic all-reduce (XLA CPU crashes
    # otherwise) and params keep their bf16 dtype through the step
    assert result["bf16_ok"]
    assert result["bf16_dtypes_kept"]


def test_policy_bf16_all_metrics_finite_on_manual_schedule(result):
    # the supported bf16 route (f32 masters + bf16 compute) stays finite
    # end-to-end under the single-sync schedule with accumulation active
    assert result["policy_bf16_finite"]


def test_planner_on_manual_schedule_uses_per_shard_candidates(result):
    # global batch 64 over 8 data-parallel devices -> the planner may only
    # try divisors of the 8-example shard; with an effectively unlimited
    # budget it must land on M=1 and never touch a global-batch candidate
    assert result["plan"]["fits"]
    assert result["plan"]["microbatch"] == 1
    assert result["plan"]["max_tried"] <= 8
