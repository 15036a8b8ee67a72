"""Distributed SAMA tests. Needs >1 host device, so the real work runs in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main
pytest process keeps 1 device, per the dry-run isolation rule).

Pins:
1. with identical per-device batches, the manual single-sync schedule equals
   the single-device Engine step bit-for-bit (same math, different comms);
2. with genuinely sharded batches, both paths produce finite, close-in-norm
   hypergradient steps (same estimator in expectation);
3. collective structure: the manual path lowers to exactly
   unroll_steps + 1 all-reduces (K base DDP syncs + ONE meta bucket),
   while the naive pjit path emits more (it syncs the meta pass too).
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
from repro.core import BilevelSpec, EngineConfig, init_state, make_meta_step, problems
from repro.launch import distributed as dist
from jax.sharding import AxisType

mesh = jax.make_mesh((8, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)

def apply_fn(theta, x):
    return jnp.tanh(x @ theta["w1"]) @ theta["w2"]

per_ex = problems.softmax_per_example(apply_fn)
spec = problems.make_data_optimization_spec(per_ex, reweight=True)

d, h, C = 6, 16, 3
key = jax.random.PRNGKey(0)
theta = {"w1": jax.random.normal(key, (d, h)) * 0.3,
         "w2": jax.random.normal(jax.random.PRNGKey(1), (h, C)) * 0.3}
lam = problems.init_data_optimization_lam(jax.random.PRNGKey(2), reweight=True)

base_opt = optim.adam(1e-2)
meta_opt = optim.adam(1e-2)
cfg = EngineConfig(method="sama", unroll_steps=2)
state = init_state(theta, lam, base_opt, meta_opt)

K, B, MB = 2, 32, 16  # per-device 4 / 2
kx = jax.random.PRNGKey(3)
x_shard = jax.random.normal(kx, (K, 4, d))
y_shard = jax.random.randint(jax.random.PRNGKey(4), (K, 4), 0, C)
mx_shard = jax.random.normal(jax.random.PRNGKey(5), (2, d))
my_shard = jax.random.randint(jax.random.PRNGKey(6), (2,), 0, C)

# identical per-device batches: tile the shard 8x
base_tiled = {"x": jnp.tile(x_shard, (1, 8, 1)), "y": jnp.tile(y_shard, (1, 8))}
meta_tiled = {"x": jnp.tile(mx_shard, (8, 1)), "y": jnp.tile(my_shard, (8,))}

pjit_step = jax.jit(dist.make_pjit_step(spec, base_opt, meta_opt, cfg))
manual_step = jax.jit(dist.make_manual_step(spec, base_opt, meta_opt, cfg, mesh))

with mesh:
    s_ref, m_ref = pjit_step(state, {"x": x_shard, "y": y_shard},
                             {"x": mx_shard, "y": my_shard})
    s_man, m_man = manual_step(state, base_tiled, meta_tiled)

# 1. bitwise-ish equality under identical shards
ok_equal = True
for a, b in zip(jax.tree_util.tree_leaves(s_ref.lam), jax.tree_util.tree_leaves(s_man.lam)):
    if not np.allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6):
        ok_equal = False
for a, b in zip(jax.tree_util.tree_leaves(s_ref.theta), jax.tree_util.tree_leaves(s_man.theta)):
    if not np.allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6):
        ok_equal = False

# 2. genuinely sharded run: finite and lam moves
xg = jax.random.normal(jax.random.PRNGKey(7), (K, B, d))
yg = jax.random.randint(jax.random.PRNGKey(8), (K, B), 0, C)
mxg = jax.random.normal(jax.random.PRNGKey(9), (MB, d))
myg = jax.random.randint(jax.random.PRNGKey(10), (MB,), 0, C)
with mesh:
    s2, m2 = manual_step(state, {"x": xg, "y": yg}, {"x": mxg, "y": myg})
ok_finite = all(np.isfinite(float(v)) for v in m2.values())
moved = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree_util.tree_leaves(s2.lam), jax.tree_util.tree_leaves(state.lam)))

# 3. collective structure audit on optimized HLO
with mesh:
    man_hlo = jax.jit(dist.make_manual_step(spec, base_opt, meta_opt, cfg, mesh)) \
        .lower(state, {"x": xg, "y": yg}, {"x": mxg, "y": myg}).compile().as_text()
    pjit_hlo = jax.jit(dist.make_pjit_step(spec, base_opt, meta_opt, cfg)) \
        .lower(
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                    sharding=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())), state),
            {"x": jax.ShapeDtypeStruct((K, B, d), jnp.float32,
                 sharding=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(None, "data"))),
             "y": jax.ShapeDtypeStruct((K, B), jnp.int32,
                 sharding=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(None, "data")))},
            {"x": jax.ShapeDtypeStruct((MB, d), jnp.float32,
                 sharding=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))),
             "y": jax.ShapeDtypeStruct((MB,), jnp.int32,
                 sharding=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data")))},
        ).compile().as_text()

from repro.roofline import hlo_parse
man_ar = hlo_parse.collective_stats(man_hlo)
pjit_ar = hlo_parse.collective_stats(pjit_hlo)

print(json.dumps({
    "equal_under_tiling": ok_equal,
    "finite": ok_finite,
    "lam_moved": moved,
    "manual_allreduce_count": man_ar["all-reduce_count"],
    "manual_total_collectives": man_ar["total_count"],
    "pjit_allreduce_count": pjit_ar["all-reduce_count"],
    "manual_collective_bytes": man_ar["total_bytes"],
    "pjit_collective_bytes": pjit_ar["total_bytes"],
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


def test_manual_equals_pjit_under_identical_shards(result):
    assert result["equal_under_tiling"]


def test_manual_step_finite_and_learning(result):
    assert result["finite"]
    assert result["lam_moved"] > 0


def test_single_sync_schedule_collective_structure(result):
    # K=2 base DDP flat-bucket pmeans + 1 meta flat bucket = EXACTLY 3
    # all-reduces. The flat bucket (distributed.flat_pmean) makes this
    # structural rather than dependent on XLA's all-reduce combiner.
    assert result["manual_allreduce_count"] == 3, result
    assert result["manual_allreduce_count"] < result["pjit_allreduce_count"], result
    assert result["manual_collective_bytes"] < result["pjit_collective_bytes"], result
