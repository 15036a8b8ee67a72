"""The paper's single-sync distributed schedule, runnable on CPU with 8
forced host devices (must be the FIRST lines, before any jax import).

Compares the manual shard_map step against naive pjit DDP on the same
problem and prints the collective-structure audit (all-reduce counts) that
underlies the paper's Fig. 2 / Table 2 multi-GPU rows.

    python examples/distributed_train.py        # note: NOT under PYTHONPATH tricks
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim, perf
from repro.core import EngineConfig, init_state, problems
from repro.launch import distributed as dist


def apply_fn(theta, x):
    return jnp.tanh(x @ theta["w1"]) @ theta["w2"]


def main():
    from jax.sharding import AxisType

    mesh = jax.make_mesh((8, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    print(f"devices: {len(jax.devices())}, mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    per_ex = problems.softmax_per_example(apply_fn)
    spec = problems.make_data_optimization_spec(per_ex, reweight=True)
    d, h, C = 12, 32, 3
    theta = {"w1": jax.random.normal(jax.random.PRNGKey(0), (d, h)) * 0.3,
             "w2": jax.random.normal(jax.random.PRNGKey(1), (h, C)) * 0.3}
    lam = problems.init_data_optimization_lam(jax.random.PRNGKey(2), reweight=True)
    base_opt, meta_opt = optim.adam(1e-2), optim.adam(1e-2)
    cfg = EngineConfig(method="sama", unroll_steps=2)
    state = init_state(theta, lam, base_opt, meta_opt)

    step = jax.jit(dist.make_manual_step(spec, base_opt, meta_opt, cfg, mesh))

    rng = np.random.default_rng(0)
    w_true = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (d,)))
    with mesh:
        for i in range(30):
            x = rng.normal(size=(2, 64, d)).astype(np.float32)
            y = (x @ w_true > 0).astype(np.int32) % C
            mx = rng.normal(size=(32, d)).astype(np.float32)
            my = ((mx @ w_true > 0).astype(np.int32)) % C
            state, metrics = step(state, {"x": jnp.asarray(x), "y": jnp.asarray(y)},
                                  {"x": jnp.asarray(mx), "y": jnp.asarray(my)})
            if i % 10 == 0:
                print({k: round(float(v), 4) for k, v in metrics.items()})

        # measured collective audit: the paper's Fig. 2 structure on the
        # COMPILED step, trip-count-scaled (repro.perf.collectives)
        compiled = step.lower(
            state, {"x": jnp.zeros((2, 64, d)), "y": jnp.zeros((2, 64), jnp.int32)},
            {"x": jnp.zeros((32, d)), "y": jnp.zeros((32,), jnp.int32)}).compile()
        s = perf.verify_single_sync(compiled, cfg.unroll_steps)
        assert s["single_sync_ok"], s
        print(f"single-sync schedule: {s['all-reduce_count']} all-reduce sync points "
              f"(= {cfg.unroll_steps} base DDP + 1 bucketed meta sync), "
              f"{s['total_bytes'] / 1e6:.2f} MB collective traffic/step/device")


if __name__ == "__main__":
    main()
