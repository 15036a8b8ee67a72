"""Tests for the first-class HypergradMethod API (DESIGN.md §2-5).

1. A toy estimator registered HERE (never touching src/repro/core) runs
   end-to-end through Engine, make_manual_step and repro.api.MetaLearner.
2. Registry/contract validation errors are loud and early.
3. Subprocess (8 forced host devices): for EVERY registered method with a
   linear reduce contract, the manual single-sync schedule equals the pjit
   step under identical per-device batches, and the lowered module carries
   exactly ONE meta-level all-reduce (count_data_allreduces audit: one
   textual all-reduce inside the scanned base unroll + one meta bucket;
   trip-scaled: unroll_steps + 1).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.api import MetaLearner
from repro.core import EngineConfig, Engine, init_state, problems
from repro.core.methods import (
    HypergradMethod,
    ReduceContract,
    available_methods,
    register_method,
    resolve_method,
    unregister_method,
)
from repro.launch import distributed as dist
from repro.launch.mesh import make_host_mesh


# ---------------------------------------------------------------------------
# a self-contained toy estimator (exact mixed VJP, no core imports)
# ---------------------------------------------------------------------------


class ToyMixedVJP(HypergradMethod):
    """T1-T2-style exact mixed second derivative, written from scratch
    against the protocol only — the "third-party estimator" scenario."""

    name = "toy_mixed_vjp"
    reduce_contract = ReduceContract(terms=("hypergrad", "meta_loss"), linear=True)

    def local_terms(self, spec, ctx):
        meta_loss, g_meta = jax.value_and_grad(spec.meta_scalar, argnums=0)(
            ctx.theta, ctx.lam, ctx.meta_batch
        )

        def inner(lam):
            g = jax.grad(spec.base_scalar, argnums=0)(ctx.theta, lam, ctx.last_batch)
            return sum(
                jnp.vdot(a, b)
                for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(g_meta))
            )

        hyper = jax.tree_util.tree_map(jnp.negative, jax.grad(inner)(ctx.lam))
        return {"hypergrad": hyper, "meta_loss": meta_loss}


@pytest.fixture
def toy_problem():
    def apply_fn(theta, x):
        return jnp.tanh(x @ theta["w1"]) @ theta["w2"]

    per_ex = problems.softmax_per_example(apply_fn)
    spec = problems.make_data_optimization_spec(per_ex, reweight=True)
    d, h, C = 6, 16, 3
    theta = {"w1": jax.random.normal(jax.random.PRNGKey(0), (d, h)) * 0.3,
             "w2": jax.random.normal(jax.random.PRNGKey(1), (h, C)) * 0.3}
    lam = problems.init_data_optimization_lam(jax.random.PRNGKey(2), reweight=True)
    base = {"x": jax.random.normal(jax.random.PRNGKey(3), (2, 8, d)),
            "y": jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0, C)}
    meta = {"x": jax.random.normal(jax.random.PRNGKey(5), (4, d)),
            "y": jax.random.randint(jax.random.PRNGKey(6), (4,), 0, C)}
    return spec, theta, lam, base, meta


@pytest.fixture
def custom_registered():
    register_method("toy_mixed_vjp", ToyMixedVJP())
    yield "toy_mixed_vjp"
    unregister_method("toy_mixed_vjp")


def _lam_moved(state, lam0):
    diffs = [float(jnp.max(jnp.abs(a - b)))
             for a, b in zip(jax.tree_util.tree_leaves(state.lam),
                             jax.tree_util.tree_leaves(lam0))]
    return max(diffs)


def test_custom_method_through_engine(toy_problem, custom_registered):
    spec, theta, lam, base, meta = toy_problem
    eng = Engine(spec, optim.adam(1e-2), optim.adam(1e-2),
                 EngineConfig(method=custom_registered, unroll_steps=2))
    state = eng.init(theta, lam)
    state, metrics = eng.step_fn(state, base, meta)
    assert np.isfinite(float(metrics["meta_loss"]))
    assert np.isfinite(float(metrics["hypergrad_norm"]))
    assert _lam_moved(state, lam) > 0


def test_custom_method_through_manual_step(toy_problem, custom_registered):
    spec, theta, lam, base, meta = toy_problem
    mesh = make_host_mesh()
    step = jax.jit(dist.make_manual_step(
        spec, optim.adam(1e-2), optim.adam(1e-2),
        EngineConfig(method=custom_registered, unroll_steps=2), mesh,
    ))
    state = init_state(theta, lam, optim.adam(1e-2), optim.adam(1e-2))
    with mesh:
        state, metrics = step(state, base, meta)
    assert np.isfinite(float(metrics["meta_loss"]))
    assert _lam_moved(state, lam) > 0


def test_custom_method_through_metalearner(toy_problem, custom_registered, tmp_path):
    """Acceptance: a method registered from test code runs end-to-end through
    repro.api.MetaLearner — including checkpoint save/load — without editing
    any src/repro/core file."""

    spec, theta, lam, base, meta = toy_problem
    learner = MetaLearner(spec, base_opt="adam", base_lr=1e-2, meta_opt="adam", meta_lr=1e-2,
                          method=custom_registered, unroll_steps=2,
                          checkpoint_dir=str(tmp_path))
    learner.init(theta, lam)
    hist = learner.fit(iter([(base, meta)] * 3), 3, log_every=1)
    assert len(hist) == 3
    assert np.isfinite(hist[-1]["meta_loss"])
    assert _lam_moved(learner.state, lam) > 0

    path = learner.save()
    assert os.path.basename(path) == "step_000003"
    moved_state = learner.state
    learner.init(theta, lam)  # reset
    learner.load()  # newest under checkpoint_dir
    for a, b in zip(jax.tree_util.tree_leaves(moved_state),
                    jax.tree_util.tree_leaves(learner.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_load_refuses_mismatched_method(toy_problem, tmp_path):
    spec, theta, lam, base, meta = toy_problem
    saver = MetaLearner(spec, method="sama", unroll_steps=2, checkpoint_dir=str(tmp_path))
    saver.init(theta, lam)
    saver.fit(iter([(base, meta)]), 1)
    saver.save()

    other = MetaLearner(spec, method="t1t2", unroll_steps=2, checkpoint_dir=str(tmp_path))
    other.init(theta, lam)
    with pytest.raises(ValueError, match="saved with method='sama'"):
        other.load()


def test_custom_method_instance_without_registration(toy_problem):
    """A HypergradMethod instance is accepted directly as EngineConfig.method."""

    spec, theta, lam, base, meta = toy_problem
    eng = Engine(spec, optim.adam(1e-2), optim.adam(1e-2),
                 EngineConfig(method=ToyMixedVJP(), unroll_steps=1))
    state = eng.init(theta, lam)
    base1 = jax.tree_util.tree_map(lambda x: x[:1], base)
    state, metrics = eng.step_fn(state, base1, meta)
    assert np.isfinite(float(metrics["meta_loss"]))


# ---------------------------------------------------------------------------
# registry / contract validation
# ---------------------------------------------------------------------------


def test_unknown_method_rejected_at_config_time():
    with pytest.raises(ValueError, match="not registered"):
        EngineConfig(method="definitely_not_a_method")


def test_duplicate_registration_rejected():
    register_method("dup_probe", ToyMixedVJP())
    try:
        with pytest.raises(ValueError, match="already registered"):
            register_method("dup_probe", ToyMixedVJP())
    finally:
        unregister_method("dup_probe")


def test_contract_must_include_mandatory_terms():
    with pytest.raises(ValueError, match="must include"):
        ReduceContract(terms=("hypergrad",))  # no meta_loss


def test_nonlinear_contract_refused_by_manual_schedule(toy_problem):
    spec, *_ = toy_problem
    mesh = make_host_mesh()
    for name in ("cg", "neumann", "iterdiff"):
        assert not resolve_method(name, EngineConfig(method=name)).reduce_contract.linear
        with pytest.raises(ValueError, match="nonlinear reduce contract"):
            dist.make_manual_step(spec, optim.adam(1e-2), optim.adam(1e-2),
                                  EngineConfig(method=name), mesh)


def test_builtin_methods_all_registered():
    assert set(available_methods()) >= {"sama", "sama_na", "t1t2", "neumann", "cg", "iterdiff"}


# ---------------------------------------------------------------------------
# pjit-vs-manual equality + single-sync audit for every linear method
# ---------------------------------------------------------------------------

LINEAR_METHODS = ("sama", "sama_na", "t1t2")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.core import EngineConfig, init_state, problems, methods
from repro.launch import distributed as dist
from repro.roofline import hlo_parse

mesh = jax.make_mesh((8, 1), ("data", "model"))

def apply_fn(theta, x):
    return jnp.tanh(x @ theta["w1"]) @ theta["w2"]

per_ex = problems.softmax_per_example(apply_fn)
spec = problems.make_data_optimization_spec(per_ex, reweight=True)

d, h, C, K = 6, 16, 3, 2
theta = {"w1": jax.random.normal(jax.random.PRNGKey(0), (d, h)) * 0.3,
         "w2": jax.random.normal(jax.random.PRNGKey(1), (h, C)) * 0.3}
lam = problems.init_data_optimization_lam(jax.random.PRNGKey(2), reweight=True)

x_shard = jax.random.normal(jax.random.PRNGKey(3), (K, 4, d))
y_shard = jax.random.randint(jax.random.PRNGKey(4), (K, 4), 0, C)
mx_shard = jax.random.normal(jax.random.PRNGKey(5), (2, d))
my_shard = jax.random.randint(jax.random.PRNGKey(6), (2,), 0, C)
base_tiled = {"x": jnp.tile(x_shard, (1, 8, 1)), "y": jnp.tile(y_shard, (1, 8))}
meta_tiled = {"x": jnp.tile(mx_shard, (8, 1)), "y": jnp.tile(my_shard, (8,))}

results = {}
for name in methods.available_methods():
    cfg = EngineConfig(method=name, unroll_steps=K)
    if not cfg.resolve().reduce_contract.linear:
        continue
    base_opt, meta_opt = optim.adam(1e-2), optim.adam(1e-2)
    state = init_state(theta, lam, base_opt, meta_opt)
    pjit_step = jax.jit(dist.make_pjit_step(spec, base_opt, meta_opt, cfg))
    manual = dist.make_manual_step(spec, base_opt, meta_opt, cfg, mesh)
    with mesh:
        s_ref, _ = pjit_step(state, {"x": x_shard, "y": y_shard},
                             {"x": mx_shard, "y": my_shard})
        s_man, _ = jax.jit(manual)(state, base_tiled, meta_tiled)
        hlo = jax.jit(manual).lower(state, base_tiled, meta_tiled).compile().as_text()
    equal = True
    for part in ("lam", "theta"):
        for a, b in zip(jax.tree_util.tree_leaves(getattr(s_ref, part)),
                        jax.tree_util.tree_leaves(getattr(s_man, part))):
            if not np.allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6):
                equal = False
    results[name] = {
        "equal": equal,
        "text_allreduces": dist.count_data_allreduces(hlo),
        "trip_scaled_allreduces": hlo_parse.collective_stats(hlo)["all-reduce_count"],
    }
print(json.dumps(results))
"""


@pytest.fixture(scope="module")
def linear_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_every_linear_method_covered(linear_results):
    assert set(linear_results) == set(LINEAR_METHODS)


@pytest.mark.parametrize("method", LINEAR_METHODS)
def test_pjit_vs_manual_equality(linear_results, method):
    assert linear_results[method]["equal"], linear_results[method]


@pytest.mark.parametrize("method", LINEAR_METHODS)
def test_exactly_one_meta_level_allreduce(linear_results, method):
    # textual: 1 all-reduce inside the scanned base-unroll body + exactly 1
    # meta bucket; trip-scaled: K per-step base syncs + that same 1 bucket.
    r = linear_results[method]
    assert r["text_allreduces"] == 2, r
    assert r["trip_scaled_allreduces"] == 2 + 1, r


TP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.core import EngineConfig, init_state, problems
from repro.launch import distributed as dist

# model axis LIVE (4 data x 2 model): the bucket must fall back to the
# per-leaf reduce so tensor-parallel sharding survives.
mesh = jax.make_mesh((4, 2), ("data", "model"))

def apply_fn(theta, x):
    return jnp.tanh(x @ theta["w1"]) @ theta["w2"]

spec = problems.make_data_optimization_spec(problems.softmax_per_example(apply_fn), reweight=True)
theta = {"w1": jax.random.normal(jax.random.PRNGKey(0), (6, 16)) * 0.3,
         "w2": jax.random.normal(jax.random.PRNGKey(1), (16, 3)) * 0.3}
lam = problems.init_data_optimization_lam(jax.random.PRNGKey(2), reweight=True)
base_opt, meta_opt = optim.adam(1e-2), optim.adam(1e-2)
state = init_state(theta, lam, base_opt, meta_opt)
step = jax.jit(dist.make_manual_step(
    spec, base_opt, meta_opt, EngineConfig(method="sama", unroll_steps=2), mesh))
base = {"x": jax.random.normal(jax.random.PRNGKey(3), (2, 8, 6)),
        "y": jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0, 3)}
meta = {"x": jax.random.normal(jax.random.PRNGKey(5), (4, 6)),
        "y": jax.random.randint(jax.random.PRNGKey(6), (4,), 0, 3)}
with mesh:
    state2, metrics = step(state, base, meta)
moved = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree_util.tree_leaves(state2.lam),
                            jax.tree_util.tree_leaves(state.lam)))
print(json.dumps({"finite": all(np.isfinite(float(v)) for v in metrics.values()),
                  "lam_moved": moved}))
"""


def test_manual_step_with_live_model_axis():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", TP_SCRIPT], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["finite"]
    assert r["lam_moved"] > 0
