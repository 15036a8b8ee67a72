"""MetaLearner's jitted step donates the learner's state.

1. The compiled ``step_fn`` aliases every byte of the ``EngineState`` to
   its output (``perf.compiled_memory(...).alias_bytes``), on the pjit
   schedule here and, in a subprocess with four host devices, on both
   schedules over a mesh.
2. From the first step on, a step deletes the previous state's arrays and
   JAX warns of no donated buffer it could not use.
3. Donation changes no number: the donating step is bitwise the
   undonated Engine step.
4. What the caller keeps stays valid: the arrays given to ``init``, a
   state object held across a step (the step donates a copy of it), and a
   copy of a state.
"""

import json
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim, perf
from repro.api import MetaLearner
from repro.core import Engine, EngineConfig, problems
from repro.launch.mesh import make_host_mesh

UNUSABLE = "donated buffers were not usable"


def _problem():
    def apply_fn(theta, x):
        return jnp.tanh(x @ theta["w1"]) @ theta["w2"]

    spec = problems.make_data_optimization_spec(
        problems.softmax_per_example(apply_fn), reweight=True)
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
def problem():
    return _problem()


def _learner(spec, mesh=None, schedule="pjit"):
    return MetaLearner(spec, base_opt="adam", base_lr=1e-2, meta_opt="adam", meta_lr=1e-2,
                       method="sama", unroll_steps=2, mesh=mesh, schedule=schedule)


def _alias_and_state_bytes(learner, base, meta):
    compiled = learner.step_fn.lower(learner.state, base, meta).compile()
    return (perf.compiled_memory(compiled).alias_bytes,
            perf.tree_bytes(learner.state))


def test_step_fn_aliases_the_whole_state(problem):
    spec, theta, lam, base, meta = problem
    learner = _learner(spec)
    learner.init(theta, lam)
    alias, state_bytes = _alias_and_state_bytes(learner, base, meta)
    assert alias == state_bytes > 0


@pytest.mark.parametrize("with_mesh", [False, True], ids=["no_mesh", "mesh"])
def test_each_step_deletes_the_previous_state(problem, with_mesh):
    spec, theta, lam, base, meta = problem
    learner = _learner(spec, make_host_mesh() if with_mesh else None)
    learner.init(theta, lam)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for _ in range(3):
            previous = jax.tree_util.tree_leaves(learner.state)
            learner.step(base, meta)
            assert all(x.is_deleted() for x in previous)
    assert not [w for w in caught if UNUSABLE in str(w.message)]
    assert int(learner.state.step) == 3


def test_fit_and_load_donate(problem, tmp_path):
    spec, theta, lam, base, meta = problem
    learner = _learner(spec, make_host_mesh())
    learner.checkpoint_dir = str(tmp_path)
    learner.init(theta, lam)
    previous = jax.tree_util.tree_leaves(learner.state)
    learner.fit(iter([(base, meta)] * 2), 2)
    assert all(x.is_deleted() for x in previous)
    learner.save()
    learner.load()
    restored = jax.tree_util.tree_leaves(learner.state)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        learner.step(base, meta)
    assert all(x.is_deleted() for x in restored)
    assert not [w for w in caught if UNUSABLE in str(w.message)]


def test_donated_step_is_bitwise_the_undonated_step(problem):
    spec, theta, lam, base, meta = problem
    learner = _learner(spec)
    learner.init(theta, lam)
    engine = Engine(spec, optim.adam(1e-2), optim.adam(1e-2),
                    EngineConfig(method="sama", unroll_steps=2))
    state = engine.init(theta, lam)
    for _ in range(2):
        mine = jax.device_get(learner.step(base, meta))
        state, theirs = engine.step_fn(state, base, meta)
        assert mine == jax.device_get(theirs)
    for x, y in zip(jax.tree_util.tree_leaves(learner.state), jax.tree_util.tree_leaves(state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_callers_arrays_and_held_state_stay_valid(problem):
    spec, theta, lam, base, meta = problem
    learner = _learner(spec)
    learner.init(theta, lam)
    learner.step(base, meta)
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves((theta, lam)))
    learner.init(theta, lam)  # the same arrays a second time

    before = learner.state
    learner.step(base, meta)
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves(before))
    assert int(before.step) == 0 and int(learner.state.step) == 1
    held = learner.state

    # the held state steps as the state itself would have
    other = _learner(spec)
    other.init(theta, lam)
    other.step(base, meta)
    for x, y in zip(jax.tree_util.tree_leaves(held), jax.tree_util.tree_leaves(other.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_a_copied_state_survives_the_step(problem):
    spec, theta, lam, base, meta = problem
    learner = _learner(spec)
    learner.init(theta, lam)
    before = jax.tree_util.tree_map(jnp.copy, learner.state)
    previous = jax.tree_util.tree_leaves(learner.state)
    learner.step(base, meta)
    assert all(x.is_deleted() for x in previous)
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves(before))

    # the copy steps as the state itself did
    other = _learner(spec)
    other.init(theta, lam)
    other.state = before
    del before
    other.step(base, meta)
    for x, y in zip(jax.tree_util.tree_leaves(learner.state),
                    jax.tree_util.tree_leaves(other.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys, warnings
import jax
sys.path.insert(0, os.environ["TEST_DIR"])
from test_donation import UNUSABLE, _alias_and_state_bytes, _learner, _problem
from repro.launch.mesh import make_host_mesh

spec, theta, lam, base, meta = _problem()
base = jax.tree_util.tree_map(lambda x: jax.numpy.concatenate([x] * 4, axis=1), base)
meta = jax.tree_util.tree_map(lambda x: jax.numpy.concatenate([x] * 4, axis=0), meta)
out = {}
for schedule in ("pjit", "single_sync"):
    learner = _learner(spec, make_host_mesh(), schedule)
    learner.init(theta, lam)
    alias, state_bytes = _alias_and_state_bytes(learner, base, meta)
    deleted = []
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for _ in range(2):
            previous = jax.tree_util.tree_leaves(learner.state)
            learner.step(base, meta)
            deleted.append(all(x.is_deleted() for x in previous))
    out[schedule] = {"devices": len(jax.devices()), "alias": alias,
                     "state_bytes": state_bytes, "deleted": deleted,
                     "unusable": [str(w.message) for w in caught if UNUSABLE in str(w.message)]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_results():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src", TEST_DIR=here)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", MESH_SCRIPT], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(here), timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("schedule", ["pjit", "single_sync"])
def test_four_device_mesh_donates(mesh_results, schedule):
    r = mesh_results[schedule]
    assert r["devices"] == 4
    assert r["alias"] == r["state_bytes"] > 0
    assert r["deleted"] == [True, True]
    assert r["unusable"] == []
