"""The kernel backend-dispatch registry (DESIGN.md §10, docs/kernels.md).

Covers the registry semantics, the selection precedence (explicit backend >
$REPRO_KERNEL_BACKEND > platform default), safe fallback for
ineligible backends, a parity sweep of EVERY registered kernel against its
ref.py oracle on every backend available on CPU CI (pallas-interpret + ref)
including ragged/non-tile-aligned shapes, and the ISSUE acceptance pins:
``adam.adaptation`` lowers through the dispatched fused kernel when enabled
(and through ref when forced), numerics within 1e-5 of the oracle, and the
manual SAMA step's measured collective census stays exactly unroll+1
all-reduces with dispatch active in the hot path.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import problems, sama
from repro.kernels import dispatch, ops, ref


@pytest.fixture(autouse=True)
def _clean_log():
    dispatch.clear_dispatch_log()
    yield
    dispatch.clear_dispatch_log()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_builtin_matrix():
    assert dispatch.available_kernels() == (
        "adafactor_adapt", "adam_adapt", "flash_attention", "flash_decode",
        "lion_adapt", "weighted_ce")
    for name in dispatch.available_kernels():
        assert dispatch.kernel_backends(name) == dispatch.BACKENDS  # all three


def test_register_duplicate_refused_and_overwrite():
    def impl(x):
        return x

    dispatch.register_kernel("_tmp_kernel", "ref", impl)
    try:
        with pytest.raises(ValueError, match="already has"):
            dispatch.register_kernel("_tmp_kernel", "ref", impl)
        dispatch.register_kernel("_tmp_kernel", "ref", impl, overwrite=True)
        with pytest.raises(ValueError, match="unknown backend"):
            dispatch.register_kernel("_tmp_kernel", "cuda", impl)
    finally:
        dispatch.unregister_kernel("_tmp_kernel")
    with pytest.raises(ValueError, match="unknown kernel"):
        dispatch.get_kernel("_tmp_kernel")


def test_backend_order_precedence(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    assert dispatch.backend_order() == (
        ("pallas-tpu", "ref") if jax.default_backend() == "tpu" else ("ref",))
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas-interpret")
    assert dispatch.backend_order() == ("pallas-interpret", "ref")
    # explicit argument beats the env var
    assert dispatch.backend_order("ref") == ("ref",)
    monkeypatch.setenv(dispatch.ENV_VAR, "nonsense")
    with pytest.raises(ValueError, match="must be one of"):
        dispatch.backend_order()


# ---------------------------------------------------------------------------
# parity: every registered kernel vs its ref.py oracle, every CPU backend,
# aligned and ragged shapes
# ---------------------------------------------------------------------------

CPU_BACKENDS = ("pallas-interpret", "ref")


def _flat_case(n, k):
    keys = [jax.random.PRNGKey(100 * n + i) for i in range(k)]
    return [jax.random.normal(kk, (n,)) for kk in keys]


def _kernel_cases(name, n):
    """(args, kwargs, oracle_fn) triples exercising kernel ``name``."""

    if name == "adam_adapt":
        g, m, v_raw, gm = _flat_case(n, 4)
        kw = dict(t=4, b1=0.9, b2=0.999, eps=1e-8, lr=0.3)
        return (g, m, jnp.abs(v_raw), gm), kw, ref.adam_adapt_product
    if name == "lion_adapt":
        g, m, gm = _flat_case(n, 3)
        kw = dict(lr=0.2, b1=0.9, delta=1e-3)
        return (g, m, gm), kw, ref.lion_adapt_product
    if name == "adafactor_adapt":
        vhat_raw, gm = _flat_case(n, 2)
        kw = dict(lr=0.2, eps=1e-8)
        return (jnp.abs(vhat_raw) + 1e-3, gm), kw, ref.adafactor_adapt_product
    raise AssertionError(name)


@pytest.mark.parametrize("backend", CPU_BACKENDS)
@pytest.mark.parametrize("n", [128, 8 * 1024, 1000, 37])  # incl. ragged tails
@pytest.mark.parametrize("name", ["adam_adapt", "lion_adapt", "adafactor_adapt"])
def test_flat_kernel_parity(name, n, backend):
    args, kw, oracle = _kernel_cases(name, n)
    out, ss = dispatch.get_kernel(name, backend=backend)(*args, **kw)
    out_r, ss_r = oracle(*args, **kw)
    # rtol 3e-5 (not 1e-5): lion's surrogate peaks near |c|=0 where f32
    # op-ordering between the fused kernel and the oracle is visible
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r), rtol=3e-5, atol=1e-7)
    np.testing.assert_allclose(float(ss), float(ss_r), rtol=1e-4, atol=1e-8)
    assert dispatch.dispatch_log()[-1][:2] == (name, backend)


@pytest.mark.parametrize("backend", CPU_BACKENDS)
@pytest.mark.parametrize("shape", [(8, 256), (5, 384), (3, 100)])  # incl. ragged
def test_weighted_ce_parity(shape, backend):
    r_, v_ = shape
    logits = jax.random.normal(jax.random.PRNGKey(r_ * v_), shape) * 4
    targets = jax.random.randint(jax.random.PRNGKey(1), (r_,), 0, v_)
    ce = dispatch.get_kernel("weighted_ce", backend=backend)(logits, targets)
    ce_r = ref.cross_entropy(logits, targets)
    np.testing.assert_allclose(np.asarray(ce), np.asarray(ce_r), rtol=1e-5, atol=1e-5)
    # the weighted backward must agree across backends too
    w = jax.random.uniform(jax.random.PRNGKey(2), (r_,))
    grad = jax.grad(lambda l: jnp.sum(
        dispatch.get_kernel("weighted_ce", backend=backend)(l, targets) * w))(logits)
    grad_r = ref.cross_entropy_grad(logits, targets, w)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(grad_r), rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# fallback semantics
# ---------------------------------------------------------------------------


@pytest.mark.skipif(jax.default_backend() == "tpu", reason="needs a non-TPU backend")
def test_forced_pallas_tpu_falls_back_safely(monkeypatch):
    """Forcing the compiled-TPU backend on a host without a TPU raises,
    naming the kernel and the backend actually present, instead of quietly
    running ``ref`` in its place; nothing is logged as dispatched."""

    monkeypatch.setenv(dispatch.ENV_VAR, "pallas-tpu")
    g, m, gm = _flat_case(64, 3)
    v = jnp.abs(gm)
    dispatch.clear_dispatch_log()
    with pytest.raises(RuntimeError, match=r"'adam_adapt'.*'pallas-tpu'.*not 'tpu'"):
        dispatch.get_kernel("adam_adapt")(g, m, v, gm, t=1, b1=0.9, b2=0.999,
                                          eps=1e-8, lr=1.0)
    assert dispatch.dispatch_log() == []
    # an explicit backend= is the same forced choice
    with pytest.raises(RuntimeError, match="pallas-tpu"):
        dispatch.get_kernel("weighted_ce", backend="pallas-tpu")(
            jnp.zeros((8, 128)), jnp.zeros((8,), jnp.int32))


def test_ineligible_shape_falls_back():
    """A kernel whose eligibility predicate rejects the call falls through
    to the next backend in the order."""

    calls = []
    dispatch.register_kernel(
        "_tmp_picky", "pallas-interpret",
        lambda x: calls.append("pallas") or x + 1,
        eligible=lambda x: x.shape[0] % 8 == 0,
    )
    dispatch.register_kernel("_tmp_picky", "ref", lambda x: x + 1)
    try:
        kern = dispatch.get_kernel("_tmp_picky", backend="pallas-interpret")
        kern(jnp.zeros((16,)))
        assert dispatch.dispatch_log()[-1][:2] == ("_tmp_picky", "pallas-interpret")
        kern(jnp.zeros((7,)))  # ragged: ineligible -> ref
        kernel, backend, reason = dispatch.dispatch_log()[-1]
        assert (kernel, backend) == ("_tmp_picky", "ref")
        assert "pallas-interpret:ineligible" in reason
        assert calls == ["pallas"]
    finally:
        dispatch.unregister_kernel("_tmp_picky")


def test_ce_tpu_eligibility_is_lane_aligned():
    """The compiled blockwise-CE kernel only claims lane-aligned vocabularies."""

    ok = jnp.zeros((4, 256))
    ragged = jnp.zeros((4, 300))
    tg = jnp.zeros((4,), jnp.int32)
    assert dispatch._ce_tiles_ok(ok, tg)
    assert not dispatch._ce_tiles_ok(ragged, tg)


# ---------------------------------------------------------------------------
# hot-path wiring
# ---------------------------------------------------------------------------


def _warm_adam(n=512, lr=0.5):
    opt = optim.adam(lr)
    params = {"w": jnp.zeros((n,))}
    state = opt.init(params)
    for i in range(2):
        g = {"w": jax.random.normal(jax.random.PRNGKey(i), (n,))}
        upd, state = opt.update(g, state, params)
        params = optim.apply_updates(params, upd)
    return opt, params, state


def test_acceptance_adaptation_lowers_through_dispatched_kernel(monkeypatch):
    """ISSUE acceptance: adam.adaptation lowers through the dispatched fused
    kernel when enabled, through ref when forced, numerics within 1e-5."""

    opt, params, state = _warm_adam()
    grads = {"w": jax.random.normal(jax.random.PRNGKey(9), (512,))}

    monkeypatch.setenv(dispatch.ENV_VAR, "pallas-interpret")
    dispatch.clear_dispatch_log()
    jaxpr_kernel = str(jax.make_jaxpr(lambda g: opt.adaptation(g, state, params))(grads))
    assert "pallas_call" in jaxpr_kernel
    assert ("adam_adapt", "pallas-interpret") in [e[:2] for e in dispatch.dispatch_log()]
    diag_kernel = opt.adaptation(grads, state, params)

    monkeypatch.setenv(dispatch.ENV_VAR, "ref")
    dispatch.clear_dispatch_log()
    jaxpr_ref = str(jax.make_jaxpr(lambda g: opt.adaptation(g, state, params))(grads))
    assert "pallas_call" not in jaxpr_ref
    assert ("adam_adapt", "ref") in [e[:2] for e in dispatch.dispatch_log()]
    diag_ref = opt.adaptation(grads, state, params)

    # both backends agree with the ref.py oracle to <= 1e-5
    ones = jnp.ones((512,))
    oracle, _ = ref.adam_adapt_product(
        grads["w"], state.mu["w"], state.nu["w"], ones,
        t=int(state.count) + 1, b1=0.9, b2=0.999, eps=1e-8, lr=0.5)
    for got in (diag_kernel["w"], diag_ref["w"]):
        np.testing.assert_allclose(np.asarray(got), np.asarray(oracle), rtol=1e-5, atol=1e-5)


def test_sama_fused_path_matches_unfused():
    """The fused adapt_product hot path must be a pure optimization: same
    hypergradient, perturbation direction and eps as the adaptation-then-
    multiply-then-norm fallback."""

    def apply_fn(theta, x):
        return jnp.tanh(x @ theta["w1"]) @ theta["w2"]

    spec = problems.make_data_optimization_spec(
        problems.softmax_per_example(apply_fn), reweight=True)
    theta = {"w1": jax.random.normal(jax.random.PRNGKey(0), (6, 16)) * 0.3,
             "w2": jax.random.normal(jax.random.PRNGKey(1), (16, 3)) * 0.3}
    lam = problems.init_data_optimization_lam(jax.random.PRNGKey(2), reweight=True)
    batch = {"x": jax.random.normal(jax.random.PRNGKey(3), (8, 6)),
             "y": jax.random.randint(jax.random.PRNGKey(4), (8,), 0, 3)}

    opt = optim.adam(1e-2)
    assert opt.adapt_product is not None
    state = opt.init(theta)
    g_base = jax.grad(spec.base_scalar)(theta, lam, batch)
    upd, state2 = opt.update(g_base, state, theta)

    kwargs = dict(base_opt_state=state, g_base=g_base, cfg=sama.SAMAConfig())
    fused = sama.sama_hypergrad(spec, theta, lam, batch, batch, base_opt=opt, **kwargs)
    unfused_opt = dataclasses.replace(opt, adapt_product=None)
    unfused = sama.sama_hypergrad(spec, theta, lam, batch, batch,
                                  base_opt=unfused_opt, **kwargs)

    np.testing.assert_allclose(float(fused.eps), float(unfused.eps), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(fused.hypergrad),
                    jax.tree_util.tree_leaves(unfused.hypergrad)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)
    for a, b in zip(jax.tree_util.tree_leaves(fused.v),
                    jax.tree_util.tree_leaves(unfused.v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("opt_name", ["lion", "adafactor"])
def test_sama_runs_on_new_adaptive_optimizers(opt_name):
    """The paper's "broad range of adaptive optimizers" claim: SAMA composes
    with lion and adafactor end to end through the fused path."""

    def apply_fn(theta, x):
        return x @ theta["w"]

    spec = problems.make_data_optimization_spec(
        problems.softmax_per_example(apply_fn), reweight=True)
    theta = {"w": jax.random.normal(jax.random.PRNGKey(0), (5, 3)) * 0.3}
    lam = problems.init_data_optimization_lam(jax.random.PRNGKey(1), reweight=True)
    batch = {"x": jax.random.normal(jax.random.PRNGKey(2), (6, 5)),
             "y": jax.random.randint(jax.random.PRNGKey(3), (6,), 0, 3)}

    opt = optim.get_optimizer(opt_name, 1e-2)
    state = opt.init(theta)
    g_base = jax.grad(spec.base_scalar)(theta, lam, batch)
    res = sama.sama_hypergrad(spec, theta, lam, batch, batch, base_opt=opt,
                              base_opt_state=state, g_base=g_base,
                              cfg=sama.SAMAConfig())
    assert float(res.eps) > 0
    assert all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree_util.tree_leaves(res.hypergrad))


def test_large_vocab_ce_routes_through_dispatch():
    from repro.models.model import token_cross_entropy

    V = dispatch.CE_VOCAB_THRESHOLD
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 3, V))
    targets = jax.random.randint(jax.random.PRNGKey(1), (2, 3), 0, V)
    dispatch.clear_dispatch_log()
    ce = token_cross_entropy(logits, targets)
    assert ("weighted_ce" in [e[0] for e in dispatch.dispatch_log()])
    ce_r = ref.cross_entropy(logits.reshape(-1, V), targets.reshape(-1)).reshape(2, 3)
    np.testing.assert_allclose(np.asarray(ce), np.asarray(ce_r), rtol=1e-5, atol=1e-5)

    dispatch.clear_dispatch_log()
    token_cross_entropy(logits[..., :64], jnp.clip(targets, 0, 63))
    assert dispatch.dispatch_log() == []  # small vocab: plain log_softmax


# ---------------------------------------------------------------------------
# ACCEPTANCE: measured census of the manual SAMA step with dispatch active
# ---------------------------------------------------------------------------

CENSUS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp

from repro import optim, perf
from repro.core import EngineConfig, init_state, problems
from repro.kernels import dispatch
from repro.launch import distributed as dist

UNROLL = 2
mesh = jax.make_mesh((8, 1), ("data", "model"))

def apply_fn(theta, x):
    return jnp.tanh(x @ theta["w1"]) @ theta["w2"]

spec = problems.make_data_optimization_spec(
    problems.softmax_per_example(apply_fn), reweight=True)
theta = {"w1": jax.random.normal(jax.random.PRNGKey(0), (6, 16)) * 0.3,
         "w2": jax.random.normal(jax.random.PRNGKey(1), (16, 3)) * 0.3}
lam = problems.init_data_optimization_lam(jax.random.PRNGKey(2), reweight=True)
base_opt, meta_opt = optim.adam(1e-2), optim.adam(1e-2)
assert base_opt.adapt_product is not None  # fused dispatch path is live
state = init_state(theta, lam, base_opt, meta_opt)
step = dist.make_manual_step(
    spec, base_opt, meta_opt, EngineConfig(method="sama", unroll_steps=UNROLL), mesh)
base = {"x": jax.random.normal(jax.random.PRNGKey(3), (UNROLL, 8, 6)),
        "y": jax.random.randint(jax.random.PRNGKey(4), (UNROLL, 8), 0, 3)}
meta = {"x": jax.random.normal(jax.random.PRNGKey(5), (8, 6)),
        "y": jax.random.randint(jax.random.PRNGKey(6), (8,), 0, 3)}
with mesh:
    compiled = jax.jit(step).lower(state, base, meta).compile()
    census = perf.verify_single_sync(compiled, UNROLL)
dispatched = sorted(set(e[:2] for e in dispatch.dispatch_log()))
print(json.dumps({"unroll": UNROLL, "census": census, "dispatched": dispatched}))
"""


def test_acceptance_census_unroll_plus_one_with_dispatch_active():
    """ISSUE acceptance: the measured (trip-scaled, compiled-HLO) collective
    census of the manual SAMA step stays exactly unroll+1 all-reduces with
    the kernel-dispatched fused adaptation product in the hot path."""

    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    env.pop(dispatch.ENV_VAR, None)
    out = subprocess.run(
        [sys.executable, "-c", CENSUS_SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    # the fused kernel path really was dispatched while tracing the step
    assert ["adam_adapt", "ref"] in r["dispatched"]
    census = r["census"]
    assert census["expected_all_reduces"] == r["unroll"] + 1 == 3
    assert census["all-reduce_count"] == r["unroll"] + 1
    assert census["single_sync_ok"] is True
    assert census["total_count"] == census["all-reduce_count"]
