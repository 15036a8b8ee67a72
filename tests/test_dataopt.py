"""repro.dataopt subsystem tests: scorer registry round-trip, heuristic
scorers vs hand-rolled oracles, prune invariants, EMA machinery, reweighted
sampling, export/import manifest validation — plus the subsystem's
distributed claim (sharded scoring bitwise-equal to single-device, and the
reweighted iterator producing data-sharded batches), which needs >1 host
device and therefore runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main pytest process
keeps 1 device, per the dry-run isolation rule).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import problems
from repro.dataopt import (
    DataOptimizer,
    EMATracker,
    ReweightedIterator,
    ScoreContext,
    available_scorers,
    class_balanced_mask,
    apply_mask,
    ema_disagreement,
    export_scores,
    import_scores,
    keep_mask,
    fit_plain,
    register_scorer,
    resolve_scorer,
    sampling_probs,
    unregister_scorer,
)

# ---------------------------------------------------------------------------
# a tiny MLP classification problem shared by the tests
# ---------------------------------------------------------------------------

D, H, C, N = 6, 16, 3, 90


def _apply_fn(theta, x):
    return jnp.tanh(x @ theta["w1"]) @ theta["w2"]


PER_EX = problems.softmax_per_example(_apply_fn)


def _init_fn(key):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (D, H)) * 0.3,
            "w2": jax.random.normal(k2, (H, C)) * 0.3}


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(0)
    return {"x": rng.normal(size=(N, D)).astype(np.float32),
            "y": rng.integers(0, C, N).astype(np.int32)}


@pytest.fixture(scope="module")
def theta():
    return _init_fn(jax.random.PRNGKey(42))


def _optimizer(dataset, scorer, theta=None, **knobs):
    return DataOptimizer(train=dataset, per_example_fn=PER_EX, init_fn=_init_fn,
                         fields=("x", "y"), num_classes=C, scorer=scorer,
                         theta=theta, batch_size=32, **knobs)


# ---------------------------------------------------------------------------
# registry round-trip
# ---------------------------------------------------------------------------


def test_scorer_registry_roundtrip(dataset):
    assert {"meta", "el2n", "grand", "margin", "loss", "random"} <= set(available_scorers())

    @register_scorer("test_constant")
    def _make(value=1.0):
        return lambda ctx: np.full(ctx.n, value, np.float32)

    try:
        assert "test_constant" in available_scorers()
        with pytest.raises(ValueError):
            register_scorer("test_constant", _make)  # duplicate refused
        scorer = resolve_scorer("test_constant", value=3.0)
        opt = _optimizer(dataset, "test_constant", value=3.0)
        s = opt.fit_scores()
        np.testing.assert_array_equal(s, np.full(N, 3.0, np.float32))
        np.testing.assert_array_equal(scorer(opt.ctx), s)
    finally:
        unregister_scorer("test_constant")
    assert "test_constant" not in available_scorers()
    with pytest.raises(ValueError):
        resolve_scorer("test_constant")


def test_resolve_scorer_rejects_knobs_on_callable():
    with pytest.raises(TypeError):
        resolve_scorer(lambda ctx: None, train_steps=3)


# ---------------------------------------------------------------------------
# heuristic scorers vs hand-rolled oracles
# ---------------------------------------------------------------------------


def test_el2n_matches_oracle(dataset, theta):
    s = _optimizer(dataset, "el2n", theta=theta).fit_scores()
    logits = np.asarray(_apply_fn(theta, jnp.asarray(dataset["x"])))
    p = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    onehot = np.eye(C)[dataset["y"]]
    oracle = np.linalg.norm(p - onehot, axis=-1)
    np.testing.assert_allclose(s, -oracle, rtol=1e-5)  # keep-easy orientation


def test_grand_matches_oracle(dataset, theta):
    s = _optimizer(dataset, "grand", theta=theta).fit_scores()
    oracle = np.empty(N)
    for i in range(N):
        b = {"x": jnp.asarray(dataset["x"][i:i + 1]), "y": jnp.asarray(dataset["y"][i:i + 1])}
        g = jax.grad(lambda p: jnp.sum(PER_EX(p, b).loss))(theta)
        oracle[i] = np.sqrt(sum(float(jnp.sum(jnp.square(x)))
                                for x in jax.tree_util.tree_leaves(g)))
    np.testing.assert_allclose(s, -oracle, rtol=1e-4)


def test_margin_and_loss_orientation(dataset, theta):
    margin = _optimizer(dataset, "margin", theta=theta).fit_scores()
    loss = _optimizer(dataset, "loss", theta=theta).fit_scores()
    pe = PER_EX(theta, {"x": jnp.asarray(dataset["x"]), "y": jnp.asarray(dataset["y"])})
    np.testing.assert_allclose(loss, -np.asarray(pe.loss), rtol=1e-5)
    # margin and loss must broadly agree on the keep-priority ordering
    assert np.corrcoef(margin, loss)[0, 1] > 0.5


def test_meta_scorer_end_to_end(dataset):
    opt = _optimizer(dataset, "meta", steps=4, unroll=2, uncertainty="entropy")
    s = opt.fit_scores()
    assert s.shape == (N,) and np.all(np.isfinite(s))
    assert np.all((s >= 0) & (s <= 1))  # MWN outputs are sigmoid weights


# ---------------------------------------------------------------------------
# prune invariants
# ---------------------------------------------------------------------------


def test_keep_mask_counts_and_order():
    scores = np.array([0.1, 0.9, 0.5, 0.7, 0.3])
    mask = keep_mask(scores, ratio=0.4)
    assert mask.sum() == 3
    assert mask[[1, 3, 2]].all() and not mask[[0, 4]].any()
    with pytest.raises(ValueError):
        keep_mask(scores, ratio=1.0)


def test_class_balanced_prune_ratio_honored_per_class(dataset):
    rng = np.random.default_rng(1)
    scores = rng.random(N).astype(np.float32)
    labels = dataset["y"]
    ratio = 0.3
    mask = class_balanced_mask(scores, labels, ratio)
    for c in np.unique(labels):
        in_class = labels == c
        expected = max(int(round(in_class.sum() * (1 - ratio))), 1)
        assert mask[in_class].sum() == expected, f"class {c}"
        # within the class, exactly the top-scored survive
        kept_scores = scores[in_class & mask]
        dropped_scores = scores[in_class & ~mask]
        if len(dropped_scores):
            assert kept_scores.min() >= dropped_scores.max()


def test_prune_and_iterative_prune(dataset):
    opt = _optimizer(dataset, "random")
    pruned, mask = opt.prune(0.5)
    assert mask.sum() == len(pruned["y"]) == max(int(round(N * 0.5)), 1)
    # iterative: same final budget, monotone shrinking keep set
    opt2 = _optimizer(dataset, "random")
    _, mask2 = opt2.prune(0.5, rounds=2)
    assert mask2.sum() == mask.sum()
    assert len(apply_mask(dataset, mask2)["x"]) == mask2.sum()


def test_iterative_prune_forwards_theta_every_round(dataset, theta):
    """rounds > 1 re-scores via per-round sub-optimizers; a user-supplied
    pre-trained theta must reach EVERY round, not just the first."""

    seen = []

    @register_scorer("test_theta_probe")
    def _make():
        def score(ctx):
            seen.append(ctx.theta)
            return np.linspace(0.0, 1.0, ctx.n, dtype=np.float32)
        return score

    try:
        opt = _optimizer(dataset, "test_theta_probe", theta=theta)
        opt.prune(0.5, rounds=2)
    finally:
        unregister_scorer("test_theta_probe")
    assert len(seen) == 2
    assert all(t is theta for t in seen), "a round dropped the supplied theta"


def test_retrain_improves_over_init(dataset):
    theta0 = _init_fn(jax.random.PRNGKey(0))
    theta = fit_plain(PER_EX, theta0, dataset, steps=60, fields=("x", "y"))
    batch = {"x": jnp.asarray(dataset["x"]), "y": jnp.asarray(dataset["y"])}
    assert float(jnp.mean(PER_EX(theta, batch).loss)) < float(jnp.mean(PER_EX(theta0, batch).loss))


# ---------------------------------------------------------------------------
# EMA machinery
# ---------------------------------------------------------------------------


def test_ema_tracker():
    t = EMATracker(decay=0.5)
    np.testing.assert_array_equal(t.update(np.ones(4)), np.ones(4))  # init, no zero-bias
    np.testing.assert_allclose(t.update(np.zeros(4)), 0.5 * np.ones(4))
    with pytest.raises(ValueError):
        t.update(np.ones(5))
    with pytest.raises(ValueError):
        EMATracker(decay=1.0)


def test_ema_disagreement_bounds():
    p = np.array([[1.0, 0.0], [0.5, 0.5]])
    np.testing.assert_allclose(ema_disagreement(p, p), [0.0, 0.5])
    flipped = p[:, ::-1]
    np.testing.assert_allclose(ema_disagreement(p, flipped), [1.0, 0.5])


# ---------------------------------------------------------------------------
# reweighted iteration
# ---------------------------------------------------------------------------


def test_sampling_probs_temperature_limits():
    s = np.array([0.0, 1.0, 2.0])
    hot = sampling_probs(s, temperature=1e6)  # ~uniform
    np.testing.assert_allclose(hot, np.full(3, 1 / 3), atol=1e-3)
    cold = sampling_probs(s, temperature=1e-6)  # ~argmax
    assert cold[2] > 0.99
    uniform = sampling_probs(np.zeros(3), temperature=1.0)
    np.testing.assert_allclose(uniform, np.full(3, 1 / 3))


def test_reweighted_iterator_respects_scores(dataset):
    scores = np.zeros(N, np.float32)
    scores[:10] = 1.0  # only the first 10 examples should ever be drawn (cold T)
    it = ReweightedIterator(dataset, dataset, scores, batch_size=8,
                            meta_batch_size=4, unroll=2, fields=("x", "y"),
                            temperature=1e-3, seed=0)
    base, meta = next(it)
    assert base["x"].shape == (2, 8, D) and meta["x"].shape == (4, D)
    drawn = np.asarray(base["x"]).reshape(-1, D)
    allowed = dataset["x"][:10]
    for row in drawn:
        assert np.any(np.all(np.isclose(row, allowed), axis=-1))
    # online update: flip the mass and the draws must follow
    flipped = np.zeros(N, np.float32)
    flipped[-10:] = 1.0
    it.update_scores(flipped)
    base2, _ = next(it)
    drawn2 = np.asarray(base2["x"]).reshape(-1, D)
    allowed2 = dataset["x"][-10:]
    for row in drawn2:
        assert np.any(np.all(np.isclose(row, allowed2), axis=-1))


def test_reweighted_iterator_curriculum_anneal(dataset):
    it = ReweightedIterator(dataset, dataset, np.arange(N, dtype=np.float32),
                            batch_size=4, meta_batch_size=2, unroll=1,
                            fields=("x", "y"), temperature=(10.0, 0.1, 5), seed=0)
    temps = [it.temperature_fn(i) for i in range(7)]
    assert temps[0] == 10.0
    assert abs(temps[5] - 0.1) < 1e-9
    assert temps[6] == temps[5]  # anneal clamps at the end temperature
    next(it)


# ---------------------------------------------------------------------------
# export / import manifest validation
# ---------------------------------------------------------------------------


def test_export_import_roundtrip(tmp_path, dataset):
    opt = _optimizer(dataset, "random")
    s = opt.fit_scores()
    mask = keep_mask(s, 0.3)
    path = opt.export(str(tmp_path / "scores"), mask=mask, meta={"note": "t"})
    s2, m2, meta = import_scores(path)
    np.testing.assert_array_equal(s, s2)
    np.testing.assert_array_equal(mask, m2)
    assert meta["scorer"] == "random" and meta["n"] == N and meta["note"] == "t"
    # a second optimizer adopts the export
    opt2 = _optimizer(dataset, "random")
    s3 = opt2.load(path, expect_scorer="random")
    np.testing.assert_array_equal(s, s3)


def test_export_import_validation_failures(tmp_path, dataset):
    with pytest.raises(ValueError):
        export_scores(str(tmp_path / "bad"), np.array([np.nan, 1.0]), scorer="x")
    with pytest.raises(ValueError):
        export_scores(str(tmp_path / "bad2"), np.ones((2, 2)), scorer="x")
    with pytest.raises(ValueError):  # reserved meta keys
        export_scores(str(tmp_path / "bad3"), np.ones(4), scorer="x", meta={"n": 9})

    path = export_scores(str(tmp_path / "ok"), np.ones(4, np.float32), scorer="el2n")
    with pytest.raises(ValueError):
        import_scores(path, expect_n=5)
    with pytest.raises(ValueError):
        import_scores(path, expect_scorer="meta")

    # a foreign checkpoint is refused (wrong manifest kind)
    from repro import checkpoint
    foreign = str(tmp_path / "foreign")
    checkpoint.save(foreign, {"scores": np.ones(4)}, meta={"kind": "model"})
    with pytest.raises(ValueError):
        import_scores(foreign)


# ---------------------------------------------------------------------------
# distributed: sharded scoring bitwise == single device; sharded reweighted
# batches (subprocess with 8 forced host devices)
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import problems
from jax.sharding import AxisType
from repro.dataopt import DataOptimizer, score_dataset
from repro.dataopt.reweight import ReweightedIterator

def apply_fn(theta, x):
    return jnp.tanh(x @ theta["w1"]) @ theta["w2"]

per_ex = problems.softmax_per_example(apply_fn)
d, h, C, n = 6, 16, 3, 100   # n NOT a multiple of the batch: exercises padding
def init_fn(key):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (d, h)) * 0.3,
            "w2": jax.random.normal(k2, (h, C)) * 0.3}

theta = init_fn(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
train = {"x": rng.normal(size=(n, d)).astype(np.float32),
         "y": rng.integers(0, C, n).astype(np.int32)}

mesh = jax.make_mesh((8, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)

pe_1 = score_dataset(per_ex, theta, train, fields=("x", "y"), batch_size=16)
pe_8 = score_dataset(per_ex, theta, train, fields=("x", "y"), batch_size=16, mesh=mesh)
bitwise = all(
    np.array_equal(np.asarray(getattr(pe_1, f)), np.asarray(getattr(pe_8, f)))
    for f in ("loss", "logits", "uncertainty")
)

# full scorer path through the facade, sharded vs not
s_1 = DataOptimizer(train=train, per_example_fn=per_ex, init_fn=init_fn,
                    fields=("x", "y"), num_classes=C, scorer="el2n",
                    theta=theta, batch_size=16).fit_scores()
s_8 = DataOptimizer(train=train, per_example_fn=per_ex, init_fn=init_fn,
                    fields=("x", "y"), num_classes=C, scorer="el2n",
                    theta=theta, batch_size=16, mesh=mesh).fit_scores()
scorer_bitwise = np.array_equal(s_1, s_8)

# reweighted iterator under the mesh: batches must come out data-sharded —
# the meta batch over dim 0, the base batches over dim 1 (dim 0 is unroll)
it = ReweightedIterator(train, train, np.abs(s_1) + 1e-3, batch_size=16,
                        meta_batch_size=16, unroll=2, fields=("x", "y"),
                        mesh=mesh, seed=0)
base, meta = it.__next__()

def shard_dim(x, dim):
    return (len(x.sharding.device_set) == 8
            and x.sharding.shard_shape(x.shape)[dim] == x.shape[dim] // 8)

shardings_ok = shard_dim(meta["x"], 0) and shard_dim(base["x"], 1)

print(json.dumps({"bitwise": bitwise, "scorer_bitwise": scorer_bitwise,
                  "shardings_ok": shardings_ok}))
"""


@pytest.fixture(scope="module")
def dist_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_scoring_bitwise_identical(dist_result):
    assert dist_result["bitwise"]
    assert dist_result["scorer_bitwise"]


def test_reweighted_iterator_shards_over_mesh(dist_result):
    assert dist_result["shardings_ok"]
