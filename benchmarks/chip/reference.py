"""Plain float32 reference of what a cell's timed path computes.

The data-reweighting bilevel problem (a weight net over each example's
loss), Adam at both levels and SAMA's meta step are written here again in
plain ``jax.numpy``, from the configuration file's sizes and the traffic
file's settings; each model family's parameter shapes, per-example loss
and FLOP count sit in ``families/<family>.py``, found by the
configuration's ``family``, on the blocks defined here. Nothing here
imports the system under test.

The same module makes the weights the program is given (``init_weights``):
one jitted call from the seed, in the parameter layout the configuration
file describes. The harness checks that the program's own
layout matches it leaf for leaf.

``precision`` picks the matrix-product precision: ``highest`` is the
reference, ``high`` (three bf16 passes on a TPU) is the control, and
``high_emulated`` is the same three passes spelt out, for a CPU where
``high`` means ``highest``.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

PyTree = Any

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def dense_layer_shapes(c, cross: bool) -> Dict[str, Any]:
    L, D, F, HD = c["num_layers"], c["d_model"], c["d_ff"], c["num_heads"] * c["head_dim"]
    attn = {"wq": (L, D, HD), "wk": (L, D, HD), "wv": (L, D, HD), "wo": (L, HD, D)}
    p = {"attn": attn,
         "ln1": {"bias": (L, D), "scale": (L, D)},
         "ln2": {"bias": (L, D), "scale": (L, D)},
         "mlp": {"up": (L, D, F), "down": (L, F, D)}}
    if cross:
        p["ln_x"] = {"bias": (L, D), "scale": (L, D)}
        p["xattn"] = dict(attn)
    return p


def family(c):
    """The module of ``families/`` that holds the configuration's model:
    its parameter shapes, its per-example loss and its forward FLOPs."""
    return _family(c["family"])


@functools.lru_cache(maxsize=None)
def _family(name: str):
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parent / "families" / f"{name}.py"
    if not path.exists():
        raise ValueError(f"no reference for model family {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location(f"chip_family_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def theta_shapes(c) -> Dict[str, Any]:
    """The parameter tree of the configuration, as nested dicts of shapes."""
    if c["num_kv_heads"] != c["num_heads"]:
        raise ValueError("the reference has multi-head attention only")
    return family(c).theta_shapes(c)


def lam_shapes(hidden: int = 100) -> Dict[str, Any]:
    """The weight net: one loss feature -> ``hidden`` -> one weight."""
    return {"reweight": {"l1": {"b": (hidden,), "w": (1, hidden)},
                         "l2": {"b": (1,), "w": (hidden, 1)}}}


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def _init_tree(shapes, key):
    """Norm scales start at 1, biases at 0, every other leaf from a normal
    scaled by 1/sqrt(fan-in) (the row count of its last two axes)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            out.append(jnp.ones(shape, jnp.float32))
        elif name.endswith("['bias']") or name.endswith("['b']"):
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            std = 1.0 / math.sqrt(shape[-2])
            out.append(jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std)
    return jax.tree_util.tree_unflatten(treedef, out)


def seed_key(seed: int):
    """A PRNG key from the whole seed: ``jax.random.key`` keeps only the low
    32 bits, so the high bits are folded in."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def init_weights(c, seed: int, sharding=None) -> Tuple[PyTree, PyTree]:
    """(theta, lam), made in one jitted call on the default device, or
    placed by ``sharding`` (the program's replicated mesh sharding, so that
    its first step takes them as it takes its own state)."""
    return _init_fn(json.dumps(c, sort_keys=True), sharding)(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _init_fn(c_json: str, sharding):
    c = json.loads(c_json)

    def make(k):
        k_theta, k_lam = jax.random.split(k)
        return _init_tree(theta_shapes(c), k_theta), _init_tree(lam_shapes(), k_lam)

    return jax.jit(make, out_shardings=sharding)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def make_einsum(precision: str):
    if precision == "high_emulated":
        def split(x):
            hi = x.astype(jnp.bfloat16).astype(jnp.float32)
            return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)

        def ein(spec, a, b):
            (ah, al), (bh, bl) = split(a), split(b)
            one = lambda x, y: jnp.einsum(spec, x, y, precision=jax.lax.Precision.HIGHEST)
            return one(ah, bh) + (one(ah, bl) + one(al, bh))

        return ein
    prec = {"highest": jax.lax.Precision.HIGHEST, "high": jax.lax.Precision.HIGH,
            "default": jax.lax.Precision.DEFAULT}[precision]
    return lambda spec, a, b: jnp.einsum(spec, a, b, precision=prec)


def layer_norm(p, x, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(ein, c, p, x, memory=None, causal=False):
    B, S, _ = x.shape
    H, Dh = c["num_heads"], c["head_dim"]
    kv_in = x if memory is None else memory
    T = kv_in.shape[1]
    q = ein("bsd,de->bse", x, p["wq"]).reshape(B, S, H, Dh)
    k = ein("btd,de->bte", kv_in, p["wk"]).reshape(B, T, H, Dh)
    v = ein("btd,de->bte", kv_in, p["wv"]).reshape(B, T, H, Dh)
    s = ein("bshd,bthd->bhst", q, k) / math.sqrt(Dh)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, T), bool)), s, -1e30)
    probs = jax.nn.softmax(s, axis=-1)
    out = ein("bhst,bthd->bshd", probs, v).reshape(B, S, H * Dh)
    return ein("bse,ed->bsd", out, p["wo"])


def mlp(ein, p, x):
    return ein("bsf,fd->bsd", gelu_tanh(ein("bsd,df->bsf", x, p["up"])), p["down"])


def sinusoidal(n, d):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    ang = pos / jnp.power(10_000.0, jnp.arange(0, d, 2, dtype=jnp.float32)[None, :] / d)
    return jnp.stack([jnp.sin(ang), jnp.cos(ang)], axis=-1).reshape(n, d)


def scan_layers(body, x, layers):
    # rematerialised per layer, so the backward pass holds one layer's
    # activations at a time (the encoder's 1500x1500 scores do not fit twelve
    # times over)
    x, _ = jax.lax.scan(jax.checkpoint(lambda h, lp: (body(h, lp), None)), x, layers)
    return x


def embed(c, theta, tokens):
    x = theta["embed"][tokens] * math.sqrt(c["d_model"])
    return x + theta["pos_embed"][: tokens.shape[1]]


# ---------------------------------------------------------------------------
# the bilevel problem, Adam and SAMA
# ---------------------------------------------------------------------------


def weight_net(lam, loss_i):
    p = lam["reweight"]
    h = jax.nn.relu(jax.lax.stop_gradient(loss_i)[:, None] @ p["l1"]["w"] + p["l1"]["b"])
    return jax.nn.sigmoid(h @ p["l2"]["w"] + p["l2"]["b"])[:, 0]


def _tmap(f, *t):
    return jax.tree_util.tree_map(f, *t)


def _norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(tree)))


def _adam(g, mu, nu, count, p, lr):
    mu = _tmap(lambda m, gi: ADAM_B1 * m + (1 - ADAM_B1) * gi, mu, g)
    nu = _tmap(lambda v, gi: ADAM_B2 * v + (1 - ADAM_B2) * gi * gi, nu, g)
    t = (count + 1).astype(jnp.float32)
    bc1, bc2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    p = _tmap(lambda x, m, v: x - lr * (m / bc1) / (jnp.sqrt(v / bc2) + ADAM_EPS), p, mu, nu)
    return p, mu, nu, count + 1


def _adam_adaptation(g, mu, nu, count, lr):
    """diag(du/dg) of Adam at the state the gradient g met (exact, no
    eps << 1 approximation)."""
    t = (count + 1).astype(jnp.float32)
    bc1, bc2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    a, b = (1 - ADAM_B1) / bc1, (1 - ADAM_B2) / bc2

    def one(gi, m, v):
        mhat = (ADAM_B1 * m + (1 - ADAM_B1) * gi) / bc1
        sq = jnp.sqrt((ADAM_B2 * v + (1 - ADAM_B2) * gi * gi) / bc2)
        den = sq + ADAM_EPS
        return lr * (a / den - mhat * b * gi / (jnp.maximum(sq, 1e-15) * den * den))

    return _tmap(one, g, mu, nu)


def make_reference_step(c, t, precision: str = "highest"):
    """The reference meta step ``(state, base_batches, meta_batch) ->
    (state, metrics)``. ``state`` is a dict of theta, lam and both Adam
    states. With ``t["chips"] > 1`` on the single-sync schedule, each chip's
    share of the batch gives its own SAMA terms and their mean is used, as
    the schedule defines the estimator; base gradients are the mean over
    the shares."""

    ein = make_einsum(precision)
    per_example = family(c).per_example_loss
    shards = t["chips"] if t["schedule"] == "single_sync" else 1
    lr, meta_lr, alpha = t["base_lr"], t["meta_lr"], t["alpha"]

    def loss_i(theta, batch):
        return per_example(ein, c, theta, batch)

    def base_loss(theta, lam, batch):
        li = loss_i(theta, batch)
        return jnp.mean(weight_net(lam, li) * li)

    def meta_loss(theta, lam, batch):
        return jnp.mean(loss_i(theta, batch))

    def split(batch):
        return [_tmap(lambda x: jnp.split(x, shards, axis=0)[s], batch) for s in range(shards)]

    def mean_of(trees):
        return _tmap(lambda *xs: sum(xs) / len(xs), *trees)

    def step(state, base_batches, meta_batch):
        theta, lam = state["theta"], state["lam"]
        mu, nu, count = state["mu"], state["nu"], state["count"]
        losses = []
        for k in range(t["unroll"]):
            batch = _tmap(lambda x: x[k], base_batches)
            lg = [jax.value_and_grad(base_loss)(theta, lam, b) for b in split(batch)]
            losses.append(sum(l for l, _ in lg) / shards)
            g = mean_of([gi for _, gi in lg])
            at_g = (g, mu, nu, count)
            theta, mu, nu, count = _adam(g, mu, nu, count, theta, lr)
        diag = _adam_adaptation(*at_g, lr)
        last = split(_tmap(lambda x: x[-1], base_batches))
        terms = []
        for s, mb in enumerate(split(meta_batch)):
            ml, g_meta = jax.value_and_grad(meta_loss)(theta, lam, mb)
            v = _tmap(lambda d, gm: d * gm, diag, g_meta)
            eps = alpha / jnp.maximum(_norm(v), 1e-12)
            gp = jax.grad(base_loss, argnums=1)(_tmap(lambda x, vi: x + eps * vi, theta, v), lam, last[s])
            gm_ = jax.grad(base_loss, argnums=1)(_tmap(lambda x, vi: x - eps * vi, theta, v), lam, last[s])
            hyper = _tmap(lambda p, m: -(p - m) / (2.0 * eps), gp, gm_)
            terms.append({"hyper": hyper, "v": v, "eps": eps, "meta_loss": ml})
        terms = mean_of(terms)
        theta = _tmap(lambda x, vi: x - terms["eps"] * vi, theta, terms["v"])
        lam, lmu, lnu, lcount = _adam(terms["hyper"], state["lmu"], state["lnu"],
                                      state["lcount"], lam, meta_lr)
        new = {"theta": theta, "lam": lam, "mu": mu, "nu": nu, "count": count,
               "lmu": lmu, "lnu": lnu, "lcount": lcount}
        metrics = {"base_loss": sum(losses) / len(losses), "meta_loss": terms["meta_loss"],
                   "hypergrad_norm": _norm(terms["hyper"]), "eps": terms["eps"]}
        return new, metrics

    return step


def init_state(theta, lam):
    zeros = lambda tr: _tmap(jnp.zeros_like, tr)
    return {"theta": theta, "lam": lam, "mu": zeros(theta), "nu": zeros(theta),
            "count": jnp.zeros([], jnp.int32), "lmu": zeros(lam), "lnu": zeros(lam),
            "lcount": jnp.zeros([], jnp.int32)}


def leaf_norms(tree) -> List[float]:
    """Per-leaf L2 norms, in float64 on the host, in tree-flatten order."""
    import numpy as np

    return [float(np.linalg.norm(np.asarray(x, np.float64).ravel()))
            for x in jax.tree_util.tree_leaves(tree)]


def leaf_change_norms(before, after) -> List[float]:
    import numpy as np

    return [float(np.linalg.norm((np.asarray(a, np.float64) - np.asarray(b, np.float64)).ravel()))
            for b, a in zip(jax.tree_util.tree_leaves(before), jax.tree_util.tree_leaves(after))]


@functools.lru_cache(maxsize=None)
def _jitted_step(c_json: str, t_json: str, precision: str):
    """One jitted reference step per configuration, mix and precision, so
    that a process checking many seeds traces it once."""
    return jax.jit(make_reference_step(json.loads(c_json), json.loads(t_json), precision))


def readings(c, t, seed: int, batches, precision: str = "highest", steps: int = 1):
    """Run the reference for ``steps`` meta steps from the seed's weights on
    ``batches`` (a list of (base, meta) host batches) and return the
    readings the comparison uses: each step's metrics; the per-leaf norms of
    both Adam first moments after step 1 and of the change of theta and lam
    over step 1; and, where ``steps`` is more than one, of the change of
    theta and lam over all of them."""

    with jax.default_matmul_precision(precision if precision in ("highest", "high") else "highest"):
        theta0, lam0 = init_weights(c, seed)
        theta0_host, lam0_host = jax.device_get((theta0, lam0))
        step = _jitted_step(json.dumps(c, sort_keys=True), json.dumps(t, sort_keys=True), precision)
        state = init_state(theta0, lam0)
        del theta0, lam0
        out = {"metrics": []}
        for i in range(steps):
            base, meta = batches[i]
            state, m = step(state, base, meta)
            out["metrics"].append({k: float(v) for k, v in jax.device_get(m).items()})
            if i == 0:
                out["base_moment"] = leaf_norms(jax.device_get(state["mu"]))
                out["meta_moment"] = leaf_norms(jax.device_get(state["lmu"]))
                out["theta_change1"] = leaf_change_norms(theta0_host, jax.device_get(state["theta"]))
                out["lam_change1"] = leaf_change_norms(lam0_host, jax.device_get(state["lam"]))
        if steps > 1:
            out["theta_change"] = leaf_change_norms(theta0_host, jax.device_get(state["theta"]))
            out["lam_change"] = leaf_change_norms(lam0_host, jax.device_get(state["lam"]))
    return out
