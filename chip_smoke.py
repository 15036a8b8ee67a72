"""Smoke run of the SAMA meta-trainer on a TPU, through ``repro.launch.train``.

    python chip_smoke.py                # one chip: bert-base, full width
    python chip_smoke.py --four-chips   # the single-sync schedule on 2x2 chips

One chip: every Pallas kernel compiled for the chip is first compared
with its ``ref`` twin at real sizes. Then ``train.main`` trains the
paper's bert-base (108.8M params, batch 32, seq 128, unroll 2, SAMA) for
three steps under ``--precision f32`` and under ``--precision bf16``,
printing each step's metrics and seconds, the compile seconds and
persistent-cache hits, and the kernel dispatch tally. Then one f32 step
with the Pallas kernels is compared with the same step on the ``ref``
backend: theta and lam after the update, and the adaptation product
itself at the same inputs.

Four chips: the manual single-sync schedule on the (data=4, model=1)
mesh ``train`` builds from the devices present. It checks the all-reduce
census (unroll+1), the placement (batch sharded, theta and lam replicated,
per-device peak bytes within 2x), that identical per-device batches give
the one-chip step to within the one-chip reorder noise (f32), and that
distinct shards give finite metrics and replica-identical state; the
distinct-shard agreement with the one-chip step on the global batch is
printed, not checked (the schedule averages per-shard estimates).

Everything runs in this one process. The last line of stdout is
``{"ok": <bool>, "device": {"platform", "kind", "count"}}``; the exit
code is 0 only when every check passed on a TPU.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import math
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

#: the paper's model at full width, at the batch the one-chip run uses
TRAIN_ARGS = ["--arch", "bert-base", "--seq", "128", "--unroll", "2",
              "--method", "sama", "--log-every", "1"]
PER_CHIP_BATCH = 32
STEPS = 3
BERT_BASE_PARAMS = 108_810_244
METRICS = ("base_loss", "meta_loss", "hypergrad_norm", "eps")

#: kernels vs ref, f32: the adaptation product is one elementwise f32
#: formula per element (a few ulp apart at most), theta moves by eps*v on
#: top of it; the hypergradient goes through a central difference, which
#: amplifies a tiny change of theta+-, and lam moves by about meta_lr*sign.
KERNEL_TOL = {"v": 1e-5, "v_sumsq": 1e-5, "theta": 1e-5, "lam": 1e-3,
              "hypergrad_norm": 1e-2}
#: four chips vs one, identical batches: allowed relative difference is
#: the one-chip reorder noise of the same quantity, but at least this many
#: f32 ulps (a pmean of four equal values may round in the last bits)
ULP_FLOOR = 1e-6


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str) -> bool:
        print(f"check {name}: {'pass' if ok else 'FAIL'} ({detail})", flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def phase(self, name: str, fn, *args):
        try:
            return fn(*args)
        except Exception:  # a phase that raises fails the run, the rest go on
            traceback.print_exc()
            print(f"check {name}: FAIL (raised)", flush=True)
            self.failed.append(name)
            return None


class CompileStats:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events (a hit also reports its retrieval as compile time)."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self):
        out = {"compile_s": self.compile_s, "persistent_cache_hits": self.cache_hits}
        self.compile_s, self.cache_hits = 0.0, 0
        return out


@contextlib.contextmanager
def forced_backend(name):
    """Set ``REPRO_KERNEL_BACKEND`` for what is traced inside."""
    from repro.kernels import dispatch

    old = os.environ.get(dispatch.ENV_VAR)
    if name is None:
        os.environ.pop(dispatch.ENV_VAR, None)
    else:
        os.environ[dispatch.ENV_VAR] = name
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(dispatch.ENV_VAR, None)
        else:
            os.environ[dispatch.ENV_VAR] = old


def rel(a, b) -> float:
    """Relative L2 difference of two pytrees (or scalars), in float64."""
    import jax
    import numpy as np

    num = den = 0.0
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        num += float(np.sum((x - y) ** 2))
        den += float(np.sum(y ** 2))
    return math.sqrt(num) / max(math.sqrt(den), 1e-30)


def adam_product_f64(g, m, v, gm, *, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    """The exact Adam adaptation product (paper App. C) in float64 on the
    host: an oracle independent of both the kernel and the ref twin."""
    import numpy as np

    g, m, v, gm = (np.asarray(x, np.float64) for x in (g, m, v, gm))
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    mhat = (b1 * m + (1.0 - b1) * g) / bc1
    sq = np.sqrt((b2 * v + (1.0 - b2) * g * g) / bc2)
    denom = sq + eps
    diag = lr * ((1.0 - b1) / bc1 / denom
                 - mhat * ((1.0 - b2) / bc2) * g / (np.maximum(sq, 1e-15) * denom ** 2))
    return diag * gm


def adam_picks(dispatch):
    """The backends ``adam_adapt`` was traced through since the log was cleared."""
    return sorted({b for k, b, _ in dispatch.dispatch_log() if k == "adam_adapt"})


def host(metrics):
    from repro import obs

    return {k: v for k, v in obs.packed_read(metrics).items() if k in METRICS}


def model_line(tr, args) -> str:
    c = tr.cfg
    return (f"model: {c.name} layers={c.num_layers} d_model={c.d_model} "
            f"heads={c.num_heads} head_dim={c.head_dim} d_ff={c.d_ff} "
            f"vocab={c.vocab_size} dtype={c.dtype} params={tr.n_params:,} "
            f"batch={args.batch} seq={args.seq} unroll={args.unroll} "
            f"precision={args.precision} schedule={tr.learner.schedule}")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def train_runs(check, stats):
    """``train.main`` at full width, f32 then bf16, three steps each."""
    from repro.kernels import dispatch
    from repro.launch import train

    dispatch.clear_dispatch_log()
    for precision in ("f32", "bf16"):
        argv = TRAIN_ARGS + ["--batch", str(PER_CHIP_BATCH), "--steps", str(STEPS),
                             "--precision", precision]
        stats.take()
        tr, rows = train.main(argv)
        timing = stats.take()
        print(model_line(tr, train.parse_args(argv)), flush=True)
        for r in rows:
            m = {k: r[k] for k in METRICS}
            print(f"{precision} step {r['step']}: {json.dumps(m)} "
                  f"seconds={r['step_s']:.4f}", flush=True)
        timing["step_s"] = [r["step_s"] for r in rows]
        print(f"{precision} timing: {json.dumps(timing)}", flush=True)
        check(f"full_width_{precision}", tr.n_params == BERT_BASE_PARAMS,
              f"params {tr.n_params:,} == {BERT_BASE_PARAMS:,}")
        finite = len(rows) == STEPS and all(
            math.isfinite(r[k]) for r in rows for k in METRICS)
        check(f"metrics_finite_{precision}", finite,
              f"{len(rows)} steps x {len(METRICS)} metrics")
        del tr

    tally = collections.Counter(dispatch.dispatch_log())
    print("dispatch tally (kernel -> backend reason) x count, at trace time:")
    for (kernel, backend, reason), n in sorted(tally.items()):
        print(f"  {kernel} -> {backend} {reason} x{n}")
    picks = set(tally)
    check("dispatch",
          ("adam_adapt", "pallas-tpu", "selected") in picks
          and not any(b == "pallas-interpret" for _, b, _ in picks)
          and not any("pallas-tpu:unavailable" in r for _, _, r in picks),
          "adam_adapt -> pallas-tpu selected; no pallas-interpret; "
          "no pallas-tpu unavailable")


def kernels_vs_ref(check):
    """One f32 step with the Pallas kernels against the same step traced
    with every kernel forced to ``ref``; then the adaptation product
    alone, at identical inputs."""
    import jax
    import numpy as np

    from repro import obs
    from repro.kernels import dispatch
    from repro.launch import train

    args = train.parse_args(TRAIN_ARGS + ["--batch", str(PER_CHIP_BATCH),
                                          "--precision", "f32"])
    out = {}
    for side, backend in (("kernels", None), ("ref", "ref")):
        with forced_backend(backend):
            dispatch.clear_dispatch_log()
            tr = train.build(args, obs.NULL_OBS)  # same seeds: same state and batches
            lr = tr.learner
            base = tr.make_batch(args.batch, args.unroll)
            meta = tr.make_batch(args.batch // 2)
            metrics = host(lr.step(base, meta))
            out[side] = {"metrics": metrics, "state": lr.state,
                         "step_via": adam_picks(dispatch)}
            print(f"f32 step kernels={side}: {json.dumps(metrics)}", flush=True)

    # the adaptation product at the kernel side's post-step state, with
    # that state's own base and meta gradients: identical inputs both ways
    spec, opt, state = lr.spec, lr.base_opt, out["kernels"]["state"]
    last_base = jax.tree_util.tree_map(lambda x: x[-1], base)
    g_base = jax.jit(jax.grad(spec.base_scalar))(state.theta, state.lam, last_base)
    g_meta = jax.jit(jax.grad(spec.meta_scalar))(state.theta, state.lam, meta)
    prod = {}
    for side, backend in (("kernels", None), ("ref", "ref")):
        with forced_backend(backend):
            dispatch.clear_dispatch_log()
            fn = jax.jit(lambda g, st, th, gm: opt.adapt_product(g, st, th, gm))
            prod[side] = fn(g_base, state.base_opt_state, state.theta, g_meta)
            out[side]["product_via"] = adam_picks(dispatch)
    via = {side: (o["step_via"], o["product_via"]) for side, o in out.items()}
    check("ref_twin_dispatch",
          via == {"kernels": (["pallas-tpu"],) * 2, "ref": (["ref"],) * 2},
          f"adam_adapt in (step, product): kernels side via {via['kernels']}, "
          f"ref side via {via['ref']}")
    # both against a float64 evaluation of the same formula on the host
    st = state.base_opt_state
    exact = jax.tree_util.tree_map(
        lambda g, m, v, gm: adam_product_f64(g, m, v, gm, t=int(st.count) + 1,
                                             lr=args.base_lr),
        g_base, st.mu, st.nu, g_meta)
    leaves = jax.tree_util.tree_leaves
    ndiff = sum(int(np.sum(np.asarray(a) != np.asarray(b)))
                for a, b in zip(leaves(prod["kernels"][0]), leaves(prod["ref"][0])))
    print(f"adaptation product vs float64: kernels rel "
          f"{rel(prod['kernels'][0], exact):.3g}, ref rel "
          f"{rel(prod['ref'][0], exact):.3g}; {ndiff} of {tr.n_params} "
          f"elements differ between kernels and ref", flush=True)
    d = {
        "v": rel(prod["kernels"][0], prod["ref"][0]),
        "v_sumsq": rel(prod["kernels"][1], prod["ref"][1]),
        "theta": rel(state.theta, out["ref"]["state"].theta),
        "lam": rel(state.lam, out["ref"]["state"].lam),
        "hypergrad_norm": rel(out["kernels"]["metrics"]["hypergrad_norm"],
                              out["ref"]["metrics"]["hypergrad_norm"]),
    }
    detail = ", ".join(f"{k} rel {v:.3g} <= {KERNEL_TOL[k]:g}" for k, v in d.items())
    nonzero = [k for k, v in d.items() if v > 0]
    check("sama_step_vs_ref", all(d[k] <= KERNEL_TOL[k] for k in d),
          f"f32: {detail}; differs from ref in {nonzero or 'nothing (bitwise equal)'}")


#: kernel vs ref twin at real sizes: f32 elementwise products agree to a
#: few ulp (sums of squares differ in summation order); bf16 inputs leave
#: the ref's bf16 arithmetic about 2^-8 from the kernels' f32 arithmetic
PARITY_TOL = {"f32": 1e-5, "f32_sum": 1e-4, "bf16": 2e-2}


def kernel_parity(check):
    """Every kernel compiled for the chip against its ``ref`` twin, at
    the sizes the configs use: the adaptation products on bert-base's
    largest leaf, CE at a 32k vocabulary, flash attention and decode at
    head_dim 128."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import dispatch

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))

    def normal(shape, dtype=jnp.float32, scale=1.0):
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)

    def compare(name, fn, args, tols, detail):
        """``fn(kernel, *args)`` through pallas-tpu and through ref."""
        outs = {}
        for backend in ("pallas-tpu", "ref"):
            dispatch.clear_dispatch_log()
            kern = dispatch.get_kernel(name, backend=backend)
            outs[backend] = jax.jit(lambda *a: fn(kern, *a))(*args)
            picked = {b for k, b, _ in dispatch.dispatch_log() if k == name}
            if backend == "pallas-tpu":
                ran = picked == {"pallas-tpu"}
        d = [rel(a, b) for a, b in zip(outs["pallas-tpu"], outs["ref"])]
        text = ", ".join(f"{n} rel {x:.3g} <= {t:g}" for n, x, t in zip(tols, d, tols.values()))
        check(name, ran and all(x <= t for x, t in zip(d, tols.values())),
              f"{detail}: pallas-tpu ran: {ran}; {text}")

    n = 30522 * 768  # bert-base's word embedding, its largest leaf
    g, m, gm = normal((n,)), normal((n,)), normal((n,))
    v = jnp.abs(normal((n,), scale=1e-3))
    f32, f32s = PARITY_TOL["f32"], PARITY_TOL["f32_sum"]
    # the step count and learning rate are traced, as in the training step
    t, lr = jnp.float32(3), jnp.float32(1e-3)
    compare("adam_adapt",
            lambda k, g, m, v, gm, t, lr: k(g, m, v, gm, t=t, b1=0.9, b2=0.999,
                                            eps=1e-8, lr=lr),
            (g, m, v, gm, t, lr), {"out": f32, "sumsq": f32s}, f"n={n}")
    compare("lion_adapt", lambda k, g, m, gm, lr: k(g, m, gm, lr=lr, b1=0.9, delta=1e-3),
            (g, m, gm, lr), {"out": f32, "sumsq": f32s}, f"n={n}")
    compare("adafactor_adapt", lambda k, vh, gm, lr: k(vh, gm, lr=lr, eps=1e-8),
            (v + 1e-3, gm, lr), {"out": f32, "sumsq": f32s}, f"n={n}")
    del g, m, gm, v

    bf = PARITY_TOL["bf16"]
    r, vocab = 4096, 32768
    logits = normal((r, vocab), jnp.bfloat16)
    targets = jax.random.randint(next(keys), (r,), 0, vocab)
    w = jax.random.uniform(next(keys), (r,))

    def ce(k, x, y, w):
        out, vjp = jax.vjp(lambda x: k(x, y), x)
        return out, vjp(w)[0]

    compare("weighted_ce", ce, (logits, targets, w),
            {"ce": PARITY_TOL["f32_sum"], "dlogits": bf}, f"({r}, {vocab}) bf16")
    del logits

    b, s, h, kvh, dh = 2, 1024, 16, 8, 128
    q = normal((b, s, h, dh), jnp.bfloat16)
    k, vv = normal((b, s, kvh, dh), jnp.bfloat16), normal((b, s, kvh, dh), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cot = normal((b, s, h, dh), jnp.bfloat16)

    def attn(kern, q, k, v, pos, cot):
        out, vjp = jax.vjp(lambda q, k, v: kern(q, k, v, pos, pos[0], causal=True), q, k, v)
        return (out,) + vjp(cot)

    compare("flash_attention", attn, (q, k, vv, pos, cot),
            {"out": bf, "dq": bf, "dk": bf, "dv": bf},
            f"B{b} S{s} H{h} KV{kvh} Dh{dh} bf16 causal")

    lanes, t = 8, 4096
    qd = normal((lanes, 1, h, dh), jnp.bfloat16)
    kd, vd = normal((lanes, t, kvh, dh), jnp.bfloat16), normal((lanes, t, kvh, dh), jnp.bfloat16)
    qpos = jnp.linspace(1, t - 1, lanes).astype(jnp.int32).reshape(lanes, 1)
    compare("flash_decode", lambda kern, *a: (kern(*a),), (qd, kd, vd, qpos),
            {"out": bf}, f"{lanes} lanes T{t} bf16")


def one_chip(check, stats):
    check.phase("kernel_parity", kernel_parity, check)
    check.phase("train_runs", train_runs, check, stats)
    check.phase("kernels_vs_ref", kernels_vs_ref, check)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def four_chips(check, stats):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api, obs, perf
    from repro.launch import train

    n = len(jax.devices())
    args = train.parse_args(TRAIN_ARGS + ["--batch", str(n * PER_CHIP_BATCH),
                                          "--precision", "f32",
                                          "--manual-collectives"])
    stats.take()
    tr = train.build(args, obs.NULL_OBS)
    lr, mesh = tr.learner, tr.mesh
    print(model_line(tr, args), flush=True)
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    print(f"mesh: {shape} over {[d.id for d in mesh.devices.flat]}", flush=True)
    check("mesh", shape == {"data": n, "model": 1}, f"{shape} from {n} devices")
    state0 = lr.state

    def tile(tree, axis):
        return jax.tree_util.tree_map(lambda x: jnp.concatenate([x] * n, axis=axis), tree)

    def flip(tree, axis):
        return jax.tree_util.tree_map(lambda x: jnp.flip(x, axis=axis), tree)

    base_dev = tr.make_batch(PER_CHIP_BATCH, args.unroll)   # one chip's share
    meta_dev = tr.make_batch(PER_CHIP_BATCH // 2)
    same = (tile(base_dev, 1), tile(meta_dev, 0))             # each chip gets it
    distinct = (tr.make_batch(args.batch, args.unroll), tr.make_batch(args.batch // 2))

    def run(learner, batches, state):
        learner.state = state
        t = time.perf_counter()
        m = host(learner.step(*batches))
        jax.block_until_ready(learner.state)
        return {"metrics": m, "theta": learner.state.theta, "lam": learner.state.lam,
                "seconds": time.perf_counter() - t}

    four_same = run(lr, same, state0)
    compile_first = stats.take()
    four_same_warm = run(lr, same, state0)
    four_dist = run(lr, distinct, state0)
    print(f"timing {n} chips: {json.dumps(dict(compile_first, first_step_s=four_same['seconds'], step_s=[four_same_warm['seconds'], four_dist['seconds']]))}",
          flush=True)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    print(f"memory per device: peak_bytes_in_use={peaks}", flush=True)

    with mesh:
        compiled = lr.step_fn.lower(state0, *distinct).compile()
    census = perf.verify_single_sync(compiled, args.unroll)
    got = census.get("all-reduce_count", 0)
    check("census", got == args.unroll + 1,
          f"all-reduces {got} == unroll+1 = {args.unroll + 1}")

    state_sh, base_sh, meta_sh = compiled.input_shardings[0]
    leaves = jax.tree_util.tree_leaves
    batch_sharded = all(
        sh.shard_shape(x.shape)[axis] * n == x.shape[axis]
        for tree_sh, tree, axis in ((base_sh, distinct[0], 1), (meta_sh, distinct[1], 0))
        for sh, x in zip(leaves(tree_sh), leaves(tree)))
    state_replicated = all(s.is_fully_replicated for s in leaves(state_sh)) and all(
        x.sharding.is_fully_replicated and len(x.sharding.device_set) == n
        for x in leaves((four_dist["theta"], four_dist["lam"])))
    ratio = min(peaks) / max(max(peaks), 1)
    check("placement", batch_sharded and state_replicated and ratio >= 0.5,
          f"batch split {n} ways: {batch_sharded}; theta and lam replicated "
          f"on {n}: {state_replicated}; peak bytes min/max {ratio:.3f} >= 0.5")

    def replicas_equal(tree):
        for x in leaves(tree):
            shards = [np.asarray(s.data) for s in x.addressable_shards]
            if not all(np.array_equal(shards[0], s) for s in shards[1:]):
                return False
        return True

    finite = all(math.isfinite(v) for v in four_dist["metrics"].values())
    same_state = replicas_equal((four_dist["theta"], four_dist["lam"]))
    check("distinct_shards", finite and same_state,
          f"metrics finite: {finite}; theta and lam identical on all {n} "
          f"devices: {same_state}")

    # the one-chip step, on device 0, from the same state
    one = api.MetaLearner(lr.spec, base_opt=lr.base_opt, meta_opt=lr.meta_opt,
                          engine_config=lr.cfg)
    state_one = jax.device_put(state0, jax.devices()[0])
    ref = run(one, (base_dev, meta_dev), state_one)
    ref_flip = run(one, (flip(base_dev, 1), flip(meta_dev, 0)), state_one)
    ref_global = run(one, distinct, state_one)
    for name, r in (("4 chips, identical shards", four_same),
                    ("1 chip, one shard", ref), ("1 chip, shard reordered", ref_flip),
                    ("4 chips, distinct shards", four_dist),
                    ("1 chip, global batch", ref_global)):
        print(f"f32 metrics {name}: {json.dumps(r['metrics'])}", flush=True)

    def diffs(a, b):
        d = {k: rel(a["metrics"][k], b["metrics"][k]) for k in METRICS}
        d["theta"] = rel(a["theta"], b["theta"])
        d["lam"] = rel(a["lam"], b["lam"])
        return d

    d4 = diffs(four_same, ref)
    noise = diffs(ref_flip, ref)
    print("  4 chips identical vs 1 chip: "
          + ", ".join(f"{k} rel {v:.3g}" for k, v in d4.items()))
    print("  1 chip reordered vs 1 chip (noise): "
          + ", ".join(f"{k} rel {v:.3g}" for k, v in noise.items()))
    check("identical_shards_equal_one_chip",
          all(d4[k] <= max(noise[k], ULP_FLOOR) for k in d4),
          ", ".join(f"{k} {d4[k]:.3g} <= {max(noise[k], ULP_FLOOR):.3g}" for k in d4))
    dd = diffs(four_dist, ref_global)
    print("  4 chips distinct vs 1 chip global batch (not checked; the schedule "
          "averages per-shard estimates): "
          + ", ".join(f"{k} rel {v:.3g}" for k, v in dd.items()), flush=True)


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip single-sync phase")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repro package is not under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    t0 = time.perf_counter()

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: {json.dumps(device)}", flush=True)
    check = Checks()
    want = 4 if args.four_chips else 1
    if device["platform"] != "tpu":
        check("tpu", False, f"JAX found no TPU (platform {device['platform']!r})")
    elif device["count"] < want:
        check("devices", False, f"{device['count']} devices, need {want}")
    else:
        from repro.launch.compile_cache import enable_compile_cache

        print(f"compile cache: {enable_compile_cache()}", flush=True)
        stats = CompileStats(jax)
        if args.four_chips:
            check.phase("four_chips", four_chips, check, stats)
        else:
            one_chip(check, stats)
    print(f"wall seconds: {time.perf_counter() - t0:.1f}; failed checks: "
          f"{check.failed or 'none'}", flush=True)
    ok = not check.failed
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
