#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``configs/<config>.json``) under a traffic mix (``traffic/<mix>.json``) on
1 or 4 chips. One run:

1. set-up: imports, the device check (a TPU, with as many chips as the cell
   asks for; otherwise the run exits 2 and prints no result), the learner
   built through ``repro.api.MetaLearner`` as ``repro.launch.train`` builds
   it, the weights made from the seed in one jitted call, the first meta
   step, which gives the readings the correctness check compares, and more
   steps until one completes with nothing compiled (or loaded from the
   persistent cache) while it ran;
2. ``--trace 0``: the window. ``MetaLearner.step`` is driven back to back,
   the host making each step's batches as a data loader would, with at
   most two steps in flight. The window opens once that step has completed
   and closes at the first step that completes after ``--seconds``.
   ``examples_per_s`` is the base examples of the steps completed in it
   over its length; ``peak_hbm_gib`` the largest ``peak_bytes_in_use``
   over the cell's chips after it.
   ``--trace 1``: the profiler records ``trace_steps`` steps instead, and
   the per-layer metrics are read from that trace.
   A run in which anything compiles inside the window exits 3 and prints
   no result: its rate would time the compiler;
3. the program's state is freed and the plain reference (``reference.py``)
   runs the first step from the same weights; ``check.py`` compares the
   numbers ``limits/<cell>.json`` names.

The last line of stdout is one JSON object; the numbers compared are its
last key, and the last lines of stderr.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE = ROOT / ".bench_cache"
sys.path.insert(0, str(HERE))

import check  # noqa: E402
import manifest  # noqa: E402

CHECKED_STEPS = 1
MAX_WARMUP = 6
IN_FLIGHT = 2
GIB = float(2 ** 30)
HOST_SPANS = ("make_batch", "dispatch", "wait")


def note(what: str, t0: float = T_START):
    """A line on stderr with the seconds since the process started."""
    print(f"{time.perf_counter() - t0:8.2f} s  {what}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """The run found no TPU, or fewer chips than the cell asks for."""


class CompiledInWindow(RuntimeError):
    """Something compiled inside the measured window."""


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax():
    """The persistent compilation cache at a fixed path inside the checkout,
    for every program however short its compile; the TPU runtime's logs
    inside the checkout too, unless the caller placed them."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Backend compiles (persistent-cache loads included) since ``n`` was
    last set to 0."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _tree_shapes(tree) -> List:
    import jax

    return [(jax.tree_util.keystr(p), tuple(x.shape), str(x.dtype))
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]]


def check_layout(model, config):
    """The program's parameter tree must be the one the configuration file
    describes, leaf for leaf: the weights the benchmark makes go in as they
    are."""
    import jax
    import jax.numpy as jnp
    import reference
    from repro.core import problems

    shapes = lambda tree: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), tree, is_leaf=lambda x: isinstance(x, tuple))
    prog_theta = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    prog_lam = jax.eval_shape(
        lambda k: problems.init_data_optimization_lam(k, reweight=True), jax.random.PRNGKey(0))
    for what, prog, ref in (("theta", prog_theta, shapes(reference.theta_shapes(config))),
                            ("lam", prog_lam, shapes(reference.lam_shapes()))):
        if _tree_shapes(prog) != _tree_shapes(ref):
            raise ValueError(f"{config['registry']}: the program's {what} layout differs from "
                             f"the configuration file's")


class Feed:
    """Makes step ``i``'s batches on the host and hands them to the devices
    where the learner's step takes them."""

    def __init__(self, traffic, learner, schedule):
        import jax
        import sut

        self.traffic = traffic
        base, meta = traffic.step_batches(0)
        self.shardings = sut.batch_shardings(learner.mesh, schedule, base, meta)
        self._put = jax.device_put

    def __call__(self, i):
        base, meta = self.traffic.step_batches(i)
        return self._put(base, self.shardings[0]), self._put(meta, self.shardings[1])


def program_readings(learner, feed, step_fn, metrics_out, steps: int = CHECKED_STEPS) -> Dict[str, Any]:
    """Drive the first ``steps`` steps through the window's own call and
    feed, and read what ``reference.readings`` reads of the same steps."""
    import jax
    import reference

    theta0, lam0 = jax.device_get((learner.state.theta, learner.state.lam))
    out = {"metrics": []}
    for i in range(steps):
        m = step_fn(*feed(i))
        jax.block_until_ready(m)
        note(f"step {i + 1} done")
        metrics_out.append(m)
        out["metrics"].append({k: float(v) for k, v in jax.device_get(m).items()})
        if i == 0:
            st = learner.state
            out["base_moment"] = reference.leaf_norms(jax.device_get(st.base_opt_state.mu))
            out["meta_moment"] = reference.leaf_norms(jax.device_get(st.meta_opt_state.mu))
            out["theta_change1"] = reference.leaf_change_norms(theta0, jax.device_get(st.theta))
            out["lam_change1"] = reference.leaf_change_norms(lam0, jax.device_get(st.lam))
    if steps > 1:
        out["theta_change"] = reference.leaf_change_norms(theta0, jax.device_get(learner.state.theta))
        out["lam_change"] = reference.leaf_change_norms(lam0, jax.device_get(learner.state.lam))
    return out


def warm_up(step_fn, feed, first: int, compiles, metrics_out) -> int:
    """Run steps from ``first`` on, one at a time, until one completes with
    nothing compiled while it ran; returns the index of the next step."""
    import jax

    for i in range(first, first + MAX_WARMUP):
        compiles.n = 0
        m = step_fn(*feed(i))
        jax.block_until_ready(m)
        metrics_out.append(m)
        note(f"step {i + 1} done, {compiles.n} compile(s)")
        if compiles.n == 0:
            return i + 1
    raise CompiledInWindow(f"the step still compiled after {MAX_WARMUP} warm-up steps")


def drive(step_fn, feed, first: int, until: Callable[[int, float], bool], annotate=False):
    """Run steps ``first, first+1, ...`` with at most ``IN_FLIGHT`` in
    flight until ``until(completed, now)``; returns (completed, t_end,
    metrics of every step dispatched)."""
    import contextlib

    import jax

    span = jax.profiler.TraceAnnotation if annotate else (lambda name: contextlib.nullcontext())
    pending, dispatched = [], []
    done, i = 0, first
    t_end = time.perf_counter()
    gaps = []
    while True:
        with span("make_batch"):
            batch = feed(i)
        with span("dispatch"):
            m = step_fn(*batch)
        pending.append(m)
        dispatched.append(m)
        i += 1
        if len(pending) >= IN_FLIGHT:
            with span("wait"):
                jax.block_until_ready(pending.pop(0))
            done += 1
            now = time.perf_counter()
            gaps.append(now - t_end)
            t_end = now
            if until(done, t_end):
                break
    jax.block_until_ready(pending)
    if gaps:
        gaps.sort()
        note(f"{done} steps completed; seconds between completions: median "
             f"{gaps[len(gaps) // 2]:.4f}, longest {gaps[-1]:.4f}")
    return done, t_end, dispatched


def peak_bytes(devs) -> int:
    """The largest ``peak_bytes_in_use`` over the chips (0 where the backend
    keeps no such count, as the CPU)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)


def traced_metrics(step_fn, feed, first, ctx, per_layer, trace_steps, devs, hlo):
    """Profile ``trace_steps`` steps from step ``first`` on and read each
    per-layer metric. ``hlo`` is the compiled step's text, which names the
    scope and the kernel of each of its ops, for a trace whose events carry
    neither."""
    import jax
    import trace_reduce as tr

    tdir = CACHE / "trace"
    shutil.rmtree(tdir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tdir), profiler_options=options)
    try:
        _, _, dispatched = drive(step_fn, feed, first,
                                 lambda done, now: done >= trace_steps, annotate=True)
    finally:
        jax.profiler.stop_trace()
    trace = tr.load(tr.find_xplane(str(tdir)), op_paths=tr.op_paths_from_hlo(hlo),
                    kernels=tr.kernels_from_hlo(hlo), host_names=HOST_SPANS)
    shutil.rmtree(tdir, ignore_errors=True)
    ids = [d.id for d in devs]
    ops = {i: trace.devices.get(i, []) for i in ids}
    ctx = dict(ctx, trace=trace, ops=ops, steps=len(dispatched))
    out = {}
    for m in per_layer:
        v = manifest.reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    first = ids[0]
    busy = sum(tr.busy_ns(ops[i]) for i in ids) / len(ids) / 1e9
    window = max(tr.window_ns(ops[i]) for i in ids) / 1e9
    breakdown = {"device_ops": [[k, v] for k, v in tr.top_ops(ops[first])],
                 "idle_gaps": [[k, v] for k, v in tr.idle_gaps(ops[first], trace.host)]}
    return out, busy, window, breakdown, dispatched


def run_cell(cell: Dict[str, Any], config: Dict[str, Any], mix: Dict[str, Any],
             limits: Dict[str, float], seed: int, seconds: float, trace: bool,
             *, bench: Optional[Dict[str, Any]] = None, require_tpu: bool = True,
             wrap_step: Optional[Callable] = None, t_start: float = T_START) -> Dict[str, Any]:
    """One run of one cell; returns the result line's object. Tests pass
    ``require_tpu=False``, and ``wrap_step`` to replace the learner's step
    with a broken one."""
    import sut

    sut.import_program()
    configure_jax()
    import jax

    import reference
    from traffic import Traffic

    chips = cell["chips"]
    devs = devices_for(chips, require_tpu)
    note(f"devices: {chips} x {devs[0].device_kind}", t_start)
    seed = seed % 2 ** 64
    _, model, learner = sut.build_learner(config, mix, chips)
    check_layout(model, config)
    traffic = Traffic(mix, config, chips, seed)
    theta, lam = reference.init_weights(config, seed, sut.replicated(learner.mesh))
    learner.init(theta, lam)
    del theta, lam
    step_fn = learner.step if wrap_step is None else wrap_step(learner)
    feed = Feed(traffic, learner, mix["schedule"])
    compiles = CompileCounter()
    note("learner built, weights made", t_start)

    metrics: List[Any] = []
    prog = program_readings(learner, feed, step_fn, metrics)
    first = warm_up(step_fn, feed, CHECKED_STEPS, compiles, metrics)
    if trace:
        # the compiled step's op metadata, read before the traced steps
        hlo = learner.step_fn.lower(learner.state, *feed(0)).compile().as_text()
    t_open = time.perf_counter()
    note(f"warmed up in {first} steps; window opens", t_start)
    setup_s = t_open - t_start
    compiles.n = 0

    result_metrics: Dict[str, Dict[str, Any]] = {}
    device: Dict[str, Any] = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                              "count": chips}
    breakdown = None
    if not trace:
        done, t_end, window_metrics = drive(
            step_fn, feed, first, lambda done, now: now - t_open >= seconds)
        rate = done * traffic.examples_per_step() / (t_end - t_open)
        metrics += window_metrics
        attempted = len(window_metrics)
    else:
        import flops

        ctx = {"chips": chips, "peaks": manifest.peaks(devs[0].device_kind),
               "config": config, "mix": mix,
               "step_flops": flops.step_flops(config, mix, chips),
               "adam_adapt": flops.adam_adapt_cost(config)}
        per_layer = manifest.metrics_of(bench, cell["name"], "per_layer") if bench else []
        result_metrics, busy, window, breakdown, traced = traced_metrics(
            step_fn, feed, first, ctx, per_layer, mix["trace_steps"], devs, hlo)
        device["busy_s"], device["window_s"] = busy, window
        metrics += traced
        attempted = len(traced)
    if compiles.n:
        raise CompiledInWindow(f"{compiles.n} compile(s) inside the measured window")
    device["memory_peak_bytes"] = peak_bytes(devs)
    host_metrics = jax.device_get(metrics)
    failed = sum(1 for m in host_metrics[first:]
                 if not all(math.isfinite(float(v)) for v in m.values()))

    # free the program's state before the reference runs
    learner.state = None
    del learner, step_fn, feed, metrics, host_metrics
    gc.collect()

    note("window closed, program state freed", t_start)
    ref = reference.readings(config, traffic.settings(), seed,
                             [traffic.step_batches(i) for i in range(CHECKED_STEPS)],
                             steps=CHECKED_STEPS)
    note("reference done", t_start)
    correct, rows = check.decide(prog, ref, limits)

    if not trace:
        result_metrics = {
            "examples_per_s": {"value": rate, "unit": "examples/s"},
            "peak_hbm_gib": {"value": device["memory_peak_bytes"] / GIB, "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": result_metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {name: {"value": v, "limit": lim} for name, v, lim in rows}
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        bench = manifest.load()
        cell = manifest.cell(bench, args.workload)
        result = run_cell(cell, manifest.config(cell["config"]), manifest.traffic(cell["traffic"]),
                          manifest.limits(cell["name"]), args.seed, args.seconds, bool(args.trace),
                          bench=bench)
    except NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    except CompiledInWindow as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
