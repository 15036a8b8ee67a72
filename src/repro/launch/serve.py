"""Serving CLI — a thin driver over ``repro.serve`` (docs/serve.md).

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --smoke \
        --requests 12 --slots 4 --gen 16

Submits a mixed-length request set to the continuous-batching executor
and reports per-request latency (p50/p99), sustained QPS, shed counts
and paged-cache memory, embedding a full ``perf.PerfRecord`` (with the
``latency`` section) in the emitted JSON. ``--serial`` runs the same
request set through the serial dense-cache ``greedy_generate`` reference
loop instead — the two modes emit the same record shape, so the CLI
doubles as an ad-hoc A/B harness (benchmarks/bench_serve.py is the
gated version).

``greedy_generate`` is re-exported from ``repro.serve.prefill`` for
back-compat; the seed's copy here prefilled with P separate jitted
calls and hard-coded f32 caches (the configured-dtype fix and the
single-call chunked prefill live in the subsystem now).
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro import configs, perf, serve
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serve import greedy_generate  # noqa: F401  (back-compat re-export)


def make_requests(cfg, n: int, prompt_len: int, gen: int, seed: int = 0):
    """Mixed-length prompts around ``prompt_len`` (the serving regime the
    paged cache exists for — uniform lengths would flatter dense caches)."""

    rng = np.random.default_rng(seed)
    lens = rng.integers(max(1, prompt_len // 2), prompt_len + 1, size=n)
    return [rng.integers(0, cfg.vocab_size, size=(int(L),)).astype(np.int32)
            for L in lens], [gen] * n


def run_continuous(model, params, prompts, gens, scfg: serve.ServeConfig,
                   obs=None, inject_hang=None):
    ex = serve.ServeExecutor(model, params, scfg, obs=obs)
    if inject_hang:
        ex.inject_hang(inject_hang)
    ids = [ex.submit(p, max_new_tokens=g) for p, g in zip(prompts, gens)]
    stats = ex.run()
    return ex, ids, stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-1.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=0,
                    help="per-request token cap (0 = prompt+gen rounded to a "
                         "page multiple)")
    ap.add_argument("--timeout-s", type=float, default=None,
                    help="per-request deadline (shed on miss)")
    ap.add_argument("--serial", action="store_true",
                    help="serial dense-cache reference loop instead of "
                         "continuous batching")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--obs-log", default=None, metavar="PATH",
                    help="append structured events (JSONL) for "
                         "`python -m repro.obs.report`")
    ap.add_argument("--chrome-trace", default=None, metavar="PATH",
                    help="write a Perfetto/chrome://tracing span timeline "
                         "(serve ticks + per-lane request tracks, or "
                         "per-request spans under --serial)")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="write flight-recorder postmortem bundles here "
                         "(read with `repro.obs.report --postmortem`)")
    ap.add_argument("--hang-deadline-s", type=float, default=None,
                    help="hang watchdog: dump a postmortem when no tick "
                         "completes within this deadline")
    ap.add_argument("--inject-hang", type=float, default=None,
                    metavar="SECONDS",
                    help="fault injection: stall the tick loop once for "
                         "SECONDS (CI exercises the watchdog with this)")
    ap.add_argument("--slo-budget", type=float, default=None,
                    help="allowed deadline-miss fraction; arms the SLO "
                         "burn-rate alert (which also triggers a postmortem "
                         "dump)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get_smoke_config(args.arch) if args.smoke else configs.get_config(args.arch)
    if cfg.family == "encoder":
        raise SystemExit("encoder-only architectures have no decode step")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts, gens = make_requests(cfg, args.requests, args.prompt_len,
                                  args.gen, args.seed)

    obs = None
    if args.obs_log:
        from repro import obs as obs_mod
        obs = obs_mod.make_obs(log_path=args.obs_log,
                               run_id=f"serve-{cfg.name}",
                               slo_budget=args.slo_budget)
        obs_mod.set_default(obs)
        obs.emit("run", "run_start", data={
            "cli": "serve", "arch": cfg.name,
            "mode": "serial" if args.serial else "continuous",
            "requests": args.requests})

    pg = args.page_size
    max_len = args.max_len or pg * ((args.prompt_len + args.gen + pg - 1) // pg)

    tracer = None
    if args.chrome_trace:
        from repro import obs as obs_mod
        # executor.run() picks the tracer up via active_tracer() and spans
        # each tick; the serial arm spans each request explicitly
        tracer = obs_mod.Tracer(obs=obs)

    if args.serial:
        import contextlib
        import time
        lat = []
        outs = []
        t0 = time.perf_counter()
        for p, g in zip(prompts, gens):
            s0 = time.perf_counter()
            with (tracer.span("serial_request") if tracer is not None
                  else contextlib.nullcontext()):
                toks = greedy_generate(model, params, np.asarray(p)[None], g,
                                       max_len)
                jax.block_until_ready(toks)
            lat.append(time.perf_counter() - s0)
            outs.append([int(t) for t in toks[0]])
        elapsed = time.perf_counter() - t0
        latency = perf.LatencyStats.from_samples(lat)
        payload = {
            "mode": "serial", "arch": cfg.name, "requests": args.requests,
            "qps": round(args.requests / elapsed, 2),
            "latency_us": latency.as_dict(),
            "sample": outs[0],
        }
        record = perf.PerfRecord(
            name=f"serve_serial_{cfg.name}",
            # n == 0 (no requests survived to decode): the payload still
            # shows the zeroed stats, but a PerfRecord latency section
            # must carry real percentiles (validate_record), so omit it
            latency=latency.as_dict() if latency.n else None,
            samples_per_s=args.requests / elapsed,
            extra={"requests": args.requests, "gen": args.gen},
        )
    else:
        scfg = serve.ServeConfig(
            slots=args.slots, page_size=pg, max_len=max_len,
            max_new_tokens=args.gen, default_timeout_s=args.timeout_s,
            flight_dir=args.flight_dir,
            hang_deadline_s=args.hang_deadline_s,
        )
        if tracer is not None:
            from repro import obs as obs_mod
            with obs_mod.activate(tracer):
                ex, ids, stats = run_continuous(model, params, prompts, gens,
                                                scfg, obs=obs,
                                                inject_hang=args.inject_hang)
        else:
            ex, ids, stats = run_continuous(model, params, prompts, gens, scfg,
                                            obs=obs,
                                            inject_hang=args.inject_hang)
        payload = {
            "mode": "continuous", "arch": cfg.name, "requests": args.requests,
            "statuses": {s: sum(ex.results[i].status == s for i in ids)
                         for s in set(ex.results[i].status for i in ids)},
            "qps": round(stats.qps, 2),
            "latency_us": stats.latency.as_dict(),
            "ttft_us": stats.ttft.as_dict(),
            "tpot_us": stats.tpot.as_dict(),
            "queue_wait_us": stats.queue_wait.as_dict(),
            "lanes": stats.lanes,
            "decode_steps": stats.steps,
            "memory": stats.memory,
            "sample": ex.results[ids[0]].tokens,
        }
        if ex.flight is not None and ex.flight.dumps:
            payload["postmortems"] = list(ex.flight.dumps)
        record = perf.PerfRecord(
            name=f"serve_{cfg.name}",
            latency=stats.latency.as_dict() if stats.latency.n else None,
            samples_per_s=stats.qps if np.isfinite(stats.qps) else None,
            extra={"requests": args.requests, "gen": args.gen,
                   "slots": args.slots, "decode_steps": stats.steps,
                   "cache_peak_bytes": stats.memory["peak_bytes"],
                   "ttft_p50_us": stats.ttft.p50_us if stats.ttft.n else None,
                   "tpot_p50_us": stats.tpot.p50_us if stats.tpot.n else None},
        )
    if tracer is not None:
        from repro import obs as obs_mod
        # continuous mode: each decode lane becomes its own track, built
        # from the flight ring's lifecycle events (always on by default)
        lane_events = []
        if not args.serial and ex.flight is not None:
            lane_events = obs_mod.lane_chrome_events(ex.flight.events())
        obs_mod.write_chrome_trace(args.chrome_trace, tracer.spans,
                                   extra_events=lane_events)
        payload["chrome_trace"] = {"path": args.chrome_trace,
                                   "spans": len(tracer.spans),
                                   "lane_events": len(lane_events)}
    payload["perf"] = record.as_dict()
    print(json.dumps(payload))
    if obs is not None:
        obs.emit("metrics", "registry_snapshot", data=obs.metrics.snapshot())
        obs.emit("run", "run_end",
                 data={"qps": payload["qps"], "health": obs.health.status,
                       "ring_dropped": obs.sink_dropped()})
        obs.close()


if __name__ == "__main__":
    main()
