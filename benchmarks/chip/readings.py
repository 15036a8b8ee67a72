#!/usr/bin/env python3
"""The readings a cell's correctness limits are set from, on the chip.

    python3 benchmarks/chip/readings.py --workload <cell> --seeds 1 2 ... \
        [--control-seeds 1 2 3] [--bf16-seeds 1 2 3] [--half-batch-seeds 1 2 3] \
        [--no-exchange-seeds 1 2 3] [--permuted-seeds 1 2 3] [--out FILE]

In one process, at the cell's own sizes and through the window's own call
and feed: for each seed the program's first three steps against the
reference (the lower readings); for each control seed the reference at
``high`` precision in the program's place (the upper readings); and, for
each bf16 seed, the program under its own ``bf16`` policy against the
reference; and for each half-batch seed, the program with half of each
batch left out, and for each no-exchange seed, the single-sync schedule
with its exchange between chips left out (faults the comparison has to
catch). For each permuted seed, on one chip, two witnesses of how far
rounding alone moves a number: the reference, and the program, each on the
same batches with their examples in another order (the same problem, since
every loss is a mean over examples), against the reference. Prints one JSON
line per run with every number ``check.gaps`` gives; the benchmark's own
runs do not run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import check  # noqa: E402
import manifest  # noqa: E402
import run  # noqa: E402

READ_STEPS = 3


class Permuted:
    """The seed's traffic with the examples of every batch in another order,
    drawn from the seed and the step."""

    def __init__(self, traffic):
        self.traffic = traffic

    def __getattr__(self, name):
        return getattr(self.traffic, name)

    def step_batches(self, i):
        import numpy as np

        base, meta = self.traffic.step_batches(i)
        rng = np.random.default_rng((self.traffic.seed, i, 1))
        pb, pm = rng.permutation(self.traffic.batch), rng.permutation(self.traffic.meta_batch)
        return {k: v[:, pb] for k, v in base.items()}, {k: v[pm] for k, v in meta.items()}


def half_batch(learner):
    """The fault of a step that leaves out half of each batch and takes the
    mean over the rest."""
    def step(base, meta):
        base = {k: v[:, : v.shape[1] // 2] for k, v in base.items()}
        meta = {k: v[: v.shape[0] // 2] for k, v in meta.items()}
        return learner.step(base, meta)
    return step


def program_side(config, mix, chips, seeds, policy=None, fault=None, no_exchange=False,
                 permuted=False):
    import reference
    import sut
    from repro.launch import distributed
    from traffic import Traffic

    cfg_file = dict(config, policy=policy) if policy else config
    flat_pmean = distributed.flat_pmean
    if no_exchange:  # the fault of a schedule that never exchanges between chips
        distributed.flat_pmean = lambda tree, axes: tree
    try:
        _, _, learner = sut.build_learner(cfg_file, mix, chips)
    finally:
        distributed.flat_pmean = flat_pmean
    out = {}
    for seed in seeds:
        traffic = Traffic(mix, config, chips, seed)
        if permuted:
            traffic = Permuted(traffic)
        learner.init(*reference.init_weights(config, seed, sut.replicated(learner.mesh)))
        feed = run.Feed(traffic, learner, mix["schedule"])
        step = fault(learner) if fault else learner.step
        out[seed] = run.program_readings(learner, feed, step, [], steps=READ_STEPS)
        learner.state = None
        gc.collect()
    del learner
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--bf16-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--half-batch-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--no-exchange-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--permuted-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import sut

    sut.import_program()
    run.configure_jax()
    import reference
    from traffic import Traffic

    bench = manifest.load()
    cell = manifest.cell(bench, args.workload)
    config, mix = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    chips = cell["chips"]
    run.devices_for(chips, require_tpu=True)
    if args.permuted_seeds and chips != 1:
        raise SystemExit("--permuted-seeds: on one chip only (each chip's share is its own mean)")

    rows = []
    prog = program_side(config, mix, chips, args.seeds)
    bf16 = program_side(config, mix, chips, args.bf16_seeds, policy="bf16") if args.bf16_seeds else {}
    half = (program_side(config, mix, chips, args.half_batch_seeds, fault=half_batch)
            if args.half_batch_seeds else {})
    lost = (program_side(config, mix, chips, args.no_exchange_seeds, no_exchange=True)
            if args.no_exchange_seeds else {})
    perm = (program_side(config, mix, chips, args.permuted_seeds, permuted=True)
            if args.permuted_seeds else {})
    for seed in sorted(set(args.seeds) | set(args.control_seeds) | set(args.bf16_seeds)
                       | set(args.half_batch_seeds) | set(args.no_exchange_seeds)
                       | set(args.permuted_seeds)):
        traffic = Traffic(mix, config, chips, seed)
        batches = [traffic.step_batches(i) for i in range(READ_STEPS)]
        ref = reference.readings(config, traffic.settings(), seed, batches, steps=READ_STEPS)
        sides = [("program", prog.get(seed)), ("bf16", bf16.get(seed)),
                 ("half_batch", half.get(seed)), ("no_exchange", lost.get(seed)),
                 ("program_permuted", perm.get(seed))]
        if seed in args.control_seeds:
            sides.append(("control_high", reference.readings(
                config, traffic.settings(), seed, batches, precision="high", steps=READ_STEPS)))
        if seed in args.permuted_seeds:
            shuffled = Permuted(traffic)
            sides.append(("reference_permuted", reference.readings(
                config, traffic.settings(), seed,
                [shuffled.step_batches(i) for i in range(READ_STEPS)], steps=READ_STEPS)))
        for side, got in sides:
            if got is None:
                continue
            row = {"workload": args.workload, "seed": seed, "side": side,
                   "gaps": check.gaps(got, ref), "metrics": got["metrics"],
                   "ref_metrics": ref["metrics"]}
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
