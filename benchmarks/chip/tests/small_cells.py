"""The cells at a size a CPU test can hold: published widths, two layers
per stack, batch 4, meta batch 2, 32 tokens (64 encoder frames).

The encoder cell's tokens are drawn from its first 64 ids. Drawn from all
30,522, the 64 tokens of a meta batch at this size are almost never in the
base batches; their embedding rows are cold in Adam (no gradient yet, so
the adaptation diagonal is lr over Adam's epsilon), the perturbation ``v``
lies almost wholly on rows the base loss never reads, and the reference's
hypergradient comes out exactly nought: the meta level would be dead, and
no fault in it could show. At the cell's own size it is alive (PERF.md)."""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import manifest  # noqa: E402
import run  # noqa: E402

DEPTH = {"bert-base.wrench-s128": {"num_layers": 2},
         "whisper-small.asr-30s": {"num_layers": 2, "encoder_layers": 2, "encoder_seq": 64}}
TOKENS_HIGH = {"bert-base.wrench-s128": 64}
SEED = 2 ** 33 + 11


def small_cell(workload):
    """(cell, config, mix, limits) of ``workload`` cut to the CPU size."""
    cell = manifest.cell(manifest.load(ROOT), workload)
    config = manifest.config(cell["config"])
    changed = dict(config["changed"], **DEPTH[workload])
    config = dict(config, changed=changed, **changed)
    mix = manifest.traffic(cell["traffic"])
    tokens = dict(mix["inputs"]["tokens"], shape=[32])
    if workload in TOKENS_HIGH:
        tokens["high"] = TOKENS_HIGH[workload]
    mix = dict(mix, batch_per_chip=4, meta_batch_per_chip=2, trace_steps=2,
               inputs=dict(mix["inputs"], tokens=tokens))
    return cell, config, mix, manifest.limits(workload)


def run_small(workload, monkeypatch, wrap_step=None):
    """One run of the small cell with the look for a chip switched off and
    the persistent compilation cache left as the test process has it."""
    monkeypatch.setattr(run, "configure_jax", lambda: None)
    cell, config, mix, limits = small_cell(workload)
    return run.run_cell(cell, config, mix, limits, SEED, 0.5, False,
                        require_tpu=False, wrap_step=wrap_step)
