"""The four-chip path on four virtual CPU devices, at a small size: the
``wrench-s128-x4`` mix on the single-sync schedule, the cell that
``PERF.md`` leaves for a later PR, with its limits file.

Run in a child process, which sets ``XLA_FLAGS`` before JAX is imported.
A sound run of the single-sync schedule on distinct shards agrees with the
reference's per-shard average, and the same run with the exchange between
chips left out (the flat bucket's mean replaced by each chip's own terms)
comes out not correct.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]

CHILD = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import manifest, run, sut
run.configure_jax = lambda: None
sut.import_program()
if sys.argv[2] == "no_exchange":
    from repro.launch import distributed
    distributed.flat_pmean = lambda tree, axes: tree
SMALL = {"num_layers": 2, "d_model": 128, "num_heads": 2, "num_kv_heads": 2, "head_dim": 64,
         "d_ff": 256, "vocab_size": 512, "max_position": 128}
cell = {"name": "bert-base.wrench-s128.x4", "config": "bert-base", "traffic": "wrench-s128-x4",
        "chips": 4}
config = dict(manifest.config(cell["config"]), changed=SMALL, **SMALL)
mix = manifest.traffic(cell["traffic"])
mix = dict(mix, batch_per_chip=4, meta_batch_per_chip=2,
           inputs=dict(mix["inputs"], tokens=dict(mix["inputs"]["tokens"], shape=[16])))
out = run.run_cell(cell, config, mix, manifest.limits(cell["name"]), 2 ** 33 + 5, 0.5, False,
                   require_tpu=False)
print(json.dumps({"correct": out["correct"], "compared": out["compared"]}))
"""


@pytest.mark.parametrize("mode", ["sound", "no_exchange"])
def test_single_sync_on_four_devices(mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    res = subprocess.run([sys.executable, "-c", CHILD, str(HERE), mode], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["correct"] == (mode == "sound"), out["compared"]
