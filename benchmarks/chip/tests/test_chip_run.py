"""The harness end to end on the CPU, at the size of ``small_cells``.

``run.run_cell`` is driven with the look for a chip switched off. A sound
run comes out correct, and so does not the control, the reference at three
bf16 passes (``high``) put in the program's place. The learner is the one
``repro.launch.train`` builds. The harness itself refuses a CPU, and a
checkout without the program.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import check  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import sut  # noqa: E402
from small_cells import DEPTH, SEED, run_small, small_cell  # noqa: E402
from traffic import Traffic  # noqa: E402


@pytest.mark.parametrize("workload", sorted(DEPTH))
def test_sound_run_is_correct(workload, monkeypatch):
    out = run_small(workload, monkeypatch)
    assert out["correct"], out["compared"]
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"examples_per_s", "peak_hbm_gib", "setup_s"}
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_control_at_three_bf16_passes_is_not_correct():
    """The reference at ``high`` (three bf16 passes, spelt out for the CPU)
    in the program's place fails the cell's limits. The whisper cell's
    control separates only at its own size, on the chip (PERF.md)."""
    cell, config, mix, limits = small_cell("bert-base.wrench-s128")
    traffic = Traffic(mix, config, 1, SEED)
    batches = [traffic.step_batches(i) for i in range(run.CHECKED_STEPS)]
    ref = reference.readings(config, traffic.settings(), SEED, batches, steps=run.CHECKED_STEPS)
    control = reference.readings(config, traffic.settings(), SEED, batches,
                                 precision="high_emulated", steps=run.CHECKED_STEPS)
    correct, rows = check.decide(control, ref, limits)
    assert not correct, rows


def test_learner_is_train_builds():
    """The harness's learner is the one ``train.build`` makes for the same
    flags: same settings, and one step from the same state and batch gives
    the same result."""
    sut.import_program()
    import jax
    import numpy as np
    from repro import obs
    from repro.launch import train

    _, config, mix, _ = small_cell("bert-base.wrench-s128")
    smoke = {"num_layers": 2, "d_model": 128, "num_heads": 2, "num_kv_heads": 2, "head_dim": 64,
             "d_ff": 256, "vocab_size": 512, "max_position": 128}
    config = dict(config, changed=dict(smoke, param_dtype="float32", dtype="float32"), **smoke)
    cfg, _, mine = sut.build_learner(config, mix, 1)
    args = train.parse_args(["--arch", "bert-base", "--smoke", "--unroll", str(mix["unroll"]),
                             "--method", mix["method"], "--base-lr", str(mix["base_lr"]),
                             "--meta-lr", str(mix["meta_lr"]), "--precision", config["policy"]])
    theirs = train.build(args, obs.NULL_OBS)
    assert cfg == theirs.cfg
    ref = theirs.learner
    assert (mine.schedule, mine.cfg) == (ref.schedule, ref.cfg)
    assert (mine.base_opt.name, mine.meta_opt.name) == (ref.base_opt.name, ref.meta_opt.name)
    weights = reference.init_weights(config, 3)
    mine.init(*weights)
    ref.init(*weights)
    base, meta = Traffic(mix, config, 1, 3).step_batches(0)
    assert jax.device_get(mine.step(base, meta)) == jax.device_get(ref.step(base, meta))
    for x, y in zip(jax.tree_util.tree_leaves(mine.state), jax.tree_util.tree_leaves(ref.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _harness(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "bert-base.wrench-s128",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_on_cpu_is_refused():
    res = _harness(ROOT)
    assert res.returncode == 2, res.stderr[-2000:]
    assert res.stdout == ""
    assert "no TPU" in res.stderr


def test_checkout_without_the_program_fails(tmp_path):
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    res = _harness(tmp_path)
    assert res.returncode != 0
    assert res.stdout == ""
    assert json.loads((tmp_path / "BENCHMARK.json").read_text())["workloads"]
