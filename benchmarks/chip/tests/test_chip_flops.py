"""``flops.py`` against XLA's own count of the compiled meta step.

The step is compiled on the CPU at d_model 256, one layer per stack,
unroll 1 and no remat, so that ``cost_analysis()`` sees every matrix
product once: it counts a loop body once, whatever its trip count, and it
counts recomputation. It also counts the elementwise work (norms, softmax,
GELU, both Adams, the adaptation product), which ``flops.py`` leaves out as
model FLOPs do; at this width that is under 5% of the total. So the model
count has to lie within [0.95, 1.0] of XLA's.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import flops  # noqa: E402
import manifest  # noqa: E402
import reference  # noqa: E402
import sut  # noqa: E402
from traffic import Traffic  # noqa: E402

SMALL = {"num_layers": 1, "d_model": 256, "num_heads": 4, "num_kv_heads": 4, "head_dim": 64,
         "d_ff": 1024, "vocab_size": 512, "max_position": 128, "remat": False}
CASES = {"bert-base": ("wrench-s128", {}),
         "whisper-small": ("asr-30s", {"encoder_layers": 1, "encoder_seq": 64})}


def small_cell(name):
    mix_name, extra = CASES[name]
    sizes = dict(SMALL, **extra)
    config = dict(manifest.config(name), changed=sizes, **sizes)
    mix = manifest.traffic(mix_name)
    mix = dict(mix, unroll=1, batch_per_chip=4, meta_batch_per_chip=2,
               inputs=dict(mix["inputs"], tokens=dict(mix["inputs"]["tokens"], shape=[64])))
    return config, mix


@pytest.mark.parametrize("name", sorted(CASES))
def test_step_flops_match_xla_count(name):
    sut.import_program()
    config, mix = small_cell(name)
    _, _, learner = sut.build_learner(config, mix, 1)
    learner.init(*reference.init_weights(config, 0))
    base, meta = Traffic(mix, config, 1, 0).step_batches(0)
    xla = learner.step_fn.lower(learner.state, base, meta).compile().cost_analysis()["flops"]
    ratio = flops.step_flops(config, mix, 1) / xla
    assert 0.95 <= ratio <= 1.0, ratio


def test_step_flops_scale_with_the_mix():
    config, mix = manifest.config("bert-base"), manifest.traffic("wrench-s128")
    fwd = flops.forward_flops(config, mix)
    # 2 base passes x 32 and a meta pass x 16 forward and backward, two
    # forward central-difference passes x 32
    assert flops.step_flops(config, mix, 1) == fwd * (3 * 2 * 32 + 3 * 16 + 2 * 32)
    assert flops.step_flops(config, mix, 4) == 4 * flops.step_flops(config, mix, 1)
    # about 176 MFLOP per token forward at bert-base width and 128 tokens
    assert 170e6 < fwd / 128 < 180e6


def test_adam_adapt_cost_counts_padded_leaves():
    cost = flops.adam_adapt_cost(manifest.config("bert-base"))
    assert cost["calls"] == 16
    n = cost["bytes"] / 20
    assert 108_810_244 <= n < 108_810_244 + 16 * 512 * 128
    assert cost["flops"] == flops.ADAM_ADAPT_OPS * n
