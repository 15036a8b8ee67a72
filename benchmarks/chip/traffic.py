"""The one traffic generator: reads a mix file and makes each step's batches.

A mix file (``traffic/<mix>.json``) gives the batch per chip, the unroll
length, the meta batch per chip, the optimizers and the schedule, and an
``inputs`` table. Each input names its distribution and its per-example
shape; a size in a shape may be a number or a key of the configuration
file (``"encoder_seq"``, ``"d_model"``), and an integer input's upper
bound may be one too (``"vocab_size"``, ``"num_labels"``).

Distributions: ``uniform_int`` (``low`` .. ``high`` - 1) and ``normal``
(mean 0, ``std``, float32).

Batches are numpy arrays made on the host, as a data loader hands them
over. Step ``i`` of seed ``s`` is drawn from its own generator, seeded by
``(s, i)``, so the reference can make any step's batch again, and every
seed draws the same sizes.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

Batch = Dict[str, np.ndarray]


def _size(x, config) -> int:
    return int(config[x]) if isinstance(x, str) else int(x)


class Traffic:
    def __init__(self, mix: Dict[str, Any], config: Dict[str, Any], chips: int, seed: int):
        self.mix = mix
        self.config = config
        self.chips = chips
        self.seed = int(seed)
        self.batch = mix["batch_per_chip"] * chips
        self.meta_batch = mix["meta_batch_per_chip"] * chips
        self.unroll = mix["unroll"]

    def settings(self) -> Dict[str, Any]:
        """What the reference needs of the mix."""
        m = self.mix
        return {"unroll": m["unroll"], "base_lr": m["base_lr"], "meta_lr": m["meta_lr"],
                "alpha": m["alpha"], "schedule": m["schedule"], "chips": self.chips}

    def _draw(self, rng: np.random.Generator, lead: Tuple[int, ...]) -> Batch:
        out = {}
        for name in sorted(self.mix["inputs"]):
            spec = self.mix["inputs"][name]
            shape = lead + tuple(_size(s, self.config) for s in spec.get("shape", []))
            if spec["dist"] == "uniform_int":
                out[name] = rng.integers(_size(spec.get("low", 0), self.config),
                                         _size(spec["high"], self.config),
                                         size=shape, dtype=np.int32)
            elif spec["dist"] == "normal":
                out[name] = rng.standard_normal(shape, dtype=np.float32) * np.float32(spec["std"])
            else:
                raise ValueError(f"input {name!r}: unknown distribution {spec['dist']!r}")
        return out

    def step_batches(self, i: int) -> Tuple[Batch, Batch]:
        """(base batches with a leading unroll axis, meta batch) of step ``i``."""
        rng = np.random.default_rng((self.seed, i))
        return self._draw(rng, (self.unroll, self.batch)), self._draw(rng, (self.meta_batch,))

    def examples_per_step(self) -> int:
        """Base-level training examples one meta step consumes."""
        return self.unroll * self.batch
