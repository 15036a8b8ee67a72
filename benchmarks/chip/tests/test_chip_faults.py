"""A run whose timed step is broken underneath comes out not correct, once
for each fault a one-chip training cell can have: a step that returns its
state unchanged; one that leaves out half of each batch and takes the mean
over the rest; one that leaves the meta level (lam and its Adam state)
unchanged; and one whose central-difference passes are skipped, so that the
hypergradient is nought. The harness runs as on the chip, with its look for
a chip switched off, at the CPU size of ``small_cells``."""

from __future__ import annotations

import pytest

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from small_cells import DEPTH, run_small  # noqa: E402


def unchanged_state(monkeypatch):
    def wrap(learner):
        def step(base, meta):
            before = learner.state
            metrics = learner.step(base, meta)
            learner.state = before
            return metrics
        return step
    return wrap


def half_batch(monkeypatch):
    def wrap(learner):
        def step(base, meta):
            base = {k: v[:, : v.shape[1] // 2] for k, v in base.items()}
            meta = {k: v[: v.shape[0] // 2] for k, v in meta.items()}
            return learner.step(base, meta)
        return step
    return wrap


def unchanged_meta_level(monkeypatch):
    def wrap(learner):
        def step(base, meta):
            before = learner.state
            metrics = learner.step(base, meta)
            learner.state = learner.state._replace(lam=before.lam,
                                                   meta_opt_state=before.meta_opt_state)
            return metrics
        return step
    return wrap


def zero_hypergradient(monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.core import sama

    def skipped(spec, theta, lam, base_batch, v, *, cfg, v_sumsq=None, loss_scale=None):
        return (jax.tree_util.tree_map(jnp.zeros_like, lam),
                sama.step_size(v, v_sumsq, cfg))

    monkeypatch.setattr(sama, "central_difference_hypergrad", skipped)
    return None


@pytest.mark.parametrize("fault", [unchanged_state, half_batch, unchanged_meta_level,
                                   zero_hypergradient], ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload", sorted(DEPTH))
def test_broken_step_is_not_correct(workload, fault, monkeypatch):
    out = run_small(workload, monkeypatch, wrap_step=fault(monkeypatch))
    assert not out["correct"], out["compared"]
