"""The comparison that decides ``correct``.

Both sides give the same readings (``reference.readings`` for the
reference, ``program_readings`` in ``run.py`` for the program): each
checked step's base loss, meta loss, hypergradient norm and step size; the
per-leaf norms of both Adam first moments after step 1 (the first
gradients as the optimizers got them) and of the change of theta and of
lam over step 1; and, where more than one step was checked, the per-leaf
norms of the change of theta and lam over all of them.

The numbers, each compared against its limit in ``limits/<cell>.json``:

* ``loss_gap``, ``hypergrad_gap``, ``eps_gap``: the largest relative gap
  of a base or meta loss, of a hypergradient norm, of a step size; with
  ``.s<n>`` of step ``n`` alone, without it over every checked step;
* ``base_moment_gap``, ``meta_moment_gap``, ``theta_change1_gap``,
  ``lam_change1_gap``, ``theta_change_gap``, ``lam_change_gap``: the worst
  leaf's gap between the program's norm and the reference's, over the
  larger of the reference's norm of that leaf and of the median leaf.

Leaves whose first moment in the reference is under a thousandth of the
median leaf's (a gradient that is nought to rounding) are left out of the
change: Adam moves them by round-off alone.

A run computes only the numbers its limits name; ``readings.py`` computes
every one its readings allow.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence, Tuple

DEAD_LEAF = 1e-3

#: per-step numbers: the step metrics each one takes the largest gap of
STEP_NUMBERS = {"loss_gap": ("base_loss", "meta_loss"),
                "hypergrad_gap": ("hypergrad_norm",),
                "eps_gap": ("eps",)}

#: per-leaf numbers: (the reading, the first moment whose live leaves count)
LEAF_NUMBERS = {"base_moment_gap": ("base_moment", None),
                "meta_moment_gap": ("meta_moment", None),
                "theta_change1_gap": ("theta_change1", "base_moment"),
                "lam_change1_gap": ("lam_change1", "meta_moment"),
                "theta_change_gap": ("theta_change", "base_moment"),
                "lam_change_gap": ("lam_change", "meta_moment")}


def _rel(p: float, r: float) -> float:
    return abs(p - r) / max(abs(r), 1e-30)


def leaf_gap(prog: Sequence[float], ref: Sequence[float],
             keep: Optional[Sequence[bool]] = None) -> float:
    if len(prog) != len(ref):
        return float("inf")
    idx = [i for i in range(len(ref)) if keep is None or keep[i]]
    med = statistics.median(ref[i] for i in idx)
    return max(abs(prog[i] - ref[i]) / max(ref[i], med, 1e-30) for i in idx)


def live(moment: Sequence[float]) -> List[bool]:
    med = statistics.median(moment)
    return [m >= DEAD_LEAF * med for m in moment]


def number(name: str, prog: Dict, ref: Dict) -> float:
    """One number of the comparison, by its name."""
    base, _, step = name.partition(".s")
    if base in STEP_NUMBERS:
        steps = [int(step) - 1] if step else range(len(ref["metrics"]))
        return max(_rel(prog["metrics"][s][k], ref["metrics"][s][k])
                   for s in steps for k in STEP_NUMBERS[base])
    reading, moment = LEAF_NUMBERS[name]
    return leaf_gap(prog[reading], ref[reading], live(ref[moment]) if moment else None)


def gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Every number the two sides' readings allow: per step (``.s1`` ...)
    and over all checked steps."""
    names = [f"{k}.s{s + 1}" for k in STEP_NUMBERS for s in range(len(ref["metrics"]))]
    names += list(STEP_NUMBERS)
    names += [k for k, (reading, _) in LEAF_NUMBERS.items() if reading in ref and reading in prog]
    return {name: number(name, prog, ref) for name in names}


def decide(prog: Dict, ref: Dict, limits: Dict[str, float]) -> Tuple[bool, List[Tuple[str, float, float]]]:
    """(correct, [(name, value, limit), ...]) over the numbers ``limits``
    names. A number that is not finite fails its limit."""
    rows = [(name, number(name, prog, ref), float(lim)) for name, lim in limits.items()]
    ok = all(v == v and v <= lim for _, v, lim in rows)
    return ok, rows
