"""``BENCHMARK.json`` and the files it names, found by name.

A cell's configuration is ``configs/<config>.json``, its traffic mix
``traffic/<traffic>.json``, its correctness limits ``limits/<cell>.json``,
and each per-layer metric's reader ``metrics/<metric>.py``. Adding a
configuration, a mix or a metric is adding files and an entry in
``BENCHMARK.json``; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Any, Callable, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load(root: Path = ROOT) -> Dict[str, Any]:
    return _json(root / "BENCHMARK.json")


def cell(bench: Dict[str, Any], workload: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str, here: Path = HERE) -> Dict[str, Any]:
    return _json(here / "configs" / f"{name}.json")


def traffic(name: str, here: Path = HERE) -> Dict[str, Any]:
    return _json(here / "traffic" / f"{name}.json")


def limits(workload: str, here: Path = HERE) -> Dict[str, float]:
    return _json(here / "limits" / f"{workload}.json")["limits"]


def peaks(device_kind: str, here: Path = HERE) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    raises."""
    table = _json(here / "peaks.json")["devices"]
    if device_kind not in table:
        raise ValueError(f"no published peaks for device kind {device_kind!r}; "
                         f"known: {sorted(table)}")
    return table[device_kind]


def metrics_of(bench: Dict[str, Any], workload: str, section: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[section] if workload in m.get("workloads", [workload])]


def reader(metric: str, here: Path = HERE) -> Callable[[Dict[str, Any]], Any]:
    """``read(ctx)`` of ``metrics/<metric>.py``."""
    path = here / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"chip_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
