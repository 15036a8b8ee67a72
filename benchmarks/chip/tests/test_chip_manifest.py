"""BENCHMARK.json and the files it names: names, units, the files each
entry needs, and that a new configuration, traffic mix and per-layer
metric are found from files alone."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import manifest  # noqa: E402
from traffic import Traffic  # noqa: E402

BENCH = manifest.load(ROOT)
SECTIONS = ("end_to_end", "per_layer")


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert len(json.dumps(BENCH)) <= 64 * 1024


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_are_allowed_and_unique(section):
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))
    for n in names:
        assert manifest.NAME.match(n), n


@pytest.mark.parametrize("section", SECTIONS)
def test_metric_units_and_sources(section):
    for m in BENCH[section]:
        assert manifest.UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        if section == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
            assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
            assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


def test_every_cell_has_its_files_and_reports_enough():
    cells = {w["name"] for w in BENCH["workloads"]}
    pairs = set()
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        manifest.config(w["config"])
        manifest.traffic(w["traffic"])
        assert manifest.limits(w["name"])
        e2e = {m["name"] for m in manifest.metrics_of(BENCH, w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert manifest.metrics_of(BENCH, w["name"], "per_layer")
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
        assert callable(manifest.reader(m["name"]))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(cells) // 2)


def test_configs_name_their_files_and_reductions():
    for c in BENCH["configs"]:
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        body = manifest.config(c["name"])
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert manifest.NAME.match(key) and not key.endswith(("_dim", "_rank"))
            assert key in body


def _copy_benchmark(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_new_config_mix_and_metric_are_found_from_files_alone(tmp_path):
    root = _copy_benchmark(tmp_path)
    here = root / "benchmarks" / "chip"
    cfg = dict(manifest.config("bert-base"), registry="bert-base", num_layers=2,
               changed={"num_layers": 2})
    (here / "configs" / "bert-2l.json").write_text(json.dumps(cfg))
    mix = dict(manifest.traffic("wrench-s128"), batch_per_chip=4, meta_batch_per_chip=2)
    mix["inputs"] = {"tokens": {"dist": "uniform_int", "high": "vocab_size", "shape": [256]},
                     "y": {"dist": "uniform_int", "high": "num_labels"}}
    (here / "traffic" / "wrench-s256.json").write_text(json.dumps(mix))
    (here / "limits" / "bert-2l.wrench-s256.json").write_text(
        json.dumps({"limits": {"loss_gap.s1": 1e-5}}))
    (here / "metrics" / "steps_traced.py").write_text("def read(ctx):\n    return ctx['steps']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "bert-2l.wrench-s256", "config": "bert-2l",
                               "traffic": "wrench-s256", "chips": 1, "why": "drop-in"})
    bench["per_layer"].append({"name": "steps_traced", "unit": "steps", "better": "higher",
                               "source": "device_trace", "layer": "device",
                               "moves": "examples_per_s", "workloads": ["bert-2l.wrench-s256"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    loaded = manifest.load(root)
    cell = manifest.cell(loaded, "bert-2l.wrench-s256")
    config = manifest.config(cell["config"], here)
    traffic = manifest.traffic(cell["traffic"], here)
    assert manifest.limits(cell["name"], here) == {"loss_gap.s1": 1e-5}
    names = [m["name"] for m in manifest.metrics_of(loaded, cell["name"], "per_layer")]
    assert "steps_traced" in names
    assert manifest.reader("steps_traced", here)({"steps": 7}) == 7
    base, meta = Traffic(traffic, config, 1, 3).step_batches(0)
    assert base["tokens"].shape == (2, 4, 256) and meta["tokens"].shape == (2, 256)
    assert base["tokens"].max() < config["vocab_size"]


def test_traffic_is_a_function_of_the_seed():
    config, mix = manifest.config("whisper-small"), manifest.traffic("asr-30s")
    mix = dict(mix, inputs=dict(mix["inputs"], frames=dict(mix["inputs"]["frames"], shape=[4, 8])))
    a = Traffic(mix, config, 1, 2 ** 33 + 1).step_batches(5)
    b = Traffic(mix, config, 1, 2 ** 33 + 1).step_batches(5)
    c = Traffic(mix, config, 1, 1).step_batches(5)
    for x, y, z in zip(a[0].values(), b[0].values(), c[0].values()):
        assert (x == y).all() and not (x == z).all()
    assert a[0]["frames"].dtype.name == "float32" and a[0]["frames"].shape == (2, 8, 4, 8)


def test_unknown_device_kind_has_no_peaks():
    assert manifest.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        manifest.peaks("TPU v9 imaginary")
