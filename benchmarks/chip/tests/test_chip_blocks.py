"""``blocks.py`` and the ``block_ms.*`` readers on hand-made operations, and
on the small recorded trace of a program from before the block scopes
(``data/small.xplane.pb``, see ``test_chip_trace_reduce.py``)."""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import blocks  # noqa: E402
import trace_reduce as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
READERS = ("attention", "mlp", "loss")


def reader(block):
    spec = importlib.util.spec_from_file_location(f"block_ms_{block}",
                                                  HERE / "metrics" / f"block_ms.{block}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def op(name, start, dur, path, opcode=""):
    return tr.Op(name, float(start), float(dur), path, opcode)


# forward and backward, inside and outside a remat'd scan, as the compiled
# step names them; times in ns over two steps
OPS = [
    op("fusion.1", 0, 4e6, "jit(meta_step)/base_unroll/while/body/closed_call/jvp(encoder)/"
                           "while/body/closed_call/attention/dot_general"),
    op("fusion.2", 4e6, 6e6, "jit(meta_step)/base_unroll/while/body/closed_call/"
                             "transpose(jvp(encoder))/while/body/closed_call/checkpoint/"
                             "rematted_computation/attention/bskgd,btkd->bkgst/dot_general"),
    op("fusion.3", 10e6, 2e6, "jit(meta_step)/local_terms/cd_passes/jvp(decoder)/while/body/"
                              "closed_call/cross_attention/dot_general"),
    op("fusion.4", 12e6, 8e6, "jit(meta_step)/local_terms/meta_pass/transpose(jvp(decoder))/"
                              "while/body/closed_call/checkpoint/mlp/dot_general"),
    op("fusion.5", 20e6, 1e6, "jit(meta_step)/base_unroll/while/body/closed_call/"
                              "transpose(jvp(loss))/mul;jit(meta_step)/base_unroll/mlp/add"),
    op("fusion.6", 21e6, 3e6, "jit(meta_step)/base_unroll/while/body/closed_call/add"),
    op("while.7", 0, 30e6, "jit(meta_step)/base_unroll/while", "while"),
    op("copy.8", 24e6, 2e6, ""),
]


def test_components_strip_transform_wrappers():
    assert blocks.components("a/transpose(jvp(loss))/jvp()/jit(_var)/mul") == \
        ["a", "loss", "", "_var", "mul"]
    assert blocks.components("a/mlp/x;b/attention/y") == ["a", "mlp", "x"]


def test_each_op_belongs_to_its_innermost_block_or_none():
    assert [blocks.block_of(o.path) for o in OPS] == \
        ["attention", "attention", "cross_attention", "mlp", "loss", None, None, None]
    assert blocks.block_of("jit(s)/mlp/moe/mlp/dot_general") == "mlp"
    assert blocks.block_of("jit(s)/attention/norm/mul") == "norm"


def test_block_time_leaves_out_containers_and_counts_nothing_twice():
    got = blocks.block_ns(OPS)
    assert got == {"attention": 10e6, "cross_attention": 2e6, "mlp": 8e6, "loss": 1e6,
                   None: 5e6}
    # every non-container op is in exactly one bucket
    assert sum(got.values()) == sum(o.dur_ns for o in OPS if not o.container)


def test_readers_give_ms_per_step_averaged_over_chips():
    ctx = {"ops": {0: OPS, 1: OPS}, "steps": 2}
    assert reader("attention")(ctx) == pytest.approx(5.0)  # never cross-attention's 2
    assert reader("mlp")(ctx) == pytest.approx(4.0)
    assert reader("loss")(ctx) == pytest.approx(0.5)


@pytest.mark.parametrize("block", READERS)
def test_reader_returns_none_where_no_op_carries_its_scope(block):
    bare = [o for o in OPS if blocks.block_of(o.path) != block]
    assert reader(block)({"ops": {0: bare}, "steps": 2}) is None
    assert reader(block)({"ops": {0: []}, "steps": 2}) is None


@pytest.mark.parametrize("block", READERS)
def test_reader_returns_none_on_a_program_without_block_scopes(block):
    ops = json.loads((DATA / "small_ops.json").read_text())
    trace = tr.load(str(DATA / "small.xplane.pb"), op_paths=ops)
    assert trace.devices[0]
    assert reader(block)({"ops": trace.devices, "steps": 2}) is None
