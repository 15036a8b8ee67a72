"""``trace_reduce`` on hand-made operations and on a small trace recorded on
a TPU v5e chip (``data/small.xplane.pb``: two meta steps of a two-layer,
d_model 128 encoder under the wrench-s128 mix at batch 4 and 16 tokens,
profiled by the harness's own ``drive``), with the HLO op name to
``op_name`` table of the program it ran (``data/small_ops.json``) and the
HLO op name to kernel source files table of its Mosaic calls
(``data/small_kernels.json``)."""

from __future__ import annotations

import base64
import importlib.util
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import trace_reduce as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def op(name, start, dur, path="", kernel=()):
    return tr.Op(name, float(start), float(dur), path, kernel=tuple(kernel))


def adam_adapt_reader():
    spec = importlib.util.spec_from_file_location("adam_adapt_roofline",
                                                  HERE / "metrics" / "adam_adapt_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


OPS = [
    op("fusion.1", 0, 10, "jit(meta_step)/base_unroll/while/body/dot_general"),
    op("fusion.2", 5, 10, "jit(meta_step)/local_terms/meta_pass/pallas_call"),
    op("all-reduce.3", 12, 10, "jit(meta_step)/allreduce_flat/psum"),
    op("fusion.4", 40, 5, "jit(meta_step)/local_terms/cd_passes/mul"),
    op("copy.5", 50, 10, ""),
]


def test_busy_is_the_union_of_intervals():
    assert tr.busy_ns(OPS) == 22 + 5 + 10
    assert tr.window_ns(OPS) == 60


def test_time_per_scope_takes_the_innermost_phase():
    assert tr.scope_ns(OPS) == {"base_unroll": 10, "meta_pass": 10, "allreduce_flat": 10,
                                "cd_passes": 5, "other": 10}
    assert tr.phase_of("a/local_terms/meta_pass/b") == "meta_pass"
    assert tr.phase_of("a/b") is None


def test_matched_and_exposed_time():
    is_ar = lambda o: o.name.startswith("all-reduce")
    assert tr.match_ns(OPS, is_ar) == 10
    # fusion.2 covers 12..15 of the all-reduce's 12..22
    assert tr.exposed_ns(OPS, is_ar) == 7


def test_idle_gaps_are_named_by_the_host_span_over_them():
    host = [op("make_batch", 20, 25), op("wait", 46, 3)]
    gaps = tr.idle_gaps(OPS, host)
    assert gaps[0] == ("make_batch", 18e-9)
    assert gaps[1] == ("wait", 5e-9)


def test_op_paths_from_hlo_text():
    text = ('  %fusion.7 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
            'calls=%fused_computation.7, metadata={op_name="jit(f)/base_unroll/add" '
            'source_file="x.py" source_line=3}\n'
            '  ROOT %tuple.9 = (f32[4]{0}) tuple(%fusion.7)\n')
    assert tr.op_paths_from_hlo(text) == {"fusion.7": "jit(f)/base_unroll/add"}


def _mosaic_call(name, scope, sources):
    """One compiled ``tpu_custom_call`` line as a TPU compile writes it: the
    kernel is a base64 Mosaic module whose locations name its source files."""
    body = base64.b64encode(b"MLIR\x00stable_mosaic\x00" + b"\x00".join(
        f"src/repro/{s}.py".encode() for s in sources) + b"\x00main").decode()
    return (f'  %{name} = (f32[8,128]{{1,0}}) custom-call(f32[8,128]{{1,0}} %p), '
            f'custom_call_target="tpu_custom_call", metadata={{op_name="jit(meta_step)/'
            f'local_terms/{scope}/pallas_call" stack_frame_id=3}}, backend_config='
            f'{{"flag_configs":[],"custom_call_config":{{"body":"{body}",'
            f'"needs_layout_passes":true}}}}')


def test_kernels_from_hlo_names_each_mosaic_call_by_its_sources():
    text = "\n".join([
        _mosaic_call("meta_pass.31", "meta_pass", ["kernels/adam_adapt", "kernels/flat"]),
        _mosaic_call("jvp__.2", "meta_pass/jvp()", ["kernels/weighted_ce", "models/heads"]),
        '  %fusion.7 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
        'metadata={op_name="jit(f)/meta_pass/add"}',
    ])
    assert tr.kernels_from_hlo(text) == {"meta_pass.31": ("adam_adapt", "flat"),
                                         "jvp__.2": ("heads", "weighted_ce")}


def test_adam_adapt_roofline_counts_its_own_kernel_alone():
    """A second Pallas kernel under ``meta_pass`` (the meta loss's weighted
    cross-entropy) does not count as the adaptation kernel's time."""
    reader = adam_adapt_reader()
    ops = [op("meta_pass.31", 0, 2e6, "jit(meta_step)/local_terms/meta_pass/pallas_call",
              ("adam_adapt", "flat")),
           op("jvp__.2", 3e6, 5e6, "jit(meta_step)/local_terms/meta_pass/jvp()/pallas_call",
              ("weighted_ce",)),
           op("fusion.4", 9e6, 1e6, "jit(meta_step)/local_terms/meta_pass/mul")]
    ctx = {"adam_adapt": {"bytes": 819e9 * 1e-3, "flops": 0.0},
           "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
           "ops": {0: ops}, "steps": 1}
    assert reader.read(ctx) == pytest.approx(50.0)
    # a trace in which no op runs the kernel gives nothing, never 0
    assert reader.read(dict(ctx, ops={0: ops[1:]})) is None


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "small.xplane.pb"
    if not path.exists():
        pytest.fail(f"missing recorded trace {path}")
    ops = json.loads((DATA / "small_ops.json").read_text())
    kernels = json.loads((DATA / "small_kernels.json").read_text())
    return tr.load(str(path), op_paths=ops, host_names=("make_batch", "dispatch", "wait"),
                   kernels=kernels)


def test_recorded_trace_has_one_busy_chip(recorded):
    assert list(recorded.devices) == [0]
    ops = recorded.devices[0]
    busy, window = tr.busy_ns(ops), tr.window_ns(ops)
    assert 0 < busy <= window
    assert {h.name for h in recorded.host} >= {"make_batch", "dispatch", "wait"}


def test_recorded_trace_splits_into_the_engine_phases(recorded):
    ops = recorded.devices[0]
    scopes = tr.scope_ns(ops)
    for phase in ("base_unroll", "meta_pass", "cd_passes"):
        assert scopes.get(phase, 0) > 0, scopes
    # operations inside one program do not overlap, so the scopes (while
    # loops left out) add up to no more than the busy time
    assert 0.9 * tr.busy_ns(ops) <= sum(scopes.values()) <= tr.busy_ns(ops) * 1.0001
    assert scopes["base_unroll"] == max(scopes.values())


def test_recorded_trace_finds_the_adaptation_kernel(recorded):
    ops = recorded.devices[0]
    kernel = [o for o in ops if adam_adapt_reader().is_kernel(o)]
    # one call per parameter leaf of the two-layer encoder, per traced step,
    # each under the meta pass
    assert kernel and len(kernel) % 16 == 0
    assert all(tr.phase_of(o.path) == "meta_pass" for o in kernel)
    assert tr.match_ns(ops, adam_adapt_reader().is_kernel) > 0
