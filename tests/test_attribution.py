"""repro.obs.profile / repro.obs.diff pins (ISSUE 8).

Three layers:

* hand-built HLO fixtures whose right answers are computable on paper —
  the FLOP model, innermost-phase matching, trip-count scaling through
  fusions called from scanned bodies, fusion-boundary byte accounting,
  per-phase collectives, and the entry liveness watermark;
* the schema (``perf.record.validate_attribution``) and the per-phase
  gate bands (``attribution.{phase}.flops`` / ``.wall_us``);
* real compiled steps: the acceptance pins (coverage >= 0.90 on the
  SAMA step, single-device and manual 8-device schedule, with
  ``models/attention.py`` the top FLOP sink on transformer configs) and
  the family smokes (gemma / qwen-moe / whisper) asserting phase FLOP
  fractions sum to ~1.

Plus the diff CLI: an injected phase regression must rank top.
"""

import json
import re
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, obs as obs_mod, optim
from repro.core import EngineConfig, init_state, make_meta_step, problems
from repro.models import Model
from repro.obs import diff as diff_mod
from repro.obs import events as events_mod
from repro.obs import profile as profile_mod
from repro.obs import report as report_mod
from repro.perf import gate as gate_mod
from repro.perf.record import validate_attribution

# ---------------------------------------------------------------------------
# synthetic HLO: every number below is hand-computable
# ---------------------------------------------------------------------------

# Entry runs a while loop (trip 3) whose body calls a fused dot
# (2*8*4*16 = 1024 FLOPs, x3 = 3072) and a reduce (8*16 = 128, x3 = 384),
# then a meta dot nested under local_terms/meta_pass (innermost wins:
# 2*4*4*16 = 512), a cd multiply (128), an all-reduce (f32[128] = 512 B),
# an UNannotated add (128 -> "other") and the finalize root add (128).
# The while condition contributes 1 unannotated compare FLOP.
SYN = """\
HloModule syn_step

%fused_computation.1 (fp0: f32[8,16], fp1: f32[16,4]) -> f32[8,4] {
  %fp0 = f32[8,16] parameter(0)
  %fp1 = f32[16,4] parameter(1)
  ROOT %fdot = f32[8,4] dot(f32[8,16] %fp0, f32[16,4] %fp1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/base_unroll/scan/mm" source_file="/repo/src/repro/models/attention.py" source_line=10}
}

%wcond (pc: (f32[8,16], f32[16,4])) -> pred[] {
  %pc = (f32[8,16], f32[16,4]) parameter(0)
  ROOT %lt = pred[] compare(f32[] %z, f32[] %z), direction=LT
}

%wbody (p: (f32[8,16], f32[16,4])) -> (f32[8,16], f32[16,4]) {
  %p = (f32[8,16], f32[16,4]) parameter(0)
  %g0 = f32[8,16] get-tuple-element((f32[8,16], f32[16,4]) %p), index=0, metadata={op_name="jit(step)/base_unroll/scan" source_file="/repo/src/repro/core/engine.py" source_line=1}
  %g1 = f32[16,4] get-tuple-element((f32[8,16], f32[16,4]) %p), index=1, metadata={op_name="jit(step)/base_unroll/scan" source_file="/repo/src/repro/core/engine.py" source_line=1}
  %fu = f32[8,4] fusion(f32[8,16] %g0, f32[16,4] %g1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/base_unroll/scan/mm" source_file="/repo/src/repro/models/attention.py" source_line=10}
  %red = f32[8] reduce(f32[8,16] %g0, f32[] %c0), dimensions={1}, metadata={op_name="jit(step)/base_unroll/scan/sum" source_file="/repo/src/repro/models/mlp.py" source_line=5}
  ROOT %rt = (f32[8,16], f32[16,4]) tuple(f32[8,16] %g0, f32[16,4] %g1), metadata={op_name="jit(step)/base_unroll/scan" source_file="/repo/src/repro/core/engine.py" source_line=1}
}

ENTRY %syn_step.main (a: f32[8,16], w: f32[16,4], m: f32[128]) -> f32[8,16] {
  %a = f32[8,16] parameter(0)
  %w = f32[16,4] parameter(1)
  %m = f32[128] parameter(2)
  %t0 = (f32[8,16], f32[16,4]) tuple(f32[8,16] %a, f32[16,4] %w), metadata={op_name="jit(step)/base_unroll" source_file="/repo/src/repro/core/engine.py" source_line=1}
  %loop = (f32[8,16], f32[16,4]) while((f32[8,16], f32[16,4]) %t0), condition=%wcond, body=%wbody, backend_config={"known_trip_count":{"n":"3"}}, metadata={op_name="jit(step)/base_unroll/scan" source_file="/repo/src/repro/core/engine.py" source_line=1}
  %g = f32[8,16] get-tuple-element((f32[8,16], f32[16,4]) %loop), index=0, metadata={op_name="jit(step)/base_unroll" source_file="/repo/src/repro/core/engine.py" source_line=1}
  %md = f32[4,4] dot(f32[16,4] %w, f32[16,4] %w), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/local_terms/meta_pass/proj" source_file="/repo/src/repro/models/attention.py" source_line=20}
  %cd = f32[8,16] multiply(f32[8,16] %g, f32[8,16] %g), metadata={op_name="jit(step)/local_terms/cd_passes/mul" source_file="/repo/src/repro/core/sama.py" source_line=30}
  %ar = f32[128] all-reduce(f32[128] %m), metadata={op_name="jit(step)/allreduce_flat/ar" source_file="/repo/src/repro/launch/distributed.py" source_line=40}
  %un = f32[8,16] add(f32[8,16] %g, f32[8,16] %g)
  ROOT %out = f32[8,16] add(f32[8,16] %cd, f32[8,16] %un), metadata={op_name="jit(step)/finalize/out" source_file="/repo/src/repro/core/engine.py" source_line=50}
}
"""


def test_synthetic_flops_per_phase_hand_computed():
    attr = profile_mod.attribute(SYN)
    ph = attr["phases"]
    assert ph["base_unroll"]["flops"] == 3072 + 384
    assert ph["meta_pass"]["flops"] == 512      # innermost beats local_terms
    assert "local_terms" not in ph              # nothing charged to the outer scope
    assert ph["cd_passes"]["flops"] == 128
    assert ph["finalize"]["flops"] == 128
    assert ph[profile_mod.OTHER]["flops"] == 128 + 1
    assert attr["total"]["flops"] == 4353
    assert attr["coverage"] == pytest.approx(1.0 - 129 / 4353)
    fracs = sum(b["flop_frac"] for b in ph.values())
    assert fracs == pytest.approx(1.0)
    # ranked: the table iterates phases largest-FLOPs first
    assert next(iter(ph)) == "base_unroll"


def test_synthetic_modules_and_top_sink():
    attr = profile_mod.attribute(SYN)
    mods = attr["modules"]
    assert mods["attention.py"]["flops"] == 3072 + 512
    assert mods["mlp.py"]["flops"] == 384
    assert attr["top_module"] == "attention.py"
    assert mods["attention.py"]["flop_frac"] == pytest.approx(3584 / 4353)


def test_operands_printed_without_types_resolve_to_defining_types():
    # current XLA prints operands as bare names: ``dot(%a, %b)``. The
    # contraction size and operand bytes then come from each operand's
    # defining instruction, so the FLOP model reads the same numbers
    bare = re.sub(r"(\([^()]*\)|[a-z0-9]+\[[0-9,]*\]) %", "%", SYN)
    assert "dot(%fp0, %fp1)" in bare and "while(%t0)" in bare
    a, b = profile_mod.attribute(SYN), profile_mod.attribute(bare)
    for ph in ("base_unroll", "meta_pass", "cd_passes", "finalize"):
        assert b["phases"][ph]["flops"] == a["phases"][ph]["flops"]
    assert b["phases"]["meta_pass"]["bytes"] == a["phases"]["meta_pass"]["bytes"]
    assert b["modules"] == a["modules"]


def test_synthetic_collectives_charged_to_phase():
    attr = profile_mod.attribute(SYN)
    arf = attr["phases"]["allreduce_flat"]
    assert arf["collective_count"] == 1
    assert arf["collective_bytes"] == 128 * 4
    # no other phase carries collectives
    assert attr["total"]["collective_count"] == 1
    assert attr["total"]["collective_bytes"] == 512


def test_fusion_interior_traffic_not_charged():
    # renaming the fused computation so it no longer looks fused makes
    # its interior operand/result traffic count -> bytes grow, FLOPs
    # identical (the FLOP model never depended on the fusion boundary)
    unfused = SYN.replace("fused_computation.1", "computation.1")
    a, b = profile_mod.attribute(SYN), profile_mod.attribute(unfused)
    assert a["phases"]["base_unroll"]["flops"] == b["phases"]["base_unroll"]["flops"]
    assert a["phases"]["base_unroll"]["bytes"] < b["phases"]["base_unroll"]["bytes"]


def test_trip_count_scales_through_fusion_call():
    # drop the trip count -> the fused dot and body reduce count once
    once = SYN.replace(', backend_config={"known_trip_count":{"n":"3"}}', "")
    attr = profile_mod.attribute(once)
    assert attr["phases"]["base_unroll"]["flops"] == 1024 + 128


def test_phase_of_innermost_and_other():
    phases = ("base_unroll", "meta_pass", "cd_passes")
    assert profile_mod.phase_of("jit(s)/base_unroll/mm", phases) == "base_unroll"
    assert profile_mod.phase_of(
        "jit(s)/base_unroll/meta_pass/x", phases) == "meta_pass"
    assert profile_mod.phase_of("jit(s)/transpose/x", phases) == profile_mod.OTHER
    assert profile_mod.phase_of("", phases) == profile_mod.OTHER


# Watermark fixture: broadcast a big temp (4 KiB), slice it down (the
# temp dies at the slice), then a dead 32 KiB result (never used, freed
# immediately), then two chained 1 KiB ops. Liveness peaks: base_unroll
# 33792 B (slice + dead live together), meta_pass 2048 B (dead already
# freed — THE pin that dead results don't haunt later phases), finalize
# 2048 B.
WM = """\
HloModule wm

ENTRY %wm.main (p0: f32[256]) -> f32[256] {
  %p0 = f32[256] parameter(0)
  %big = f32[1024] broadcast(f32[256] %p0), dimensions={0}, metadata={op_name="jit(step)/base_unroll/b"}
  %r = f32[256] slice(f32[1024] %big), slice={[0:256]}, metadata={op_name="jit(step)/base_unroll/s"}
  %dead = f32[8192] broadcast(f32[256] %r), dimensions={0}, metadata={op_name="jit(step)/base_unroll/d"}
  %m = f32[256] multiply(f32[256] %r, f32[256] %r), metadata={op_name="jit(step)/meta_pass/m"}
  ROOT %o = f32[256] add(f32[256] %m, f32[256] %m), metadata={op_name="jit(step)/finalize/o"}
}
"""


def test_entry_watermark_liveness():
    attr = profile_mod.attribute(WM)
    ph = attr["phases"]
    assert ph["base_unroll"]["peak_live_bytes"] == 1024 + 32768
    assert ph["meta_pass"]["peak_live_bytes"] == 1024 + 1024
    assert ph["finalize"]["peak_live_bytes"] == 1024 + 1024
    assert attr["memory_source"] == "hlo_entry_walk"


def test_wall_join_computes_utilization():
    spans = [{"name": "base_unroll", "dur_us": 100.0, "traced": False},
             {"name": "base_unroll", "dur_us": 100.0, "traced": False},
             {"name": "meta_pass", "dur_us": 50.0, "traced": False},
             {"name": "meta_pass", "dur_us": 999.0, "traced": True}]

    class S:
        def __init__(self, d):
            self.__dict__.update(d)
    attr = profile_mod.attribute(SYN, spans=[S(d) for d in spans],
                                 peak_flops=1e9, n_devices=2)
    bu = attr["phases"]["base_unroll"]
    assert bu["wall_us"] == 200.0                        # traced span excluded
    assert bu["achieved_flops_per_s"] == pytest.approx(3456 / 200e-6)
    assert bu["utilization"] == pytest.approx(3456 / 200e-6 / 2e9)
    assert "wall_us" not in attr["phases"]["cd_passes"]  # no span, no join
    assert attr["wall_source"] == "tracer_runtime_spans"
    assert attr["n_devices"] == 2


# ---------------------------------------------------------------------------
# schema + gate bands
# ---------------------------------------------------------------------------


def test_validate_attribution_accepts_real_section():
    assert validate_attribution(profile_mod.attribute(SYN)) == []


def test_validate_attribution_catalogs_errors():
    assert validate_attribution([]) != []                     # not a dict
    assert any("phases" in e for e in validate_attribution({"phases": {}}))
    bad = profile_mod.attribute(SYN)
    bad["phases"]["base_unroll"]["flops"] = -1.0
    assert any(".flops" in e for e in validate_attribution(bad))
    off = profile_mod.attribute(SYN)
    off["phases"]["base_unroll"]["flop_frac"] += 0.5          # fracs no longer ~1
    assert any("sum" in e for e in validate_attribution(off))
    cov = profile_mod.attribute(SYN)
    cov["coverage"] = 1.5
    assert any("coverage" in e for e in validate_attribution(cov))
    wall = profile_mod.attribute(SYN)
    wall["phases"]["base_unroll"]["wall_us"] = 0.0
    assert any("wall_us" in e for e in validate_attribution(wall))


def _attr_record(flops=1000.0, wall_us=None):
    b = {"flops": flops, "flop_frac": 1.0}
    if wall_us is not None:
        b["wall_us"] = wall_us
    return {"name": "step",
            "attribution": {"phases": {"base_unroll": b},
                            "total": {"flops": flops}, "coverage": 1.0}}


def test_gate_attribution_flops_band_is_tight():
    tol = gate_mod.Tolerance()
    base = _attr_record(flops=1000.0)
    ok = gate_mod.compare_record("b", _attr_record(flops=1050.0), base, tol)
    assert ok == []                                           # within 1.10x
    bad = gate_mod.compare_record("b", _attr_record(flops=1200.0), base, tol)
    assert [v.metric for v in bad] == ["attribution.base_unroll.flops"]
    # improvements never fail
    assert gate_mod.compare_record("b", _attr_record(flops=10.0), base, tol) == []


def test_gate_attribution_wall_uses_time_band():
    tol = gate_mod.Tolerance()  # time_ratio 2.5
    base = _attr_record(wall_us=100.0)
    assert gate_mod.compare_record(
        "b", _attr_record(wall_us=200.0), base, tol) == []
    bad = gate_mod.compare_record("b", _attr_record(wall_us=300.0), base, tol)
    assert [v.metric for v in bad] == ["attribution.base_unroll.wall_us"]


# ---------------------------------------------------------------------------
# the diff CLI: injected regression must rank top
# ---------------------------------------------------------------------------


def _span_log(path, walls):
    """Write a run log whose phase spans have the given mean durations."""

    sink = events_mod.JsonlSink(path)
    for name, durs in walls.items():
        for d in durs:
            sink.write(events_mod.make_event(
                "span", name, data={"dur_us": float(d), "traced": False}))
    sink.close()
    return path


def test_diff_ranks_injected_phase_top(tmp_path):
    base = _span_log(str(tmp_path / "base.jsonl"),
                     {"base_unroll": [400.0, 400.0], "meta_pass": [100.0],
                      "cd_passes": [80.0]})
    cur = _span_log(str(tmp_path / "cur.jsonl"),
                    {"base_unroll": [410.0, 410.0], "meta_pass": [300.0],
                     "cd_passes": [60.0]})
    rows, unit = diff_mod.diff_paths(base, cur)
    assert unit == "us"
    assert rows[0].phase == "meta_pass"          # injected +200 beats +10
    assert rows[0].delta == pytest.approx(200.0)
    assert rows[0].ratio == pytest.approx(3.0)
    worst = diff_mod.top_regressor(rows)
    assert worst is not None and worst.phase == "meta_pass"
    text = diff_mod.render_diff(rows, unit)
    assert "top regressor is meta_pass" in text
    assert "-20us" in text                       # improvements keep their sign


def test_diff_main_fail_over_and_json(tmp_path, capsys):
    base = _span_log(str(tmp_path / "base.jsonl"), {"meta_pass": [100.0]})
    cur = _span_log(str(tmp_path / "cur.jsonl"), {"meta_pass": [300.0]})
    assert diff_mod.main([base, cur]) == 0       # report-only: no gate
    capsys.readouterr()
    assert diff_mod.main([base, cur, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["unit"] == "us"
    assert out["top_regressor"]["phase"] == "meta_pass"
    assert diff_mod.main([base, cur, "--fail-over", "50"]) == 1
    assert diff_mod.main([cur, base, "--fail-over", "50"]) == 0  # improvement
    assert diff_mod.main([base, str(tmp_path / "nope.jsonl")]) == 2


def test_diff_bench_records_prefer_wall_else_flops(tmp_path):
    with_wall = {"records": [
        {"name": "a", "attribution": {
            "phases": {"base_unroll": {"flops": 100.0, "wall_us": 5.0},
                       "meta_pass": {"flops": 50.0, "wall_us": 2.0}}}},
        {"name": "b", "attribution": {
            "phases": {"base_unroll": {"flops": 10.0, "wall_us": 1.0}}}},
    ]}
    costs, unit = diff_mod.phase_costs_from_bench(with_wall)
    assert unit == "us" and costs == {"base_unroll": 6.0, "meta_pass": 2.0}
    no_wall = {"records": [{"name": "a", "attribution": {
        "phases": {"base_unroll": {"flops": 100.0}}}}]}
    costs, unit = diff_mod.phase_costs_from_bench(no_wall)
    assert unit == "flops" and costs == {"base_unroll": 100.0}


def test_diff_refuses_unit_mismatch(tmp_path):
    jl = _span_log(str(tmp_path / "a.jsonl"), {"meta_pass": [100.0]})
    bench = tmp_path / "b.json"
    bench.write_text(json.dumps({"records": [{"name": "x", "attribution": {
        "phases": {"meta_pass": {"flops": 9.0}}}}]}))
    with pytest.raises(ValueError, match="cannot diff"):
        diff_mod.diff_paths(jl, str(bench))
    assert diff_mod.main([jl, str(bench)]) == 2


def test_report_diff_hook(tmp_path, capsys):
    base = _span_log(str(tmp_path / "base.jsonl"), {"meta_pass": [100.0]})
    cur = _span_log(str(tmp_path / "cur.jsonl"), {"meta_pass": [250.0]})
    assert report_mod.main([cur, "--diff", base, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["diff"]["unit"] == "us"
    assert out["diff"]["phases"][0]["phase"] == "meta_pass"
    assert report_mod.main([cur, "--diff", base]) == 0
    assert "top regressor is meta_pass" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# real compiled steps: the ISSUE acceptance pins
# ---------------------------------------------------------------------------


def _mini_bert_problem():
    cfg = configs.get_smoke_config("bert-base").replace(
        d_model=128, num_layers=2, num_labels=4, num_heads=2, num_kv_heads=2,
        head_dim=64, d_ff=256, remat=False)
    model = Model(cfg)
    spec = problems.make_data_optimization_spec(model.classifier_per_example,
                                                reweight=True)
    theta = model.init(jax.random.PRNGKey(0))
    lam = problems.init_data_optimization_lam(jax.random.PRNGKey(1),
                                              reweight=True)
    rng = np.random.default_rng(0)
    K, B, S, MB = 2, 16, 32, 8
    bb = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (K, B, S)),
                                jnp.int32),
          "y": jnp.zeros((K, B), jnp.int32)}
    mb = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (MB, S)),
                                jnp.int32),
          "y": jnp.zeros((MB,), jnp.int32)}
    return spec, theta, lam, bb, mb


@pytest.fixture(scope="module")
def sama_attr():
    """Compiled single-device SAMA step on a 2-layer transformer + one
    eager step under the tracer for measured phase walls."""

    spec, theta, lam, bb, mb = _mini_bert_problem()
    base_opt, meta_opt = optim.adam(1e-3), optim.adam(1e-3)
    cfg = EngineConfig(method="sama", unroll_steps=2)
    state = init_state(theta, lam, base_opt, meta_opt, scale=cfg.scale)
    step = make_meta_step(spec, base_opt, meta_opt, cfg)
    tracer = obs_mod.Tracer()
    with obs_mod.activate(tracer):
        jax.block_until_ready(step(state, bb, mb))
    compiled = jax.jit(step).lower(state, bb, mb).compile()
    return profile_mod.attribute(compiled, spans=tracer.runtime_spans())


def test_sama_step_coverage_and_attention_top(sama_attr):
    # ISSUE 8 acceptance: >= 90% of the compiled step's FLOPs land on a
    # named phase, and attention is the top FLOP sink on a transformer
    assert sama_attr["coverage"] >= 0.90
    assert sama_attr["top_module"] == "attention.py"
    assert sama_attr["modules"]["attention.py"]["flop_frac"] > 0.3
    ph = sama_attr["phases"]
    for needed in ("base_unroll", "meta_pass", "cd_passes"):
        assert ph[needed]["flops"] > 0
    assert next(iter(ph)) == "base_unroll"       # the unroll dominates
    assert sum(b["flop_frac"] for b in ph.values()) == pytest.approx(1.0)
    assert validate_attribution(sama_attr) == []


def test_sama_step_single_device_has_no_collectives(sama_attr):
    assert sama_attr["total"]["collective_count"] == 0


def test_sama_step_watermark_and_walls(sama_attr):
    ph = sama_attr["phases"]
    assert any(b.get("peak_live_bytes", 0) > 0 for b in ph.values())
    bu = ph["base_unroll"]
    assert bu["wall_us"] > 0 and 0 < bu["utilization"]
    assert bu["achieved_flops_per_s"] == pytest.approx(
        bu["flops"] / (bu["wall_us"] * 1e-6))


# family smokes: fractions sum to ~1 everywhere; attention dominates the
# configs whose smoke dims keep real head counts (qwen-moe, whisper) —
# gemma's tiny smoke collapses to common.py ops, which is itself pinned
# so a FLOP-model change that flips it shows up here.
@pytest.mark.parametrize("arch,attention_top", [
    ("gemma3-1b", False),
    ("qwen2-moe-a2.7b", True),
    ("whisper-small", True),
])
def test_family_attribution_smoke(arch, attention_top):
    attr = profile_mod._smoke_attribution(arch)["attribution"]
    assert sum(b["flop_frac"]
               for b in attr["phases"].values()) == pytest.approx(1.0)
    assert attr["coverage"] >= 0.85
    assert validate_attribution(attr) == []
    if attention_top:
        assert attr["top_module"] == "attention.py"
    else:
        assert "attention.py" in attr["modules"]


# manual single-sync schedule on 8 forced host devices: attribution must
# keep the paper's collective story — unroll all-reduces inside
# base_unroll, exactly ONE in allreduce_flat, meta/cd collective-free.
MANUAL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp

from repro import configs, optim
from repro.core import EngineConfig, init_state, problems
from repro.launch import distributed as dist
from jax.sharding import AxisType
from repro.models import Model
from repro.obs import profile as profile_mod

UNROLL = 2
mesh = jax.make_mesh((8, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
cfg = configs.get_smoke_config("bert-base").replace(
    d_model=128, num_layers=2, num_labels=4, num_heads=2, num_kv_heads=2,
    head_dim=64, d_ff=256, remat=False)
model = Model(cfg)
spec = problems.make_data_optimization_spec(model.classifier_per_example,
                                            reweight=True)
lam = problems.init_data_optimization_lam(jax.random.PRNGKey(1), reweight=True)
theta = model.init(jax.random.PRNGKey(0))
base_opt, meta_opt = optim.adam(1e-3), optim.adam(1e-3)
K, B, S, MB = UNROLL, 32, 32, 16
bb = {"tokens": jnp.zeros((K, B, S), jnp.int32), "y": jnp.zeros((K, B), jnp.int32)}
mb = {"tokens": jnp.zeros((MB, S), jnp.int32), "y": jnp.zeros((MB,), jnp.int32)}
ecfg = EngineConfig(method="sama", unroll_steps=K)
state = init_state(theta, lam, base_opt, meta_opt, scale=ecfg.scale)
with mesh:
    manual = jax.jit(dist.make_manual_step(spec, base_opt, meta_opt, ecfg, mesh))
    compiled = manual.lower(state, bb, mb).compile()
attr = profile_mod.attribute(compiled, n_devices=8)
print(json.dumps({"unroll": UNROLL, "attribution": attr}))
"""


@pytest.fixture(scope="module")
def manual_attr():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", MANUAL_SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_manual_schedule_coverage_and_attention(manual_attr):
    attr = manual_attr["attribution"]
    assert attr["coverage"] >= 0.90              # the ISSUE acceptance pin
    assert attr["top_module"] == "attention.py"
    assert attr["n_devices"] == 8
    assert validate_attribution(attr) == []


def test_manual_schedule_collectives_by_phase(manual_attr):
    attr = manual_attr["attribution"]
    unroll = manual_attr["unroll"]
    ph = attr["phases"]
    # unroll+1 single-sync story, now phase-localized
    assert ph["base_unroll"]["collective_count"] == unroll
    assert ph["allreduce_flat"]["collective_count"] == 1
    assert attr["total"]["collective_count"] == unroll + 1
    for quiet in ("meta_pass", "cd_passes"):
        assert ph[quiet]["collective_count"] == 0
    assert ph["allreduce_flat"]["collective_bytes"] > 0


# ISSUE 9: the single-sync census must be invariant to the attention
# backend. With the flash Pallas kernel (interpret mode) forcibly
# dispatched, the 8-device manual schedule still shows EXACTLY unroll+1
# all-reduces and the attribution/event streams stay obs-clean
# (schema-valid, fractions summing to 1). Dims are tiny: interpret mode
# unrolls the kernel grid into the HLO, so this pins structure, not speed.
FLASH_MANUAL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["REPRO_KERNEL_BACKEND"] = "pallas-interpret"
import json
import jax
import jax.numpy as jnp

from repro import configs, optim
from repro.core import EngineConfig, init_state, problems
from repro.kernels import dispatch
from repro.launch import distributed as dist
from jax.sharding import AxisType
from repro.models import Model
from repro.obs import profile as profile_mod

UNROLL = 2
mesh = jax.make_mesh((8, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
cfg = configs.get_smoke_config("bert-base").replace(
    d_model=64, num_layers=1, num_labels=4, num_heads=2, num_kv_heads=2,
    head_dim=32, d_ff=128, remat=False)
model = Model(cfg)
spec = problems.make_data_optimization_spec(model.classifier_per_example,
                                            reweight=True)
lam = problems.init_data_optimization_lam(jax.random.PRNGKey(1), reweight=True)
theta = model.init(jax.random.PRNGKey(0))
base_opt, meta_opt = optim.adam(1e-3), optim.adam(1e-3)
K, B, S, MB = UNROLL, 16, 8, 8
bb = {"tokens": jnp.zeros((K, B, S), jnp.int32), "y": jnp.zeros((K, B), jnp.int32)}
mb = {"tokens": jnp.zeros((MB, S), jnp.int32), "y": jnp.zeros((MB,), jnp.int32)}
ecfg = EngineConfig(method="sama", unroll_steps=K)
state = init_state(theta, lam, base_opt, meta_opt, scale=ecfg.scale)
with mesh:
    manual = jax.jit(dist.make_manual_step(spec, base_opt, meta_opt, ecfg, mesh))
    compiled = manual.lower(state, bb, mb).compile()
attr = profile_mod.attribute(compiled, n_devices=8)
picks = sorted({(k, b) for k, b, _ in dispatch.dispatch_log()
                if k == "flash_attention"})
print(json.dumps({"unroll": UNROLL, "attribution": attr,
                  "flash_picks": picks}))
"""


@pytest.fixture(scope="module")
def manual_attr_flash():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", FLASH_MANUAL_SCRIPT], capture_output=True,
        text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_manual_census_invariant_under_flash_dispatch(manual_attr_flash):
    # the kernel actually lowered (not a silent ref fallback)
    assert ["flash_attention", "pallas-interpret"] in [
        list(p) for p in manual_attr_flash["flash_picks"]]
    attr = manual_attr_flash["attribution"]
    unroll = manual_attr_flash["unroll"]
    ph = attr["phases"]
    assert ph["base_unroll"]["collective_count"] == unroll
    assert ph["allreduce_flat"]["collective_count"] == 1
    assert attr["total"]["collective_count"] == unroll + 1
    for quiet in ("meta_pass", "cd_passes"):
        assert ph[quiet]["collective_count"] == 0


def test_manual_flash_attribution_stays_obs_clean(manual_attr_flash):
    attr = manual_attr_flash["attribution"]
    assert validate_attribution(attr) == []
    assert sum(b["flop_frac"]
               for b in attr["phases"].values()) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# the profile CLI
# ---------------------------------------------------------------------------


def test_profile_cli_validate(tmp_path, capsys):
    good = tmp_path / "attr.json"
    good.write_text(json.dumps(profile_mod.attribute(SYN)))
    assert profile_mod.main(["--validate", str(good)]) == 0
    assert "valid" in capsys.readouterr().out
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"phases": {}}))
    assert profile_mod.main(["--validate", str(bad)]) == 1
    empty = tmp_path / "none.json"
    empty.write_text(json.dumps({"rows": []}))
    assert profile_mod.main(["--validate", str(empty)]) == 1


def test_render_mentions_top_sink():
    text = profile_mod.render(profile_mod.attribute(SYN))
    assert "top FLOP sink: attention.py" in text
    assert "base_unroll" in text and "coverage" in text
