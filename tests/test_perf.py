"""repro.perf: timers / memory / record schema / regression gate /
MetaLearner.profile, plus the acceptance pin — the MEASURED
(compiled-HLO, trip-scaled) all-reduce census of the manual SAMA step is
exactly unroll_steps + 1 on a forced 8-device CPU mesh.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import perf
from repro.api import MetaLearner
from repro.core import problems
from repro.perf import gate as gate_mod


# ---------------------------------------------------------------------------
# timers
# ---------------------------------------------------------------------------


def test_timing_stats_robust_summary():
    stats = perf.TimingStats.from_samples([1e-3, 2e-3, 3e-3, 4e-3, 100e-3], warmup=2)
    assert stats.median_us == pytest.approx(3000.0)
    assert stats.min_us == pytest.approx(1000.0)
    assert stats.max_us == pytest.approx(100000.0)
    assert stats.repeats == 5 and stats.warmup == 2
    assert stats.iqr_us > 0
    # the median shrugs off the 100ms outlier the mean absorbs
    assert stats.mean_us > 5 * stats.median_us


def test_measure_splits_compile_from_run():
    m = perf.measure(jax.jit(lambda x: (x * 2).sum()), jnp.ones((32,)),
                     warmup=1, repeats=3)
    assert m.timing.repeats == 3
    assert m.timing.median_us > 0
    assert m.compile_s is not None and m.compile_s >= 0
    assert m.lower_s is not None and m.lower_s >= 0
    assert m.compiled is not None
    # compile happened once, up front: run-phase medians are far below it
    assert m.timing.median_us / 1e6 < m.compile_s + m.lower_s
    assert m.samples_per_s(32) == pytest.approx(32 / (m.timing.median_us / 1e6))


def test_measure_non_loweable_callable_still_times():
    def host_loop(x):
        # host-side concretization: traceable drivers this is not
        return jnp.asarray(float(jnp.asarray(x) + 1))

    m = perf.measure(host_loop, 1.0, warmup=1, repeats=2)
    assert m.compiled is None and m.compile_s is None and m.lower_s is None
    assert m.timing.repeats == 2 and m.timing.median_us > 0


def test_time_callable_rejects_zero_repeats():
    with pytest.raises(ValueError, match="repeats"):
        perf.time_callable(lambda: jnp.zeros(()), repeats=0)


# ---------------------------------------------------------------------------
# memory
# ---------------------------------------------------------------------------


def test_compiled_memory_breakdown():
    compiled = jax.jit(lambda x: x @ x.T).lower(jnp.ones((16, 16))).compile()
    stats = perf.compiled_memory(compiled)
    assert stats.source == "memory_analysis"
    assert stats.argument_bytes == 16 * 16 * 4
    assert stats.output_bytes == 16 * 16 * 4
    assert stats.peak_bytes is not None
    assert stats.peak_bytes >= stats.argument_bytes + stats.output_bytes - (stats.alias_bytes or 0)


def test_memory_aval_fallback_when_analysis_unavailable():
    class NoAnalysis:
        def memory_analysis(self):
            raise NotImplementedError("backend without buffer assignment")

    args = ({"w": jnp.ones((8, 4)), "b": jnp.ones((4,), jnp.bfloat16)},)
    stats = perf.compiled_memory(NoAnalysis(), example_args=args,
                                 example_out=jnp.ones((8,)))
    assert stats.source == "aval_fallback"
    assert stats.argument_bytes == 8 * 4 * 4 + 4 * 2
    assert stats.output_bytes == 8 * 4
    assert stats.temp_bytes is None and stats.peak_bytes is None


def test_memory_report_shape():
    compiled = jax.jit(lambda x: x + 1).lower(jnp.ones((4,))).compile()
    rep = perf.memory_report(compiled)
    assert rep["n_devices"] == jax.device_count()
    assert "peak_bytes" in rep["per_device"]
    # CPU container: no allocator stats -> no device_stats section
    if perf.device_memory() is None:
        assert "device_stats" not in rep


# ---------------------------------------------------------------------------
# record schema
# ---------------------------------------------------------------------------


def _timing_dict():
    return perf.TimingStats.from_samples([1e-3, 2e-3, 3e-3], warmup=1).as_dict()


def test_record_roundtrip_and_validation():
    rec = perf.PerfRecord(name="probe", us_per_step=_timing_dict(),
                          samples_per_s=10.0, compile_s=0.5)
    d = rec.as_dict()
    assert perf.validate_record(d) == []
    assert d["schema_version"] == perf.SCHEMA_VERSION
    assert rec.timing.median_us == pytest.approx(2000.0)


@pytest.mark.parametrize("mutate,needle", [
    (lambda d: d.pop("name"), "name"),
    (lambda d: d.update(schema_version=99), "schema_version"),
    (lambda d: d["us_per_step"].pop("median_us"), "us_per_step"),
    (lambda d: d.update(samples_per_s=-1), "samples_per_s"),
    (lambda d: d.update(us_per_step=None), "no measured section"),
])
def test_record_validation_catches(mutate, needle):
    d = perf.PerfRecord(name="probe", us_per_step=_timing_dict()).as_dict()
    d.setdefault("us_per_step", None)
    mutate(d)
    errors = perf.validate_record(d)
    assert errors and any(needle in e for e in errors), errors


def test_write_bench_atomic_and_validated(tmp_path):
    payload = perf.bench_payload(
        "bench_probe", fast=True, elapsed_s=1.0,
        rows=[{"name": "r", "us_per_call": 1.0, "derived": {}}],
        records=[perf.PerfRecord(name="probe", us_per_step=_timing_dict())],
    )
    path = str(tmp_path / "BENCH_probe.json")
    perf.write_bench(path, payload)
    loaded = perf.load_bench(path)
    assert loaded["bench"] == "bench_probe"
    assert loaded["records"][0]["name"] == "probe"
    assert loaded["env"]["jax_version"] == jax.__version__
    # no tmp litter from the atomic write
    assert [f for f in os.listdir(tmp_path) if f.startswith(".tmp_")] == []

    bad = dict(payload, records=[{"name": "x", "schema_version": perf.SCHEMA_VERSION}])
    with pytest.raises(ValueError, match="no measured section"):
        perf.write_bench(str(tmp_path / "BENCH_bad.json"), bad)
    assert not (tmp_path / "BENCH_bad.json").exists()


# ---------------------------------------------------------------------------
# the regression gate
# ---------------------------------------------------------------------------


def _bench_file(tmp_path, subdir, *, median_us=1000.0, samples_per_s=100.0,
                peak_bytes=1 << 20, ar_count=3, total_bytes=4096.0):
    t = _timing_dict()
    t["median_us"] = median_us
    rec = perf.PerfRecord(
        name="step", us_per_step=t, samples_per_s=samples_per_s,
        memory={"per_device": {"argument_bytes": 1, "output_bytes": 1,
                               "temp_bytes": 1, "generated_code_bytes": 0,
                               "alias_bytes": 0, "peak_bytes": peak_bytes,
                               "source": "memory_analysis"},
                "n_devices": 1},
        collectives={"all-reduce_count": ar_count, "total_count": ar_count,
                     "total_bytes": total_bytes},
    )
    payload = perf.bench_payload("bench_x", fast=True, elapsed_s=0.1,
                                 rows=[], records=[rec])
    d = tmp_path / subdir
    d.mkdir(exist_ok=True)
    perf.write_bench(str(d / "BENCH_x.json"), payload)
    return str(d)


def test_gate_passes_within_bands(tmp_path):
    base = _bench_file(tmp_path, "base")
    cur = _bench_file(tmp_path, "cur", median_us=1800.0,  # < 2.5x
                      samples_per_s=60.0, peak_bytes=int(1.1 * (1 << 20)))
    report = perf.compare_dirs(cur, base)
    assert report.compared == 1
    assert report.violations == []
    assert gate_mod.main(["--records", cur, "--baselines", base]) == 0


def test_gate_improvements_never_fail(tmp_path):
    base = _bench_file(tmp_path, "base")
    cur = _bench_file(tmp_path, "cur", median_us=10.0, samples_per_s=1e5,
                      peak_bytes=1024, total_bytes=16.0)
    assert perf.compare_dirs(cur, base).violations == []


@pytest.mark.parametrize("knobs,metric", [
    (dict(median_us=3000.0), "us_per_step.median_us"),
    (dict(samples_per_s=10.0), "samples_per_s"),
    (dict(peak_bytes=2 << 20), "memory.peak_bytes"),
    (dict(ar_count=4), "collectives.all-reduce_count"),
    (dict(total_bytes=8192.0), "collectives.total_bytes"),
])
def test_gate_flags_each_regression_axis(tmp_path, knobs, metric):
    base = _bench_file(tmp_path, "base")
    cur = _bench_file(tmp_path, "cur", **knobs)
    report = perf.compare_dirs(cur, base)
    assert any(v.metric == metric for v in report.violations), report.violations
    assert gate_mod.main(["--records", cur, "--baselines", base]) == 1


def test_gate_collective_count_is_exact_even_when_lower(tmp_path):
    # one FEWER all-reduce is still a structural change worth a look
    base = _bench_file(tmp_path, "base", ar_count=3)
    cur = _bench_file(tmp_path, "cur", ar_count=2)
    report = perf.compare_dirs(cur, base)
    assert any(v.metric == "collectives.all-reduce_count" for v in report.violations)


def test_gate_new_and_missing_benches(tmp_path):
    base = _bench_file(tmp_path, "base")
    cur = tmp_path / "cur"
    cur.mkdir()
    payload = perf.bench_payload("bench_y", fast=True, elapsed_s=0.1, rows=[],
                                 records=[perf.PerfRecord(name="other",
                                                          us_per_step=_timing_dict())])
    perf.write_bench(str(cur / "BENCH_y.json"), payload)
    report = perf.compare_dirs(str(cur), base)
    assert report.compared == 0
    assert report.missing_benches == ["x"]
    assert any("bench_y" in n for n in report.new_records)
    # subset runs pass by default; --strict-missing turns lost coverage into failure
    assert gate_mod.main(["--records", str(cur), "--baselines", base]) == 0
    assert gate_mod.main(["--records", str(cur), "--baselines", base,
                          "--strict-missing"]) == 1


def test_gate_strict_missing_records_catches_dropped_record(tmp_path):
    """Subset-CI strictness: a RE-RUN bench that silently dropped a
    baselined record fails under --strict-missing-records, while whole
    non-run benches still pass (unlike --strict-missing)."""

    base = _bench_file(tmp_path, "base")
    # baseline gains a second record the current run does not reproduce
    base_payload = perf.load_bench(str(tmp_path / "base" / "BENCH_x.json"))
    base_payload["records"].append(
        perf.PerfRecord(name="dropped", us_per_step=_timing_dict()).as_dict())
    perf.write_bench(str(tmp_path / "base" / "BENCH_x.json"), base_payload)
    cur = _bench_file(tmp_path, "cur")
    report = perf.compare_dirs(cur, base)
    assert report.missing_records == ["bench_x/dropped"]
    assert gate_mod.main(["--records", cur, "--baselines", base]) == 0
    assert gate_mod.main(["--records", cur, "--baselines", base,
                          "--strict-missing-records"]) == 1
    # an extra never-run baselined bench must NOT trip record-level strictness
    shutil.copy(str(tmp_path / "base" / "BENCH_x.json"),
                str(tmp_path / "base" / "BENCH_z.json"))
    report = perf.compare_dirs(cur, base)
    assert report.missing_benches == ["z"]
    assert report.ok(strict_missing_records=True) is False  # dropped record still fails
    # with only the whole-bench gap (record restored), subset mode passes
    cur2 = _bench_file(tmp_path, "cur2")
    cur2_payload = perf.load_bench(str(tmp_path / "cur2" / "BENCH_x.json"))
    cur2_payload["records"].append(
        perf.PerfRecord(name="dropped", us_per_step=_timing_dict()).as_dict())
    perf.write_bench(str(tmp_path / "cur2" / "BENCH_x.json"), cur2_payload)
    assert gate_mod.main(["--records", cur2, "--baselines", base,
                          "--strict-missing-records"]) == 0
    assert gate_mod.main(["--records", cur2, "--baselines", base,
                          "--strict-missing"]) == 1  # full-run mode still strict


def test_gate_warns_on_env_mismatch(tmp_path, capsys):
    base = _bench_file(tmp_path, "base")
    base_payload = perf.load_bench(str(tmp_path / "base" / "BENCH_x.json"))
    base_payload["env"]["jax_version"] = "0.0.0-minted-elsewhere"
    perf.write_bench(str(tmp_path / "base" / "BENCH_x.json"), base_payload)
    cur = _bench_file(tmp_path, "cur")
    report = perf.compare_dirs(cur, base)
    assert report.env_mismatches and "0.0.0-minted-elsewhere" in report.env_mismatches[0]
    assert gate_mod.main(["--records", cur, "--baselines", base]) == 0  # warn, not fail
    assert "WARNING env mismatch" in capsys.readouterr().out


def test_gate_custom_tolerance(tmp_path):
    base = _bench_file(tmp_path, "base")
    cur = _bench_file(tmp_path, "cur", median_us=1800.0)
    assert gate_mod.main(["--records", cur, "--baselines", base,
                          "--tol-time", "1.5"]) == 1


# ---------------------------------------------------------------------------
# MetaLearner.profile
# ---------------------------------------------------------------------------


def test_metalearner_profile_emits_valid_record():
    def apply_fn(theta, x):
        return jnp.tanh(x @ theta["w1"]) @ theta["w2"]

    spec = problems.make_data_optimization_spec(
        problems.softmax_per_example(apply_fn), reweight=True)
    theta = {"w1": jax.random.normal(jax.random.PRNGKey(0), (6, 16)) * 0.3,
             "w2": jax.random.normal(jax.random.PRNGKey(1), (16, 3)) * 0.3}
    lam = problems.init_data_optimization_lam(jax.random.PRNGKey(2), reweight=True)
    base = {"x": jax.random.normal(jax.random.PRNGKey(3), (2, 8, 6)),
            "y": jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0, 3)}
    meta = {"x": jax.random.normal(jax.random.PRNGKey(5), (4, 6)),
            "y": jax.random.randint(jax.random.PRNGKey(6), (4,), 0, 3)}

    learner = MetaLearner(spec, method="sama", unroll_steps=2)
    learner.init(theta, lam)
    state_before = learner.state
    rec = learner.profile(base, meta, warmup=1, repeats=2)
    assert perf.validate_record(rec.as_dict()) == []
    assert rec.name == "sama_pjit"
    assert rec.timing.median_us > 0 and rec.timing.repeats == 2
    assert rec.memory["per_device"]["peak_bytes"] > 0
    assert rec.collectives["total_count"] == 0  # single device: no collectives
    assert rec.extra == {"method": "sama", "schedule": "pjit", "unroll_steps": 2,
                         "microbatch": 1, "policy": "f32"}
    # profiling is a probe, not training: state untouched, its arrays alive
    # (the probe's compile donates nothing) and still the learner's to step
    assert learner.state is state_before
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves(learner.state))
    assert int(learner.state.step) == 0
    learner.step(base, meta)
    assert int(learner.state.step) == 1
    with pytest.raises(RuntimeError, match="before profile"):
        MetaLearner(spec, method="sama", unroll_steps=2).profile(base, meta)


# ---------------------------------------------------------------------------
# ACCEPTANCE: measured all-reduce census of the manual SAMA step
# ---------------------------------------------------------------------------

CENSUS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp

from repro import optim, perf
from repro.core import EngineConfig, init_state, problems
from repro.launch import distributed as dist

UNROLL = 2
mesh = jax.make_mesh((8, 1), ("data", "model"))

def apply_fn(theta, x):
    return jnp.tanh(x @ theta["w1"]) @ theta["w2"]

spec = problems.make_data_optimization_spec(
    problems.softmax_per_example(apply_fn), reweight=True)
theta = {"w1": jax.random.normal(jax.random.PRNGKey(0), (6, 16)) * 0.3,
         "w2": jax.random.normal(jax.random.PRNGKey(1), (16, 3)) * 0.3}
lam = problems.init_data_optimization_lam(jax.random.PRNGKey(2), reweight=True)
base_opt, meta_opt = optim.adam(1e-2), optim.adam(1e-2)
state = init_state(theta, lam, base_opt, meta_opt)
step = dist.make_manual_step(
    spec, base_opt, meta_opt, EngineConfig(method="sama", unroll_steps=UNROLL), mesh)
base = {"x": jax.random.normal(jax.random.PRNGKey(3), (UNROLL, 8, 6)),
        "y": jax.random.randint(jax.random.PRNGKey(4), (UNROLL, 8), 0, 3)}
meta = {"x": jax.random.normal(jax.random.PRNGKey(5), (8, 6)),
        "y": jax.random.randint(jax.random.PRNGKey(6), (8,), 0, 3)}
with mesh:
    compiled = jax.jit(step).lower(state, base, meta).compile()
    census = perf.verify_single_sync(compiled, UNROLL)
print(json.dumps({"unroll": UNROLL, "census": census}))
"""


def test_measured_manual_sama_census_is_unroll_plus_one():
    """The paper's single-sync claim, verified on the COMPILED step: the
    trip-scaled all-reduce count of the manual SAMA schedule on an
    8-device CPU mesh is exactly unroll_steps (per-step base DDP syncs)
    + 1 (the one flat meta bucket)."""

    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", CENSUS_SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    census = r["census"]
    assert census["expected_all_reduces"] == r["unroll"] + 1 == 3
    assert census["all-reduce_count"] == r["unroll"] + 1
    assert census["single_sync_ok"] is True
    assert isinstance(census["all-reduce_count"], int)
    # the single-sync schedule introduces no other collective kinds
    assert census["total_count"] == census["all-reduce_count"]
