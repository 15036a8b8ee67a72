"""Model block scopes, the step marker and host spans on the profiler's clock.

* every model block (``obs.trace.BLOCKS``) reaches the compiled step's
  ``op_name`` metadata, forward and backward, through ``lax.scan``, remat
  and ``jax.grad``, and changes nothing but that metadata;
* ``MetaLearner.step`` marks each step in a ``jax.profiler`` capture with a
  host-side step number and reads nothing back from the device, and the
  training loop names its host work (``next_batch``, ``log_read``,
  ``checkpoint``);
* ``obs.Tracer`` spans start where their TraceMe events start;
* ``train.py --profile-dir`` writes a capture of jitted steps whose device
  ops carry phase and block scopes, and ``--obs-log`` no longer runs an
  eager step, so its log has no span events and still renders;
* ``obs.profile`` charges a remat'd layer's ops to the module that opened
  their block;
* on four host devices each all-reduce of the single-sync step is named
  ``grad_sync`` or ``allreduce_flat``.
"""

import contextlib
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, configs
from repro.core import problems
from repro.launch import train
from repro.models import Model
from repro.obs import events as events_mod
from repro.obs import profile as profile_mod
from repro.obs import report as report_mod
from repro.obs import trace as trace_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKED = ("embed", "attention", "mlp", "loss", "unembed")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r", metadata=\{[^}]*\}")
_FRAME_TABLES = re.compile(r"\nFileNames\n.*?\n(?=%|ENTRY)", re.S)
_NAME = re.compile(r"%[\w.\-]+")


def _strip_metadata(text):
    """The compiled text without what names and locates its ops: each op's
    ``metadata={...}``, the module's stack-frame tables, and the numbers in
    its instruction names. XLA names an instruction after its op's location
    and numbers the names in order of creation, so a scope can renumber
    them (whisper's step holds the same broadcasts, numbered 2004-2005 in
    place of 2006-2007). Each ``%name`` becomes its order of first
    appearance: a one-to-one renaming, so the program's structure is still
    compared whole."""
    text = _METADATA.sub("", _FRAME_TABLES.sub("\n", text))
    order = {}
    return _NAME.sub(lambda m: "%" + str(order.setdefault(m.group(0), len(order))), text)


def _learner(arch, **overrides):
    cfg = configs.get_smoke_config(arch).replace(**overrides)
    model = Model(cfg)
    spec = problems.make_data_optimization_spec(
        model.classifier_per_example if cfg.family == "encoder" else model.per_example,
        reweight=True)
    learner = api.MetaLearner(spec, base_opt="adam", base_lr=1e-3, meta_opt="adam",
                              meta_lr=1e-3, method="sama", unroll_steps=2)
    learner.init(model.init(jax.random.PRNGKey(0)),
                 problems.init_data_optimization_lam(jax.random.PRNGKey(1), reweight=True))
    rng = np.random.default_rng(0)

    def batch(lead):
        out = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, lead + (16,)), jnp.int32)}
        if cfg.family == "encoder":
            out["y"] = jnp.asarray(rng.integers(0, cfg.num_labels, lead), jnp.int32)
        if cfg.family == "audio":
            out["frames"] = jnp.asarray(rng.normal(size=lead + (cfg.encoder_seq, cfg.d_model)),
                                        jnp.float32)
        return out

    return learner, batch((2, 4)), batch((2,))


def _compiled_text(learner, bb, mb):
    return learner.step_fn.lower(learner.state, bb, mb).compile().as_text()


STEPS = {"bert-base": {}, "whisper-small": {"remat": True}}


@pytest.fixture(scope="module", params=sorted(STEPS))
def compiled(request):
    learner, bb, mb = _learner(request.param, **STEPS[request.param])
    return request.param, learner, bb, mb, _compiled_text(learner, bb, mb)


def test_blocks_reach_forward_and_backward_ops(compiled):
    arch, _, _, _, text = compiled
    paths = _OPNAME.findall(text)
    for block in CHECKED:
        mine = [p for p in paths if trace_mod.block_of(p) == block]
        assert [p for p in mine if "transpose(" not in p], (arch, block, "forward")
        assert [p for p in mine if "transpose(" in p], (arch, block, "backward")
    if arch == "whisper-small":
        # the stacks say where a block ran, also inside the remat'd scans
        names = [trace_mod.scope_names(p) for p in paths if trace_mod.block_of(p) == "attention"]
        assert any("encoder" in n and "checkpoint" in n for n in names)
        assert any("decoder" in n for n in names)
        assert any(trace_mod.block_of(p) == "cross_attention" for p in paths)


def test_blocks_change_nothing_but_metadata(compiled, monkeypatch):
    arch, learner, bb, mb, text = compiled
    monkeypatch.setattr(trace_mod, "block", lambda name: contextlib.nullcontext())
    bare, _, _ = _learner(arch, **STEPS[arch])
    plain = _compiled_text(bare, bb, mb)
    assert not any(trace_mod.block_of(p) for p in _OPNAME.findall(plain))
    assert _strip_metadata(plain) == _strip_metadata(text)


def test_block_of_strips_transform_wrappers():
    assert trace_mod.block_of("jit(s)/base_unroll/transpose(jvp(loss))/mul") == "loss"
    assert trace_mod.block_of("jit(s)/transpose(jvp(decoder))/while/body/closed_call/"
                              "checkpoint/rematted_computation/attention/dot_general") == "attention"
    assert trace_mod.block_of("jit(s)/decoder/cross_attention/dot_general") == "cross_attention"
    assert trace_mod.block_of("jit(s)/mlp/moe/mlp/dot_general") == "mlp"
    assert trace_mod.block_of("jit(s)/base_unroll/jvp(jit(_var))/mul;jit(s)/mlp/add") is None
    assert trace_mod.block_of("") is None


# ---------------------------------------------------------------------------
# the step marker and host spans in a profiler capture
# ---------------------------------------------------------------------------


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path)
    start = None
    events = []
    for plane in data.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start = stats["profile_start_time"]
        for line in plane.lines:
            for e in line.events:
                events.append((plane.name, line.name, e.name, e.start_ns, dict(e.stats)))
    return start, events


@pytest.fixture(scope="module")
def small_learner():
    return _learner("bert-base")


def test_profile_marks_each_step_and_lines_up_tracer_spans(small_learner, tmp_path):
    learner, bb, mb = small_learner
    jax.block_until_ready(learner.step(bb, mb))  # compiled outside the capture
    first = learner._dispatched
    tracer = trace_mod.Tracer()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            m = learner.step(bb, mb)
        with tracer.span("clock_probe"):
            jax.block_until_ready(m)
    start, events = _host_events(tmp_path)
    steps = [st["step_num"] for _, _, name, _, st in events if name == "meta_step"]
    assert steps == [first, first + 1, first + 2]
    (probe,) = [t for _, _, name, t, _ in events if name == "clock_probe"]
    (span,) = tracer.spans
    assert abs(span.start_s * 1e9 - (start + probe)) < 1e6


def test_fit_names_its_host_work(small_learner, tmp_path):
    learner, bb, mb = small_learner
    learner.checkpoint_dir = str(tmp_path / "ck")
    with jax.profiler.trace(str(tmp_path / "prof")):
        learner.fit(iter([(bb, mb)] * 2), steps=2, log_every=1, save_every=2)
    _, events = _host_events(tmp_path / "prof")
    names = [name for _, _, name, _, _ in events]
    assert names.count("next_batch") == 2 and names.count("log_read") == 2
    assert names.count("checkpoint") == 1


def test_step_reads_nothing_back_from_the_device(small_learner):
    learner, bb, mb = small_learner
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(2):
            learner.step(bb, mb)
    jax.block_until_ready(learner.state)


def _train_main(argv):
    """``train.main``, leaving the process's obs default and compile cache
    settings as it found them."""
    from repro import obs as obs_mod

    cache_dir = jax.config.jax_compilation_cache_dir
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        return train.main(argv)
    finally:
        obs_mod.set_default(obs_mod.NULL_OBS)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_compilation_cache_include_metadata_in_key", keyed)


def test_train_profile_dir_captures_scoped_device_ops(tmp_path, capsys):
    out, log = str(tmp_path / "prof"), str(tmp_path / "run.jsonl")
    tr, rows = _train_main(["--arch", "bert-base", "--smoke", "--steps", "5",
                            "--log-every", "2", "--batch", "4", "--seq", "16",
                            "--profile-dir", out, "--obs-log", log])
    assert [r["step"] for r in rows] == [0, 2, 4]
    # the log holds no span events now, and the report still renders it
    assert not [e for e in events_mod.read_jsonl(log) if e.kind == "span"]
    capsys.readouterr()
    assert report_mod.main([log]) == 0
    assert "metrics (3 logged steps)" in capsys.readouterr().out
    base, meta = tr.make_batch(4, 2), tr.make_batch(2)
    text = tr.learner.step_fn.lower(tr.learner.state, base, meta).compile().as_text()
    op_paths = dict(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"",
                               text, re.M))
    _, events = _host_events(out)
    names = [name for _, _, name, _, _ in events]
    assert [st["step_num"] for _, _, n, _, st in events if n == "meta_step"] == [2, 3, 4]
    assert "next_batch" in names and "log_read" in names
    scoped = [op_paths[n] for n in names if n in op_paths]
    for phase in ("base_unroll", "meta_pass", "cd_passes"):
        for block in ("attention", "mlp"):
            assert any(phase in p and trace_mod.block_of(p) == block for p in scoped), \
                (phase, block)


def test_train_profile_dir_needs_the_captured_steps():
    with pytest.raises(SystemExit):
        train.parse_args(["--steps", "4", "--profile-dir", "x"])


# ---------------------------------------------------------------------------
# module attribution under remat
# ---------------------------------------------------------------------------


def test_module_of_charges_the_scan_site_to_the_block_owner():
    site = "/repo/src/repro/models/transformer.py"
    remat = "jit(s)/base_unroll/while/body/closed_call/checkpoint"
    assert profile_mod.module_of(site, f"{remat}/attention/dot_general") == "attention.py"
    assert profile_mod.module_of(site, f"{remat}/cross_attention/dot_general") == "attention.py"
    assert profile_mod.module_of(site, f"{remat}/mlp/dot_general") == "common.py"
    assert profile_mod.module_of(site, f"{remat}/mlp/moe/dot_general") == "moe.py"
    assert profile_mod.module_of(site, f"{remat}/mlp/moe/mlp/dot_general") == "common.py"
    assert profile_mod.module_of(site, "jit(s)/transpose(jvp(loss))/mul") == "model.py"
    # no block on the path, or a file that is not the scan site: its own file
    assert profile_mod.module_of(site, f"{remat}/add") == "transformer.py"
    assert profile_mod.module_of("/x/kernels/ref.py", f"{remat}/attention/dot") == "ref.py"
    assert profile_mod.module_of("", "jit(s)/attention/dot") is None


# ---------------------------------------------------------------------------
# the single-sync step's exchanges keep their names on four devices
# ---------------------------------------------------------------------------

GRAD_SYNC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, re
import jax
import jax.numpy as jnp
from jax.sharding import AxisType
from repro import configs, optim
from repro.core import EngineConfig, init_state, problems
from repro.launch import distributed as dist
from repro.models import Model
from repro.roofline import hlo_parse

UNROLL = 2
mesh = jax.make_mesh((4, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
cfg = configs.get_smoke_config("bert-base").replace(num_layers=1, remat=False)
model = Model(cfg)
spec = problems.make_data_optimization_spec(model.classifier_per_example, reweight=True)
theta = model.init(jax.random.PRNGKey(0))
lam = problems.init_data_optimization_lam(jax.random.PRNGKey(1), reweight=True)
base_opt, meta_opt = optim.adam(1e-3), optim.adam(1e-3)
bb = {"tokens": jnp.zeros((UNROLL, 8, 8), jnp.int32), "y": jnp.zeros((UNROLL, 8), jnp.int32)}
mb = {"tokens": jnp.zeros((4, 8), jnp.int32), "y": jnp.zeros((4,), jnp.int32)}
ecfg = EngineConfig(method="sama", unroll_steps=UNROLL)
state = init_state(theta, lam, base_opt, meta_opt, scale=ecfg.scale)
with mesh:
    step = jax.jit(dist.make_manual_step(spec, base_opt, meta_opt, ecfg, mesh))
    text = step.lower(state, bb, mb).compile().as_text()
comps = hlo_parse.split_computations(text)
trips = hlo_parse.computation_multipliers(comps, follow_calls=True)
ops = []
for comp, lines in comps.items():
    if comp == "__entry__":
        continue
    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = .*? (all-reduce(?:-start)?)\(", line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            ops.append([m.group(1), name.group(1) if name else "", trips.get(comp, 1.0)])
print(json.dumps({"unroll": UNROLL, "all_reduces": ops}))
"""


def test_single_sync_all_reduces_carry_their_scope():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", GRAD_SYNC_SCRIPT], capture_output=True,
                         text=True, env=env, cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    import json

    got = json.loads(out.stdout.strip().splitlines()[-1])
    ops = got["all_reduces"]  # [name, op_name, times run per step]
    unnamed = [name for name, path, _ in ops if "grad_sync" not in path
               and "allreduce_flat" not in path]
    assert not unnamed, f"all-reduces with no exchange scope in op_name: {unnamed}"
    assert sum(n for _, path, n in ops if "grad_sync" in path) == got["unroll"]
    assert sum(n for _, path, n in ops if "allreduce_flat" in path) == 1
