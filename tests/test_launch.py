"""Launch-layer tests: sharding rules, roofline HLO parser, and a smoke-scale
dry-run (subprocess with 512 forced host devices) proving two cheap
(arch x shape) combos lower+compile on the production mesh inside CI.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import sharding as sh


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = _FakeMesh({"data": 16, "model": 16})


@pytest.mark.parametrize(
    "path,shape,expect",
    [
        ("['layers']['attn']['wq']", (26, 1152, 1024), P(None, None, "model")),
        ("['layers']['attn']['wo']", (26, 1024, 1152), P(None, "model", None)),
        ("['layers']['mlp']['down']", (26, 6912, 1152), P(None, "model", None)),
        ("['embed']", (262144, 1152), P("model", None)),
        ("['embed']", (51865, 768), P(None, None)),  # whisper: indivisible vocab
        ("['layers']['moe']['experts']['up']", (60, 384, 7168, 2048), P(None, "model", None, None)),
        # qwen: 60 experts don't divide 16 -> tensor-parallel within experts
        ("['layers']['moe']['experts']['up']", (24, 60, 2048, 1408), P(None, None, None, "model")),
        ("['layers']['moe']['router']", (24, 2048, 60), P(None, None, None)),
        ("['layers']['ln1']['scale']", (26, 1152), P()),
        ("['layers']['tmix']['wv']", (24, 2048, 2048), P(None, None, "model")),
        ("['layers']['cmix']['wv']", (24, 7168, 2048), P(None, "model", None)),
    ],
)
def test_param_spec_rules(path, shape, expect):
    assert sh.param_spec(path, shape, MESH) == expect


def test_head_alignment_replicates_unaligned_attention():
    from repro import configs

    cfg = configs.get_config("gemma3-1b")  # 4 heads, kv=1: neither divides 16
    assert sh.param_spec("['layers']['attn']['wq']", (26, 1152, 1024), MESH, cfg) == P()
    assert sh.param_spec("['layers']['attn']['wk']", (26, 1152, 256), MESH, cfg) == P()
    cfg2 = configs.get_config("kimi-k2-1t-a32b")  # 64 heads: aligned
    assert sh.param_spec("['layers']['attn']['wq']", (60, 7168, 8192), MESH, cfg2) == P(
        None, None, "model"
    )


def test_cache_spec_long_context_shards_sequence():
    # B=1 (long_500k): sequence axis goes to data, kv heads to model
    spec = sh.cache_spec("['kv']['k']", (62, 1, 524288, 16, 128), MESH)
    assert spec == P(None, None, "data", "model", None)
    # batch-shardable decode: batch to data
    spec = sh.cache_spec("['kv']['k']", (62, 128, 32768, 16, 128), MESH)
    assert spec[1] == "data"


def test_hlo_parser_trip_counts():
    """The micro-case from EXPERIMENTS §Method: exact collective accounting."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.roofline import hlo_parse
from jax.sharding import AxisType

mesh = jax.make_mesh((4, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
L, B, D = 8, 16, 64
def f(x, ws):
    def body(c, w):
        return c @ w, None
    out, _ = jax.lax.scan(body, x, ws)
    return out
x = jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=NamedSharding(mesh, P("data", None)))
ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32, sharding=NamedSharding(mesh, P(None, "model", None)))
with mesh:
    c = jax.jit(f).lower(x, ws).compile()
s = hlo_parse.collective_stats(c.as_text())
print(json.dumps({"bytes": s["all-reduce_bytes"], "count": s["all-reduce_count"]}))
"""
    out = _run_subprocess(script)
    r = json.loads(out)
    assert r["count"] == 8  # one per scan iteration
    assert r["bytes"] == 8 * (16 // 4) * 64 * 4  # L x (B_loc, D) f32


@pytest.mark.slow
def test_dryrun_smoke_production_mesh():
    """Two cheap jobs must lower+compile on the real 16x16 mesh (512 forced
    host devices, subprocess)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json
from repro.launch.dryrun import run_job
ok = []
for arch, shape in [("gemma3-1b", "decode_32k"), ("zamba2-7b", "long_500k")]:
    r = run_job(arch, shape, save=False)
    ok.append(r["status"])
print(json.dumps(ok))
"""
    out = _run_subprocess(script, timeout=500)
    assert json.loads(out) == ["ok", "ok"]


def _run_subprocess(script: str, timeout: int = 300) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, cwd=root, timeout=timeout)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_dir_from_env_or_fixed_in_checkout(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is used and no directory is
    configured; otherwise the cache goes to <repo>/.jax_cache. Either way
    the ops' metadata (their scope names) is part of the cache key."""
    from pathlib import Path

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        repo = Path(__file__).resolve().parents[1]
        assert got == str(repo / ".jax_cache") == jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_compilation_cache_include_metadata_in_key", keyed)


def test_train_main_returns_rows_and_builds_mesh_from_devices():
    """``train.main(argv)`` is the library entry point: it returns the
    Trainer and one unrounded row per logged step, and its mesh spans
    every device present, (data=n, model=1)."""
    import math

    from repro import obs as obs_mod
    from repro.launch import train

    before = jax.config.jax_compilation_cache_dir
    try:
        tr, rows = train.main(["--arch", "bert-base", "--smoke", "--steps", "2",
                               "--log-every", "1", "--batch", "4", "--seq", "16"])
    finally:
        obs_mod.set_default(obs_mod.NULL_OBS)
        jax.config.update("jax_compilation_cache_dir", before)
    assert dict(zip(tr.mesh.axis_names, tr.mesh.devices.shape)) == {
        "data": len(jax.devices()), "model": 1}
    assert [r["step"] for r in rows] == [0, 1]
    for r in rows:
        assert r["step_s"] > 0
        assert all(math.isfinite(r[k]) for k in ("base_loss", "meta_loss",
                                                  "hypergrad_norm", "eps"))
    assert rows[0]["eps"] > 0  # unrounded (the console line rounds to 4 places)
