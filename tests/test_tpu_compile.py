"""Compile-only checks: the main-path Pallas kernels compile for a TPU v5e
chip at the widths the configs use, with no chip attached.

The chip is described (a ``v5e:2x2`` topology), not attached, so nothing
runs: each test compiles one kernel for one chip and asserts that the
Mosaic kernel (``tpu_custom_call``) is in the compiled program. The
topology is described inside a module fixture, never while a module is
imported, so every pytest worker collects the same tests and only the
worker given this file loads the TPU compiler.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import adam_adapt, flash_attn, weighted_ce

BERT_BASE_PARAMS = 108_810_244


@pytest.fixture(scope="module")
def one_chip():
    pytest.importorskip("libtpu", reason="the TPU compiler is not installed")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")  # the compiler logs nowhere
            # requirements.txt pins libtpu, so a topology that cannot be
            # described with it installed is a failure, not a skip
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_adam_adapt_compiles_for_all_of_bert_base(one_chip):
    """The flat adaptation kernel over all 108.8M bert-base parameters."""
    n = BERT_BASE_PARAMS
    text = _compiled_text(
        lambda g, m, v, gm, t, lr: adam_adapt.adam_adapt_product(
            g, m, v, gm, t=t, lr=lr),
        one_chip, *[((n,), jnp.float32)] * 4, ((), jnp.float32), ((), jnp.float32))
    assert text.count("tpu_custom_call") == 1


def test_flash_attention_forward_and_backward_compile(one_chip):
    """Dh=128, S=1024, GQA 16/8 heads: forward, dq and dk/dv kernels."""
    b, s, h, kv, dh = 2, 1024, 16, 8, 128

    def loss(q, k, v, pos):
        out = flash_attn.flash_attention(q, k, v, pos, pos[0], causal=True)
        return out.astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                          ((b, s, h, dh), jnp.bfloat16),
                          ((b, s, kv, dh), jnp.bfloat16),
                          ((b, s, kv, dh), jnp.bfloat16),
                          ((b, s), jnp.int32))
    assert text.count("tpu_custom_call") == 3


def test_weighted_ce_forward_and_backward_compile(one_chip):
    """4096 rows over a 32k vocabulary, bf16 logits."""
    r, vocab = 4096, 32768
    text = _compiled_text(
        jax.value_and_grad(lambda x, y: weighted_ce.cross_entropy(x, y).sum()),
        one_chip, ((r, vocab), jnp.bfloat16), ((r,), jnp.int32))
    assert text.count("tpu_custom_call") == 2


def test_flash_decode_compiles(one_chip):
    """Split-KV decode: 8 lanes over a 4096-token cache, Dh=128."""
    lanes, t, h, kv, dh = 8, 4096, 16, 8, 128
    text = _compiled_text(
        lambda q, k, v, pos: flash_attn.flash_decode(q, k, v, pos), one_chip,
        ((lanes, 1, h, dh), jnp.bfloat16), ((lanes, t, kv, dh), jnp.bfloat16),
        ((lanes, t, kv, dh), jnp.bfloat16), ((lanes, 1), jnp.int32))
    assert text.count("tpu_custom_call") == 1
