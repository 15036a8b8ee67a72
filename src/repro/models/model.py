"""Model: the user-facing handle tying an ArchConfig to init/forward/decode
and to the SAMA data-optimization problem builders.

The per-example adapter returns mean-per-token cross-entropy per *sequence*
(the unit the paper reweights: an utterance / document / image-text pair),
plus predictive-entropy uncertainty for the Sec. 4.3 pruning variant.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.problems import PerExample
from repro.models import transformer as tf
from repro.kernels import dispatch as kdispatch
from repro.kernels import ops as kops
from repro.obs import trace as obs_trace

PyTree = Any


def token_cross_entropy(
    logits: jnp.ndarray, targets: jnp.ndarray, use_kernel: bool = False, sharded: bool = False
):
    """logits: (B, S, V) f32; targets: (B, S) int. Returns per-token CE (B, S).

    ``sharded=True`` uses the one-hot-reduction form: lse via local max/sum
    (SPMD lowers the V-axis reductions to (token,)-sized psums) and the target
    logit via a compare-select reduction instead of take_along_axis, whose
    gather over a vocab-sharded axis all-gathers the full logits tensor.

    Unsharded large vocabularies (V >= ``kernels.CE_VOCAB_THRESHOLD``) route
    through the dispatched blockwise ``weighted_ce`` kernel automatically;
    ``use_kernel=True`` forces that route for any size (which backend then
    runs — compiled Pallas, interpreter, or jnp ref — is the dispatch
    registry's call, docs/kernels.md). The kernel route returns f32 CE
    regardless of logits dtype (the kernels compute in f32); the small-vocab
    path keeps logits dtype.
    """

    with obs_trace.block("loss"):
        if sharded:
            m = jnp.max(logits, axis=-1, keepdims=True)
            lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
            ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
            tgt = jnp.sum(jnp.where(ids == targets[..., None], logits, 0.0), axis=-1)
            return lse - tgt
        if use_kernel or logits.shape[-1] >= kdispatch.CE_VOCAB_THRESHOLD:
            return kops.cross_entropy(logits, targets)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


@dataclasses.dataclass(eq=False)  # identity hash/eq: Model instances key
class Model:                      # per-model jit caches (dataopt.prune)
    cfg: Any
    use_ce_kernel: bool = False

    # -- params / caches --
    def init(self, key) -> PyTree:
        return tf.init_params(self.cfg, key)

    def init_cache(self, batch: int, cache_len: int, dtype=jnp.bfloat16) -> PyTree:
        return tf.init_cache(self.cfg, batch, cache_len, dtype)

    # -- compute paths --
    def forward(self, params, batch):
        return tf.forward(self.cfg, params, batch)

    def decode_step(self, params, cache, tokens, pos):
        return tf.decode_step(self.cfg, params, cache, tokens, pos)

    # -- losses --
    def lm_loss(self, params, batch) -> jnp.ndarray:
        """Next-token LM loss (scalar) + MoE aux. batch: tokens (B,S) [+ modality]."""
        logits, aux = self.forward(params, batch)
        ce = token_cross_entropy(
            logits[:, :-1], batch["tokens"][:, 1:], self.use_ce_kernel, self.cfg.sharded_ce
        )
        return jnp.mean(ce) + aux

    def per_example(self, params, batch) -> PerExample:
        """Per-sequence loss for data-optimization meta learning."""
        logits, aux = self.forward(params, batch)
        del aux  # aux load-balance is added by train_loss wrappers, not reweighted
        with obs_trace.block("loss"):
            ce = token_cross_entropy(
                logits[:, :-1], batch["tokens"][:, 1:], self.use_ce_kernel, self.cfg.sharded_ce
            )
            loss = jnp.mean(ce, axis=-1)  # (B,)
            logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
            entropy = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
        return PerExample(loss=loss, uncertainty=entropy)

    def classifier_per_example(self, params, batch) -> PerExample:
        """family == 'encoder': batch = {tokens (B,S), y (B,)}. Label spaces
        at ``kernels.CE_VOCAB_THRESHOLD``+ route the per-sample CE through
        the dispatched ``weighted_ce`` kernel (docs/kernels.md)."""
        logits, _ = self.forward(params, batch)
        with obs_trace.block("loss"):
            onehot = jax.nn.one_hot(batch["y"], logits.shape[-1], dtype=logits.dtype)
            logp = jax.nn.log_softmax(logits, axis=-1)
            if logits.shape[-1] >= kdispatch.CE_VOCAB_THRESHOLD:
                loss = kops.cross_entropy(logits, batch["y"])
            else:
                loss = -jnp.sum(onehot * logp, axis=-1)
            p = jnp.exp(logp)
            entropy = -jnp.sum(p * logp, axis=-1)
        return PerExample(loss=loss, logits=logits, label_onehot=onehot, uncertainty=entropy)

    def num_params(self, params) -> int:
        return sum(x.size for x in jax.tree_util.tree_leaves(params))
