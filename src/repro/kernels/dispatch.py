"""The kernel backend-dispatch registry (DESIGN.md §10, docs/kernels.md).

Every fused kernel in this package has up to three interchangeable
implementations of one calling convention:

* ``"pallas-tpu"``      — the Pallas kernel compiled for real (TPU runtimes);
* ``"pallas-interpret"`` — the same kernel body run through the Pallas
  interpreter (works on any backend; the CPU CI's way of executing the
  actual kernel code);
* ``"ref"``             — a pure-jnp implementation in the inputs' native
  dtype (the fastest choice on CPU/GPU and the always-eligible fallback).

``register_kernel(name, backend, impl, eligible=...)`` installs one
implementation; ``get_kernel(name)`` returns a dispatching callable that
picks an implementation *per call*, in this precedence order:

1. an explicit ``backend=`` argument to ``get_kernel`` (tests, benchmarks);
2. the ``REPRO_KERNEL_BACKEND`` environment variable — consulted on every
   dispatch, which under jit means at TRACE time: set it before the first
   call for a given shape, because an already-cached executable will not
   re-dispatch;
3. the platform default: ``jax.default_backend() == "tpu"`` prefers
   ``pallas-tpu``, everything else prefers ``ref`` (the interpreter is a
   correctness tool, not a fast path).

Whatever picked the backend, a per-kernel ``eligible(*args, **kwargs)``
predicate is consulted on the concrete call (static shapes/dtypes only — it
runs at trace time). An ineligible or unregistered choice falls through to
the next entry in the order, ending at ``ref`` which must always be
registered and always eligible; the fallback is recorded, never an error.
Forcing ``pallas-tpu`` where the JAX backend is not a TPU raises: the
compiled kernel cannot run there, and quietly running another backend
would hide that the device is missing.
Ragged/non-tile-aligned shapes are therefore safe on every backend: the
flat adaptation kernels pad internally (pad-or-fallback), and shapes the
blockwise-CE kernel cannot tile fall back to ``ref``.

Dispatch decisions are appended to a trace-time log (``dispatch_log()``) —
selection happens while JAX traces, so the log records which implementation
a jitted function lowered through (what the acceptance tests pin), not
per-call execution counts.
"""

from __future__ import annotations

import collections
import dataclasses
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

ENV_VAR = "REPRO_KERNEL_BACKEND"

#: recognised backends, in no particular order (precedence is computed
#: per-call by ``backend_order``).
BACKENDS = ("pallas-tpu", "pallas-interpret", "ref")

#: vocabulary size at or above which the CE loss paths route through the
#: dispatched ``weighted_ce`` kernel (below it, a plain fused-by-XLA
#: log_softmax is already optimal and the blockwise machinery buys nothing).
CE_VOCAB_THRESHOLD = 4096


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    """One registered implementation of a kernel."""

    name: str
    backend: str
    fn: Callable[..., Any]
    #: static-shape eligibility predicate; None = always eligible.
    eligible: Optional[Callable[..., bool]] = None

    def is_eligible(self, *args, **kwargs) -> bool:
        if self.eligible is None:
            return True
        return bool(self.eligible(*args, **kwargs))


_REGISTRY: Dict[str, Dict[str, KernelImpl]] = {}

#: trace-time dispatch decisions: (kernel, backend, reason) tuples. Bounded
#: so eager callers in long-running processes (scoring loops, serve) don't
#: leak — jitted hot paths only append on (re)trace anyway.
_DISPATCH_LOG: "collections.deque[Tuple[str, str, str]]" = collections.deque(maxlen=4096)


def register_kernel(
    name: str,
    backend: str,
    impl: Optional[Callable[..., Any]] = None,
    *,
    eligible: Optional[Callable[..., bool]] = None,
    overwrite: bool = False,
):
    """Register ``impl`` as the ``backend`` implementation of kernel
    ``name``. Usable directly or as a decorator::

        register_kernel("adam_adapt", "ref", _adam_ref)

        @register_kernel("mine", "pallas-interpret", eligible=_tiles_ok)
        def _mine(x): ...

    All implementations of one name must share a calling convention —
    callers never know which backend they got.
    """

    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; have {BACKENDS}")

    def _install(fn):
        per_kernel = _REGISTRY.setdefault(name, {})
        if backend in per_kernel and not overwrite:
            raise ValueError(
                f"kernel {name!r} already has a {backend!r} implementation "
                "(pass overwrite=True to replace)"
            )
        per_kernel[backend] = KernelImpl(name=name, backend=backend, fn=fn, eligible=eligible)
        return fn

    if impl is None:
        return _install
    return _install(impl)


def unregister_kernel(name: str, backend: Optional[str] = None):
    """Remove a kernel (or one backend of it) — test hygiene."""

    if backend is None:
        _REGISTRY.pop(name, None)
    elif name in _REGISTRY:
        _REGISTRY[name].pop(backend, None)


def available_kernels() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def kernel_backends(name: str) -> Tuple[str, ...]:
    """Backends registered for ``name`` (registry order-independent)."""

    if name not in _REGISTRY:
        raise ValueError(f"unknown kernel {name!r}; have {available_kernels()}")
    return tuple(b for b in BACKENDS if b in _REGISTRY[name])


def backend_order(backend: Optional[str] = None) -> Tuple[str, ...]:
    """The per-call backend precedence list (most preferred first). ``ref``
    is always the terminal fallback."""

    forced = backend or os.environ.get(ENV_VAR)
    if forced:
        if forced not in BACKENDS:
            raise ValueError(f"{ENV_VAR}/backend= must be one of {BACKENDS}, got {forced!r}")
        return (forced, "ref") if forced != "ref" else ("ref",)
    if jax.default_backend() == "tpu":
        return ("pallas-tpu", "ref")
    return ("ref",)


def _observe_dispatch(name: str, cand: str, reason: str) -> None:
    """Mirror one dispatch decision into the process-global obs pipeline
    (counter keyed by kernel/backend/reason + a ``dispatch`` event).
    Dispatch happens at trace time, so per-decision cost is per-compile,
    not per-step; the NULL_OBS default makes this a two-attribute check."""

    from repro import obs as obs_mod

    obs = obs_mod.get_default()
    if not obs.enabled:
        return
    obs.counter("dispatch_total").inc(
        labels={"kernel": name, "backend": cand, "reason": reason})
    obs.emit("dispatch", name, data={"backend": cand, "reason": reason})


def dispatch_log() -> List[Tuple[str, str, str]]:
    """Trace-time decisions so far (most recent 4096): (kernel, backend,
    reason)."""

    return list(_DISPATCH_LOG)


def clear_dispatch_log() -> None:
    _DISPATCH_LOG.clear()


def get_kernel(name: str, *, backend: Optional[str] = None) -> Callable[..., Any]:
    """A callable dispatching ``name`` per the precedence rules above.

    The returned function resolves its implementation at every call (trace
    time under jit): explicit ``backend=`` beats ``$REPRO_KERNEL_BACKEND``
    beats the platform default, and an ineligible/unregistered choice falls
    through to ``ref``."""

    if name not in _REGISTRY:
        raise ValueError(f"unknown kernel {name!r}; have {available_kernels()}")

    def dispatch(*args, **kwargs):
        per_kernel = _REGISTRY[name]
        order = backend_order(backend)
        tried = []
        for cand in order:
            if cand == "pallas-tpu" and jax.default_backend() != "tpu":
                # only a forced choice reaches here (the platform default
                # never orders pallas-tpu off a TPU): refuse it rather than
                # run something other than what was asked for
                raise RuntimeError(
                    f"kernel {name!r}: backend 'pallas-tpu' was forced "
                    f"({ENV_VAR} or backend=) but the JAX backend is "
                    f"{jax.default_backend()!r}, not 'tpu'")
            impl = per_kernel.get(cand)
            if impl is None:
                tried.append(f"{cand}:unregistered")
                continue
            if not impl.is_eligible(*args, **kwargs):
                tried.append(f"{cand}:ineligible")
                continue
            reason = "selected" if not tried else "fallback(" + ",".join(tried) + ")"
            _DISPATCH_LOG.append((name, cand, reason))
            _observe_dispatch(name, cand, reason)
            return impl.fn(*args, **kwargs)
        raise RuntimeError(  # unreachable while every kernel registers a ref impl
            f"no eligible implementation for kernel {name!r}: tried {tried}"
        )

    dispatch.__name__ = f"dispatch[{name}]"
    return dispatch


# ---------------------------------------------------------------------------
# built-in registrations: the package's support matrix (docs/kernels.md)
# ---------------------------------------------------------------------------


def _flat_inputs_ok(*arrays, **kwargs) -> bool:
    """The flat adaptation kernels pad ragged tails internally, so any
    non-empty 1-D input is tile-eligible."""

    return all(a.ndim == 1 for a in arrays) and arrays[0].size > 0


def _ce_tiles_ok(logits, targets, **kwargs) -> bool:
    """The compiled blockwise-CE kernel needs a lane-aligned vocabulary
    (V % 128) — `_pick_blocks` would otherwise fall back to BV=V, which
    defeats the VMEM streaming the kernel exists for — and rows that
    tile into f32 sublanes (R % 8), or few enough (R <= 128) to form one
    row block. Interpret mode has no such constraint (any block shape
    interprets)."""

    rows = logits.shape[0]
    return (logits.ndim == 2 and logits.shape[-1] % 128 == 0
            and (rows % 8 == 0 or rows <= 128))


_ATTN_DTYPES = ("float32", "bfloat16", "float16")


def _attn_shapes_ok(q, k, v, *args, **kwargs) -> bool:
    """Flash attention handles any S/T (pad+mask internally); the gate is
    the calling convention itself: 4-D GQA layouts with H % KV == 0 and a
    dtype the f32-accumulating kernel supports."""

    return (
        q.ndim == 4 and k.ndim == 4 and v.shape == k.shape
        and q.shape[0] == k.shape[0] and q.shape[-1] == k.shape[-1]
        and k.shape[2] > 0 and q.shape[2] % k.shape[2] == 0
        and str(q.dtype) in _ATTN_DTYPES
    )


def _attn_tpu_ok(q, k, v, *args, **kwargs) -> bool:
    """Compiled TPU tiles additionally want a lane-aligned head dim and
    sequences long enough that 128-wide q/kv tiles are not all padding."""

    return (
        _attn_shapes_ok(q, k, v, *args, **kwargs)
        and q.shape[-1] % 128 == 0
        and q.shape[1] >= 128 and k.shape[1] >= 128
    )


def _decode_shapes_ok(q, k, v, *args, **kwargs) -> bool:
    return (
        q.ndim == 4 and q.shape[1] == 1 and k.ndim == 4 and v.shape == k.shape
        and q.shape[0] == k.shape[0] and q.shape[-1] == k.shape[-1]
        and k.shape[2] > 0 and q.shape[2] % k.shape[2] == 0
        and str(q.dtype) in _ATTN_DTYPES
    )


def _decode_tpu_ok(q, k, v, *args, **kwargs) -> bool:
    return (_decode_shapes_ok(q, k, v, *args, **kwargs)
            and q.shape[-1] % 128 == 0 and k.shape[1] >= 128)


def _register_builtins() -> None:
    from repro.kernels import (adafactor_adapt, adam_adapt, flash_attn,
                               lion_adapt, ref, weighted_ce)

    # -- adam_adapt: (g, m, v, g_meta, *, t, b1, b2, eps, lr) -> (out, sumsq)
    register_kernel(
        "adam_adapt", "pallas-tpu",
        lambda *a, **k: adam_adapt.adam_adapt_product(*a, interpret=False, **k),
        eligible=_flat_inputs_ok,
    )
    register_kernel(
        "adam_adapt", "pallas-interpret",
        lambda *a, **k: adam_adapt.adam_adapt_product(*a, interpret=True, **k),
        eligible=_flat_inputs_ok,
    )
    register_kernel("adam_adapt", "ref", ref.adam_adapt_math)

    # -- lion_adapt: (g, m, g_meta, *, lr, b1, delta) -> (out, sumsq)
    register_kernel(
        "lion_adapt", "pallas-tpu",
        lambda *a, **k: lion_adapt.lion_adapt_product(*a, interpret=False, **k),
        eligible=_flat_inputs_ok,
    )
    register_kernel(
        "lion_adapt", "pallas-interpret",
        lambda *a, **k: lion_adapt.lion_adapt_product(*a, interpret=True, **k),
        eligible=_flat_inputs_ok,
    )
    register_kernel("lion_adapt", "ref", ref.lion_adapt_math)

    # -- adafactor_adapt: (vhat, g_meta, *, lr, eps) -> (out, sumsq)
    register_kernel(
        "adafactor_adapt", "pallas-tpu",
        lambda *a, **k: adafactor_adapt.adafactor_adapt_product(*a, interpret=False, **k),
        eligible=_flat_inputs_ok,
    )
    register_kernel(
        "adafactor_adapt", "pallas-interpret",
        lambda *a, **k: adafactor_adapt.adafactor_adapt_product(*a, interpret=True, **k),
        eligible=_flat_inputs_ok,
    )
    register_kernel("adafactor_adapt", "ref", ref.adafactor_adapt_math)

    # -- weighted_ce: (logits (R, V), targets (R,)) -> per-row CE (R,),
    #    differentiable (the pallas paths carry the flash-style custom VJP).
    register_kernel(
        "weighted_ce", "pallas-tpu",
        lambda logits, targets: weighted_ce.cross_entropy(logits, targets, False),
        eligible=_ce_tiles_ok,
    )
    register_kernel(
        "weighted_ce", "pallas-interpret",
        lambda logits, targets: weighted_ce.cross_entropy(logits, targets, True),
        eligible=lambda logits, targets: logits.ndim == 2,
    )
    register_kernel("weighted_ce", "ref", ref.cross_entropy)

    # -- flash_attention: (q, k, v, q_pos, kv_pos, local_flag=None, *,
    #    softcap, window, causal, chunk) -> (B, S, H, Dh); differentiable
    #    (recompute-based custom VJP on the pallas paths).
    register_kernel(
        "flash_attention", "pallas-tpu",
        lambda *a, **k: flash_attn.flash_attention(*a, interpret=False, **k),
        eligible=_attn_tpu_ok,
    )
    register_kernel(
        "flash_attention", "pallas-interpret",
        lambda *a, **k: flash_attn.flash_attention(*a, interpret=True, **k),
        eligible=_attn_shapes_ok,
    )
    register_kernel("flash_attention", "ref", flash_attn.flash_attention_ref)

    # -- flash_decode: (q, k, v, q_pos, local_flag=None, *, softcap,
    #    window) -> (B, 1, H, Dh); split-KV two-stage merge, inference-only.
    register_kernel(
        "flash_decode", "pallas-tpu",
        lambda *a, **k: flash_attn.flash_decode(*a, interpret=False, **k),
        eligible=_decode_tpu_ok,
    )
    register_kernel(
        "flash_decode", "pallas-interpret",
        lambda *a, **k: flash_attn.flash_decode(*a, interpret=True, **k),
        eligible=_decode_shapes_ok,
    )
    register_kernel("flash_decode", "ref", flash_attn.flash_decode_ref)


_register_builtins()
