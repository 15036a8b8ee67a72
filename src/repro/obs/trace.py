"""Named scopes over the meta step, host spans, and Chrome-trace export.

Three layers cooperate here, and keeping them straight is what makes the
"byte-identical HLO when disabled" guarantee hold (tests/test_obs.py):

1. **In-graph phase names** — :func:`phase` wraps each engine phase in
   ``jax.named_scope`` *unconditionally*. named_scope only attaches
   name metadata to the ops traced under it; it is applied whether or
   not observability is on, so the lowered HLO text is identical either
   way (and the `unroll+1` collective census is untouched).
2. **In-graph block names** — :func:`block` is the same bare
   ``jax.named_scope`` one level down, on each model block
   (:data:`BLOCKS`) and on each stack of an encoder-decoder (``encoder``,
   ``decoder``). It never records a host span: under ``jax.jit`` one
   would only time tracing.
   Both scope levels reach each compiled op's ``op_name``, so a device
   trace of the jitted step can be split by phase and by block. Inside a
   remat'd ``lax.scan`` body a block keeps its plain name; outside one, a
   backward op carries it inside JAX's transform wrappers, e.g.
   ``transpose(jvp(loss))`` (:func:`block_of` strips them).
3. **Host span capture** — when a :class:`Tracer` is activated (a
   contextvar, see :func:`activate`), :func:`phase` ALSO records a host
   wall-time span and enters ``jax.profiler.TraceAnnotation`` so native
   JAX profiles carry the same labels. With no tracer active the extra
   cost is one contextvar read at Python execution time — which for
   jitted code means once per compilation, not per step.

Spans are stamped on the profiler's own clock (:func:`profiler_clock_ns`),
so a span's start lines up with its TraceMe event, and with device ops, in
a ``jax.profiler`` capture. A span recorded while jax was tracing measures
tracing cost and is tagged ``traced=True``.

Spans nest: ``depth`` and ``parent`` reconstruct the tree, and
:func:`chrome_trace` emits ``traceEvents`` (``ph="X"``, µs timestamps)
loadable in chrome://tracing or Perfetto.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import json
import os
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence

#: Engine phase names, in execution order. Used by report.py to order
#: the span table; emitting other names is fine.
PHASES = (
    "base_unroll",      # K-step inner unroll (core/engine._unroll_base)
    "local_terms",      # per-method local hypergrad terms (any method);
                        # SAMA's meta_pass/cd_passes nest inside it
    "meta_pass",        # SAMA perturbation direction (core/sama.py)
    "cd_passes",        # central-difference hypergradient passes
    "finalize",         # method.finalize / hypergrad assembly
    "meta_update",      # guarded_meta_update (gate + optimizer apply)
    "allreduce_flat",   # flat-bucket all-reduce (launch/distributed.py)
)

#: Model block names (:func:`block`), in forward order. A device op is
#: charged to the innermost block on its ``op_name`` path; an op on no
#: block's path (residual adds, optimizer updates, scan bookkeeping) is
#: charged to none.
BLOCKS = (
    "embed",            # token (+ position) embedding (models/transformer._embed)
    "norm",             # LayerNorm / RMSNorm (models/common.apply_norm)
    "attention",        # self-attention: QKV/O projections + score/softmax/AV
                        # (models/attention.self_attention, mla_attention)
    "cross_attention",  # encoder-decoder attention (attention.cross_attention, cross_kv)
    "mlp",              # dense MLP / GLU and the MoE expert layer
                        # (models/common.apply_mlp, models/moe.apply_moe)
    "unembed",          # vocabulary projection or classifier head
    "loss",             # per-token / per-example cross-entropy (models/model.py)
)


@dataclasses.dataclass
class Span:
    name: str
    start_s: float          # profiler-clock seconds (profiler_clock_ns)
    dur_s: float
    depth: int
    parent: Optional[str]
    traced: bool            # True if recorded while jax was tracing (compile-time span)
    step: Optional[int] = None

    @property
    def dur_us(self) -> float:
        return self.dur_s * 1e6

    def as_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "start_s": self.start_s, "dur_s": self.dur_s,
                "dur_us": self.dur_us, "depth": self.depth, "parent": self.parent,
                "traced": self.traced, "step": self.step}


def profiler_clock_ns() -> int:
    """The clock ``jax.profiler`` stamps TraceMe events with: the realtime
    clock. A capture's event times are offsets from its
    ``profile_start_time`` (a stat of its ``Task Environment`` plane),
    which is on this clock too."""

    return time.time_ns()


def _in_jax_trace() -> bool:
    import jax
    return not jax.core.trace_ctx.is_top_level()


class Tracer:
    """Collects nested spans; optionally mirrors each completed span as
    a ``span`` event into an obs pipeline."""

    def __init__(self, obs=None, use_profiler: bool = True):
        self.spans: List[Span] = []
        self._stack: List[str] = []
        self._obs = obs
        self._use_profiler = use_profiler
        self.step: Optional[int] = None  # callers set this per step for labeling

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        annotation = None
        if self._use_profiler:
            try:
                import jax
                annotation = jax.profiler.TraceAnnotation(name)
            except Exception:  # pragma: no cover - profiler unavailable
                annotation = None
        parent = self._stack[-1] if self._stack else None
        depth = len(self._stack)
        self._stack.append(name)
        traced = _in_jax_trace()
        t0 = profiler_clock_ns()
        try:
            if annotation is not None:
                with annotation:
                    yield
            else:
                yield
        finally:
            dur = (profiler_clock_ns() - t0) / 1e9
            self._stack.pop()
            sp = Span(name=name, start_s=t0 / 1e9, dur_s=dur, depth=depth,
                      parent=parent, traced=traced, step=self.step)
            self.spans.append(sp)
            if self._obs is not None and self._obs.enabled:
                self._obs.emit("span", name, data={
                    "dur_us": sp.dur_us, "depth": depth, "parent": parent,
                    "traced": traced}, step=self.step)

    def runtime_spans(self) -> List[Span]:
        """Spans measured during real execution (not jit tracing)."""

        return [s for s in self.spans if not s.traced]

    def clear(self) -> None:
        self.spans.clear()


_ACTIVE: "contextvars.ContextVar[Optional[Tracer]]" = contextvars.ContextVar(
    "repro_obs_tracer", default=None)


def active_tracer() -> Optional[Tracer]:
    return _ACTIVE.get()


@contextlib.contextmanager
def activate(tracer: Tracer) -> Iterator[Tracer]:
    """Make ``tracer`` the target of :func:`phase` spans in this context."""

    token = _ACTIVE.set(tracer)
    try:
        yield tracer
    finally:
        _ACTIVE.reset(token)


@contextlib.contextmanager
def phase(name: str) -> Iterator[None]:
    """Annotate an engine phase.

    Always applies ``jax.named_scope(name)`` (metadata-only, identical
    HLO with obs on or off). Additionally records a host span iff a
    Tracer is activated in the current context.
    """

    try:
        import jax
        scope = jax.named_scope(name)
    except Exception:  # pragma: no cover - jax absent
        scope = contextlib.nullcontext()
    tracer = _ACTIVE.get()
    with scope:
        if tracer is None:
            yield
        else:
            with tracer.span(name):
                yield


def block(name: str):
    """Name a model block (:data:`BLOCKS`), a stack of an encoder-decoder
    (whisper's ``encoder`` and ``decoder``, so a block's path says which
    stack it ran in) or a finer scope inside a block (the MoE expert
    layer's ``moe``): a bare ``jax.named_scope``, metadata only and always
    on. Unlike :func:`phase` it records no host span."""

    import jax

    return jax.named_scope(name)


def scope_names(op_name: str) -> List[str]:
    """The components of an ``op_name`` scope path, outermost first, with
    JAX's transform wrappers stripped (``transpose(jvp(loss))`` reads as
    ``loss``). Of a fused op's ``;``-joined names the first is read."""

    out = []
    for seg in op_name.split(";", 1)[0].split("/"):
        while seg.endswith(")") and "(" in seg:
            seg = seg[seg.index("(") + 1:-1]
        out.append(seg)
    return out


def block_of(op_name: str) -> Optional[str]:
    """The innermost :data:`BLOCKS` name on an ``op_name`` scope path
    (:func:`scope_names`); None when the path names no block."""

    found = None
    for name in scope_names(op_name):
        if name in BLOCKS:
            found = name
    return found


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------


def chrome_trace(spans: Sequence[Span]) -> Dict[str, Any]:
    """Render spans as a Chrome-trace/Perfetto ``traceEvents`` document.

    Complete events (``ph="X"``) with µs timestamps relative to the
    earliest span; trace-time spans land on a separate "tid" row so
    compile-time work is visually distinct from runtime phases.
    """

    events: List[Dict[str, Any]] = []
    t0 = min((s.start_s for s in spans), default=0.0)
    for s in spans:
        events.append({
            "name": s.name,
            "ph": "X",
            "ts": (s.start_s - t0) * 1e6,
            "dur": s.dur_us,
            "pid": 0,
            "tid": 1 if s.traced else 0,
            "args": {k: v for k, v in (("step", s.step), ("parent", s.parent),
                                       ("traced", s.traced)) if v is not None},
        })
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "metadata": {"producer": "repro.obs.trace", "schema": 1},
    }


def lane_chrome_events(events: Sequence[Any]) -> List[Dict[str, Any]]:
    """Per-decode-lane request tracks from a serve event stream.

    Consumes the lifecycle events the serving executor emits (admitted /
    first_token / terminal, each carrying a ``trace_id``) and renders one
    Chrome-trace row per decode lane (``pid=1``, ``tid=slot``) with a
    request span from admission to its terminal event. Load next to the
    tick spans in Perfetto and the lane occupancy/goodput picture is the
    timeline itself: gaps are trash-page ticks.
    """

    TERMINALS = ("done", "deadline_miss", "shed", "rejected", "error")
    # trace_id -> {start, end, slot, status, tokens, request_id}
    reqs: Dict[str, Dict[str, Any]] = {}
    for e in events:
        if e.kind != "serve":
            continue
        tid = e.data.get("trace_id")
        if tid is None:
            continue
        r = reqs.setdefault(tid, {"start": None, "end": None, "slot": None,
                                  "status": None, "tokens": None,
                                  "request_id": e.data.get("request_id")})
        if e.name == "admitted" and r["start"] is None:
            r["start"] = e.t
        elif e.name == "first_token":
            r["slot"] = e.data.get("slot", r["slot"])
            if r["start"] is None:
                r["start"] = e.t
        elif e.name in TERMINALS:
            r["end"] = e.t
            r["status"] = e.data.get("status", e.name)
            r["tokens"] = e.data.get("tokens")
            if r["slot"] is None:
                r["slot"] = e.data.get("slot")

    spans = [(tid, r) for tid, r in reqs.items()
             if r["slot"] is not None and r["start"] is not None
             and r["end"] is not None]
    if not spans:
        return []
    t0 = min(r["start"] for _, r in spans)
    out: List[Dict[str, Any]] = [
        {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
         "args": {"name": "serve lanes"}},
    ]
    for slot in sorted({r["slot"] for _, r in spans}):
        out.append({"name": "thread_name", "ph": "M", "pid": 1, "tid": slot,
                    "args": {"name": f"lane {slot}"}})
    for tid, r in sorted(spans, key=lambda kv: kv[1]["start"]):
        out.append({
            "name": f"req {r['request_id']}" if r["request_id"] is not None
            else f"req {tid[:8]}",
            "ph": "X",
            "ts": (r["start"] - t0) * 1e6,
            "dur": max(0.0, (r["end"] - r["start"]) * 1e6),
            "pid": 1,
            "tid": r["slot"],
            "args": {k: v for k, v in (("trace_id", tid),
                                       ("status", r["status"]),
                                       ("tokens", r["tokens"]))
                     if v is not None},
        })
    return out


def write_chrome_trace(path: str, spans: Sequence[Span],
                       extra_events: Optional[Sequence[Dict[str, Any]]] = None
                       ) -> str:
    """Write a Chrome-trace document for ``spans``; ``extra_events`` are
    appended to ``traceEvents`` verbatim (e.g. :func:`lane_chrome_events`
    request tracks)."""

    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    doc = chrome_trace(spans)
    if extra_events:
        doc["traceEvents"].extend(extra_events)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    return path


def span_tree_summary(spans: Sequence[Span]) -> List[Dict[str, Any]]:
    """Aggregate spans by name → {name, n, total_us, mean_us, max_us,
    depth, parent}, ordered by PHASES then first appearance. Used by the
    report CLI's per-phase table."""

    order: List[str] = []
    agg: Dict[str, Dict[str, Any]] = {}
    for s in spans:
        if s.name not in agg:
            order.append(s.name)
            agg[s.name] = {"name": s.name, "n": 0, "total_us": 0.0,
                           "max_us": 0.0, "depth": s.depth, "parent": s.parent}
        a = agg[s.name]
        a["n"] += 1
        a["total_us"] += s.dur_us
        a["max_us"] = max(a["max_us"], s.dur_us)
    for a in agg.values():
        a["mean_us"] = a["total_us"] / a["n"]

    def _rank(name: str) -> tuple:
        try:
            return (0, PHASES.index(name))
        except ValueError:
            return (1, order.index(name))

    return [agg[name] for name in sorted(agg, key=_rank)]
