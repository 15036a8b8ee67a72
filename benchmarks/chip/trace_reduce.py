"""From a profiler trace (``.xplane.pb``) to device time.

``load`` reads the trace with ``jax.profiler.ProfileData`` alone: for each
TPU device, the operations it ran (HLO op name, start, duration, and the
scope path of the JAX code that made it, and for a Pallas kernel the source
files it was written in), and the host spans the harness wrote with
``TraceAnnotation``. The scope path comes from the event's own ``tf_op``
stat where the trace carries one, and otherwise from a table of HLO op name
to ``op_name`` metadata read from the compiled program; the kernel's files
from the compiled program's Mosaic custom calls (``kernels_from_hlo``),
since every Pallas call's ``op_name`` ends in the same ``pallas_call``.

The functions below it reduce one device's operations:

* ``busy_ns``: the union of the operations' intervals;
* ``scope_ns``: device time per phase scope (the innermost of ``PHASES`` on
  the operation's path);
* ``match_ns``: device time of the operations a predicate picks;
* ``exposed_ns``: the part of those operations' intervals during which no
  other operation runs on that device;
* ``idle_gaps``: the device's idle gaps inside the window, each named by
  the host span that covers most of it.

On a TPU an event's name is the HLO instruction's text (``%fusion.12 =
f32[...] fusion(...)``). A ``while``, ``call`` or ``conditional`` event
spans the operations of its body, which the trace lists as events of their
own: such containers count towards busy time but not towards the time of
any scope or kernel, and an operation whose own path names no phase takes
the phase of the innermost container around it.
"""

from __future__ import annotations

import base64
import binascii
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

#: the engine's phase scopes (``repro.obs.trace.phase``), outermost first
PHASES = ("base_unroll", "local_terms", "meta_pass", "cd_passes", "finalize",
          "meta_update", "allreduce_flat")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


CONTAINERS = ("while", "call", "conditional")
_INSTR = re.compile(r"^%?([\w.\-]+) = .*?\s([\w\-]+)\(")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start_ns: float
    dur_ns: float
    path: str = ""
    opcode: str = ""
    kernel: Tuple[str, ...] = ()

    @property
    def container(self) -> bool:
        return self.opcode in CONTAINERS

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    devices: Dict[int, List[Op]]
    host: List[Op]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _stats(event) -> Dict[str, object]:
    out = {}
    for item in event.stats:
        k, v = item
        out[k] = v
    return out


def load(path: str, op_paths: Optional[Dict[str, str]] = None,
         host_names: Sequence[str] = (), kernels: Optional[Dict[str, Tuple[str, ...]]] = None) -> Trace:
    """Device operations and the named host spans of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, List[Op]] = {}
    host: List[Op] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    st = _stats(e)
                    im = _INSTR.match(e.name)
                    name, opcode = (im.group(1), im.group(2)) if im else (e.name, "")
                    p = str(st.get("tf_op") or (op_paths or {}).get(name, ""))
                    ops.append(Op(name, float(e.start_ns), float(e.duration_ns), p, opcode,
                                  tuple((kernels or {}).get(name, ()))))
            devices[int(m.group(1))] = _inherit_phases(sorted(ops, key=lambda o: o.start_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host_names:
                        host.append(Op(e.name, float(e.start_ns), float(e.duration_ns)))
    return Trace(devices=devices, host=sorted(host, key=lambda o: o.start_ns))


def _inherit_phases(ops: List[Op]) -> List[Op]:
    """Give an operation with no phase on its path the path of the innermost
    container around it that has one."""
    out, stack = [], []
    for o in ops:
        while stack and stack[-1].end_ns <= o.start_ns:
            stack.pop()
        if phase_of(o.path) is None:
            for c in reversed(stack):
                if phase_of(c.path) is not None:
                    o = dataclasses.replace(o, path=c.path)
                    break
        if o.container:
            stack.append(o)
        out.append(o)
    return out


def op_paths_from_hlo(hlo_text: str) -> Dict[str, str]:
    """HLO op name -> its ``op_name`` metadata, from a compiled module's text."""
    table = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*op_name=\"([^\"]*)\"",
                         hlo_text, re.M):
        table[m.group(1)] = m.group(2)
    return table


_CUSTOM_CALL = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*custom_call_target=\"tpu_custom_call\"",
                          re.M)
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_SOURCE = re.compile(rb"([A-Za-z_][\w\-]*)\.py")


def kernels_from_hlo(hlo_text: str) -> Dict[str, Tuple[str, ...]]:
    """HLO op name -> the source files (stems, sorted) named in the
    locations of the Mosaic kernel it runs, for each ``tpu_custom_call`` of
    a compiled module's text. ``adam_adapt`` among them marks the fused
    Adam adaptation kernel; a weighted cross-entropy kernel names
    ``weighted_ce`` and not it."""
    table = {}
    for line in hlo_text.splitlines():
        m = _CUSTOM_CALL.match(line)
        body = _BODY.search(line) if m else None
        if body is None:
            continue
        try:
            raw = base64.b64decode(body.group(1))
        except (binascii.Error, ValueError):
            continue
        table[m.group(1)] = tuple(sorted({f.decode() for f in _SOURCE.findall(raw)}))
    return table


def _merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(ops: Sequence[Op]) -> float:
    return sum(b - a for a, b in _merge((o.start_ns, o.end_ns) for o in ops))


def window_ns(ops: Sequence[Op]) -> float:
    """From the first operation's start to the last one's end."""
    if not ops:
        return 0.0
    return max(o.end_ns for o in ops) - min(o.start_ns for o in ops)


def phase_of(path: str) -> Optional[str]:
    parts = path.split("/")
    hit = None
    for name in PHASES:
        if name in parts:
            hit = name if hit is None or PHASES.index(name) > PHASES.index(hit) else hit
    return hit


def scope_ns(ops: Sequence[Op]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for o in ops:
        if o.container:
            continue
        ph = phase_of(o.path) or "other"
        out[ph] = out.get(ph, 0.0) + o.dur_ns
    return out


def match_ns(ops: Sequence[Op], pick: Callable[[Op], bool]) -> float:
    return sum(o.dur_ns for o in ops if not o.container and pick(o))


def exposed_ns(ops: Sequence[Op], pick: Callable[[Op], bool]) -> float:
    """Time in the picked operations' intervals that no other operation
    covers."""
    picked = _merge((o.start_ns, o.end_ns) for o in ops if not o.container and pick(o))
    others = _merge((o.start_ns, o.end_ns) for o in ops if not o.container and not pick(o))
    total = 0.0
    for a, b in picked:
        covered = 0.0
        for c, d in others:
            if d <= a:
                continue
            if c >= b:
                break
            covered += min(b, d) - max(a, c)
        total += (b - a) - covered
    return total


def top_ops(ops: Sequence[Op], n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` operations (by HLO name, with their scope) that took most
    device time, in seconds."""
    acc: Dict[str, float] = {}
    for o in ops:
        if o.container:
            continue
        key = f"{o.name} [{phase_of(o.path) or 'other'}]"
        acc[key] = acc.get(key, 0.0) + o.dur_ns
    return [(k, v / 1e9) for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops: Sequence[Op], host: Sequence[Op], n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps between device operations, in seconds,
    each named by the host span that overlaps it most (``host idle`` where
    none does)."""
    busy = _merge((o.start_ns, o.end_ns) for o in ops)
    gaps = [(b0[1], b1[0]) for b0, b1 in zip(busy, busy[1:]) if b1[0] > b0[1]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        best, best_ov = "host idle", 0.0
        for h in host:
            ov = min(b, h.end_ns) - max(a, h.start_ns)
            if ov > best_ov:
                best, best_ov = h.name, ov
        out.append((best, (b - a) / 1e9))
    return out
