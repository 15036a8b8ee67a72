"""Per-phase cost attribution of a compiled step (``repro.obs.profile``).

PR 7 wrapped every engine phase in an unconditional ``jax.named_scope``
(`trace.PHASES`), and those scope names survive lowering into each HLO
instruction's ``metadata={op_name="jit(step)/.../base_unroll/..."}``.
This module walks the compiled module text and charges every
instruction's cost to the *innermost* phase on its op_name path:

* **FLOPs** — ``dot`` = 2 x out-elements x contracted sizes (read off
  ``lhs_contracting_dims``), ``convolution`` = 2 x out x kernel/out-ch,
  reduce = input elements, elementwise/transcendental = output elements,
  pure data movement = 0. Instructions inside a scanned loop body are
  scaled by the loop's ``known_trip_count`` — including ops hidden in
  fusion computations called *from* the body
  (``hlo_parse.computation_multipliers(follow_calls=True)``).
* **Bytes moved** — operand + result bytes per instruction, counted at
  fusion boundaries only (traffic inside a fused computation stays
  on-chip and is not charged).
* **Collectives** — per-phase bytes/count, trip-scaled, same opcode set
  as ``hlo_parse.collective_stats``.
* **Live-buffer watermark** — a liveness walk over the scheduled entry
  computation (alloc at def, free after last use) yields each phase's
  peak live bytes. Buffer sizes are aval arithmetic over the printed
  shapes — the CPU-safe fallback of ``perf.memory``; loop internals are
  charged as their carried state.
* **Modules** — FLOPs per source file of the op. Under remat, jax stamps
  every op of a scanned layer with the scan's call site in
  ``transformer.py``; such an op goes to the module that opened its
  innermost model scope (``trace.BLOCKS``, see :data:`SCOPE_MODULES`).

Joining with measured per-phase wall time (``Span`` objects or dicts
named by phase, e.g. ``Tracer.runtime_spans()`` of an eager step) turns
the static counts into achieved FLOP/s and utilization against the
roofline peak (the roofline table's peak for the target chip by default).

The result dict is the optional ``attribution`` section of a
``PerfRecord`` (schema v1, additive — ``perf.record.validate_attribution``)
and the input of ``python -m repro.obs.diff``. CLI::

    PYTHONPATH=src python -m repro.obs.profile --smoke-arch gemma3-1b \
        --out attr.json        # attribute one smoke train step
    PYTHONPATH=src python -m repro.obs.profile --validate attr.json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
from collections import defaultdict
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

from repro.roofline import hlo_parse
from repro.obs.trace import PHASES, scope_names

#: phase bucket for instructions carrying no recognized phase annotation
OTHER = "other"

#: default phase vocabulary: the engine phases plus serve's fused step
DEFAULT_PHASES: Tuple[str, ...] = PHASES + ("serve_step",)

#: the module that opens each model scope (``trace.BLOCKS``, and the MoE
#: expert layer's ``moe`` inside its ``mlp``)
SCOPE_MODULES = {
    "embed": "transformer.py", "norm": "common.py",
    "attention": "attention.py", "cross_attention": "attention.py",
    "mlp": "common.py", "moe": "moe.py",
    "unembed": "transformer.py", "loss": "model.py",
}

#: where the scanned layer stacks are called: jax lowers a remat'd scan
#: body once and stamps every op inside with this file's call site
SCAN_SITE = "transformer.py"

_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"([a-zA-Z][\w\-]*)\(")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_SRC_RE = re.compile(r'source_file="([^"]*)"')
_FRAME_RE = re.compile(r"stack_frame_id=(\d+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_WINDOW_SIZE_RE = re.compile(r"window=\{[^}]*size=([0-9x]+)")
_DIM_LABELS_RE = re.compile(r"dim_labels=\w+_(\w+)->")
_OPERAND_REF_RE = re.compile(r"%([\w.\-]+)")

#: opcodes costing ~1 FLOP per output element (elementwise arithmetic,
#: comparisons, transcendentals — close enough for attribution)
ELEMENTWISE = frozenset({
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "negate",
    "abs", "sign", "compare", "select", "clamp", "exponential", "log",
    "tanh", "sqrt", "rsqrt", "power", "cosine", "sine", "logistic", "atan2",
    "remainder", "and", "or", "xor", "not", "floor", "ceil",
    "round-nearest-afz", "round-nearest-even", "exponential-minus-one",
    "log-plus-one", "is-finite", "cbrt", "tan", "erf",
})

#: opcodes whose result aliases existing buffers — no fresh allocation
#: in the watermark walk
NO_ALLOC = frozenset({"get-tuple-element", "tuple", "bitcast", "parameter"})


@dataclasses.dataclass
class Instr:
    """One parsed HLO instruction."""

    name: str               # result variable (no leading %)
    opcode: str
    type_text: str          # result type segment, layouts included
    operand_text: str       # inside the opcode's parens
    attr_text: str          # everything after the operand parens
    is_root: bool

    @property
    def out_bytes(self) -> int:
        return hlo_parse.shape_bytes(self.type_text)

    @property
    def operand_bytes(self) -> int:
        return hlo_parse.shape_bytes(self.operand_text)

    @property
    def op_name(self) -> str:
        m = _OPNAME_RE.search(self.attr_text)
        return m.group(1) if m else ""

    @property
    def source_file(self) -> str:
        m = _SRC_RE.search(self.attr_text)
        return m.group(1) if m else ""

    @property
    def stack_frame_id(self) -> Optional[int]:
        m = _FRAME_RE.search(self.attr_text)
        return int(m.group(1)) if m else None


def _split_type(rest: str) -> Tuple[str, str]:
    """Split ``f32[8,4]{1,0} add(...)`` (or a tuple type) into
    (type segment, remainder)."""

    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return rest[: i + 1], rest[i + 1:].lstrip()
    i = rest.find(" ")
    if i < 0:
        return rest, ""
    return rest[:i], rest[i + 1:].lstrip()


def parse_instructions(lines: Iterable[str]) -> List[Instr]:
    """Parse the instructions of one computation's body lines."""

    out: List[Instr] = []
    for line in lines:
        m = _INSTR_RE.match(line)
        if not m:
            continue
        rest = m.group(3)
        type_text, rem = _split_type(rest)
        mo = _OPCODE_RE.match(rem)
        if not mo:
            continue
        # operand segment: up to the paren matching the opcode's open
        depth, end = 0, len(rem)
        for i in range(mo.end() - 1, len(rem)):
            if rem[i] == "(":
                depth += 1
            elif rem[i] == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        out.append(Instr(
            name=m.group(2), opcode=mo.group(1), type_text=type_text,
            operand_text=rem[mo.end():end], attr_text=rem[end:],
            is_root=bool(m.group(1)),
        ))
    # current XLA prints operands as bare names (``dot(%a, %b)``); give
    # each its defining instruction's type, as older XLA printed it, so
    # contraction sizes and operand bytes stay readable
    types = {ins.name: ins.type_text for ins in out}
    for ins in out:
        refs = _OPERAND_REF_RE.findall(ins.operand_text)
        if refs and not hlo_parse._SHAPE_RE.search(ins.operand_text):
            ins.operand_text = ", ".join(f"{types.get(r, '')} %{r}".lstrip()
                                         for r in refs)
    return out


def _first_shape_dims(segment: str, index: int = 0) -> List[int]:
    got = hlo_parse._SHAPE_RE.findall(segment)
    dims = []
    for k, (dtype, d) in enumerate(got):
        if dtype not in hlo_parse._DTYPE_BYTES:
            continue
        dims.append([int(x) for x in d.split(",")] if d else [])
    return dims[index] if index < len(dims) else []


def _nelems(dims: Sequence[int]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def instr_flops(ins: Instr) -> float:
    """FLOP estimate for one instruction (see module docstring)."""

    op = ins.opcode
    if op == "dot":
        mc = _LHS_CONTRACT_RE.search(ins.attr_text)
        cdims = ([int(x) for x in mc.group(1).split(",")]
                 if mc and mc.group(1) else [])
        lhs = _first_shape_dims(ins.operand_text, 0)
        contracted = 1
        for d in cdims:
            contracted *= lhs[d] if d < len(lhs) else 1
        return 2.0 * _nelems(_first_shape_dims(ins.type_text)) * contracted
    if op == "convolution":
        out_elems = _nelems(_first_shape_dims(ins.type_text))
        rhs = _first_shape_dims(ins.operand_text, 1)
        kernel = _nelems(rhs)
        ml = _DIM_LABELS_RE.search(ins.attr_text)
        out_ch = 1
        if ml and rhs:
            o = ml.group(1).find("o")
            if 0 <= o < len(rhs):
                out_ch = max(1, rhs[o])
        return 2.0 * out_elems * kernel / out_ch
    if op in ("reduce", "reduce-window"):
        return float(_nelems(_first_shape_dims(ins.operand_text)))
    if op in ELEMENTWISE:
        return float(_nelems(_first_shape_dims(ins.type_text)))
    return 0.0


def phase_of(op_name: str, phases: Sequence[str]) -> str:
    """Innermost phase-name segment on an op_name scope path, so an op
    under ``.../local_terms/meta_pass/...`` charges to ``meta_pass``."""

    found = OTHER
    for seg in op_name.split("/"):
        if seg in phases:
            found = seg
    return found


def _module_of(source_file: str) -> Optional[str]:
    return source_file.rsplit("/", 1)[-1] if source_file else None


def module_of(source_file: str, op_name: str) -> Optional[str]:
    """The module an op's cost is charged to: its source file's, except
    where that is the scan call site (:data:`SCAN_SITE`), which hides the
    op's own file under remat; there the module that opened the innermost
    model scope on ``op_name`` (:data:`SCOPE_MODULES`) owns it."""

    mod = _module_of(source_file)
    if mod == SCAN_SITE:
        for name in scope_names(op_name):
            mod = SCOPE_MODULES.get(name, mod)
    return mod


def _proto_fields(buf: bytes) -> Iterator[Tuple[int, Any]]:
    """(field number, value) pairs of one serialized protobuf message:
    varints as ints, length-delimited fields as bytes."""

    def varint(i):
        out = shift = 0
        while True:
            b = buf[i]
            out |= (b & 0x7F) << shift
            shift += 7
            i += 1
            if b < 0x80:
                return out, i

    i = 0
    while i < len(buf):
        key, i = varint(i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = varint(i)
        elif wire == 2:
            n, i = varint(i)
            val, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def stack_frame_files(compiled: Any) -> Dict[int, str]:
    """``stack_frame_id`` -> source file, for one compiled module.

    Current JAX writes ``stack_frame_id=N`` into each instruction's
    metadata instead of ``source_file=``. The ids index the module's
    stack-frame table (``HloModuleProto.stack_frame_index``, field 17),
    which the printed text leaves out, so it is read from the serialized
    module proto. Frame N is the innermost user frame of the op; its
    ``file_location_id`` and that location's ``file_name_id`` are
    1-based."""

    buf = compiled.runtime_executable().hlo_modules()[0].as_serialized_hlo_module_proto()
    files: List[str] = []
    loc_file: List[int] = []
    frame_loc: List[int] = []
    for num, val in _proto_fields(buf):
        if num != 17:
            continue
        for n2, v2 in _proto_fields(val):
            if n2 == 1:
                files.append(v2.decode())
            elif n2 == 3:
                loc_file.append(dict(_proto_fields(v2)).get(1, 0))
            elif n2 == 4:
                frame_loc.append(dict(_proto_fields(v2)).get(1, 0))
    out = {}
    for fid, loc in enumerate(frame_loc, start=1):
        if 0 < loc <= len(loc_file) and 0 < loc_file[loc - 1] <= len(files):
            out[fid] = files[loc_file[loc - 1] - 1]
    return out


def _collective_opcode(op: str) -> Optional[str]:
    if op.endswith("-start"):
        op = op[: -len("-start")]
    return op if op in hlo_parse.COLLECTIVES else None


def _entry_watermark(instrs: List[Instr],
                     phases: Sequence[str]) -> Dict[str, float]:
    """Per-phase peak live bytes over the scheduled entry computation:
    alloc at def, free past the last use. Aval arithmetic on printed
    shapes; aliasing opcodes (gte/tuple/bitcast) allocate nothing."""

    size: Dict[str, int] = {}
    last_use: Dict[str, int] = {}
    for idx, ins in enumerate(instrs):
        size[ins.name] = 0 if ins.opcode in NO_ALLOC else ins.out_bytes
        for ref in _OPERAND_REF_RE.findall(ins.operand_text):
            last_use[ref] = idx
    frees: Dict[int, List[str]] = defaultdict(list)
    for ref, idx in last_use.items():
        frees[idx].append(ref)
    live = 0.0
    peaks: Dict[str, float] = {}
    for idx, ins in enumerate(instrs):
        live += size[ins.name]
        ph = phase_of(ins.op_name, phases)
        peaks[ph] = max(peaks.get(ph, 0.0), live)
        for ref in frees[idx]:
            live -= size.get(ref, 0)
        if ins.name not in last_use and not ins.is_root:
            live -= size[ins.name]  # dead result: freed immediately
    return peaks


def _wall_by_phase(spans) -> Dict[str, float]:
    """Total measured wall µs per span name (runtime spans only)."""

    out: Dict[str, float] = defaultdict(float)
    for s in spans:
        if getattr(s, "traced", False):
            continue
        dur = s.dur_us if hasattr(s, "dur_us") else float(s.get("dur_us", 0.0))
        name = s.name if hasattr(s, "name") else s.get("name")
        out[name] += dur
    return dict(out)


def attribute(compiled_or_text: Any, *, phases: Optional[Sequence[str]] = None,
              spans: Optional[Sequence[Any]] = None,
              peak_flops: Optional[float] = None,
              n_devices: int = 1) -> Dict[str, Any]:
    """Partition one compiled program's cost by engine phase.

    ``compiled_or_text`` is a ``jax.stages.Compiled`` (or anything with
    ``as_text()``) or the HLO module text itself. ``spans`` (optional)
    are measured ``Tracer`` spans — when given, each phase also carries
    ``wall_us``/``achieved_flops_per_s``/``utilization`` against
    ``peak_flops`` x ``n_devices`` (default: the roofline model's
    per-chip bf16 peak). Returns the ``attribution`` PerfRecord section.
    """

    text = (compiled_or_text if isinstance(compiled_or_text, str)
            else compiled_or_text.as_text())
    frame_files = (stack_frame_files(compiled_or_text)
                   if hasattr(compiled_or_text, "runtime_executable") else {})
    phases = tuple(phases) if phases is not None else DEFAULT_PHASES
    if peak_flops is None:
        from repro.roofline.analysis import TARGET_DEVICE_KIND, device_peaks
        peak_flops = device_peaks(TARGET_DEVICE_KIND).flops

    comps = hlo_parse.split_computations(text)
    mult = hlo_parse.computation_multipliers(comps, follow_calls=True)

    entry_name = None
    for name, lines in comps.items():
        if name != "__entry__" and comps.get("__entry__") is lines:
            entry_name = name
            break

    zero = lambda: {"flops": 0.0, "bytes": 0.0,
                    "collective_bytes": 0.0, "collective_count": 0.0}
    per_phase: Dict[str, Dict[str, float]] = defaultdict(zero)
    per_module: Dict[str, float] = defaultdict(float)

    for cname, lines in comps.items():
        if cname == "__entry__":
            continue
        m = mult.get(cname, 1.0)
        if m == 0.0:
            continue  # unreachable computation
        in_fusion = cname.startswith("fused_computation")
        for ins in parse_instructions(lines):
            ph = phase_of(ins.op_name, phases)
            bucket = per_phase[ph]
            flops = instr_flops(ins) * m
            bucket["flops"] += flops
            if not in_fusion:
                bucket["bytes"] += (ins.out_bytes + ins.operand_bytes) * m
            coll = _collective_opcode(ins.opcode)
            if coll is not None and not ins.opcode.endswith("-done"):
                bucket["collective_bytes"] += ins.out_bytes * m
                bucket["collective_count"] += m
            if flops:
                mod = module_of(ins.source_file
                                or frame_files.get(ins.stack_frame_id, ""),
                                ins.op_name)
                if mod:
                    per_module[mod] += flops

    total = {k: sum(b[k] for b in per_phase.values())
             for k in ("flops", "bytes", "collective_bytes", "collective_count")}
    total_flops = total["flops"]
    for b in per_phase.values():
        b["flop_frac"] = b["flops"] / total_flops if total_flops else 0.0
    coverage = (1.0 - per_phase[OTHER]["flops"] / total_flops
                if total_flops and OTHER in per_phase else
                (1.0 if total_flops else 0.0))

    if entry_name is not None:
        peaks = _entry_watermark(parse_instructions(comps[entry_name]), phases)
        for ph, peak in peaks.items():
            per_phase[ph]["peak_live_bytes"] = peak

    wall_source = None
    if spans is not None:
        wall = _wall_by_phase(spans)
        wall_source = "tracer_runtime_spans"
        device_peak = peak_flops * max(1, n_devices)
        for ph, b in per_phase.items():
            us = wall.get(ph)
            if us is None or us <= 0:
                continue
            b["wall_us"] = us
            b["achieved_flops_per_s"] = b["flops"] / (us * 1e-6)
            b["utilization"] = b["achieved_flops_per_s"] / device_peak

    modules = {}
    for mod, fl in sorted(per_module.items(), key=lambda kv: -kv[1]):
        modules[mod] = {"flops": fl,
                        "flop_frac": fl / total_flops if total_flops else 0.0}
    top_module = next(iter(modules), None)

    return {
        "phases": {ph: dict(b) for ph, b in sorted(
            per_phase.items(), key=lambda kv: -kv[1]["flops"])},
        "total": total,
        "coverage": coverage,
        "modules": modules,
        "top_module": top_module,
        "wall_source": wall_source,
        "memory_source": "hlo_entry_walk",
        "peak_flops": peak_flops,
        "n_devices": int(n_devices),
    }


def render(attr: Dict[str, Any]) -> str:
    """Human-readable attribution table."""

    lines: List[str] = []
    add = lines.append
    add("== cost attribution ==")
    add(f"coverage: {attr['coverage']:.1%} of "
        f"{attr['total']['flops']:.3e} FLOPs attributed to a phase")
    add(f"{'phase':<16} {'flops':>12} {'frac':>7} {'bytes':>12} "
        f"{'coll':>5} {'peak_live':>12} {'wall':>10} {'util':>8}")
    for ph, b in attr["phases"].items():
        wall = f"{b['wall_us'] / 1e3:.1f}ms" if b.get("wall_us") else "-"
        util = f"{b['utilization']:.2e}" if b.get("utilization") else "-"
        peak = (f"{b['peak_live_bytes'] / 2**20:.1f}MB"
                if b.get("peak_live_bytes") else "-")
        add(f"{ph:<16} {b['flops']:>12.3e} {b['flop_frac']:>7.3f} "
            f"{b['bytes']:>12.3e} {b['collective_count']:>5.0f} "
            f"{peak:>12} {wall:>10} {util:>8}")
    if attr.get("modules"):
        add("")
        add(f"{'module':<28} {'flops':>12} {'frac':>7}")
        for mod, b in list(attr["modules"].items())[:10]:
            add(f"{mod:<28} {b['flops']:>12.3e} {b['flop_frac']:>7.3f}")
        add(f"top FLOP sink: {attr['top_module']}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# CLI: smoke-probe one arch / validate attribution sections in a JSON
# ---------------------------------------------------------------------------


def _smoke_attribution(arch: str, *, unroll: int = 2, batch: int = 4,
                       seq: int = 32) -> Dict[str, Any]:
    """Compile one smoke-config SAMA step for ``arch`` and attribute it.
    Pure compile — nothing executes, so even the MoE configs stay fast."""

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs, data, optim
    from repro.core import EngineConfig, init_state, make_meta_step, problems
    from repro.models import Model

    cfg = configs.get_smoke_config(arch)
    model = Model(cfg)
    spec = problems.make_data_optimization_spec(
        model.classifier_per_example if cfg.family == "encoder"
        else model.per_example, reweight=True)
    theta = model.init(jax.random.PRNGKey(0))
    lam = problems.init_data_optimization_lam(jax.random.PRNGKey(1),
                                              reweight=True)
    base_opt, meta_opt = optim.adam(1e-3), optim.adam(1e-3)
    ecfg = EngineConfig(method="sama", unroll_steps=unroll)
    state = init_state(theta, lam, base_opt, meta_opt, scale=ecfg.scale)
    step = make_meta_step(spec, base_opt, meta_opt, ecfg)

    lm = data.LMStreamConfig(vocab_size=cfg.vocab_size, seq_len=seq)
    rng = np.random.default_rng(0)

    def batch_of(b, k=None):
        raw = data.lm_batch(lm, rng, b * (k or 1))
        toks = raw["tokens"].reshape((k, b, seq) if k else (b, seq))
        out = {"tokens": jnp.asarray(toks)}
        if cfg.family == "vlm":
            shp = ((k, b) if k else (b,)) + (cfg.vision_tokens, cfg.vision_dim)
            out["patches"] = jnp.zeros(shp, jnp.float32)
        if cfg.family == "audio":
            shp = ((k, b) if k else (b,)) + (cfg.encoder_seq, cfg.d_model)
            out["frames"] = jnp.zeros(shp, jnp.float32)
        if cfg.family == "encoder":
            yshape = (k, b) if k else (b,)
            out["y"] = jnp.asarray(rng.integers(0, cfg.num_labels, size=yshape),
                                   jnp.int32)
        return out

    compiled = jax.jit(step).lower(state, batch_of(batch, unroll),
                                   batch_of(max(batch // 2, 1))).compile()
    attr = attribute(compiled)
    attr_extra = {"arch": cfg.name, "unroll": unroll, "batch": batch, "seq": seq}
    return {"attribution": attr, "extra": attr_extra}


def _validate_file(path: str) -> List[str]:
    """Validate every attribution section found in ``path`` (a BENCH
    payload, a PerfRecord dict, or a bare attribution dict)."""

    from repro.perf.record import validate_attribution

    with open(path) as f:
        payload = json.load(f)
    found = []
    if "records" in payload:  # BENCH file
        found = [(r.get("name", "?"), r["attribution"])
                 for r in payload["records"] if r.get("attribution")]
    elif "attribution" in payload:
        found = [(payload.get("name", "record"), payload["attribution"])]
    elif "phases" in payload:
        found = [("attribution", payload)]
    if not found:
        return [f"{path}: no attribution section found"]
    errors: List[str] = []
    for name, attr in found:
        errors.extend(f"{path}:{name}: {e}" for e in validate_attribution(attr))
    return errors


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs.profile",
        description="Attribute a compiled step's cost to engine phases.")
    ap.add_argument("--smoke-arch", default=None, metavar="ARCH",
                    help="compile one smoke SAMA step for ARCH and print "
                         "its attribution table")
    ap.add_argument("--unroll", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="also write the attribution (with a validated "
                         "'attribution' key) as JSON")
    ap.add_argument("--validate", default=None, metavar="PATH",
                    help="validate attribution sections in a record/BENCH "
                         "JSON and exit")
    args = ap.parse_args(argv)

    if args.validate:
        errors = _validate_file(args.validate)
        for e in errors:
            print(e, file=sys.stderr)
        print(f"{args.validate}: attribution "
              + ("INVALID" if errors else "valid"))
        return 1 if errors else 0

    if not args.smoke_arch:
        ap.error("one of --smoke-arch or --validate is required")
    probe = _smoke_attribution(args.smoke_arch, unroll=args.unroll,
                               batch=args.batch)
    print(render(probe["attribution"]))
    if args.out:
        from repro.perf.record import validate_attribution
        errors = validate_attribution(probe["attribution"])
        if errors:
            for e in errors:
                print(e, file=sys.stderr)
            return 1
        with open(args.out, "w") as f:
            json.dump(probe, f, indent=1)
        print(f"# wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
