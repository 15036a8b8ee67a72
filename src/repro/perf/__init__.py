"""Measured-performance telemetry: timers, memory accounting, collective
census, the versioned PerfRecord schema, and the baseline regression gate.

The paper's headline claims are systems numbers (throughput, memory,
collective count). ``repro.roofline`` predicts them analytically; this
package MEASURES them — every benchmark, example and the MetaLearner
facade reports through it, and CI gates the results against committed
baselines (gate.py). See DESIGN.md §9.

    from repro import perf

    m = perf.measure(jitted_step, state, bb, mb)          # warmup/repeat/block
    rec = perf.profile_step("sama", jitted_step, state, bb, mb,
                            samples_per_step=batch * unroll)
    rec.as_dict()  # -> PerfRecord JSON (timing + memory + collectives)
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from repro.perf.collectives import census, census_of, verify_single_sync
from repro.perf.gate import GateReport, Tolerance, compare_dirs, compare_record
from repro.perf.memory import (
    MemoryStats,
    compiled_memory,
    device_memory,
    memory_report,
    tree_bytes,
)
from repro.perf.record import (
    SCHEMA_VERSION,
    PerfRecord,
    bench_payload,
    env_info,
    load_bench,
    validate_attribution,
    validate_bench,
    validate_record,
    write_bench,
    write_json_atomic,
)
from repro.perf.timers import (
    LatencyStats,
    StepMeasurement,
    TimingStats,
    compile_split,
    measure,
    time_callable,
)


def profile_step(name: str, fn, *args, samples_per_step: Optional[float] = None,
                 warmup: int = 2, repeats: int = 5,
                 extra: Optional[Dict[str, Any]] = None,
                 attribution: bool = False,
                 attribution_spans=None) -> PerfRecord:
    """The full protocol on one step function: compile split + run timing
    + per-device memory + trip-scaled collective census, as a PerfRecord.
    Call under the owning mesh context when the step is sharded.

    ``attribution=True`` additionally partitions the compiled HLO's
    FLOPs/bytes/collectives by engine phase (``repro.obs.profile``) into
    the record's optional ``attribution`` section;
    ``attribution_spans`` (measured ``Tracer`` spans, e.g. of one eager
    step under an activated tracer) joins per-phase wall time and
    roofline utilization into it."""

    m = measure(fn, *args, warmup=warmup, repeats=repeats)
    mem = coll = None
    if m.compiled is not None:
        mem = memory_report(m.compiled, example_args=args)
        coll = census(m.compiled)
    rec = PerfRecord.from_measurement(
        name, m, samples_per_step=samples_per_step, memory=mem,
        collectives=coll, extra=extra,
    )
    if attribution and m.compiled is not None:
        from repro.obs import profile as profile_mod  # lazy: obs imports perf

        rec.attribution = profile_mod.attribute(m.compiled,
                                                spans=attribution_spans)
    return rec


__all__ = [
    "GateReport", "LatencyStats", "MemoryStats", "PerfRecord", "SCHEMA_VERSION",
    "StepMeasurement", "TimingStats", "Tolerance",
    "bench_payload", "census", "census_of", "compare_dirs", "compare_record",
    "compile_split", "compiled_memory", "device_memory", "env_info",
    "load_bench", "measure", "memory_report", "profile_step", "time_callable",
    "tree_bytes", "validate_attribution", "validate_bench", "validate_record",
    "verify_single_sync", "write_bench", "write_json_atomic",
]
