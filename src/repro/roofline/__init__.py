"""Roofline analysis from compiled dry-run artifacts (per-device peaks
keyed by ``device_kind``)."""

from repro.roofline.analysis import (
    DEVICE_PEAKS,
    TARGET_DEVICE_KIND,
    DevicePeaks,
    Roofline,
    analyze,
    device_peaks,
    forward_flops,
    param_counts,
    step_bytes,
    step_flops,
)
from repro.roofline.hlo_parse import collective_stats

__all__ = [
    "DEVICE_PEAKS", "TARGET_DEVICE_KIND", "DevicePeaks",
    "Roofline", "analyze", "collective_stats", "device_peaks", "forward_flops",
    "param_counts", "step_bytes", "step_flops",
]
