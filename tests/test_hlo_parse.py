"""roofline.hlo_parse trip-count correction against hand-built HLO.

perf.collectives (the measured single-sync audit) builds directly on this
parser, so the multiplier propagation — collectives inside while (scan)
bodies scaled by ``known_trip_count``, nested loops multiplying — is
pinned here on a fixture whose right answers are computable by hand.
"""

import pytest

from repro.roofline import hlo_parse

# ENTRY carries one all-reduce-start/-done pair (counted ONCE) and a
# while loop with trip count 4; the loop body carries one all-reduce and
# a nested while (trip 2) whose body carries one all-gather. Multipliers:
# entry x1, %body x4, %inner x(4*2)=8.
FIXTURE = """\
HloModule manual_step

%inner (q: f32[8]) -> f32[8] {
  %ag = f32[64] all-gather(%q), dimensions={0}
  ROOT %ri = f32[8] add(%q, %q)
}

%body (p: f32[8]) -> f32[8] {
  %ar1 = f32[256] all-reduce(%p), to_apply=%sum
  %w2 = f32[8] while(%p), condition=%cond2, body=%inner, backend_config={"known_trip_count":{"n":"2"}}
  ROOT %rb = f32[8] add(%p, %p)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %ar0 = f32[128] all-reduce-start(%a), to_apply=%sum
  %ard = f32[128] all-reduce-done(%ar0)
  %w = f32[8] while(%a), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"4"}}
  ROOT %r = f32[8] add(%a, %a)
}
"""


def test_multipliers_propagate_through_nested_loops():
    comps = hlo_parse.split_computations(FIXTURE)
    mult = hlo_parse.computation_multipliers(comps)
    assert mult["main"] == 1.0
    assert mult["body"] == 4.0
    assert mult["inner"] == 8.0


def test_collectives_scaled_by_trip_count():
    stats = hlo_parse.collective_stats(FIXTURE)
    # 1 entry all-reduce (start/done pair counted once) + 4x the body's
    assert stats["all-reduce_count"] == 1 + 4
    assert stats["all-reduce_bytes"] == 128 * 4 + 4 * (256 * 4)
    # nested: the inner all-gather runs 4*2 times
    assert stats["all-gather_count"] == 8
    assert stats["all-gather_bytes"] == 8 * (64 * 4)
    assert stats["total_count"] == 13
    assert stats["total_bytes"] == stats["all-reduce_bytes"] + stats["all-gather_bytes"]


def test_while_without_trip_count_defaults_to_once():
    text = FIXTURE.replace(', backend_config={"known_trip_count":{"n":"4"}}', "")
    stats = hlo_parse.collective_stats(text)
    # outer loop now x1: 1 entry + 1 body all-reduce; inner loop still x2
    assert stats["all-reduce_count"] == 2
    assert stats["all-gather_count"] == 2


def test_scalar_and_unknown_dtypes_in_shape_bytes():
    assert hlo_parse.shape_bytes("f32[]") == 4
    assert hlo_parse.shape_bytes("bf16[2,3]") == 12
    assert hlo_parse.shape_bytes("token[]") == 0  # unknown dtype ignored
    assert hlo_parse.shape_bytes("(f32[4], s32[2])") == 16 + 8


@pytest.mark.parametrize("collective", ["all-reduce", "reduce-scatter", "all-to-all"])
def test_start_done_pairs_counted_once(collective):
    text = f"""\
HloModule pairs
ENTRY %main (a: f32[4]) -> f32[4] {{
  %c0 = f32[16] {collective}-start(%a), to_apply=%sum
  %c1 = f32[16] {collective}-done(%c0)
  ROOT %r = f32[4] add(%a, %a)
}}
"""
    stats = hlo_parse.collective_stats(text)
    assert stats[f"{collective}_count"] == 1
    assert stats[f"{collective}_bytes"] == 16 * 4


def test_device_peaks_keyed_by_device_kind():
    from repro.roofline import analysis

    v5e = analysis.device_peaks("TPU v5 lite")
    assert v5e.flops == 197e12 and v5e.hbm_bw == 819e9
    assert analysis.device_peaks(analysis.TARGET_DEVICE_KIND) is v5e
    with pytest.raises(ValueError, match="cpu"):
        analysis.device_peaks("cpu")


def test_trip_count_read_from_loop_condition():
    """A while without a known_trip_count annotation (as the TPU compiler
    prints it) takes its trip count from a ``counter < constant``
    condition, so collectives in its body are scaled."""
    text = """\
HloModule tpu_loop
%cond (p: (s32[], f32[4])) -> pred[] {
  %constant.7 = s32[]{:T(128)} constant(3)
  %p = (s32[], f32[4]) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%p), index=0
  ROOT %lt = pred[]{:T(512)} compare(%i, %constant.7), direction=LT
}
%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %x = f32[4] get-tuple-element(%p), index=1
  %ar = f32[4] all-reduce(%x), to_apply=%sum
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[4]) tuple(%i, %ar)
}
ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4] parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[4]) tuple(%z, %a)
  %w = (s32[], f32[4]) while(%t0), condition=%cond, body=%body
  ROOT %r = f32[4] get-tuple-element(%w), index=1
}
"""
    assert hlo_parse.collective_stats(text)["all-reduce_count"] == 3
