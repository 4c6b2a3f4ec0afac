"""Unit tests for the HLO static analyser (roofline inputs)."""
import textwrap

import pytest

from repro.launch.hlo_analysis import HloModule, _type_bytes, analyze


SAMPLE = textwrap.dedent("""
    HloModule jit_step, num_partitions=4

    %body.1 (p.1: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
      %p.1 = (s32[], f32[8,16]) parameter(0)
      %g.1 = f32[8,16]{1,0} get-tuple-element(%p.1), index=1
      %w.1 = f32[16,16]{1,0} constant({...})
      %dot.1 = f32[8,16]{1,0} dot(%g.1, %w.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ar.1 = f32[8,16]{1,0} all-reduce(%dot.1), replica_groups={}
      %i.1 = s32[] get-tuple-element(%p.1), index=0
      ROOT %t.1 = (s32[], f32[8,16]) tuple(%i.1, %ar.1)
    }

    %cond.1 (p.2: (s32[], f32[8,16])) -> pred[] {
      %p.2 = (s32[], f32[8,16]) parameter(0)
      %i.2 = s32[] get-tuple-element(%p.2), index=0
      %c.2 = s32[] constant(10)
      ROOT %lt = pred[] compare(%i.2, %c.2), direction=LT
    }

    ENTRY %main.1 (arg0: f32[8,16]) -> f32[8,16] {
      %arg0 = f32[8,16]{1,0} parameter(0)
      %i0 = s32[] constant(0)
      %t0 = (s32[], f32[8,16]) tuple(%i0, %arg0)
      %w = (s32[], f32[8,16]) while(%t0), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"10"}}
      %out = f32[8,16]{1,0} get-tuple-element(%w), index=1
      %ag = f32[32,16]{1,0} all-gather(%out), dimensions={0}
      ROOT %slice = f32[8,16]{1,0} slice(%ag), slice={[0:8], [0:16]}
    }
""")


def test_type_bytes():
    assert _type_bytes("f32[8,16]{1,0}") == 8 * 16 * 4
    assert _type_bytes("bf16[4]") == 8
    assert _type_bytes("(s32[], f32[2,2])") == 4 + 16
    assert _type_bytes("pred[]") == 1


def test_known_trip_count_used():
    mod = HloModule(SAMPLE)
    mult = mod.multipliers([999])        # fallback must NOT be used
    assert mult["body.1"] == 10
    assert mult["main.1"] == 1


def test_dot_flops_with_loop_expansion():
    a = analyze(SAMPLE, loop_trips=[1])
    # dot: 2 * (8*16) * 16 = 4096 flops per trip, 10 trips
    assert a["flops"] == 2 * 8 * 16 * 16 * 10
    assert a["dot_count"] == 1


def test_collective_bytes_per_kind():
    a = analyze(SAMPLE)
    per = a["collectives"]["per_kind"]
    # all-reduce inside the loop: 8*16*4 bytes x 10 trips
    assert per["all-reduce"] == 8 * 16 * 4 * 10
    # all-gather at entry: result 32*16*4, once
    assert per["all-gather"] == 32 * 16 * 4


def test_hbm_bytes_counts_fusion_boundaries():
    a = analyze(SAMPLE)
    assert a["hbm_bytes"] > 0


def _roofline_record(**kw):
    rec = {"ok": True, "arch": "a", "shape": "train_4k", "mesh": "16x16",
           "chips": 256, "kind": "train", "hlo_flops": 197e12,
           "hlo_hbm_bytes": 819e9 / 2, "collectives": {"total_bytes": 0.0},
           "device_kind": "TPU v5 lite"}
    rec.update(kw)
    return rec


def test_roofline_uses_the_record_device_peaks():
    from repro.launch.roofline import analyze_record

    row = analyze_record(_roofline_record())
    assert row.compute_s == 1.0 and row.memory_s == 0.5
    assert row.dominant == "compute"


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", None])
def test_roofline_rejects_unknown_device_kind(kind):
    from repro.launch.roofline import analyze_record

    with pytest.raises(ValueError, match="no published peaks"):
        analyze_record(_roofline_record(device_kind=kind))
