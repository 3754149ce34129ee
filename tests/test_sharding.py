"""Sharding-rule resolution + HLO accounting unit tests (no devices needed:
AbstractMesh carries axis names/sizes without hardware)."""

import numpy as np
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed.sharding import DEFAULT_RULES, resolve_spec

POD = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_basic_resolution():
    spec = resolve_spec(("batch", "seq", "embed"), (256, 4096, 2048), POD)
    assert spec == P("data", None, None)
    spec = resolve_spec(("batch", "seq", "embed"), (256, 4096, 2048), MULTI)
    assert spec == P(("pod", "data"), None, None)


def test_divisibility_fallback():
    # kv_heads=1 cannot shard on model=16 => replicated
    spec = resolve_spec(("batch", "seq_kv", "kv_heads", None),
                        (128, 32768, 1, 128), POD)
    assert spec == P("data", "model", None, None)
    # odd vocab falls back to replicated
    spec = resolve_spec(("vocab", "embed"), (504, 1280), POD)
    assert spec == P(None, None)


def test_axis_used_once():
    # seq_kv grabs "model" first; kv_heads then cannot reuse it
    spec = resolve_spec(("batch", "seq_kv", "kv_heads", None),
                        (128, 32768, 16, 128), POD)
    assert spec == P("data", "model", None, None)


def test_tuple_prefix_fallback():
    # batch=2 divides pod(2) but not pod*data(32) => prefix ("pod",) is used
    spec = resolve_spec(("batch", "seq"), (2, 64), MULTI)
    assert spec == P("pod", None)


def test_moe_expert_padding():
    from repro.models.moe import phys_experts

    assert phys_experts(60) == 64
    assert phys_experts(64) == 64
    assert phys_experts(16) == 16
    assert phys_experts(8) == 8


def test_hlo_analyze_synthetic():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.hlo_utils import analyze_hlo

    hlo = """
HloModule jit_f

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %w = f32[8,8]{1,0} constant({...})
  %d = f32[8,8]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), to_apply=%add
  ROOT %t = (s32[], f32[8,8]) tuple(%i, %ar)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%z, %a)
  %w5 = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w5), index=1
}
"""
    res = analyze_hlo(hlo)
    assert res["flops"] == 5 * 2 * 8 * 8 * 8          # 5 trips x 2*out*K
    assert res["collectives"]["all-reduce"] == 5 * 8 * 8 * 4
    assert res["while_trip_counts"] == [5]
