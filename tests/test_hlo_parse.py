"""Collective-parsing layer for the roofline analysis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo import collective_bytes, parse_collectives
from repro.roofline.hlo_cost import hlo_cost
from repro.roofline.model import HW_V5E, roofline_terms

SYNTH = """
HloModule m
%cond.1 (a: s32[]) -> pred[] {
  %c = s32[] constant(24)
  ROOT %lt = pred[] compare(s32[] %a, s32[] %c), direction=LT
}
%body.1 (a: s32[]) -> s32[] {
  %ar = f32[128,256] all-reduce(f32[128,256] %x), replica_groups={}
  ROOT %n = s32[] add(s32[] %a, s32[] %one)
}
ENTRY %main () -> f32[] {
  %w = s32[] while(s32[] %i), condition=%cond.1, body=%body.1
  %ag = bf16[512,512] all-gather(bf16[512,256] %y), dimensions={1}
  ROOT %r = f32[] constant(0)
}
"""


def test_parse_synthetic_hlo():
    items = parse_collectives(SYNTH)
    kinds = sorted((k, m) for k, _, m in items)
    assert ("all-gather", 1) in kinds
    assert ("all-reduce", 24) in kinds  # trip count folded in
    agg = collective_bytes(SYNTH)
    expected_ar = 128 * 256 * 4 * 24 * 2.0  # f32, 24 trips, ring factor 2
    expected_ag = 512 * 512 * 2 * 1.0
    assert abs(agg["all-reduce"] - expected_ar) < 1
    assert abs(agg["all-gather"] - expected_ag) < 1


def test_parse_real_psum_module():
    """Lower an actual psum over a 1-device mesh and find the all-reduce."""
    mesh = jax.make_mesh((1,), ("x",))

    def f(a):
        return jax.lax.psum(a, "x")

    from jax.sharding import PartitionSpec as P

    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P()))
    comp = g.lower(jax.ShapeDtypeStruct((8, 16), jnp.float32)).compile()
    agg = collective_bytes(comp.as_text())
    # 1-device groups may be optimized away; parser must not crash and must
    # return a well-formed dict either way
    assert "total" in agg


# one dot, hand-countable: flops = 2*4*16*8 = 1024; bytes = the dot's
# result (4*16*4=256) + both operands (4*8*4=128, 8*16*4=512) = 896
# (parameter defs are free ops — only the consumer pays the traffic)
_DOT_HLO = """
HloModule tiny
ENTRY %main (a: f32[4,8], b: f32[8,16]) -> f32[4,16] {
  %a = f32[4,8] parameter(0)
  %b = f32[8,16] parameter(1)
  ROOT %d = f32[4,16] dot(f32[4,8] %a, f32[8,16] %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""

# a 5-trip while whose body holds one dot + one add; XLA's own
# cost_analysis would count the body ONCE — hlo_cost must multiply by
# the known_trip_count (and fall back to the condition's constant)
_LOOP_HLO = """
HloModule loop
%cond (p: (s32[], f32[4,8])) -> pred[] {
  %p = (s32[], f32[4,8]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4,8]) %p), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %c), direction=LT
}
%body (p: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %p = (s32[], f32[4,8]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4,8]) %p), index=0
  %x = f32[4,8] get-tuple-element((s32[], f32[4,8]) %p), index=1
  %w = f32[8,8] constant(0)
  %d = f32[4,8] dot(f32[4,8] %x, f32[8,8] %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ni = s32[] add(s32[] %i, s32[] %one)
  ROOT %t = (s32[], f32[4,8]) tuple(s32[] %ni, f32[4,8] %d)
}
ENTRY %main (a: f32[4,8]) -> (s32[], f32[4,8]) {
  %a = f32[4,8] parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[4,8]) tuple(s32[] %z, f32[4,8] %a)
  ROOT %w2 = (s32[], f32[4,8]) while((s32[], f32[4,8]) %t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
}
"""


def test_hlo_cost_hand_counted_dot():
    c = hlo_cost(_DOT_HLO)
    assert c["flops"] == 2 * 4 * 16 * 8          # 1024
    assert c["bytes"] == 256 + 128 + 512         # 896


def test_hlo_cost_while_multiplies_by_trip_count():
    c = hlo_cost(_LOOP_HLO)
    # per trip: dot 2*4*8*8 = 512 flops; bytes = dot (128 result +
    # 128 + 256 operands) + add (4 + 4 + 4) = 524. The while op itself,
    # tuples, GTEs, parameters and constants are free; the condition
    # computation is never charged.
    assert c["flops"] == 512 * 5
    assert c["bytes"] == 524 * 5


def test_hlo_cost_trip_count_falls_back_to_cond_constant():
    no_cfg = _LOOP_HLO.replace(
        ', backend_config={"known_trip_count":{"n":"5"}}', "")
    assert hlo_cost(no_cfg) == hlo_cost(_LOOP_HLO)


def test_roofline_terms_math():
    t = roofline_terms(197e12, 819e9, 50e9)  # exactly 1 second each
    assert abs(t["compute_s"] - 1) < 1e-9
    assert abs(t["memory_s"] - 1) < 1e-9
    assert abs(t["collective_s"] - 1) < 1e-9
    t2 = roofline_terms(1e12, 900e9, 0, model_flops=5e11, num_devices=2)
    assert t2["dominant"] == "memory_s"
    assert 0 < t2["useful_flop_fraction"] < 1
