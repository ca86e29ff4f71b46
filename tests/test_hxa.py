"""HxA analyzer unit tests: parsing, trip counts, collective census —
validated against a real compiled module AND synthetic HLO text."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hxa

SYNTH = """
HloModule test

%loop_cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %it = s32[] get-tuple-element(%p), index=0
  %bound = s32[] constant(13)
  ROOT %cmp = pred[] compare(%it, %bound), direction=LT
}

%loop_body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %it = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8] get-tuple-element(%p), index=1
  %one = s32[] constant(1)
  %nit = s32[] add(%it, %one)
  %y = f32[8,8] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8] all-reduce(%y), replica_groups=[2,4]<=[8], to_apply=%adder
  ROOT %t = (s32[], f32[8,8]) tuple(%nit, %ar)
}

%adder (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,8]) tuple(%zero, %x)
  %w = (s32[], f32[8,8]) while(%init), condition=%loop_cond, body=%loop_body
  ROOT %out = f32[8,8] get-tuple-element(%w), index=1
}
"""


def test_synthetic_loop_census():
    res = hxa.analyze_hlo_text(SYNTH)
    assert res["loops"] and res["loops"][0]["trips"] == 13
    # dot: 2*8*8*8 = 1024 flops per iteration, x13 (+ the trivial adds)
    assert 13 * 1024 <= res["flops"] <= 13 * 1024 + 13 * 8 + 16
    # all-reduce: 8*8*4 bytes, 13 iterations
    assert res["collectives"]["all-reduce"]["count"] == 13
    assert res["collectives"]["all-reduce"]["bytes"] == 13 * 256


def test_real_module_trip_aware_flops():
    """HxA multiplies scan bodies by trip count; XLA cost_analysis does not."""
    def f(w, x):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        c, _ = jax.lax.scan(body, x, w)
        return c

    w = jnp.zeros((9, 64, 64), jnp.float32)
    x = jnp.zeros((8, 64), jnp.float32)
    comp = jax.jit(f).lower(w, x).compile()
    res = hxa.analyze_hlo_text(comp.as_text())
    xla_flops = comp.cost_analysis()["flops"]
    per_iter = 2 * 8 * 64 * 64
    assert res["flops"] >= 9 * per_iter
    assert xla_flops < 2 * per_iter  # body counted once


def test_dot_flops_contracting_dims():
    comp = jax.jit(lambda a, b: a @ b).lower(
        jnp.zeros((32, 128)), jnp.zeros((128, 16))).compile()
    res = hxa.analyze_hlo_text(comp.as_text())
    assert abs(res["flops"] - 2 * 32 * 128 * 16) / (2 * 32 * 128 * 16) < 0.05


def test_bytes_positive_and_finite():
    comp = jax.jit(lambda x: jnp.sum(jnp.exp(x))).lower(
        jnp.zeros((256, 256))).compile()
    res = hxa.analyze_hlo_text(comp.as_text())
    assert 0 < res["hbm_bytes"] < 1e9
