"""Roofline machinery: collective parser (incl. while-loop trip counts)
and the jaxpr cost walker vs XLA's own analysis on unrolled modules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import (RooflineTerms, _loop_trip_count,
                                     collective_bytes)
from repro.roofline.jaxpr_cost import Cost, step_cost

FAKE_HLO = """\
HloModule test

%add.clone (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %r = f32[] add(%x, %y)
}

%cond (arg: (s32[], f32[16,8])) -> pred[] {
  %arg = (s32[], f32[16,8]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (arg: (s32[], f32[16,8])) -> (s32[], f32[16,8]) {
  %arg = (s32[], f32[16,8]) parameter(0)
  %x = f32[16,8] get-tuple-element(%arg), index=1
  %ar = f32[16,8] all-reduce(%x), replica_groups={}, to_apply=%add.clone
  %i = s32[] get-tuple-element(%arg), index=0
  ROOT %t = (s32[], f32[16,8]) tuple(%i, %ar)
}

ENTRY %main (p0: f32[16,8], p1: f32[32,4]) -> f32[16,8] {
  %p0 = f32[16,8] parameter(0)
  %p1 = f32[32,4] parameter(1)
  %ag = f32[32,4] all-gather(%p1), dimensions={0}
  %init = (s32[], f32[16,8]) tuple(s32[] constant(0), %p0)
  %w = (s32[], f32[16,8]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[16,8] get-tuple-element(%w), index=1
}
"""


def test_collective_parser_with_loop_multiplier():
    res = collective_bytes(FAKE_HLO)
    # all-reduce inside a 12-trip while: 16*8*4 bytes * 12
    assert res["all-reduce"] == 16 * 8 * 4 * 12
    assert res["counts"]["all-reduce"] == 12
    # all-gather at top level once: operand f32[32,4]
    assert res["all-gather"] == 32 * 4 * 4
    assert res["total"] == res["all-reduce"] + res["all-gather"]


def test_trip_count_extraction():
    assert _loop_trip_count(["  %c = s32[] constant(42)"]) == 42
    assert _loop_trip_count([]) == 1


def test_jaxpr_walker_dot_flops():
    f = lambda a, b: a @ b
    a = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((64, 16), jnp.float32)
    c = step_cost(f, a, b)
    assert c.flops == 2 * 32 * 64 * 16
    assert c.bytes == (32 * 64 + 64 * 16 + 32 * 16) * 4


def test_jaxpr_walker_scan_multiplies():
    def f(x):
        def body(c, _):
            return c @ c, None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out
    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    c = step_cost(f, x)
    assert c.flops == 7 * 2 * 16 ** 3


def test_jaxpr_walker_grad_includes_backward():
    def loss(w, x):
        return jnp.sum((x @ w) ** 2)
    w = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    x = jax.ShapeDtypeStruct((16, 8), jnp.float32)
    fwd = step_cost(loss, w, x)
    both = step_cost(jax.grad(loss), w, x)
    # grad-wrt-w only: forward + the dw matmul (~2x the forward flops)
    assert both.flops >= 1.8 * fwd.flops


@pytest.mark.slow
def test_walker_vs_xla_on_unrolled_model():
    """Agreement with XLA cost analysis on a no-loop module (the case
    where XLA's numbers are trustworthy)."""
    from repro.models.model_zoo import build_model
    from repro.models.transformer import RunConfig
    m = build_model("stablelm-3b", RunConfig(scan_layers=False),
                    reduced=True)
    params, _ = m.init(jax.random.key(0))
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "labels": jnp.ones((2, 16), jnp.int32)}
    fn = jax.jit(lambda p, b: m.loss(p, b)[0])
    compiled = fn.lower(params, batch).compile()
    from repro.core.xla_cost import cost_analysis_dict
    xla_flops = float(cost_analysis_dict(compiled)["flops"])
    ours = step_cost(fn, params, batch).flops
    assert 0.5 < ours / xla_flops < 2.0, (ours, xla_flops)


def test_roofline_terms_dominant():
    t = RooflineTerms(compute_s=1.0, memory_s=0.5, collective_s=2.0,
                      flops_per_chip=1, bytes_per_chip=1,
                      coll_bytes_per_chip=1, model_flops=197e12 * 256,
                      n_chips=256)
    assert t.dominant == "collective"
    assert t.bound_s == 2.0
    assert 0 < t.roofline_fraction <= 1.0


def test_cost_addition():
    a = Cost(1.0, 2.0, 0.0) + Cost(3.0, 4.0, 1.0) * 2
    assert a.flops == 7.0 and a.bytes == 10.0


def test_cost_analysis_dict_is_empty_only_without_an_analysis():
    """``{}`` means the backend has no cost analysis; a backend that has
    one and fails raises instead of silently zeroing the flops feature."""
    from repro.core.xla_cost import cost_analysis_dict

    class NoAnalysis:            # what JAX reports for such a backend
        def cost_analysis(self):
            return None

    class Failing:
        def cost_analysis(self):
            raise RuntimeError("cost analysis crashed")

    assert cost_analysis_dict(NoAnalysis()) == {}
    with pytest.raises(RuntimeError, match="crashed"):
        cost_analysis_dict(Failing())
    compiled = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8))).compile()
    assert cost_analysis_dict(compiled)["flops"] > 0
