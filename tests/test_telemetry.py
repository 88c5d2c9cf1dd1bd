"""HLO cost walker + roofline: trip-count multipliers, collective parsing,
fusion byte accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.telemetry import hlo_cost, roofline


def _compile(fn, *avals):
    return jax.jit(fn).lower(*avals).compile()


def test_dot_flops_exact():
    c = _compile(lambda a, b: a @ b,
                 jax.ShapeDtypeStruct((128, 256), jnp.float32),
                 jax.ShapeDtypeStruct((256, 512), jnp.float32))
    t = hlo_cost.analyze_text(c.as_text())
    expect = 2 * 128 * 256 * 512
    assert abs(t.flops - expect) / expect < 0.02


def test_while_trip_count_multiplier():
    def f(x, w):
        def body(c, wi):
            return c @ wi, None
        y, _ = jax.lax.scan(body, x, w)
        return y
    c = _compile(f, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                 jax.ShapeDtypeStruct((9, 64, 64), jnp.float32))
    t = hlo_cost.analyze_text(c.as_text())
    one = 2 * 64 * 64 * 64
    assert abs(t.flops - 9 * one) / (9 * one) < 0.1
    xla = c.cost_analysis()["flops"]  # body x1
    assert t.flops > 5 * xla                  # the bug we fixed


def test_nested_scan_multipliers():
    def f(x, w):
        def outer(c, wi):
            def inner(ci, _):
                return ci @ wi, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, x, w)
        return y
    c = _compile(f, jax.ShapeDtypeStruct((32, 32), jnp.float32),
                 jax.ShapeDtypeStruct((4, 32, 32), jnp.float32))
    t = hlo_cost.analyze_text(c.as_text())
    expect = 4 * 3 * 2 * 32 ** 3
    assert abs(t.flops - expect) / expect < 0.15


def test_dus_inplace_bytes_not_full_buffer():
    """Writing one row into a big buffer must cost ~row bytes, not buffer
    bytes — otherwise paged-KV decode traffic is overstated 1000x."""
    big = jax.ShapeDtypeStruct((4096, 1024), jnp.float32)
    row = jax.ShapeDtypeStruct((1, 1024), jnp.float32)

    def f(buf, r):
        return jax.lax.dynamic_update_slice(buf, r, (17, 0))
    c = jax.jit(f, donate_argnums=(0,)).lower(big, row).compile()
    t = hlo_cost.analyze_text(c.as_text())
    assert t.bytes < 4096 * 1024 * 4 * 0.5    # far below full-buffer copy


def test_collective_parse_shapes_and_groups():
    txt = """
HloModule m
ENTRY %main (p: f32[1024,8]) -> f32[1024,8] {
  %p = f32[1024,8]{1,0} parameter(0)
  ROOT %ar = f32[1024,8]{1,0} all-reduce(%p), replica_groups=[2,4]<=[8], to_apply=%add
}
"""
    t = hlo_cost.analyze_text(txt)
    assert t.coll_counts == {"all-reduce": 1.0}
    nbytes = 1024 * 8 * 4
    assert t.coll_bytes_naive["all-reduce"] == nbytes
    # ring wire bytes for group of 4: 2*(4-1)/4
    assert abs(t.coll_bytes_wire["all-reduce"] - 1.5 * nbytes) < 1


def test_tuple_type_with_index_comments_parses():
    txt = """
HloModule m
ENTRY %main (p: s32[]) -> s32[] {
  %p = s32[] parameter(0)
  %w = (s32[], f32[8,8]{1,0}, /*index=2*/f32[30,16]{1,0}) while(%t), body=%b, condition=%c, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %r = s32[] get-tuple-element(%w), index=0
}
%b (a: (s32[], f32[8,8], f32[30,16])) -> (s32[], f32[8,8], f32[30,16]) {
  %a = (s32[], f32[8,8]{1,0}, f32[30,16]{1,0}) parameter(0)
  %x = f32[8,8]{1,0} get-tuple-element(%a), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t2 = (s32[], f32[8,8]{1,0}, f32[30,16]{1,0}) tuple(%p, %d, %y)
}
"""
    t = hlo_cost.analyze_text(txt)
    assert t.flops >= 5 * 2 * 8 * 8 * 8       # trip-multiplied dot


def test_roofline_terms_and_dominance():
    r = roofline.Roofline(
        flops_per_device=197e12, bytes_per_device=819e9 * 2,
        coll=roofline.CollectiveStats(), chips=256,
        model_flops=197e12 * 256 * 0.5)
    assert abs(r.compute_s - 1.0) < 1e-9
    assert abs(r.memory_s - 2.0) < 1e-9
    assert r.dominant == "memory"
    assert abs(r.roofline_fraction - 0.25) < 1e-9


def test_wire_factor_model():
    assert hlo_cost._wire_factor("all-reduce", 2) == 1.0
    assert hlo_cost._wire_factor("all-gather", 4) == 0.75
    assert hlo_cost._wire_factor("reduce-scatter", 4) == 3.0
    assert hlo_cost._wire_factor("collective-permute", 2) == 1.0
    assert hlo_cost._wire_factor("all-reduce", 1) == 0.0
