"""Trip-count-aware HLO analyzer: exactness on known scan structures."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import parse_collectives, roofline_terms
from repro.roofline.hlo_parse import analyze_module


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_single_dot_exact():
    c = _compile(
        lambda a, b: a @ b,
        jax.ShapeDtypeStruct((64, 32), jnp.float32),
        jax.ShapeDtypeStruct((32, 16), jnp.float32),
    )
    mc = analyze_module(c.as_text())
    assert mc.dot_flops == pytest.approx(2 * 64 * 32 * 16, rel=0.01)


@pytest.mark.parametrize("trip", [1, 5, 33])
def test_scan_trip_count(trip):
    def f(x, ws):
        def body(c, w):
            return c @ w, ()
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    c = _compile(
        f,
        jax.ShapeDtypeStruct((128, 128), jnp.float32),
        jax.ShapeDtypeStruct((trip, 128, 128), jnp.float32),
    )
    mc = analyze_module(c.as_text())
    assert mc.dot_flops == pytest.approx(2 * 128**3 * trip, rel=0.02)


def test_nested_scan():
    def g(x, ws):
        def outer(c, wo):
            def inner(ci, w):
                return ci @ w, ()
            c2, _ = jax.lax.scan(inner, c, ws)
            return c2 @ wo, ()
        y, _ = jax.lax.scan(outer, x, jnp.stack([jnp.eye(128)] * 3))
        return y.sum()

    c = _compile(
        g,
        jax.ShapeDtypeStruct((128, 128), jnp.float32),
        jax.ShapeDtypeStruct((5, 128, 128), jnp.float32),
    )
    mc = analyze_module(c.as_text())
    assert mc.dot_flops == pytest.approx(2 * 128**3 * (3 * 5 + 3), rel=0.02)


def test_traffic_nonzero_and_scales_with_trip():
    def f(x, ws):
        def body(c, w):
            return c @ w, ()
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    m1 = analyze_module(
        _compile(f, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                 jax.ShapeDtypeStruct((2, 64, 64), jnp.float32)).as_text()
    )
    m2 = analyze_module(
        _compile(f, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                 jax.ShapeDtypeStruct((20, 64, 64), jnp.float32)).as_text()
    )
    assert m2.traffic_bytes > 5 * m1.traffic_bytes


def test_collective_parse_synthetic():
    hlo = """
HloModule test
ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %ag = f32[64,8]{1,0} all-gather(%a), dimensions={0}
  %ar = f32[8,8]{1,0} all-reduce(%a), to_apply=%sum
  ROOT %out = f32[8,8] copy(%ar)
}
"""
    c = parse_collectives(hlo)
    assert c["all-gather"]["count"] == 1
    assert c["all-gather"]["bytes"] == 64 * 8 * 4
    assert c["all-reduce"]["bytes"] == 8 * 8 * 4
    assert c["total"]["count"] == 2


def test_roofline_terms_math():
    r = roofline_terms(197e12, 819e9, 50e9, chips=1)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(1.0)
    assert r.collective_s == pytest.approx(1.0)
    assert r.dominant in ("compute", "memory", "collective")


def test_tpu_layouts_async_pairs_and_stated_trip_count():
    """A module as the TPU compiler prints it: tiled layouts in the types,
    tuple results, an async all-gather pair (counted once, by its -done),
    an async wrapper whose three ops name one computation (run once), and
    a loop whose trip count the compiler states in its backend config."""
    hlo = """
HloModule tpu
%wrapped (p0: bf16[16,8]) -> bf16[16,8] {
  %p0 = bf16[16,8]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %ar = bf16[16,8]{1,0:T(8,128)(2,1)} all-reduce(%p0), to_apply=%add
}

%cond (p: (s32[], bf16[16,8])) -> pred[] {
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (p: (s32[], bf16[16,8])) -> (s32[], bf16[16,8]) {
  %ar = bf16[16,8]{1,0:T(8,128)(2,1)S(1)} all-reduce(%y), channel_id=2, to_apply=%add
  ROOT %t = (s32[]{:T(128)}, bf16[16,8]{1,0:T(8,128)(2,1)S(1)}) tuple(%i, %ar)
}

ENTRY %main (a: bf16[16,8]) -> bf16[16,32] {
  %a = bf16[16,8]{1,0:T(8,128)(2,1)} parameter(0)
  %ags = (bf16[16,8]{1,0:T(8,128)(2,1)}, bf16[16,32]{1,0:T(8,128)(2,1)}) all-gather-start(%a), dimensions={1}
  %agd = bf16[16,32]{1,0:T(8,128)(2,1)} all-gather-done(%ags)
  %as = ((bf16[16,8]{1,0}), bf16[16,8]{1,0}, u32[]{:S(2)}) async-start(%a), calls=%wrapped
  %au = ((bf16[16,8]{1,0}), bf16[16,8]{1,0}, u32[]{:S(2)}) async-update(%as), calls=%wrapped
  %ad = bf16[16,8]{1,0:T(8,128)(2,1)} async-done(%au), calls=%wrapped
  %w = (s32[]{:T(128)}, bf16[16,8]{1,0:T(8,128)(2,1)S(1)}) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"20"}}
  ROOT %r = bf16[16,32]{1,0:T(8,128)(2,1)} copy(%agd)
}
"""
    mc = analyze_module(hlo)
    ar = 16 * 8 * 2
    assert mc.collective_bytes == 16 * 32 * 2 + ar + 20 * ar
    assert mc.collective_counts["all-gather"] == 1
    assert mc.collective_counts["all-reduce"] == 1 + 20
    assert mc.num_whiles == 1
