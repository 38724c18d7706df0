"""Trip-count-aware HLO cost model: parity with XLA on loop-free programs,
x trip-count on scans (where XLA's own cost_analysis undercounts)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.roofline.hlo_cost import HloCostModel, analyse_hlo


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_matches_xla_on_loop_free():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    c = _compile(lambda x, w: jnp.tanh(x @ w), x, w)
    ours = analyse_hlo(c.as_text()).flops
    xla = c.cost_analysis()["flops"]
    assert ours == pytest.approx(xla, rel=0.05)


def test_scan_multiplied_by_trip_count():
    def scanned(x, w):
        return lax.scan(lambda h, wi: (jnp.tanh(h @ wi), None), x, w)[0]

    def unrolled(x, w):
        h = x
        for i in range(10):
            h = jnp.tanh(h @ w[i])
        return h

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
    c_scan = _compile(scanned, x, w)
    c_unroll = _compile(unrolled, x, w)
    f_scan = analyse_hlo(c_scan.as_text()).flops
    f_unroll = analyse_hlo(c_unroll.as_text()).flops
    # ours: scan == unrolled; XLA's builtin: scan == unrolled / 10
    assert f_scan == pytest.approx(f_unroll, rel=0.05)
    assert c_scan.cost_analysis()["flops"] == \
        pytest.approx(f_unroll / 10, rel=0.05)


def test_nested_scan_multiplies():
    def nested(x, w):
        def outer(h, wi):
            def inner(h2, _):
                return jnp.tanh(h2 @ wi), None
            return lax.scan(inner, h, jnp.arange(4))[0], None
        return lax.scan(outer, x, w)[0]

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((5, 128, 128), jnp.float32)
    c = _compile(nested, x, w)
    flops = analyse_hlo(c.as_text()).flops
    per_mm = 2 * 64 * 128 * 128
    assert flops == pytest.approx(20 * per_mm, rel=0.2)   # 5 x 4 matmuls


def test_collectives_counted_with_shapes():
    import os
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >1 host device (dry-run process sets 512)")


def test_dynamic_update_slice_counts_update_not_buffer():
    def f(buf, val):
        return lax.dynamic_update_slice(buf, val, (0, 0))

    buf = jax.ShapeDtypeStruct((4096, 1024), jnp.float32)
    val = jax.ShapeDtypeStruct((1, 1024), jnp.float32)
    # donated buffer -> true in-place update (like our decode caches)
    c = jax.jit(f, donate_argnums=(0,)).lower(buf, val).compile()
    b = analyse_hlo(c.as_text()).bytes
    assert b < 2 * 4096 * 1024 * 4 * 0.1     # nowhere near full-buffer traffic


def test_scan_accumulator_not_counted_as_full_buffer():
    """The falcon-mamba regression: per-step ys stacking must cost the slice,
    not the whole (S, ...) output buffer."""
    def f(x):
        def step(c, xt):
            return c, jnp.tanh(xt)
        return lax.scan(step, 0.0, x)[1]

    x = jax.ShapeDtypeStruct((4096, 512), jnp.float32)
    c = _compile(f, x)
    b = analyse_hlo(c.as_text()).bytes
    full = 4096 * 512 * 4
    # read input once + write output once (x small per-step overhead), NOT
    # 4096 x full-buffer
    assert b < 20 * full
