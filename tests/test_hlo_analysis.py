"""The roofline engine: trip-count-corrected HLO accounting."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.perf.hlo_analysis import analyze_hlo, _shape_bytes


def test_shape_bytes():
    assert _shape_bytes("f32[32,32]{1,0}") == 4096
    assert _shape_bytes("bf16[8]") == 16
    assert _shape_bytes("(s32[], f32[4,4])") == 4 + 64
    assert _shape_bytes("pred[]") == 1


def test_scan_flops_multiplied_by_trip_count():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=11)
        return out

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = jax.jit(f).lower(x, w).compile()
    st = analyze_hlo(c.as_text())
    assert st.flops == 11 * 2 * 64 ** 3
    assert st.unknown_trip_loops == 0
    # cost_analysis undercounts (one body visit) — the reason this module
    # exists; guard the assumption so a jax upgrade that fixes it is noticed
    ca = c.cost_analysis()
    assert ca["flops"] < st.flops / 2


def test_nested_scan():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out

    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    c = jax.jit(f).lower(x, w).compile()
    st = analyze_hlo(c.as_text())
    assert st.flops == 15 * 2 * 16 ** 3


def test_dot_general_batch_dims():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)
    a = jax.ShapeDtypeStruct((4, 8, 16), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 16, 8), jnp.float32)
    c = jax.jit(f).lower(a, b).compile()
    st = analyze_hlo(c.as_text())
    assert st.flops == 2 * 4 * 8 * 8 * 16


def test_hbm_bytes_reasonable_for_elementwise():
    def f(a, b):
        return a + b
    a = jax.ShapeDtypeStruct((1024,), jnp.float32)
    c = jax.jit(f).lower(a, a).compile()
    st = analyze_hlo(c.as_text())
    # read a, read b, write out = 3 * 4096 (fusion boundary accounting)
    assert 2 * 4096 <= st.hbm_bytes <= 4 * 4096
