"""Batched multi-RHS execution through the engine (``kernels/engine.py``).

The contract under test:
  * ``loops_spmm``/``loops_spmm_values`` accept ``B`` of shape
    ``(..., K, N)`` and return ``(..., M, N)`` — native batched == the
    vmap-unrolled per-element stack == the jnp oracle, for forward AND
    gradients, across {fp32, bf16} × G{1, 8} × {pure-CSR, pure-BCSR,
    hybrid};
  * ``jax.vmap`` over the operand and a direct ``(batch, K, N)`` input both
    lower to ONE batched ``pallas_call`` per part (no unrolling in the
    jaxpr);
  * the value cotangents of ``loops_spmm_values`` are summed over the batch
    (values are shared), while ``dB`` stays per-element;
  * empty batches and the empty-matrix path return correctly-shaped zeros
    on every backend; rank-1 / K-mismatched operands raise ``ValueError``;
  * one native batched call costs ``ceil(batch/bz)`` × the single-element
    grid steps — strictly fewer than the per-element loop from batch ≥ 2.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (csr_from_dense, loops_from_csr, loops_spmm,
                        loops_spmm_values)
from repro.core.spmm import loops_batched_grid_steps, loops_grid_steps
from repro.kernels import engine

DTYPES = [(jnp.float32, 1e-4), (jnp.bfloat16, 5e-2)]
PANEL_GS = [1, 8]
BATCH = 3


def _sparse(rng, m, k, density, dtype):
    a = (rng.random((m, k)) < density) * rng.standard_normal((m, k))
    return np.asarray(jnp.asarray(a, dtype))


def _boundaries(m, br):
    # pure CSR, pure BCSR, and a hybrid br-aligned interior boundary
    return [m, 0, br]


def _count_pallas_calls(jaxpr) -> int:
    """Recursively count pallas_call equations, re-visiting shared
    sub-jaxprs per call site (= number of kernel dispatches)."""
    import jax.extend.core as core

    def subjaxprs(v):
        if isinstance(v, core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from subjaxprs(x)

    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for j in subjaxprs(v):
                n += _count_pallas_calls(j)
    return n


@pytest.mark.parametrize("dtype,tol", DTYPES)
@pytest.mark.parametrize("g", PANEL_GS)
def test_batched_forward_parity(rng, dtype, tol, g):
    """Native batched == vmap-unrolled == jnp oracle, fwd, across plans."""
    m, k, n = 24, 17, 8
    br = 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8
    a = _sparse(rng, m, k, 0.3, dtype)
    b3 = jnp.asarray(rng.standard_normal((BATCH, k, n)), dtype)
    want = np.einsum("mk,zkn->zmn", np.asarray(a, np.float32),
                     np.asarray(b3, np.float32))
    for r_b in _boundaries(m, br):
        fmt = loops_from_csr(csr_from_dense(a), r_b, br, panel_g=g)
        native = loops_spmm(fmt, b3, backend="interpret")
        assert native.shape == (BATCH, m, n)
        oracle = loops_spmm(fmt, b3, backend="jnp")
        unrolled = jnp.stack([loops_spmm(fmt, b3[i], backend="interpret")
                              for i in range(BATCH)])
        atol = tol * max(np.abs(want).max(), 1.0)
        np.testing.assert_allclose(np.asarray(native, np.float32), want,
                                   rtol=tol, atol=atol,
                                   err_msg=f"r_boundary={r_b} g={g}")
        np.testing.assert_allclose(np.asarray(native, np.float32),
                                   np.asarray(oracle, np.float32),
                                   rtol=tol, atol=atol)
        np.testing.assert_allclose(np.asarray(native, np.float32),
                                   np.asarray(unrolled, np.float32),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype,tol", DTYPES)
@pytest.mark.parametrize("g", PANEL_GS)
def test_batched_grad_b_parity(rng, dtype, tol, g):
    """The custom VJP carries the batch through dB = Aᵀ·dY per element."""
    m, k, n = 24, 17, 8
    br = 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8
    a = _sparse(rng, m, k, 0.3, dtype)
    b3 = jnp.asarray(rng.standard_normal((BATCH, k, n)), dtype)
    dy = rng.standard_normal((BATCH, m, n)).astype(np.float32)
    want = np.einsum("mk,zmn->zkn", np.asarray(a, np.float32), dy)
    for r_b in _boundaries(m, br):
        fmt = loops_from_csr(csr_from_dense(a), r_b, br, panel_g=g)

        def loss(bb):
            out = loops_spmm(fmt, bb, backend="interpret")
            return jnp.sum(out * jnp.asarray(dy, out.dtype))

        db = jax.jit(jax.grad(loss))(b3)
        assert db.dtype == b3.dtype and db.shape == b3.shape
        np.testing.assert_allclose(
            np.asarray(db, np.float32), want, rtol=tol,
            atol=tol * max(np.abs(want).max(), 1.0),
            err_msg=f"r_boundary={r_b} g={g}")


@pytest.mark.parametrize("g", PANEL_GS)
def test_batched_value_grads_summed_over_batch(rng, g):
    """loops_spmm_values under a batched operand: d(values) is the batch
    sum (shared parameters), dB stays per-element — both equal the jnp
    oracle's native autodiff."""
    m, k, n = 21, 13, 8
    a = _sparse(rng, m, k, 0.35, jnp.float32)
    b3 = jnp.asarray(rng.standard_normal((BATCH, k, n)), jnp.float32)
    fmt = loops_from_csr(csr_from_dense(a), 8, 8, panel_g=g)
    cv = jnp.asarray(fmt.csr_part.vals)
    bv = jnp.asarray(fmt.bcsr_part.tile_vals)

    def loss(cv_, bv_, bb, backend):
        out = loops_spmm_values(fmt, cv_, bv_, bb, backend=backend)
        return jnp.sum(jnp.tanh(out))

    gi = jax.jit(jax.grad(loss, argnums=(0, 1, 2)),
                 static_argnums=3)(cv, bv, b3, "interpret")
    gj = jax.grad(loss, argnums=(0, 1, 2))(cv, bv, b3, "jnp")
    for got, want in zip(gi, gj):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
    # the value grads of a batch are the sum of the per-element grads
    per_elem = [jax.grad(loss, argnums=0)(cv, bv, b3[i:i + 1], "interpret")
                for i in range(BATCH)]
    np.testing.assert_allclose(np.asarray(gi[0]),
                               np.asarray(sum(per_elem)), rtol=1e-4,
                               atol=1e-4)


def test_vmap_lowers_to_single_batched_call(rng):
    """jax.vmap and a direct (batch, K, N) input both produce ONE
    pallas_call per part in the jaxpr; the per-element loop pays batch ×."""
    m, k, n = 24, 16, 8
    a = _sparse(rng, m, k, 0.3, jnp.float32)
    fmt = loops_from_csr(csr_from_dense(a), 8, 8, panel_g=8)  # hybrid: 2 parts
    b3 = jnp.asarray(rng.standard_normal((BATCH, k, n)), jnp.float32)

    def f(bb):
        return loops_spmm(fmt, bb, backend="interpret")

    n_vmap = _count_pallas_calls(jax.make_jaxpr(jax.vmap(f))(b3).jaxpr)
    n_direct = _count_pallas_calls(jax.make_jaxpr(f)(b3).jaxpr)
    n_loop = _count_pallas_calls(jax.make_jaxpr(
        lambda bb: jnp.stack([f(bb[i]) for i in range(BATCH)]))(b3).jaxpr)
    assert n_vmap == 2, f"vmap must lower to one pallas_call per part, got " \
                        f"{n_vmap}"
    assert n_direct == 2
    assert n_loop == 2 * BATCH
    # and the vmapped execution matches the native batched one exactly
    np.testing.assert_allclose(np.asarray(jax.vmap(f)(b3)),
                               np.asarray(f(b3)), rtol=0, atol=0)


def test_multi_leading_batch_dims(rng):
    """Arbitrary-rank leading dims flatten into one batched call."""
    m, k, n = 16, 12, 8
    a = _sparse(rng, m, k, 0.3, jnp.float32)
    fmt = loops_from_csr(csr_from_dense(a), 8, 8, panel_g=4)
    b4 = jnp.asarray(rng.standard_normal((2, 2, k, n)), jnp.float32)
    out = loops_spmm(fmt, b4, backend="interpret")
    assert out.shape == (2, 2, m, n)
    want = loops_spmm(fmt, b4, backend="jnp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", ["interpret", "jnp"])
def test_empty_batch_returns_zeros(rng, backend):
    """A zero-size batch dim yields correctly shaped zeros (all backends),
    as does the empty-matrix path under batching."""
    m, k, n = 16, 12, 8
    a = _sparse(rng, m, k, 0.3, jnp.float32)
    fmt = loops_from_csr(csr_from_dense(a), 8, 8)
    out = loops_spmm(fmt, jnp.zeros((0, k, n)), backend=backend)
    assert out.shape == (0, m, n)
    out = loops_spmm(fmt, jnp.zeros((2, 0, k, n)), backend=backend)
    assert out.shape == (2, 0, m, n)
    cv = jnp.asarray(fmt.csr_part.vals)
    bv = jnp.asarray(fmt.bcsr_part.tile_vals)
    out = loops_spmm_values(fmt, cv, bv, jnp.zeros((0, k, n)),
                            backend=backend)
    assert out.shape == (0, m, n)
    # empty matrix × non-empty batch
    zfmt = loops_from_csr(csr_from_dense(np.zeros((m, k), np.float32)), 8, 8)
    out = loops_spmm(zfmt, jnp.zeros((2, k, n)), backend=backend)
    assert out.shape == (2, m, n)
    assert not np.asarray(out).any()


def test_bad_rhs_raises_value_error(rng):
    """Rank-1 and K-mismatched operands fail fast with a clear message,
    not an opaque Pallas shape error."""
    m, k = 16, 12
    a = _sparse(rng, m, k, 0.3, jnp.float32)
    fmt = loops_from_csr(csr_from_dense(a), 8, 8)
    with pytest.raises(ValueError, match=r"\(\.\.\., K, N\)"):
        loops_spmm(fmt, jnp.zeros((k,)), backend="jnp")
    with pytest.raises(ValueError, match="ncols"):
        loops_spmm(fmt, jnp.zeros((k + 1, 4)), backend="interpret")
    cv = jnp.asarray(fmt.csr_part.vals)
    bv = jnp.asarray(fmt.bcsr_part.tile_vals)
    with pytest.raises(ValueError, match=r"\(\.\.\., K, N\)"):
        loops_spmm_values(fmt, cv, bv, jnp.zeros((k,)), backend="jnp")
    with pytest.raises(ValueError, match="ncols"):
        engine.csr_spmm(fmt.csr_part, jnp.zeros((k + 3, 4)), backend="jnp")


def test_batched_grid_steps_beat_per_element_loop(rng):
    """One native batched call costs ceil(batch/bz) × the single-element
    steps — strictly below batch × (the per-element loop) from batch 2 up,
    and equal to the single-element count while batch ≤ MAX_BATCH_BLOCK."""
    m, k = 48, 32
    a = _sparse(rng, m, k, 0.15, jnp.float32)
    fmt = loops_from_csr(csr_from_dense(a), 24, 8, panel_g=8)
    one = loops_grid_steps(fmt, 32)
    for batch in (2, 4, 8):
        native = loops_batched_grid_steps(fmt, batch, 32)
        assert native < batch * one
        assert native == one  # batch <= MAX_BATCH_BLOCK folds into bz
    assert loops_batched_grid_steps(fmt, 16, 32) == 2 * one
    assert loops_batched_grid_steps(fmt, 0, 32) == 0
    assert loops_batched_grid_steps(fmt, (2, 4), 32) == one
    # awkward sizes (no divisor <= MAX_BATCH_BLOCK) zero-pad into wide
    # blocks instead of degrading to per-slice steps
    assert loops_batched_grid_steps(fmt, 11, 32) == 2 * one
    assert loops_batched_grid_steps(fmt, 13, 32) == 2 * one
    assert loops_batched_grid_steps(fmt, 12, 32) == 2 * one  # divisor 6


def test_prime_batch_pads_not_degrades(rng):
    """A batch with no small divisor (11) stays correct fwd + bwd — the
    engine pads it to full-width blocks and trims, rather than falling
    back to one slice per grid step."""
    m, k, n, batch = 16, 12, 8, 11
    a = _sparse(rng, m, k, 0.3, jnp.float32)
    fmt = loops_from_csr(csr_from_dense(a), 8, 8, panel_g=4)
    b3 = jnp.asarray(rng.standard_normal((batch, k, n)), jnp.float32)
    out = loops_spmm(fmt, b3, backend="interpret")
    assert out.shape == (batch, m, n)
    want = loops_spmm(fmt, b3, backend="jnp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    cv = jnp.asarray(fmt.csr_part.vals)
    bv = jnp.asarray(fmt.bcsr_part.tile_vals)

    def loss(cv_, bv_, bb, backend):
        return jnp.sum(loops_spmm_values(fmt, cv_, bv_, bb,
                                         backend=backend) ** 2)

    gi = jax.grad(loss, argnums=(0, 1, 2))(cv, bv, b3, "interpret")
    gj = jax.grad(loss, argnums=(0, 1, 2))(cv, bv, b3, "jnp")
    for got, ref in zip(gi, gj):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_sparse_ffn_batched_activations(rng):
    """The sparse FFN consumes the batched path: rank-3 activations keep
    their batch structure and match the jnp oracle fwd + bwd."""
    from repro.models.sparse_ffn import (sparse_linear_apply,
                                         sparse_linear_from_dense)
    w = rng.standard_normal((24, 16)).astype(np.float32)
    layer = sparse_linear_from_dense(w, 0.6)
    vals = layer.init_values()
    x = jnp.asarray(rng.standard_normal((2, 5, 16)), jnp.float32)

    def loss(v, x_, backend):
        y = sparse_linear_apply(layer, v, x_, backend=backend)
        assert y.shape == (2, 5, 24)
        return jnp.sum(y ** 2)

    gi = jax.grad(loss, argnums=(0, 1))(vals, x, "interpret")
    gj = jax.grad(loss, argnums=(0, 1))(vals, x, "jnp")
    for a_, b_ in zip(jax.tree.leaves(gi), jax.tree.leaves(gj)):
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)
