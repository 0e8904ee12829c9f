"""Gradients through the LOOPS kernels: the custom VJP vs the dense /
jnp-reference oracles.

Covers the tentpole contract of the differentiable-LOOPS work:
  * ``jax.grad`` through ``loops_spmm`` on the Pallas (interpret) backend
    equals the dense-adjacency reference across dtypes × panel widths ×
    plan shapes (pure-CSR / pure-BCSR / hybrid boundary);
  * ``loops_spmm_values`` additionally yields per-stored-value gradients
    that equal ``dY @ Bᵀ`` masked to the sparsity pattern (the SDD kernel);
  * the transposed format is built once and cached on the ``LoopsFormat`` —
    a second backward pass performs no re-conversion;
  * the sparse FFN layer trains identically on the interpret and jnp paths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (csr_from_dense, loops_from_csr, loops_spmm,
                        loops_spmm_values, plan_and_convert)
from repro.core import formats as formats_lib

DTYPES = [(jnp.float32, 1e-4), (jnp.bfloat16, 5e-2)]
PANEL_GS = [1, 8]


def _sparse(rng, m, k, density, dtype):
    a = (rng.random((m, k)) < density) * rng.standard_normal((m, k))
    return np.asarray(jnp.asarray(a, dtype))


def _boundaries(m, br):
    # pure CSR, pure BCSR, and a hybrid br-aligned interior boundary
    return [m, 0, br]


@pytest.mark.parametrize("dtype,tol", DTYPES)
@pytest.mark.parametrize("g", PANEL_GS)
def test_grad_b_matches_dense_reference(rng, dtype, tol, g):
    """check_grads-style: the custom VJP's dB equals Aᵀ·dY from the dense
    reference, across pure-CSR / pure-BCSR / hybrid plans."""
    m, k, n = 24, 17, 16
    br = 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8
    a = _sparse(rng, m, k, 0.3, dtype)
    b = jnp.asarray(rng.standard_normal((k, n)), dtype)
    dy = rng.standard_normal((m, n)).astype(np.float32)
    want = np.asarray(a, np.float32).T @ dy
    for r_b in _boundaries(m, br):
        fmt = loops_from_csr(csr_from_dense(a), r_b, br, panel_g=g)

        def loss(bb):
            out = loops_spmm(fmt, bb, backend="interpret")
            return jnp.sum(out * jnp.asarray(dy, out.dtype))

        db = jax.jit(jax.grad(loss))(b)
        assert db.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(db, np.float32), want, rtol=tol,
            atol=tol * max(np.abs(want).max(), 1.0),
            err_msg=f"r_boundary={r_b} g={g}")


@pytest.mark.parametrize("g", PANEL_GS)
def test_grad_matches_jnp_backend(rng, g):
    """The jnp reference differentiates natively; the custom VJP must agree
    with it bit-for-tolerance on the same format."""
    m, k, n = 21, 13, 8
    a = _sparse(rng, m, k, 0.35, jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    fmt = loops_from_csr(csr_from_dense(a), 8, 8, panel_g=g)

    def loss(bb, backend):
        return jnp.sum(jnp.tanh(loops_spmm(fmt, bb, backend=backend)))

    d_interp = jax.grad(lambda bb: loss(bb, "interpret"))(b)
    d_jnp = jax.grad(lambda bb: loss(bb, "jnp"))(b)
    np.testing.assert_allclose(np.asarray(d_interp), np.asarray(d_jnp),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype,tol", DTYPES)
@pytest.mark.parametrize("g", PANEL_GS)
def test_sdd_value_grads_match_masked_dense(rng, dtype, tol, g):
    """d(stored values) = (dY @ Bᵀ) sampled at the stored coordinates —
    CSR-part entries and BCSR-part tile elements both."""
    m, k, n = 21, 17, 16
    br = 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8
    a = _sparse(rng, m, k, 0.3, dtype)
    b = jnp.asarray(rng.standard_normal((k, n)), dtype)
    dy = rng.standard_normal((m, n)).astype(np.float32)
    r_b = br if m > br else m
    fmt = loops_from_csr(csr_from_dense(a), r_b, br, panel_g=g)
    cv = jnp.asarray(fmt.csr_part.vals)
    bv = jnp.asarray(fmt.bcsr_part.tile_vals)

    def loss(cv_, bv_, bb):
        out = loops_spmm_values(fmt, cv_, bv_, bb, backend="interpret")
        return jnp.sum(out * jnp.asarray(dy, out.dtype))

    d_cv, d_bv, d_b = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(cv, bv, b)
    dw = dy @ np.asarray(b, np.float32).T        # (m, k) dense dY·Bᵀ

    csr = fmt.csr_part
    want_cv = dw[csr.row_ids, csr.col_idx]
    np.testing.assert_allclose(np.asarray(d_cv, np.float32), want_cv,
                               rtol=tol, atol=tol * np.abs(dw).max())

    bc = fmt.bcsr_part
    rows_g = (fmt.r_boundary + np.repeat(bc.tile_rows, bc.br) * bc.br
              + np.tile(np.arange(bc.br), bc.ntiles))
    cols_g = np.repeat(bc.tile_cols, bc.br)
    want_bv = np.where(rows_g < m, dw[np.minimum(rows_g, m - 1), cols_g],
                       0.0).reshape(bc.ntiles, bc.br)
    np.testing.assert_allclose(np.asarray(d_bv, np.float32), want_bv,
                               rtol=tol, atol=tol * np.abs(dw).max())

    want_db = np.asarray(a, np.float32).T @ dy
    np.testing.assert_allclose(np.asarray(d_b, np.float32), want_db,
                               rtol=tol, atol=tol * np.abs(want_db).max())


def test_transpose_cache_reused_across_backwards(rng, monkeypatch):
    """The O(nnz) transpose conversion runs once; the second backward pass
    is a pure cache hit on the LoopsFormat instance."""
    m, k, n = 24, 16, 8
    a = _sparse(rng, m, k, 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    fmt, _ = plan_and_convert(csr_from_dense(a), total_workers=4)

    calls = {"n": 0}
    real = formats_lib._build_transposed

    def counting(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(formats_lib, "_build_transposed", counting)

    def loss(bb):
        return jnp.sum(loops_spmm(fmt, bb, backend="interpret") ** 2)

    g1 = jax.grad(loss)(b)
    g2 = jax.grad(loss)(b)   # second backward: no re-conversion
    assert calls["n"] == 1
    assert fmt.transposed() is fmt.transposed()
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=0,
                               atol=0)


def test_tuned_transpose_is_built_once(rng, monkeypatch):
    """A GCN graph prepared with a tuner builds Aᵀ once: the build is
    cached under the plan it resolved to, which the backward pass pins
    (``Graph.transpose_plan``), so tracing the gradient converts nothing."""
    from repro.models import gcn

    class Stub:                       # a tuner: .tune(csr) -> (fmt, plan)
        def tune(self, csr):
            return plan_and_convert(csr, total_workers=4)

    m, n = 40, 8
    rows, cols = rng.integers(0, m, 120), rng.integers(0, m, 120)
    adj = gcn.normalized_adjacency(rows, cols, m)
    calls = {"n": 0}
    real = formats_lib._build_transposed

    def counting(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(formats_lib, "_build_transposed", counting)
    graph = gcn.prepare_graph(adj, backend="interpret", tuner=Stub())
    assert calls["n"] == 1 and graph.transpose_plan is not None
    b = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)

    def loss(bb):
        return jnp.sum(loops_spmm(graph.adj, bb, backend="interpret",
                                  transpose_plan=graph.transpose_plan) ** 2)

    got = jax.grad(loss)(b)
    assert calls["n"] == 1
    dense = np.zeros((m, m))
    dense[adj.row_ids, adj.col_idx] = adj.vals
    want = 2 * dense.T @ (dense @ np.asarray(b, np.float64))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_transposed_structure_roundtrip(rng):
    """Aᵀ's LOOPS format densifies back to the dense transpose, and the
    value-linear maps reproduce the converted parts from A's flat values
    (the invariant the traced-values backward leans on)."""
    m, k = 19, 12
    a = _sparse(rng, m, k, 0.4, jnp.float32)
    fmt = loops_from_csr(csr_from_dense(a), 8, 8, panel_g=4)
    tl = fmt.transposed()
    assert tl.fmt.shape == (k, m)
    # densify Aᵀ from its two parts
    from repro.core import csr_to_dense
    dense_t = np.zeros((k, m), np.float32)
    dense_t[:tl.fmt.r_boundary] = csr_to_dense(tl.fmt.csr_part)
    bc = tl.fmt.bcsr_part
    for t in range(bc.ntiles):
        r0 = tl.fmt.r_boundary + int(bc.tile_rows[t]) * bc.br
        for off in range(bc.br):
            if r0 + off < k:
                dense_t[r0 + off, bc.tile_cols[t]] += bc.tile_vals[t, off]
    np.testing.assert_allclose(dense_t, np.asarray(a, np.float32).T,
                               rtol=1e-6, atol=1e-6)
    # traced-value carry: injecting A's values reproduces the parts
    cv, bv = formats_lib.transposed_values(
        tl, jnp.asarray(fmt.csr_part.vals),
        jnp.asarray(fmt.bcsr_part.tile_vals))
    np.testing.assert_allclose(np.asarray(cv), tl.fmt.csr_part.vals,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(bv), tl.fmt.bcsr_part.tile_vals,
                               rtol=1e-6, atol=1e-6)


def test_sparse_ffn_grads_interpret_vs_jnp(rng):
    """The sparse FFN layer trains on the real kernel path: gradients on
    the interpret backend match the jnp oracle for values AND activations."""
    from repro.models.sparse_ffn import (sparse_linear_apply,
                                         sparse_linear_from_dense)
    w = rng.standard_normal((24, 16)).astype(np.float32)
    layer = sparse_linear_from_dense(w, 0.6)
    vals = layer.init_values()
    x = jnp.asarray(rng.standard_normal((5, 16)), jnp.float32)

    def loss(v, x_, backend):
        y = sparse_linear_apply(layer, v, x_, backend=backend)
        return jnp.sum(y ** 2)

    gi = jax.grad(loss, argnums=(0, 1))(vals, x, "interpret")
    gj = jax.grad(loss, argnums=(0, 1))(vals, x, "jnp")
    for a_, b_ in zip(jax.tree.leaves(gi), jax.tree.leaves(gj)):
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)
    gn = sum(float(jnp.abs(t).sum()) for t in jax.tree.leaves(gi))
    assert np.isfinite(gn) and gn > 0


def test_gcn_hybrid_grad_end_to_end(rng):
    """The acceptance-criterion scenario: a hybrid-plan GCN loss, fp32,
    grads through backend='interpret' vs the dense-adjacency reference to
    <= 1e-4 — with no csr_to_dense in the differentiated path."""
    from repro.core import csr_to_dense, suite
    adj = suite.gcn_graph(256, 4, seed=0)
    fmt, plan = plan_and_convert(adj, total_workers=8)
    assert 0 < plan.r_boundary < adj.nrows, "scenario must be hybrid"
    n_in, n_out = 8, 4
    x = jnp.asarray(rng.standard_normal((adj.nrows, n_in)), jnp.float32)
    y = jnp.asarray(rng.integers(0, n_out, adj.nrows), jnp.int32)
    w = jnp.asarray(rng.standard_normal((n_in, n_out)) * 0.1, jnp.float32)

    def loss(w_, agg):
        logits = agg(jax.nn.relu(agg(x)) @ w_)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)

    g_loops = jax.grad(
        lambda w_: loss(w_, lambda h: loops_spmm(fmt, h,
                                                 backend="interpret")))(w)
    dense = jnp.asarray(csr_to_dense(adj))
    g_dense = jax.grad(lambda w_: loss(w_, lambda h: dense @ h))(w)
    np.testing.assert_allclose(np.asarray(g_loops), np.asarray(g_dense),
                               rtol=1e-4, atol=1e-4)


def _stored(fmt):
    """(row, col) of the stored entries of a LOOPS format: every CSR-part
    entry and the BCSR tile slots a stored entry landed in."""
    csr, bc = fmt.csr_part, fmt.bcsr_part
    coords = set(zip(csr.row_ids.tolist(), csr.col_idx.tolist()))
    t, off = np.nonzero(bc.stored)
    rows = fmt.r_boundary + bc.tile_rows[t] * bc.br + off
    return coords | set(zip(rows.tolist(), bc.tile_cols[t].tolist()))


def _with_zeros_and_empty_rows(rng, m, k, symmetric):
    """COO with explicit zeros, two empty rows and an empty column."""
    a = (rng.random((m, k)) < 0.25) * rng.standard_normal((m, k))
    if symmetric:
        a = np.triu(a) + np.triu(a, 1).T
    a[[3, m - 2]] = 0
    a[:, [3, m - 2]] = 0
    rows, cols = np.nonzero(a)
    zr, zc = [0, 5, 9, 9], [1, 0, 4, 9]        # stored zeros
    rows, cols = np.r_[rows, zr], np.r_[cols, zc]
    return formats_lib.csr_from_coo(rows, cols, a[rows, cols], (m, k))


@pytest.mark.parametrize("case,r_b", [
    ("nonsymmetric", 8), ("symmetric", 8), ("nonsymmetric", 0),
    ("symmetric", 24), ("transpose_of_transpose", 8)])
def test_transpose_holds_stored_entries_only(rng, case, r_b):
    """Aᵀ stores exactly A's stored entries, transposed — explicit zeros
    included, BCSR tile padding left out — plus one pad per empty row of
    Aᵀ, and densifies to Aᵀ; live values keep every tile slot of A's
    rows."""
    m = 24
    csr = _with_zeros_and_empty_rows(rng, m, m, case == "symmetric")
    fmt = loops_from_csr(csr, r_b, 8, panel_g=4)
    if case == "transpose_of_transpose":
        fmt = fmt.transposed().fmt         # a BCSR part that keeps zeros
    tl = fmt.transposed()
    want = {(c, r) for r, c in _stored(fmt)}
    empty = set(range(fmt.ncols)) - {r for r, _ in want}
    assert empty                               # Aᵀ has empty rows here
    assert _stored(tl.fmt) == want | {(r, 0) for r in empty}
    assert tl.n_slots == len(want) + len(empty)
    bc = fmt.bcsr_part
    assert bc.stored.sum() < bc.ntiles * bc.br  # A has tile padding
    np.testing.assert_allclose(_densify(tl.fmt), _densify(fmt).T)
    live = fmt.transposed(live_values=True)
    rows = fmt.r_boundary + np.repeat(bc.tile_rows, bc.br) * bc.br \
        + np.tile(np.arange(bc.br), bc.ntiles)
    slots = set(zip(np.repeat(bc.tile_cols, bc.br)[rows < m].tolist(),
                    rows[rows < m].tolist()))
    csr_t = {(c, r) for r, c in zip(fmt.csr_part.row_ids.tolist(),
                                    fmt.csr_part.col_idx.tolist())}
    live_want = slots | csr_t
    live_empty = set(range(fmt.ncols)) - {r for r, _ in live_want}
    assert live.n_slots == len(live_want) + len(live_empty)
    assert live.n_slots > tl.n_slots or fmt.r_boundary == m


def _densify(fmt):
    from repro.core import csr_to_dense
    out = np.zeros(fmt.shape, np.float64)
    out[:fmt.r_boundary] = csr_to_dense(fmt.csr_part)
    bc = fmt.bcsr_part
    for t in range(bc.ntiles):
        r0 = fmt.r_boundary + int(bc.tile_rows[t]) * bc.br
        for off in range(bc.br):
            if r0 + off < fmt.nrows:
                out[r0 + off, bc.tile_cols[t]] += bc.tile_vals[t, off]
    return out
