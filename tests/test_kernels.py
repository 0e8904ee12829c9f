"""Per-kernel sweeps: Pallas (interpret=True) vs the pure-jnp oracle, over
shapes x dtypes x sparsity patterns."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import csr_from_dense, loops_from_csr
from repro.kernels import ref
from repro.kernels.bcsr_spmm import bcsr_spmm_pallas
from repro.kernels.csr_spmm import csr_spmm_pallas

DTYPES = [(jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)]
SHAPES = [(1, 1, 4), (7, 5, 8), (16, 16, 16), (33, 29, 32), (40, 64, 128)]
DENSITIES = [0.02, 0.2, 0.7]


def _sparse(rng, m, k, density, dtype):
    a = ((rng.random((m, k)) < density) * rng.standard_normal((m, k)))
    return np.asarray(jnp.asarray(a, dtype))


@pytest.mark.parametrize("dtype,tol", DTYPES)
@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("density", DENSITIES)
def test_csr_kernel_matches_ref(rng, dtype, tol, m, k, n, density):
    a = _sparse(rng, m, k, density, dtype)
    b = jnp.asarray(rng.standard_normal((k, n)), dtype)
    csr = csr_from_dense(a)
    row_ids = jnp.asarray(csr.row_ids)
    col_idx = jnp.asarray(csr.col_idx)
    vals = jnp.asarray(csr.vals)
    got = csr_spmm_pallas(row_ids, col_idx, vals, b, nrows=m, interpret=True)
    want = ref.csr_spmm_ref(row_ids, col_idx, vals, b, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)
    # and against the dense ground truth
    dense = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    np.testing.assert_allclose(np.asarray(got), dense, rtol=10 * tol,
                               atol=10 * tol)


@pytest.mark.parametrize("dtype,tol", DTYPES)
@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("br", [2, 8])
def test_bcsr_kernel_matches_ref(rng, dtype, tol, m, k, n, br):
    a = _sparse(rng, m, k, 0.25, dtype)
    b = jnp.asarray(rng.standard_normal((k, n)), dtype)
    fmt = loops_from_csr(csr_from_dense(a), 0, br)  # pure BCSR
    bc = fmt.bcsr_part
    got = bcsr_spmm_pallas(jnp.asarray(bc.tile_rows),
                           jnp.asarray(bc.tile_cols),
                           jnp.asarray(bc.tile_vals), b,
                           nblocks=bc.nblocks, interpret=True)
    want = ref.bcsr_spmm_ref(jnp.asarray(bc.tile_rows),
                             jnp.asarray(bc.tile_cols),
                             jnp.asarray(bc.tile_vals), b, bc.nblocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)
    dense = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    np.testing.assert_allclose(np.asarray(got)[:m], dense, rtol=10 * tol,
                               atol=10 * tol)


def test_fp64_kernels(rng):
    """FP64 path (paper's highest precision) — needs x64."""
    jax.config.update("jax_enable_x64", True)
    try:
        m, k, n = 19, 13, 8
        a = _sparse(rng, m, k, 0.3, jnp.float64)
        b = jnp.asarray(rng.standard_normal((k, n)), jnp.float64)
        csr = csr_from_dense(a)
        got = csr_spmm_pallas(jnp.asarray(csr.row_ids),
                              jnp.asarray(csr.col_idx),
                              jnp.asarray(csr.vals), b, nrows=m,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(a) @ np.asarray(b), rtol=1e-12)
        assert got.dtype == jnp.float64
    finally:
        jax.config.update("jax_enable_x64", False)


def test_bn_blocking_equivalence(rng):
    """Wider bn (the multi-ZA-tile analogue) must not change results."""
    m, k, n = 24, 16, 64
    a = _sparse(rng, m, k, 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    csr = csr_from_dense(a)
    args = (jnp.asarray(csr.row_ids), jnp.asarray(csr.col_idx),
            jnp.asarray(csr.vals), b)
    outs = [csr_spmm_pallas(*args, nrows=m, bn=bn, interpret=True)
            for bn in (16, 32, 64)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-6)


def test_out_dtype_override(rng):
    m, k, n = 8, 8, 8
    a = _sparse(rng, m, k, 0.5, jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.bfloat16)
    csr = csr_from_dense(a)
    out = csr_spmm_pallas(jnp.asarray(csr.row_ids), jnp.asarray(csr.col_idx),
                          jnp.asarray(csr.vals), b, nrows=m,
                          out_dtype=jnp.bfloat16, interpret=True)
    assert out.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# G-wide panel kernels: adversarial panel shapes vs the jnp oracle
# ---------------------------------------------------------------------------

import contextlib
import functools

from repro.core import loops_grid_steps, loops_spmm
from repro.core.formats import panelize_bcsr, panelize_csr
from repro.kernels.bcsr_spmm import bcsr_panels_spmm_pallas
from repro.kernels.csr_spmm import csr_panels_spmm_pallas

PANEL_GS = [1, 4, 8]
PANEL_DTYPES = [(jnp.float32, 1e-5), (jnp.bfloat16, 3e-2),
                (jnp.float64, 1e-12)]


@contextlib.contextmanager
def _x64_if(dtype):
    if jnp.dtype(dtype) == jnp.float64:
        jax.config.update("jax_enable_x64", True)
        try:
            yield
        finally:
            jax.config.update("jax_enable_x64", False)
    else:
        yield


def _adversarial_cases(rng, dtype):
    """Dense matrices whose panelizations exercise every padding edge."""
    cases = {}
    # nnz not divisible by G: odd-count random fill
    a = _sparse(rng, 11, 9, 0.35, dtype)
    cases["indivisible"] = a
    # single-row matrix
    cases["single_row"] = _sparse(rng, 1, 13, 0.6, dtype)
    # one hub row spanning multiple panels (nnz >> G)
    hub = np.zeros((5, 24))
    hub[2, :] = rng.standard_normal(24)
    hub[0, 3] = 1.5
    cases["row_spans_panels"] = np.asarray(jnp.asarray(hub, dtype))
    # many short rows: a contiguous nonzero stream would let panels span row
    # boundaries — packing must pad at each boundary instead
    short = np.zeros((9, 6))
    for r in range(9):
        short[r, r % 6] = r + 1.0
        if r % 2:
            short[r, (r + 3) % 6] = -1.0
    cases["panel_at_row_boundary"] = np.asarray(jnp.asarray(short, dtype))
    return cases


@pytest.mark.parametrize("dtype,tol", PANEL_DTYPES)
@pytest.mark.parametrize("g", PANEL_GS)
def test_csr_panel_kernel_adversarial(rng, dtype, tol, g):
    with _x64_if(dtype):
        for name, a in _adversarial_cases(rng, dtype).items():
            m, k = a.shape
            b = jnp.asarray(rng.standard_normal((k, 8)), dtype)
            csr = csr_from_dense(a)
            p = panelize_csr(csr, g)
            # no panel mixes rows, all rows covered, mask marks real lanes
            assert (np.diff(p.panel_rows) >= 0).all()
            assert set(p.panel_rows.tolist()) == set(range(m))
            assert int(p.panel_mask.sum()) == csr.nnz
            got = csr_panels_spmm_pallas(
                jnp.asarray(p.panel_rows), jnp.asarray(p.lane_cols),
                jnp.asarray(p.lane_vals), b, g=g, nrows=m, interpret=True)
            want = ref.csr_spmm_ref(jnp.asarray(csr.row_ids),
                                    jnp.asarray(csr.col_idx),
                                    jnp.asarray(csr.vals), b, m)
            np.testing.assert_allclose(np.asarray(got, np.float64),
                                       np.asarray(want, np.float64),
                                       rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("dtype,tol", PANEL_DTYPES)
@pytest.mark.parametrize("g", PANEL_GS)
def test_bcsr_panel_kernel_adversarial(rng, dtype, tol, g):
    with _x64_if(dtype):
        for name, a in _adversarial_cases(rng, dtype).items():
            m, k = a.shape
            b = jnp.asarray(rng.standard_normal((k, 8)), dtype)
            fmt = loops_from_csr(csr_from_dense(a), 0, 4, panel_g=g)
            p = fmt.bcsr_panels
            assert (np.diff(p.panel_rows) >= 0).all()
            assert set(p.panel_rows.tolist()) == set(range(p.nblocks))
            got = bcsr_panels_spmm_pallas(
                jnp.asarray(p.panel_rows), jnp.asarray(p.lane_cols),
                jnp.asarray(p.vals_window), b, g=g, nblocks=p.nblocks,
                interpret=True)
            bc = fmt.bcsr_part
            want = ref.bcsr_spmm_ref(jnp.asarray(bc.tile_rows),
                                     jnp.asarray(bc.tile_cols),
                                     jnp.asarray(bc.tile_vals), b,
                                     bc.nblocks)
            np.testing.assert_allclose(np.asarray(got, np.float64),
                                       np.asarray(want, np.float64),
                                       rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("g", PANEL_GS)
def test_hybrid_panel_parity_nondivisible(rng, g):
    """End-to-end hybrid at a br-aligned boundary, nnz not divisible by G:
    the fused single-pass output must match dense exactly."""
    m, k, n = 21, 17, 16
    a = _sparse(rng, m, k, 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    fmt = loops_from_csr(csr_from_dense(a), 8, 8, panel_g=g)
    out = loops_spmm(fmt, b, backend="interpret")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(a, np.float32) @ np.asarray(b),
        rtol=1e-4, atol=1e-4)


def test_fused_single_pass_no_concatenate(rng):
    """Hybrid Pallas execution is single-pass: both kernels write disjoint
    row ranges of one buffer; no concatenate appears anywhere in the jaxpr
    (inner pallas jaxprs included)."""
    a = _sparse(rng, 32, 24, 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((24, 16)), jnp.float32)
    fmt = loops_from_csr(csr_from_dense(a), 16, 8, panel_g=4)
    jaxpr = jax.make_jaxpr(
        lambda bb: loops_spmm(fmt, bb, backend="interpret"))(b)
    assert "concatenate" not in str(jaxpr)


def test_empty_matrix_returns_full_zero_block(rng):
    """Zero nnz in both parts with nrows > 0 must yield (nrows, N) zeros,
    not a (0, N) stub."""
    fmt = loops_from_csr(csr_from_dense(np.zeros((7, 5), np.float32)), 0, 8)
    assert fmt.nnz == 0 and fmt.nrows == 7
    b = jnp.asarray(rng.standard_normal((5, 8)), jnp.float32)
    for backend in ("interpret", "jnp"):
        out = loops_spmm(fmt, b, backend=backend)
        assert out.shape == (7, 8)
        assert not np.asarray(out).any()


def test_grid_steps_shrink_with_g(rng):
    a = _sparse(rng, 64, 48, 0.25, jnp.float32)
    csr = csr_from_dense(a)
    steps = {g: loops_grid_steps(loops_from_csr(csr, 32, 8, panel_g=g), 32)
             for g in (1, 4, 8)}
    assert steps[8] <= steps[4] <= steps[1]
    assert steps[1] >= 2 * steps[8]  # the Fig.2 batching pays off


def test_default_br_named_constants():
    from repro.core.formats import HALF_PACKED_ROWS, SUBLANE_ROWS
    from repro.core.spmm import default_br
    assert default_br(jnp.float32) == SUBLANE_ROWS == 8
    assert default_br(jnp.float64) == SUBLANE_ROWS
    assert default_br(jnp.bfloat16) == HALF_PACKED_ROWS == 16
    assert default_br(jnp.float16) == HALF_PACKED_ROWS


# ---------------------------------------------------------------------------
# SMEM-sized chunks: rows spanning a chunk seam resume from the carry
# ---------------------------------------------------------------------------

def _hub_matrix(rng):
    """Rows 0-2 short, row 3 a hub of 40 nonzeros (10 panels at G=4),
    rows 4-7 short, an empty row 8: with 3 panels per call the hub spans
    four chunks and every seam lands inside or beside it."""
    a = np.zeros((9, 48), np.float32)
    for r in (0, 1, 2, 4, 5, 6, 7):
        a[r, rng.choice(48, 3, replace=False)] = rng.standard_normal(3)
    a[3, rng.choice(48, 40, replace=False)] = rng.standard_normal(40)
    return a


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("batched", [False, True])
def test_csr_chunks_resume_rows_across_seams(rng, depth, batched):
    a = _hub_matrix(rng)
    csr = csr_from_dense(a)
    p = panelize_csr(csr, 4)
    shape = ((3, 48, 8) if batched else (48, 8))
    b = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    args = (jnp.asarray(p.panel_rows), jnp.asarray(p.lane_cols),
            jnp.asarray(p.lane_vals), b)
    whole = csr_panels_spmm_pallas(*args, g=4, nrows=9, interpret=True,
                                   pipeline_depth=depth)
    chunked = csr_panels_spmm_pallas(*args, g=4, nrows=9, interpret=True,
                                     pipeline_depth=depth, panels_per_call=3)
    want = a @ np.asarray(b)
    np.testing.assert_allclose(np.asarray(chunked), want, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(whole),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("depth", [1, 2])
def test_bcsr_chunks_with_carry_keep_foreign_rows(rng, depth):
    """The fused path's carry across chunk launches: rows the kernel never
    visits keep the carry's values, visited block-rows spanning a seam sum
    every panel."""
    a = np.zeros((16, 40), np.float32)
    a[4:8] = rng.standard_normal((4, 40))          # block-row 1: 40 tiles
    a[0, 3], a[13, 7] = 1.0, -2.0
    fmt = loops_from_csr(csr_from_dense(a), 0, 4, panel_g=4)
    p = fmt.bcsr_panels
    b = jnp.asarray(rng.standard_normal((40, 8)).astype(np.float32))
    sentinel = jnp.full((24, 8), 7.0, jnp.float32)  # rows 16..23: foreign
    got = bcsr_panels_spmm_pallas(
        jnp.asarray(p.panel_rows), jnp.asarray(p.lane_cols),
        jnp.asarray(p.vals_window), b, g=4, nblocks=p.nblocks, out_rows=24,
        carry=sentinel, interpret=True, pipeline_depth=depth,
        panels_per_call=5)
    np.testing.assert_allclose(np.asarray(got)[:16], a @ np.asarray(b),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got)[16:], 7.0)


@pytest.mark.parametrize("part", ["csr", "bcsr"])
def test_sdd_chunks_match_single_call(rng, part):
    from repro.kernels.spmm_sdd import (bcsr_sdd_panels_pallas,
                                        csr_sdd_panels_pallas)
    a = _hub_matrix(rng)
    dy = jnp.asarray(rng.standard_normal((12, 8)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((48, 8)).astype(np.float32))
    if part == "csr":
        p = panelize_csr(csr_from_dense(a), 4)
        run = functools.partial(csr_sdd_panels_pallas, jnp.asarray(
            p.panel_rows), jnp.asarray(p.lane_cols), dy[:9], b, g=4,
            interpret=True)
    else:
        p = loops_from_csr(csr_from_dense(a), 0, 4, panel_g=4).bcsr_panels
        run = functools.partial(bcsr_sdd_panels_pallas, jnp.asarray(
            p.panel_rows), jnp.asarray(p.lane_cols), dy, b, g=4, br=4,
            interpret=True)
    np.testing.assert_allclose(np.asarray(run(panels_per_call=3)),
                               np.asarray(run()), rtol=1e-6, atol=1e-6)


def test_grid_steps_count_one_ramp_per_chunk():
    """Depth-2 grids pay their ramp once per launch: a part with more
    panels than one SMEM chunk holds counts every launch's ramp, and the
    replay predictor agrees exactly."""
    from repro.core.spmm import SpmmPlan
    from repro.kernels.panel_common import CSR_WORDS, panel_calls
    from repro.perf.replay import predict_part_steps
    a = np.zeros((30_000, 64), np.float32)
    a[np.arange(30_000), np.arange(30_000) % 64] = 1.0
    csr = csr_from_dense(a)
    plan = SpmmPlan(r_boundary=30_000, t_vpu=1, t_mxu=0, br=8, panel_g=1,
                    pipeline_depth=2)
    fmt = loops_from_csr(csr, 30_000, 8, panel_g=1, pipeline_depth=2)
    calls = panel_calls(fmt.csr_panels.npanels, 1, CSR_WORDS)
    assert calls > 1
    assert loops_grid_steps(fmt, 8) == 30_000 + calls
    assert predict_part_steps(csr, plan, 8) == (30_000 + calls, 0)
