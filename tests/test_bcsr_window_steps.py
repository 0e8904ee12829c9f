"""The BCSR kernel's window-step grid (interpret mode) against the jnp
reference: one grid step per 128-lane window of a block row, B rows
fetched by a manual double-buffered DMA gather.

Every case compares ``loops_spmm`` or the kernel itself with
``ref.bcsr_spmm_ref`` / the dense product on a matrix shaped to put the
step table at one of its edges: block rows spanning windows, block rows
sharing one, padding lanes, chunk seams inside a block row (carry resume),
the fused and parts paths, the batched grid, packed half precision, N of
32, 128 and above one lane tile (a B row's 128-lane pieces fetched by one
DMA), a B row no tile reads holding inf/NaN, and a wide operand.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import csr_from_dense, loops_from_csr, loops_spmm
from repro.kernels import engine, ref
from repro.kernels.bcsr_spmm import bcsr_panels_spmm_pallas
from repro.kernels.panel_common import LANES, block_row_steps, window_steps


def _matrix(rng, m, k, density, dtype=jnp.float32):
    a = (rng.random((m, k)) < density) * rng.standard_normal((m, k))
    return np.array(jnp.asarray(a, dtype))


def _ref(fmt, b):
    bc = fmt.bcsr_part
    return np.asarray(ref.bcsr_spmm_ref(
        jnp.asarray(bc.tile_rows), jnp.asarray(bc.tile_cols),
        jnp.asarray(bc.tile_vals), b, bc.nblocks), np.float64)


def _parts(fmt, b, **kw):
    """The parts path: the BCSR part alone through the engine."""
    return np.asarray(engine.bcsr_spmm(
        fmt.bcsr_part, b, backend="interpret", panels=fmt.bcsr_panels,
        **kw), np.float64)


def _check(got, want, tol=1e-5):
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def case_row_spans_windows(rng):
    a = np.zeros((16, 400), np.float32)
    a[8:16] = _matrix(rng, 8, 400, 0.9)          # block row 1: ~400 tiles
    a[2, 5] = 1.5
    fmt = loops_from_csr(csr_from_dense(a), 0, 8, panel_g=8)
    rows, _ = fmt.bcsr_panels.steps
    assert (rows == 1).sum() >= 3                # three windows or more
    b = jnp.asarray(rng.standard_normal((400, 32)).astype(np.float32))
    _check(_parts(fmt, b)[:16], a @ np.asarray(b, np.float64), 1e-4)


def case_rows_share_a_window(rng):
    a = _matrix(rng, 64, 40, 0.02)               # 8 block rows, few tiles
    fmt = loops_from_csr(csr_from_dense(a), 0, 8, panel_g=4)
    rows, starts = fmt.bcsr_panels.steps
    assert starts[-1] <= LANES and len(rows) == fmt.bcsr_panels.nblocks
    b = jnp.asarray(rng.standard_normal((40, 32)).astype(np.float32))
    _check(_parts(fmt, b), _ref(fmt, b))


def case_padding_lanes(rng):
    a = np.zeros((24, 64), np.float32)
    for r, n in ((0, 3), (9, 13), (17, 1)):      # counts not multiples of G
        a[r, rng.choice(64, n, replace=False)] = rng.standard_normal(n)
    fmt = loops_from_csr(csr_from_dense(a), 0, 8, panel_g=8)
    assert (fmt.bcsr_panels.lane_cols < 0).any()
    b = jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32))
    _check(_parts(fmt, b)[:24], a @ np.asarray(b, np.float64))


def case_chunk_seams_resume(rng):
    a = np.zeros((32, 300), np.float32)
    a[8:16] = _matrix(rng, 8, 300, 0.95)
    a[0, 1], a[30, 299] = 2.0, -1.0
    fmt = loops_from_csr(csr_from_dense(a), 0, 8, panel_g=8)
    p = fmt.bcsr_panels
    b = jnp.asarray(rng.standard_normal((300, 32)).astype(np.float32))
    want = a @ np.asarray(b, np.float64)
    for per_call in (1, 2, 3):
        got = bcsr_panels_spmm_pallas(
            p.panel_rows, jnp.asarray(p.lane_cols), jnp.asarray(p.vals_window),
            b, g=8, nblocks=p.nblocks, interpret=True, per_call=per_call)
        _check(np.asarray(got, np.float64)[:32], want, 1e-4)


def case_fused_and_parts(rng):
    a = _matrix(rng, 40, 200, 0.3)
    b = jnp.asarray(rng.standard_normal((200, 32)).astype(np.float32))
    want = a @ np.asarray(b, np.float64)
    fused = loops_from_csr(csr_from_dense(a), 16, 8, panel_g=8)
    parts = loops_from_csr(csr_from_dense(a), 12, 4, panel_g=8)  # unaligned
    for fmt in (fused, parts):
        _check(np.asarray(loops_spmm(fmt, b, backend="interpret")), want,
               1e-4)


def case_batched(rng, n=32):
    a = _matrix(rng, 40, 200, 0.3)
    fmt = loops_from_csr(csr_from_dense(a), 16, 8, panel_g=8)
    b = jnp.asarray(rng.standard_normal((3, 200, n)).astype(np.float32))
    got = np.asarray(loops_spmm(fmt, b, backend="interpret"))
    for z in range(3):
        _check(got[z], a @ np.asarray(b[z], np.float64), 1e-4)
    _check(_parts(fmt, b)[1], _ref(fmt, b[1]), 1e-4)


def case_packed_bf16(rng):
    a = _matrix(rng, 48, 300, 0.3, jnp.bfloat16)
    fmt = loops_from_csr(csr_from_dense(a), 0, 16, panel_g=8)
    b = jnp.asarray(rng.standard_normal((300, 32)), jnp.bfloat16)
    got = _parts(fmt, b)
    _check(got, _ref(fmt, b), 3e-2)
    _check(got, np.asarray(a, np.float64) @ np.asarray(b, np.float64), 3e-2)


def case_width(rng, n):
    """One column block of ``n`` lanes: at N 200 padded to two pieces, at
    N 256 and 384 two and three, each lane's pieces one DMA."""
    a = _matrix(rng, 40, 200, 0.3)
    fmt = loops_from_csr(csr_from_dense(a), 0, 8, panel_g=8)
    b = jnp.asarray(rng.standard_normal((200, n)).astype(np.float32))
    _check(_parts(fmt, b), a @ np.asarray(b, np.float64), 1e-4)


def case_unread_row_nonfinite(rng, n=32):
    """B rows no tile reads hold inf/NaN: row 7, and row 0, which padding
    lanes fetch and the mask drops."""
    a = _matrix(rng, 40, 64, 0.3)
    a[:, [0, 7]] = 0.0
    fmt = loops_from_csr(csr_from_dense(a), 0, 8, panel_g=8)
    assert (fmt.bcsr_panels.lane_cols < 0).any()
    b = rng.standard_normal((64, n)).astype(np.float32)
    b[[0, 7], ::2], b[[0, 7], 1::2] = np.inf, np.nan
    got = _parts(fmt, jnp.asarray(b))
    assert np.isfinite(got).all()
    b[[0, 7]] = 0.0
    _check(got, a @ b.astype(np.float64), 1e-4)


def case_wide_operand(rng):
    """B rows spread over a 40,000-row operand, fetched by DMA."""
    a = np.zeros((64, 40000), np.float32)
    for r in range(64):
        cols = np.r_[rng.choice(2000, 20, replace=False),
                     rng.choice(np.arange(20000, 40000), 5, replace=False)]
        a[r, cols] = rng.standard_normal(25)
    fmt = loops_from_csr(csr_from_dense(a), 0, 8, panel_g=8)
    b = jnp.asarray(rng.standard_normal((40000, 32)).astype(np.float32))
    _check(_parts(fmt, b), _ref(fmt, b), 1e-4)


def _at(case, n, name):
    """``case`` at N ``n``, as the entry ``name`` of :data:`CASES`."""
    entry = functools.partial(case, n=n)
    entry.__name__ = name
    return entry


CASES = [case_row_spans_windows, case_rows_share_a_window,
         case_padding_lanes, case_chunk_seams_resume, case_fused_and_parts,
         case_batched, case_packed_bf16,
         _at(case_width, 128, "case_n128"), case_unread_row_nonfinite,
         case_wide_operand, _at(case_width, 200, "case_n200"),
         _at(case_width, 256, "case_n256"), _at(case_width, 384, "case_n384"),
         _at(case_batched, 256, "case_batched_n256"),
         _at(case_unread_row_nonfinite, 256,
             "case_unread_row_nonfinite_n256")]


@pytest.mark.parametrize("case", CASES,
                         ids=[c.__name__[5:] for c in CASES])
def test_window_steps_match_reference(rng, case):
    case(rng)


def test_window_steps_table():
    """Block rows split at every 128-lane window boundary; a block row
    opens a step of its own; the predictor's count from lanes per block
    row agrees."""
    # G=8 lanes per panel: block rows of 2, 20 (spans windows) and 1 panel
    panel_rows = np.repeat([0, 1, 2], [2, 20, 1]).astype(np.int32)
    rows, starts = window_steps(panel_rows, 8)
    assert rows.tolist() == [0, 1, 1, 2]
    assert starts.tolist() == [0, 16, 128, 176, 184]
    assert block_row_steps([16, 160, 8]) == len(rows)


def test_wide_operand_is_its_halves_bitwise(rng):
    """At N 256 a lane's two 128-lane pieces arrive by one DMA and are
    contracted one by one, in the order and arithmetic of N 128: each half
    of the result is bitwise the result on that half of B."""
    a = _matrix(rng, 48, 300, 0.3)
    fmt = loops_from_csr(csr_from_dense(a), 0, 8, panel_g=8)
    b = jnp.asarray(rng.standard_normal((300, 256)).astype(np.float32))
    whole = np.asarray(engine.bcsr_spmm(
        fmt.bcsr_part, b, backend="interpret", panels=fmt.bcsr_panels))
    for h in range(2):
        half = np.asarray(engine.bcsr_spmm(
            fmt.bcsr_part, b[:, h * LANES:(h + 1) * LANES],
            backend="interpret", panels=fmt.bcsr_panels))
        np.testing.assert_array_equal(whole[:, h * LANES:(h + 1) * LANES],
                                      half)


def test_engine_gathers_count_the_valid_tiles(rng):
    """``engine.gathers`` counts the B rows the step table fetches: one per
    tile, none for padding lanes; ``engine.gather_dmas`` the DMAs that
    fetch them, one per lane (padding lanes included) at N 32 and at N 256,
    where a row is two 128-lane pieces."""
    from repro.obs import Obs
    a = np.zeros((16, 12), np.float32)
    a[0, [1, 4, 9]] = 1.0          # block row 0: 3 tiles (rows 0-7)
    a[5, [4, 11]] = 2.0            # ... plus column 11: 4 tiles
    a[12, [0, 2]] = 3.0            # block row 1: 2 tiles
    fmt = loops_from_csr(csr_from_dense(a), 0, 8, panel_g=8)
    assert fmt.bcsr_part.ntiles == 6
    assert fmt.bcsr_panels.lane_cols.size == 16  # 2 panels of 8 lanes
    for n in (32, 256):
        obs = Obs(source="t")
        with obs.attach_engine():
            out = loops_spmm(fmt, jnp.ones((12, n), jnp.float32),
                             backend="interpret")
        _check(np.asarray(out), a @ np.ones((12, n)))
        counters = {inst.name: inst.value
                    for _, inst in obs.metrics.instruments()
                    if inst.labels.get("part") == "bcsr"}
        assert counters["engine.gathers"] == 6
        assert counters["engine.gather_dmas"] == 16
        assert counters["engine.grid_steps_compiled"] == 2  # one a block row
