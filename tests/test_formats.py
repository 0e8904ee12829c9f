"""Format construction: CSR round-trip, Algorithm 1 conversion, invariants."""
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import (bcsr_from_csr_rows, csr_from_coo, csr_from_dense,
                        csr_to_dense, loops_from_csr, row_stats)


def _rand_dense(seed, m, k, density):
    rng = np.random.default_rng(seed)
    return ((rng.random((m, k)) < density)
            * rng.standard_normal((m, k))).astype(np.float32)


def _loops_to_dense(fmt):
    """Reassemble a dense matrix from the hybrid format."""
    out = np.zeros(fmt.shape, np.float32)
    c = fmt.csr_part
    np.add.at(out[:fmt.r_boundary], (c.row_ids, c.col_idx), c.vals)
    b = fmt.bcsr_part
    for t in range(b.ntiles):
        r0 = fmt.r_boundary + int(b.tile_rows[t]) * b.br
        col = int(b.tile_cols[t])
        for i in range(b.br):
            if r0 + i < fmt.shape[0]:
                out[r0 + i, col] += b.tile_vals[t, i]
    return out


def test_csr_round_trip():
    a = _rand_dense(0, 23, 17, 0.2)
    assert np.array_equal(csr_to_dense(csr_from_dense(a)), a)


def test_csr_empty_rows_padded():
    a = np.zeros((5, 4), np.float32)
    a[1, 2] = 3.0
    csr = csr_from_dense(a)
    counts = np.diff(csr.row_ptr)
    assert (counts >= 1).all()  # every row visited (kernel contract)
    assert np.array_equal(csr_to_dense(csr), a)


@given(st.integers(0, 6), st.integers(1, 40), st.integers(1, 30),
       st.sampled_from([0.0, 0.05, 0.3, 0.9]), st.sampled_from([2, 4, 8]))
def test_loops_conversion_value_preserving(seed, m, k, density, br):
    """Algorithm 1 must preserve every value for ANY r_boundary."""
    a = _rand_dense(seed, m, k, density)
    csr = csr_from_dense(a)
    for r_b in {0, m // 2, m}:
        fmt = loops_from_csr(csr, r_b, br)
        np.testing.assert_allclose(_loops_to_dense(fmt), a, rtol=1e-6)


@given(st.integers(0, 5), st.integers(1, 50), st.sampled_from([2, 8]))
def test_bcsr_invariants(seed, m, br):
    a = _rand_dense(seed, m, m, 0.2)
    csr = csr_from_dense(a)
    b = bcsr_from_csr_rows(csr, 0, m, br)
    # tiles sorted by (block_row, col); every block-row represented
    rows = b.tile_rows
    assert (np.diff(rows) >= 0).all()
    assert set(range(b.nblocks)) <= set(rows.tolist())
    assert b.nblocks == max((m + br - 1) // br, 1)
    # block_ptr consistent with tile_rows
    counts = np.bincount(rows, minlength=b.nblocks)
    assert np.array_equal(np.diff(b.block_ptr), counts)


def test_row_stats_matches_numpy():
    a = _rand_dense(1, 64, 32, 0.15)
    csr = csr_from_dense(a)
    s = row_stats(csr)
    counts = (a != 0).sum(1)
    # stats include structural pads for empty rows; only compare when no
    # empty rows exist
    if (counts > 0).all():
        assert s.nnz_max == counts.max()
        assert abs(s.nnz_mean - counts.mean()) < 1e-9


def test_coo_duplicate_accumulation():
    rows = [0, 0, 1]
    cols = [1, 1, 0]
    vals = [2.0, 3.0, 4.0]
    csr = csr_from_coo(rows, cols, vals, (2, 2))
    dense = csr_to_dense(csr)
    assert dense[0, 1] == pytest.approx(5.0)
    assert dense[1, 0] == pytest.approx(4.0)
    # Coalescing happens during *construction* (full regression suite:
    # tests/test_tune.py, which also runs in hypothesis-free environments).
    coords = list(zip(csr.row_ids.tolist(), csr.col_idx.tolist()))
    assert len(coords) == len(set(coords))


def _bcsr_loop_reference(csr, start, stop, br, keep_zeros):
    """The per-nonzero dict construction ``bcsr_from_csr_rows`` replaced:
    the reference its vectorised form must reproduce exactly."""
    nrows = stop - start
    nblocks = max((nrows + br - 1) // br, 1)
    tile_map, dest = {}, []
    for i in range(start, stop):
        tr, off = (i - start) // br, (i - start) % br
        for k in range(int(csr.row_ptr[i]), int(csr.row_ptr[i + 1])):
            j, v = int(csr.col_idx[k]), csr.vals[k]
            if v == 0 and not keep_zeros:
                dest.append(None)
                continue
            tile_map.setdefault((tr, j), np.zeros(br, csr.vals.dtype))
            tile_map[(tr, j)][off] += v
            dest.append((tr, j, off))
    present = {tr for tr, _ in tile_map}
    for tr in range(nblocks):
        if tr not in present:
            tile_map[(tr, 0)] = np.zeros(br, csr.vals.dtype)
    keys = sorted(tile_map)
    tile_of = {k: t for t, k in enumerate(keys)}
    slot = np.array([-1 if d is None else tile_of[d[:2]] * br + d[2]
                     for d in dest], np.int64)
    return (np.array([k[0] for k in keys], np.int32),
            np.array([k[1] for k in keys], np.int32),
            np.stack([tile_map[k] for k in keys]), slot)


@pytest.mark.parametrize("keep_zeros", [False, True])
@pytest.mark.parametrize("kind", ["banded", "powerlaw", "block", "uniform"])
def test_bcsr_vectorised_matches_loop_and_dense(kind, keep_zeros):
    from repro.core import suite
    gen = {"banded": lambda: suite.banded(200, 200, 5, fill=0.7, seed=1),
           "powerlaw": lambda: suite.powerlaw(200, 200, 6.0, seed=2),
           "block": lambda: suite.block_dense(192, 192, 16, 0.2, seed=3),
           "uniform": lambda: suite.uniform(200, 160, 0.04, seed=4)}[kind]
    csr = gen()
    csr = csr.astype(np.float32)
    vals = csr.vals.copy()
    vals[::7] = 0.0              # zero-valued stored entries: dropped or kept
    csr = type(csr)(row_ptr=csr.row_ptr, col_idx=csr.col_idx, vals=vals,
                    row_ids=csr.row_ids, shape=csr.shape)
    start, stop, br = 37, csr.nrows, 8
    bc, slot = bcsr_from_csr_rows(csr, start, stop, br,
                                  keep_zeros=keep_zeros, return_map=True)
    rows, cols, tvals, slot_ref = _bcsr_loop_reference(csr, start, stop, br,
                                                       keep_zeros)
    np.testing.assert_array_equal(bc.tile_rows, rows)
    np.testing.assert_array_equal(bc.tile_cols, cols)
    np.testing.assert_array_equal(bc.tile_vals, tvals)
    np.testing.assert_array_equal(slot, slot_ref)
    # dense reconstruction of the tiles equals the sliced rows
    dense = np.zeros((bc.nblocks * br, csr.ncols), np.float32)
    for t in range(bc.ntiles):
        r0 = int(bc.tile_rows[t]) * br
        dense[r0:r0 + br, int(bc.tile_cols[t])] += bc.tile_vals[t]
    np.testing.assert_array_equal(dense[:stop - start],
                                  csr_to_dense(csr)[start:stop])
    # the slot_map scatter carries the entries' values into the tiles
    s, e = int(csr.row_ptr[start]), int(csr.row_ptr[stop])
    flat = np.zeros(bc.ntiles * br, np.float32)
    kept = slot >= 0
    np.add.at(flat, slot[kept], csr.vals[s:e][kept])
    np.testing.assert_array_equal(flat.reshape(bc.ntiles, br), bc.tile_vals)
