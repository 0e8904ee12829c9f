"""Sparse formats for LOOPS (paper §3.2).

The LOOPS hybrid format row-splits a CSR matrix at ``r_boundary`` into

  * a **CSR-part** (rows ``[0, r_boundary)``) kept in row-wise CSR and executed by
    the *vector* pipeline (paper: NEON AXPY kernel; here: TPU VPU Pallas kernel),
  * a **vector-wise BCSR-part** (rows ``[r_boundary, nrows)``) re-tiled into
    asymmetric ``Br x 1`` column tiles executed by the *matrix* pipeline
    (paper: SME ``fmopa`` outer products into ZA tiles; here: TPU MXU rank-1
    accumulation chains — the systolic array natively sums rank-1 updates).

Construction follows the paper's Algorithm 1.  All format construction is
host-side numpy (the paper likewise excludes conversion from kernel timing and
amortizes it in end-to-end runs, §4.5); the resulting arrays are jit-traceable
constants or device arrays.

TPU-specific invariants (documented deviations from the Arm layout):
  * every CSR row and every BCSR block-row carries at least one (possibly
    zero-valued) entry so that the scatter-style Pallas output ``index_map``
    visits — and therefore initialises — every output block;
  * entries are sorted by (row, col) / (block_row, col): the kernels rely on the
    *monotone* output index to legally revisit accumulator blocks.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import numpy as np

__all__ = [
    "CSR",
    "VectorBCSR",
    "PanelCSR",
    "PanelBCSR",
    "LoopsFormat",
    "TransposedLoops",
    "csr_from_dense",
    "csr_to_dense",
    "csr_slice_rows",
    "bcsr_from_csr_rows",
    "panelize_csr",
    "panelize_bcsr",
    "loops_from_csr",
    "loops_from_csr_mapped",
    "transposed_values",
    "SUBLANE_ROWS",
    "HALF_PACKED_ROWS",
    "DEFAULT_PANEL_G",
]

# Tile heights (paper: cntd / cntf / cnth — elements per vector register).
# TPU vregs are (8, 128): fp32/fp64 tiles use the 8-sublane extent; bf16/fp16
# pack two values per 32-bit lane, doubling the natural tile height exactly as
# the paper's cnth = 2 * cntf.  ``core.spmm.default_br`` selects between them.
SUBLANE_ROWS = 8
HALF_PACKED_ROWS = 2 * SUBLANE_ROWS

# Default panel width G: nonzeros (CSR part) / tiles (BCSR part) processed per
# kernel grid step.  8 matches the paper's Figure-2 multi-tile fmopa batching
# (several outer-product rounds per ZA-tile visit) and shrinks the Pallas grid
# from nnz to ~nnz/G steps.
DEFAULT_PANEL_G = 8


@dataclasses.dataclass(frozen=True)
class CSR:
    """Standard CSR with an auxiliary per-nonzero row-id array.

    ``row_ids`` is redundant with ``row_ptr`` but makes both the pure-jnp
    reference (segment-sum) and the Pallas scatter kernel static-shape friendly.
    """

    row_ptr: np.ndarray  # (nrows + 1,) int32
    col_idx: np.ndarray  # (nnz,) int32
    vals: np.ndarray     # (nnz,) float
    row_ids: np.ndarray  # (nnz,) int32, nondecreasing
    shape: Tuple[int, int]

    @property
    def nrows(self) -> int:
        return self.shape[0]

    @property
    def ncols(self) -> int:
        return self.shape[1]

    @property
    def nnz(self) -> int:
        return int(self.vals.shape[0])

    def astype(self, dtype) -> "CSR":
        return dataclasses.replace(self, vals=self.vals.astype(dtype))


@dataclasses.dataclass(frozen=True)
class VectorBCSR:
    """Vector-wise BCSR: ``Br x 1`` column tiles grouped by block-row.

    A tile ``t`` holds the ``Br`` values of column ``tile_cols[t]`` for the rows
    ``[row_offset + tile_rows[t]*Br, ... + Br)``.  ``tile_rows`` is sorted
    nondecreasing; within a block-row tiles are sorted by column.  This is the
    paper's LOOPS BCSR-part with ``(B_r, B_c) = (vector_size, 1)`` — the
    asymmetric shape that kills the zero-propagation padding of square tiles
    (paper C1) — stored as CSR-of-tiles rather than ELL so that skewed
    block-rows cost no padding.
    """

    tile_rows: np.ndarray  # (ntiles,) int32 block-row index, nondecreasing
    tile_cols: np.ndarray  # (ntiles,) int32 column index
    tile_vals: np.ndarray  # (ntiles, Br) float
    block_ptr: np.ndarray  # (nblocks + 1,) int32 tile extents per block-row
    br: int                # tile height (paper: cntd / cntf / cnth)
    nrows: int             # logical row count covered (<= nblocks * br)
    shape: Tuple[int, int]  # (nrows, ncols)

    @property
    def nblocks(self) -> int:
        return int(self.block_ptr.shape[0] - 1)

    @property
    def ntiles(self) -> int:
        return int(self.tile_cols.shape[0])

    @property
    def ncols(self) -> int:
        return self.shape[1]

    def astype(self, dtype) -> "VectorBCSR":
        return dataclasses.replace(self, tile_vals=self.tile_vals.astype(dtype))


@dataclasses.dataclass(frozen=True)
class PanelCSR:
    """CSR-part nonzeros packed into dense ``(P, G)`` panels.

    Panel ``p`` holds up to ``G`` nonzeros of the single output row
    ``panel_rows[p]``: the kernel gathers the ``G`` rows
    ``B[panel_cols[p]]`` once and broadcast-multiply-reduces them against
    ``panel_vals[p]`` in one grid step — the paper's Figure-2 multi-tile
    batching applied to the vector pipeline.  Rows never share a panel
    (the scatter-output index map writes one row per step), so a row's
    last panel is padded: ``panel_mask`` is 1 for real entries, 0 for
    padding (padding has col 0 and value 0).  ``panel_rows`` is
    nondecreasing and covers every row at least once, preserving the
    output-coverage and monotone-revisit invariants of the G=1 layout.
    """

    panel_rows: np.ndarray  # (P,) int32 output row per panel, nondecreasing
    panel_cols: np.ndarray  # (P, G) int32 gather rows of B (0 where padded)
    panel_vals: np.ndarray  # (P, G) values (0 where padded)
    panel_mask: np.ndarray  # (P, G) validity, same dtype as vals (1 / 0)
    src_panel: np.ndarray   # (nnz,) int32 panel of flat nonzero k
    src_lane: np.ndarray    # (nnz,) int32 lane of flat nonzero k
    g: int
    nrows: int
    shape: Tuple[int, int]

    @property
    def npanels(self) -> int:
        return int(self.panel_rows.shape[0])

    def astype(self, dtype) -> "PanelCSR":
        return dataclasses.replace(self,
                                   panel_vals=self.panel_vals.astype(dtype),
                                   panel_mask=self.panel_mask.astype(dtype))

    def scatter_values(self, vals):
        """Traced flat ``(nnz,)`` values -> the ``(P, G)`` panel layout.

        The scatter indices are static, so this stays a single XLA scatter;
        padding lanes (no source nonzero) remain exactly zero.  Used by the
        autodiff path to execute the Pallas panel kernels with *live* (traced)
        values instead of the host-packed constants.
        """
        import jax.numpy as jnp
        out = jnp.zeros(self.panel_vals.shape, vals.dtype)
        return out.at[self.src_panel, self.src_lane].set(vals)

    def gather_values(self, panel_arr):
        """Inverse of :meth:`scatter_values`: ``(P, G)`` -> flat ``(nnz,)``
        (padding lanes dropped).  Used to read per-nonzero gradients out of
        the SDD kernel's panel-layout output."""
        return panel_arr[self.src_panel, self.src_lane]

    @functools.cached_property
    def lane_cols(self) -> np.ndarray:
        """Flat signed gather columns, the kernels' layout
        (``kernels.panel_common.lane_cols``)."""
        from ..kernels.panel_common import lane_cols
        return lane_cols(self.panel_cols, self.panel_mask)

    @functools.cached_property
    def lane_vals(self) -> np.ndarray:
        """Flat ``(P·G,)`` values, the CSR kernel's SMEM layout."""
        return self.panel_vals.reshape(-1)


@dataclasses.dataclass(frozen=True)
class PanelBCSR:
    """BCSR-part tiles packed into dense ``(P, Br, G)`` value panels.

    Panel ``p`` stacks up to ``G`` of block-row ``panel_rows[p]``'s
    ``Br x 1`` column tiles side by side: ``panel_vals[p]`` is a real
    ``(Br, G)`` operand and the kernel's contraction becomes one
    ``(Br, G) @ (G, bn)`` MXU matmul per grid step instead of a chain of
    G rank-1 updates — the multi-round fmopa batching of paper Figure 2.
    Block-rows never share a panel; the trailing panel of each block-row
    is padded (mask 0, zero columns).  ``panel_rows`` is nondecreasing.
    """

    panel_rows: np.ndarray  # (P,) int32 block-row per panel, nondecreasing
    panel_cols: np.ndarray  # (P, G) int32 gather rows of B (0 where padded)
    panel_vals: np.ndarray  # (P, Br, G) tile values (zero columns = padding)
    panel_mask: np.ndarray  # (P, G) validity, same dtype as vals (1 / 0)
    src_panel: np.ndarray   # (ntiles,) int32 panel of tile t
    src_lane: np.ndarray    # (ntiles,) int32 lane of tile t
    g: int
    br: int
    nblocks: int
    nrows: int              # logical rows covered (<= nblocks * br)
    shape: Tuple[int, int]

    @property
    def npanels(self) -> int:
        return int(self.panel_rows.shape[0])

    def astype(self, dtype) -> "PanelBCSR":
        return dataclasses.replace(self,
                                   panel_vals=self.panel_vals.astype(dtype),
                                   panel_mask=self.panel_mask.astype(dtype))

    def scatter_values(self, tile_vals):
        """Traced ``(ntiles, Br)`` tile values -> the ``(P, Br, G)`` panel
        layout (static scatter indices; padding columns stay zero)."""
        import jax.numpy as jnp
        p, br, g = self.panel_vals.shape
        out = jnp.zeros((p, g, br), tile_vals.dtype)
        out = out.at[self.src_panel, self.src_lane].set(tile_vals)
        return out.transpose(0, 2, 1)

    def gather_values(self, panel_arr):
        """Inverse of :meth:`scatter_values`: ``(P, Br, G)`` panel-layout
        data -> ``(ntiles, Br)`` (padding columns dropped)."""
        return panel_arr[self.src_panel, :, self.src_lane]

    @functools.cached_property
    def lane_cols(self) -> np.ndarray:
        """Flat signed gather columns, the kernels' layout
        (``kernels.panel_common.lane_cols``)."""
        from ..kernels.panel_common import lane_cols
        return lane_cols(self.panel_cols, self.panel_mask)

    @functools.cached_property
    def vals_window(self) -> np.ndarray:
        """Lane-dense ``(Br, L)`` values, the kernel's layout
        (``kernels.panel_common.values_window``)."""
        from ..kernels.panel_common import values_window
        return values_window(self.panel_vals)


@dataclasses.dataclass(frozen=True)
class LoopsFormat:
    """The hybrid LOOPS format (paper §3.2.1, Algorithm 1).

    ``csr_panels``/``bcsr_panels`` are the G-wide panelized views of the two
    parts (``panel_g`` is the width G).  They are built lazily on first
    access and cached: the Pallas kernels execute the panels, while the
    pure-jnp reference executes the flat ``csr_part``/``bcsr_part`` arrays
    and never pays for the packing — both views hold identical values.

    ``macro_m`` is the macro-step fusion factor: ``macro_m`` consecutive
    same-(block-)row G-panels are packed into ONE grid step by panelizing at
    the effective width ``panel_g * macro_m`` (:attr:`panel_g_eff`).  The
    kernels are macro-blind — they just see wider panels — and tails that
    don't fill a macro step are validity-safe for free through the existing
    per-lane padding mask.  Accumulator init/flush and the A-panel load thus
    amortise over ``macro_m * G`` nonzeros and grid steps shrink ``~M×`` on
    dense rows.

    ``pipeline_depth`` selects the kernels' software-pipeline depth (1 =
    serial gather->contract, 2 = double-buffered B-panel prefetch); it does
    not change the panel layout, only how the engine dispatches it.
    """

    csr_part: CSR          # rows [0, r_boundary)
    bcsr_part: VectorBCSR  # rows [r_boundary, nrows)
    r_boundary: int
    shape: Tuple[int, int]
    panel_g: int = 1
    macro_m: int = 1
    pipeline_depth: int = 1

    @property
    def nrows(self) -> int:
        return self.shape[0]

    @property
    def ncols(self) -> int:
        return self.shape[1]

    @property
    def panel_g_eff(self) -> int:
        """Effective panel width after macro-step fusion: the panels are
        physically packed at ``panel_g * macro_m`` lanes per grid step."""
        return max(self.panel_g, 1) * max(self.macro_m, 1)

    @functools.cached_property
    def csr_panels(self) -> "PanelCSR":
        return panelize_csr(self.csr_part, self.panel_g_eff)

    @functools.cached_property
    def bcsr_panels(self) -> "PanelBCSR":
        return panelize_bcsr(self.bcsr_part, self.panel_g_eff)

    @functools.cached_property
    def nnz(self) -> int:
        # Logical nonzeros (excluding structural zero padding).  Cached:
        # ``loops_spmm`` consults it on every call and the count is an
        # O(nnz) host scan over the value arrays.
        return int(np.count_nonzero(self.csr_part.vals)
                   + np.count_nonzero(self.bcsr_part.tile_vals))

    def astype(self, dtype) -> "LoopsFormat":
        # Panel views are derived state: the replaced instance rebuilds
        # them (lazily) from the cast parts.
        return dataclasses.replace(
            self, csr_part=self.csr_part.astype(dtype),
            bcsr_part=self.bcsr_part.astype(dtype))

    def transposed(self, *, plan=None, tuner=None,
                   total_workers: int = 8) -> "TransposedLoops":
        """Aᵀ as a LOOPS format plus the value-linear maps from A's stored
        values — the backward-pass operand of the custom VJP (``dB = Aᵀ·dY``
        runs through the same panel kernels, just on this format).

        ``plan`` pins the transposed execution plan (a
        :class:`repro.core.spmm.SpmmPlan`); otherwise it is resolved through
        ``tuner`` (the measured plan cache) or the model-only
        ``plan_and_convert`` with ``total_workers``.  The result is cached on
        this instance per ``(plan, tuner, total_workers)``, so repeated
        backward passes — every training step — pay the O(nnz) transpose
        conversion exactly once.
        """
        key = (plan, id(tuner) if tuner is not None else None, total_workers)
        cache = self.__dict__.setdefault("_transposed_cache", {})
        if key not in cache:
            # The entry pins the tuner: id() is only a safe key while the
            # object is alive (a freed address can be recycled by a new
            # tuner, which must not hit this entry).
            cache[key] = (tuner, _build_transposed(
                self, plan=plan, tuner=tuner, total_workers=total_workers))
        return cache[key][1]


# ---------------------------------------------------------------------------
# CSR construction
# ---------------------------------------------------------------------------

def _ensure_nonempty_rows(row_ptr, col_idx, vals):
    """Insert a single explicit zero entry (col 0) into every empty row.

    Guarantees the scatter-output Pallas kernels visit every output row, so no
    block is left uninitialised on hardware where out-of-grid blocks are
    undefined (interpret mode zero-fills; real TPUs do not).
    """
    counts = np.diff(row_ptr)
    if (counts > 0).all() and len(counts) > 0:
        return row_ptr, col_idx, vals
    nrows = len(counts)
    new_counts = np.maximum(counts, 1)
    new_ptr = np.zeros(nrows + 1, np.int32)
    np.cumsum(new_counts, out=new_ptr[1:])
    new_cols = np.zeros(new_ptr[-1], np.int32)
    new_vals = np.zeros(new_ptr[-1], vals.dtype)
    # Entry k of row r moves to new_ptr[r] + (k - row_ptr[r]); an empty
    # row's slot keeps the zero pad entry at (r, 0).
    row = np.repeat(np.arange(nrows, dtype=np.int64), counts)
    dest = (new_ptr[:-1].astype(np.int64)[row]
            + np.arange(len(col_idx), dtype=np.int64)
            - row_ptr[:-1].astype(np.int64)[row])
    new_cols[dest] = col_idx
    new_vals[dest] = vals
    return new_ptr, new_cols, new_vals


def _csr_from_arrays(row_ptr, col_idx, vals, shape) -> CSR:
    row_ptr = np.asarray(row_ptr, np.int32)
    col_idx = np.asarray(col_idx, np.int32)
    vals = np.asarray(vals)
    row_ptr, col_idx, vals = _ensure_nonempty_rows(row_ptr, col_idx, vals)
    row_ids = np.repeat(
        np.arange(shape[0], dtype=np.int32), np.diff(row_ptr)).astype(np.int32)
    return CSR(row_ptr=row_ptr, col_idx=col_idx, vals=vals, row_ids=row_ids,
               shape=tuple(shape))


def csr_from_dense(dense: np.ndarray) -> CSR:
    dense = np.asarray(dense)
    nrows, _ = dense.shape
    mask = dense != 0
    counts = mask.sum(axis=1)
    row_ptr = np.zeros(nrows + 1, np.int32)
    np.cumsum(counts, out=row_ptr[1:])
    rows, cols = np.nonzero(mask)
    return _csr_from_arrays(row_ptr, cols, dense[rows, cols], dense.shape)


def csr_from_coo(rows, cols, vals, shape, *,
                 validate: str | None = "strict") -> CSR:
    """COO -> CSR, coalescing duplicates: values sharing a ``(row, col)``
    coordinate are *summed* (random generators like ``suite.uniform`` emit
    colliding coordinates; un-coalesced duplicates inflate nnz and every
    statistic derived from it).

    Coordinates are validated first (``repro.resilience.validate``): a
    negative or out-of-range coordinate used to corrupt the linearised
    dedup silently — under ``validate="strict"`` (default) it now raises a
    classified ``SparseInputError``; ``"drop"``/``"clip"`` repair instead
    (drop the entry, or clip it into range), recording ``validate.repaired``
    counters; ``None`` skips the gate (trusted internal callers only).
    """
    if validate is not None:
        from ..resilience.validate import validate_coo
        rows, cols, vals, _ = validate_coo(
            rows, cols, vals, shape,
            repair=None if validate == "strict" else validate)
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals)
    # np.unique on the linearised coordinate both dedups and (row, col)-sorts.
    lin = rows * int(shape[1]) + cols
    uniq, inv = np.unique(lin, return_inverse=True)
    summed = np.zeros(len(uniq), vals.dtype)
    np.add.at(summed, inv, vals)
    rows = uniq // int(shape[1])
    cols = uniq % int(shape[1])
    counts = np.bincount(rows, minlength=shape[0])
    row_ptr = np.zeros(shape[0] + 1, np.int32)
    np.cumsum(counts, out=row_ptr[1:])
    return _csr_from_arrays(row_ptr, cols, summed, shape)


def csr_to_dense(csr: CSR) -> np.ndarray:
    out = np.zeros(csr.shape, csr.vals.dtype)
    # += (not =) so structural-zero pads coexisting with real entries are safe.
    np.add.at(out, (csr.row_ids, csr.col_idx), csr.vals)
    return out


def csr_slice_rows(csr: CSR, start: int, stop: int) -> CSR:
    """Rows [start, stop) as a new CSR (paper Alg. 1 Step 1)."""
    s, e = int(csr.row_ptr[start]), int(csr.row_ptr[stop])
    row_ptr = (csr.row_ptr[start:stop + 1] - csr.row_ptr[start]).astype(np.int32)
    return _csr_from_arrays(row_ptr, csr.col_idx[s:e], csr.vals[s:e],
                            (stop - start, csr.shape[1]))


# ---------------------------------------------------------------------------
# Vector-wise BCSR construction (paper Alg. 1 Step 2, with B_c = 1)
# ---------------------------------------------------------------------------

def bcsr_from_csr_rows(csr: CSR, start: int, stop: int, br: int, *,
                       keep_zeros: bool = False, return_map: bool = False):
    """Re-tile rows [start, stop) of ``csr`` into ``br x 1`` tiles.

    Mirrors Algorithm 1's tile-map construction: each nonzero (i, j) lands in
    tile ``(i // br, j)`` at intra-tile offset ``i % br``.  Tiles are emitted
    sorted by (block_row, col); every block-row gets >= 1 tile.

    ``keep_zeros`` keeps zero-*valued* stored entries as tile coordinates
    instead of dropping them — required when the structure must be a function
    of the sparsity pattern only, never the values (the autodiff transpose:
    a trainable entry that happens to be zero at conversion time must not
    lose its slot).  ``return_map`` additionally returns ``slot_map``, an
    int64 array over the sliced entries where ``slot_map[k]`` is the flat
    destination ``tile_index * br + offset`` of entry ``row_ptr[start] + k``
    (−1 for dropped entries) — the static scatter that carries *traced*
    values into the tile layout.
    """
    nrows = stop - start
    nblocks = max((nrows + br - 1) // br, 1)
    s, e = int(csr.row_ptr[start]), int(csr.row_ptr[stop])
    local = np.repeat(np.arange(nrows, dtype=np.int64),
                      np.diff(csr.row_ptr[start:stop + 1]).astype(np.int64))
    cols = csr.col_idx[s:e].astype(np.int64)
    vals = np.asarray(csr.vals[s:e])
    # Zero-valued entries are structural pads of the parent CSR: dropped
    # unless the structure must not depend on the values.
    keep = np.ones(e - s, bool) if keep_zeros else vals != 0
    tr, off = local[keep] // br, local[keep] % br
    width = max(int(csr.shape[1]), 1)
    # Tile key (block_row, col), linearised so np.unique sorts by
    # (block_row, col); every block-row without a tile gets a zero tile at
    # column 0 so the kernel still visits it.
    missing = np.setdiff1d(np.arange(nblocks, dtype=np.int64), tr)
    keys, inv = np.unique(np.concatenate([tr * width + cols[keep],
                                          missing * width]),
                          return_inverse=True)
    tile_of = inv[:tr.size]
    ntiles = int(keys.size)
    tile_rows = (keys // width).astype(np.int32)
    tile_cols = (keys % width).astype(np.int32)
    tile_vals = np.zeros((ntiles, br), csr.vals.dtype)
    # Duplicate (row, col) entries sum into one slot, in entry order.
    np.add.at(tile_vals, (tile_of, off), vals[keep])
    counts = np.bincount(tile_rows, minlength=nblocks)
    block_ptr = np.zeros(nblocks + 1, np.int32)
    np.cumsum(counts, out=block_ptr[1:])
    bcsr = VectorBCSR(tile_rows=tile_rows, tile_cols=tile_cols,
                      tile_vals=tile_vals, block_ptr=block_ptr, br=br,
                      nrows=nrows, shape=(nrows, csr.shape[1]))
    if not return_map:
        return bcsr
    slot_map = np.full(e - s, -1, np.int64)
    slot_map[keep] = tile_of * br + off
    return bcsr, slot_map


# ---------------------------------------------------------------------------
# G-wide panelization (paper Figure 2 multi-tile batching)
# ---------------------------------------------------------------------------

def _pack_panels(group_of_item: np.ndarray, group_ptr: np.ndarray,
                 ngroups: int, g: int):
    """Shared panel bookkeeping: split each group's items into ceil(n/g)
    dense panels (>= 1 per group so output coverage is preserved).

    Returns ``(panel_rows, item_panel, item_lane, npanels)`` where item ``t``
    lands in panel ``item_panel[t]`` at lane ``item_lane[t]``.
    """
    counts = np.diff(group_ptr).astype(np.int64)
    per_group = np.maximum(-(-counts // g), 1)          # ceil, min 1
    start = np.zeros(ngroups + 1, np.int64)
    np.cumsum(per_group, out=start[1:])
    npanels = int(start[-1])
    panel_rows = np.repeat(np.arange(ngroups, dtype=np.int32),
                           per_group).astype(np.int32)
    offset = np.arange(len(group_of_item), dtype=np.int64) \
        - group_ptr[group_of_item].astype(np.int64)
    item_panel = start[group_of_item] + offset // g
    item_lane = offset % g
    return panel_rows, item_panel, item_lane, npanels


def panelize_csr(csr: CSR, g: int) -> PanelCSR:
    """Pack the CSR-part nonzeros into ``(P, G)`` panels, G per row-visit.

    O(nnz) and fully vectorised; a row with ``c`` nonzeros yields
    ``max(ceil(c / g), 1)`` panels (empty rows get one fully-masked panel so
    the kernel still zero-initialises their output block).
    """
    if g < 1:
        raise ValueError(f"panel width g must be >= 1, got {g}")
    panel_rows, pnl, lane, npanels = _pack_panels(
        csr.row_ids, csr.row_ptr, csr.nrows, g)
    cols = np.zeros((npanels, g), np.int32)
    vals = np.zeros((npanels, g), csr.vals.dtype)
    mask = np.zeros((npanels, g), csr.vals.dtype)
    cols[pnl, lane] = csr.col_idx
    vals[pnl, lane] = csr.vals
    mask[pnl, lane] = 1
    return PanelCSR(panel_rows=panel_rows, panel_cols=cols, panel_vals=vals,
                    panel_mask=mask, src_panel=pnl.astype(np.int32),
                    src_lane=lane.astype(np.int32), g=g, nrows=csr.nrows,
                    shape=csr.shape)


def panelize_bcsr(bcsr: VectorBCSR, g: int) -> PanelBCSR:
    """Pack the BCSR-part ``Br x 1`` tiles into ``(P, Br, G)`` panels.

    Each panel stacks up to G same-block-row tiles into one ``(Br, G)``
    matmul operand; block-rows with ``t`` tiles yield ``max(ceil(t/g), 1)``
    panels.
    """
    if g < 1:
        raise ValueError(f"panel width g must be >= 1, got {g}")
    panel_rows, pnl, lane, npanels = _pack_panels(
        bcsr.tile_rows, bcsr.block_ptr, bcsr.nblocks, g)
    cols = np.zeros((npanels, g), np.int32)
    mask = np.zeros((npanels, g), bcsr.tile_vals.dtype)
    cols[pnl, lane] = bcsr.tile_cols
    mask[pnl, lane] = 1
    # (P, G, Br) scatter then transpose to the (P, Br, G) operand layout.
    vals = np.zeros((npanels, g, bcsr.br), bcsr.tile_vals.dtype)
    vals[pnl, lane] = bcsr.tile_vals
    return PanelBCSR(panel_rows=panel_rows, panel_cols=cols,
                     panel_vals=np.ascontiguousarray(vals.transpose(0, 2, 1)),
                     panel_mask=mask, src_panel=pnl.astype(np.int32),
                     src_lane=lane.astype(np.int32), g=g, br=bcsr.br,
                     nblocks=bcsr.nblocks, nrows=bcsr.nrows, shape=bcsr.shape)


# ---------------------------------------------------------------------------
# Hybrid LOOPS format (Algorithm 1)
# ---------------------------------------------------------------------------

def loops_from_csr(csr: CSR, r_boundary: int, br: int,
                   panel_g: int = DEFAULT_PANEL_G, *,
                   macro_m: int = 1,
                   pipeline_depth: int = 1) -> LoopsFormat:
    """Algorithm 1: CSR-part = rows [0, r_boundary), BCSR-part = the rest.

    ``panel_g`` is the panel width the Pallas kernels consume (G nonzeros /
    tiles per grid step); the panelized views are derived lazily from the
    flat arrays on first kernel use.  ``macro_m`` fuses that many
    consecutive same-row panels into one grid step (the panels pack at
    ``panel_g * macro_m`` lanes); ``pipeline_depth`` selects the kernels'
    software-pipeline depth (1 or 2).  Both default to the knob-less
    layout.
    """
    if not 0 <= r_boundary <= csr.nrows:
        raise ValueError(f"r_boundary {r_boundary} out of range [0, {csr.nrows}]")
    if macro_m < 1:
        raise ValueError(f"macro_m must be >= 1, got {macro_m}")
    return LoopsFormat(csr_part=csr_slice_rows(csr, 0, r_boundary),
                       bcsr_part=bcsr_from_csr_rows(csr, r_boundary,
                                                    csr.nrows, br),
                       r_boundary=r_boundary, shape=csr.shape,
                       panel_g=panel_g, macro_m=macro_m,
                       pipeline_depth=pipeline_depth)


def permute_rows(csr: CSR, order: np.ndarray) -> CSR:
    """New CSR whose row i is ``csr`` row ``order[i]`` (O(nnz))."""
    counts = np.diff(csr.row_ptr)[order]
    new_ptr = np.zeros(csr.nrows + 1, np.int32)
    np.cumsum(counts, out=new_ptr[1:])
    idx = np.concatenate([
        np.arange(csr.row_ptr[r], csr.row_ptr[r + 1]) for r in order
    ]) if csr.nnz else np.zeros(0, np.int64)
    return _csr_from_arrays(new_ptr, csr.col_idx[idx], csr.vals[idx],
                            csr.shape)


def loops_from_csr_sorted(csr: CSR, r_boundary: int, br: int,
                          panel_g: int = DEFAULT_PANEL_G, *,
                          macro_m: int = 1, pipeline_depth: int = 1
                          ) -> Tuple[LoopsFormat, np.ndarray]:
    """Beyond-paper variant (§Perf): sort rows by nnz descending before the
    positional split, so scattered hub rows all land in the CSR(vector) part
    and the BCSR region has no monster block-rows (which are indivisible
    under contiguous device chunking and explode the padding).

    Returns (format, order) with ``C_permuted[i] == C[order[i]]``; consumers
    either apply the inverse permutation to the output or keep operating in
    permuted row space (GNN layers don't care about row order)."""
    order = np.argsort(-np.diff(csr.row_ptr), kind="stable").astype(np.int64)
    return loops_from_csr(permute_rows(csr, order), r_boundary, br,
                          panel_g=panel_g, macro_m=macro_m,
                          pipeline_depth=pipeline_depth), order


# ---------------------------------------------------------------------------
# Transposed format (autodiff: dB = Aᵀ · dY through the same kernels)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TransposedLoops:
    """Aᵀ in LOOPS form plus the *value-linear* maps from A's stored values.

    The structure is a function of A's sparsity pattern only; the maps are
    static index arrays, so the transposed value arrays can be rebuilt from
    **traced** values (learned-sparse-weight layers) with two XLA scatters —
    see :func:`transposed_values`.  A's "flat value vector" is
    ``concat(csr_part.vals, bcsr_part.tile_vals.ravel())``; BCSR tile slots
    on padding rows (``row >= nrows``) are excluded (the forward pass trims
    those rows, so they carry no gradient and contribute nothing to Aᵀ).
    """

    fmt: LoopsFormat        # Aᵀ, converted under the resolved plan
    plan: object            # the SpmmPlan the conversion used
    entry_src: np.ndarray   # (E,) int64 — index into A's flat value vector
    entry_slot: np.ndarray  # (E,) int64 — destination slot in Aᵀ's CSR
    n_slots: int            # stored entries of Aᵀ (incl. empty-row pads)
    csr_len: int            # slots [0, csr_len) are fmt.csr_part.vals
    bcsr_slot: np.ndarray   # (n_slots - csr_len,) int64 flat tile*Br+off


def loops_from_csr_mapped(csr: CSR, r_boundary: int, br: int,
                          panel_g: int = DEFAULT_PANEL_G, *,
                          macro_m: int = 1, pipeline_depth: int = 1
                          ) -> Tuple[LoopsFormat, int, np.ndarray]:
    """Algorithm 1 with value-slot bookkeeping (autodiff transpose variant).

    Like :func:`loops_from_csr` but the BCSR part keeps zero-valued stored
    entries (structure must not depend on values) and the return carries the
    maps from ``csr``'s flat value order into the two parts:
    ``(fmt, csr_len, bcsr_slot)`` where entries ``[0, csr_len)`` become
    ``fmt.csr_part.vals`` verbatim and entry ``csr_len + j`` lands at flat
    tile slot ``bcsr_slot[j]``.  Requires ``csr`` to have no empty rows
    (the transposed-CSR builder guarantees this via explicit pad slots).
    """
    if not 0 <= r_boundary <= csr.nrows:
        raise ValueError(f"r_boundary {r_boundary} out of range "
                         f"[0, {csr.nrows}]")
    csr_part = csr_slice_rows(csr, 0, r_boundary)
    csr_len = int(csr.row_ptr[r_boundary])
    if csr_part.nnz != csr_len:
        raise ValueError("loops_from_csr_mapped needs a CSR with no empty "
                         "rows (slicing inserted pad entries)")
    bcsr_part, bcsr_slot = bcsr_from_csr_rows(
        csr, r_boundary, csr.nrows, br, keep_zeros=True, return_map=True)
    fmt = LoopsFormat(csr_part=csr_part, bcsr_part=bcsr_part,
                      r_boundary=r_boundary, shape=csr.shape,
                      panel_g=panel_g, macro_m=macro_m,
                      pipeline_depth=pipeline_depth)
    return fmt, csr_len, bcsr_slot


def _transposed_csr(fmt: LoopsFormat) -> Tuple[CSR, np.ndarray, np.ndarray]:
    """Aᵀ as a (row, col)-sorted CSR with *every* row populated, plus the
    entry maps ``(csr_t, entry_src, entry_slot)``: A's flat stored entry
    ``entry_src[e]`` contributes (additively — duplicate coordinates
    coalesce) to ``csr_t.vals[entry_slot[e]]``.  Empty rows of Aᵀ get an
    explicit zero pad at column 0 with no source entry.
    """
    csr, bc = fmt.csr_part, fmt.bcsr_part
    m, k = fmt.shape
    t, br = bc.tile_vals.shape
    # Global (row, col) coordinate of every flat stored value of A.
    rows = np.concatenate([
        csr.row_ids.astype(np.int64),
        fmt.r_boundary + np.repeat(bc.tile_rows.astype(np.int64), br) * br
        + np.tile(np.arange(br, dtype=np.int64), t)])
    cols = np.concatenate([csr.col_idx.astype(np.int64),
                           np.repeat(bc.tile_cols.astype(np.int64), br)])
    keep = rows < m          # BCSR padding rows never reach the output
    entry_src = np.nonzero(keep)[0].astype(np.int64)
    # Transposed coordinate, linearised in Aᵀ's (row, col) = (col, row) order.
    lin = cols[keep] * m + rows[keep]
    uniq, inv = np.unique(lin, return_inverse=True)
    missing = np.setdiff1d(np.arange(k, dtype=np.int64),
                           np.unique(uniq // m))
    all_lin = np.sort(np.concatenate([uniq, missing * m]))
    entry_slot = np.searchsorted(all_lin, uniq)[inv].astype(np.int64)
    rows_t = (all_lin // m).astype(np.int32)
    cols_t = (all_lin % m).astype(np.int32)
    flat_vals = np.concatenate([np.asarray(csr.vals).ravel(),
                                np.asarray(bc.tile_vals).ravel()])
    vals_t = np.zeros(len(all_lin), flat_vals.dtype)
    np.add.at(vals_t, entry_slot, flat_vals[entry_src])
    row_ptr = np.zeros(k + 1, np.int32)
    np.cumsum(np.bincount(rows_t, minlength=k), out=row_ptr[1:])
    csr_t = CSR(row_ptr=row_ptr, col_idx=cols_t, vals=vals_t,
                row_ids=rows_t, shape=(k, m))
    return csr_t, entry_src, entry_slot


def _build_transposed(fmt: LoopsFormat, *, plan=None, tuner=None,
                      total_workers: int = 8) -> TransposedLoops:
    """Materialise :class:`TransposedLoops` (cached by
    ``LoopsFormat.transposed``).  Plan resolution goes through the same
    front door as the forward format — ``plan_and_convert`` / the tuner —
    so the backward SpMM is scheduled for Aᵀ's own row statistics, not A's.
    """
    from .spmm import plan_and_convert  # lazy: formats <- spmm at import time
    csr_t, entry_src, entry_slot = _transposed_csr(fmt)
    if plan is None:
        _, plan = plan_and_convert(csr_t, total_workers=total_workers,
                                   panel_g=fmt.panel_g or None, tuner=tuner,
                                   macro_m=fmt.macro_m,
                                   pipeline_depth=fmt.pipeline_depth)
    fmt_t, csr_len, bcsr_slot = loops_from_csr_mapped(
        csr_t, plan.r_boundary, plan.br, panel_g=plan.panel_g,
        macro_m=int(getattr(plan, "macro_m", 1)),
        pipeline_depth=int(getattr(plan, "pipeline_depth", 1)))
    tl = TransposedLoops(fmt=fmt_t, plan=plan, entry_src=entry_src,
                         entry_slot=entry_slot, n_slots=csr_t.nnz,
                         csr_len=csr_len, bcsr_slot=bcsr_slot)
    # Static round-trip check: injecting A's own values must reproduce the
    # converted parts exactly (catches any map/structure drift at build
    # time, where it is cheap, instead of as a silent wrong gradient).
    # Pure numpy — this runs under jit *tracing* of the backward pass, where
    # any jnp op would be staged into the jaxpr instead of executed.
    flat = np.concatenate([np.asarray(fmt.csr_part.vals).ravel(),
                           np.asarray(fmt.bcsr_part.tile_vals).ravel()])
    vals_t = np.zeros(tl.n_slots, flat.dtype)
    np.add.at(vals_t, tl.entry_slot, flat[tl.entry_src])
    nt, brr = fmt_t.bcsr_part.tile_vals.shape
    tile_flat = np.zeros(nt * brr, flat.dtype)
    np.add.at(tile_flat, tl.bcsr_slot, vals_t[tl.csr_len:])
    if not (np.allclose(vals_t[:tl.csr_len].astype(np.float64),
                        np.asarray(fmt_t.csr_part.vals, np.float64))
            and np.allclose(tile_flat.reshape(nt, brr).astype(np.float64),
                            np.asarray(fmt_t.bcsr_part.tile_vals,
                                       np.float64))):
        raise AssertionError("transposed value maps disagree with the "
                             "converted transposed format")
    return tl


def transposed_values(tl: TransposedLoops, csr_vals, bcsr_vals):
    """Carry (possibly traced) values of A into the transposed layout.

    Returns ``(csr_vals_t, bcsr_tile_vals_t)`` matching
    ``tl.fmt.csr_part`` / ``tl.fmt.bcsr_part`` — two static-index scatters,
    linear in the inputs, so gradients flow through them natively.
    """
    import jax.numpy as jnp
    flat = jnp.concatenate([jnp.reshape(csr_vals, (-1,)),
                            jnp.reshape(bcsr_vals, (-1,))])
    vals_t = jnp.zeros((tl.n_slots,), flat.dtype)
    vals_t = vals_t.at[tl.entry_slot].add(flat[tl.entry_src])
    nt, br = tl.fmt.bcsr_part.tile_vals.shape
    tile_flat = jnp.zeros((nt * br,), flat.dtype)
    tile_flat = tile_flat.at[tl.bcsr_slot].add(vals_t[tl.csr_len:])
    return vals_t[:tl.csr_len], tile_flat.reshape(nt, br)
