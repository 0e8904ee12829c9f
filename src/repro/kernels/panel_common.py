"""Shared scaffolding for the G-wide panel kernels.

The CSR SpMM panel kernel (``csr_spmm``) speaks one operand protocol:
scalar-prefetched ``(prev_row, panel_rows, signed panel_cols,
panel_vals)``, then the tensor train ``[carry?, B x G]``, then outputs and
scratch.  The operand ORDER is load-bearing — ``input_output_aliases`` is
positional — so it is defined here exactly once and the kernel assembles
its specs and unpacks its refs through these helpers.  ``panel_cols``
arrive flattened (lane ``i`` of panel ``p`` at ``p * G + i``) with padding
lanes set to -1 (:func:`lane_cols`): the mask rides in the column index,
and gathers clamp it to row 0.

The BCSR SpMM kernel (``bcsr_spmm``) walks window steps instead
(:func:`window_steps`) and gathers B rows by its own DMAs; it shares the
layouts here (:func:`lane_cols`, :func:`values_window`) and the revisit
predicates; the SDD kernels share the gathers and the chunk bound.

TPU tiling: the last two dimensions of every block must be divisible by
(8, 128) or equal the array's own, and HBM stores every array in such tiles.
Single-row blocks (one B row, one CSR output row) therefore address a
``(rows, 1, N)`` view of their array (:func:`row_view`), whose last two
dimensions ``(1, N)`` the ``(1, bn)`` block matches; the leading row
dimension is squeezed (``None``) so kernels still see ``(1, bn)`` refs.
Per-panel values would pad each ``(1, G)`` or ``(Br, G)`` panel to a whole
tile, so they travel lane-dense instead: the CSR part's as SMEM scalars,
the BCSR part's as a ``(Br, P·G)`` array read one 128-lane window per step
(:func:`values_window`).

Chunked metadata: the scalar-prefetched metadata lives in SMEM, which holds
about 1 MiB per ``pallas_call``.  :func:`run_panel_chunks` splits the panel
axis into chunks of :func:`panels_per_call` panels — one ``pallas_call`` per
chunk (a ``lax.fori_loop`` over the full chunks, so one kernel is compiled) —
threading the output through the aliased carry.  A row whose panels span
two chunks resumes from the carry: ``prev_row`` is the row the previous
chunk ended on, and a chunk whose first panel continues it loads the
accumulator from the carry instead of zeroing it (:func:`init_acc`).

Batched execution: when the dense operand carries a leading batch dimension
``(batch, K, N)``, the grid gains a leading batch-block axis and every
tensor BlockSpec gains a leading ``bz``-wide block dimension (``bz`` batch
slices per grid step, :func:`repro.kernels.engine.batch_block`).  The
scalar-prefetch panel metadata is shared across the batch — A's static
panel layout is loaded once per grid step and applied to all ``bz``
slices.  ``grid_dims`` centralises the two grid layouts so the kernels'
``first``/``last`` revisit predicates can never disagree with the specs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["CARRY_OPERAND_INDEX", "CSR_WORDS", "SMEM_PREFETCH_BYTES",
           "default_bn", "dot_precision", "first_last", "first_last_at",
           "gather_col", "grid_dims", "init_acc", "panel_calls",
           "panel_operands", "panels_per_call", "lane_cols", "parity",
           "row_view", "run_panel_chunks", "split_panel_refs",
           "values_window", "window_lanes", "window_steps",
           "block_row_steps", "gather_bn", "LANES"]

# Position of the fused-path carry among ALL pallas_call operands of the CSR
# kernel (scalar prefetch included): prev_row(0), rows(1), cols(2), vals(3),
# carry(4).
CARRY_OPERAND_INDEX = 4

# SMEM bytes one pallas_call may spend on its scalar-prefetched panel
# metadata: ``panel_rows`` (P,) plus ``words_per_lane`` flat (P·G,) 32-bit
# arrays (a 2-D SMEM operand would pad its rows to 128 words).  The v5e
# compiler refuses more than 1 MiB of prefetched SMEM, and its compile time
# grows faster than linearly in the prefetched panel count (about 2 s at
# 16k panels, 8 s at 39k); a quarter of the limit keeps one kernel compile
# under a second.
SMEM_PREFETCH_BYTES = 256 * 1024

# Prefetched 32-bit words per panel lane of the CSR SpMM kernel (column and
# value); the BCSR SpMM and both SDD kernels prefetch the column only.
CSR_WORDS = 2


def default_bn(n: int) -> int:
    """Column-block width that tiles ``n`` exactly and is legal on TPU.

    A block's last dimension must be a multiple of 128 lanes or the whole
    row.  ``n <= 512`` keeps the whole row in one block; above that, pick
    the largest multiple of 128 that divides ``n`` and is ``<= 512``, else
    the whole row (N=600 -> 600, N=1536 -> 512).
    """
    n = int(n)
    if n <= 512:
        return max(n, 1)
    aligned = [d for d in range(128, 513, 128) if n % d == 0]
    return max(aligned) if aligned else n


def dot_precision(dtype):
    """MXU precision for a contraction in ``dtype``: fp32 operands run at
    full fp32 (``HIGHEST``, the multi-pass bf16 decomposition) instead of
    the TPU default of one bf16 pass; half precision is exact in one
    pass."""
    if jnp.dtype(dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return None


def panels_per_call(g: int, words_per_lane: int = 1) -> int:
    """Panels per ``pallas_call``: as many as fit :data:`SMEM_PREFETCH_BYTES`
    of ``1 + words_per_lane·G`` 32-bit metadata words each (CSR SpMM
    prefetches columns and values, 2 words per lane; BCSR SpMM and SDD
    columns only), rounded down to a multiple of 8."""
    per = SMEM_PREFETCH_BYTES // (4 * (1 + int(words_per_lane) * int(g)))
    return max(per // 8 * 8, 8)


def panel_calls(npanels: int, g: int, words_per_lane: int = 1,
                per_call: int | None = None) -> int:
    """``pallas_call`` launches (chunks) needed for ``npanels`` panels of
    width ``g``."""
    per = per_call or panels_per_call(g, words_per_lane)
    return max(-(-int(npanels) // per), 1)


# TPU vector lanes: the HBM and VMEM tile width of the last dimension.
LANES = 128


def _xp(*arrays):
    """numpy for host arrays (the formats' cached layouts), jax.numpy once
    any operand is a jax value (traced live values)."""
    return np if all(isinstance(a, np.ndarray) for a in arrays) else jnp


def lane_cols(cols, mask):
    """``(P, G)`` panel columns → the flat ``(P·G,)`` int32 kernel layout
    (lane ``i`` of panel ``p`` at ``p·G + i``), padding lanes at -1 so the
    kernels read the lane mask out of the column index."""
    xp = _xp(cols, mask)
    return xp.where(mask > 0, cols, -1).astype(np.int32).reshape(-1)


def window_lanes(g: int) -> int:
    """Lanes ``W = lcm(G, 128)`` of one BCSR values window: ``W / G`` whole
    panels per tile-aligned window."""
    return int(np.lcm(int(g), LANES))


def values_window(vals):
    """``(P, Br, G)`` BCSR panel values → the lane-dense ``(Br, L)`` kernel
    layout (panel ``p`` at lanes ``[p·G, (p+1)·G)``), ``L`` padded to whole
    :func:`window_lanes` windows.  A ``(Br, G)`` block per panel would pad
    to a whole (8, 128) tile in HBM."""
    xp = _xp(vals)
    p, br, g = vals.shape
    flat = xp.transpose(vals, (1, 0, 2)).reshape(br, p * g)
    pad = -(p * g) % window_lanes(g)
    return xp.pad(flat, ((0, 0), (0, pad))) if pad else flat


def window_steps(panel_rows, g: int):
    """The BCSR kernel's step table: ``(step_rows (T,), step_starts (T+1,))``
    int32 host arrays.

    One step is one aligned :data:`LANES`-lane window of the values
    window intersected with one block row: a block row's panels are
    contiguous in lane order, so its lanes ``[first·G, (last+1)·G)`` split
    at every window boundary.  Steps follow lane order (so ``step_rows``
    is nondecreasing); step ``k`` covers lanes ``[step_starts[k],
    step_starts[k+1])``, all in window ``step_starts[k] // LANES``, and
    ``step_starts[T]`` is the lane count ``P·G``."""
    rows = np.asarray(panel_rows).astype(np.int64)
    lanes = rows.shape[0] * int(g)
    if not lanes:
        return np.zeros(0, np.int32), np.zeros(1, np.int32)
    opens = np.flatnonzero(np.r_[True, rows[1:] != rows[:-1]]) * int(g)
    starts = np.union1d(opens, np.arange(0, lanes, LANES))
    return (rows[starts // int(g)].astype(np.int32),
            np.append(starts, lanes).astype(np.int32))


def block_row_steps(lanes_per_row) -> int:
    """Steps of :func:`window_steps` from the lanes of each block row (in
    panel order) alone: each block row takes one step, plus one for every
    window boundary inside it."""
    lanes = np.asarray(lanes_per_row, np.int64)
    ends = np.cumsum(lanes)
    return int(((ends - 1) // LANES - (ends - lanes) // LANES + 1).sum())


def gather_bn(n: int, bn: int | None = None) -> int:
    """Column-block width of the BCSR kernel, whose gathers copy whole
    rows: ``bn`` (default :func:`default_bn`) where it and ``n`` are
    lane-aligned, else all of ``n`` in one block."""
    bn = bn or default_bn(n)
    return bn if n % LANES == 0 and bn % LANES == 0 else int(n)


def gather_col(cols, lane):
    """Row of B that lane ``lane`` gathers (padding lanes, at column -1,
    read row 0)."""
    return jnp.maximum(cols[lane], 0)


def row_view(x, axis: int):
    """``x`` with a unit dimension inserted after ``axis`` — the ``(rows,
    1, N)`` view whose single-row blocks satisfy the TPU tiling rule."""
    return jnp.expand_dims(x, axis + 1)


def parity(k):
    """``k % 2`` in ``k``'s own integer dtype — ``jax.lax.rem(k, 2)`` trips
    the stablehlo verifier under x64 (i32 program_id vs weak-i64 literal)."""
    return jax.lax.rem(k, jnp.asarray(2, k.dtype))


def grid_dims(*, batch: int | None, bz: int, n: int, bn: int, npanels: int):
    """``(grid, panel_axis)`` for a panel kernel: the panel axis is always
    innermost (the accumulator-revisit protocol needs all panels of a row
    consecutive); batched calls prepend a batch-block axis."""
    if batch is None:
        return (n // bn, npanels), 1
    return (batch // bz, n // bn, npanels), 2


def first_last(rows_ref, panel_axis: int = 1):
    """(first, last) predicates for the nondecreasing-row revisit protocol:
    does the inner grid step ``k`` (on ``panel_axis``) open / close its
    output row's visit?"""
    k = pl.program_id(panel_axis)
    return first_last_at(rows_ref, k, pl.num_programs(panel_axis))


def first_last_at(rows_ref, c, npanels):
    """(first, last) revisit predicates evaluated at an explicit panel
    (or window step) index ``c`` over ``npanels`` of them — the BCSR
    kernel's step index, which its grid axis shares with nothing else."""
    row_here = rows_ref[c]
    row_prev = rows_ref[jnp.maximum(c - 1, 0)]
    row_next = rows_ref[jnp.minimum(c + 1, npanels - 1)]
    first = jnp.logical_or(c == 0, row_here != row_prev)
    last = jnp.logical_or(c == npanels - 1, row_here != row_next)
    return first, last


def init_acc(acc_ref, carry_ref, prev_ref, rows_ref, c):
    """Open panel ``c``'s output row: zero the accumulator, or — when this
    chunk's first panel continues the row the previous chunk ended on —
    resume from that partial sum, which the previous ``pallas_call``
    flushed into the aliased carry."""
    if carry_ref is None:
        acc_ref[...] = jnp.zeros_like(acc_ref)
        return
    resume = jnp.logical_and(c == 0, rows_ref[0] == prev_ref[0])

    @pl.when(resume)
    def _resume():
        acc_ref[...] = carry_ref[...].astype(acc_ref.dtype)

    @pl.when(jnp.logical_not(resume))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)


def split_panel_refs(refs, g: int, n_scalar: int, n_lead: int,
                     has_carry: bool):
    """Unpack a panel kernel's ref train into ``(scalars, lead, carry,
    b_refs, tail)``: the ``n_scalar`` scalar-prefetch refs, the
    ``n_lead`` tensor refs before the carry, the carry (None without one), the G gathered B rows and the
    kernel-specific (outputs + scratch) remainder.  The carry is read only
    to resume a row that spans two chunks (:func:`init_acc`); aliasing
    preserves every other row."""
    scalars, rest = refs[:n_scalar], refs[n_scalar:]
    lead, rest = rest[:n_lead], rest[n_lead:]
    carry_ref = rest[0] if has_carry else None
    rest = rest[1:] if has_carry else rest
    return scalars, lead, carry_ref, rest[:g], rest[g:]


def panel_operands(*, g: int, bn: int, b, carry=None, carry_block=None,
                   row_map=None, bz: int | None = None):
    """Assemble the tensor-operand train of the CSR panel kernel.

    Index maps receive the grid indices, then the scalar-prefetch refs
    ``(prev_row, rows, cols, ...)``.

    Args:
      b:           the row view of the dense operand — ``(K, 1, N)``, or
                   ``(batch, K, 1, N)`` when batched.
      row_map:     ``row_index(rows, k, j)`` → the block index of the
                   carry/output; used to build the carry spec.
      carry_block: block shape of the carry, squeezed dims as ``None``.
      bz:          batch slices per grid step, or None for the unbatched
                   layout.

    Returns ``(in_specs, args, input_output_aliases)``: the optional
    aliased carry, then G gathers of ``b`` indexed by
    the scalar-prefetched, signed ``panel_cols`` — one DMA stream per panel
    lane, ``bz`` batch slices wide when batched.
    """
    grid_lead = (lambda z: ()) if bz is None else (lambda z: (z,))
    if bz is None:
        def spec(block, index):
            return pl.BlockSpec(block, lambda j, k, *s: index(None, j, k, s))
    else:
        def spec(block, index):
            return pl.BlockSpec(block, lambda z, j, k, *s: index(z, j, k, s))

    in_specs, args, aliases = [], [], {}
    if carry is not None:
        lead = () if bz is None else (bz,)
        in_specs.append(spec(lead + tuple(carry_block), lambda z, j, k, s:
                             grid_lead(z) + row_map(s[1], k, j)))
        args.append(carry)
        aliases = {CARRY_OPERAND_INDEX: 0}
    b_block = (None, 1, bn) if bz is None else (bz, None, 1, bn)
    for i in range(g):
        in_specs.append(spec(b_block, lambda z, j, k, s, i=i:
                             grid_lead(z)
                             + (gather_col(s[2], k * g + i), 0, j)))
        args.append(b)
    return in_specs, args, aliases


def run_panel_chunks(call, rows, lanes, *, g: int, per_call: int,
                     carry=None, out_shape=None):
    """Run a panel kernel over its panel axis in SMEM-sized chunks.

    ``rows`` is ``panel_rows`` (P,); ``lanes`` are ``(array, axis)`` pairs
    whose ``axis`` holds G entries per panel (the flat columns and
    values).  ``call(prev_row, rows,
    *lane_arrays, carry)`` launches one ``pallas_call`` over a chunk and
    returns the full output.  Up to ``per_call`` panels run as one launch
    exactly as before (no carry unless the caller passed one).  Beyond
    that the full chunks run in a ``lax.fori_loop`` — one compiled kernel —
    and a remainder chunk follows; each launch writes into the output of
    the one before through the aliased ``carry`` (zeros of ``out_shape``
    when the caller has none), and ``prev_row`` carries the row the
    previous chunk ended on so a row spanning the seam resumes instead of
    restarting.
    """
    npanels = int(rows.shape[0])
    if npanels <= per_call:
        return call(jnp.full((1,), -1, rows.dtype), rows,
                    *[a for a, _ in lanes], carry)
    out = carry if carry is not None else jnp.zeros(*out_shape)
    nfull, span = npanels // per_call, per_call * g

    def body(c, acc):
        start = c * per_call
        prev = jnp.where(c > 0, rows[jnp.maximum(start - 1, 0)], -1)
        return call(prev[None],
                    jax.lax.dynamic_slice_in_dim(rows, start, per_call),
                    *[jax.lax.dynamic_slice_in_dim(a, c * span, span, axis)
                      for a, axis in lanes], acc)

    out = jax.lax.fori_loop(0, nfull, body, out)
    head = nfull * per_call
    if head < npanels:
        out = call(rows[head - 1][None], rows[head:],
                   *[jax.lax.slice_in_dim(a, head * g, a.shape[axis],
                                          axis=axis)
                     for a, axis in lanes], out)
    return out
