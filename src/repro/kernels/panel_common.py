"""Shared scaffolding for the G-wide panel kernels.

Both panel kernels (``csr_spmm``, ``bcsr_spmm``) speak the same operand
protocol: scalar-prefetched ``(prev_row, panel_rows, signed panel_cols[,
panel_vals])``, then the tensor train ``[vals_window?, carry?, B x G]``,
then outputs and scratch.  The operand ORDER is load-bearing —
``input_output_aliases`` is positional — so it is defined here exactly once
and both kernels assemble their specs and unpack their refs through these
helpers.  ``panel_cols`` arrive flattened (lane ``i`` of panel ``p`` at
``p * G + i``) with padding lanes set to -1 (:func:`signed_cols`): the
mask rides in the column index, and gathers clamp it to row 0.

TPU tiling: the last two dimensions of every block must be divisible by
(8, 128) or equal the array's own, and HBM stores every array in such tiles.
Single-row blocks (one B row, one CSR output row) therefore address a
``(rows, 1, N)`` view of their array (:func:`row_view`), whose last two
dimensions ``(1, N)`` the ``(1, bn)`` block matches; the leading row
dimension is squeezed (``None``) so kernels still see ``(1, bn)`` refs.
Per-panel values would pad each ``(1, G)`` or ``(Br, G)`` panel to a whole
tile, so they travel lane-dense instead: the CSR part's as SMEM scalars,
the BCSR part's as a ``(Br, P·G)`` array read one ``(Br, W)`` window
(``W = lcm(G, 128)``) per ``W / G`` panels (:func:`values_window`).

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

Pipelining (``pipeline_depth=2``): the panel axis is stretched by
``depth - 1`` ramp steps and the load/compute streams are skewed one step
apart — grid step ``k`` *assembles* panel ``lidx(k) = min(k, P-1)``'s B rows
into the ping-pong scratch slot ``k % 2`` while it *contracts* panel
``cidx(k) = max(k - (depth-1), 0)`` out of slot ``(k+1) % 2``.  The B-row
gathers (the dominant DMA traffic) for panel ``p+1`` thus overlap the MXU
contraction of panel ``p``.  ``pipeline_index`` builds the two index maps;
with ``depth=1`` both are the identity and every spec below is exactly the
unpipelined layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["CARRY_OPERAND_INDEX", "CSR_WORDS", "PIPELINE_DEPTHS",
           "SMEM_PREFETCH_BYTES",
           "check_pipeline_depth", "default_bn", "dot_precision",
           "first_last", "first_last_at", "gather_col", "grid_dims",
           "init_acc", "panel_calls", "panel_operands", "panels_per_call",
           "lane_cols", "pad_window", "parity", "pipeline_index", "row_view",
           "run_panel_chunks", "split_panel_refs", "values_window",
           "window_lanes", "window_panel", "LANES"]

# Position of the fused-path carry among ALL pallas_call operands (scalar
# prefetch included): CSR prev_row(0), rows(1), cols(2), vals(3), carry(4);
# BCSR prev_row(0), rows(1), cols(2), vals_window(3), carry(4).
CARRY_OPERAND_INDEX = 4

# Supported software-pipeline depths: 1 = today's serial gather->contract
# kernels, 2 = double-buffered B-panel prefetch (ping-pong scratch).
PIPELINE_DEPTHS = (1, 2)

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
    width ``g`` — each launch adds its own pipeline ramp steps."""
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


def gather_col(cols, lane):
    """Row of B that lane ``lane`` gathers (padding lanes, at column -1,
    read row 0)."""
    return jnp.maximum(cols[lane], 0)


def window_panel(window, c, g: int):
    """Panel ``c``'s ``(Br, G)`` values out of its ``(Br, W)`` window.

    Mosaic has no dynamic lane slice, so each of the G columns is a masked
    lane reduction of the window: exactly one lane survives the mask, so
    the result is exact in any dtype (half precision selects in fp32; v5e's
    VPU has no bf16 arithmetic)."""
    w = window.shape[-1]
    off = jax.lax.rem(c, jnp.asarray(w // g, c.dtype)) * g
    sel = (jax.lax.broadcasted_iota(jnp.int32, (1, g, w), 2)
           == off + jax.lax.broadcasted_iota(jnp.int32, (1, g, w), 1))
    wide = window.astype(jnp.promote_types(window.dtype, jnp.float32))
    picked = jnp.where(sel, wide[:, None, :], jnp.zeros_like(wide)[:, None, :])
    return jnp.sum(picked, axis=-1).astype(window.dtype)


def row_view(x, axis: int):
    """``x`` with a unit dimension inserted after ``axis`` — the ``(rows,
    1, N)`` view whose single-row blocks satisfy the TPU tiling rule."""
    return jnp.expand_dims(x, axis + 1)


def parity(k):
    """``k % 2`` in ``k``'s own integer dtype — ``jax.lax.rem(k, 2)`` trips
    the stablehlo verifier under x64 (i32 program_id vs weak-i64 literal)."""
    return jax.lax.rem(k, jnp.asarray(2, k.dtype))


def check_pipeline_depth(pipeline_depth: int) -> int:
    depth = int(pipeline_depth)
    if depth not in PIPELINE_DEPTHS:
        raise ValueError(f"pipeline_depth must be one of {PIPELINE_DEPTHS}, "
                         f"got {pipeline_depth}")
    return depth


def pipeline_index(depth: int, npanels: int):
    """``(lidx, cidx)`` index maps for a depth-deep panel pipeline.

    ``lidx(k)`` is the panel whose B rows grid step ``k`` loads (clamped to
    the last panel during the drain); ``cidx(k)`` is the panel it contracts
    (clamped to 0 during the fill ramp — compute is predicated off there,
    the clamp only keeps the block indices in range).  ``depth=1`` returns
    identities, reproducing the unpipelined specs exactly.
    """
    if depth == 1:
        return (lambda k: k), (lambda k: k)
    return (lambda k: jnp.minimum(k, npanels - 1),
            lambda k: jnp.maximum(k - (depth - 1), 0))


def grid_dims(*, batch: int | None, bz: int, n: int, bn: int, npanels: int,
              pipeline_depth: int = 1):
    """``(grid, panel_axis)`` for a panel kernel: the panel axis is always
    innermost (the accumulator-revisit protocol needs all panels of a row
    consecutive); batched calls prepend a batch-block axis.  A depth-``d``
    pipeline stretches the panel axis by ``d - 1`` fill/drain ramp steps."""
    steps = npanels + check_pipeline_depth(pipeline_depth) - 1
    if batch is None:
        return (n // bn, steps), 1
    return (batch // bz, n // bn, steps), 2


def first_last(rows_ref, panel_axis: int = 1):
    """(first, last) predicates for the nondecreasing-row revisit protocol:
    does the inner grid step ``k`` (on ``panel_axis``) open / close its
    output row's visit?"""
    k = pl.program_id(panel_axis)
    return first_last_at(rows_ref, k, pl.num_programs(panel_axis))


def first_last_at(rows_ref, c, npanels):
    """(first, last) revisit predicates evaluated at an explicit panel
    index ``c`` over ``npanels`` panels — the pipelined kernels compute
    panel ``cidx(k)``, not panel ``k``, so the predicates must follow the
    compute stream, not the grid step."""
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
    ``n_lead`` tensor refs before the carry (the BCSR values window),
    the carry (None without one), the G gathered B rows and the
    kernel-specific (outputs + scratch) remainder.  The carry is read only
    to resume a row that spans two chunks (:func:`init_acc`); aliasing
    preserves every other row."""
    scalars, rest = refs[:n_scalar], refs[n_scalar:]
    lead, rest = rest[:n_lead], rest[n_lead:]
    carry_ref = rest[0] if has_carry else None
    rest = rest[1:] if has_carry else rest
    return scalars, lead, carry_ref, rest[:g], rest[g:]


def panel_operands(*, g: int, bn: int, b, carry=None, carry_block=None,
                   row_map=None, bz: int | None = None,
                   pipeline_depth: int = 1, npanels: int | None = None,
                   window=None):
    """Assemble the tensor-operand train shared by both panel kernels.

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
      pipeline_depth / npanels: skew the load stream (B gathers, indexed at
                   ``lidx(k)``) ``depth - 1`` steps ahead of the compute
                   stream (values window + carry, indexed at ``cidx(k)``).
                   ``depth=1`` keeps both at ``k`` — today's layout.
      window:      the BCSR part's ``(Br, L)`` lane-dense values
                   (:func:`values_window`), read one ``(Br, W)`` window per
                   ``W / G`` panels; None for the CSR part (values in SMEM).

    Returns ``(in_specs, args, input_output_aliases)``: the values window
    if any, the optional aliased carry, then G gathers of ``b`` indexed by
    the scalar-prefetched, signed ``panel_cols`` — one DMA stream per panel
    lane, ``bz`` batch slices wide when batched.
    """
    depth = check_pipeline_depth(pipeline_depth)
    if depth > 1 and npanels is None:
        raise ValueError("pipelined panel_operands needs npanels")
    lidx, cidx = pipeline_index(depth, npanels if npanels is not None else 0)
    grid_lead = (lambda z: ()) if bz is None else (lambda z: (z,))
    if bz is None:
        def spec(block, index):
            return pl.BlockSpec(block, lambda j, k, *s: index(None, j, k, s))
    else:
        def spec(block, index):
            return pl.BlockSpec(block, lambda z, j, k, *s: index(z, j, k, s))

    in_specs, args, aliases = [], [], {}
    if window is not None:
        br, w = window.shape[0], window_lanes(g)
        in_specs.append(spec((br, w), lambda z, j, k, s:
                             (0, cidx(k) // (w // g))))
        args.append(window)
    if carry is not None:
        lead = () if bz is None else (bz,)
        in_specs.append(spec(lead + tuple(carry_block), lambda z, j, k, s:
                             grid_lead(z) + row_map(s[1], cidx(k), j)))
        args.append(carry)
        aliases = {CARRY_OPERAND_INDEX: 0}
    b_block = (None, 1, bn) if bz is None else (bz, None, 1, bn)
    for i in range(g):
        in_specs.append(spec(b_block, lambda z, j, k, s, i=i:
                             grid_lead(z)
                             + (gather_col(s[2], lidx(k) * g + i), 0, j)))
        args.append(b)
    return in_specs, args, aliases


def pad_window(win, g: int):
    """Right-pad a ``(Br, n)`` slice of a values window to whole windows."""
    pad = -win.shape[-1] % window_lanes(g)
    return jnp.pad(win, ((0, 0), (0, pad))) if pad else win


def run_panel_chunks(call, rows, lanes, *, g: int, per_call: int,
                     carry=None, out_shape=None):
    """Run a panel kernel over its panel axis in SMEM-sized chunks.

    ``rows`` is ``panel_rows`` (P,); ``lanes`` are ``(array, axis)`` pairs
    whose ``axis`` holds G entries per panel (the flat columns and values,
    the BCSR values window — padded beyond P·G).  ``call(prev_row, rows,
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
