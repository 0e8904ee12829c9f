"""BCSR-part SpMM Pallas kernel — the MXU (matrix-pipeline) half of LOOPS.

Paper mapping (§3.3 "Outer-product based SME kernel for BCSR part",
Algorithm 2 + Figure 2): the BCSR-part stores ``Br x 1`` column tiles; each
tile contributes a rank-1 update

    C[block p] += tile_vals[t] (x) B[tile_cols[t], :]

accumulated in a ZA tile register.  On TPU the accumulator is a VMEM block
streamed through the MXU.

Panelized execution (paper Figure 2 "multi-tile" batching)
----------------------------------------------------------
The kernel consumes ``(P, Br, G)`` panels (``repro.core.formats.PanelBCSR``):
G same-block-row tiles stacked side by side form a real ``(Br, G)`` operand,
and one grid step performs a single

    C[block] += A_panel(Br, G) @ B_panel(G, bn)

MXU contraction — G fmopa rounds batched per ZA-tile visit, exactly the
paper's multi-tile optimisation.  The B panel is assembled in VMEM scratch
from G scalar-prefetch-indexed row gathers with masked (padding-dropping)
stores.  G = 1 degenerates to the historical rank-1-per-step kernel
(``bcsr_spmm_pallas`` is that wrapper).

Batched execution (multi-RHS)
-----------------------------
A rank-3 dense operand ``(batch, K, N)`` adds a leading batch-block grid
axis: each grid step loads the static ``(Br, G)`` A panel ONCE, assembles
``bz`` B panels (one per batch slice) in scratch, and issues one batched
``(bz, Br, G) @ (bz, G, bn)`` MXU contraction — ``bz`` independent matmuls
sharing the A operand.  Grid steps grow by ``ceil(batch / bz)`` over the
unbatched call.

Precision (§3.3 FP16 path, Algorithm 3): the paper uses the 2-way widening
``fmopa`` (two f16 outer products into one f32 ZA tile) with vzip register
shuffles.  The TPU MXU natively multiplies bf16 operands and accumulates in
fp32 (``preferred_element_type=float32``), which realises the same
half-in/single-accumulate contract without any shuffle — the packing is done
by the hardware.  fp32 operands contract at ``Precision.HIGHEST`` — full
fp32, not the TPU's default single bf16 pass
(``panel_common.dot_precision``).

grid = (N // bn, P) (batched: (batch // bz, N // bn, P)); ``panel_rows`` is
nondecreasing so output-block revisiting is legal, exactly as in the CSR
kernel.  The panel values travel lane-dense — a ``(Br, W)`` window serves
``W / G`` consecutive panels (``panel_common.values_window``) — because a
``(Br, G)`` block per panel would pad to a whole HBM tile.  ``carry`` +
``row_block_offset`` support the fused single-pass
``loops_spmm``: the kernel writes its blocks at a row offset into a shared
buffer whose other rows (the CSR part's) are preserved through
``input_output_aliases``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .engine import batch_block, register_kernel, resolve_dtypes
from .panel_common import (check_pipeline_depth, default_bn, dot_precision,
                           first_last, first_last_at, grid_dims, init_acc,
                           panel_operands, parity, row_view,
                           pad_window, run_panel_chunks, split_panel_refs,
                           window_panel)
from .panel_common import panels_per_call as default_panels_per_call

__all__ = ["bcsr_spmm_pallas", "bcsr_panels_spmm_pallas"]


def _contract(acc_ref, a_panel, bpan, bz: int | None):
    """``acc += (Br, G) @ (G, bn)`` on the MXU — once per batch slice
    (sharing the A panel) when batched — at full precision for fp32
    operands."""
    prec = dot_precision(a_panel.dtype)
    dims = (((1,), (0,)), ((), ()))
    if bz is None:
        acc_ref[...] += jax.lax.dot_general(
            a_panel, bpan, dims, precision=prec,
            preferred_element_type=acc_ref.dtype)
        return
    for z in range(bz):
        acc_ref[z] += jax.lax.dot_general(
            a_panel, bpan[z], dims, precision=prec,
            preferred_element_type=acc_ref.dtype)


def _gather_rows(b_refs, cols_ref, p, g: int, bpan_ref, bz: int | None,
                 slot=None):
    """Masked gather: assemble panel ``p``'s ``(G, bn)`` B panel(s) in VMEM
    scratch (``bpan_ref`` is ``(G, bn)``, or ``(bz, G, bn)`` when batched,
    behind a leading ping-pong ``slot`` axis when pipelined), zeroing
    padding lanes (column -1: panels shorter than G at block-row
    boundaries)."""
    lead = () if slot is None else (slot,)
    for i, b_ref in enumerate(b_refs):
        row = b_ref[...].astype(bpan_ref.dtype)   # (1, bn) / (bz, 1, bn)
        row = jnp.where(cols_ref[p * g + i] >= 0, row, jnp.zeros_like(row))
        if bz is None:
            bpan_ref[lead + (pl.ds(i, 1), slice(None))] = row
        else:
            bpan_ref[lead + (slice(None), pl.ds(i, 1), slice(None))] = row


def _panel_kernel(g: int, has_carry: bool, bz: int | None, *refs):
    """One grid step: gather G rows of B into scratch, one (Br,G)@(G,bn)
    MXU contraction (``bz`` of them, sharing the A panel, when batched)."""
    (prev_ref, rows_ref, cols_ref), (win_ref,), carry_ref, b_refs, \
        (o_ref, bpan_ref, acc_ref) = \
        split_panel_refs(refs, g, 3, 1, has_carry)
    axis = 1 if bz is None else 2
    k = pl.program_id(axis)
    first, last = first_last(rows_ref, panel_axis=axis)

    @pl.when(first)
    def _init():
        init_acc(acc_ref, carry_ref, prev_ref, rows_ref, k)

    _gather_rows(b_refs, cols_ref, k, g, bpan_ref, bz)

    # One real MXU contraction per grid step: G batched fmopa rounds
    # (Figure 2) instead of a chain of rank-1 (Br,1)@(1,bn) updates.  For
    # bf16 the MXU widens to fp32 in hardware (2-way fmopa equivalent).
    _contract(acc_ref, window_panel(win_ref[...], k, g), bpan_ref[...], bz)

    @pl.when(last)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _piped_panel_kernel(g: int, has_carry: bool, bz: int | None, depth: int,
                        *refs):
    """Depth-2 software pipeline: grid step ``k`` assembles panel
    ``min(k, P-1)``'s B rows into ping-pong scratch slot ``k % 2`` while the
    MXU contracts panel ``max(k - 1, 0)`` out of slot ``(k+1) % 2`` — the
    gather DMAs of the next panel overlap this panel's ``(Br,G)@(G,bn)``
    contraction.  Compute/init/flush are predicated off during the
    ``depth - 1`` fill ramp steps."""
    (prev_ref, rows_ref, cols_ref), (win_ref,), carry_ref, b_refs, \
        (o_ref, bpan_ref, acc_ref) = \
        split_panel_refs(refs, g, 3, 1, has_carry)
    axis = 1 if bz is None else 2
    k = pl.program_id(axis)
    npanels = pl.num_programs(axis) - (depth - 1)
    load = jnp.minimum(k, npanels - 1)

    for s in (0, 1):
        @pl.when(parity(k) == s)
        def _(s=s):
            _gather_rows(b_refs, cols_ref, load, g, bpan_ref, bz, slot=s)

    @pl.when(k >= depth - 1)
    def _compute():
        c = jnp.maximum(k - (depth - 1), 0)
        first, last = first_last_at(rows_ref, c, npanels)

        @pl.when(first)
        def _init():
            init_acc(acc_ref, carry_ref, prev_ref, rows_ref, c)

        a_panel = window_panel(win_ref[...], c, g)   # (Br, G), panel c

        for s in (0, 1):
            @pl.when(parity(k + 1) == s)
            def _(s=s):
                _contract(acc_ref, a_panel, bpan_ref[s], bz)

        @pl.when(last)
        def _flush():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("g", "nblocks", "row_block_offset", "out_rows", "bn",
                     "out_dtype", "interpret", "pipeline_depth",
                     "panels_per_call"))
def bcsr_panels_spmm_pallas(panel_rows: jax.Array, lane_cols: jax.Array,
                            vals_window: jax.Array, b: jax.Array, *, g: int,
                            nblocks: int, interpret: bool,
                            row_block_offset: int = 0,
                            out_rows: int | None = None,
                            bn: int | None = None, out_dtype=None,
                            carry: jax.Array | None = None,
                            pipeline_depth: int = 1,
                            panels_per_call: int | None = None) -> jax.Array:
    """Panelized vector-wise BCSR SpMM.

    Args:
      panel_rows: (P,) int32 block-row per panel, nondecreasing.
      lane_cols:  (P·G,) int32 gather rows of ``b``, lane ``i`` of panel
                  ``p`` at ``p·G + i``, -1 on padding lanes
                  (``PanelBCSR.lane_cols``).
      vals_window: (Br, L) lane-dense tile values, panel ``p``'s ``(Br,
                  G)`` operand at lanes ``[p·G, (p+1)·G)``, ``L >= P·G``
                  padded to whole windows (``PanelBCSR.vals_window``).
      g:          panel width G (static).
      b:          (K, N) dense operand, or (batch, K, N) for the native
                  batched grid (one kernel call serves every slice).
      nblocks:    number of block-rows (static).
      interpret:  run the Pallas interpreter (CPU validation) or compile
                  for the TPU; every caller states which.
      row_block_offset: first output block-row this kernel writes (static;
                  the fused path sets it to ``r_boundary // Br``).
      out_rows:   total rows of the returned array; defaults to
                  ``(row_block_offset + nblocks) * Br``.
      bn:         B/accumulator column width per visit (multi-ZA-tile
                  factor); defaults to ``panel_common.default_bn(N)``.
      carry:      optional (..., out_rows, N) array aliased into the output;
                  rows not visited here keep its contents (fused mode).
      pipeline_depth: 1 (serial gather->contract, default) or 2 (double-
                  buffered B-panel prefetch through a ping-pong scratch
                  slot).  Unbatched results are bitwise identical across
                  depths; batched results agree to ~1 ulp.
      panels_per_call: panels per ``pallas_call`` (default
                  ``panel_common.panels_per_call(G)``, the SMEM bound);
                  more panels run as chained chunks.
    """
    if b.ndim not in (2, 3):
        raise ValueError(f"b must be (K, N) or (batch, K, N); got rank "
                         f"{b.ndim}")
    depth = check_pipeline_depth(pipeline_depth)
    br = vals_window.shape[0]
    n = b.shape[-1]
    bn = bn or default_bn(n)
    if n % bn:
        raise ValueError(f"N={n} not divisible by bn={bn}")
    acc_dtype, out_dtype = resolve_dtypes(vals_window.dtype, out_dtype)
    out_rows = out_rows or (row_block_offset + nblocks) * br
    batch = b.shape[0] if b.ndim == 3 else None
    bz = batch_block(batch) if batch is not None else 0
    out_shape = ((out_rows, n) if batch is None else (batch, out_rows, n))
    b_view = row_view(b, b.ndim - 2)

    def _rows(rows, k, j):
        return (row_block_offset + rows[k], j)

    def call(prev, rows, cols, win, acc_in):
        npanels = rows.shape[0]
        has_carry = acc_in is not None
        grid, _ = grid_dims(batch=batch, bz=bz, n=n, bn=bn, npanels=npanels,
                            pipeline_depth=depth)
        in_specs, args, aliases = panel_operands(
            g=g, bn=bn, b=b_view, carry=acc_in, carry_block=(br, bn),
            row_map=_rows, bz=None if batch is None else bz,
            pipeline_depth=depth, npanels=npanels,
            window=pad_window(win, g))

        if depth == 1:
            def _out_k(k):
                return k
        else:
            def _out_k(k):
                return jnp.maximum(k - (depth - 1), 0)

        if batch is None:
            out_specs = pl.BlockSpec(
                (br, bn), lambda j, k, *s: _rows(s[1], _out_k(k), j))
            bpan_shape = (g, bn) if depth == 1 else (depth, g, bn)
            acc_shape = (br, bn)
        else:
            out_specs = pl.BlockSpec(
                (bz, br, bn),
                lambda z, j, k, *s: (z,) + _rows(s[1], _out_k(k), j))
            bpan_shape = (bz, g, bn) if depth == 1 else (depth, bz, g, bn)
            acc_shape = (bz, br, bn)
        scratch = [pltpu.VMEM(bpan_shape, b.dtype),     # B panel (packed)
                   pltpu.VMEM(acc_shape, acc_dtype)]    # accumulator

        if depth > 1:
            kernel = functools.partial(_piped_panel_kernel, g, has_carry,
                                       None if batch is None else bz, depth)
        else:
            kernel = functools.partial(_panel_kernel, g, has_carry,
                                       None if batch is None else bz)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # prev_row, rows, signed cols
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
            input_output_aliases=aliases,
            interpret=interpret,
        )(prev, rows, cols, *args)

    return run_panel_chunks(
        call, panel_rows, ((lane_cols, 0), (vals_window, 1)), g=g,
        per_call=panels_per_call or default_panels_per_call(g),
        carry=carry, out_shape=(out_shape, out_dtype))


@functools.partial(
    jax.jit,
    static_argnames=("nblocks", "bn", "out_dtype", "interpret"))
def bcsr_spmm_pallas(tile_rows: jax.Array, tile_cols: jax.Array,
                     tile_vals: jax.Array, b: jax.Array, *, nblocks: int,
                     interpret: bool, bn: int | None = None,
                     out_dtype=None) -> jax.Array:
    """Flat-array entry point: one tile per panel (G = 1, rank-1 updates).

    Returns the padded (..., nblocks * Br, N) result.  Format-level callers
    should prefer :func:`bcsr_panels_spmm_pallas` with a host-packed
    ``PanelBCSR`` for real G-wide matmul panels.
    """
    return bcsr_panels_spmm_pallas(
        tile_rows, tile_cols.astype(jnp.int32), jnp.transpose(tile_vals), b,
        g=1, nblocks=nblocks, bn=bn, out_dtype=out_dtype,
        interpret=interpret)


register_kernel("bcsr", "spmm", "panels", bcsr_panels_spmm_pallas)
register_kernel("bcsr", "spmm", "flat", bcsr_spmm_pallas)
