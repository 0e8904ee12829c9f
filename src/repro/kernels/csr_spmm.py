"""CSR-part SpMM Pallas kernel — the VPU (vector-pipeline) half of LOOPS.

Paper mapping (§3.3 "AXPY based NEON kernel for CSR part"): for each nonzero
``(r, c, v)`` of the CSR-part, AXPY-accumulate ``v * B[c, :]`` into output row
``r``.  On Arm this vectorises over NEON lanes; on TPU it vectorises over the
VPU's 8x128 lanes along the N (dense-column) dimension.  No MXU involvement —
this kernel exists precisely so that irregular rows do not pay the
outer-product padding cost (paper C1) and so that the matrix pipeline is left
free for the BCSR-part (paper C3).

Panelized execution (paper Figure 2 "multi-tile" batching)
----------------------------------------------------------
The kernel consumes ``(P, G)`` panels (``repro.core.formats.PanelCSR``): one
grid step gathers the G rows ``B[panel_cols[p]]`` (G independent scalar-
prefetch-indexed DMAs that all overlap with compute of the previous step) and
masked-broadcast-multiply-reduces them against ``panel_vals[p]`` into the
resident accumulator.  The grid shrinks from ``nnz`` to ``ceil(nnz/G)`` inner
steps — the TPU analogue of batching several fmopa rounds per ZA-tile visit.
G = 1 with a trivial mask reproduces the historical one-nonzero-per-step
kernel exactly (``csr_spmm_pallas`` is that wrapper).

Batched execution (multi-RHS)
-----------------------------
A rank-3 dense operand ``(batch, K, N)`` adds a leading batch-block grid
axis: each grid step loads A's ``(1, G)`` panel metadata ONCE and applies it
to ``bz`` batch slices (``repro.kernels.engine.batch_block``) of B at a
time, producing a ``(bz, 1, bn)`` output block per step.  Grid steps grow by
``ceil(batch / bz)`` — not ``batch`` — over the unbatched call, which is
what lets one batched engine call replace a per-element Python loop.

Implementation notes
--------------------
* grid = (N // bn, P) (batched: (batch // bz, N // bn, P)): the innermost
  grid dimension walks panels in (row, col) order; the *output* BlockSpec
  index_map scatters to ``panel_rows[p]`` which is nondecreasing, so Pallas
  legally keeps the current output block resident in VMEM across consecutive
  grid steps of the same row (the TPU analogue of keeping the NEON
  accumulator registers live across a row).
* ``panel_rows``/``panel_cols`` arrive via scalar prefetch (SMEM) so the B-row
  gathers are expressed in BlockSpec index_maps — the standard Pallas-TPU
  sparse-gather idiom; the DMAs for step k+1 overlap with compute of step k.
  SMEM bounds the panels per ``pallas_call``; longer panel streams run in
  chained chunks (``panel_common.run_panel_chunks``).
* B rows and output rows are single-row blocks, addressed through ``(rows,
  1, N)`` views so that every block satisfies the TPU tiling rule
  (``panel_common.row_view``); panel values ride in SMEM as scalars, and the
  lane mask as column -1 (``panel_common.lane_cols``).
* Accumulation runs in fp32 scratch for {bf16, f16} inputs (f16f16f32
  contract) and in the native dtype for f32/f64 — the shared promotion
  helper ``repro.kernels.engine.resolve_dtypes``.
* every output row must appear in ``panel_rows`` at least once (format layer
  guarantees this via >= 1 panel per row) or its block would be left
  uninitialised on real hardware.
* ``carry``: optional full-size output operand aliased to the result
  (``input_output_aliases``) for the fused single-pass ``loops_spmm`` — rows
  this kernel does not visit keep the carry's values, letting the CSR and
  BCSR kernels fill disjoint row ranges of ONE buffer with no concatenate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .engine import batch_block, register_kernel, resolve_dtypes
from .panel_common import (CSR_WORDS, check_pipeline_depth, default_bn,
                           first_last, first_last_at, grid_dims, init_acc,
                           panel_operands, parity, row_view,
                           run_panel_chunks, split_panel_refs)
from .panel_common import panels_per_call as default_panels_per_call

__all__ = ["csr_spmm_pallas", "csr_panels_spmm_pallas"]


def _axpy(acc, cols_ref, vals_ref, lane, row):
    """``acc + vals[lane] * row`` where the lane is real, else ``acc``.
    A padding lane's B row is zeroed before the multiply (its value is 0),
    so a padding lane never turns a non-finite B row into a NaN."""
    row = row.astype(acc.dtype)
    row = jnp.where(cols_ref[lane] >= 0, row, jnp.zeros_like(row))
    return acc + vals_ref[lane] * row     # AXPY over N lanes


def _panel_kernel(g: int, has_carry: bool, bz: int | None, *refs):
    """One grid step: masked gather of G rows of B, multiply-reduce over G
    into the resident accumulator (``bz`` batch slices at once when
    batched).  Rows are ``(1, bn)``, or ``(bz, 1, bn)`` when batched."""
    (prev_ref, rows_ref, cols_ref, vals_ref), _, carry_ref, b_refs, \
        (o_ref, acc_ref) = split_panel_refs(refs, g, 4, 0, has_carry)
    axis = 1 if bz is None else 2
    k = pl.program_id(axis)
    first, last = first_last(rows_ref, panel_axis=axis)

    @pl.when(first)
    def _init():
        init_acc(acc_ref, carry_ref, prev_ref, rows_ref, k)

    # Masked broadcast-multiply-reduce over the G axis: lane i contributes
    # vals[i] * B[cols[i], :] iff the lane is real (padding lanes carry
    # column -1 and value 0, so panels shorter than G — nnz not divisible
    # by G, row boundaries — are exact, not approximate).  Values are SMEM
    # scalars; B's rows stay packed in their storage dtype and only the multiply
    # promotes (bf16 -> f32 is exact, so half-precision panels cost half
    # the VMEM traffic at identical results).
    acc = acc_ref[...]
    for i, b_ref in enumerate(b_refs):
        acc = _axpy(acc, cols_ref, vals_ref, k * g + i, b_ref[...])
    acc_ref[...] = acc

    @pl.when(last)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _piped_panel_kernel(g: int, has_carry: bool, bz: int | None, depth: int,
                        *refs):
    """Depth-2 software pipeline: grid step ``k`` assembles panel
    ``min(k, P-1)``'s B rows into ping-pong scratch slot ``k % 2`` while
    contracting panel ``max(k - 1, 0)`` out of slot ``(k+1) % 2`` — the B
    gathers of the next panel overlap the AXPY of the current one.  The
    grid carries ``depth - 1`` extra fill/drain ramp steps; compute, init
    and flush are predicated off during the fill ramp."""
    (prev_ref, rows_ref, cols_ref, vals_ref), _, carry_ref, b_refs, \
        (o_ref, bpan_ref, acc_ref) = \
        split_panel_refs(refs, g, 4, 0, has_carry)
    axis = 1 if bz is None else 2
    k = pl.program_id(axis)
    npanels = pl.num_programs(axis) - (depth - 1)

    def _assemble(slot):
        # Stage the raw (packed-dtype) B rows; the compute stream applies
        # the lane mask exactly like the depth-1 kernel, so results stay
        # bitwise identical.
        for i, b_ref in enumerate(b_refs):
            bpan_ref[slot, i] = b_ref[...]

    for s in (0, 1):
        @pl.when(parity(k) == s)
        def _(s=s):
            _assemble(s)

    @pl.when(k >= depth - 1)
    def _compute():
        c = jnp.maximum(k - (depth - 1), 0)
        first, last = first_last_at(rows_ref, c, npanels)

        @pl.when(first)
        def _init():
            init_acc(acc_ref, carry_ref, prev_ref, rows_ref, c)

        def _accumulate(slot):
            acc = acc_ref[...]
            for i in range(g):
                acc = _axpy(acc, cols_ref, vals_ref, c * g + i,
                            bpan_ref[slot, i])
            acc_ref[...] = acc

        for s in (0, 1):
            @pl.when(parity(k + 1) == s)
            def _(s=s):
                _accumulate(s)

        @pl.when(last)
        def _flush():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("g", "nrows", "out_rows", "bn", "out_dtype",
                     "interpret", "pipeline_depth", "panels_per_call"))
def csr_panels_spmm_pallas(panel_rows: jax.Array, lane_cols: jax.Array,
                           lane_vals: jax.Array, b: jax.Array, *, g: int,
                           nrows: int, interpret: bool,
                           out_rows: int | None = None, bn: int | None = None,
                           out_dtype=None, carry: jax.Array | None = None,
                           pipeline_depth: int = 1,
                           panels_per_call: int | None = None) -> jax.Array:
    """C[r] += sum_i vals[p,i] * B[cols[p,i], :] over the real lanes of
    every panel p.

    Args:
      panel_rows: (P,) int32, nondecreasing output row per panel.
      lane_cols:  (P·G,) int32 gather rows of ``b``, lane ``i`` of panel
                  ``p`` at ``p·G + i``, -1 on padding lanes
                  (``PanelCSR.lane_cols``).
      lane_vals:  (P·G,) values in the same order, 0 on padding lanes
                  (``PanelCSR.lane_vals``).
      g:          panel width G (static).
      b:          (K, N) dense operand, or (batch, K, N) for the native
                  batched grid (one kernel call serves every slice).
      nrows:      logical output row count this kernel writes (static).
      interpret:  run the Pallas interpreter (CPU validation) or compile
                  for the TPU; every caller states which.
      out_rows:   total rows of the returned array (>= nrows; rows beyond
                  ``nrows`` are the fused path's BCSR territory).  Defaults
                  to ``nrows``.
      bn:         dense-column block width; defaults to
                  ``panel_common.default_bn(N)`` (the whole row up to 512,
                  else the widest 128-lane multiple) — the wide block is
                  the column-direction analogue of the paper's multi-tile
                  trick (several 128-lane tiles per visit).
      carry:      optional (..., out_rows, N) array aliased into the output;
                  rows not visited here keep its contents (fused mode).
      pipeline_depth: 1 (serial gather->contract, default) or 2 (double-
                  buffered B-panel prefetch: the next panel's rows assemble
                  into a ping-pong VMEM slot while this panel contracts).
                  Unbatched results are bitwise identical across depths
                  (the compute stream replays the depth-1 expression);
                  batched results agree to ~1 ulp (XLA's multiply-add
                  contraction differs across the two graphs).
      panels_per_call: panels per ``pallas_call`` (default
                  ``panel_common.panels_per_call(G, CSR_WORDS)``, the SMEM
                  bound for prefetched columns and values); more panels
                  run as chained chunks.
    """
    if b.ndim not in (2, 3):
        raise ValueError(f"b must be (K, N) or (batch, K, N); got rank "
                         f"{b.ndim}")
    depth = check_pipeline_depth(pipeline_depth)
    n = b.shape[-1]
    bn = bn or default_bn(n)
    if n % bn:
        raise ValueError(f"N={n} not divisible by bn={bn}")
    acc_dtype, out_dtype = resolve_dtypes(lane_vals.dtype, out_dtype)
    out_rows = out_rows or nrows
    batch = b.shape[0] if b.ndim == 3 else None
    bz = batch_block(batch) if batch is not None else 0
    row_axis = b.ndim - 2
    out_shape = ((out_rows, n) if batch is None else (batch, out_rows, n))
    b_view = row_view(b, row_axis)

    def _rows(rows, k, j):
        return (rows[k], 0, j)

    def call(prev, rows, cols, vals, acc_in):
        npanels = rows.shape[0]
        has_carry = acc_in is not None
        grid, _ = grid_dims(batch=batch, bz=bz, n=n, bn=bn, npanels=npanels,
                            pipeline_depth=depth)
        in_specs, args, aliases = panel_operands(
            g=g, bn=bn, b=b_view,
            carry=None if acc_in is None else row_view(acc_in, row_axis),
            carry_block=(None, 1, bn), row_map=_rows,
            bz=None if batch is None else bz, pipeline_depth=depth,
            npanels=npanels)

        if depth == 1:
            def _out_k(k):
                return k
        else:
            def _out_k(k):
                return jnp.maximum(k - (depth - 1), 0)

        if batch is None:
            out_specs = pl.BlockSpec(
                (None, 1, bn), lambda j, k, *s: _rows(s[1], _out_k(k), j))
            acc_shape = (1, bn)
            bpan_shape = (depth, g, 1, bn)
        else:
            out_specs = pl.BlockSpec(
                (bz, None, 1, bn),
                lambda z, j, k, *s: (z,) + _rows(s[1], _out_k(k), j))
            acc_shape = (bz, 1, bn)
            bpan_shape = (depth, g, bz, 1, bn)

        scratch = [pltpu.VMEM(acc_shape, acc_dtype)]
        if depth > 1:
            # Ping-pong B-panel buffer, packed in B's storage dtype (half
            # precision stays half-width in VMEM; promotion happens at the
            # multiply against the fp32-resident accumulator).
            scratch.insert(0, pltpu.VMEM(bpan_shape, b.dtype))
            kernel = functools.partial(_piped_panel_kernel, g, has_carry,
                                       None if batch is None else bz, depth)
        else:
            kernel = functools.partial(_panel_kernel, g, has_carry,
                                       None if batch is None else bz)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # prev_row, rows, signed cols, values
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                tuple(out_shape[:-1]) + (1, n), out_dtype),
            input_output_aliases=aliases,
            interpret=interpret,
        )(prev, rows, cols, vals.astype(acc_dtype), *args)
        return out.reshape(out_shape)

    return run_panel_chunks(
        call, panel_rows, ((lane_cols, 0), (lane_vals, 0)), g=g,
        per_call=panels_per_call or default_panels_per_call(g, CSR_WORDS),
        carry=carry, out_shape=(out_shape, out_dtype))


@functools.partial(
    jax.jit,
    static_argnames=("nrows", "bn", "out_dtype", "interpret"))
def csr_spmm_pallas(row_ids: jax.Array, col_idx: jax.Array, vals: jax.Array,
                    b: jax.Array, *, nrows: int, interpret: bool,
                    bn: int | None = None, out_dtype=None) -> jax.Array:
    """Flat-array entry point: one nonzero per panel (G = 1).

    Packing a (row, col)-sorted nonzero stream into width-1 panels is pure
    reshaping, so this stays jit-traceable; format-level callers should
    prefer :func:`csr_panels_spmm_pallas` with a host-packed
    ``PanelCSR`` for real G-wide panels.
    """
    return csr_panels_spmm_pallas(
        row_ids, col_idx.astype(jnp.int32), vals, b, g=1, nrows=nrows,
        bn=bn, out_dtype=out_dtype, interpret=interpret)


register_kernel("csr", "spmm", "panels", csr_panels_spmm_pallas)
register_kernel("csr", "spmm", "flat", csr_spmm_pallas)
