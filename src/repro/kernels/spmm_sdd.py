"""Sampled dense-dense (SDD) Pallas kernels — the value-gradient half of the
LOOPS custom VJP.

For ``Y = A @ B`` with A sparse, the cotangent of A's *stored values* is the
dense product ``dY @ Bᵀ`` sampled at the stored coordinates only:

    dA[i, j] = dY[i, :] · B[j, :]        (i, j) ∈ structure(A)

Materialising ``dY @ Bᵀ`` would cost O(M·K·N) and defeat the point of
training a pruned layer; these kernels spend O(nnz·N) by walking the same
G-wide panels the forward kernels execute (``repro.core.formats.PanelCSR`` /
``PanelBCSR``), gathering the G rows ``B[panel_cols[p]]`` per grid step and
contracting them against the panel's cotangent rows:

  * CSR part — one grid step computes the G dot products
    ``dY[panel_rows[p], :] · B[panel_cols[p, i], :]`` as one ``(1, bn) @
    (bn, G)`` contraction (the AXPY kernel read backwards);
  * BCSR part — one grid step computes a ``(Br, bn) @ (bn, G)`` MXU
    contraction between the block-row's cotangent slab and the gathered B
    panel, yielding all ``Br × G`` per-tile-element gradients at once.

The grid is ``(P, N // bn)`` with the *column* blocks innermost: each panel's
accumulator stays resident in VMEM scratch while the N-reduction streams
through, then flushes once — the transpose of the forward kernels' resident
output block.  Padding lanes produce garbage that is never read: the callers
(``repro.kernels.engine.loops_sdd``) gather only real slots via the panels'
``src_panel``/``src_lane`` maps, so no in-kernel mask is needed.

Batched execution (multi-RHS backward)
--------------------------------------
With rank-3 ``(batch, ..., N)`` cotangent/operand pairs the grid becomes
``(P, batch // bz, N // bn)``: the stored values are shared across the
batch, so their cotangent is the **batch sum**, which the kernels realise
by folding the batch axis into the same resident accumulation the
N-reduction already uses — ``bz`` slices per step, one flush per panel.

Single-row blocks (B rows, the CSR part's cotangent row, its ``(1, G)``
output panel) address ``(rows, 1, N)`` views so that every block satisfies
the TPU tiling rule, and the panel axis runs in SMEM-sized chunks
(``panel_common.panels_per_call``); fp32 contractions run at full fp32
precision (``panel_common.dot_precision``).

Outputs are panel-layout ``(P, G)`` / ``(P, Br, G)`` arrays in the fp32
accumulation dtype (the f16f16f32 contract of the forward kernels applies to
the backward pass too).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .engine import acc_dtype_for, batch_block, register_kernel
from .panel_common import (check_pipeline_depth, default_bn, dot_precision,
                           gather_col, panels_per_call, parity, row_view)

__all__ = ["csr_sdd_panels_pallas", "bcsr_sdd_panels_pallas"]


def _reduction_edges(bz: int | None, depth: int = 1):
    """(first, last) predicates over the per-panel reduction axes — the
    column blocks and, when batched, the batch blocks — shared by both SDD
    kernels so init/flush can never disagree with the grid layout.  A
    depth-``d`` pipeline skews the compute stream ``d - 1`` steps behind
    the column-block grid axis (the fill-ramp steps are load-only), so the
    reduction opens at ``j == depth - 1`` instead of 0."""
    if bz is None:
        j = pl.program_id(1)
        nb = pl.num_programs(1)
        return j == depth - 1, j == nb - 1
    z, j = pl.program_id(1), pl.program_id(2)
    nz, nb = pl.num_programs(1), pl.num_programs(2)
    return jnp.logical_and(z == 0, j == depth - 1), \
        jnp.logical_and(z == nz - 1, j == nb - 1)


def _sdd_col_maps(depth: int, nb: int):
    """``(lj, cj)`` column-block index maps for the SDD reduction axis:
    grid step ``jj`` loads B's column block ``lj(jj) = min(jj, nb-1)`` and
    reduces the cotangent's column block ``cj(jj) = max(jj - (depth-1), 0)``.
    Identity maps at depth 1."""
    if depth == 1:
        return (lambda jj: jj), (lambda jj: jj)
    return (lambda jj: jnp.minimum(jj, nb - 1),
            lambda jj: jnp.maximum(jj - (depth - 1), 0))


def _gather_rows(b_refs, bpan_ref, bz: int | None, slot=None):
    """Copy the G gathered B rows into the ``(G, bn)`` panel scratch
    (``(bz, G, bn)`` when batched, behind a leading ping-pong ``slot`` axis
    when pipelined), packed in B's storage dtype."""
    lead = () if slot is None else (slot,)
    for i, b_ref in enumerate(b_refs):
        row = b_ref[...].astype(bpan_ref.dtype)   # (1, bn) / (bz, 1, bn)
        if bz is None:
            bpan_ref[lead + (pl.ds(i, 1), slice(None))] = row
        else:
            bpan_ref[lead + (slice(None), pl.ds(i, 1), slice(None))] = row


def _contract_dy(acc_ref, dy, bpan, bz: int | None):
    """``acc += dY_block @ B_panelᵀ`` — ``(rows, bn) x (G, bn) -> (rows,
    G)`` on the MXU, summed over the ``bz`` batch slices when batched (the
    shared-values batch-sum contract of the backward pass)."""
    acc = acc_ref.dtype
    prec = dot_precision(acc)
    dims = (((1,), (1,)), ((), ()))
    slices = [(dy, bpan)] if bz is None else \
        [(dy[z], bpan[z]) for z in range(bz)]
    for d, bp in slices:
        acc_ref[...] += jax.lax.dot_general(
            d.astype(acc), bp.astype(acc), dims, precision=prec,
            preferred_element_type=acc)


def _sdd_kernel(g: int, bz: int | None, *refs):
    """One grid step: gather the G B-rows into scratch, one MXU contraction
    against the panel's cotangent rows — ``(1, bn)`` for the CSR part (the
    G dot products ``dY[row]·B[col_i]``), ``(Br, bn)`` for the BCSR part
    (all ``Br × G`` tile-element gradients) — summed over the batch slices
    when batched; flush after the last reduction block."""
    _, _, dy_ref, *rest = refs
    b_refs, (o_ref, bpan_ref, acc_ref) = rest[:g], rest[g:]
    first, last = _reduction_edges(bz)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _gather_rows(b_refs, bpan_ref, bz)
    _contract_dy(acc_ref, dy_ref[...], bpan_ref[...], bz)

    @pl.when(last)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _piped_sdd_kernel(g: int, bz: int | None, depth: int, *refs):
    """Depth-2 SDD pipeline over the column-block reduction axis: step
    ``jj`` copies B's column block ``min(jj, nb-1)`` into ping-pong scratch
    slot ``jj % 2`` (packed in B's storage dtype) while contracting the
    cotangent's column block ``max(jj - 1, 0)`` against slot
    ``(jj+1) % 2``."""
    _, _, dy_ref, *rest = refs
    b_refs, (o_ref, bpan_ref, acc_ref) = rest[:g], rest[g:]
    jaxis = 1 if bz is None else 2
    jj = pl.program_id(jaxis)
    first, last = _reduction_edges(bz, depth)

    for s in (0, 1):
        @pl.when(parity(jj) == s)
        def _(s=s):
            _gather_rows(b_refs, bpan_ref, bz, slot=s)

    @pl.when(jj >= depth - 1)
    def _compute():
        @pl.when(first)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        for s in (0, 1):
            @pl.when(parity(jj + 1) == s)
            def _(s=s):
                _contract_dy(acc_ref, dy_ref[...], bpan_ref[s], bz)

        @pl.when(last)
        def _flush():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _run_chunks(call, rows, cols, g: int, per_call: int):
    """``call(rows, cols)`` over SMEM-sized chunks of the panel axis: a
    ``lax.fori_loop`` over the full chunks (one compiled kernel) plus a
    remainder launch, each writing its panels' rows of the output.  The SDD
    grids carry no state across panels, so chunks are independent."""
    npanels = int(rows.shape[0])
    if npanels <= per_call:
        return call(rows, cols)
    nfull, head = npanels // per_call, npanels // per_call * per_call

    def body(c, out):
        part = call(jax.lax.dynamic_slice_in_dim(rows, c * per_call,
                                                 per_call),
                    jax.lax.dynamic_slice_in_dim(cols, c * per_call * g,
                                                 per_call * g))
        return jax.lax.dynamic_update_slice_in_dim(out, part, c * per_call,
                                                   0)

    part = jax.eval_shape(call, rows[:per_call], cols[:per_call * g])
    out = jnp.zeros((npanels,) + part.shape[1:], part.dtype)
    out = jax.lax.fori_loop(0, nfull, body, out)
    if head < npanels:
        out = out.at[head:].set(call(rows[head:], cols[head * g:]))
    return out


def _sdd_panels(panel_rows, lane_cols, dy, b, *, g: int, br: int | None,
                bn, interpret: bool, pipeline_depth: int, per_call):
    """Shared entry of both SDD kernels: ``br=None`` is the CSR part
    (one cotangent row per panel, addressed through its ``(M, 1, N)``
    view), an int the BCSR part (one ``(Br, bn)`` block-row slab)."""
    if dy.ndim != b.ndim or b.ndim not in (2, 3):
        raise ValueError(f"dy/b must both be rank 2 or 3; got {dy.ndim} / "
                         f"{b.ndim}")
    depth = check_pipeline_depth(pipeline_depth)
    n = b.shape[-1]
    bn = bn or default_bn(n)
    if n % bn:
        raise ValueError(f"N={n} not divisible by bn={bn}")
    acc_dtype = acc_dtype_for(b.dtype)
    batch = b.shape[0] if b.ndim == 3 else None
    nb = n // bn
    lj, cj = _sdd_col_maps(depth, nb)
    row_axis = b.ndim - 2
    b_view = row_view(b, row_axis)
    rows_out = 1 if br is None else br
    if br is None:
        dy = row_view(dy, row_axis)
        dy_block, dy_index = (None, 1, bn), (lambda r, j: (r, 0, cj(j)))
    else:
        dy_block, dy_index = (br, bn), (lambda r, j: (r, cj(j)))
    if batch is None:
        bz = None
        grid = (nb + depth - 1,)
        in_specs = [
            pl.BlockSpec(dy_block, lambda p, j, rows, cols:
                         dy_index(rows[p], j)),
            *[pl.BlockSpec((None, 1, bn),
                           lambda p, j, rows, cols, i=i:
                           (gather_col(cols, p * g + i), 0, lj(j)))
              for i in range(g)],
        ]
        out_specs = pl.BlockSpec((None, rows_out, g),
                                 lambda p, j, rows, cols: (p, 0, 0))
        bpan_shape = (g, bn)
    else:
        bz = batch_block(batch)
        grid = (batch // bz, nb + depth - 1)
        in_specs = [
            pl.BlockSpec((bz,) + dy_block, lambda p, z, j, rows, cols:
                         (z,) + dy_index(rows[p], j)),
            *[pl.BlockSpec((bz, None, 1, bn),
                           lambda p, z, j, rows, cols, i=i:
                           (z, gather_col(cols, p * g + i), 0, lj(j)))
              for i in range(g)],
        ]
        out_specs = pl.BlockSpec((None, rows_out, g),
                                 lambda p, z, j, rows, cols: (p, 0, 0))
        bpan_shape = (bz, g, bn)
    if depth > 1:
        bpan_shape = (depth,) + bpan_shape     # packed ping-pong
        kernel = functools.partial(_piped_sdd_kernel, g, bz, depth)
    else:
        kernel = functools.partial(_sdd_kernel, g, bz)
    scratch = [pltpu.VMEM(bpan_shape, b.dtype),
               pltpu.VMEM((rows_out, g), acc_dtype)]

    def call(rows, cols):
        npanels = rows.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # panel_rows, flat panel columns
            grid=(npanels,) + grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((npanels, rows_out, g),
                                           acc_dtype),
            interpret=interpret,
        )(rows, cols, dy, *([b_view] * g))

    out = _run_chunks(call, panel_rows, lane_cols, g,
                      per_call or panels_per_call(g))
    return out.reshape(out.shape[0], g) if br is None else out


@functools.partial(jax.jit,
                   static_argnames=("g", "bn", "interpret", "pipeline_depth",
                                    "panels_per_call"))
def csr_sdd_panels_pallas(panel_rows: jax.Array, lane_cols: jax.Array,
                          dy: jax.Array, b: jax.Array, *, g: int,
                          interpret: bool, bn: int | None = None,
                          pipeline_depth: int = 1,
                          panels_per_call: int | None = None) -> jax.Array:
    """Per-nonzero gradients for the CSR part, in panel layout.

    Args:
      panel_rows: (P,) int32 — cotangent row per panel (``PanelCSR`` order).
      lane_cols:  (P·G,) int32 — gather rows of ``b``, lane ``i`` of panel
                  ``p`` at ``p·G + i`` (``PanelCSR.lane_cols``).
      dy:         (M, N) output cotangent, or (batch, M, N) — batch summed
                  (rows beyond the CSR region are simply never indexed).
      b:          (K, N) or (batch, K, N) the forward dense operand.
      g:          panel width G (static).
      interpret:  run the Pallas interpreter (CPU validation) or compile
                  for the TPU; every caller states which.
      panels_per_call: panels per ``pallas_call`` (default: the SMEM
                  bound of ``panel_common.panels_per_call``).
    Returns:
      (P, G) gradients in the accumulation dtype; padding lanes undefined —
      gather real slots with ``PanelCSR.gather_values``.
    """
    return _sdd_panels(panel_rows, lane_cols, dy, b, g=g, br=None, bn=bn,
                       interpret=interpret, pipeline_depth=pipeline_depth,
                       per_call=panels_per_call)


@functools.partial(jax.jit, static_argnames=("g", "br", "bn", "interpret",
                                             "pipeline_depth",
                                             "panels_per_call"))
def bcsr_sdd_panels_pallas(panel_rows: jax.Array, lane_cols: jax.Array,
                           dy_pad: jax.Array, b: jax.Array, *, g: int,
                           br: int, interpret: bool, bn: int | None = None,
                           pipeline_depth: int = 1,
                           panels_per_call: int | None = None) -> jax.Array:
    """Per-tile-element gradients for the BCSR part, in panel layout.

    Args:
      panel_rows: (P,) int32 — block-row per panel (``PanelBCSR`` order).
      lane_cols:  (P·G,) int32 — gather rows of ``b``, lane ``i`` of panel
                  ``p`` at ``p·G + i`` (``PanelBCSR.lane_cols``).
      dy_pad:     (nblocks * Br, N) or (batch, nblocks * Br, N) — the BCSR
                  region of the cotangent, zero-padded to full blocks
                  (trimmed rows ⇒ zero grad); batch summed.
      b:          (K, N) or (batch, K, N) the forward dense operand.
      g:          panel width G (static).
      interpret:  run the Pallas interpreter (CPU validation) or compile
                  for the TPU; every caller states which.
      panels_per_call: panels per ``pallas_call`` (default: the SMEM
                  bound of ``panel_common.panels_per_call``).
    Returns:
      (P, Br, G) gradients in the accumulation dtype; padding lanes
      undefined — gather real slots with ``PanelBCSR.gather_values``.
    """
    return _sdd_panels(panel_rows, lane_cols, dy_pad, b, g=g, br=br, bn=bn,
                       interpret=interpret, pipeline_depth=pipeline_depth,
                       per_call=panels_per_call)


register_kernel("csr", "sdd", "panels", csr_sdd_panels_pallas)
register_kernel("bcsr", "sdd", "panels", bcsr_sdd_panels_pallas)
