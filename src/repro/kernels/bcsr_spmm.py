"""BCSR-part SpMM Pallas kernel — the MXU (matrix-pipeline) half of LOOPS.

Paper mapping (§3.3 "Outer-product based SME kernel for BCSR part",
Algorithm 2 + Figure 2): the BCSR-part stores ``Br x 1`` column tiles; each
tile contributes a rank-1 update

    C[block p] += tile_vals[t] (x) B[tile_cols[t], :]

accumulated in a ZA tile register.  On TPU the accumulator is a VMEM block
streamed through the MXU.

Window steps (paper Figure 2 "multi-tile" batching)
---------------------------------------------------
The tiles arrive as ``(P, Br, G)`` panels (``repro.core.formats.PanelBCSR``)
whose values travel lane-dense: panel ``p`` sits at lanes ``[p·G,
(p+1)·G)`` of a ``(Br, L)`` window (``panel_common.values_window``).  A
block row's panels are contiguous in lane order, so one grid step takes the
lanes ``[lo, hi)`` of one aligned 128-lane window that belong to one block
row (``panel_common.window_steps``): a long block row runs as several
consecutive steps, short block rows share a window over several steps.  The
``(Br, 128)`` window block is the A operand as it is, lanes outside ``[lo,
hi)`` masked, and each step makes one

    C[block] += A(Br, 128) @ B_stage(128, bn)

MXU contraction — up to 128 fmopa rounds per ZA-tile visit instead of G.

B stays in HBM.  Step ``k`` starts one DMA per lane of step ``k + 1`` into
the other slot of a two-slot VMEM stage, then waits for its own slot with
one wait per power-of-two block of 128-lane rows: the gathers of the next
step overlap this step's contraction, and the first step of each sweep
fetches its own rows.  The issue loop has no branch (a padding lane,
column -1, fetches row 0) and is unrolled by four; the DMA descriptors,
about 23 ns each on a v5e, set the kernel's pace, not their bytes, so
each lane takes exactly one.  Stage rows outside the step and padding
rows are masked by a select (stale VMEM or row 0 may hold NaN), never
multiplied by a zero.

A DMA copies whole 128-lane rows of B's view.  For ``N > 128`` the view is
``(K·pieces, 128)``, a plain reshape of the row-major (padded) operand:
row ``r``'s piece ``c`` (its lanes ``[128·c, 128·c + 128)``) is view row
``r·pieces + c``, so a B row's pieces are adjacent in HBM and one DMA
copies all of a column block's, into stage rows ``[t·pieces, (t+1)·
pieces)`` of lane ``t``.  The contraction of piece ``c`` reads stage rows
``c, c + pieces, ...`` (a strided sublane load), the ``(128, 128)`` operand
of lanes in order.  For ``N < 128`` the operand is read through a
lane-dense ``(K·N'/128, 128)`` view (``N'`` the power of two ``>= N``):
lane ``c`` fetches view row ``c // q`` (``q = 128 / N'``), keeps the
``N'`` lanes of group ``c % q`` by mask, and the flush folds the ``q`` lane
groups of the ``(Br, 128)`` accumulator into ``(Br, N)``.  Other widths
not a multiple of 128 are padded to one.  At one piece (``N <= 128``) the
stage is ``(128, 128)`` and the load plain.

Batched execution (multi-RHS)
-----------------------------
A rank-3 dense operand ``(batch, K, N)`` adds a leading batch-block grid
axis and the view ``(batch, K·pieces, 128)``: each lane's DMA copies the
row of ``bz`` batch slices, and each step issues ``bz`` contractions per
piece sharing the A operand.

Precision (§3.3 FP16 path, Algorithm 3): the TPU MXU natively multiplies
bf16 operands and accumulates in fp32 (``preferred_element_type``), which
realises the paper's half-in/single-accumulate contract.  fp32 operands
contract at ``Precision.HIGHEST`` (``panel_common.dot_precision``).

grid = (N // bn, S) (batched: (batch // bz, N // bn, S)) over ``S`` steps
per ``pallas_call``; the step table (block row, first lane) is
scalar-prefetched, and ``step_rows`` is nondecreasing so output-block
revisiting is legal, exactly as in the CSR kernel.  More steps than one
call's SMEM holds run as chained calls (a ``lax.fori_loop``) threading the
output through the aliased carry; a block row across a seam resumes from
it.  ``carry`` + ``row_block_offset`` support the fused single-pass
``loops_spmm``: the kernel writes its blocks at a row offset into a shared
buffer whose other rows (the CSR part's) are preserved through
``input_output_aliases``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .engine import batch_block, register_kernel, resolve_dtypes
from .panel_common import (LANES, SMEM_PREFETCH_BYTES, dot_precision,
                           first_last_at, gather_bn, parity, window_steps)

__all__ = ["bcsr_spmm_pallas", "bcsr_panels_spmm_pallas", "gather_layout",
           "steps_per_call"]

# Operand positions among ALL pallas_call operands: the five scalar-prefetch
# arrays (prev_row, cols_base, step_rows, step_starts, lane_cols), the
# values window, then the carry (fused path) and B.
_CARRY_INDEX = 6


def gather_layout(n: int, bn: int | None = None):
    """``(bn, width, groups)`` of the gathers against an ``N``-wide operand:
    a lane gathers ``width / 128`` pieces of 128 lanes by one DMA, which
    hold ``groups`` lane groups of which one is the wanted B row
    (``groups > 1`` only for ``N < 128``, read through the lane-dense
    view)."""
    bn = gather_bn(n, bn)
    if n % LANES == 0:
        return bn, bn, 1
    if n > LANES:
        return bn, -(-n // LANES) * LANES, 1
    sub = 1 << (int(n) - 1).bit_length()     # power of two >= N, divides 128
    return bn, LANES, LANES // sub


def _gather_view(b, n: int, groups: int):
    """The operand as the kernel's DMAs read it: ``(rows·pieces, 128)`` per
    batch slice, row ``r``'s piece ``c`` (its lanes ``[128·c, 128·c +
    128)``) at view row ``r·pieces + c`` — a plain reshape of the row-major
    operand, so a B row's pieces are adjacent sublanes and one DMA fetches
    them all.  Mosaic refuses a DMA of one row out of an (8, 128)-tiled
    array wider than 128 lanes, and one of a single packed half-precision
    row, hence the 128-lane view and the 32-bit carrier.  Columns are
    zero-padded to a multiple of 128 or, when ``groups > 1``, to ``128 /
    groups`` with ``groups`` B rows folded into each 128-lane row."""
    b = b.astype(jnp.promote_types(b.dtype, jnp.float32))
    lead = b.shape[:-2]
    sub = LANES // groups if groups > 1 else -(-n // LANES) * LANES
    k = b.shape[-2]
    kp = max(-(-k // groups), LANES) * groups    # a wait names 128 rows
    if (kp, sub) != (k, n):
        b = jnp.pad(b, [(0, 0)] * len(lead) + [(0, kp - k), (0, sub - n)])
    return b.reshape(lead + (kp * sub // LANES, LANES))


def steps_per_call(step_starts, per_call: int | None = None,
                   budget_bytes: int = SMEM_PREFETCH_BYTES):
    """``(S, Lc)``: steps per ``pallas_call`` and the lane columns each call
    prefetches.  A call's SMEM holds ``2·S + 3`` step words and ``Lc`` =
    the widest lane span of any ``S`` consecutive steps; ``S`` is
    ``per_call`` or else the largest multiple of 8 that keeps both within
    ``budget_bytes``."""
    starts = np.asarray(step_starts, np.int64)
    t = starts.shape[0] - 1
    budget = budget_bytes // 4

    def span(s):
        idx = np.arange(0, max(t, 1), s)
        return int((starts[np.minimum(idx + s, t)] - starts[idx]).max())

    if per_call is not None:
        return int(per_call), span(int(per_call))
    if t == 0 or 2 * t + 3 + span(t) <= budget:
        return max(t, 1), int(starts[-1])
    lo, hi = 1, budget // 16            # in units of 8 steps
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if 16 * mid + 3 + span(8 * mid) <= budget:
            lo = mid
        else:
            hi = mid - 1
    while lo > 1 and 16 * lo + 3 + span(8 * lo) > budget:
        lo -= 1
    return 8 * lo, span(8 * lo)


def _contract(acc_ref, lanes, a, x, bz: int | None):
    """``acc[:, lanes] += A(Br, 128) @ piece(128, 128)`` on the MXU — once
    per batch slice (sharing A) when batched — at full precision for
    fp32."""
    prec = dot_precision(a.dtype)
    dims = (((1,), (0,)), ((), ()))
    for z in ([None] if bz is None else range(bz)):
        at = (slice(None), lanes) if z is None else (z, slice(None), lanes)
        acc_ref[at] += jax.lax.dot_general(
            a, x if z is None else x[z], dims, precision=prec,
            preferred_element_type=acc_ref.dtype)


def _step_kernel(groups: int, bn: int, row_pieces: int, cdt,
                 has_carry: bool, bz: int | None, *refs):
    """One grid step: start the next step's B-row DMAs, one per lane, wait
    for this step's, then one masked ``(Br, 128) @ (128, 128)``
    contraction per 128-lane piece of the column block.  A B row is
    ``row_pieces`` rows of the view, of which the column block takes
    ``pieces``."""
    prev_ref, base_ref, rows_ref, starts_ref, cols_ref, win_ref = refs[:6]
    rest = refs[6:]
    carry_ref, rest = (rest[0], rest[1:]) if has_carry else (None, rest)
    b_hbm, o_ref, stage_ref, grp_ref, acc_ref, sem = rest
    axis = 1 if bz is None else 2
    k = pl.program_id(axis)
    nsteps = pl.num_programs(axis)
    j = pl.program_id(axis - 1)
    pieces = stage_ref.shape[-2] // LANES
    first_piece = j * pieces
    base = base_ref[0]
    # Grid indices are read here: the interpreter cannot read them inside
    # the lane loops below.
    zs = None if bz is None else pl.ds(pl.program_id(0) * bz, bz)

    def copy(row, lane, slot):
        """The DMA of B row ``row``'s pieces of this column block into
        stage rows ``[lane·pieces, (lane+1)·pieces)``: adjacent in both."""
        src = pl.ds(row * row_pieces + first_piece, pieces)
        dst = pl.ds(lane * pieces, pieces)
        if bz is None:
            src, dst = b_hbm.at[src], stage_ref.at[slot, dst]
        else:
            src, dst = b_hbm.at[zs, src], stage_ref.at[slot, :, dst]
        return pltpu.make_async_copy(src, dst, sem.at[slot])

    shift = groups.bit_length() - 1          # groups is a power of two

    def issue(step, slot):
        """Start one DMA per lane of ``step`` into ``slot`` and note each
        lane's group.  A padding lane (column -1) fetches row 0 under group
        -1, which the mask drops: the loop has no branch, and it is
        unrolled by four."""
        t0 = starts_ref[step]
        t1 = starts_ref[step + 1]
        w0 = t0 - t0 % LANES

        def lane(col, t):
            grp_ref[slot, pl.ds(t - w0, 1), :] = jnp.full(
                (1, 1), jnp.where(col >= 0, col & (groups - 1), -1),
                jnp.int32)
            copy(jnp.maximum(col, 0) >> shift, t - w0, slot).start()

        def four(i, carry):
            t = t0 + 4 * i
            cols = [cols_ref[t + d - base] for d in range(4)]
            for d in range(4):
                lane(cols[d], t + d)
            return carry
        full = (t1 - t0) // 4
        jax.lax.fori_loop(0, full, four, 0)
        for d in range(3):
            @pl.when(t0 + 4 * full + d < t1)
            def _(d=d):
                t = t0 + 4 * full + d
                lane(cols_ref[t - base], t)

    def wait(step, slot):
        """Wait for ``step``'s DMAs into ``slot``: a DMA semaphore counts
        the bytes that arrived, so one wait for a ``(rows, 128)`` block
        covers ``rows`` 128-lane rows, ``pieces`` to a lane."""
        def block(rows):
            if bz is None:
                dst = stage_ref.at[slot, pl.ds(0, rows)]
                src = b_hbm.at[pl.ds(0, rows)]
            else:
                dst = stage_ref.at[slot, :, pl.ds(0, rows)]
                src = b_hbm.at[zs, pl.ds(0, rows)]
            pltpu.make_async_copy(src, dst, sem.at[slot]).wait()

        count = (starts_ref[step + 1] - starts_ref[step]) * pieces
        jax.lax.fori_loop(0, count >> 7, lambda i, c: (block(LANES), c)[1],
                          0)
        for bit in (64, 32, 16, 8, 4, 2, 1):
            @pl.when((count & bit) != 0)
            def _(bit=bit):
                block(bit)

    slot = parity(k)

    @pl.when(k == 0)
    def _own():
        issue(k, slot)

    @pl.when(k + 1 < nsteps)
    def _next():
        issue(k + 1, 1 - slot)

    wait(k, slot)
    first, last = first_last_at(rows_ref, k, nsteps)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if carry_ref is not None:
            # A block row continued from the previous call resumes from
            # the partial sum that call flushed into the aliased carry.
            @pl.when(jnp.logical_and(k == 0, rows_ref[0] == prev_ref[0]))
            def _resume():
                acc_ref[..., :bn] = carry_ref[...].astype(acc_ref.dtype)

    t0 = starts_ref[k]
    lo = t0 % LANES
    hi = starts_ref[k + 1] - (t0 - lo)
    a = win_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    a = jnp.where((lane >= lo) & (lane < hi), a, jnp.zeros_like(a))
    sub = LANES // groups
    row = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    grp = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1) // sub
    keep = (row >= lo) & (row < hi) & (grp_ref[slot] == grp)
    a = a.astype(cdt)
    for c in range(pieces):
        # Piece ``c`` of every lane: stage rows ``c, c + pieces, ...``.
        rows = pl.ds(c, LANES, stride=pieces)
        x = stage_ref[slot, rows] if bz is None else stage_ref[slot, :, rows]
        x = jnp.where(keep, x, jnp.zeros_like(x)).astype(cdt)
        _contract(acc_ref, slice(c * LANES, (c + 1) * LANES), a, x, bz)

    @pl.when(last)
    def _flush():
        acc = acc_ref[...]
        out = acc[..., :bn]
        for g in range(1, groups):
            out = out + acc[..., g * sub:g * sub + bn]
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("nblocks", "row_block_offset", "out_rows", "bn",
                     "out_dtype", "interpret", "per_call", "call_lanes",
                     "tail_window", "name"))
def _bcsr_steps_spmm(step_rows, step_starts, lane_cols, vals_window, b,
                     carry, *, nblocks: int, interpret: bool,
                     row_block_offset: int, out_rows: int | None,
                     bn: int | None, out_dtype, per_call: int,
                     call_lanes: int, tail_window: int, name: str):
    br = vals_window.shape[0]
    n = b.shape[-1]
    bn, width, groups = gather_layout(n, bn)
    if n % bn:
        raise ValueError(f"N={n} not divisible by bn={bn}")
    acc_dtype, out_dtype = resolve_dtypes(vals_window.dtype, out_dtype)
    out_rows = out_rows or (row_block_offset + nblocks) * br
    batch = b.shape[0] if b.ndim == 3 else None
    bz = batch_block(batch) if batch is not None else None
    out_shape = (out_rows, n) if batch is None else (batch, out_rows, n)
    view = _gather_view(b, n, groups)
    pieces = width // LANES
    row_pieces = 1 if groups > 1 else -(-n // LANES)
    cdt = jnp.promote_types(vals_window.dtype, b.dtype)
    pad = -vals_window.shape[-1] % LANES
    if pad:
        vals_window = jnp.pad(vals_window, ((0, 0), (0, pad)))
    lead = () if bz is None else (bz,)

    def spec(block, index):
        # Index maps get the grid indices, then the scalar-prefetch refs.
        if bz is None:
            return pl.BlockSpec(block, lambda j, k, *s: index((), j, k, s))
        return pl.BlockSpec(block, lambda z, j, k, *s: index((z,), j, k, s))

    def out_block(z, j, k, s):
        return z + (row_block_offset + s[2][k], j)

    def call(prev, base, rows, starts, cols, acc_in, first_window=0):
        # The values from window ``first_window`` on: a static slice, so
        # the remainder call does not hold a second copy of the constant
        # the chunk loop reads.
        nsteps = rows.shape[0]
        grid = (n // bn, nsteps) if bz is None else (batch // bz, n // bn,
                                                     nsteps)
        in_specs = [spec((br, LANES), lambda z, j, k, s:
                         (0, s[3][k] // LANES - first_window))]
        args = [vals_window[:, first_window * LANES:]]
        aliases = {}
        if acc_in is not None:
            in_specs.append(spec(lead + (br, bn), out_block))
            args.append(acc_in)
            aliases = {_CARRY_INDEX: 0}
        in_specs.append(pl.BlockSpec(memory_space=pltpu.HBM))
        args.append(view)
        kernel = functools.partial(_step_kernel, groups, bn, row_pieces,
                                   cdt, acc_in is not None, bz)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=in_specs,
            out_specs=spec(lead + (br, bn), out_block),
            scratch_shapes=[
                pltpu.VMEM((2,) + lead + (pieces * LANES, LANES),
                           view.dtype),
                pltpu.VMEM((2, LANES, 1), jnp.int32),      # lane groups
                pltpu.VMEM(lead + (br, width), acc_dtype),  # accumulator
                pltpu.SemaphoreType.DMA((2,)),
            ],
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
            input_output_aliases=aliases,
            interpret=interpret,
            name=name,
        )(prev, base, rows, starts, cols, *args)

    nsteps = step_rows.shape[0]
    zero = jnp.zeros((1,), jnp.int32)
    if nsteps <= per_call:
        return call(zero - 1, zero, step_rows, step_starts, lane_cols, carry)
    lanes_total = lane_cols.shape[0]
    out = carry if carry is not None else jnp.zeros(out_shape, out_dtype)
    def chunk(s0, count, acc, first_window=0):
        # Steps [s0, s0 + count) and the lane columns they read, from
        # ``base`` (kept in range: a slice past the end would shift).
        base = jnp.minimum(step_starts[s0], lanes_total - call_lanes)
        prev = jnp.where(s0 > 0, step_rows[jnp.maximum(s0 - 1, 0)], -1)
        return call(prev[None].astype(jnp.int32), base[None],
                    jax.lax.dynamic_slice_in_dim(step_rows, s0, count),
                    jax.lax.dynamic_slice_in_dim(step_starts, s0, count + 1),
                    jax.lax.dynamic_slice_in_dim(lane_cols, base, call_lanes),
                    acc, first_window)

    nfull = nsteps // per_call
    out = jax.lax.fori_loop(
        0, nfull, lambda c, acc: chunk(c * per_call, per_call, acc), out)
    if nfull * per_call < nsteps:
        out = chunk(nfull * per_call, nsteps - nfull * per_call, out,
                    tail_window)
    return out


def bcsr_panels_spmm_pallas(panel_rows, lane_cols, vals_window, b, *,
                            g: int, nblocks: int, interpret: bool,
                            row_block_offset: int = 0,
                            out_rows: int | None = None,
                            bn: int | None = None, out_dtype=None,
                            carry: jax.Array | None = None, steps=None,
                            per_call: int | None = None,
                            name: str = "loops_bcsr_spmm") -> jax.Array:
    """Panelized vector-wise BCSR SpMM over window steps.

    Args:
      panel_rows: (P,) int32 block-row per panel, nondecreasing; host data
                  (the step table is built from it) unless ``steps`` is
                  given.
      lane_cols:  (P·G,) int32 gather rows of ``b``, lane ``i`` of panel
                  ``p`` at ``p·G + i``, -1 on padding lanes
                  (``PanelBCSR.lane_cols``).
      vals_window: (Br, L) lane-dense tile values, panel ``p``'s ``(Br,
                  G)`` operand at lanes ``[p·G, (p+1)·G)``
                  (``PanelBCSR.vals_window``).
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
      bn:         output column block; used where it and N are multiples
                  of 128 (``panel_common.gather_bn``), else all of N.
      carry:      optional (..., out_rows, N) array aliased into the output;
                  rows not visited here keep its contents (fused mode).
      steps:      the host step table ``(step_rows, step_starts)``
                  (``PanelBCSR.steps``); built from ``panel_rows`` when
                  omitted.
      per_call:   steps per ``pallas_call`` (default
                  :func:`steps_per_call`, the SMEM bound); more steps run
                  as chained calls.
      name:       the kernel's name, which a device trace shows
                  (``loops_bcsr_spmm_t`` on a custom VJP's Aᵀ).
    """
    if b.ndim not in (2, 3):
        raise ValueError(f"b must be (K, N) or (batch, K, N); got rank "
                         f"{b.ndim}")
    step_rows, step_starts = (steps if steps is not None
                              else window_steps(np.asarray(panel_rows), g))
    per, lanes = steps_per_call(step_starts, per_call)
    # The window of the first step past the last whole chunk.
    tail = int(np.asarray(step_starts)[len(step_rows) // per * per])
    return _bcsr_steps_spmm(
        jnp.asarray(step_rows), jnp.asarray(step_starts),
        jnp.asarray(lane_cols), vals_window, b, carry, nblocks=nblocks,
        interpret=interpret, row_block_offset=row_block_offset,
        out_rows=out_rows, bn=bn, out_dtype=out_dtype, per_call=per,
        call_lanes=lanes, tail_window=tail // LANES, name=name)


def bcsr_spmm_pallas(tile_rows, tile_cols, tile_vals, b: jax.Array, *,
                     nblocks: int, interpret: bool, bn: int | None = None,
                     out_dtype=None) -> jax.Array:
    """Flat-array entry point: one tile per panel (G = 1).

    Returns the padded (..., nblocks * Br, N) result; ``tile_rows`` is host
    data.  Format-level callers should prefer
    :func:`bcsr_panels_spmm_pallas` with a host-packed ``PanelBCSR``.
    """
    return bcsr_panels_spmm_pallas(
        tile_rows, jnp.asarray(tile_cols).astype(jnp.int32),
        jnp.transpose(jnp.asarray(tile_vals)), b, g=1, nblocks=nblocks,
        bn=bn, out_dtype=out_dtype, interpret=interpret)


register_kernel("bcsr", "spmm", "panels", bcsr_panels_spmm_pallas)
register_kernel("bcsr", "spmm", "flat", bcsr_spmm_pallas)
