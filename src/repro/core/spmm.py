"""Public LOOPS SpMM API (paper §3.1 pipeline: partition -> schedule -> execute).

``loops_spmm`` executes a pre-converted ``LoopsFormat`` (CSR-part on the
vector pipeline, BCSR-part on the matrix pipeline, concatenated row-wise —
output rows are exclusive so no atomics are needed, paper §3.4).

``plan_and_convert`` is the front half of the pipeline: calibrate/query the
quadratic performance model, solve Eq. 1 for ``r_boundary``, and run
Algorithm 1.

Both execution entry points (``loops_spmm`` for static matrices,
``loops_spmm_values`` for trainable stored values) are differentiable on
the Pallas backends via ``jax.custom_vjp`` — ``dB = Aᵀ·dY`` through the
same kernels on the cached transposed format, ``dA``-at-nonzeros through
the SDD kernels; see ``docs/training.md``.

Batched multi-RHS execution
---------------------------
The dense operand may carry any leading batch dims — ``B`` of shape
``(..., K, N)`` returns ``(..., M, N)`` — and executes as ONE batched
engine call (``kernels/engine.py``): the Pallas grids gain a leading
batch-block axis that reuses A's static panel layout across all slices.
``jax.vmap`` over the operand lowers to the same native batched call via a
``jax.custom_batching.custom_vmap`` rule instead of unrolling one
``pallas_call`` per element; the custom VJP carries the batch through
``dB = Aᵀ·dY`` (batched) and the SDD ``dA`` (summed over the batch — the
stored values are shared).  An empty batch returns correctly-shaped zeros
on every backend.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import engine, ref
from ..kernels.panel_common import default_bn, gather_bn
from ..obs.runtime import span
from ..resilience import fallback as _resilience
from . import partition
from .formats import (CSR, DEFAULT_PANEL_G, HALF_PACKED_ROWS, LoopsFormat,
                      SUBLANE_ROWS, loops_from_csr)
from .perf_model import QuadraticPerfModel

__all__ = ["loops_spmm", "loops_spmm_values", "loops_grid_steps",
           "loops_batched_grid_steps", "plan_and_convert", "SpmmPlan",
           "spmm_csr_baseline", "spmm_dense_baseline"]


@dataclasses.dataclass(frozen=True)
class SpmmPlan:
    """Resolved execution plan for one sparse matrix (paper Fig. 1)."""

    r_boundary: int
    t_vpu: int      # paper: t_neon — workers for the CSR part
    t_mxu: int      # paper: t_sme  — workers for the BCSR part
    br: int         # tile height (cntd / cntf / cnth analogue)
    panel_g: int = DEFAULT_PANEL_G  # panel width (Fig. 2 multi-tile count)


def default_br(dtype) -> int:
    """Paper: B_r = elements per vector register (cntd=2 f64 ... cnth=8 f16 on
    128-bit NEON).  TPU registers are (8, 128) vregs and the MXU contraction
    wants sublane multiples, so fp32 and fp64 both use the 8-sublane extent
    (``formats.SUBLANE_ROWS``); half precision packs 2x per 32-bit lane
    (``formats.HALF_PACKED_ROWS``), mirroring cnth = 2*cntf."""
    dtype = jnp.dtype(dtype)
    if dtype in (jnp.bfloat16, jnp.float16):
        return HALF_PACKED_ROWS
    return SUBLANE_ROWS


def plan_and_convert(csr: CSR, *, total_workers: int = 8,
                     model: QuadraticPerfModel | None = None,
                     tp_vpu: float = 1.0, tp_mxu: float = 4.0,
                     br: int | None = None, panel_g: int | None = None,
                     paper_literal: bool = False,
                     tuner=None, validate: str | None = "strict"
                     ) -> tuple[LoopsFormat, SpmmPlan]:
    """Pick (t_vpu, t_mxu) via the perf model, solve Eq. 1, run Algorithm 1.

    ``tp_vpu``/``tp_mxu`` are per-worker row throughputs; defaults reflect the
    v5e VPU:MXU FLOP ratio for regular rows.  When ``model`` is given, the
    allocation is the model argmax (Eq. 3); otherwise it is proportional to
    the throughputs.

    ``tuner`` — a :class:`repro.tune.Tuner` (or anything with
    ``.tune(csr) -> (fmt, plan)``) — replaces the model-only path entirely:
    the plan comes from the measured, fingerprint-keyed cache, so repeated
    call sites (FFN layers, GCN epochs, serving) never re-derive it.

    ``validate`` gates ingestion validation of ``csr``
    (:mod:`repro.resilience.validate`): ``"strict"`` (default) raises a
    classified :class:`repro.resilience.SparseInputError` on a malformed
    input before Algorithm 1 can index with it; ``"drop"``/``"clip"`` repair
    instead (recording ``validate.repaired`` counters); ``None`` trusts the
    caller (hot inner loops that already validated).

    Runs under the program span ``loops.format``.
    """
    with span("loops.format"):
        if validate is not None:
            from ..resilience.validate import validate_csr
            csr, _ = validate_csr(
                csr, repair=None if validate == "strict" else validate)
        if tuner is not None:
            return tuner.tune(csr)
        br = br or default_br(csr.vals.dtype)
        panel_g = panel_g or DEFAULT_PANEL_G
        if model is not None:
            t_vpu, t_mxu = model.best_allocation(total_workers)
        else:
            t_mxu = max(int(round(total_workers * tp_mxu
                                  / (tp_vpu + tp_mxu))), 1)
            t_vpu = max(total_workers - t_mxu, 1)
        r_b = partition.choose_r_boundary(
            csr.nrows, tp_vpu, tp_mxu, t_vpu, t_mxu, br=br,
            paper_literal=paper_literal)
        fmt = loops_from_csr(csr, r_b, br, panel_g=panel_g)
        return fmt, SpmmPlan(
            r_boundary=r_b, t_vpu=t_vpu, t_mxu=t_mxu, br=br, panel_g=panel_g)


def _loops_execute(fmt: LoopsFormat, b: jax.Array, backend: str, bn,
                   out_dtype, csr_vals=None, bcsr_vals=None,
                   transposed: bool = False) -> jax.Array:
    """Backend dispatch for one hybrid SpMM (no differentiation rule).

    ``b`` may carry leading batch dims (the engine folds them into the
    kernels' native batch grid).  ``csr_vals``/``bcsr_vals`` optionally
    substitute traced live values for the format's host-packed constants
    (learned-sparse-weight layers and the transposed backward pass both need
    this); the structure stays static.  ``transposed`` marks ``fmt`` as a
    custom VJP's Aᵀ, whose kernels carry the ``_t`` names
    (``engine.spmm_kernel_name``).
    """
    has_csr = fmt.r_boundary > 0
    has_bcsr = fmt.r_boundary < fmt.nrows
    pallas = backend != "jnp"   # panel views only materialise for Pallas
    if (has_csr and has_bcsr and pallas
            and fmt.r_boundary % fmt.bcsr_part.br == 0):
        try:
            return engine.loops_spmm_fused(
                fmt, b, backend=backend, bn=bn, out_dtype=out_dtype,
                csr_vals=csr_vals, bcsr_vals=bcsr_vals,
                transposed=transposed)
        except Exception as e:   # noqa: BLE001 - the parts path IS the handler
            # The fused chain (pallas → interpret) is exhausted: degrade to
            # the two-pass parts path below, whose per-part chains reach the
            # jnp oracle.  With the kill switch on, or from pallas on a TPU,
            # the failure propagates for tests/operators to see.
            if not _resilience.degrades(backend):
                raise
            _resilience.note_degraded("engine.fallback", part="fused",
                                      op="spmm",
                                      reason=_resilience.classify(e))
    parts = []
    if has_csr:
        parts.append(engine.csr_spmm(
            fmt.csr_part, b, backend=backend, bn=bn, out_dtype=out_dtype,
            panels=fmt.csr_panels if pallas else None, vals=csr_vals,
            transposed=transposed))
    if has_bcsr:
        parts.append(engine.bcsr_spmm(
            fmt.bcsr_part, b, backend=backend, bn=bn, out_dtype=out_dtype,
            panels=fmt.bcsr_panels if pallas else None, vals=bcsr_vals,
            transposed=transposed))
    if not parts:
        _, out = engine.resolve_dtypes(fmt.csr_part.vals.dtype, out_dtype)
        return jnp.zeros(b.shape[:-2] + (fmt.nrows, b.shape[-1]), out)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-2)


def _index_maybe(x, batched: bool, i):
    return None if x is None else (x[i] if batched else x)


def _execute_engine(fmt: LoopsFormat, b: jax.Array, backend: str, bn,
                    out_dtype, csr_vals=None, bcsr_vals=None,
                    transposed: bool = False) -> jax.Array:
    """Pallas-path executor with a custom batching rule.

    ``jax.vmap`` over the dense operand folds the mapped axis into the
    kernels' native leading batch dimension — one batched ``pallas_call``
    per part — instead of relying on generic per-element unrolling.  A vmap
    over A's *values* has no native batched kernel (the operand panels
    change per element) and falls back to trace-time unrolling, the exact
    pre-batched behaviour.
    """

    @jax.custom_batching.custom_vmap
    def call(b_, cv, bv):
        return _loops_execute(fmt, b_, backend, bn, out_dtype,
                              csr_vals=cv, bcsr_vals=bv,
                              transposed=transposed)

    @call.def_vmap
    def _batch_rule(axis_size, in_batched, b_, cv, bv):
        b_batched = bool(jax.tree.leaves(in_batched[0])[0])
        vals_batched = (any(jax.tree.leaves(in_batched[1]))
                        or any(jax.tree.leaves(in_batched[2])))
        if vals_batched or not b_batched:
            outs = [_execute_engine(
                fmt, _index_maybe(b_, b_batched, i), backend, bn, out_dtype,
                _index_maybe(cv, any(jax.tree.leaves(in_batched[1])), i),
                _index_maybe(bv, any(jax.tree.leaves(in_batched[2])), i),
                transposed)
                for i in range(axis_size)]
            return jnp.stack(outs), True
        lead = b_.shape[:-2]
        out = _execute_engine(fmt, b_.reshape((-1,) + b_.shape[-2:]),
                              backend, bn, out_dtype, cv, bv, transposed)
        return out.reshape(lead + out.shape[-2:]), True

    return call(b, csr_vals, bcsr_vals)


def _backward_db(fmt: LoopsFormat, dy: jax.Array, backend: str, bn,
                 transpose_plan, csr_vals=None, bcsr_vals=None) -> jax.Array:
    """``dB = Aᵀ · dY`` through the same panel kernels on the (cached)
    transposed format — batched per cotangent slice when ``dy`` carries
    batch dims.  The cotangent is cast to the format's value dtype first —
    the backward matmuls honour the forward kernels' precision contract
    (bf16 operands, fp32 accumulation) instead of silently running a wider
    product.  With live values every tile slot of A is a parameter, so Aᵀ
    keeps them all; with A's own values it holds A's stored entries only
    (``LoopsFormat.transposed``)."""
    from .formats import transposed_values
    tl = fmt.transposed(plan=transpose_plan,
                        live_values=csr_vals is not None)
    dy = dy.astype(tl.fmt.csr_part.vals.dtype)
    cv = bv = None
    if csr_vals is not None:
        cv, bv = transposed_values(tl, csr_vals, bcsr_vals)
    if backend == "jnp":
        return _loops_execute(tl.fmt, dy, backend, bn, None,
                              csr_vals=cv, bcsr_vals=bv)
    return _execute_engine(tl.fmt, dy, backend, bn, None, cv, bv,
                           transposed=True)


def loops_spmm(fmt: LoopsFormat, b: jax.Array, *, backend: str | None = None,
               bn: int | None = None, out_dtype=None,
               transpose_plan: "SpmmPlan | None" = None) -> jax.Array:
    """Execute the hybrid SpMM: C = A @ B with A in LOOPS format.

    ``b`` has shape ``(..., K, N)``; the result is ``(..., nrows, N)``.
    Leading batch dims execute as ONE batched engine call — the Pallas
    grids gain a batch axis that reuses A's static panel layout across all
    slices — and ``jax.vmap`` over ``b`` lowers to the same call via a
    custom batching rule.  A batch dim of zero returns correctly-shaped
    zeros on every backend; a rank-1 or K-mismatched ``b`` raises
    ``ValueError``.

    The CSR-part rows land in C[..., :r_boundary, :], the BCSR-part rows in
    C[..., r_boundary:, :]; each output row is written by exactly one kernel
    (paper §3.4 — conflict-free by construction).

    On the Pallas backends a hybrid format executes single-pass
    (:func:`repro.kernels.engine.loops_spmm_fused`): both kernels fill
    disjoint row ranges of ONE buffer through ``input_output_aliases`` +
    offset index_maps, so no ``concatenate`` copy appears in the jaxpr.  The
    two-output + concatenate fallback remains for the jnp reference and for
    boundaries not aligned to the tile height.

    Differentiable end-to-end: on the Pallas backends a ``jax.custom_vjp``
    computes ``dB = Aᵀ · dY`` through the *same* panel kernels on a lazily
    materialised, cached transposed format (``fmt.transposed()``);
    ``transpose_plan`` pins that format's execution plan (otherwise it is
    resolved by ``plan_and_convert`` on Aᵀ's own row statistics).  The jnp
    reference differentiates natively and stays the gradient oracle.  A's
    values are compile-time constants here — for trainable values use
    :func:`loops_spmm_values`.  (Reverse mode only; the VJP itself is not
    further differentiable.)
    """
    backend = engine.resolve_backend(backend)
    _, out_dtype = engine.resolve_dtypes(fmt.csr_part.vals.dtype, out_dtype)
    engine.check_rhs(fmt.ncols, b)
    if fmt.nnz == 0 or any(d == 0 for d in b.shape[:-2]):
        # All-zero matrix (every stored entry is structural padding) or an
        # empty batch: the product is identically zero with the full
        # (..., nrows, N) shape — never a (0, N) stub.
        return jnp.zeros(b.shape[:-2] + (fmt.nrows, b.shape[-1]), out_dtype)
    if backend == "jnp":
        return _loops_execute(fmt, b, backend, bn, out_dtype)

    @jax.custom_vjp
    def run(b_):
        return _execute_engine(fmt, b_, backend, bn, out_dtype)

    def run_fwd(b_):
        return run(b_), None   # A is static: dB needs only the cotangent

    def run_bwd(_, dy):
        db = _backward_db(fmt, dy, backend, bn, transpose_plan)
        return (db.astype(b.dtype),)

    run.defvjp(run_fwd, run_bwd)
    return run(b)


def loops_spmm_values(fmt: LoopsFormat, csr_vals: jax.Array,
                      bcsr_vals: jax.Array, b: jax.Array, *,
                      backend: str | None = None, bn: int | None = None,
                      out_dtype=None,
                      transpose_plan: "SpmmPlan | None" = None) -> jax.Array:
    """Hybrid SpMM with *trainable* stored values: C = A(vals) @ B.

    ``csr_vals`` (nnz,) and ``bcsr_vals`` (ntiles, Br) are live (traced)
    pytree leaves laid out exactly like ``fmt.csr_part.vals`` /
    ``fmt.bcsr_part.tile_vals``; the structure in ``fmt`` stays static.
    This is the learned-sparse-weight entry point
    (:mod:`repro.models.sparse_ffn`).  ``b`` follows the same batched
    ``(..., K, N)`` contract as :func:`loops_spmm`.

    On the Pallas backends a ``jax.custom_vjp`` supplies all three
    cotangents:

      * ``dB = Aᵀ · dY`` — the same panel kernels on the cached transposed
        format, with the live values carried across by the static
        value-linear maps (:func:`repro.core.formats.transposed_values`),
        batched per cotangent slice;
      * ``dA`` at stored coordinates — the sampled dense-dense kernels
        (:func:`repro.kernels.engine.loops_sdd`), never materialising
        ``dY @ Bᵀ``, **summed over batch dims** (the values are shared
        across the batch).

    The jnp reference differentiates natively (gradient oracle).
    """
    backend = engine.resolve_backend(backend)
    _, out_dtype = engine.resolve_dtypes(jnp.dtype(csr_vals.dtype), out_dtype)
    engine.check_rhs(fmt.ncols, b)
    if any(d == 0 for d in b.shape[:-2]):
        return jnp.zeros(b.shape[:-2] + (fmt.nrows, b.shape[-1]), out_dtype)
    if backend == "jnp":
        return _loops_execute(fmt, b, backend, bn, out_dtype,
                              csr_vals=csr_vals, bcsr_vals=bcsr_vals)

    @jax.custom_vjp
    def run(cv, bv, b_):
        return _execute_engine(fmt, b_, backend, bn, out_dtype, cv, bv)

    def run_fwd(cv, bv, b_):
        return run(cv, bv, b_), (cv, bv, b_)

    def run_bwd(res, dy):
        cv, bv, b_ = res
        db = _backward_db(fmt, dy, backend, bn, transpose_plan,
                          csr_vals=cv, bcsr_vals=bv)
        d_cv, d_bv = engine.loops_sdd(fmt, dy, b_, backend=backend, bn=bn)
        return (d_cv.astype(cv.dtype), d_bv.astype(bv.dtype),
                db.astype(b_.dtype))

    run.defvjp(run_fwd, run_bwd)
    return run(csr_vals, bcsr_vals, b)


def loops_grid_steps(fmt: LoopsFormat, n_cols: int,
                     bn: int | None = None) -> int:
    """Total Pallas grid steps to execute ``fmt`` against an (K, n_cols)
    operand — the hardware-independent cost proxy the benchmarks track.

    The CSR grid walks panels, not nonzeros, so its count drops from
    ``nnz_csr * col_blocks`` at G=1 towards a ``~G``-fold reduction
    (padding at row boundaries is the gap from the ideal), and its SMEM
    chunking adds no step.  The BCSR grid walks window steps, one per
    128-lane window of a block row (``PanelBCSR.steps``), once per column
    block of ``panel_common.gather_bn``.  A part the executor skips
    contributes nothing.
    """
    bn = bn or default_bn(n_cols)
    steps = 0
    # The empty BCSR part is not inherently zero-count
    # (``bcsr_from_csr_rows`` keeps >= 1 structural pad tile even for zero
    # rows), so a skipped part is read off the boundary.
    if fmt.r_boundary > 0:
        steps += fmt.csr_panels.npanels * -(-n_cols // bn)
    if fmt.r_boundary < fmt.nrows:
        steps += (fmt.bcsr_panels.steps[0].shape[0]
                  * (n_cols // gather_bn(n_cols, bn)))
    return steps


def loops_batched_grid_steps(fmt: LoopsFormat, batch, n_cols: int,
                             bn: int | None = None) -> int:
    """Grid steps of ONE native batched engine call against a
    ``(*batch, K, n_cols)`` operand.

    The batched grids process ``engine.batch_block`` slices per step (A's
    panel loaded once, applied to every slice), so the count grows by
    ``ceil(batch / bz)`` — at ``batch ≤ MAX_BATCH_BLOCK`` it EQUALS the
    single-element count, while a per-element Python loop pays
    ``batch × loops_grid_steps`` (plus a dispatch per element).
    """
    b = int(np.prod(batch)) if np.ndim(batch) else int(batch)
    if b == 0:
        return 0
    bp = engine.padded_batch(b)   # awkward sizes zero-pad into wide blocks
    return (bp // engine.batch_block(bp)) * loops_grid_steps(fmt, n_cols, bn)


# ---------------------------------------------------------------------------
# Baselines the paper compares against (implemented, per assignment scope)
# ---------------------------------------------------------------------------

def spmm_csr_baseline(csr: CSR, b: jax.Array, out_dtype=None) -> jax.Array:
    """TACO-style row-wise CSR schedule (pure XLA segment-sum lowering)."""
    return ref.csr_spmm_ref(jnp.asarray(csr.row_ids), jnp.asarray(csr.col_idx),
                            jnp.asarray(csr.vals), b, csr.nrows,
                            out_dtype=out_dtype)


def spmm_dense_baseline(a_dense: np.ndarray, b: jax.Array,
                        out_dtype=None) -> jax.Array:
    """Armadillo-style dense GEMM on the densified operand."""
    return ref.dense_spmm(jnp.asarray(a_dense), b, out_dtype=out_dtype)
