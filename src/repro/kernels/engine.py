"""Batched SpMM execution engine — the one dispatch layer over the kernels.

Historically ``kernels/ops.py`` grew six near-duplicate entry points
(``csr_spmm``, ``bcsr_spmm``, ``loops_spmm_fused``, ``loops_sdd`` plus the
``vals=``-override variants threaded through each), every one re-implementing
the same three decisions: which backend executes, how half precision promotes,
and how traced values ride the static panel layout.  This module collapses
them into a single engine:

  * **one registry** — kernel implementations are registered under a
    ``(part, op)`` key (``part`` ∈ {"csr", "bcsr"}, ``op`` ∈ {"spmm", "sdd"})
    with an implementation flavour per backend class (``panels`` — the G-wide
    Pallas kernels, ``flat`` — the G=1 wrappers, ``ref`` — the jnp oracles).
    The kernel home modules register themselves on import
    (:func:`register_kernel`); dispatch resolves through :func:`get_kernel`.
  * **one precision-promotion path** — :func:`acc_dtype_for` /
    :func:`resolve_dtypes` are defined here and re-exported by ``ref.py``
    (the ``{bf16, f16} → fp32-accumulate`` contract lives in exactly one
    place);
  * **one backend-pick path** — :func:`resolve_backend`;
  * **one panel-vals scatter path** — :func:`panel_values` (traced live
    values into the static panel layout);
  * **one shape contract** — every entry point accepts a dense operand of
    shape ``(..., K, N)``.  Leading dimensions are flattened into the
    kernels' native batch grid dimension (:func:`flatten_batch`); rank or
    K mismatches raise a clear :class:`ValueError` (:func:`check_rhs`)
    instead of an opaque Pallas shape error, and an empty batch returns
    correctly-shaped zeros on every backend.

Batched execution (ROADMAP: "heavy traffic, many scenarios")
------------------------------------------------------------
The Pallas kernels take a leading batch grid dimension and block it by
:func:`batch_block` (``bz`` slices per grid step, VMEM-bounded): one grid
step loads A's panel once and applies it to ``bz`` batch slices of B, so the
grid-step count grows by ``ceil(batch / bz)`` — NOT by ``batch`` — relative
to the unbatched call.  A per-element Python loop pays ``batch ×`` steps and
``batch ×`` dispatches; the native batched call pays one dispatch and, for
``batch ≤ MAX_BATCH_BLOCK``, the *same* step count as a single-element call.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ..resilience import fallback as _fallback

__all__ = [
    "acc_dtype_for", "resolve_dtypes", "default_backend", "resolve_backend",
    "check_rhs", "flatten_batch", "unflatten_batch", "batch_block",
    "padded_batch", "MAX_BATCH_BLOCK", "register_kernel", "get_kernel",
    "panel_values", "csr_spmm", "bcsr_spmm", "loops_spmm_fused", "loops_sdd",
    "set_tracer", "get_tracer",
]

# Max batch slices processed per kernel grid step.  8 slices × bn=512 lanes
# × 4 bytes ≈ 16 KiB per gathered B row — G of those plus the accumulator
# stay comfortably inside VMEM while buying up to an 8× grid-step reduction
# over per-element execution.
MAX_BATCH_BLOCK = 8


# ---------------------------------------------------------------------------
# precision promotion (the ONE copy; ref.py re-exports for compatibility)
# ---------------------------------------------------------------------------

def acc_dtype_for(dtype) -> jnp.dtype:
    """fp32 accumulation for half precision (the paper's f16f16f32 contract,
    realised on TPU as the native bf16xbf16->f32 MXU mode); otherwise the
    input precision.  Canonicalised so f64 degrades to f32 when x64 is off."""
    dtype = jax.dtypes.canonicalize_dtype(jnp.dtype(dtype))
    if dtype in (jnp.bfloat16, jnp.float16):
        return jnp.dtype(jnp.float32)
    return dtype


def resolve_dtypes(value_dtype, out_dtype) -> Tuple[jnp.dtype, jnp.dtype]:
    """``(accumulation dtype, output dtype)`` for stored values of
    ``value_dtype`` — the promotion decision every kernel and dispatch layer
    shares.  ``out_dtype`` (when given) overrides the output only; the
    accumulator always follows the promotion contract."""
    acc = acc_dtype_for(value_dtype)
    return acc, (jnp.dtype(out_dtype) if out_dtype is not None else acc)


# ---------------------------------------------------------------------------
# backend pick (the ONE copy)
# ---------------------------------------------------------------------------

def default_backend() -> str:
    """'pallas' on real TPUs, 'interpret' elsewhere (CPU validation), matching
    the assignment contract: TPU is the target, interpret mode the oracle
    runner."""
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def resolve_backend(backend: str | None) -> str:
    """Normalise a caller's backend choice (``None`` → platform default)."""
    backend = backend or default_backend()
    if backend not in ("pallas", "interpret", "jnp"):
        raise ValueError(f"unknown backend {backend!r}; expected 'pallas', "
                         "'interpret' or 'jnp'")
    return backend


# ---------------------------------------------------------------------------
# the (..., K, N) shape contract
# ---------------------------------------------------------------------------

def check_rhs(ncols: int, b, *, what: str = "B") -> None:
    """Validate the dense operand's shape contract ``(..., K, N)`` against
    A's column count, raising a clear ValueError instead of letting a rank
    or contraction mismatch surface as an opaque Pallas shape error."""
    if b.ndim < 2:
        raise ValueError(
            f"dense operand {what} must have shape (..., K, N); got rank "
            f"{b.ndim} with shape {tuple(b.shape)}")
    if b.shape[-2] != ncols:
        raise ValueError(
            f"dense operand {what} has K={b.shape[-2]} rows but A has "
            f"ncols={ncols}; shapes must contract as (M, K) @ (..., K, N)")


def flatten_batch(b: jax.Array) -> Tuple[jax.Array, Tuple[int, ...]]:
    """``(..., K, N)`` → ``((B, K, N) or (K, N), leading batch shape)``.

    Rank ≤ 3 passes through untouched (no reshape in the jaxpr); higher
    ranks collapse every leading dim into the kernels' single native batch
    grid dimension."""
    if b.ndim <= 3:
        return b, b.shape[:-2]
    batch = b.shape[:-2]
    return b.reshape((-1,) + b.shape[-2:]), batch


def unflatten_batch(out: jax.Array, batch: Tuple[int, ...]) -> jax.Array:
    """Inverse of :func:`flatten_batch` on the kernel output's leading dim."""
    if out.ndim == 2 or len(batch) == 1:
        return out
    return out.reshape(batch + out.shape[-2:])


def batch_block(batch: int) -> int:
    """Batch slices per grid step: the largest divisor of ``batch`` that is
    ≤ :data:`MAX_BATCH_BLOCK` (the grid needs ``batch % bz == 0``).  The
    engine entry points first round the flat batch up to
    :func:`padded_batch`, so an awkward size (a prime beyond the cap) is
    zero-padded into a wide block instead of degrading to per-slice
    steps."""
    if batch <= 0:
        return 1
    for d in range(min(batch, MAX_BATCH_BLOCK), 0, -1):
        if batch % d == 0:
            return d
    return 1


def padded_batch(batch: int) -> int:
    """Flat batch size after zero-padding to the step-minimising block.

    Two candidates per size: keep ``batch`` and block by its largest
    divisor ≤ :data:`MAX_BATCH_BLOCK` (no padded compute), or round up to a
    multiple of ``min(batch, MAX_BATCH_BLOCK)`` (full-width blocks, some
    zero slices).  Whichever yields fewer grid-step groups wins; ties keep
    the unpadded batch.  E.g. 12 stays 12 (bz=6, 2 groups), 11 pads to 16
    (bz=8, 2 groups instead of 11).  ``batch_block`` of the returned size
    recovers the chosen block width."""
    if batch <= 0:
        return batch
    bz_pad = min(batch, MAX_BATCH_BLOCK)
    groups_pad = -(-batch // bz_pad)
    if groups_pad < batch // batch_block(batch):
        return groups_pad * bz_pad
    return batch


def _pad_flat_batch(x: jax.Array) -> jax.Array:
    """Zero-pad a flat-batched ``(B, ..., N)`` operand to ``padded_batch(B)``
    slices (rank-2 operands pass through).  Padding slices are all-zero, so
    they contribute zero rows (trimmed by the caller) to a forward product
    and zero terms to the SDD batch sum."""
    if x.ndim == 2:
        return x
    nb = x.shape[0]
    target = padded_batch(nb)
    if target == nb:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((target - nb,) + x.shape[1:], x.dtype)])


def _empty_batch(b) -> bool:
    return any(d == 0 for d in b.shape[:-2])


# ---------------------------------------------------------------------------
# dispatch tracer (repro.perf.trace attaches here)
# ---------------------------------------------------------------------------

# A single process-wide tracer hook.  The entry points below call
# ``_note(part, op, ...)`` with STRUCTURAL dispatch facts (which kernel
# flavour ran, how many panels/nonzeros the grid walks, the flat batch and
# column extents).  The calls fire at trace time — under ``jax.jit`` that is
# once per compilation, not once per execution — so a tracer must never
# record wall-clock here; timing belongs at blocking call sites
# (``repro.perf.trace.TraceRecorder``'s timed wrappers).
_TRACER = None


def set_tracer(tracer):
    """Install ``tracer`` (an object with ``on_dispatch(**fields)``, or
    ``None`` to detach) as the engine's dispatch hook; returns the previous
    tracer so callers can restore it."""
    global _TRACER
    prev, _TRACER = _TRACER, tracer
    return prev


def get_tracer():
    return _TRACER


def _note(part: str, op: str, **fields) -> None:
    if _TRACER is None:
        return
    if "steps" not in fields:
        # Grid steps this dispatch walks: panels × batch groups for the
        # Pallas kernels (the flat batch is already padded to a multiple of
        # its block), or plain units for the gather-based references.
        units = int(fields.get("units", 0))
        nb = int(fields.get("batch", 1))
        if fields.get("impl") == "ref":
            fields["steps"] = units
        else:
            fields["steps"] = units * max(-(-nb // batch_block(nb)), 1)
    _TRACER.on_dispatch(part=part, op=op, **fields)


def _panel_note_fields(*, part: str, depth: int, npanels: int, nb: int,
                       n: int, bn: int | None, g: int, br: int,
                       b_dtype, value_dtype) -> dict:
    """Pipeline observability fields for a G-wide panel dispatch.

    ``steps`` — grid steps including the ``depth - 1`` fill/drain ramp of
    each ``pallas_call`` (one per SMEM-sized panel chunk)
    (× batch groups, matching ``_note``'s default accounting at depth 1);
    ``scratch_bytes`` — VMEM scratch footprint (accumulator + the packed
    ping-pong B-panel buffer, which stays in B's storage dtype);
    ``prefetch_overlap`` — fraction of grid steps whose B-row gathers
    overlap a contraction (0.0 for the serial depth-1 kernels).
    """
    from .panel_common import CSR_WORDS, default_bn, panel_calls
    groups = max(-(-nb // batch_block(nb)), 1)
    bz = batch_block(nb)
    bn_eff = bn or default_bn(n)
    acc = acc_dtype_for(value_dtype)
    acc_rows = br if part == "bcsr" else 1
    scratch = bz * acc_rows * bn_eff * jnp.dtype(acc).itemsize
    b_item = jnp.dtype(b_dtype).itemsize
    if part == "bcsr":
        bpan_elems = max(depth, 1) * g * bn_eff * bz
    else:   # depth-1 CSR reads gathered B rows directly (no staging buffer)
        bpan_elems = depth * g * bn_eff * bz if depth > 1 else 0
    # Each SMEM-sized chunk is its own launch with its own ramp.
    calls = panel_calls(npanels, g, CSR_WORDS if part == "csr" else 1)
    steps = npanels + calls * (depth - 1)
    overlap = (max(npanels - calls, 0) / steps) if depth > 1 else 0.0
    return {"pipeline_depth": depth,
            "steps": steps * groups,
            "scratch_bytes": int(scratch + bpan_elems * b_item),
            "prefetch_overlap": float(overlap)}


# ---------------------------------------------------------------------------
# kernel registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[Tuple[str, str], Dict[str, Callable]] = {}
_POPULATED = False


def register_kernel(part: str, op: str, impl: str, fn: Callable) -> Callable:
    """Register a kernel implementation under ``(part, op)`` with flavour
    ``impl`` ∈ {"panels", "flat", "ref"}.  Called by the kernel home modules
    at import time; idempotent (last registration wins)."""
    _REGISTRY.setdefault((part, op), {})[impl] = fn
    return fn


def get_kernel(part: str, op: str, impl: str = "panels") -> Callable:
    """Resolve a registered kernel, importing the kernel homes on first use
    (registration is a side effect of importing them — lazy so this module
    never holds a static import cycle with the kernels it dispatches)."""
    global _POPULATED
    if not _POPULATED:
        from . import bcsr_spmm, csr_spmm, ref, spmm_sdd  # noqa: F401
        _POPULATED = True
    try:
        return _REGISTRY[(part, op)][impl]
    except KeyError:
        raise KeyError(f"no kernel registered for part={part!r} op={op!r} "
                       f"impl={impl!r}; known: {sorted(_REGISTRY)}") from None


# ---------------------------------------------------------------------------
# panel-value scatter (the ONE copy)
# ---------------------------------------------------------------------------

def panel_values(panels, vals):
    """Panel values in the kernels' lane layout (flat ``(P·G,)`` for CSR
    panels, the ``(Br, L)`` window for BCSR panels): the host-packed
    constants, or the traced scatter of ``vals`` into the panels'
    ``src_panel``/``src_lane`` layout (live parameters of a learned-sparse
    layer ride the static structure)."""
    from .panel_common import values_window
    if vals is None:
        return jnp.asarray(panels.vals_window if panels.panel_vals.ndim == 3
                           else panels.lane_vals)
    live = panels.scatter_values(jnp.asarray(vals))
    return values_window(live) if live.ndim == 3 else live.reshape(-1)


# ---------------------------------------------------------------------------
# dispatch entry points
# ---------------------------------------------------------------------------

def csr_spmm(csr, b: jax.Array, *, backend: str | None = None,
             bn: int | None = None, out_dtype=None, panels=None,
             vals=None, pipeline_depth: int = 1) -> jax.Array:
    """SpMM of a ``repro.core.formats.CSR`` against dense ``b`` (..., K, N).

    ``panels`` — a ``repro.core.formats.PanelCSR`` view of the same matrix —
    routes the Pallas backends through the G-wide panel kernel.  ``vals`` —
    optional traced (nnz,) values replacing ``csr.vals``.  Leading batch
    dims of ``b`` execute as the kernels' native batch grid dimension.
    ``pipeline_depth=2`` double-buffers the B-row gathers on the panel
    kernel (ignored by the flat and jnp paths).
    """
    backend = resolve_backend(backend)
    check_rhs(csr.ncols, b)
    v = jnp.asarray(csr.vals) if vals is None else jnp.asarray(vals)
    if _empty_batch(b):
        _, out = resolve_dtypes(v.dtype, out_dtype)
        return jnp.zeros(b.shape[:-2] + (csr.nrows, b.shape[-1]), out)

    def attempt(bk: str) -> jax.Array:
        if bk == "jnp":
            _note("csr", "spmm", backend=bk, impl="ref", units=csr.nnz,
                  batch=1, n=int(b.shape[-1]))
            return get_kernel("csr", "spmm", "ref")(
                jnp.asarray(csr.row_ids), jnp.asarray(csr.col_idx), v, b,
                csr.nrows, out_dtype=out_dtype)
        interpret = bk == "interpret"
        b3, batch = flatten_batch(b)
        b3p = _pad_flat_batch(b3)
        nb = int(b3p.shape[0]) if b3p.ndim == 3 else 1
        depth = int(pipeline_depth) if panels is not None else 1
        extra = _panel_note_fields(
            part="csr", depth=depth, npanels=int(panels.npanels), nb=nb,
            n=int(b.shape[-1]), bn=bn, g=int(panels.g), br=1,
            b_dtype=b.dtype, value_dtype=v.dtype) if panels is not None else {}
        _note("csr", "spmm", backend=bk,
              impl="panels" if panels is not None else "flat",
              units=int(panels.npanels) if panels is not None
              else int(csr.nnz),
              batch=nb, n=int(b.shape[-1]), **extra)
        if panels is not None:
            out = get_kernel("csr", "spmm", "panels")(
                jnp.asarray(panels.panel_rows), jnp.asarray(panels.lane_cols),
                panel_values(panels, vals), b3p, g=panels.g,
                nrows=csr.nrows, bn=bn, out_dtype=out_dtype,
                interpret=interpret, pipeline_depth=depth)
        else:
            out = get_kernel("csr", "spmm", "flat")(
                jnp.asarray(csr.row_ids), jnp.asarray(csr.col_idx), v, b3p,
                nrows=csr.nrows, bn=bn, out_dtype=out_dtype,
                interpret=interpret)
        if b3p is not b3:
            out = out[:b3.shape[0]]
        return unflatten_batch(out, batch)

    return _fallback.run_chain("csr", "spmm", backend, attempt)


def bcsr_spmm(bcsr, b: jax.Array, *, backend: str | None = None,
              bn: int | None = None, out_dtype=None, panels=None,
              vals=None, pipeline_depth: int = 1) -> jax.Array:
    """SpMM of a ``repro.core.formats.VectorBCSR`` against dense ``b``.

    Returns the *logical* (..., bcsr.nrows, N) result (padding rows
    trimmed).  ``panels`` — a ``repro.core.formats.PanelBCSR`` — routes the
    Pallas backends through the G-wide kernel; ``vals`` — optional traced
    (ntiles, Br) tile values replacing ``bcsr.tile_vals``.
    """
    backend = resolve_backend(backend)
    check_rhs(bcsr.ncols, b)
    v = jnp.asarray(bcsr.tile_vals) if vals is None else jnp.asarray(vals)
    if _empty_batch(b):
        _, out = resolve_dtypes(v.dtype, out_dtype)
        return jnp.zeros(b.shape[:-2] + (bcsr.nrows, b.shape[-1]), out)

    def attempt(bk: str) -> jax.Array:
        if bk == "jnp":
            _note("bcsr", "spmm", backend=bk, impl="ref",
                  units=int(bcsr.ntiles), batch=1, n=int(b.shape[-1]))
            padded = get_kernel("bcsr", "spmm", "ref")(
                jnp.asarray(bcsr.tile_rows), jnp.asarray(bcsr.tile_cols), v,
                b, bcsr.nblocks, out_dtype=out_dtype)
            return padded[..., :bcsr.nrows, :]
        interpret = bk == "interpret"
        b3, batch = flatten_batch(b)
        b3p = _pad_flat_batch(b3)
        nb = int(b3p.shape[0]) if b3p.ndim == 3 else 1
        depth = int(pipeline_depth) if panels is not None else 1
        extra = _panel_note_fields(
            part="bcsr", depth=depth, npanels=int(panels.npanels), nb=nb,
            n=int(b.shape[-1]), bn=bn, g=int(panels.g), br=int(panels.br),
            b_dtype=b.dtype, value_dtype=v.dtype) if panels is not None else {}
        _note("bcsr", "spmm", backend=bk,
              impl="panels" if panels is not None else "flat",
              units=int(panels.npanels) if panels is not None
              else int(bcsr.ntiles),
              batch=nb, n=int(b.shape[-1]), **extra)
        if panels is not None:
            padded = get_kernel("bcsr", "spmm", "panels")(
                jnp.asarray(panels.panel_rows), jnp.asarray(panels.lane_cols),
                panel_values(panels, vals), b3p, g=panels.g,
                nblocks=panels.nblocks, bn=bn, out_dtype=out_dtype,
                interpret=interpret, pipeline_depth=depth)
        else:
            padded = get_kernel("bcsr", "spmm", "flat")(
                jnp.asarray(bcsr.tile_rows), jnp.asarray(bcsr.tile_cols), v,
                b3p, nblocks=bcsr.nblocks, bn=bn, out_dtype=out_dtype,
                interpret=interpret)
        if b3p is not b3:
            padded = padded[:b3.shape[0]]
        return unflatten_batch(padded[..., :bcsr.nrows, :], batch)

    return _fallback.run_chain("bcsr", "spmm", backend, attempt)


def loops_spmm_fused(fmt, b: jax.Array, *, backend: str | None = None,
                     bn: int | None = None, out_dtype=None,
                     csr_vals=None, bcsr_vals=None,
                     pipeline_depth: int = 1) -> jax.Array:
    """Single-pass hybrid SpMM into ONE preallocated output.

    Pass 1 (CSR panels) allocates the full ``(..., r_boundary + nblocks*Br,
    N)`` buffer and fills rows ``[0, r_boundary)``; pass 2 (BCSR panels)
    takes that buffer as an aliased carry and fills the remaining blocks at
    ``row_block_offset = r_boundary // Br`` — the pallas-level
    ``input_output_aliases`` keeps pass 1's rows intact with zero copies,
    per batch element.  No ``concatenate`` appears in the jaxpr; the only
    residual movement is the final row trim when the last block-row
    overhangs.

    Requires both parts non-empty, panel views present, and ``r_boundary``
    aligned to ``Br`` (planners guarantee the alignment; ``loops_spmm``
    falls back to the two-output path otherwise).  ``csr_vals``/``bcsr_vals``
    optionally substitute traced live values for the host-packed constants.
    """
    backend = resolve_backend(backend)
    if backend == "jnp":
        raise ValueError("fused path is Pallas-only; use backend="
                         "'interpret' or 'pallas'")
    check_rhs(fmt.ncols, b)
    cp, bp = fmt.csr_panels, fmt.bcsr_panels
    r_b, br = fmt.r_boundary, bp.br
    if r_b % br or not 0 < r_b < fmt.nrows:
        raise ValueError(f"fused path needs 0 < r_boundary < nrows with "
                         f"r_boundary % Br == 0, got {r_b} (Br={br})")
    if _empty_batch(b):
        _, out = resolve_dtypes(fmt.csr_part.vals.dtype, out_dtype)
        return jnp.zeros(b.shape[:-2] + (fmt.nrows, b.shape[-1]), out)

    def attempt(bk: str) -> jax.Array:
        interpret = bk == "interpret"
        b3, batch = flatten_batch(b)
        b3p = _pad_flat_batch(b3)
        nb = int(b3p.shape[0]) if b3p.ndim == 3 else 1
        depth = int(pipeline_depth)
        vdt = fmt.csr_part.vals.dtype
        _note("csr", "spmm", backend=bk, impl="panels", fused=True,
              units=int(cp.npanels), batch=nb, n=int(b.shape[-1]),
              **_panel_note_fields(
                  part="csr", depth=depth, npanels=int(cp.npanels), nb=nb,
                  n=int(b.shape[-1]), bn=bn, g=int(cp.g), br=1,
                  b_dtype=b.dtype, value_dtype=vdt))
        _note("bcsr", "spmm", backend=bk, impl="panels", fused=True,
              units=int(bp.npanels), batch=nb, n=int(b.shape[-1]),
              **_panel_note_fields(
                  part="bcsr", depth=depth, npanels=int(bp.npanels), nb=nb,
                  n=int(b.shape[-1]), bn=bn, g=int(bp.g), br=int(bp.br),
                  b_dtype=b.dtype, value_dtype=vdt))
        r_pad = r_b + bp.nblocks * br
        out = get_kernel("csr", "spmm", "panels")(
            jnp.asarray(cp.panel_rows), jnp.asarray(cp.lane_cols),
            panel_values(cp, csr_vals), b3p, g=cp.g, nrows=r_b,
            out_rows=r_pad, bn=bn, out_dtype=out_dtype,
            interpret=interpret, pipeline_depth=depth)
        out = get_kernel("bcsr", "spmm", "panels")(
            jnp.asarray(bp.panel_rows), jnp.asarray(bp.lane_cols),
            panel_values(bp, bcsr_vals), b3p, g=bp.g, nblocks=bp.nblocks,
            row_block_offset=r_b // br, out_rows=r_pad, bn=bn,
            out_dtype=out_dtype, interpret=interpret, carry=out,
            pipeline_depth=depth)
        if b3p is not b3:
            out = out[:b3.shape[0]]
        if r_pad != fmt.nrows:
            out = out[..., :fmt.nrows, :]
        return unflatten_batch(out, batch)

    # The fused chain ends at interpret (no jnp single-pass exists);
    # core.spmm._loops_execute catches an exhausted chain and degrades to
    # the two-pass parts path, whose per-part chains reach the oracle.
    return _fallback.run_chain("fused", "spmm", backend, attempt)


def loops_sdd(fmt, dy: jax.Array, b: jax.Array, *,
              backend: str | None = None, bn: int | None = None,
              pipeline_depth: int = 1):
    """Gradient of ``Y = A @ B`` w.r.t. A's stored values (both parts).

    Args:
      fmt: the forward :class:`~repro.core.formats.LoopsFormat` (structure
        source — its value arrays are not read).
      dy:  (..., nrows, N) output cotangent.
      b:   (..., K, N) the forward dense operand (leading dims must match
        ``dy``'s).
    Returns:
      ``(d_csr_vals, d_bcsr_tile_vals)`` with shapes ``(nnz_csr,)`` and
      ``(ntiles, Br)`` in the accumulation dtype — **summed over any batch
      dims** (the stored values are shared across the batch, so their
      cotangent is the batch sum).  Pallas backends run the G-wide SDD
      kernels with the batch folded into the grid; the jnp backend runs the
      gather-based references — both sample ``dY @ Bᵀ`` only at stored
      coordinates.

    Under ``jax.vmap`` a custom batching rule unrolls per mapped element
    (each element then carries its *own* value cotangent — vmap semantics,
    not the shared-values batch sum).
    """
    backend = resolve_backend(backend)
    check_rhs(fmt.ncols, b)
    if dy.shape[:-2] != b.shape[:-2]:
        raise ValueError(f"dy batch dims {dy.shape[:-2]} do not match b "
                         f"batch dims {b.shape[:-2]}")
    if backend == "jnp" or _empty_batch(b):
        return _loops_sdd_impl(fmt, dy, b, backend, bn, pipeline_depth)

    def attempt(bk: str):
        if bk == "jnp":
            return _loops_sdd_impl(fmt, dy, b, bk, bn, pipeline_depth)

        @jax.custom_batching.custom_vmap
        def call(dy_, b_):
            return _loops_sdd_impl(fmt, dy_, b_, bk, bn, pipeline_depth)

        @call.def_vmap
        def _vmap_rule(axis_size, in_batched, dy_, b_):
            dy_b, b_b = in_batched
            outs = [loops_sdd(fmt, dy_[i] if dy_b else dy_,
                              b_[i] if b_b else b_, backend=bk, bn=bn,
                              pipeline_depth=pipeline_depth)
                    for i in range(axis_size)]
            return (jnp.stack([o[0] for o in outs]),
                    jnp.stack([o[1] for o in outs])), (True, True)

        return call(dy, b)

    return _fallback.run_chain("loops", "sdd", backend, attempt)


def _loops_sdd_impl(fmt, dy, b, backend, bn, pipeline_depth=1):
    """The actual SDD dispatch (batch summed); see :func:`loops_sdd`."""
    csr, bc = fmt.csr_part, fmt.bcsr_part
    nblocks, br = bc.nblocks, bc.br
    acc, _ = resolve_dtypes(b.dtype, None)
    has_csr = fmt.r_boundary > 0
    has_bcsr = fmt.r_boundary < fmt.nrows
    if _empty_batch(b):
        return (jnp.zeros((csr.nnz,), acc),
                jnp.zeros(bc.tile_vals.shape, acc))
    # BCSR region of the cotangent, zero-padded to whole blocks: rows the
    # forward pass trims carry exactly zero gradient.
    dy_b = dy[..., fmt.r_boundary:, :]
    pad = nblocks * br - dy_b.shape[-2]
    if pad:
        widths = [(0, 0)] * (dy_b.ndim - 2) + [(0, pad), (0, 0)]
        dy_pad = jnp.pad(dy_b, widths)
    else:
        dy_pad = dy_b
    if backend == "jnp":
        d_csr = get_kernel("csr", "sdd", "ref")(
            jnp.asarray(csr.row_ids), jnp.asarray(csr.col_idx), dy, b) \
            if has_csr else jnp.zeros((csr.nnz,), acc)
        d_bcsr = get_kernel("bcsr", "sdd", "ref")(
            jnp.asarray(bc.tile_rows), jnp.asarray(bc.tile_cols), dy_pad, b,
            nblocks) \
            if has_bcsr else jnp.zeros(bc.tile_vals.shape, acc)
        return d_csr, d_bcsr
    interpret = backend == "interpret"
    # Zero pad-slices contribute zero terms to the batch sum, so the SDD
    # outputs need no trim.
    b3 = _pad_flat_batch(flatten_batch(b)[0])
    dy3 = _pad_flat_batch(flatten_batch(dy)[0])
    dy_pad3 = _pad_flat_batch(flatten_batch(dy_pad)[0])
    cp, bp = fmt.csr_panels, fmt.bcsr_panels
    nb = int(b3.shape[0]) if b3.ndim == 3 else 1
    depth = int(pipeline_depth)
    if has_csr:
        _note("csr", "sdd", backend=backend, impl="panels",
              units=int(cp.npanels), batch=nb, n=int(b.shape[-1]),
              pipeline_depth=depth)
    if has_bcsr:
        _note("bcsr", "sdd", backend=backend, impl="panels",
              units=int(bp.npanels), batch=nb, n=int(b.shape[-1]),
              pipeline_depth=depth)
    if has_csr:
        d_csr = cp.gather_values(get_kernel("csr", "sdd", "panels")(
            jnp.asarray(cp.panel_rows), jnp.asarray(cp.lane_cols), dy3, b3,
            g=cp.g, bn=bn, interpret=interpret, pipeline_depth=depth))
    else:
        d_csr = jnp.zeros((csr.nnz,), acc)
    if has_bcsr:
        d_bcsr = bp.gather_values(get_kernel("bcsr", "sdd", "panels")(
            jnp.asarray(bp.panel_rows), jnp.asarray(bp.lane_cols), dy_pad3,
            b3, g=bp.g, br=br, bn=bn, interpret=interpret,
            pipeline_depth=depth))
    else:
        d_bcsr = jnp.zeros(bc.tile_vals.shape, acc)
    return d_csr, d_bcsr
