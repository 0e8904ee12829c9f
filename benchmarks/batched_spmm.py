"""Batched multi-RHS SpMM: per-element loop vs vmap-unrolled vs native.

The paper keeps the matrix engine saturated by feeding wide dense panels;
the batched execution engine (``kernels/engine.py``) extends that to whole
batches of right-hand sides — one engine call with a leading batch grid
dimension, A's static panel layout loaded once per grid step and applied to
every batch slice.  This suite measures the three ways a batched workload
(GNN minibatches, sparse-FFN activations, concurrent serving requests) can
execute the same math:

  * **loop**       — the pre-engine strategy: a Python loop over batch
                     elements, one jitted ``loops_spmm`` dispatch each
                     (``batch ×`` grid steps AND ``batch ×`` dispatches);
  * **vmap**       — trace-time unrolled stack of per-element calls under
                     one jit, mimicking what ``jax.vmap`` lowered to before
                     the custom batching rule (``batch ×`` grid steps, one
                     dispatch);
  * **native**     — ONE batched engine call on the ``(batch, K, N)``
                     operand (``ceil(batch / bz) ×`` the single-element
                     grid steps — equal to them for ``batch ≤ 8``).

Both forward and forward+backward (``grad`` w.r.t. the operand) are timed,
and the grid-step cost proxy (``loops_batched_grid_steps``) is recorded —
the hardware-independent column the acceptance tracking pins: native
batched must beat the per-element loop on grid steps from batch ≥ 4.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import csr_from_dense, loops_spmm, plan_and_convert
from repro.core.spmm import loops_batched_grid_steps, loops_grid_steps

from ._util import bench_rng, csv_row, time_fn

N = 32                       # dense columns per RHS (paper fixes N=32)
BATCHES = [1, 4, 8]
SMOKE_BATCHES = [4]
BACKEND = "interpret"        # the real (Pallas) kernel path off-TPU


def _strategies(fmt, batch):
    """(name -> jitted fwd fn of the (batch, K, N) operand); 'loop' is a
    Python loop of per-element dispatches and is returned separately."""
    def native(b3):
        return loops_spmm(fmt, b3, backend=BACKEND)

    def unrolled(b3):
        return jnp.stack([loops_spmm(fmt, b3[i], backend=BACKEND)
                          for i in range(batch)])

    return {"native": jax.jit(native), "vmap": jax.jit(unrolled)}


def main(out=print, record=None, smoke: bool = False):
    scale = 96 if smoke else 256
    density = 0.08
    repeats, warmup = (2, 1) if smoke else (5, 2)
    rng = bench_rng()
    a = ((rng.random((scale, scale // 2)) < density)
         * rng.standard_normal((scale, scale // 2))).astype(np.float32)
    csr = csr_from_dense(a)
    fmt, plan = plan_and_convert(csr, total_workers=8)
    k = csr.shape[1]

    f_elem = jax.jit(lambda b2: loops_spmm(fmt, b2, backend=BACKEND))
    g_elem = jax.jit(jax.grad(lambda b2: jnp.sum(
        loops_spmm(fmt, b2, backend=BACKEND))))

    for batch in (SMOKE_BATCHES if smoke else BATCHES):
        b3 = jnp.asarray(rng.standard_normal((batch, k, N)).astype(np.float32))
        steps_one = loops_grid_steps(fmt, N)
        steps = {"loop": batch * steps_one, "vmap": batch * steps_one,
                 "native": loops_batched_grid_steps(fmt, batch, N)}
        fns = _strategies(fmt, batch)

        # Parity: native batched == vmap-unrolled (the acceptance contract).
        ref = np.asarray(fns["vmap"](b3))
        np.testing.assert_allclose(np.asarray(fns["native"](b3)), ref,
                                   rtol=1e-4, atol=1e-4)

        times = {}
        # Per-element Python loop: batch separate dispatches.
        def loop_fwd(b3_):
            return [f_elem(b3_[i]) for i in range(batch)]
        times[("loop", "fwd")] = time_fn(loop_fwd, b3, repeats=repeats,
                                         warmup=warmup)

        def loop_fwdbwd(b3_):
            return [g_elem(b3_[i]) for i in range(batch)]
        times[("loop", "fwdbwd")] = time_fn(loop_fwdbwd, b3, repeats=repeats,
                                            warmup=warmup)
        for name, fn in fns.items():
            times[(name, "fwd")] = time_fn(fn, b3, repeats=repeats,
                                           warmup=warmup)
            gfn = jax.jit(jax.grad(lambda bb, f=fn: jnp.sum(f(bb))))
            times[(name, "fwdbwd")] = time_fn(gfn, b3, repeats=repeats,
                                              warmup=warmup)

        for name in ("loop", "vmap", "native"):
            out(csv_row(
                f"batched_b{batch}_{name}", times[(name, "fwd")] * 1e6,
                f"grid_steps={steps[name]};"
                f"fwdbwd_us={times[(name, 'fwdbwd')] * 1e6:.1f};"
                f"steps_vs_loop={steps['loop'] / max(steps[name], 1):.2f}x"))
        if batch >= 4:
            assert steps["native"] < steps["loop"], \
                (f"native batched must beat the per-element loop on grid "
                 f"steps at batch={batch}: {steps['native']} vs "
                 f"{steps['loop']}")
        if record is not None:
            record({
                "suite": "batched", "batch": batch, "n_cols": N,
                "panel_g": plan.panel_g,
                "grid_steps_loop": steps["loop"],
                "grid_steps_native": steps["native"],
                "step_reduction_vs_loop":
                    steps["loop"] / max(steps["native"], 1),
                "fwd_us_loop": times[("loop", "fwd")] * 1e6,
                "fwd_us_vmap": times[("vmap", "fwd")] * 1e6,
                "fwd_us_native": times[("native", "fwd")] * 1e6,
                "fwdbwd_us_loop": times[("loop", "fwdbwd")] * 1e6,
                "fwdbwd_us_vmap": times[("vmap", "fwdbwd")] * 1e6,
                "fwdbwd_us_native": times[("native", "fwdbwd")] * 1e6,
            })


if __name__ == "__main__":
    main()
