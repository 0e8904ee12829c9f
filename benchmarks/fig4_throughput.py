"""Paper Fig. 4: SpMM throughput (GFLOPS), FP64 and FP32, LOOPS vs CPU
baselines across the Table-2-like suite.

Baselines (implemented, per assignment scope):
  * taco-like   — row-wise CSR schedule in pure XLA (segment-sum), the
                  schedule TACO emits for CSR SpMM;
  * armadillo-like — dense GEMM on the densified operand (Armadillo stores
                  sparse, but its SpMM lowers to generic kernels; the dense
                  GEMM is the upper-bound-friendly stand-in).

Container caveat (recorded in EXPERIMENTS.md): wall-clock numbers are
CPU-XLA proxies — this machine has ONE homogeneous engine, so the paper's
heterogeneous-engine speedup mechanism cannot appear in wall-clock; what IS
reproducible here is the *adaptive scheduling* claim: the calibrated perf
model (Eq. 2) discovers the machine's best split per matrix (on CPU that is
usually CSR-heavy; on the TPU target the roofline terms in §Roofline carry
the perf claim).  The Pallas kernels are TPU-targeted and validated in
interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (csr_to_dense, loops_from_csr, loops_grid_steps,
                        loops_spmm, plan_and_convert, spmm_csr_baseline,
                        spmm_dense_baseline, suite)
from repro.core.partition import choose_r_boundary
from repro.core.perf_model import calibrate

from ._util import bench_rng, csv_row, gflops, time_fn

N = 32  # paper fixes N=32
MATRICES = ["m6", "m8", "m9", "m10", "m12", "m13", "m14", "m16", "m17", "m19"]
SMOKE_MATRICES = ["m6", "m12", "m13"]
G_CHOICES = (4, 8)         # tuned-G candidates (G=1 is the baseline column)
WALL_MATRICES = 3          # matrices that also get interpret wall-clock


def calibrated_plan(csr, b, total: int = 4, deterministic: bool = False):
    """Paper §3.5: fit Eq. 2 from warm-up runs of candidate splits, then
    argmax (Eq. 3) -> boundary (Eq. 1).

    ``deterministic`` skips the wall-clock calibration and plans from the
    proportional prior alone — smoke mode uses it so the recorded plan (and
    with it every grid-step column the perf gate diffs exactly) is a pure
    function of the seeded matrix, not of machine timing noise.
    """
    if deterministic:
        return plan_and_convert(csr, total_workers=total)

    def measure(x, y):
        r = choose_r_boundary(csr.nrows, 1.0, 4.0, max(x, 0), max(y, 0),
                              br=8)
        fmt = loops_from_csr(csr, r, 8)
        f = jax.jit(lambda bb: loops_spmm(fmt, bb, backend="jnp"))
        return 1.0 / time_fn(f, b, repeats=2, warmup=1)

    model = calibrate(measure, total=total)
    return plan_and_convert(csr, total_workers=total, model=model)


def panel_comparison(csr, plan, b, *, mid: str, name_dt: str, out,
                     record=None, wall_clock: bool, smoke: bool):
    """G=1 vs tuned-G: grid-step cost proxy for every matrix, plus
    interpret-mode (Pallas) wall-clock on a subset — the panelization
    speedup tracked in the perf trajectory (benchmark JSON)."""
    fmts = {g: loops_from_csr(csr, plan.r_boundary, plan.br, panel_g=g)
            for g in (1,) + tuple(G_CHOICES)}
    steps = {g: loops_grid_steps(f, N) for g, f in fmts.items()}
    tuned_g = min(G_CHOICES, key=lambda g: steps[g])
    g_ref = max(G_CHOICES)   # the reduction the acceptance tracking pins
    red_tuned = steps[1] / max(steps[tuned_g], 1)
    red_ref = steps[1] / max(steps[g_ref], 1)

    wall = {}
    if wall_clock:
        repeats, warmup = (2, 1) if smoke else (3, 1)
        for g in (1, tuned_g):
            f = jax.jit(lambda bb, fg=fmts[g]: loops_spmm(
                fg, bb, backend="interpret"))
            wall[g] = time_fn(f, b, repeats=repeats, warmup=warmup)

    wall_note = (f";wall_g1_us={wall[1] * 1e6:.1f}"
                 f";wall_tuned_us={wall[tuned_g] * 1e6:.1f}"
                 f";wall_speedup={wall[1] / wall[tuned_g]:.2f}x"
                 if wall else "")
    out(csv_row(f"fig4_{name_dt}_{mid}_panelG", steps[tuned_g],
                f"panel_g={tuned_g};steps_g1={steps[1]};"
                f"steps_tuned={steps[tuned_g]};step_reduction="
                f"{red_tuned:.2f}x;step_reduction_g{g_ref}={red_ref:.2f}x"
                + wall_note))
    if record is not None:
        record({
            "suite": "fig4_panel", "matrix": mid, "dtype": name_dt,
            "panel_g": tuned_g,
            "steps_g1": steps[1], f"steps_g{g_ref}": steps[g_ref],
            "steps_tuned": steps[tuned_g],
            "step_reduction_tuned": red_tuned,
            f"step_reduction_g{g_ref}": red_ref,
            "wall_us_g1": wall.get(1, 0.0) * 1e6,
            "wall_us_tuned": wall.get(tuned_g, 0.0) * 1e6,
        })
    return red_ref


def run(dtype=np.float32, scale_rows: int = 1024, out=print, record=None,
        smoke: bool = False, recorder=None):
    name_dt = {np.float32: "fp32", np.float64: "fp64"}[dtype]
    if dtype == np.float64:
        jax.config.update("jax_enable_x64", True)
    try:
        rng = bench_rng()
        matrices = SMOKE_MATRICES if smoke else MATRICES
        rows, g8_reds = [], []
        for i, mid in enumerate(matrices):
            csr = suite.table2_like(mid, scale_rows=scale_rows, seed=3,
                                    dtype=dtype)
            nnz = csr.nnz
            b = jnp.asarray(rng.standard_normal((csr.shape[1], N)), dtype)
            fmt, plan = calibrated_plan(csr, b, deterministic=smoke)
            dense = jnp.asarray(csr_to_dense(csr))

            f_loops = jax.jit(lambda bb: loops_spmm(fmt, bb, backend="jnp"))
            f_taco = jax.jit(lambda bb: spmm_csr_baseline(csr, bb))
            f_arma = jax.jit(lambda bb: spmm_dense_baseline(dense, bb))

            t_loops = time_fn(f_loops, b)
            t_taco = time_fn(f_taco, b)
            t_arma = time_fn(f_arma, b)
            g = gflops(nnz, N, t_loops)
            out(csv_row(f"fig4_{name_dt}_{mid}_{suite.TABLE2_STATS[mid].name}",
                        t_loops * 1e6,
                        f"GFLOPS={g:.2f};vs_taco={t_taco / t_loops:.2f}x;"
                        f"vs_dense={t_arma / t_loops:.2f}x"))
            rows.append((t_taco / t_loops, t_arma / t_loops))
            if record is not None:
                record({"suite": "fig4", "matrix": mid, "dtype": name_dt,
                        "panel_g": plan.panel_g, "nnz": nnz,
                        "us_per_call": t_loops * 1e6, "gflops": g,
                        "vs_taco": t_taco / t_loops,
                        "vs_dense": t_arma / t_loops})
            if recorder is not None:
                recorder.record_spmm(csr, plan, wall_s=t_loops, n_cols=N,
                                     backend="jnp", gflops=g)
            g8_reds.append(panel_comparison(
                csr, plan, b, mid=mid, name_dt=name_dt, out=out,
                record=record, wall_clock=(i < WALL_MATRICES), smoke=smoke))
        sp = np.array(rows)
        g_ref = max(G_CHOICES)
        ref_geo = float(np.exp(np.log(np.maximum(g8_reds, 1e-9)).mean()))
        out(csv_row(f"fig4_{name_dt}_geomean", 0.0,
                    f"speedup_vs_taco={np.exp(np.log(sp[:, 0]).mean()):.2f}x;"
                    f"speedup_vs_dense={np.exp(np.log(sp[:, 1]).mean()):.2f}x;"
                    f"step_reduction_g{g_ref}={ref_geo:.2f}x"))
        if record is not None:
            record({"suite": "fig4_panel_geomean", "matrix": "geomean",
                    "dtype": name_dt,
                    f"step_reduction_g{g_ref}": ref_geo})
    finally:
        if dtype == np.float64:
            jax.config.update("jax_enable_x64", False)


def main(out=print, record=None, smoke: bool = False, recorder=None):
    scale = 192 if smoke else 1024
    run(np.float32, scale_rows=scale, out=out, record=record, smoke=smoke,
        recorder=recorder)
    if not smoke:
        run(np.float64, out=out, record=record, recorder=recorder)


if __name__ == "__main__":
    main()
