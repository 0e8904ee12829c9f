"""Paper Fig. 5: half-precision SpMM (paper: FP16 2-way fmopa; here: bf16 in
/ fp32 accumulate — the TPU-native equivalent).

Baselines:
  * block-only — pure vector-wise-BCSR execution (r_boundary = 0): the
    Magicube-style "everything through the matrix unit" strategy, which pays
    padding on irregular rows;
  * csr-only   — pure row-wise execution (the no-matrix-unit strategy).
LOOPS is the adaptive hybrid of the two.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (csr_from_dense, csr_to_dense, loops_from_csr,
                        loops_spmm, plan_and_convert, suite)

from ._util import csv_row, gflops, time_fn

N = 32
MATRICES = ["m6", "m8", "m10", "m13", "m14", "m17"]


def main(out=print):
    rng = np.random.default_rng(1)
    sp_block, sp_csr = [], []
    for mid in MATRICES:
        csr32 = suite.table2_like(mid, scale_rows=1024, seed=4)
        dense16 = jnp.asarray(csr_to_dense(csr32), jnp.bfloat16)
        csr = csr_from_dense(np.asarray(dense16))
        nnz = csr.nnz
        b = jnp.asarray(rng.standard_normal((csr.shape[1], N)), jnp.bfloat16)

        from .fig4_throughput import calibrated_plan
        fmt, plan = calibrated_plan(csr, b)
        fmt_block = loops_from_csr(csr, 0, plan.br)       # pure BCSR
        fmt_csr = loops_from_csr(csr, csr.nrows, plan.br)  # pure CSR

        f_hybrid = jax.jit(lambda bb: loops_spmm(fmt, bb, backend="jnp"))
        f_block = jax.jit(lambda bb: loops_spmm(fmt_block, bb, backend="jnp"))
        f_csr = jax.jit(lambda bb: loops_spmm(fmt_csr, bb, backend="jnp"))

        t_h = time_fn(f_hybrid, b)
        t_b = time_fn(f_block, b)
        t_c = time_fn(f_csr, b)
        g = gflops(nnz, N, t_h)
        # Packed-path wall clock: the Pallas kernels (interpret mode off-TPU)
        # keep the bf16 B rows packed and accumulate in fp32.
        f_packed = jax.jit(lambda bb: loops_spmm(fmt, bb,
                                                 backend="interpret"))
        t_p = time_fn(f_packed, b, repeats=2, warmup=1)
        out(csv_row(f"fig5_bf16_{mid}_packed", t_p * 1e6,
                    f"panel_g={plan.panel_g}"))
        # padding waste of the block-only format (zero fraction of tiles)
        tiles = fmt_block.bcsr_part.tile_vals
        waste = 1.0 - (np.count_nonzero(tiles) / max(tiles.size, 1))
        out(csv_row(f"fig5_bf16_{mid}_{suite.TABLE2_STATS[mid].name}",
                    t_h * 1e6,
                    f"GFLOPS={g:.2f};vs_blockonly={t_b / t_h:.2f}x;"
                    f"vs_csronly={t_c / t_h:.2f}x;block_pad_waste={waste:.2f}"))
        sp_block.append(t_b / t_h)
        sp_csr.append(t_c / t_h)
    out(csv_row("fig5_bf16_geomean", 0.0,
                f"vs_blockonly={np.exp(np.log(sp_block).mean()):.2f}x;"
                f"vs_csronly={np.exp(np.log(sp_csr).mean()):.2f}x"))


if __name__ == "__main__":
    main()
