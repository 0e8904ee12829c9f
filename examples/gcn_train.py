"""Paper §4.5 end-to-end case study: full-graph GCN training whose
neighbourhood aggregation runs through the LOOPS SpMM operator.

The model is the repository's GCN (``repro.models.gcn``: OGB's ogbn-arxiv
baseline, at ``configs.gcn_arxiv.reduced()`` widths unless ``--full``), on
a random graph with planted labels, trained by the normal train step
(``dist.step.build_train_step``: Adam on a one-device mesh).  Aggregation is
``loops_spmm`` on the real kernel path by default — 'pallas' on TPU,
'interpret' (the Pallas oracle) elsewhere: the forward pass runs the fused
panel kernels, the backward pass the same kernels on Âᵀ, built once before
the step is traced (``docs/training.md`` walks the dataflow).
``--backend jnp`` runs the reference path.  At start-up the script checks
that the loss's gradient through the kernels equals the jnp reference's.

Run:  PYTHONPATH=src python examples/gcn_train.py              # real kernels
      PYTHONPATH=src python examples/gcn_train.py --backend jnp --steps 200
      PYTHONPATH=src python examples/gcn_train.py --steps 2    # CI smoke
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import make_mesh
from repro.configs import gcn_arxiv
from repro.dist.step import build_train_step
from repro.kernels import ops
from repro.models import api, gcn
from repro.optim import adamw


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=None,
                    help="default 200 (jnp/pallas) / 40 (interpret: the "
                         "sequential Pallas oracle is ~100x slower per "
                         "nonzero, so the default problem is sized down)")
    ap.add_argument("--nodes", type=int, default=None,
                    help="default 2048 (jnp/pallas) / 256 (interpret)")
    ap.add_argument("--degree", type=int, default=None,
                    help="edges per node, default 8 (jnp/pallas) / 4 "
                         "(interpret)")
    ap.add_argument("--full", action="store_true",
                    help="the published widths 128-256-256-40 instead of "
                         "the reduced ones")
    ap.add_argument("--backend", default=None,
                    choices=["pallas", "interpret", "jnp"],
                    help="kernel path for BOTH the forward and backward "
                         "SpMM (default: the real kernels — 'pallas' on "
                         "TPU, 'interpret' elsewhere; 'jnp' is the "
                         "reference/oracle path)")
    ap.add_argument("--skip-grad-check", action="store_true",
                    help="skip the one-off kernel vs jnp-reference "
                         "gradient parity check")
    ap.add_argument("--autotune", action="store_true",
                    help="plan Â and Âᵀ via the measured repro.tune cache "
                         "instead of the hand-set total_workers=8 model "
                         "path; every GCN layer (and every restart of this "
                         "script with the same graph statistics) shares "
                         "one cached plan each way")
    args = ap.parse_args()
    backend = args.backend or ops.default_backend()
    small = backend == "interpret"   # oracle mode: keep the default quick
    if args.steps is None:
        args.steps = 40 if small else 200
    if args.nodes is None:
        args.nodes = 256 if small else 2048
    if args.degree is None:
        args.degree = 4 if small else 8
    cfg = gcn_arxiv.CONFIG if args.full else gcn_arxiv.reduced()

    n = args.nodes
    rng = np.random.default_rng(0)
    t0 = time.time()
    rows = rng.integers(0, n, n * args.degree)
    cols = rng.integers(0, n, n * args.degree)
    adj = gcn.normalized_adjacency(rows, cols, n)
    tuner = None
    if args.autotune:
        from repro.tune import PlanCache, Tuner
        # $REPRO_TUNE_CACHE honoured; the search runs once per graph
        # fingerprint (Â, then Âᵀ), a restart hits the plans on disk.
        tuner = Tuner(cache=PlanCache(), n_cols=cfg.hidden, backend="jnp")
    graph = gcn.prepare_graph(adj, backend=backend, tuner=tuner)
    t_prep = time.time() - t0
    if tuner is not None:
        print(f"autotune: Aᵀ plan={graph.transpose_plan}; cache "
              f"{tuner.cache.stats} ({len(tuner.cache)} stored plans in "
              f"{tuner.cache.dir})")
    print(f"graph: {n} nodes, nnz={adj.nnz}; conversion and Aᵀ "
          f"{t_prep:.3f}s (r_boundary={graph.adj.r_boundary}); "
          f"backend={backend}")

    x = jnp.asarray(rng.standard_normal((n, cfg.in_dim)), jnp.float32)
    # planted labels: the class of a random linear map of the aggregated
    # features, so that the graph carries the signal
    w_true = rng.standard_normal((cfg.in_dim, cfg.num_classes))
    agg = np.zeros((n, cfg.in_dim))
    np.add.at(agg, adj.row_ids, adj.vals[:, None] * np.asarray(x)[adj.col_idx])
    y = jnp.asarray(np.argmax(agg @ w_true, axis=1), jnp.int32)
    train = jnp.asarray(rng.random(n) < 0.5)
    key = jax.random.key(0)

    def batch_at(step):
        return gcn.make_batch(x, y, train, jax.random.key_data(
            jax.random.fold_in(key, step)))

    params = api.init_params(cfg, jax.random.key(1))
    if not args.skip_grad_check:
        # One-off parity: the gradient through the LOOPS custom VJP equals
        # the jnp reference path's native autodiff.
        oracle = gcn.Graph(adj=graph.adj, backend="jnp")
        mb = jax.tree.map(lambda a: a[0], batch_at(0))
        g_loops, g_ref = (jax.grad(lambda p, g=g: api.train_loss(
            cfg, p, mb, g)[0])(params) for g in (graph, oracle))
        err = max(float(jnp.abs(a - b).max())
                  for a, b in zip(jax.tree.leaves(g_loops),
                                  jax.tree.leaves(g_ref)))
        assert err <= 1e-4, f"custom-VJP grads off by {err:.2e} (> 1e-4)"
        print(f"grad check: max |loops - jnp| = {err:.2e}  (<= 1e-4) OK")

    mesh = make_mesh((1,), ("data",))
    bundle = build_train_step(
        cfg, mesh, jax.eval_shape(lambda: params),
        jax.eval_shape(lambda: batch_at(0)), gcn.opt_config(cfg),
        loss_fn=lambda p, mb: api.train_loss(cfg, p, mb, graph))
    opt = adamw.init_opt_state(params, 1)

    t0 = time.time()
    first = None
    for s in range(args.steps):
        params, opt, metrics = bundle.fn(params, opt, batch_at(s))
        loss = float(metrics["loss"])
        if first is None:
            first = loss
        if s % max(args.steps // 10, 1) == 0 or s == args.steps - 1:
            print(f"step {s:4d} loss {loss:.4f}")
    dt = time.time() - t0
    print(f"{args.steps} steps in {dt:.1f}s "
          f"({dt / args.steps * 1e3:.1f} ms/step); "
          f"prep amortised over {t_prep / (dt / args.steps):.0f} steps "
          f"(paper: 1.3% of e2e)")
    if args.steps >= 40:
        assert loss < first * 0.7, "GCN failed to learn"
        print("OK: loss decreased", first, "->", loss)


if __name__ == "__main__":
    main()
