"""Serving driver: continuous-batching queue over the compiled step halves.

Requests flow through :mod:`repro.serve` (PR 9, docs/serving.md): the pure
injectable-clock scheduler coalesces same-prompt-shape requests into ragged
batches padded to the engine's batch-block grid, ``ServeQueue`` executes the
resulting prefill/decode actions through the two compiled halves from
``repro.dist.step`` (``build_prefill`` / cache-donating ``build_serve_step``)
via a warm :class:`~repro.serve.queue.ExecutorPool`, and admission control
sheds overload with a counted ``serve.rejected``.

Observability (``--obs``): the run is captured by a :class:`repro.obs.Obs` —
engine dispatch counters via the kernel-registry tracer hook, per-request
``serve.prefill_us`` / ``serve.decode_token_us`` / ``serve.ttft_us`` /
``serve.request_us`` histograms, ``serve.queue_depth`` / ``serve.in_flight``
gauges, spans around every phase, and a LOOPS plan-cache warm-up for the
model's FFN weight shapes (the "warm plan-cache pool" half of continuous
batching: the tuner search is paid before traffic, then bulk-installed into
the serving pool via ``PlanCache.prewarm`` — never on the hot path).  The
capture saves a versioned JSONL plus a Perfetto-loadable Chrome trace under
``benchmarks/results/obs/``; render either with ``tools/obs_report.py``.

Resilience (PR 8, docs/robustness.md): ``REPRO_FAULT_PLAN`` is honoured,
every engine call passes the ``serve.prefill`` / ``serve.step`` fault points
and retries with backoff, and retries/degraded plans are counted.

Demonstrates the serving path end-to-end on CPU with a reduced config:

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
      --batch 4 --prompt-len 32 --gen-len 16 --obs
"""
from __future__ import annotations

import argparse
import contextlib
import os
import time

import jax
import numpy as np

from ..configs import REDUCED, get_config
from ..resilience.inject import fault_point, install_from_env, note_degraded
# compat re-export: the cache-padding helper moved to the serve package
# (tests and notebooks import it from here)
from ..serve.queue import pad_cache  # noqa: F401
from ..serve.queue import ServeQueue
from ..serve.scheduler import POLICIES, SchedulerConfig
from .mesh import make_test_mesh


# Largest error the warm-up SpMM may show against the segment-sum reference,
# relative to |W|·|x| per output entry: fp32 differs only in summation
# order (~1e-7), while a single bf16 MXU pass errs ~4e-3.
WARM_SPMM_TOL = 1e-5


def warm_spmm_plan_cache(cfg, params, obs, *, sparsity: float = 0.9,
                         n_cols: int = 8, on_miss: str = "search",
                         pool=None):
    """Warm the LOOPS plan pool for this model's FFN weight shapes.

    The "warm plan-cache pool" prerequisite of continuous batching
    (ROADMAP item 1): magnitude-prune each layer's FFN weight, tune-or-
    fetch its execution plan through the persistent cache, and run one
    engine SpMM per layer to validate the plan: it must match the
    segment-sum reference within :data:`WARM_SPMM_TOL` (the largest error
    lands in the ``serve.warm_spmm_max_err`` gauge; a breach raises).
    Same-shaped layers fingerprint alike, so layer 0 pays the (budgeted)
    search and every later layer is a cache hit — the hit rate lands in
    the obs capture's ``tune.cache.*`` gauges, and each validation SpMM
    lands in the ``engine.dispatch`` counters.  Plans are tuned on the
    engine's platform default backend — the one that serves.  Families
    without a stacked dense FFN (MoE/SSM variants) warm a synthetic
    ``(4*d_model, d_model)`` matrix of the same sparsity instead.

    The tuned records are then bulk-installed into the serving ``pool``
    (default: a ``serve-pool`` cache beside the tuning store) in ONE atomic
    write via :meth:`repro.tune.PlanCache.prewarm` — ``stats.prewarmed``
    counts exactly the newly installed keys, so a re-warmed pool counts
    zero and no request ever pays a tuner search on the hot path.

    Resilience (docs/robustness.md): the weight passes an
    ``ingest.serve.weights`` fault point and the pruned CSR is validated
    with ``repair="drop"`` — corrupt values are repaired (and counted)
    rather than fed to Algorithm 1.  ``on_miss="model"`` switches the
    cache-miss policy to degraded mode: serve the Eq. 2 model-prior plan
    immediately (no measurement sweep on the request path), counting each
    such miss as ``serve.degraded{reason="plan-cache-miss"}``.

    Returns the warmed pool cache.
    """
    import dataclasses

    import jax.numpy as jnp

    from ..core.formats import csr_from_dense
    from ..core.spmm import loops_spmm, spmm_csr_baseline
    from ..kernels.engine import default_backend
    from ..models.sparse_ffn import magnitude_prune
    from ..resilience.validate import validate_csr
    from ..tune import PlanCache, SearchBudget, autotune
    from ..tune.fingerprint import cache_key, fingerprint

    cache = PlanCache()
    cache.stats.reset()
    obs.watch_cache(cache, name="serve-warm")
    budget = SearchBudget(top_k=2, repeats=1, warmup=0)

    mlp = params.get("layers", {}).get("mlp") if isinstance(params, dict) \
        else None
    if mlp is not None and "wi" in mlp and np.asarray(mlp["wi"]).ndim == 3:
        weights = [np.asarray(w).T for w in np.asarray(mlp["wi"],
                                                       np.float32)]
    else:
        rng = np.random.default_rng(0)
        d = cfg.d_model
        weights = [rng.standard_normal((4 * d, d)).astype(np.float32)]

    # Tune on the backend that serves: the engine's platform default.
    backend = default_backend()
    keys = []
    max_err = 0.0
    xrng = np.random.default_rng(0)
    for i, w in enumerate(weights):
        with obs.span("serve.warm_plan", cat="warm", layer=i):
            w = np.asarray(fault_point("ingest.serve.weights", w))
            csr = csr_from_dense(magnitude_prune(w, sparsity))
            csr, _ = validate_csr(csr, repair="drop")
            misses0 = cache.stats.misses
            fmt, _plan = autotune(csr, n_cols=n_cols, cache=cache,
                                  budget=budget, backend=backend,
                                  on_miss=on_miss)
            if on_miss == "model" and cache.stats.misses > misses0:
                note_degraded("serve.degraded", reason="plan-cache-miss")
            keys.append(cache_key(fingerprint(csr), n_cols=n_cols,
                                  dtype=csr.vals.dtype, backend=backend))
            x = xrng.standard_normal((csr.ncols, n_cols)).astype(np.float32)
            out = loops_spmm(fmt, jnp.asarray(x))
            ref = spmm_csr_baseline(csr, jnp.asarray(x))
            scale = spmm_csr_baseline(
                dataclasses.replace(csr, vals=np.abs(csr.vals)),
                jnp.asarray(np.abs(x)))
            err = float(jnp.max(jnp.abs(out - ref)
                                / jnp.maximum(scale, 1e-30)))
            max_err = max(max_err, err)
            if not err <= WARM_SPMM_TOL:
                raise RuntimeError(
                    f"warm-up SpMM of layer {i} is off the reference by "
                    f"{err:.3g} > {WARM_SPMM_TOL:g}")
    # Hand the tuned plans to the serving pool in one bulk write.
    if pool is None:
        pool = PlanCache(os.path.join(cache.dir, "serve-pool"))
    obs.watch_cache(pool, name="serve-pool")
    records = [cache.peek(k) for k in dict.fromkeys(keys)]
    installed = pool.prewarm([r for r in records if r is not None])
    obs.gauge("serve.warm_layers").set(len(weights))
    obs.gauge("serve.warm_spmm_max_err").set(max_err)
    obs.gauge("serve.prewarmed_plans").set(installed)
    return pool


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of concurrent requests to submit")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16,
                    help="tokens generated per request (prefill's first "
                         "token included)")
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int,
                    default=int(os.environ.get("REPRO_TEST_SEED", "0")),
                    help="params/prompt/sampling seed (default honours "
                         "REPRO_TEST_SEED for machine-reproducible runs)")
    # continuous-batching knobs (docs/serving.md)
    ap.add_argument("--max-batch", type=int, default=8,
                    help="requests coalesced per prefill call")
    ap.add_argument("--min-batch", type=int, default=1)
    ap.add_argument("--max-wait-ms", type=float, default=50.0,
                    help="batch-formation timeout for the oldest request")
    ap.add_argument("--max-in-flight", type=int, default=2,
                    help="groups admitted to the engine at once")
    ap.add_argument("--max-queue-depth", type=int, default=64,
                    help="admission control: submits beyond this are shed "
                         "(counted as serve.rejected)")
    ap.add_argument("--policy", choices=POLICIES, default="prefill-first",
                    help="prefill/decode interleave policy")
    ap.add_argument("--obs", nargs="?", const="serve", default=None,
                    metavar="STEM",
                    help="capture runtime metrics/spans; writes STEM.jsonl "
                         "+ STEM.trace.json (Chrome/Perfetto) under "
                         "--obs-dir (default benchmarks/results/obs/)")
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="override the obs output directory")
    ap.add_argument("--no-warm-spmm-cache", action="store_true",
                    help="skip the LOOPS plan-cache warm-up under --obs")
    ap.add_argument("--plan-on-miss", choices=("search", "model"),
                    default="search",
                    help="plan-cache miss policy for the warm-up: 'search' "
                         "pays the measurement sweep (default); 'model' "
                         "serves the Eq. 2 model-prior plan immediately "
                         "(degraded mode, counted as serve.degraded)")
    ap.add_argument("--step-retries", type=int, default=2,
                    help="host-level retries per prefill/decode step")
    ap.add_argument("--retry-backoff-ms", type=float, default=10.0,
                    help="initial retry backoff (doubles per attempt)")
    ap.add_argument("--step-deadline-ms", type=float, default=None,
                    help="per-request deadline across retries; exceeding it "
                         "raises DeadlineExceeded instead of sleeping past")
    args = ap.parse_args()
    from .compile_cache import enable as enable_compile_cache
    enable_compile_cache()

    # Chaos harness: honour REPRO_FAULT_PLAN so CI can inject failures into
    # a stock serving run (docs/robustness.md).
    install_from_env()

    obs = None
    if args.obs:
        from ..obs import Obs, set_active
        obs = Obs(source=args.obs)
        set_active(obs)

    cfg = REDUCED[args.arch]() if args.reduced else get_config(args.arch)
    mesh = make_test_mesh(args.mesh_data, args.mesh_model)
    from ..models import api
    params = api.init_params(cfg, jax.random.key(args.seed))

    # Degraded-mode step execution: transient host-level failures retry
    # with exponential backoff under the optional per-request deadline;
    # every retry is a counted degradation, never a silent one.
    retry_kw = dict(
        retries=args.step_retries,
        backoff_s=args.retry_backoff_ms / 1e3,
        deadline_s=(args.step_deadline_ms / 1e3
                    if args.step_deadline_ms is not None else None),
        on_retry=lambda n, e: (
            note_degraded("serve.degraded", reason="retry"),
            note_degraded("serve.retries")),
    )

    sched_cfg = SchedulerConfig(
        max_queue_depth=args.max_queue_depth,
        max_in_flight=args.max_in_flight,
        max_batch=args.max_batch, min_batch=args.min_batch,
        max_wait_s=args.max_wait_ms / 1e3, policy=args.policy)

    engine_ctx = obs.attach_engine() if obs else contextlib.nullcontext()
    with engine_ctx:
        if obs is not None and not args.no_warm_spmm_cache:
            warm_spmm_plan_cache(cfg, params, obs,
                                 on_miss=args.plan_on_miss)

        queue = ServeQueue(cfg, mesh, params, config=sched_cfg, obs=obs,
                           temperature=args.temperature, seed=args.seed,
                           retry_kw=retry_kw)

        # Seeded prompt set: one request per row, all through the queue.
        rng = np.random.default_rng(args.seed + 1)
        prompts = rng.integers(0, cfg.vocab_size,
                               (args.batch, args.prompt_len))
        t0 = time.perf_counter()
        reqs = [queue.submit([int(t) for t in row], args.gen_len)
                for row in prompts]
        done = queue.drain()
        t_total = time.perf_counter() - t0

    rejected = queue.sched.counters["rejected"]
    n_tokens = sum(r.tokens_generated for r in done)
    tps = n_tokens / max(t_total, 1e-9)
    print(f"served {len(done)}/{len(reqs)} requests "
          f"({args.batch}x{args.prompt_len}+{args.gen_len}) in "
          f"{t_total:.2f}s; {n_tokens} tokens at {tps:.1f} tok/s; "
          f"{queue.sched.counters['prefill_batches']} prefill batches, "
          f"{queue.sched.counters['decode_steps']} decode steps, "
          f"{rejected} rejected")
    if done:
        print("generated token ids (first request):",
              np.asarray(done[0].tokens[:16]))

    if obs is not None:
        from ..obs import set_active
        obs.gauge("serve.tokens_per_s").set(tps)
        obs.counter("serve.tokens_generated").inc(n_tokens)
        jsonl, chrome = obs.save(args.obs_dir, stem=args.obs)
        print(f"obs: {jsonl}")
        print(f"obs: {chrome}  (load in ui.perfetto.dev)")
        print(f"obs summary: {obs.summary()}")
        set_active(None)


if __name__ == "__main__":
    main()
