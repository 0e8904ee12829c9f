"""End-to-end training driver.

Thin CLI over the device layer: ``repro.dist.step.build_train_step`` builds
the jitted grad-accumulating ZeRO-1 step, ``repro.dist.sharding`` places
params/optimizer/batches on the mesh (docs/architecture.md §4 for the spec
conventions).  This driver only owns the loop: data, checkpoints, logging.

Fault tolerance contract:
  * checkpoints are step-atomic and async (``repro.checkpoint``); the data
    "iterator" is the step counter itself (deterministic pipeline), so
    restart resumes the exact token stream;
  * ``--resume`` restores from the newest checkpoint — with ANY mesh shape
    (checkpoints are unsharded; the restoring job re-applies its own
    shardings => elastic up/down-scaling across restarts);
  * a heartbeat file is touched every step; an external supervisor (or the
    ``--max-step-seconds`` watchdog here) can kill and restart a hung run —
    combined with atomic checkpoints this is the whole crash-recovery story.

Observability: ``--obs`` captures the run with :class:`repro.obs.Obs` —
per-step latency histogram (``step.wall_us{op=train_step}`` via the step
builder), engine dispatch counters, a ``train.steps_per_s`` gauge — and
saves a versioned JSONL + Chrome trace under ``benchmarks/results/obs/``
(render with ``tools/obs_report.py``).

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --steps 20 --seq-len 64 --global-batch 8 --mesh-data 1 --mesh-model 1
"""
from __future__ import annotations

import argparse
import contextlib
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import Checkpointer, latest_step, restore
from ..configs import REDUCED, get_config
from ..configs.base import ShapeConfig
from ..data import DataConfig, global_batch_at
from ..dist import sharding as shr
from ..dist import step as step_lib
from ..models import api
from ..optim import adamw
from ..optim.adamw import OptConfig
from .mesh import make_test_mesh


def build_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-scale config of the same family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-step-seconds", type=float, default=0,
                    help="watchdog: abort if one step exceeds this")
    ap.add_argument("--obs", nargs="?", const="train", default=None,
                    metavar="STEM",
                    help="capture runtime metrics/spans; writes STEM.jsonl "
                         "+ STEM.trace.json (Chrome/Perfetto) under "
                         "--obs-dir (default benchmarks/results/obs/)")
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="override the obs output directory")
    return ap.parse_args()


def main():
    args = build_args()
    from .compile_cache import enable as enable_compile_cache
    enable_compile_cache()
    # Chaos harness: honour REPRO_FAULT_PLAN (docs/robustness.md).
    from ..resilience.inject import install_from_env
    install_from_env()
    obs = None
    if args.obs:
        from ..obs import Obs, set_active
        obs = Obs(source=args.obs)
        set_active(obs)
    cfg = REDUCED[args.arch]() if args.reduced else get_config(args.arch)
    mesh = make_test_mesh(args.mesh_data, args.mesh_model)
    n_devices = args.mesh_data * args.mesh_model
    shape = ShapeConfig("cli_train", args.seq_len, args.global_batch, "train")
    opt_cfg = OptConfig(lr=args.lr, total_steps=args.steps,
                        warmup_steps=max(args.steps // 20, 1))
    data_cfg = DataConfig(seed=args.seed)

    n_mb = step_lib.default_microbatches(shape, mesh)
    params = api.init_params(cfg, jax.random.key(args.seed))
    pav = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    bav = jax.eval_shape(
        lambda: global_batch_at(data_cfg, cfg, shape, n_mb, 0))
    bundle = step_lib.build_train_step(cfg, mesh, pav, bav, opt_cfg,
                                       n_microbatches=n_mb, obs=obs)

    # placement
    psh = shr.spec_to_sharding(bundle.param_spec, mesh)
    params = jax.device_put(params, psh)
    opt_state = adamw.init_opt_state(params, n_devices)
    osh = shr.spec_to_sharding(bundle.opt_spec, mesh)
    opt_state = jax.device_put(opt_state, osh)

    start_step = 0
    ckpt = Checkpointer(args.ckpt_dir)
    if args.resume and latest_step(args.ckpt_dir) is not None:
        tmpl = {"params": params, "opt": opt_state}
        start_step, tree, meta = restore(args.ckpt_dir, tmpl)
        # Validated ingestion: a checkpoint that restores NaN/Inf params
        # would train to garbage silently — fail loudly at the boundary.
        from ..resilience.validate import check_finite_tree
        check_finite_tree(tree["params"], what="restored params")
        params = jax.device_put(tree["params"], psh)
        opt_state = jax.device_put(tree["opt"], osh)
        print(f"[resume] step {start_step} from {args.ckpt_dir} "
              f"(meta={meta})")

    hb_path = os.path.join(args.ckpt_dir, "heartbeat")
    os.makedirs(args.ckpt_dir, exist_ok=True)
    batch_fn = jax.jit(lambda s: global_batch_at(data_cfg, cfg, shape, n_mb,
                                                 s))
    t_start = time.perf_counter()
    engine_ctx = obs.attach_engine() if obs else contextlib.nullcontext()
    with engine_ctx:
        for step in range(start_step, args.steps):
            t0 = time.perf_counter()
            batch = batch_fn(step)
            params, opt_state, metrics = bundle.fn(params, opt_state, batch)
            t_step = time.perf_counter() - t0
            if args.max_step_seconds and t_step > args.max_step_seconds:
                raise TimeoutError(
                    f"step {step} exceeded watchdog "
                    f"({t_step:.1f}s > {args.max_step_seconds}s)")
            with open(hb_path, "w") as f:
                f.write(str(step))
            if step % args.log_every == 0 or step == args.steps - 1:
                m = jax.device_get(metrics)
                print(f"step {step:6d} loss {float(m['loss']):.4f} "
                      f"gnorm {float(m['grad_norm']):.3f} "
                      f"lr {float(m['lr']):.2e} "
                      f"({time.perf_counter() - t0:.2f}s/step)", flush=True)
            if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                ckpt.save_async(step + 1,
                                {"params": params, "opt": opt_state},
                                meta={"arch": cfg.name})
    ckpt.save_async(args.steps, {"params": params, "opt": opt_state},
                    meta={"arch": cfg.name, "final": True})
    ckpt.close()
    t_total = time.perf_counter() - t_start
    n_steps = args.steps - start_step
    print(f"trained {n_steps} steps in {t_total:.1f}s; final loss "
          f"{float(jax.device_get(metrics)['loss']):.4f}")
    if obs is not None:
        from ..obs import set_active
        obs.gauge("train.steps_per_s").set(n_steps / max(t_total, 1e-9))
        obs.counter("train.steps").inc(n_steps)
        jsonl, chrome = obs.save(args.obs_dir, stem=args.obs)
        print(f"obs: {jsonl}")
        print(f"obs: {chrome}  (load in ui.perfetto.dev)")
        print(f"obs summary: {obs.summary()}")
        set_active(None)


if __name__ == "__main__":
    main()
