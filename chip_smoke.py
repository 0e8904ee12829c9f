"""Bring-up smoke run: drive the LOOPS main path once on a TPU.

    python chip_smoke.py              # one chip: spmm, gcn and serve phases
    python chip_smoke.py --chips 4    # four chips: the device-group path only

One process holds the chip(s) for the whole run and starts no child.  Each
phase goes through the entry points a user calls — ``plan_and_convert`` →
``jax.jit(loops_spmm)``, the GCN training step, ``launch/serve.py``'s
``ServeQueue`` and plan-cache warm-up — at published sizes, and checks its
result against the repo's plain references:

* spmm  — Table 2's m4 (in-2004, power-law, 1.4M rows, fp32) and m6 (pwtk,
  banded, 200k rows, fp32 and bf16) at N=32, plus one batched call with B of
  shape (4, K, 32), each against ``spmm_csr_baseline``;
* gcn   — jitted training steps of a 2-layer GCN on a 250k-node graph
  (F_in = F_hid = 128, 16 classes; see ``GCN_NODES`` for why not 1M)
  through the LOOPS custom VJP, gradients against the same loss on the
  segment-sum reference;
* serve — ``hymba-1.5b`` at its published widths, all 32 layers, in its
  config dtype: the FFN plan-cache warm-up (5504×1600 at 90% sparsity, each
  layer's SpMM checked against the reference) and 4 requests of a 512-token
  prompt plus 16 generated tokens;
* dist (``--chips 4`` only) — the m4 SpMM and one GCN gradient through
  ``shard_loops_auto`` + ``distributed_spmm`` on a 4-device mesh, against
  the one-chip result, with each device holding its own row shard.  Its
  per-device body is the jnp reference, which materialises a (tiles, Br,
  N) product: at 1.4M rows (m4) it needs ~20 GB per device, so this phase
  cuts m4 to 200k rows and the graph to 125k nodes.

Every engine dispatch must run on ``pallas`` and no ``engine.fallback`` or
``dist.fallback`` may fire.  Earlier lines are bring-up readings (sizes,
panel and chunk counts, errors, seconds); they are not measurements of
speed.  The last line is ``{"ok": true, "device": {...}}``.  The script
exits non-zero, and prints no such line, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_COLS = 32
# Error bound for an SpMM against the segment-sum reference, per output
# entry relative to (|A|·|B|) there: the fp32 and bf16-input paths differ
# from the reference only in fp32 summation order (~1e-7), while a single
# bf16 MXU pass on fp32 data errs ~4e-3.
SPMM_TOL = 1e-5
# GCN gradients after two aggregations, a ReLU and a softmax, relative to
# the largest gradient entry: fp32 summation order only (~1e-6); a bf16
# pass anywhere in the chain errs ~1e-2.
GCN_TOL = 1e-4
# 250k nodes, not 1M: the backward's transposed format keeps every padding
# slot of A's Br-row tiles as an explicit entry of Aᵀ, which then pads
# again — 27.7M tiles for 4.25M nonzeros at 250k nodes.  Building it takes
# ~13 GB of host memory at 250k nodes and ~50 GB at 1M, past a one-chip
# host's 40 GiB.
GCN_NODES, GCN_DEGREE, GCN_F, GCN_CLASSES, GCN_STEPS = 250_000, 8, 128, 16, 3
# The dist phase's cuts (see the module docstring).
DIST_M4_ROWS, DIST_GCN_NODES = 200_000, 125_000
SERVE_ARCH, SERVE_REQUESTS, SERVE_PROMPT, SERVE_GEN = "hymba-1.5b", 4, 512, 16


def say(**fields) -> None:
    print(json.dumps(fields, default=float), flush=True)


class Failure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Failure(what)


class DispatchWatch:
    """Engine tracer: the backend of every LOOPS dispatch."""

    def __init__(self):
        self.backends = collections.Counter()

    def on_dispatch(self, **fields):
        self.backends[fields["backend"]] += 1


def timed(fn, *args):
    """``(result, seconds)`` of one blocking call."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def scaled_error(out, ref, scale):
    """max |out - ref| / (|A|·|B|), entrywise — the scale of the
    dot-product error bound, so rows with cancellation do not inflate
    the error."""
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)
                         / jnp.maximum(scale, 1e-30)))


def panel_counts(fmt):
    from repro.kernels.panel_common import CSR_WORDS, panel_calls
    cp, bp = fmt.csr_panels, fmt.bcsr_panels
    return ({"csr": cp.npanels, "bcsr": bp.npanels},
            {"csr": panel_calls(cp.npanels, cp.g, CSR_WORDS),
             "bcsr": panel_calls(bp.npanels, bp.g)})


# Nonzeros per chunk of the reference SpMM: bounds its (nnz, N) gather.
REF_CHUNK = 1 << 21


def csr_arrays(csr, absolute=False):
    """(rows, cols, vals) of ``csr`` in fp32, zero-padded to whole
    reference chunks, shaped (chunks, REF_CHUNK)."""
    import numpy as np
    vals = np.abs(csr.vals) if absolute else csr.vals
    pad = -csr.nnz % REF_CHUNK
    out = []
    for a, dt in ((csr.row_ids, np.int32), (csr.col_idx, np.int32),
                  (vals, np.float32)):
        a = np.concatenate([np.asarray(a, dt), np.zeros(pad, dt)])
        out.append(a.reshape(-1, REF_CHUNK))
    return tuple(out)


def segment_sum_spmm(arrays, b, nrows):
    """The segment-sum reference (``kernels.ref.csr_spmm_ref``, which
    ``spmm_csr_baseline`` runs) over chunks of nonzeros, summed: the same
    arithmetic in bounded memory."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ref import csr_spmm_ref

    def body(acc, chunk):
        rows, cols, vals = chunk
        return acc + csr_spmm_ref(rows, cols, vals, b, nrows), None

    zero = jnp.zeros(b.shape[:-2] + (nrows, b.shape[-1]), jnp.float32)
    return jax.lax.scan(body, zero, arrays)[0]


def reference_spmm(csr, b):
    """The fp32 segment-sum reference at full matmul precision, and the
    matching |A|·|B| scale."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda arr, x: segment_sum_spmm(arr, x, csr.nrows))
    b32 = b.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        return f(csr_arrays(csr), b32), f(csr_arrays(csr, True), jnp.abs(b32))


def convert(mid, nrow, dtype, seed):
    from repro.core import suite
    from repro.core.spmm import plan_and_convert
    t0 = time.perf_counter()
    csr = suite.table2_like(mid, scale_rows=nrow, seed=seed)
    if dtype != "float32":
        import jax.numpy as jnp
        csr = csr.astype(jnp.dtype(dtype))
    fmt, plan = plan_and_convert(csr)
    fmt.csr_panels, fmt.bcsr_panels   # panelize now: part of conversion
    return csr, fmt, plan, time.perf_counter() - t0


def spmm_cell(label, csr, fmt, conv_s, b):
    """One ``jax.jit(loops_spmm)`` call checked against the reference."""
    import jax
    from repro.core.spmm import loops_spmm
    f = jax.jit(lambda x: loops_spmm(fmt, x))
    out, first_s = timed(f, b)
    _, steady_s = timed(f, b)
    ref, scale = reference_spmm(csr, b)
    check(out.shape == ref.shape, f"{label}: shape {out.shape} != "
                                  f"{ref.shape}")
    err = scaled_error(out, ref, scale)
    panels, chunks = panel_counts(fmt)
    say(phase="spmm", cell=label, a_shape=list(csr.shape),
        b_shape=list(b.shape), dtype=str(csr.vals.dtype), nnz=int(fmt.nnz),
        r_boundary=fmt.r_boundary, panels=panels, chunks=chunks,
        max_err=err, tol=SPMM_TOL, convert_s=conv_s,
        compile_and_first_call_s=first_s, steady_call_s=steady_s,
        reading="bring-up")
    check(err <= SPMM_TOL, f"{label}: error {err:.3g} > {SPMM_TOL:g}")
    return out


def phase_spmm(seed, cells=(("m4", 1_400_000, "float32"),
                            ("m6", 200_000, "float32"),
                            ("m6", 200_000, "bfloat16")), batch=4):
    import jax
    import jax.numpy as jnp
    key = jax.random.key(seed)
    batched_done = False
    for mid, nrow, dtype in cells:
        csr, fmt, _, conv_s = convert(mid, nrow, dtype, seed)
        key, kb = jax.random.split(key)
        b = jax.random.normal(kb, (csr.ncols, N_COLS), jnp.float32
                              ).astype(dtype)
        spmm_cell(f"{mid}/{dtype}", csr, fmt, conv_s, b)
        if not batched_done and mid == "m6" and dtype == "float32":
            key, kb = jax.random.split(key)
            bb = jax.random.normal(kb, (batch, csr.ncols, N_COLS),
                                   jnp.float32)
            spmm_cell(f"{mid}/{dtype}/batched", csr, fmt, 0.0, bb)
            batched_done = True


def gcn_loss(x, y, agg):
    import jax
    import jax.numpy as jnp

    def loss(p):
        h = jax.nn.relu(agg(x @ p["w0"]))
        logits = agg(h @ p["w1"])
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)
    return loss


def gcn_problem(seed, nodes, degree, feat, classes):
    import jax
    import jax.numpy as jnp
    from repro.core import suite
    from repro.core.spmm import plan_and_convert
    t0 = time.perf_counter()
    adj = suite.gcn_graph(nodes, degree, seed=seed)
    fmt, _ = plan_and_convert(adj)
    fmt.csr_panels, fmt.bcsr_panels
    conv_s = time.perf_counter() - t0
    kx, ky, k0, k1 = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(kx, (nodes, feat), jnp.float32)
    y = jax.random.randint(ky, (nodes,), 0, classes)
    params = {"w0": jax.random.normal(k0, (feat, feat)) * 0.1,
              "w1": jax.random.normal(k1, (feat, classes)) * 0.1}
    return adj, fmt, conv_s, x, y, params


def reference_grad(adj, x, y, params):
    """Gradients of the GCN loss on the segment-sum reference at full
    matmul precision."""
    import jax

    def loss(p, arr):
        return gcn_loss(x, y, lambda h: segment_sum_spmm(
            arr, h, adj.nrows))(p)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.grad(loss))(params, csr_arrays(adj))


def grad_error(g, g_ref):
    import jax
    import jax.numpy as jnp
    return max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
               for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)))


def phase_gcn(seed, nodes=GCN_NODES, degree=GCN_DEGREE, feat=GCN_F,
              classes=GCN_CLASSES, steps=GCN_STEPS, lr=0.5):
    import jax
    import jax.numpy as jnp
    from repro.core.spmm import loops_spmm
    adj, fmt, conv_s, x, y, params = gcn_problem(seed, nodes, degree, feat,
                                                 classes)
    with jax.default_matmul_precision("highest"):
        loss = gcn_loss(x, y, lambda h: loops_spmm(fmt, h))

        @jax.jit
        def step(p):
            val, g = jax.value_and_grad(loss)(p)
            return jax.tree.map(lambda w, gw: w - lr * gw, p, g), val, g

        # The first step's gradients are the ones checked.
        (p, l0, g), first_s = timed(step, params)
        g_ref = reference_grad(adj, x, y, params)
        err = grad_error(g, g_ref)
        check(err <= GCN_TOL, f"gcn: gradient error {err:.3g} > "
                              f"{GCN_TOL:g}")
        losses, times = [float(l0)], []
        for _ in range(steps - 1):
            (p, val, _), s = timed(step, p)
            losses.append(float(val))
            times.append(s)
    check(all(jnp.isfinite(jnp.asarray(losses))), f"gcn: losses {losses}")
    panels, chunks = panel_counts(fmt)
    say(phase="gcn", nodes=nodes, nnz=int(fmt.nnz), features=feat,
        classes=classes, r_boundary=fmt.r_boundary, panels=panels,
        chunks=chunks, grad_max_rel_err=err, tol=GCN_TOL, losses=losses,
        convert_s=conv_s, compile_and_first_step_s=first_s, step_s=times,
        reading="bring-up")


def phase_serve(seed, obs, arch=SERVE_ARCH, requests=SERVE_REQUESTS,
                prompt_len=SERVE_PROMPT, gen_len=SERVE_GEN, reduced=False):
    import jax
    import numpy as np
    from repro.configs import REDUCED, get_config
    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import WARM_SPMM_TOL, warm_spmm_plan_cache
    from repro.models import api
    from repro.serve.queue import ServeQueue
    from repro.tune import PlanCache
    cfg = REDUCED[arch]() if reduced else get_config(arch)
    mesh = make_test_mesh(1, 1)
    t0 = time.perf_counter()
    params = api.init_params(cfg, jax.random.key(seed))
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    # Plans are tuned into the checkout (git-ignored), never into $HOME.
    tune_dir = os.path.join(ROOT, ".tune_cache")
    os.environ["REPRO_TUNE_CACHE"] = tune_dir
    warm_spmm_plan_cache(cfg, params, obs,
                         pool=PlanCache(os.path.join(tune_dir, "serve-pool")))
    warm_s = time.perf_counter() - t0
    warm_err = obs.metrics.find("gauge", "serve.warm_spmm_max_err").value
    queue = ServeQueue(cfg, mesh, params, obs=obs, seed=seed)
    rng = np.random.default_rng(seed + 1)
    prompts = rng.integers(0, cfg.vocab_size, (requests, prompt_len))
    t0 = time.perf_counter()
    reqs = [queue.submit([int(t) for t in row], gen_len) for row in prompts]
    done = queue.drain()
    serve_s = time.perf_counter() - t0
    check(len(done) == requests, f"serve: {len(done)}/{requests} answered")
    for r in done:
        check(len(r.tokens) == gen_len
              and all(0 <= t < cfg.vocab_size for t in r.tokens),
              f"serve: request {r.rid} returned {r.tokens}")
    ttft = [r.wall_first_token_s - r.wall_arrival_s for r in done]
    say(phase="serve", arch=cfg.name, layers=cfg.num_layers,
        d_model=cfg.d_model, d_ff=cfg.d_ff, dtype=str(np.dtype(cfg.dtype)),
        requests=len(reqs), prompt_len=prompt_len, gen_len=gen_len,
        warm_layers=cfg.num_layers, warm_spmm_max_err=warm_err,
        warm_tol=WARM_SPMM_TOL, init_s=init_s, warm_s=warm_s,
        serve_s=serve_s, ttft_s=ttft, first_tokens=done[0].tokens,
        reading="bring-up; the first requests include compilation")


def phase_dist(seed, nchips, nrow=DIST_M4_ROWS, nodes=DIST_GCN_NODES):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.compat import make_mesh
    from repro.core.distributed import distributed_spmm, shard_loops_auto
    from repro.core.spmm import loops_spmm
    say(phase="dist", note="the per-device body is the jnp reference "
                           "(core/distributed.py), not the Pallas kernels")
    mesh = make_mesh((nchips,), ("model",))
    devices = list(mesh.devices.flat)

    # m4 SpMM, assembled, against the one-chip result.
    csr, fmt, _, conv_s = convert("m4", nrow, "float32", seed)
    b = jax.random.normal(jax.random.key(seed), (csr.ncols, N_COLS))
    one, _ = timed(jax.jit(lambda x: loops_spmm(fmt, x)), b)
    sharded = shard_loops_auto(fmt, nchips)
    dist = jax.jit(lambda x: distributed_spmm(sharded, x, mesh, axis="model"))
    got, first_s = timed(dist, b)
    ref, scale = reference_spmm(csr, b)
    err_one = scaled_error(got, one, scale)
    err_ref = scaled_error(got, ref, scale)
    check(err_one <= SPMM_TOL and err_ref <= SPMM_TOL,
          f"dist spmm: error vs one chip {err_one:.3g}, vs reference "
          f"{err_ref:.3g} > {SPMM_TOL:g}")

    # Each device holds its own exclusive row shard.
    stacked = jax.jit(lambda x: distributed_spmm(
        sharded, x, mesh, axis="model", assemble=False))(b)
    one_np = np.asarray(one)
    owners = []
    for shard in stacked.addressable_shards:
        d = shard.index[0].start or 0
        check(shard.data.shape[0] == 1, f"dist: shard {d} holds "
                                        f"{shard.data.shape[0]} row blocks")
        check(shard.device == devices[d], f"dist: shard {d} on "
                                          f"{shard.device}")
        o, c = int(sharded.row_offset[d]), int(sharded.row_count[d])
        rows = np.asarray(shard.data)[0, :c]
        err = float(np.max(np.abs(rows - one_np[o:o + c]),
                           initial=0.0)) / float(np.max(np.abs(one_np)))
        check(err <= SPMM_TOL, f"dist: shard {d} rows off by {err:.3g}")
        owners.append({"device": d, "rows": [o, o + c]})
    check(sorted(o["device"] for o in owners) == list(range(nchips)),
          f"dist: shards on {owners}")
    say(phase="dist", cell="m4/float32", a_shape=list(csr.shape),
        nnz=int(fmt.nnz), devices=nchips, g_vpu=sharded.g_vpu,
        max_err_vs_one_chip=err_one, max_err_vs_reference=err_ref,
        tol=SPMM_TOL, shards=owners, convert_s=conv_s,
        compile_and_first_call_s=first_s, reading="bring-up")

    # One GCN gradient through distributed_spmm's custom VJP (cotangent
    # psum), against the one-chip LOOPS gradient.
    adj, gfmt, gconv_s, x, y, params = gcn_problem(seed, nodes, GCN_DEGREE,
                                                   GCN_F, GCN_CLASSES)
    gsharded = shard_loops_auto(gfmt, nchips)
    with jax.default_matmul_precision("highest"):
        g_one = jax.jit(jax.grad(gcn_loss(
            x, y, lambda h: loops_spmm(gfmt, h))))(params)
        g_dist, gfirst_s = timed(jax.jit(jax.grad(gcn_loss(
            x, y, lambda h: distributed_spmm(gsharded, h, mesh,
                                             axis="model")))), params)
    err = grad_error(g_dist, g_one)
    check(err <= GCN_TOL, f"dist gcn: gradient error {err:.3g} > "
                          f"{GCN_TOL:g}")
    say(phase="dist", cell="gcn-grad", nodes=nodes, devices=nchips,
        g_vpu=gsharded.g_vpu, grad_max_rel_err_vs_one_chip=err, tol=GCN_TOL,
        convert_s=gconv_s, compile_and_first_call_s=gfirst_s,
        reading="bring-up")


def counter_total(obs, name) -> float:
    return sum(inst.value for kind, inst in obs.metrics.instruments()
               if kind == "counter" and inst.name == name)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: spmm, gcn and serve phases on one chip; 4: "
                         "only the device-group path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.kernels import engine
    from repro.launch.compile_cache import enable as enable_compile_cache
    from repro.obs import Obs, set_active
    say(compile_cache=enable_compile_cache(), jax=jax.__version__,
        device_kind=devices[0].device_kind, devices=len(devices))
    obs = Obs(source="chip_smoke")
    set_active(obs)
    watch = DispatchWatch()
    engine.set_tracer(watch)

    if args.chips == 4:
        phases = [("dist", lambda: phase_dist(args.seed, 4))]
    else:
        phases = [("spmm", lambda: phase_spmm(args.seed)),
                  ("gcn", lambda: phase_gcn(args.seed)),
                  ("serve", lambda: phase_serve(args.seed, obs))]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception as e:  # noqa: BLE001 - report every phase, then fail
            import traceback
            traceback.print_exc()
            failed.append(f"{name}: {type(e).__name__}: {e}")
        say(phase=name, seconds=time.perf_counter() - t0)

    fallbacks = {m: counter_total(obs, m)
                 for m in ("engine.fallback", "dist.fallback")}
    say(dispatch_backends=dict(watch.backends), fallbacks=fallbacks)
    if set(watch.backends) != {"pallas"}:
        failed.append(f"dispatch backends {dict(watch.backends)}, "
                      f"expected only pallas")
    if any(fallbacks.values()):
        failed.append(f"fallbacks fired: {fallbacks}")
    if failed:
        for f in failed:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
