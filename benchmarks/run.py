"""Benchmark orchestrator — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (and mirrors them to
benchmarks/results/bench.csv).  Suites that emit structured records (fig4's
panelization columns, the batched engine suite) also land in
benchmarks/results/bench.json — the machine-readable perf trajectory
(``panel_g``, grid-step reductions, wall-clock) that CI diffs against a
committed baseline via tools/perf_gate.py.

  fig4   — FP64/FP32 SpMM throughput vs TACO-like / Armadillo-like (Fig. 4)
           + the G=1 vs tuned-G panelization columns
  fig5   — bf16(=FP16) SpMM vs block-only / csr-only strategies (Fig. 5)
  sec43  — adaptive scheduling ablation (§4.3)
  table3 — modeled energy efficiency (Table 3)
  table4 — end-to-end GCN training (§4.5 / Table 4)
  roofline — §Roofline terms for every dry-run cell (assignment)
  autotune — model-only vs measured/cached plans + cache hit rates
  batched  — multi-RHS engine: per-element loop vs vmap-unrolled vs
             native batched (fwd and fwd+bwd, grid-step columns)
  spmm_dryrun    — production-mesh distributed SpMM cell; skip-records
                   unless a 256-device platform is live (standalone CLI
                   forces one: ``python -m benchmarks.spmm_dryrun``)
  compress_bytes — int8/bf16 compressed-psum collective bytes; skip-records
                   unless 16 devices are live (standalone CLI forces them)
  serve_traffic  — closed-loop serving load through the continuous-batching
                   queue: p50/p99 latency + goodput, batched vs no-batching

``--smoke`` shrinks the suites that support it (tiny matrices, fewer
repeats) for CI: kernel-layer regressions then surface as benchmark
failures, not only as test failures.  In smoke mode fig4 plans
deterministically (no wall-clock calibration), so the grid-step columns
are a pure function of the seeded matrices — the property the perf gate's
exact checks rely on.

Perf-gate flags: ``--baseline F`` runs tools/perf_gate.py against F after
the suites finish (non-zero exit on regression); ``--update-baseline``
copies the freshly merged bench.json over F instead (refreshing the
committed BENCH_<PR>.json after an intentional change).  ``--trace``
records a perf trace (engine dispatches + per-matrix SpMM wall-clock) to
benchmarks/results/traces/<source>.jsonl for replay/cost-model fitting.
``--obs-trace`` additionally captures the run with ``repro.obs`` (per-suite
spans + engine dispatch counters) to benchmarks/results/obs/ in the same
JSONL schema live ``--obs`` runs use, so ``tools/obs_report.py`` renders
benchmark and serving captures interchangeably.
"""
from __future__ import annotations

import argparse
import contextlib
import inspect
import json
import os
import shutil
import sys
import traceback

DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__), "results",
                                "BENCH_010.json")


def _suite_registry():
    from . import (autotune_suite, batched_spmm, compress_bytes,
                   fig4_throughput, fig5_halfprec, roofline, sec43_scheduling,
                   serve_traffic, spmm_dryrun, table3_energy, table4_gnn)
    return {
        "fig4": fig4_throughput.main,
        "fig5": fig5_halfprec.main,
        "sec43": sec43_scheduling.main,
        "table3": table3_energy.main,
        "table4": table4_gnn.main,
        "roofline": roofline.main,
        "autotune": autotune_suite.main,
        "batched": batched_spmm.main,
        "spmm_dryrun": spmm_dryrun.bench_main,
        "compress_bytes": compress_bytes.main,
        "serve_traffic": serve_traffic.main,
    }


# Keep --only's help in sync with the registry without importing the suite
# modules (and therefore jax) just to print --help.
SUITE_NAMES = ["fig4", "fig5", "sec43", "table3", "table4", "roofline",
               "autotune", "batched", "spmm_dryrun", "compress_bytes",
               "serve_traffic"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list of suites: " + ",".join(SUITE_NAMES))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-suite CI mode (suites that support it)")
    ap.add_argument("--trace", action="store_true",
                    help="record a perf trace (engine dispatch + SpMM "
                         "wall-clock) to benchmarks/results/traces/")
    ap.add_argument("--obs-trace", action="store_true",
                    help="capture the run with repro.obs (per-suite spans, "
                         "engine dispatch counters) to "
                         "benchmarks/results/obs/ — same JSONL schema as "
                         "live-run --obs captures, so obs_report.py and "
                         "diff tooling treat them interchangeably")
    ap.add_argument("--baseline", nargs="?", const=DEFAULT_BASELINE,
                    default=None, metavar="F",
                    help="after the run, gate the merged bench.json against "
                         f"this baseline (default {DEFAULT_BASELINE})")
    ap.add_argument("--update-baseline", action="store_true",
                    help="copy the merged bench.json over the baseline file "
                         "instead of gating against it")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable as enable_compile_cache
    enable_compile_cache()

    suites = _suite_registry()
    assert sorted(suites) == sorted(SUITE_NAMES), \
        "suite registry drifted from SUITE_NAMES — update both"
    chosen = (args.only.split(",") if args.only else list(suites))
    unknown = [n for n in chosen if n not in suites]
    if unknown:
        ap.error(f"unknown suite(s) {unknown}; choose from "
                 + ",".join(SUITE_NAMES))

    results_dir = os.path.join(os.path.dirname(__file__), "results")
    os.makedirs(results_dir, exist_ok=True)
    rows: list[str] = []
    records: list[dict] = []

    recorder = None
    if args.trace:
        from repro.perf.trace import TraceRecorder
        recorder = TraceRecorder(source="bench-" + "-".join(chosen))
    obs = None
    if args.obs_trace:
        from repro.obs import Obs, set_active
        obs = Obs(source="bench-" + "-".join(chosen))
        set_active(obs)

    def emit(line: str):
        print(line, flush=True)
        rows.append(line)

    emit("name,us_per_call,derived")
    failures = 0
    for name in chosen:
        fn = suites[name]
        kwargs = {}
        params = inspect.signature(fn).parameters
        if "smoke" in params:
            kwargs["smoke"] = args.smoke
        if "record" in params:
            kwargs["record"] = records.append
        if recorder is not None and "recorder" in params:
            kwargs["recorder"] = recorder
        try:
            with contextlib.ExitStack() as stack:
                if recorder is not None:
                    stack.enter_context(recorder.attach_engine())
                if obs is not None:
                    # obs chains onto the recorder's tracer, so --trace and
                    # --obs-trace compose (both see every dispatch)
                    stack.enter_context(obs.attach_engine())
                    stack.enter_context(obs.span(f"suite.{name}",
                                                 cat="bench"))
                fn(out=emit, **kwargs)
        except Exception:
            failures += 1
            emit(f"{name}_FAILED,0,{traceback.format_exc(limit=1).strip()}")
    with open(os.path.join(results_dir, "bench.csv"), "w") as f:
        f.write("\n".join(rows) + "\n")
    # bench.json merges per suite: records of the suites run THIS invocation
    # are replaced (so a re-run can never leave stale numbers), records of
    # suites not selected by --only survive.
    json_path = os.path.join(results_dir, "bench.json")
    try:
        with open(json_path) as f:
            kept = [r for r in json.load(f)
                    if not any(str(r.get("suite", "")).startswith(name)
                               for name in chosen)]
    except (OSError, ValueError):
        kept = []
    with open(json_path, "w") as f:
        json.dump(kept + records, f, indent=1, sort_keys=True)
    if recorder is not None and recorder.records:
        print(f"trace: {recorder.save()}", flush=True)
    if obs is not None:
        from repro.obs import set_active
        jsonl, chrome = obs.save()
        print(f"obs: {jsonl}", flush=True)
        print(f"obs: {chrome}", flush=True)
        set_active(None)
    if failures:
        sys.exit(1)

    if args.update_baseline:
        target = args.baseline or DEFAULT_BASELINE
        shutil.copyfile(json_path, target)
        print(f"baseline updated: {target}", flush=True)
    elif args.baseline:
        tools_dir = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        sys.path.insert(0, tools_dir)
        import perf_gate
        sys.exit(perf_gate.main(["--baseline", args.baseline,
                                 "--current", json_path]))


if __name__ == "__main__":
    main()
