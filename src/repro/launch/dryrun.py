"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Run as a program, this module forces 512 placeholder host devices before
anything imports jax (jax locks the device count at first initialisation;
single-pod cells use the first 256).  Imported, it leaves ``XLA_FLAGS``
alone.

The step functions come from ``repro.dist.step`` (built against abstract
avals — nothing is allocated) with in/out shardings baked from
``repro.dist.sharding``; a successful compile is therefore a proof that the
sharding config is coherent at production scale (docs/architecture.md §4).

For each cell this script:
  1. builds allocation-free avals (params / optimizer / batch / cache),
  2. lowers the pjit'd step with explicit in/out shardings,
  3. compiles — success proves the sharding config is coherent (no mismatch,
     no unsupported collective, no compile-time OOM),
  4. records memory_analysis() + cost_analysis() + the HLO-derived roofline
     inputs (trip-count-corrected flops / hbm bytes / collective bytes) to
     benchmarks/results/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""
import os

if __name__ == "__main__":
    # Must run before the jax import below.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import math
import sys
import time
import traceback

import jax

from repro.configs import ALL_ARCHS, SHAPES, applicable_shapes, get_config
from repro.dist import step as step_lib
from repro.launch import specs
from repro.launch.mesh import make_production_mesh
from repro.optim import adamw
from repro.optim.adamw import OptConfig
from repro.perf.hlo_analysis import analyze_hlo

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def _mem_dict(ma) -> dict:
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        try:
            out[k] = int(getattr(ma, k))
        except Exception:
            pass
    return out


def lower_cell(arch: str, shape_name: str, mesh, overrides: dict | None = None):
    """Returns (lowered, n_microbatches) for one cell.  ``overrides`` are
    dataclasses.replace fields on the ModelConfig (perf-iteration knobs)."""
    import dataclasses
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    pav = specs.abstract_params(cfg)
    n_devices = math.prod(mesh.shape.values())
    if shape.kind == "train":
        n_mb = step_lib.default_microbatches(shape, mesh)
        bav = specs.train_batch_specs(cfg, shape, n_mb)
        oav = adamw.abstract_opt_state(pav, n_devices)
        bundle = step_lib.build_train_step(cfg, mesh, pav, bav, OptConfig(),
                                           n_microbatches=n_mb)
        return bundle.fn.lower(pav, oav, bav), n_mb
    if shape.kind == "prefill":
        bav = specs.prefill_batch_specs(cfg, shape)
        fn, _, _ = step_lib.build_prefill(cfg, mesh, pav, bav)
        return fn.lower(pav, bav), 1
    # decode
    cav, tok, ln = specs.decode_input_specs(cfg, shape)
    fn, _, _ = step_lib.build_serve_step(cfg, mesh, pav, cav)
    return fn.lower(pav, cav, tok, ln), 1


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             keep_hlo: bool = False, overrides: dict | None = None,
             tag: str = "") -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "mesh_shape": dict(mesh.shape), "status": "error",
           "overrides": overrides or {}, "tag": tag}
    try:
        with mesh:  # ambient mesh for bare-PartitionSpec constraints
            lowered, n_mb = lower_cell(arch, shape_name, mesh, overrides)
        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()
        rec["n_microbatches"] = n_mb
        try:
            rec["memory_analysis"] = _mem_dict(compiled.memory_analysis())
        except Exception as e:  # CPU backend may not support it
            rec["memory_analysis"] = {"error": str(e)}
        try:
            ca = compiled.cost_analysis()
            rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                    if isinstance(v, (int, float))
                                    and ("flops" in k or "bytes" in k
                                         or "utilization" not in k)}
        except Exception as e:
            rec["cost_analysis"] = {"error": str(e)}
        hlo_text = compiled.as_text()
        st = analyze_hlo(hlo_text)
        rec["hlo"] = {
            "flops_per_device": st.flops,
            "hbm_bytes_per_device": st.hbm_bytes,
            "collective_bytes_per_device": st.collective_bytes,
            "collective_by_kind": st.collective_by_kind,
            "unknown_trip_loops": st.unknown_trip_loops,
            "text_len": len(hlo_text),
        }
        if keep_hlo:
            suffix = f"__{tag}" if tag else ""
            rec["hlo_path"] = os.path.join(
                RESULTS_DIR,
                f"{arch}__{shape_name}__{mesh_kind}{suffix}.hlo.txt")
            with open(rec["hlo_path"], "w") as f:
                f.write(hlo_text)
        rec["lower_s"] = round(t_lower - t0, 2)
        rec["compile_s"] = round(t_compile - t_lower, 2)
        rec["status"] = "ok"
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--tag", default="",
                    help="suffix for result files (perf iterations)")
    ap.add_argument("--set", action="append", default=[],
                    help="ModelConfig override key=value (perf knobs), e.g. "
                         "--set attn_schedule=triangular")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        key, val = kv.split("=", 1)
        for cast in (int, float):
            try:
                val = cast(val)
                break
            except ValueError:
                continue
        overrides[key] = val

    os.makedirs(RESULTS_DIR, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = ALL_ARCHS if args.all or not args.arch else [args.arch]

    n_ok = n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([args.shape] if args.shape
                  else list(applicable_shapes(cfg)))
        for shape_name in shapes:
            for mk in meshes:
                suffix = f"__{args.tag}" if args.tag else ""
                out_path = os.path.join(
                    RESULTS_DIR, f"{arch}__{shape_name}__{mk}{suffix}.json")
                if args.skip_done and os.path.exists(out_path):
                    try:
                        old = json.load(open(out_path))
                        if old.get("status") == "ok":
                            print(f"[skip] {arch} {shape_name} {mk}")
                            continue
                    except Exception:
                        pass
                rec = run_cell(arch, shape_name, mk, keep_hlo=args.keep_hlo,
                               overrides=overrides or None, tag=args.tag)
                with open(out_path, "w") as f:
                    json.dump(rec, f, indent=1)
                ok = rec["status"] == "ok"
                n_ok += ok
                n_fail += (not ok)
                msg = (f"lower={rec.get('lower_s')}s "
                       f"compile={rec.get('compile_s')}s"
                       if ok else rec.get("error", ""))
                print(f"[{'ok' if ok else 'FAIL'}] {arch} {shape_name} {mk} "
                      f"{msg}", flush=True)
    print(f"done: {n_ok} ok, {n_fail} failed")
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
