"""Readings from which the GCN cell's correctness limits are set: the
program's numbers on many seeds and the control's on a few.

    python3 bench/gcn_control.py --workload gcn-arxiv-train --seeds 1,2,... \\
        --control-seeds 101,102,103

One process builds the cell's system once (conversion and compile, as a
run does) and then, for each of ``--seeds``, starts from that seed's
weights, runs the steps a run's set-up runs and then the window's up to
the checked one, and judges that step as a run's check does
(``System.check`` against the configuration's limits).  The control is the
reference put in the program's place and computed in bfloat16, the
precision below the configuration's float32: operands of the dense
products and of the aggregation rounded to bfloat16, fp32 accumulation
(``reference``'s ``"default"``).  Three bfloat16 passes (``"high"``) are
judged the same way: only the output layer's gradient tells them from the
float32 program (PERF.md §4).  The benchmark's own runs never run this
script.  Each reading is printed as one JSON line, with ``correct`` as a
run would report it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

CONTROL_PRECISION = "default"
SETUP_STEPS = 3   # the build's two steps and the harness's depth call
SIDES = {"program": None, "control": CONTROL_PRECISION, "high": "high"}


def _say(out, **fields):
    print(json.dumps(fields), file=out, flush=True)


def checked_step(system, seed: int):
    """Start ``system`` at ``seed``, run the set-up's steps, then the
    window's until its checked step has a state after it; returns
    ``(before, after, loss, key)``."""
    import jax
    system.start(seed)
    for _ in range(SETUP_STEPS):
        system.dispatch(-1)
    for k in range(system.check_step + 2):
        out = system.dispatch(k)
    jax.block_until_ready(out)
    return system.before, system.after, *system.checked


def judged(system, seed: int, side: str) -> dict:
    """The checks of ``seed``'s checked step with ``side`` in the
    program's place: ``{check: (value, limit)}``, as ``System.check``
    gives them."""
    _, _, _, key = checked_step(system, seed)
    if SIDES[side] is None:
        return system.check()
    p0, o0 = system.before
    return system.check(system.reference_step(p0, o0, key, SIDES[side]))


def readings(root, cell_name, seeds, control_seeds, require_tpu=True,
             out=sys.stdout):
    """Build the cell's system once and print every reading."""
    import jax
    bench = harness.Bench(root)
    cell = bench.cell(cell_name)
    if require_tpu and jax.devices()[0].platform != "tpu":
        raise harness.NoChip(f"JAX found no TPU (platform "
                             f"{jax.devices()[0].platform!r})")
    cfg = bench.config(cell["config"])
    system = bench.system(cfg["kind"]).System(
        cfg, bench.traffic(cell["traffic"]), seeds[0])
    clock = harness.Clock()
    system.build(clock)
    _say(out, side="setup", cell=cell_name,
         seconds=time.perf_counter() - T_START, **clock)
    for side, group in (("program", seeds), ("control", control_seeds),
                        ("high", control_seeds)):
        for s in group:
            checks = judged(system, s, side)
            _say(out, side=side, seed=s, step=system.check_step,
                 loss=float(system.checked[0]),
                 correct=all(v <= lim for v, lim in checks.values()),
                 **{k: v for k, (v, _) in checks.items()})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    args = ap.parse_args(argv)
    import jax
    from repro.launch.compile_cache import enable as enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        readings(ROOT, args.workload,
                 [int(s) for s in args.seeds.split(",")],
                 [int(s) for s in args.control_seeds.split(",")])
    except harness.NoChip as e:
        print(f"gcn_control: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
