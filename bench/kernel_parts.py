"""Device time of the LOOPS kernels by part, read from the trace the traced
stretch leaves on disk (``bench/out/trace/<cell>``).

Each kernel's ``pallas_call`` is named by its part (``loops_csr_spmm``,
``loops_bcsr_spmm``, ...), and on a TPU the op line names an op by its HLO
instruction, so ``%loops_bcsr_spmm.3 = ... custom-call(...)`` is a BCSR
kernel wherever the compiler puts it.  A part's seconds are the self time of
its kernel ops in the traced window, counted by ``tracing.reduce`` as it
counts ``kernel_s``, with every other op taken as not a kernel: so the parts
sum to ``kernel_s`` when every kernel carries a part's name.  A program whose
kernels carry no such name (``%closed_call.27``) has no part to read.
"""
from __future__ import annotations

import functools
import os

from . import tracing

PREFIXES = {"csr": "loops_csr_", "bcsr": "loops_bcsr_"}


def part_of(ev: dict) -> str | None:
    """The part whose kernel ``ev`` is, by its instruction name, or None."""
    if ev["kind"] != "device" or not tracing.is_kernel(ev):
        return None
    inst = tracing.short_name(ev["name"]).split(" ")[0].lstrip("%")
    for part, prefix in PREFIXES.items():
        if inst.startswith(prefix):
            return part
    return None


def part_seconds(events: list[dict]) -> dict[str, float]:
    """Self seconds of each part's kernels in the traced window, for the
    parts the trace names."""
    parts = {part_of(e) for e in events} - {None}
    return {part: tracing.reduce(
        [e if e["kind"] == "host" or part_of(e) == part
         else dict(e, name="", stats={}) for e in events])["kernel_s"]
        for part in parts}


@functools.lru_cache(maxsize=4)
def _trace_parts(path: str, mtime_ns: int) -> dict[str, float]:
    return part_seconds(tracing.load(path))


def read(ctx, part: str, metric_file: str):
    """Milliseconds per traced step of ``part``'s kernels in the run
    ``ctx`` describes, or None where the trace names none.
    ``metric_file`` is the reader's own path: the trace lies under the
    ``bench/`` directory that holds it."""
    t = ctx.trace
    if not t or not t["steps"]:
        return None
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(metric_file)))
    try:
        path = tracing.xplane_file(
            os.path.join(bench_dir, "out", "trace", ctx.cell))
    except FileNotFoundError:
        return None
    seconds = _trace_parts(path, os.stat(path).st_mtime_ns).get(part)
    if not seconds:
        return None
    return seconds / t["steps"] * 1e3
