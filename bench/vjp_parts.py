"""Device time of the kernels that run on a custom VJP's Aᵀ, read from the
trace the traced stretch leaves on disk (``bench/out/trace/<cell>``).

Those kernels carry their part's name with ``_t`` (``loops_csr_spmm_t``,
``loops_bcsr_spmm_t``), so on a TPU their ops read ``%loops_bcsr_spmm_t.3``
wherever the compiler puts them.  Their seconds are the self time of those
ops in the traced window, counted by ``tracing.reduce`` as it counts
``kernel_s``, with every other op taken as not a kernel.  A program whose
backward kernels carry no such name has nothing to read.
"""
from __future__ import annotations

import functools
import os
import re

from . import tracing

VJP_OP = re.compile(r"^loops_[a-z_]+_t(\.|$)")


def is_vjp(ev: dict) -> bool:
    """Whether ``ev`` is a kernel op on Aᵀ, by its instruction name."""
    if ev["kind"] != "device" or not tracing.is_kernel(ev):
        return False
    inst = tracing.short_name(ev["name"]).split(" ")[0].lstrip("%")
    return bool(VJP_OP.match(inst))


def vjp_seconds(events: list[dict]) -> float:
    """Self seconds of the kernels on Aᵀ in the traced window."""
    return tracing.reduce(
        [e if e["kind"] == "host" or is_vjp(e) else dict(e, name="", stats={})
         for e in events])["kernel_s"]


@functools.lru_cache(maxsize=4)
def _trace_seconds(path: str, mtime_ns: int) -> float:
    return vjp_seconds(tracing.load(path))


def read_ms(ctx, metric_file: str):
    """Milliseconds per traced step of the kernels on Aᵀ in the run ``ctx``
    describes, or None where the trace names none.  ``metric_file`` is the
    reader's own path: the trace lies under the ``bench/`` that holds it."""
    t = ctx.trace
    if not t or not t["steps"]:
        return None
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(metric_file)))
    try:
        path = tracing.xplane_file(
            os.path.join(bench_dir, "out", "trace", ctx.cell))
    except FileNotFoundError:
        return None
    seconds = _trace_seconds(path, os.stat(path).st_mtime_ns)
    if not seconds:
        return None
    return seconds / t["steps"] * 1e3
