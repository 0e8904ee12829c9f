"""Least time of a step's SpMM work on Aᵀ (counted from the graph and
shapes, the ``vjp`` part of the system's kernel work) over the device time
of the kernels on Aᵀ, in %."""
from bench import vjp_parts


def read(ctx):
    ms = vjp_parts.read_ms(ctx, __file__)
    vjp = getattr(ctx.kernel_work, "vjp", None)
    if ms is None or vjp is None or ctx.peak is None:
        return None
    return vjp.least_seconds(ctx.peak) / (ms * 1e-3) * 100.0
