"""Device time per step of the BCSR-part kernels (ops named
``%loops_bcsr_*``), in ms, from the trace."""
from bench import kernel_parts


def read(ctx):
    return kernel_parts.read(ctx, "bcsr", __file__)
