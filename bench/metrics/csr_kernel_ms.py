"""Device time per step of the CSR-part kernels (ops named
``%loops_csr_*``), in ms, from the trace."""
from bench import kernel_parts


def read(ctx):
    return kernel_parts.read(ctx, "csr", __file__)
