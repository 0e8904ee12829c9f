"""Device time per step of the kernels that run on a custom VJP's Aᵀ (ops
named ``%loops_*_t``), in ms, from the trace."""
from bench import vjp_parts


def read(ctx):
    return vjp_parts.read_ms(ctx, __file__)
