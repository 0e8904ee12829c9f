"""Host seconds of the Aᵀ build in set-up (the program's
``loops.transpose`` span), where the system reads it."""


def read(ctx):
    return ctx.clock.get("transpose_s")
