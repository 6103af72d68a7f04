"""Backend compile seconds during set-up (``jax.monitoring``)."""


def read(ctx):
    return float(ctx.setup_compile["compile_s"])
