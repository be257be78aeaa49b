"""Backend compiles inside the measured window (``jax.monitoring``'s
``backend_compile_duration`` events); the guarantee is 0."""


def read(ctx):
    return float(ctx["compiles"])
