"""Seconds JAX spent compiling or loading programs during set-up
(``jax.monitoring``'s backend compile duration); moves ``setup_s``."""


def read(ctx):
    return ctx.compile_s
