"""Seconds of backend compilation during set-up (jax.monitoring)."""


def read(ctx):
    return ctx["setup_compile_s"]
