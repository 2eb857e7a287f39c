"""Seconds of XLA backend compiles during set-up, from the jax.monitoring
event ``/jax/core/compile/backend_compile_duration``; about 0 when every
program loads from the compilation cache."""


def read(ctx):
    return ctx["compile_s"]
