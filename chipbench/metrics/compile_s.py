"""Seconds JAX spent tracing, lowering and compiling during set-up (the
sum of its `/jax/core/compile/*_duration` events; a warm persistent cache
still traces and lowers)."""


def read(ctx):
    return ctx["run"]["compile_s"]
