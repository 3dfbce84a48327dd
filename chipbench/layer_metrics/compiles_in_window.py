"""Compile: programs built or fetched from the persistent cache between the
window's start and end (``jax.monitoring`` backend-compile events counted by
run.py's listener). Expected 0."""


def read(ctx):
    return ctx["window_compiles"]
