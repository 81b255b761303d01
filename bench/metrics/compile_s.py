"""Seconds spent compiling during set-up (a persistent-cache hit counts
its retrieval), from JAX's compile events."""


def read(ctx):
    return ctx["out"]["compile_s"]
