"""Seconds JAX spent obtaining executables before the window (backend_compile events, cache reads included), in the process that holds the chip."""


def read(ctx):
    return ctx["counters"].get("compile_s")
