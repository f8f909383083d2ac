"""Mean time a measured step waited for its batch from the host generator."""

from benchmarks import readers


def read(ctx):
    return readers.mean_ms(ctx["counters"].get("data_wait_s"))
