"""Requests the front door shed (503) or timed out (504) over requests it saw in the window."""

from benchmarks import readers


def read(ctx):
    return readers.proxy_refused_share(ctx)
