"""Device time of the three Pallas attention kernels per traced step."""

from benchmarks import readers


def read(ctx):
    s = readers.kernel_seconds(ctx, readers.FLASH_KERNELS)
    return s * 1e3 / ctx["counters"]["trace_steps"] if s else None
