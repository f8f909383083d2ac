"""99th percentile gap between chunks of one stream, all streams pooled. In the chat cell it sits on the edge between the gaps stalled by a 1024-bucket prefill and those stalled by a 512-bucket one, and jumps between them (PERF.md section 6); in the document cell every admitted document stalls all streams. Recorded, not judged."""

from benchmarks import readers


def read(ctx):
    return readers.percentile_ms(ctx["counters"].get("gaps_s"), 99)
