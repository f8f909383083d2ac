"""Due (closed loop: sent) time -> first streamed chunk at the client, 50th percentile, a failed request counting as the worst. The chat window holds 46 requests and the document queue is full by design, so it swings between seeds: recorded, not judged."""

from benchmarks import readers


def read(ctx):
    return readers.percentile_ms(ctx["counters"].get("ttft_all_s"), 50)
