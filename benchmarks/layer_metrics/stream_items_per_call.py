"""Mean `items` of the traced `ray_tpu.worker.stream_rpc` spans: how many generator items one StreamingYield call of the replica's stream sender carried to the front door (1.0: every item found the sender idle and left alone); None on a program whose spans carry no `items` (one call an item, before PR 37)."""

import statistics

from benchmarks import program_spans, stream_spans


def _mean_items(parsed, name):
    found = [s[4]["items"] for s in program_spans.named(parsed, name)
             if "items" in s[4]]
    return statistics.fmean(found) if found else None


def read(ctx):
    return program_spans.read(ctx, _mean_items, stream_spans.STREAM_RPC)
