"""The front door's own work on one streamed item (resolve, JSON, chunk, write): `http_proxy_stats()["stream_forward_s"]` over `["stream_items"]`, wall seconds on that process's clock, both over the WHOLE window (`counters["stream_path"]`). That process holds no profiler, but only a traced run reports this, and there the replica it forwards for is slowed by one (its stop lies inside the window): a traced line read 0.170-0.223 where `stream_counters.py` read 0.164-0.172 with the profiler off (PERF.md, section 6, PR 42). Compare two traced lines of one cell."""

from benchmarks import stream_spans


def read(ctx):
    return stream_spans.proxy_forward_ms_per_item(
        ctx["counters"].get("stream_path"))
