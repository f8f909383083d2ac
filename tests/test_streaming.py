"""Streaming generators + asyncio actors (reference: streaming-generator
returns task_manager.cc:778; async actors via fibers fiber.h /
concurrency_group_manager.cc)."""

import time

import numpy as np
import pytest

import ray_tpu


# ---------------------------------------------------------------------------
# local mode
# ---------------------------------------------------------------------------
def test_local_streaming_generator(ray_start_local):
    @ray_tpu.remote
    def gen(n):
        for i in range(n):
            yield i * i

    g = gen.remote(5)
    assert isinstance(g, ray_tpu.ObjectRefGenerator)
    vals = [ray_tpu.get(ref, timeout=30) for ref in g]
    assert vals == [0, 1, 4, 9, 16]


def test_local_streaming_error(ray_start_local):
    @ray_tpu.remote
    def gen():
        yield 1
        raise ValueError("stream boom")

    g = gen.remote()
    assert ray_tpu.get(next(g), timeout=30) == 1
    with pytest.raises(ValueError, match="stream boom"):
        next(g)


def test_local_async_actor_overlap(ray_start_local):
    import asyncio

    @ray_tpu.remote
    class Async:
        async def slow(self, x):
            await asyncio.sleep(0.3)
            return x

    a = Async.remote()
    t0 = time.monotonic()
    refs = [a.slow.remote(i) for i in range(100)]
    vals = ray_tpu.get(refs, timeout=60)
    elapsed = time.monotonic() - t0
    assert vals == list(range(100))
    # 100 x 0.3s sequentially = 30s; overlapped should be ~0.3s
    assert elapsed < 10, f"async calls did not overlap: {elapsed:.1f}s"


# ---------------------------------------------------------------------------
# cluster runtime
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=3, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_cluster_streaming_generator_incremental(cluster):
    """Consume yields while the task is still producing."""

    @ray_tpu.remote
    def slow_gen(n):
        for i in range(n):
            time.sleep(0.05)
            yield i

    g = slow_gen.remote(20)
    first = ray_tpu.get(next(g), timeout=60)
    assert first == 0  # arrived long before the task finished (20*0.05s)
    rest = [ray_tpu.get(r, timeout=60) for r in g]
    assert rest == list(range(1, 20))


def test_cluster_streaming_1k_objects(cluster):
    @ray_tpu.remote
    def gen(n):
        for i in range(n):
            yield i

    vals = [ray_tpu.get(r, timeout=120) for r in gen.remote(1000)]
    assert vals == list(range(1000))


def test_cluster_streaming_big_values_through_plasma(cluster):
    @ray_tpu.remote
    def gen():
        for i in range(4):
            yield np.full(300_000, float(i))  # 2.4MB -> plasma

    arrs = [ray_tpu.get(r, timeout=120) for r in gen.remote()]
    assert [a[0] for a in arrs] == [0.0, 1.0, 2.0, 3.0]


def test_cluster_streaming_error_propagates(cluster):
    @ray_tpu.remote
    def gen():
        yield "ok"
        raise RuntimeError("mid-stream failure")

    g = gen.remote()
    assert ray_tpu.get(next(g), timeout=60) == "ok"
    with pytest.raises(RuntimeError, match="mid-stream failure"):
        for _ in g:
            pass


def test_cluster_actor_streaming_method(cluster):
    @ray_tpu.remote
    class Producer:
        def stream(self, n):
            for i in range(n):
                yield i * 10

    p = Producer.remote()
    vals = [ray_tpu.get(r, timeout=60) for r in p.stream.remote(5)]
    assert vals == [0, 10, 20, 30, 40]


def test_cluster_async_actor_overlap(cluster):
    import asyncio

    @ray_tpu.remote
    class Async:
        async def slow(self, x):
            await asyncio.sleep(0.5)
            return x * 2

    a = Async.remote()
    t0 = time.monotonic()
    refs = [a.slow.remote(i) for i in range(100)]
    vals = ray_tpu.get(refs, timeout=120)
    elapsed = time.monotonic() - t0
    assert sorted(vals) == [i * 2 for i in range(100)]
    assert elapsed < 30, f"async actor calls did not overlap: {elapsed:.1f}s"


# ---------------------------------------------------------------------------
# the transport: a yield is handed to the caller's sender and does not wait
# for its ack; one StreamingYield call carries what has gathered
# ---------------------------------------------------------------------------
@ray_tpu.remote(max_concurrency=10)
class Streams:
    """Streaming methods that count what they have produced, beside plain
    methods that read the counts while the streams run."""

    def __init__(self):
        self.produced = {}

    def stream(self, key, n, pause=0.0, fail_after=None):
        for i in range(n):
            if i == fail_after:
                raise RuntimeError(f"failed after {i}")
            self.produced[key] = i + 1
            yield (key, i)
            if pause:
                time.sleep(pause)

    def mixed(self, n):
        for i in range(n):
            # small values ride in the call, large ones go through plasma
            yield np.full(300_000, float(i)) if i % 2 else float(i)

    def count(self, key):
        return self.produced.get(key, 0)

    def sent(self):
        from ray_tpu._private import streaming

        return streaming.send_stats()


def test_a_slow_consumer_gets_every_item_and_bounds_the_producer(cluster):
    from ray_tpu._private.config import config

    limit = config.streaming_generator_buffer_size
    s = Streams.remote()
    n = 2000
    g = s.stream.remote("a", n)
    taken = []

    def ahead():
        return ray_tpu.get(s.count.remote("a"), timeout=60) - len(taken)

    for _ in range(4):
        # the consumer sleeps and the producer runs into the bound: what it
        # has yielded (the last perhaps still in its hand-over) is never
        # more than the buffer ahead of what was taken
        _until(lambda: ahead() >= limit, timeout=60)
        time.sleep(0.2)
        assert ahead() <= limit + 1
        taken += [next(g) for _ in range(300)]
    refs = taken + list(g)
    assert len(refs) == n and len({r.id() for r in refs}) == n  # a ref each
    assert ray_tpu.get(refs, timeout=120) == [("a", i) for i in range(n)]


def test_eight_streams_to_one_caller_share_their_calls(cluster):
    s = Streams.remote()
    before = ray_tpu.get(s.sent.remote(), timeout=60)
    n = 300
    gens = [s.stream.remote(k, n) for k in range(8)]
    got = {k: [] for k in range(8)}
    for _ in range(n):
        for k, g in enumerate(gens):  # round robin: all eight stay live
            got[k].append(ray_tpu.get(next(g), timeout=60))
    for k, g in enumerate(gens):
        assert got[k] == [(k, i) for i in range(n)]  # each stream's order
        with pytest.raises(StopIteration):  # and its count
            next(g)
    after = ray_tpu.get(s.sent.remote(), timeout=60)
    items = after["stream_items_sent"] - before["stream_items_sent"]
    calls = after["stream_calls"] - before["stream_calls"]
    assert items == 8 * n
    assert 0 < calls < items  # some call carried more than one item


def test_a_dropped_stream_stops_and_the_others_finish(cluster):
    s = Streams.remote()
    n = 400
    gens = [s.stream.remote(k, n, 0.002) for k in range(4)]
    got = {k: [] for k in range(4)}
    for _ in range(20):
        for k, g in enumerate(gens):
            got[k].append(ray_tpu.get(next(g), timeout=60))
    dropped = gens.pop(1)
    del dropped  # the consumer abandons one of them mid-way
    time.sleep(0.3)
    stopped_at = ray_tpu.get(s.count.remote(1), timeout=60)
    for k, g in zip((0, 2, 3), gens):
        got[k] += [ray_tpu.get(r, timeout=60) for r in g]
        assert got[k] == [(k, i) for i in range(n)]
    # its generator stopped within a few yields of the drop, for good
    assert ray_tpu.get(s.count.remote(1), timeout=60) == stopped_at < n / 2


def test_an_exception_arrives_after_exactly_the_items_before_it(cluster):
    s = Streams.remote()
    k = 50
    g = s.stream.remote("e", 100, 0.0, k)
    time.sleep(0.5)  # items, the error and the end have all arrived
    got = []
    with pytest.raises(RuntimeError, match=f"failed after {k}"):
        for ref in g:
            got.append(ray_tpu.get(ref, timeout=60))
    assert got == [("e", i) for i in range(k)]


def test_inline_and_plasma_items_of_one_stream_keep_their_order(cluster):
    s = Streams.remote()
    values = [ray_tpu.get(r, timeout=120) for r in s.mixed.remote(8)]
    assert [float(np.ravel(v)[0]) for v in values] == [
        float(i) for i in range(8)]
    assert [np.size(v) for v in values] == [1, 300_000] * 4


class _Caller:
    """The caller's end of a sender, faked: acknowledges every item, says a
    stream in `gone` is abandoned, and breaks once `broken` is set."""

    def __init__(self, gone=()):
        self.gone = set(gone)
        self.broken = False
        self.calls = []

    def call(self, method, **kwargs):
        if self.broken:
            raise ConnectionError("caller lost")
        if method == "StreamingYield":
            self.calls.append([(i[0], i[1]) for i in kwargs["items"]])
            return {i[0]: {"ok": i[0] not in self.gone, "pending": 0}
                    for i in kwargs["items"]}
        self.calls.append((method, kwargs["task_id_bin"], kwargs["count"]))
        return {"ok": True}


def _until(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline
        time.sleep(0.005)


def test_a_sender_fails_the_streams_of_a_lost_call_and_drops_the_abandoned(
        monkeypatch):
    from ray_tpu._private import streaming

    caller = _Caller(gone={b"gone"})
    monkeypatch.setattr(streaming, "get_client", lambda addr: caller)
    sender = streaming.StreamSender(("127.0.0.1", 1))
    a, b, gone = (streaming.OutStream(t) for t in (b"a", b"b", b"gone"))

    # an abandoned stream learns of it by its next yield; the streams it
    # shared a call with are acknowledged as ever
    assert sender.put(gone, 0, "inline", b"x", 1)
    assert sender.put(a, 0, "inline", b"x", 1)
    _until(lambda: gone.unacked == 0 and a.unacked == 0)
    assert not sender.put(gone, 1, "inline", b"x", 1)
    assert sender.put(a, 1, "inline", b"x", 1)
    sender.finish(a, 2, None)
    sent = [c for call in caller.calls if isinstance(call, list) for c in call]
    assert [i for t, i in sent if t == b"a"] == [0, 1]
    assert (b"gone", 1) not in sent
    assert caller.calls[-1] == ("StreamingDone", b"a", 2)  # behind its items

    # a lost connection raises in the generator whose item the call carried
    # at its next yield, and its end does not hang
    caller.broken = True
    assert sender.put(b, 0, "inline", b"x", 1)
    _until(lambda: b.error is not None)
    with pytest.raises(ConnectionError, match="caller lost"):
        sender.put(b, 1, "inline", b"x", 1)
    sender.finish(b, 1, None, timeout=5.0)
    assert b.done
    assert sender.items == 3 >= sender.calls  # a failed call is not counted
