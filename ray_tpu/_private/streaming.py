"""Streaming generators: consume a task's yields while it still runs.

Reference: streaming-generator returns in src/ray/core_worker/
task_manager.cc:778 (HandleReportGeneratorItemReturns) and
python/ray/_raylet.pyx ObjectRefGenerator — re-designed for the pickle-RPC
runtime. Both ends of the transport live here.

Producer: a yield hands its serialised item to the ``StreamSender`` of the
caller's address and goes on; the sender's thread makes one
``StreamingYield`` call carrying every item that every stream of this process
has handed over for that caller since its last call (an item that finds it
idle leaves at once, alone), then each stream's ``StreamingDone``. A
producer waits only when its own stream is ``streaming_generator_buffer_size``
items ahead of its consumer.

Caller: the handler registers each item under its own ObjectID (inline
payload or a plasma location); the ``ObjectRefGenerator`` hands out one
ObjectRef a yield, in yield order, as they arrive.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple, TYPE_CHECKING

from ray_tpu._private.config import config
from ray_tpu._private.ids import ObjectID, TaskID
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.rpc import get_client
from ray_tpu.exceptions import GetTimeoutError
from ray_tpu.observability import schema as obs_schema
from ray_tpu.observability import tracing as obs_tracing

if TYPE_CHECKING:  # pragma: no cover
    from ray_tpu._private.core_worker import CoreWorker

logger = logging.getLogger(__name__)


class StreamEnd(Exception):
    """Async end-of-stream marker: ``anext_ref`` cannot raise
    StopIteration (PEP 479 turns it into a bare RuntimeError inside a
    coroutine), so exhaustion surfaces as this instead."""


class _StreamState:
    """Caller-side bookkeeping for one streaming task."""

    def __init__(self) -> None:
        self.cv = threading.Condition()
        self.arrived: Dict[int, ObjectID] = {}  # yield index -> oid
        self.next_index = 0  # next index to hand to the consumer
        self.total: Optional[int] = None  # set by StreamingDone
        self.error: Optional[BaseException] = None
        # async consumers (the serve proxy loop) park a thread-safe
        # waker here instead of blocking a thread on the cv; fired on
        # every state change alongside the cv notify
        self.wakers: List[Callable[[], None]] = []

    def notify_locked(self) -> None:
        """State changed (yield arrived / done / error / abandon): wake
        every consumer. Must be called with ``cv`` held. Wakers are
        drained — an async consumer re-registers per wait."""
        self.cv.notify_all()
        wakers, self.wakers = self.wakers, []
        for w in wakers:
            try:
                w()
            except Exception:  # noqa: BLE001 — a dead consumer loop
                pass  # must not break delivery to the live ones


class ObjectRefGenerator:
    """Iterator over a streaming task's yields (reference:
    python/ray/_raylet.pyx ObjectRefGenerator). Each ``__next__`` returns
    an ObjectRef as soon as that yield has been produced — the task may
    still be running."""

    def __init__(self, core: "CoreWorker", task_id: TaskID, state: _StreamState):
        self._core = core
        self._task_id = task_id
        self._state = state
        self._close_cb = None
        self._close_fired = False

    def _set_close_callback(self, cb) -> None:
        """Invoked exactly once when the stream terminates (exhausted,
        errored, or dropped) — e.g. Serve uses it to release the routing
        slot the stream occupies."""
        self._close_cb = cb

    def _fire_close(self) -> None:
        if self._close_fired:
            return
        self._close_fired = True
        if self._close_cb is not None:
            try:
                self._close_cb()
            except Exception:  # noqa: BLE001
                pass

    def __iter__(self) -> "ObjectRefGenerator":
        return self

    def __next__(self) -> ObjectRef:
        return self._next(timeout=None)

    def next_ref(self, timeout: Optional[float] = None) -> ObjectRef:
        """Like ``next()`` but with a timeout (raises GetTimeoutError)."""
        return self._next(timeout=timeout)

    def _take_locked(self) -> Optional[ObjectRef]:
        """One non-blocking state inspection (``st.cv`` held): returns
        the next ref, raises the stream's terminal error/StopIteration,
        or returns None when the consumer must wait."""
        st = self._state
        if st.next_index in st.arrived:
            oid = st.arrived.pop(st.next_index)
            st.next_index += 1
            return ObjectRef(oid, owner_addr=self._core.address)
        if st.error is not None:
            self._core._streams.pop(self._task_id, None)
            self._fire_close()
            raise st.error
        if st.total is not None and st.next_index >= st.total:
            self._core._streams.pop(self._task_id, None)
            self._fire_close()
            raise StopIteration
        return None

    def _next(self, timeout: Optional[float]) -> ObjectRef:
        st = self._state
        deadline = None if timeout is None else time.monotonic() + timeout
        with st.cv:
            while True:
                ref = self._take_locked()
                if ref is not None:
                    return ref
                remaining = None if deadline is None else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    raise GetTimeoutError(
                        f"no yield from streaming task {self._task_id.hex()[:12]} in time"
                    )
                st.cv.wait(timeout=remaining if remaining is not None else 1.0)

    async def anext_ref(self, timeout: Optional[float] = None) -> ObjectRef:
        """Async ``next_ref``: waits on the consumer's event loop without
        parking a thread per stream (the serve proxy serves hundreds of
        concurrent streams off one loop). Raises GetTimeoutError on
        timeout and :class:`StreamEnd` on exhaustion (StopIteration
        cannot cross a coroutine boundary)."""
        import asyncio

        st = self._state
        loop = asyncio.get_event_loop()
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with st.cv:
                try:
                    ref = self._take_locked()
                except StopIteration:
                    raise StreamEnd() from None
                if ref is not None:
                    return ref
                remaining = None if deadline is None \
                    else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    raise GetTimeoutError(
                        f"no yield from streaming task "
                        f"{self._task_id.hex()[:12]} in time")
                fut = loop.create_future()

                def _wake(fut=fut):
                    def _set():
                        if not fut.done():
                            fut.set_result(True)
                    loop.call_soon_threadsafe(_set)

                st.wakers.append(_wake)
            try:
                # bounded re-check even with no deadline: a waker lost to
                # a dying producer must not hang the consumer forever
                await asyncio.wait_for(
                    fut, timeout=min(remaining, 1.0)
                    if remaining is not None else 1.0)
            except asyncio.TimeoutError:
                pass  # loop re-checks state / deadline

    def completed(self) -> bool:
        st = self._state
        with st.cv:
            return st.error is not None or (
                st.total is not None and st.next_index >= st.total
            )

    def __del__(self):
        # dropping the generator abandons the stream: undelivered yields
        # are freed and the producer's next push is refused (the worker
        # then stops producing) — without this a dropped generator pins
        # every yield for the life of the driver
        try:
            self._fire_close()
            abandon = getattr(self._core, "_abandon_stream", None)
            if abandon is not None:
                abandon(self._task_id)
        except Exception:  # noqa: BLE001 — GC context
            pass

    def __repr__(self) -> str:
        return f"ObjectRefGenerator(task={self._task_id.hex()[:12]})"


# ======================================================================
# Producer side: the executing worker's end of the transport
# ======================================================================

# a sender's thread with nothing to send for this long ends; the next
# hand-over starts another (a pooled worker outlives many callers)
_SENDER_IDLE_S = 30.0
_DONE = "done"


class OutStream:
    """One streaming task's account with its sender. Fields are read and
    written under the sender's lock."""

    __slots__ = ("task_bin", "unacked", "pending", "ok", "error", "done")

    def __init__(self, task_bin: bytes):
        self.task_bin = task_bin
        self.unacked = 0  # items handed over and not yet acknowledged
        self.pending = 0  # the caller's unconsumed buffer, as last acked
        self.ok = True  # False: the consumer abandoned the stream
        self.error: Optional[BaseException] = None  # its call failed
        self.done = False  # its StreamingDone went out (or failed)

    def acked(self, rep: dict) -> None:
        """The caller's answer for this stream: ``{ok, pending}``."""
        self.pending = rep.get("pending", 0)
        self.ok = self.ok and rep.get("ok", True)


class StreamSender:
    """Forwards what this process's streams yield for ONE caller.

    Self-clocked: each time its thread is free it sends everything that
    was handed over since it last did, in hand-over order, as one
    ``StreamingYield`` call. No timer and no target batch: the batch is
    whatever gathered while the previous call was out, and an item that
    finds the thread idle leaves at once, alone."""

    def __init__(self, addr: Tuple[str, int]):
        self._addr = addr
        self._lock = threading.Lock()
        self._handed = threading.Condition(self._lock)  # wakes the thread
        self._acked = threading.Condition(self._lock)  # wakes producers
        # (stream, (task_bin, index, kind, payload), payload bytes); a
        # stream's end is (stream, (task_bin, count, _DONE, error), 0)
        self._queue: List[tuple] = []
        self._running = False
        self.calls = 0  # StreamingYield calls made
        self.items = 0  # items they carried

    def put(self, stream: OutStream, index: int, kind: str, payload,
            size: int) -> bool:
        """Hand over one item and return: True, or False once the consumer
        has abandoned the stream. Raises what failed a call that carried
        this stream's items. Blocks only while the stream is
        ``streaming_generator_buffer_size`` ahead of its consumer: items
        handed over and not acknowledged plus the buffer the last ack
        reported (reference: generator_backpressure_num_objects)."""
        limit = config.streaming_generator_buffer_size
        while True:
            with self._lock:
                if stream.error is not None:
                    raise stream.error
                if not stream.ok:
                    return False
                if stream.unacked + stream.pending < limit:
                    self._enqueue(
                        stream, (stream.task_bin, index, kind, payload), size)
                    stream.unacked += 1
                    return True
                if stream.unacked:  # an ack is due, and it wakes us
                    self._acked.wait(1.0)
                    continue
            # the buffer is deep at the caller and nothing of this stream
            # is in flight: no ack will say when it drains. Ask (slow path)
            time.sleep(0.02)
            try:
                rep = get_client(self._addr).call(
                    "StreamingCredit", task_id_bin=stream.task_bin,
                    timeout=30)
            except Exception:  # noqa: BLE001 — the next call will raise
                rep = {"pending": 0}
            with self._lock:
                stream.acked(rep)

    def finish(self, stream: OutStream, count: int,
               error: Optional[bytes], timeout: float = 65.0) -> None:
        """Send the stream's StreamingDone behind its last item and wait
        until it is out: the task's reply must not overtake its items."""
        deadline = time.monotonic() + timeout
        with self._lock:
            self._enqueue(stream, (stream.task_bin, count, _DONE, error), 0)
            while not stream.done:
                left = deadline - time.monotonic()
                if left <= 0:
                    break  # the task's reply carries the same count
                self._acked.wait(left)

    def _enqueue(self, stream: OutStream, item: tuple, size: int) -> None:
        self._queue.append((stream, item, size))
        if self._running:
            self._handed.notify()
        else:
            self._running = True
            threading.Thread(target=self._run, daemon=True,
                             name=f"stream-sender-{self._addr[1]}").start()

    def _run(self) -> None:
        while True:
            with self._lock:
                if not self._queue:
                    self._handed.wait(_SENDER_IDLE_S)
                    if not self._queue:
                        self._running = False
                        return
                batch, self._queue = self._queue, []
            try:
                self._send(batch)
            except Exception as e:  # noqa: BLE001 — never strand a producer
                # on a thread that died: fail every stream of the batch
                logger.exception("stream sender to %s failed", self._addr)
                with self._lock:
                    for stream, _, _ in batch:
                        stream.error = stream.error or e
                        stream.done = True
                    self._acked.notify_all()

    def _send(self, batch: List[tuple]) -> None:
        # the items of a stream its consumer has dropped stay behind
        live = [e for e in batch if e[1][2] != _DONE and e[0].ok]
        if live:
            try:
                with obs_tracing.device_span(
                        obs_schema.WORKER_STREAM_RPC, items=len(live),
                        bytes=sum(e[2] for e in live)):
                    replies = get_client(self._addr).call(
                        "StreamingYield", items=[e[1] for e in live],
                        timeout=60)
                self._settle(live, replies or {}, None)
            except Exception as e:  # noqa: BLE001 — raised in every
                # generator whose items the failed call carried
                self._settle(live, {}, e)
        for stream, (task_bin, count, kind, error), _ in batch:
            if kind != _DONE:
                continue
            try:
                get_client(self._addr).call(
                    "StreamingDone", task_id_bin=task_bin, count=count,
                    error=error, timeout=60)
            except Exception:  # noqa: BLE001 — the reply carries the same
                pass
            with self._lock:
                stream.done = True
                self._acked.notify_all()

    def _settle(self, sent: List[tuple], replies: dict,
                error: Optional[BaseException]) -> None:
        """Book a call's outcome to the streams whose items it carried."""
        with self._lock:
            for stream, _, _ in sent:
                stream.unacked -= 1
                if error is not None:
                    stream.error = error
                    continue
                stream.acked(replies.get(stream.task_bin) or {})
            if error is None:
                self.calls += 1
                self.items += len(sent)
            self._acked.notify_all()


_senders: Dict[Tuple[str, int], StreamSender] = {}
_senders_lock = threading.Lock()


def sender_for(addr: Tuple[str, int]) -> StreamSender:
    """Process-wide, one per caller address (as ``rpc.get_client``): every
    stream this process produces for that caller shares it."""
    addr = tuple(addr)
    with _senders_lock:
        s = _senders.get(addr)
        if s is None:
            s = _senders[addr] = StreamSender(addr)
        return s


def send_stats() -> Dict[str, int]:
    """This process as a producer: the ``StreamingYield`` calls it has made
    and the items they carried (``items / calls`` is how much gathers
    while a call is out)."""
    with _senders_lock:
        senders = list(_senders.values())
    return {"stream_calls": sum(s.calls for s in senders),
            "stream_items_sent": sum(s.items for s in senders)}
