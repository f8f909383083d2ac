"""Distributed reference-counting / borrower-protocol tests.

Reference test matrix: python/ray/tests/test_reference_counting*.py —
the owner must keep an object alive while any borrower holds a ref,
including refs NESTED inside task args, actor state, and return values
(src/ray/core_worker/reference_counter.h:44).
"""

import time

import numpy as np
import pytest

import ray_tpu


class TestBorrowedRefs:
    def test_nested_ref_in_actor_state_outlives_owner_scope(self, ray_start_regular):
        """The regression behind the collective-group hang: worker A puts
        an object, ships [ref] to an actor, A's local ref dies; a later
        reader must still resolve it through the actor's borrow."""

        @ray_tpu.remote
        class Holder:
            def __init__(self):
                self.refs = None

            def hold(self, refs):
                self.refs = refs
                return True

            def fetch(self):
                return ray_tpu.get(self.refs[0])

        @ray_tpu.remote
        def producer(holder):
            ref = ray_tpu.put(np.arange(1000))
            ray_tpu.get(holder.hold.remote([ref]))
            return True  # ref goes out of scope here

        holder = Holder.remote()
        assert ray_tpu.get(producer.remote(holder))
        time.sleep(0.5)  # let any (buggy) premature free happen
        out = ray_tpu.get(holder.fetch.remote())
        np.testing.assert_array_equal(out, np.arange(1000))

    def test_ref_returned_from_task(self, ray_start_regular):
        """A task returns a ref to an object it owns; the caller must be
        able to read it after the producing worker's frame is gone."""

        @ray_tpu.remote
        def make():
            return [ray_tpu.put(np.ones(500) * 7)]

        (inner,) = ray_tpu.get(make.remote())
        time.sleep(0.5)
        np.testing.assert_array_equal(ray_tpu.get(inner), np.ones(500) * 7)

    def test_freed_object_raises_not_hangs(self, ray_start_regular):
        """Reading a ref whose owner has freed it errors promptly."""

        @ray_tpu.remote
        class Leaker:
            def make_dead_ref(self):
                import ray_tpu as rt
                from ray_tpu._private import worker as wm

                ref = rt.put(np.zeros(10))
                oid = ref.id()
                # simulate full release at the owner (all refs dropped)
                del ref
                wm.global_worker.core.free_object(oid)
                from ray_tpu._private.object_ref import ObjectRef

                return [ObjectRef(oid, owner_addr=wm.global_worker.core.address)]

        leaker = Leaker.remote()
        (dead,) = ray_tpu.get(leaker.make_dead_ref.remote())
        with pytest.raises(Exception):
            ray_tpu.get(dead, timeout=6)

    def test_plain_value_roundtrip_unaffected(self, ray_start_regular):
        @ray_tpu.remote
        def f(x):
            return x + 1

        assert ray_tpu.get(f.remote(41)) == 42


def test_release_work_is_handed_over_without_the_pools_lock():
    """An ObjectRef can die at any allocation, also inside
    ``ThreadPoolExecutor.submit`` (``run_in_executor`` on a proxy's loop, a
    gRPC server), which holds the lock every pool of the process shares. The
    release path runs from that ``__del__``: handing its work over must not
    need that lock, or the thread blocks on itself and every pool with it."""
    import threading
    from concurrent.futures import thread as pools

    from ray_tpu._private.core_worker import _ReleaseWorker

    worker, ran, done = _ReleaseWorker(), [], threading.Event()
    try:
        with pools._global_shutdown_lock:  # where submit builds its thread
            for i in range(3):
                worker.submit(ran.append, i)
            worker.submit(done.set)
            assert done.wait(5), "release work waited for the pools' lock"
        assert ran == [0, 1, 2]  # in the order handed over
        worker.submit(lambda: 1 / 0)  # a failing release ends nothing
        worker.submit(ran.append, 3)
    finally:
        worker.stop(timeout=5)
    assert ran == [0, 1, 2, 3] and not worker._thread.is_alive()


def test_release_after_stop_is_dropped_and_stop_waits_a_bounded_time():
    """``CoreWorker.shutdown`` stops the release thread and waits a bounded
    time for a release in flight; an ``ObjectRef.__del__`` that comes later
    finds the worker stopped: its release is dropped, not queued for ever."""
    import threading
    import time

    from ray_tpu._private.core_worker import _ReleaseWorker

    worker, ran, gate = _ReleaseWorker(), [], threading.Event()
    worker.submit(gate.wait, 10)  # a release RPC that hangs
    worker.submit(ran.append, "before")
    t0 = time.monotonic()
    worker.stop(timeout=0.2)
    assert 0.15 < time.monotonic() - t0 < 2.0 and worker._thread.is_alive()
    worker.submit(ran.append, "after")
    gate.set()
    worker._thread.join(5)
    assert ran == ["before"] and not worker._thread.is_alive()


def test_a_ref_the_collector_finalises_under_the_memory_stores_lock_is_queued(
        ray_start_regular):
    """The cyclic collector runs wherever an allocation tips it: under the
    memory store's lock too (``ObjectID.__hash__`` is Python). A ref in a
    cycle that it finalises there must not be released inline (the release
    path takes that very lock: the thread waited on itself for ever, and
    every ``get`` of the process behind it): it is queued, and let go of
    where the program next lets a ref go or asks for an object."""
    import gc
    import threading

    import ray_tpu
    from ray_tpu._private import object_ref
    from ray_tpu._private import worker as worker_mod

    w = worker_mod.global_worker
    store, counter = w.core.memory_store, w.reference_counter

    class CollectsWhenHashed(type(ray_tpu.put(0)._id)):
        def __hash__(self):  # the collector, tipped under the store's lock
            gc.collect()
            return super().__hash__()

    gc.disable()
    try:
        ref = ray_tpu.put(b"held by a cycle alone")
        oid = ref._id
        cycle = [ref]
        cycle.append(cycle)
        del ref, cycle
        found = []
        looking = threading.Thread(target=lambda: found.append(
            store.contains(CollectsWhenHashed(oid.binary()))), daemon=True)
        looking.start()
        looking.join(10)
    finally:
        gc.enable()
    assert found == [False], "the lookup waited on the lock it holds"
    assert oid in object_ref._orphans and counter.has_reference(oid)
    assert ray_tpu.get(ray_tpu.put(1)) == 1  # asking for an object lets go
    assert oid not in object_ref._orphans and not counter.has_reference(oid)
    assert not store.contains(oid)
