"""ObjectRef — the future/handle for a ray_tpu object.

Reference: python/ray/includes/object_ref.pxi and src/ray/common/id.h.
An ObjectRef carries its id plus owner metadata (the address of the worker
that owns the object's lifetime — reference ownership model:
src/ray/core_worker/reference_counter.h:44). Serializing a ref through a
task argument registers a borrow with the owner.
"""

from __future__ import annotations

import collections
import gc
import threading
from typing import Any, Optional, Tuple

from ray_tpu._private.ids import ObjectID

# A ref that dies in a reference cycle is finalised by the cyclic collector,
# which runs wherever an allocation tipped it: inside the release path's own
# critical sections too (``ObjectID.__hash__`` under the memory store's
# lock, the borrow and lineage tables, the store client), whose plain locks
# the very same thread then holds. Releasing inline there waits on itself
# for ever, and every ``get`` of the process behind it (PR 34: a serve
# driver that never returned; ROADMAP Design `undelivered-result-hang`). So a
# ref the collector finalises is only queued, and released where the program
# next lets a ref go (`__del__` outside a collection) or asks for an object.
_collector = threading.local()  # .running: this thread is inside a collection
_orphans: collections.deque = collections.deque()  # append / popleft: atomic


def _collector_phase(phase: str, info: dict) -> None:
    _collector.running = phase == "start"


gc.callbacks.append(_collector_phase)


def release_orphans() -> None:
    """Let go of the refs the cyclic collector finalised; a no-op inside a
    collection, which may be anywhere."""
    if not _orphans or getattr(_collector, "running", False):
        return
    from ray_tpu._private import worker as _worker_mod

    w = _worker_mod.global_worker
    while _orphans:
        try:
            oid = _orphans.popleft()
        except IndexError:
            return
        if w is not None and w.connected:
            w.reference_counter.remove_local_reference(oid)


class ObjectRef:
    __slots__ = ("_id", "_owner_addr", "_call_site", "__weakref__")

    def __init__(
        self,
        object_id: ObjectID,
        owner_addr: Optional[Tuple[str, int]] = None,
        call_site: str = "",
    ) -> None:
        self._id = object_id
        self._owner_addr = owner_addr
        self._call_site = call_site
        # Register with the current worker's reference counter, if connected.
        from ray_tpu._private import worker as _worker_mod

        w = _worker_mod.global_worker
        if w is not None and w.connected:
            w.reference_counter.add_local_reference(self._id)
            # Borrowed ref (constructed from a deserialized payload in a
            # process that doesn't own it): register with the owner so it
            # keeps the object alive (reference_counter.h:44 borrowers).
            if owner_addr is not None:
                core = getattr(w, "core", None)
                if core is not None and hasattr(core, "on_ref_created"):
                    core.on_ref_created(self._id, tuple(owner_addr))

    # -- identity ---------------------------------------------------------
    def id(self) -> ObjectID:
        return self._id

    def binary(self) -> bytes:
        return self._id.binary()

    def hex(self) -> str:
        return self._id.hex()

    def task_id(self):
        return self._id.task_id()

    @property
    def owner_address(self) -> Optional[Tuple[str, int]]:
        return self._owner_addr

    # -- lifecycle --------------------------------------------------------
    def __del__(self) -> None:
        try:
            if getattr(_collector, "running", False):
                _orphans.append(self._id)
                return
            from ray_tpu._private import worker as _worker_mod

            release_orphans()
            w = _worker_mod.global_worker
            if w is not None and w.connected:
                w.reference_counter.remove_local_reference(self._id)
        except Exception:
            pass  # __del__ during interpreter teardown: modules half-gone

    # -- pickling: refs travel with owner metadata ------------------------
    def __reduce__(self):
        return (ObjectRef, (self._id, self._owner_addr, self._call_site))

    # -- conveniences -----------------------------------------------------
    def future(self):
        """Return a concurrent.futures.Future resolved with the value."""
        from ray_tpu._private import worker as _worker_mod

        return _worker_mod.global_worker.core.as_future(self)

    def __await__(self):
        from ray_tpu._private.async_compat import as_asyncio_future

        return as_asyncio_future(self).__await__()

    def __eq__(self, other: object) -> bool:
        return isinstance(other, ObjectRef) and other._id == self._id

    def __hash__(self) -> int:
        return hash(self._id)

    def __repr__(self) -> str:
        return f"ObjectRef({self._id.hex()})"
