"""ObjectRef: a future for a value in the distributed object store.

Parity: python/ray/includes/object_ref.pxi / ray.ObjectRef in the
reference. Refs are cheap value objects (an id); they re-bind to the
current process's core client when unpickled, so they can flow through
task args, actor calls, and nested data structures.
"""

from __future__ import annotations

import collections
import threading
import time
from concurrent.futures import Future
from typing import Any, Optional

from ._private.ids import ObjectID


class ObjectRef:
    __slots__ = ("_id", "_bin", "_owned", "_shared", "_hold", "__weakref__")

    def __init__(self, object_id: ObjectID, *, _owned: bool = False):
        self._id = object_id
        # raw id bytes, cached at construction: wait() pop-loops rebuild
        # the id list of ~n refs per call (O(n^2) per drain), so the
        # per-ref cost there must be one slot load, not an attr+method
        # chain (single_client_wait_1k_refs)
        self._bin = object_id.binary()
        # strong refs this ref keeps alive: owned twins of args the
        # submitter spilled to the object store — when the caller drops
        # its last return ref, the twins die and ownership GC frees the
        # spilled args (the hub defers while the task is in flight)
        self._hold = None
        # Ownership GC (simplified form of the reference's
        # ReferenceCounter, reference_count.h:43): a ref created by this
        # process's own put()/task submission is "owned"; when the LAST
        # local handle to an owned, never-pickled ref dies, the hub
        # frees the object. Pickling makes borrowers possible, so a
        # shared ref is never auto-freed (it leaks like pre-GC — the
        # conservative direction).
        self._owned = _owned
        self._shared = False

    def binary(self) -> bytes:
        return self._bin

    def hex(self) -> str:
        return self._id.hex()

    def __hash__(self):
        return hash(self._id)

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other._id == self._id

    def __repr__(self):
        return f"ObjectRef({self._id.hex()})"

    def __reduce__(self):
        self._shared = True  # a copy may now exist anywhere: never auto-free
        return (_rebuild_ref, (self._id.binary(),))

    def __del__(self):
        if not getattr(self, "_owned", False) or getattr(self, "_shared", True):
            return
        try:
            from ._private import worker

            client = worker._client
            if client is not None and not client._closed:
                client.release_owned(self._bin)
        except Exception:
            pass  # interpreter teardown / connection already gone

    # -- convenience -----------------------------------------------------
    def get(self, timeout: Optional[float] = None) -> Any:
        from ._private import worker

        return worker.get(self, timeout=timeout)

    def future(self) -> Future:
        """A concurrent.futures.Future resolving to the object's value."""
        fut: Future = Future()

        def run():
            try:
                fut.set_result(self.get())
            except Exception as e:  # noqa: BLE001
                fut.set_exception(e)

        threading.Thread(target=run, daemon=True).start()
        return fut

    def __await__(self):
        """Support `await ref` inside async actors."""
        import asyncio

        return asyncio.wrap_future(self.future()).__await__()


def _rebuild_ref(id_bytes: bytes) -> ObjectRef:
    return ObjectRef(ObjectID(id_bytes))


class ObjectRefGenerator:
    """Incrementally-resolved refs from a `num_returns="streaming"` task.

    Parity: the reference's ObjectRefGenerator (_raylet.pyx:280) — sync
    and async iteration over ObjectRefs as the remote generator yields;
    a mid-stream exception surfaces as a final ref whose get() raises.

    One STREAM_NEXT brings every item that is there (``_BATCH`` at the
    most) with its inline value, so a consumer that fell behind a fast
    producer (a served model's tokens, 128 streams at once) catches up
    in one round trip and its ``get`` of a small value sends nothing:
    an item then costs the hub its STREAM_YIELD and no more. Three round
    trips an item held every stream of a process to some 900 items a
    second together (PERF.md section 6, PR 52).
    """

    # items one STREAM_NEXT asks for at the most: what has queued up
    # behind a consumer that fell behind comes in one reply. Flat from
    # 4 on (32 streams of one actor on a CPU: 1400 / 2470 / 2500 /
    # 2560 / 2620 items/s at 1 / 4 / 16 / 64 / 256; PERF.md section 6,
    # PR 52), so the value only has to cover a reader's longest stall
    _BATCH = 64

    def __init__(self, task_id: bytes):
        self._task_id = task_id
        self._idx = 0
        # handed over by the hub and not yet returned: (object id, the
        # producer's yield stamp, the inline value or None, the hub's
        # stamp of the item's STREAM_YIELD)
        self._ready: collections.deque = collections.deque()
        # the producer's stamp of when it yielded the ref last returned
        # (an anchored wall time, tracing.wall_at); None where it sent
        # none. It came with the STREAM_NEXT reply: no message of its own
        self.last_yield_wall: Optional[float] = None
        # the hub's two stamps of that ref, the same way: when it
        # handled the item's STREAM_YIELD, and when it sent the reply
        # that carried the item here. None from a hub that stamps neither
        self.last_hub_wall: Optional[float] = None
        self.last_reply_wall: Optional[float] = None
        # seconds the consumer spent in the STREAM_NEXT round trip that
        # brought the ref last returned; None where the ref came with an
        # earlier one's reply (so a count of these is a count of replies)
        self.last_next_wait_s: Optional[float] = None

    def __iter__(self):
        return self

    def __next__(self) -> ObjectRef:
        from ._private import protocol as P
        from ._private import worker

        client = worker.get_client()
        if not self._ready:
            t0 = time.monotonic()
            reply = client.request(
                P.STREAM_NEXT, {"task_id": self._task_id, "index": self._idx,
                                "batch": self._BATCH}
            )
            if reply.get("end"):
                raise StopIteration
            self._ready.extend(reply["items"])
            self.last_reply_wall = reply.get("t_reply")
            self.last_next_wait_s = time.monotonic() - t0
        else:
            self.last_next_wait_s = None
        # a hub from before it stamped sends three fields an item
        oid, self.last_yield_wall, inline, *t_hub = self._ready.popleft()
        self.last_hub_wall = t_hub[0] if t_hub else None
        self._idx += 1
        if inline is not None:
            # the value came with the reply: ``get`` finds it here
            client.hold_inline(oid, inline)
        return ObjectRef(ObjectID(oid))

    def __aiter__(self):
        return self

    async def __anext__(self) -> ObjectRef:
        import asyncio

        def step():
            try:
                return self.__next__()
            except StopIteration:
                return None

        ref = await asyncio.to_thread(step)
        if ref is None:
            raise StopAsyncIteration
        return ref

    def __reduce__(self):
        return (ObjectRefGenerator, (self._task_id,))
