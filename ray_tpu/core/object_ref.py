"""ObjectRef: a future naming an object in the cluster.

Reference parity: python/ray/_raylet.pyx ObjectRef +
ObjectRefGenerator (streaming returns, _raylet.pyx:1067).
"""

from __future__ import annotations

import threading
from collections import deque

from ray_tpu.core.ids import ObjectID


def _client():
    from ray_tpu.core.context import get_client

    return get_client()


# ----------------------------------------------------------------------
# per-process reference counting (reference: reference_counter.h — local
# counts per process; 0->1 / 1->0 transitions flow to the owner/head)
# ----------------------------------------------------------------------
_rc_lock = threading.Lock()
_rc_counts: dict[bytes, int] = {}
_rc_events: list[tuple[bytes, bool]] = []  # (id, True=register / False=release)
# ids of refs that were finalized and are not counted down yet. ``ObjectRef.__del__`` runs wherever
# the collector runs, which is also INSIDE a holder of ``_rc_lock`` on the same thread: a collection
# that began under ``local_ref_count`` finalized a ref whose ``_decref`` then waited for the lock
# its own thread held, for ever, and every later ref of the process behind it (a whole test run
# hung so, PR 40; PR 31's and PR 34's "every process asleep" have the same shape). So a finalizer
# takes no lock: it appends here (atomic), and whoever next takes the lock counts down first.
_rc_dead: deque[bytes] = deque()
_rc_enabled = True
_ref_sink = threading.local()  # active serialization sinks (serialize())


def set_ref_counting(enabled: bool):
    global _rc_enabled
    _rc_enabled = enabled


def push_ref_sink(sink: list):
    stack = getattr(_ref_sink, "stack", None)
    if stack is None:
        stack = _ref_sink.stack = []
    stack.append(sink)
    return len(stack) - 1


def pop_ref_sink(token: int):
    stack = getattr(_ref_sink, "stack", None)
    if stack and len(stack) - 1 == token:
        stack.pop()


def _count_down_the_dead():
    """Apply the finalized refs' decrements, in the order they died. The caller holds ``_rc_lock``."""
    while _rc_dead:
        try:
            k = _rc_dead.popleft()
        except IndexError:  # another holder-to-be cannot race us (we hold the lock); a finalizer only appends
            return
        c = _rc_counts.get(k)
        if c is None:
            continue
        if c <= 1:
            del _rc_counts[k]
            _rc_events.append((k, False))
        else:
            _rc_counts[k] = c - 1


def _incref(obj_id: ObjectID):
    if not _rc_enabled:
        return
    try:
        k = obj_id.binary()
        with _rc_lock:
            _count_down_the_dead()
            c = _rc_counts.get(k, 0)
            _rc_counts[k] = c + 1
            if c == 0:
                _rc_events.append((k, True))
    except Exception:
        pass


def _decref(obj_id: ObjectID):
    if not _rc_enabled:
        return
    try:
        _rc_dead.append(obj_id.binary())
    except Exception:
        pass  # interpreter teardown


def drain_ref_events() -> list[tuple[bytes, bool]]:
    with _rc_lock:
        _count_down_the_dead()
        ev, _rc_events[:] = list(_rc_events), []
        return ev


def local_ref_count(obj_id: ObjectID) -> int:
    k = obj_id.binary()
    with _rc_lock:
        _count_down_the_dead()
        return _rc_counts.get(k, 0)


_note_hint = None  # lazily bound direct.note_hint (avoids per-ref import)
_get_hint = None  # lazily bound direct.get_hint
_mark_serialized = None  # lazily bound direct.mark_serialized_out


class ObjectRef:
    __slots__ = ("id", "_owner_hint", "__weakref__")

    def __init__(self, obj_id: ObjectID, owner_hint: str | None = None):
        self.id = obj_id
        self._owner_hint = owner_hint
        if owner_hint is not None:
            # remember who owns this object so get/free/borrow events can
            # go straight to the owner (core/direct.py ownership model)
            global _note_hint, _get_hint
            if _note_hint is None:
                from ray_tpu.core.direct import get_hint as _gh
                from ray_tpu.core.direct import note_hint as _nh

                _note_hint, _get_hint = _nh, _gh
            _note_hint(obj_id.binary(), owner_hint)
        _incref(obj_id)

    def __del__(self):
        _decref(self.id)

    def hex(self) -> str:
        return self.id.hex()

    def binary(self) -> bytes:
        return self.id.binary()

    def task_id(self):
        return self.id.task_id()

    def get(self, timeout: float | None = None):
        return _client().get_object(self.id, timeout=timeout)

    def wait(self, timeout: float | None = None) -> bool:
        return _client().wait_ready([self.id], num_returns=1, timeout=timeout)[0] != []

    def future(self):
        """concurrent.futures.Future view of this ref."""
        import concurrent.futures

        fut = concurrent.futures.Future()

        def _done(value, err):
            if err is not None:
                fut.set_exception(err)
            else:
                fut.set_result(value)

        _client().add_done_callback(self.id, _done)
        return fut

    def __await__(self):
        import asyncio

        return asyncio.wrap_future(self.future()).__await__()

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and self.id == other.id

    def __hash__(self):
        return hash(self.id)

    def __repr__(self):
        return f"ObjectRef({self.id.hex()[:16]})"

    def __reduce__(self):
        # Refs crossing a process boundary are borrowed: the receiving
        # process's __init__ registers its local count, and an active
        # serialization sink (serialize()) records the ref so the carrying
        # container/message pins it meanwhile (reference:
        # reference_counter.h borrow protocol).
        stack = getattr(_ref_sink, "stack", None)
        if stack:
            stack[-1].append(self.id)
        global _mark_serialized
        if _mark_serialized is None:
            try:
                from ray_tpu.core.direct import mark_serialized_out as _ms

                _mark_serialized = _ms
            except ImportError:  # partial teardown
                _mark_serialized = lambda _k: None  # noqa: E731
        # if we own this object, the owner store must now wait for the
        # borrow-release instead of the short grace timer
        _mark_serialized(self.id.binary())
        hint = self._owner_hint
        if hint is None and _get_hint is not None:
            # a ref rebuilt without its hint attribute (raw-id construction
            # in library code) still travels with the owner it was learned
            # to have in this process
            hint = _get_hint(self.id.binary())
        return (ObjectRef, (self.id, hint))


class ObjectRefGenerator:
    """Iterator over the streamed return refs of a generator task.

    Reference parity: _raylet.pyx ObjectRefGenerator (:1067) — each next()
    yields an ObjectRef whose value is produced incrementally by the task.
    """

    def __init__(self, generator_id: ObjectID):
        self.generator_id = generator_id
        self._index = 0
        self._done = False

    def __iter__(self):
        return self

    def __next__(self) -> ObjectRef:
        return self.next_ref(timeout_s=None)

    def next_ref(self, timeout_s: float | None = None) -> ObjectRef:
        """next() with a bound on the wait for the producer's next item
        (GetTimeoutError on expiry; the stream stays consumable)."""
        if self._done:
            raise StopIteration
        ref = _client().next_generator_item(self.generator_id, self._index, timeout=timeout_s)
        if ref is None:
            self._done = True
            raise StopIteration
        self._index += 1
        return ref if isinstance(ref, ObjectRef) else ObjectRef(ref)

    def __aiter__(self):
        return self

    async def __anext__(self) -> ObjectRef:
        import asyncio

        loop = asyncio.get_running_loop()
        try:
            return await loop.run_in_executor(None, self.__next__)
        except StopIteration:
            raise StopAsyncIteration from None

    def completed(self) -> bool:
        return self._done

    def __reduce__(self):
        return (ObjectRefGenerator, (self.generator_id,))
