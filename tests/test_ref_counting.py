"""Reference-counting object GC tests (reference: reference_counter.h —
local counts per process, borrow protocol for refs crossing boundaries,
pins for in-flight task arguments)."""

import gc
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core import context


def _wait_freed(client, oid, timeout=8.0) -> bool:
    deadline = time.time() + timeout
    while time.time() < deadline:
        gc.collect()
        if not client.store.contains(oid):
            return True
        time.sleep(0.1)
    return False


def _wait_alive(client, oid, hold_s=1.2) -> bool:
    deadline = time.time() + hold_s
    while time.time() < deadline:
        if not client.store.contains(oid):
            return False
        time.sleep(0.1)
    return True


def test_put_object_freed_when_last_ref_dropped(rt_start):
    client = context.get_client()
    ref = ray_tpu.put(np.zeros(100_000))
    oid = ref.id
    assert client.store.contains(oid)
    assert _wait_alive(client, oid)  # held -> stays
    del ref
    assert _wait_freed(client, oid)


def test_task_output_freed_and_kept(rt_start):
    client = context.get_client()

    @ray_tpu.remote
    def produce():
        return np.ones(50_000)

    ref = produce.remote()
    assert float(ray_tpu.get(ref)[0]) == 1.0
    oid = ref.id
    assert _wait_alive(client, oid)
    assert float(ray_tpu.get(ref)[0]) == 1.0  # still reachable while held
    del ref
    assert _wait_freed(client, oid)


def test_inflight_task_arg_pinned_after_driver_drop(rt_start):
    """The classic race: pass a ref to a slow task and immediately drop
    the driver's handle — the spec pin must keep the argument alive."""

    @ray_tpu.remote
    def slow_sum(arr, delay):
        import time as _t

        _t.sleep(delay)
        return float(arr.sum())

    ref = ray_tpu.put(np.ones(200_000))
    out = slow_sum.remote(ref, 2.0)
    del ref
    gc.collect()
    assert ray_tpu.get(out, timeout=60) == 200_000.0


def test_contained_ref_cascade(rt_start):
    """An object pickled inside another stays alive while the container
    lives anywhere, and cascades free afterwards."""
    client = context.get_client()
    inner = ray_tpu.put(np.full(60_000, 7.0))
    inner_id = inner.id
    outer = ray_tpu.put({"payload": inner, "tag": "container"})
    outer_id = outer.id
    del inner
    gc.collect()
    assert _wait_alive(client, inner_id)  # container pins it
    got = ray_tpu.get(outer)
    assert float(ray_tpu.get(got["payload"])[0]) == 7.0
    del got
    del outer
    assert _wait_freed(client, outer_id)
    assert _wait_freed(client, inner_id)  # cascade


def test_worker_held_ref_counts_as_holder(rt_start):
    client = context.get_client()

    @ray_tpu.remote
    class Holder:
        def __init__(self):
            self.ref = None

        def grab(self, wrapped):
            # nested refs are NOT resolved (reference semantics): the
            # actor borrows the ObjectRef itself
            self.ref = wrapped[0]
            return True

        def peek(self):
            import ray_tpu as rt

            return float(rt.get(self.ref)[0])

        def drop(self):
            self.ref = None
            import gc as _gc

            _gc.collect()
            return True

    h = Holder.remote()
    ref = ray_tpu.put(np.full(80_000, 3.0))
    oid = ref.id
    assert ray_tpu.get(h.grab.remote([ref]))
    del ref
    gc.collect()
    time.sleep(1.5)  # driver released; actor's borrow must hold it
    assert client.store.contains(oid), "worker-held object freed prematurely"
    assert ray_tpu.get(h.peek.remote()) == 3.0
    assert ray_tpu.get(h.drop.remote())
    assert _wait_freed(client, oid)


def test_ref_counting_disabled_flag():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, _system_config={"object_ref_counting": False})
    try:
        client = context.get_client()
        ref = ray_tpu.put(np.zeros(10_000))
        oid = ref.id
        del ref
        gc.collect()
        time.sleep(1.0)
        assert client.store.contains(oid)  # nothing freed when disabled
    finally:
        ray_tpu.shutdown()


def test_a_ref_finalized_under_the_count_lock_does_not_wait_for_it():
    """A finalizer runs wherever the collector does, also on a thread that holds the count's lock (a collection that began
    inside ``local_ref_count`` hung a whole test run, PR 40): it takes no lock, and the next holder counts it down."""
    from ray_tpu.core import object_ref as oref
    from ray_tpu.core.ids import ObjectID

    oref.set_ref_counting(True)
    oid = ObjectID.from_random()
    first, second = oref.ObjectRef(oid), oref.ObjectRef(oid)
    assert oref.local_ref_count(oid) == 2
    with oref._rc_lock:  # as ``_ref_gc_loop`` holds it when a collection starts under it
        del first  # its ``__del__`` runs here; until PR 40 it waited for the lock this thread holds
    assert oref.local_ref_count(oid) == 1
    del second
    assert oref.local_ref_count(oid) == 0 and (oid.binary(), False) in oref.drain_ref_events()
