"""Serialization: cloudpickle for closures + pickle5 out-of-band buffers.

TPU-native equivalent of the reference's serialization stack (reference:
python/ray/_private/serialization.py — cloudpickle for code, Pickle5
out-of-band buffers for zero-copy numpy, ObjectRef-in-object tracking).

Large contiguous buffers (numpy arrays, arrow buffers) are extracted
out-of-band so they can live in shared memory and be mapped zero-copy by
workers. Host-side jax.Arrays are converted to numpy on serialize.
"""

from __future__ import annotations

import pickle
from dataclasses import dataclass, field

import cloudpickle


@dataclass
class Serialized:
    header: bytes
    buffers: list = field(default_factory=list)  # list of bytes/memoryview
    # ObjectRefs found inside the object (for borrowed-ref tracking;
    # reference: reference_counter.h borrow protocol).
    contained_refs: list = field(default_factory=list)

    def total_size(self) -> int:
        return len(self.header) + sum(len(b.raw() if hasattr(b, "raw") else b) for b in self.buffers)


# exact types that cannot contain ObjectRefs or closures: the C pickler
# handles them directly and the cloudpickle sink machinery is pure
# overhead (it dominated small puts)
_FAST_TYPES = frozenset({bytes, bytearray, str, int, float, bool, type(None)})


def serialize(obj) -> Serialized:
    t = type(obj)
    if t in _FAST_TYPES:
        return Serialized(header=pickle.dumps(obj, protocol=5))
    if t.__name__ == "ndarray" and t.__module__ == "numpy" and not obj.dtype.hasobject:
        fast_buffers: list[pickle.PickleBuffer] = []
        header = pickle.dumps(obj, protocol=5, buffer_callback=lambda b: fast_buffers.append(b) or False)
        return Serialized(header=header, buffers=[b.raw() for b in fast_buffers])
    return _serialize_general(obj)


def _serialize_general(obj) -> Serialized:
    from ray_tpu.core import object_ref as _oref

    buffers: list[pickle.PickleBuffer] = []
    contained: list = []
    _track_contained_refs(obj, contained)

    def cb(buf: pickle.PickleBuffer):
        buffers.append(buf)
        return False  # out-of-band

    # pickle-time sink: ObjectRef.__reduce__ reports every ref actually
    # serialized (incl. ones nested in arbitrary objects the pre-scan
    # cannot see) — the union drives borrow/pin bookkeeping
    sink: list = []
    token = _oref.push_ref_sink(sink)
    try:
        header = cloudpickle.dumps(obj, protocol=5, buffer_callback=cb)
    finally:
        _oref.pop_ref_sink(token)
    seen = {r.id.binary() for r in contained}
    for oid in sink:
        if oid.binary() not in seen:
            seen.add(oid.binary())
            contained.append(_oref.ObjectRef(oid))
    return Serialized(header=header, buffers=[b.raw() for b in buffers], contained_refs=contained)


def deserialize(header: bytes, buffers) -> object:
    return pickle.loads(header, buffers=buffers)


def deserialize_s(s: Serialized) -> object:
    return deserialize(s.header, s.buffers)


def _track_contained_refs(obj, out: list, depth: int = 0):
    """Complete tracking happens at pickle time: ObjectRef.__reduce__
    reports into the active serialization sink (see object_ref._REF_SINK),
    catching refs nested inside arbitrary objects. This pre-scan remains
    for the cheap shallow cases so contained_refs is populated even for
    values that skip the sink path."""
    if depth > 3:
        return
    from ray_tpu.core.object_ref import ObjectRef

    if isinstance(obj, ObjectRef):
        out.append(obj)
    elif isinstance(obj, (list, tuple, set)):
        for x in obj:
            _track_contained_refs(x, out, depth + 1)
    elif isinstance(obj, dict):
        for v in obj.values():
            _track_contained_refs(v, out, depth + 1)
