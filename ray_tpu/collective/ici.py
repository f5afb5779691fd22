"""ICI collective backend: XLA-compiled collectives over local mesh devices.

The host-side collective API (collective.py) moves tensors through the shm
object store — the DCN/control plane. When the participating "ranks" are
the chips of one host (one PJRT client), the right data plane is ICI via a
single jitted XLA program; these helpers wrap that for driver-held
per-device arrays. (Inside jit/shard_map, just use lax.psum/all_gather —
see ray_tpu.parallel; this module is for eager host code that owns one
array per chip, e.g. a parameter server pushing to device replicas.)

Reference shape: util/collective/collective_group/nccl_collective_group.py
(a real device backend for the same API) — here the "backend" is XLA +
GSPMD, no NCCL.
"""

from __future__ import annotations

import functools

import numpy as np

from ray_tpu.collective.types import ReduceOp
from ray_tpu.lint import jaxcheck


def _bucket_reduce(W=8, rows=256, cols=1024):
    import jax
    import jax.numpy as jnp

    return (jax.ShapeDtypeStruct((W, rows, cols), jnp.float32),), {}


@jaxcheck.entry(
    name="collective.ici.reduce_stacked",
    shapes={"w8_256x1024": _bucket_reduce},
    # no explicit collective primitives: the all-reduce is GSPMD-inserted
    # by the P('d') -> P() resharding, so the jaxpr must stay collective-
    # free and host-free — exactly what JXC002/JXC005 assert here
    mesh_axes=(),
)
def _reduce_sum_stacked(x):
    return x.sum(axis=0)


_REDUCERS = {
    ReduceOp.SUM: _reduce_sum_stacked,
    ReduceOp.PRODUCT: lambda x: x.prod(axis=0),
    ReduceOp.MIN: lambda x: x.min(axis=0),
    ReduceOp.MAX: lambda x: x.max(axis=0),
}


def _mesh_for(n: int):
    import jax
    from jax.sharding import Mesh

    devices = jax.local_devices()[:n]
    return Mesh(np.asarray(devices), ("d",))


@functools.lru_cache(maxsize=32)
def _reduce_prog(n: int, op: ReduceOp):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh_for(n)
    return jax.jit(
        _REDUCERS[op],
        in_shardings=NamedSharding(mesh, P("d")),
        out_shardings=NamedSharding(mesh, P()),
    )


def _stack(per_device):
    """Per-device arrays -> one [W, ...] array sharded over the 1D mesh
    without leaving the devices."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = len(per_device)
    mesh = _mesh_for(n)
    shape = (n,) + tuple(per_device[0].shape)
    shards = [a[None] for a in per_device]  # [1, ...] views on each device
    return jax.make_array_from_single_device_arrays(
        shape, NamedSharding(mesh, P("d")), shards
    )


def _unstack(replicated, n: int):
    """Replicated output -> the per-device arrays (no copies)."""
    shards = sorted(replicated.addressable_shards, key=lambda s: s.device.id)
    return [s.data for s in shards[:n]]


def allreduce(per_device, op: ReduceOp = ReduceOp.SUM):
    """per_device: list of same-shape jax.Arrays, one per local device.
    Returns the reduced array materialized on every participating device.
    One XLA program; the all-reduce rides ICI."""
    n = len(per_device)
    out = _reduce_prog(n, op)(_stack(per_device))
    return _unstack(out, n)


@functools.lru_cache(maxsize=32)
def _gather_prog(n: int):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh_for(n)
    return jax.jit(
        lambda x: x,
        in_shardings=NamedSharding(mesh, P("d")),
        out_shardings=NamedSharding(mesh, P()),  # resharding = all-gather
    )


def allgather(per_device):
    """Returns on every device the stacked [W, ...] of all inputs."""
    n = len(per_device)
    return _unstack(_gather_prog(n)(_stack(per_device)), n)


@functools.lru_cache(maxsize=32)
def _reducescatter_prog(n: int, op: ReduceOp):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh_for(n)
    return jax.jit(
        _REDUCERS[op],
        in_shardings=NamedSharding(mesh, P("d")),
        out_shardings=NamedSharding(mesh, P("d")),  # shard rows of the result
    )


def reducescatter(per_device, op: ReduceOp = ReduceOp.SUM):
    """Reduce then scatter row-shards back: device i gets rows i*k:(i+1)*k
    of the reduction (inputs' leading dim must divide by world size)."""
    n = len(per_device)
    out = _reducescatter_prog(n, op)(_stack(per_device))
    shards = sorted(out.addressable_shards, key=lambda s: s.device.id)
    return [s.data for s in shards[:n]]


def broadcast(array, n_devices: int):
    """One array -> materialized on each of the first n local devices."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh_for(n_devices)
    out = jax.device_put(array, NamedSharding(mesh, P()))
    return _unstack(out, n_devices)


# ---------------------------------------------------------------------------
# quantized in-program collectives (shard_map bodies)
#
# Unlike everything above (eager helpers over driver-held per-device
# arrays), these run INSIDE a traced shard_map body with a bound axis
# name — they are the explicit collective schedule of the tensor-parallel
# serving hot path (llm/model_runner.py), owned by the runtime instead of
# left implicit in GSPMD.
# ---------------------------------------------------------------------------
def quantized_psum(x, axis_name: str):
    """EQuARX-style int8 all-reduce (arxiv 2506.17615): the all-reduce is
    decomposed into its reduce-scatter + all-gather halves with the bulk
    payload quantized to int8 on the wire for BOTH phases.

    x: [..., H] local partial sum with H % axis_size == 0. Each shard
    splits its partial into `axis_size` chunks along the trailing axis and
    quantizes each chunk symmetrically to int8 with one f32 amax scale per
    chunk row (the kv_quant.py recipe — scale computed from the exact
    vector being shipped, no calibration). An all-to-all routes chunk j's
    int8 partials (plus their tiny f32 scales) to shard j, which
    dequantizes and accumulates its owned chunk EXACTLY in f32, then
    requantizes the reduced chunk once for the int8 all-gather back.

    Wire bytes per shard ≈ 2·(n-1)/n · (|x|·1 byte + scale rows·4 bytes)
    vs 2·(n-1)/n · |x|·itemsize for the fp psum — ~1/2 the ICI bytes at
    bf16 operands, ~1/4 at f32. Quantization error is bounded by the two
    int8 roundings (inner accumulation is exact f32).
    """
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.kv_quant import quantize_heads

    n = jax.lax.psum(1, axis_name)  # static axis size under shard_map
    H = x.shape[-1]
    if H % n:
        raise ValueError(f"quantized_psum needs trailing dim {H} divisible by axis size {n}")
    chunks = x.reshape(x.shape[:-1] + (n, H // n))  # [..., n, C]
    q, s = quantize_heads(chunks)  # int8 [..., n, C], f32 [..., n]
    d = q.ndim - 2
    # route chunk j (int8 + scale) to shard j: the reduce-scatter half
    qx = jax.lax.all_to_all(q, axis_name, split_axis=d, concat_axis=d, tiled=True)
    sx = jax.lax.all_to_all(s, axis_name, split_axis=s.ndim - 1, concat_axis=s.ndim - 1, tiled=True)
    owned = jnp.sum(qx.astype(jnp.float32) * sx[..., None], axis=d)  # exact f32 accumulate
    # one requant of the reduced chunk, then the int8 all-gather half
    q2, s2 = quantize_heads(owned)
    qf = jax.lax.all_gather(q2, axis_name, axis=d, tiled=False)  # [..., n, C]
    sf = jax.lax.all_gather(s2, axis_name, axis=s2.ndim, tiled=False)  # [..., n]
    out = (qf.astype(jnp.float32) * sf[..., None]).reshape(x.shape)
    return out.astype(x.dtype)


# primitives that put bytes on the wire. Per-chip ring wire bytes as a
# multiple of the traced OPERAND's bytes: all-reduce moves 2(n-1)/n of
# its (full-size) operand, one-directional exchanges over full-size
# operands (all-to-all, reduce-scatter) move (n-1)/n — but all_gather's
# operand is the PRE-gather local shard, of which a ring ships (n-1)
# full copies per chip, so it gets n x the (n-1)/n factor.
_WIRE_PRIMS = {"psum": 2.0, "all_to_all": 1.0, "psum_scatter": 1.0, "reduce_scatter": 1.0}


def _wire_factor(prim: str, axis_size: int) -> float:
    if prim == "all_gather":
        return float(axis_size - 1)
    return _WIRE_PRIMS[prim] * (axis_size - 1) / max(axis_size, 1)


def collective_wire_report(closed_jaxpr, axis_size: int) -> dict:
    """Per-execution ICI wire bytes of every collective in a traced
    program, by operand dtype — the bytes-on-the-wire evidence for the
    quantized-collective A/B (CPU cannot show the ICI wall-clock win, so
    the jaxpr IS the measurement). Descends scan bodies multiplying by
    the trip count, so a per-layer psum inside the layer scan counts L
    times. Returns {"bytes_by_dtype": {dtype: bytes}, "total_bytes": n,
    "ops": [{prim, dtype, shape, count, wire_bytes}, ...]}."""
    import math as _math

    from jax.extend import core as _core

    by_dtype: dict[str, float] = {}
    ops: list[dict] = []

    def _walk(jx, mult: float):
        for eqn in jx.eqns:
            pname = eqn.primitive.name
            if (pname in _WIRE_PRIMS or pname == "all_gather") and eqn.invars:
                for iv in eqn.invars:
                    aval = getattr(iv, "aval", None)
                    if aval is None:
                        continue
                    try:
                        nbytes = int(_math.prod(aval.shape)) * aval.dtype.itemsize
                    except (AttributeError, TypeError):
                        continue
                    wire = nbytes * _wire_factor(pname, axis_size) * mult
                    dt = str(aval.dtype)
                    by_dtype[dt] = by_dtype.get(dt, 0.0) + wire
                    ops.append({
                        "prim": pname, "dtype": dt, "shape": list(aval.shape),
                        "count": mult, "wire_bytes": int(wire),
                    })
            sub_mult = mult
            if pname == "scan":
                sub_mult = mult * int(eqn.params.get("length", 1))
            for v in eqn.params.values():
                for item in v if isinstance(v, (tuple, list)) else (v,):
                    if isinstance(item, _core.ClosedJaxpr):
                        _walk(item.jaxpr, sub_mult)
                    elif isinstance(item, _core.Jaxpr):
                        _walk(item, sub_mult)

    _walk(closed_jaxpr.jaxpr, 1.0)
    return {
        "bytes_by_dtype": {k: int(v) for k, v in sorted(by_dtype.items())},
        "total_bytes": int(sum(by_dtype.values())),
        "ops": ops,
    }
