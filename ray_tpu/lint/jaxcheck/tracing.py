"""Bucket -> jaxpr: abstract tracing and jaxpr-walking helpers.

Everything here is shape arithmetic — ``jax.make_jaxpr`` over
``ShapeDtypeStruct`` leaves compiles nothing and allocates nothing, so
buckets use production-realistic dimensions (the (8,128) tile math in
JXC006 is meaningless on toy shapes).
"""

from __future__ import annotations

import inspect
import math
import os
import sys
from dataclasses import dataclass
from typing import Any, Iterator


def ensure_trace_env(min_devices: int = 8) -> None:
    """Tracing wants CPU and (for shard_map entries) a multi-device mesh.
    Effective only if jax has not been imported yet — under pytest the
    conftest has already configured an 8-device CPU backend, and a live
    TPU backend is equally fine."""
    if "jax" in sys.modules:
        return
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count={min_devices}"


def _is_array_leaf(x: Any) -> bool:
    import jax

    return isinstance(x, jax.ShapeDtypeStruct) or (
        hasattr(x, "shape") and hasattr(x, "dtype") and not inspect.isclass(x)
    )


@dataclass
class InLeaf:
    arg: str  # parameter name the leaf belongs to
    path: str  # pretty pytree path, e.g. "cache['k']"
    aval: Any  # ShapedArray
    donated: bool


@dataclass
class TracedBucket:
    bucket: str
    jaxpr: Any  # ClosedJaxpr
    in_leaves: list[InLeaf]
    out_avals: list[Any]
    statics: dict[str, Any]  # python-valued params, by name (JXC004 probes these)


def _key_str(k) -> str:
    name = getattr(k, "name", None)
    if name is not None:
        return f".{name}"
    key = getattr(k, "key", None)
    if key is not None:
        return f"[{key!r}]"
    idx = getattr(k, "idx", None)
    if idx is not None:
        return f"[{idx}]"
    return f"[{k}]"


def trace_bucket(spec, bucket: str, overrides: dict[str, Any] | None = None) -> TracedBucket:
    """Trace one registered bucket to a ClosedJaxpr.

    Array leaves (ShapeDtypeStructs / arrays) become traced arguments;
    every other leaf is static, bound by closure — the same split the
    production ``jax.jit(partial(fn, cfg=cfg))`` makes. ``overrides``
    replaces named static parameters (the JXC004 probe path).
    """
    import jax

    args, kwargs = _build(spec, bucket)
    sig = inspect.signature(spec.fn)
    bound = sig.bind(*args, **kwargs)
    bound.apply_defaults()
    if overrides:
        for k, v in overrides.items():
            if k not in bound.arguments:
                raise KeyError(f"{spec.name}: varying param {k!r} not in bucket {bucket!r} args")
            bound.arguments[k] = v

    dyn_leaves: list[Any] = []
    in_leaves: list[InLeaf] = []
    statics: dict[str, Any] = {}
    # per-parameter: flatten, partition into traced leaves and statics
    placements: list[tuple[str, Any, list[tuple[int, Any]]]] = []  # (param, treedef, [(slot, static)])
    for pname, pval in bound.arguments.items():
        leaves_kp, treedef = jax.tree_util.tree_flatten_with_path(pval)
        slots: list[tuple[int, Any]] = []
        for kp, leaf in leaves_kp:
            if _is_array_leaf(leaf):
                slots.append((len(dyn_leaves), None))
                dyn_leaves.append(jax.ShapeDtypeStruct(leaf.shape, leaf.dtype))
                in_leaves.append(InLeaf(
                    arg=pname,
                    path=pname + "".join(_key_str(k) for k in kp),
                    aval=None,  # filled below from the jaxpr invars
                    donated=pname in spec.donate,
                ))
            else:
                slots.append((-1, leaf))
                if not kp:  # whole param is one static leaf
                    statics[pname] = leaf
        placements.append((pname, treedef, slots))

    def rebuilt(flat):
        import jax as _jax

        rebuilt_args = {}
        for pname, treedef, slots in placements:
            leaves = [flat[i] if i >= 0 else s for i, s in slots]
            rebuilt_args[pname] = _jax.tree_util.tree_unflatten(treedef, leaves)
        return rebuilt_args

    def wrapper(*flat):
        ba = rebuilt(list(flat))
        return spec.fn(**ba)

    closed = jax.make_jaxpr(wrapper)(*dyn_leaves)
    for leaf, var in zip(in_leaves, closed.jaxpr.invars):
        leaf.aval = var.aval
    out_avals = [v.aval for v in closed.jaxpr.outvars]
    return TracedBucket(bucket=bucket, jaxpr=closed, in_leaves=in_leaves, out_avals=out_avals, statics=statics)


def _build(spec, bucket: str) -> tuple[tuple, dict]:
    built = spec.shapes[bucket]()
    if isinstance(built, tuple) and len(built) == 2 and isinstance(built[1], dict) and isinstance(built[0], tuple):
        return built
    if isinstance(built, tuple):
        return built, {}
    raise TypeError(f"{spec.name}[{bucket}]: builder must return (args, kwargs) or an args tuple")


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------
def _sub_jaxprs(params: dict) -> Iterator[Any]:
    from jax.extend import core

    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if isinstance(item, core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, core.Jaxpr):
                yield item


def iter_jaxprs(closed) -> Iterator[Any]:
    """Every (sub-)Jaxpr reachable from a ClosedJaxpr: the top level plus
    scan/while/cond/pjit/shard_map/custom_* bodies, recursively. Yields
    raw ``core.Jaxpr`` objects (each its own variable scope)."""
    stack = [closed.jaxpr]
    seen: set[int] = set()
    while stack:
        jx = stack.pop()
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        yield jx
        for eqn in jx.eqns:
            stack.extend(_sub_jaxprs(eqn.params))


def iter_eqns(closed) -> Iterator[Any]:
    for jx in iter_jaxprs(closed):
        yield from jx.eqns


def aval_bytes(aval) -> int:
    try:
        return int(math.prod(aval.shape)) * aval.dtype.itemsize
    except (AttributeError, TypeError):
        return 0


def fmt_aval(aval) -> str:
    try:
        return f"{aval.dtype.name}[{','.join(str(d) for d in aval.shape)}]"
    except AttributeError:
        return str(aval)


def canonical(closed) -> str:
    """Stable text form of a jaxpr for equality comparison (JXC004):
    pretty-printing assigns variable names deterministically per trace,
    so two traces of the same program produce identical strings."""
    return str(closed)
