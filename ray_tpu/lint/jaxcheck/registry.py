"""Entry-point registry: which functions jaxcheck traces, and with what.

An entry is a module-level function plus everything the checker cannot
infer from source: the abstract input shapes production calls it with
(``shapes`` — named buckets, mirroring the engine's pow-2 padding
buckets), which arguments the production ``jax.jit`` donates
(``donate``), which mesh axis names its collectives may use
(``mesh_axes``), and which closure-bound Python scalars vary per request
at runtime (``varying`` — the JXC004 probes).

Bucket builders return ``(args, kwargs)`` exactly as the production
call site passes them, with two conventions:

- array arguments are ``jax.ShapeDtypeStruct`` leaves (build whole
  pytrees with ``jax.eval_shape``) — traced abstractly, never allocated;
- anything else (configs, ints, floats, strings) is STATIC: bound into
  the closure before tracing, mirroring how production binds it via
  ``functools.partial``/default args. A value the production jit traces
  (a per-step scalar) must therefore be given as a 0-d
  ``ShapeDtypeStruct``, not a Python number — that distinction is
  exactly what JXC004 audits.

Registration happens at import of the host module and must stay cheap:
the decorator records the spec and returns the function unchanged;
builders run only when a check runs.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

# Modules whose import registers the production entry points. Kept here —
# not in CLI code — so tests and the CI gate agree on coverage.
ENTRY_MODULES = (
    "ray_tpu.llm.model_runner",
    "ray_tpu.llm.hybrid_runner",
    "ray_tpu.llm.disagg.scatter",
    "ray_tpu.llm.kvplane.quant",
    "ray_tpu.llm.pallas.paged_attn",
    "ray_tpu.llm.spec.drafter",
    "ray_tpu.llm.spec.verify",
    "ray_tpu.parallel.train_step",
    "ray_tpu.parallel.pipeline",
    "ray_tpu.collective.ici",
)


@dataclass
class EntrySpec:
    name: str  # "llm.fused_step" — stable id, used in finding contexts
    fn: Callable
    shapes: dict[str, Callable[[], tuple]]  # bucket name -> () -> (args, kwargs)
    donate: tuple[str, ...] = ()  # parameter names the production jit donates
    mesh_axes: tuple[str, ...] = ()  # axis names collectives may legally use
    varying: dict[str, tuple] = field(default_factory=dict)  # param -> (v1, v2) probe values
    donate_bytes: int = 1 << 20  # JXC001 floor: smaller undonated buffers pass
    pad_min_bytes: int = 1 << 20  # JXC006 floor
    pad_waste: float = 0.25  # JXC006 budget: flag waste beyond this fraction
    flops_frac: float = 0.10  # JXC003: "dominant" = >= this fraction of entry dot flops
    path: str = ""  # abs source file of the registered def
    line: int = 0  # line of the def (where inline disables live)
    # parameter name -> signature line (driver-filled from the source AST);
    # per-argument findings (JXC001) anchor here so a multi-line signature
    # gives per-argument inline-disable granularity
    arg_lines: dict[str, int] = field(default_factory=dict)


_REGISTRY: dict[str, EntrySpec] = {}


def entry(
    name: str,
    shapes: dict[str, Callable[[], tuple]],
    donate: tuple[str, ...] = (),
    mesh_axes: tuple[str, ...] = (),
    varying: dict[str, tuple] | None = None,
    donate_bytes: int = 1 << 20,
    pad_min_bytes: int = 1 << 20,
    pad_waste: float = 0.25,
    flops_frac: float = 0.10,
):
    """Register the decorated function as a jaxcheck entry point."""

    def wrap(fn: Callable) -> Callable:
        code = getattr(fn, "__code__", None)
        _REGISTRY[name] = EntrySpec(
            name=name,
            fn=fn,
            shapes=dict(shapes),
            donate=tuple(donate),
            mesh_axes=tuple(mesh_axes),
            varying=dict(varying or {}),
            donate_bytes=donate_bytes,
            pad_min_bytes=pad_min_bytes,
            pad_waste=pad_waste,
            flops_frac=flops_frac,
            path=code.co_filename if code else "",
            line=code.co_firstlineno if code else 0,
        )
        return fn

    return wrap


def all_entries() -> list[EntrySpec]:
    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


def get_entry(name: str) -> EntrySpec | None:
    return _REGISTRY.get(name)


def clear_registry() -> None:
    """Test hook: forget everything. Note module imports are cached, so
    re-registering after a clear needs ``importlib.reload`` of the entry
    modules, not just ``import_entry_modules``."""
    _REGISTRY.clear()
