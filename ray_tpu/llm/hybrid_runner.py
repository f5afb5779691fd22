"""Step programs for a hybrid model: layers of several kinds in one stack,
each keeping its own kind of state. The model is a DESCRIPTION
(``ray_tpu.models.hybrid.HybridDescription``, which the model files under
``ray_tpu/models/`` mix into their configs) walked by that module's loops.

Three programs, under stable names a trace reader finds (``prefill`` and
``fused`` in a name mean what they mean in ``model_runner``):

- ``llm_hybrid_prefill``: a batch of same-bucket prompts, right-padded.
  Attention masks padding; a recurrence would run over it, so every
  recurrent layer hands back its state AT each prompt's true length.
- ``llm_state_insert``: a prefilled sequence's recurrent state into its
  slot of the state cache (``llm/state_cache.py``), replacing whatever
  the slot's last sequence left; what it keeps per position (keys and
  values, or a latent layer's rows: the description's
  ``position_entries()``) goes through the slot cache's own
  ``llm_kv_insert``.
- ``llm_hybrid_fused_step``: decode -> sample -> advance, one program a
  token. Both caches ride the layer loop as its carry and are updated in
  place (one token's key and value scattered into the donated rows, one
  layer's state overwritten), never rebuilt from per-layer outputs.

The engine picks these from the config object: a description with
``layer_kinds`` is a hybrid, and carries its mixers (``config.mixers``:
each kind's two forms), its norm, what each kind keeps
(``config.cache_spec()``) and its weights' initialisation
(``config.init_params``), so that neither this file nor the engine names
a model or a kind of layer. What the hybrid cannot do yet is refused by
name in ``refuse`` and ``refuser``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.exceptions import HybridModelUnsupportedError
from ray_tpu.lint import jaxcheck
from ray_tpu.llm import state_cache
from ray_tpu.llm.model_runner import _sds, _sds_lanes, named_jit
from ray_tpu.models import hybrid
from ray_tpu.util.profiling import scope, scoped

# one step's expert-routing counters, in the order the fused step returns them
MOE_STATS = ("experts_hit", "moe_pairs_local", "moe_pairs_total", "moe_max_load", "experts_read")
# an admission's, in the order the prefill returns them (means over the routing layers; the last only from a program whose blocks the kernel runs)
PREFILL_STATS = ("experts_hit", "moe_pairs_local", "moe_rows_computed", "moe_rows_kernel")
ROUTING = hybrid.ROUTING


def _kept(config) -> str:
    """What a sequence of this description is, beside keys and values by head: the words of a refusal."""
    per_sequence = sorted(state_cache.sequence_entries(config))
    rings = config.ring_entries()
    latent = sorted(set(config.position_entries()) - {"k", "v"} - set(rings))
    parts = ([f"its recurrent layers keep a state per sequence ({', '.join(per_sequence)})"] if per_sequence else []) \
        + ([f"its attention layers keep {' and '.join(latent)} per position, not keys and values by head"] if latent else []) \
        + ([f"its window layers keep {' and '.join(sorted(rings))} in a ring of the last {max(rings.values())} positions, not every position"] if rings else [])
    return " and ".join(parts) or "its layers are walked by the description loop"


def refuse(config, *, kv_layout, cache_dtype, mesh, speculative, kv_plane) -> None:
    """Engine features that assume "a sequence's state is its keys and values by head, in the one
    step program", each refused by its name at construction: reusing, verifying, moving or
    re-laying-out a sequence would need a snapshot, a rollback or a codec of what the description
    keeps (``_kept``), and none is built."""
    why = {
        "kv_layout='paged'": kv_layout != "slots",
        "cache_dtype='int8'": cache_dtype is not None and str(cache_dtype).lower() in ("int8", "i8"),
        "tensor_parallel_size > 1 (a mesh)": mesh is not None,
        "speculative decoding": speculative is not None,
        "the cluster KV plane (kv_plane)": kv_plane is not None,
    }
    for what, asked in why.items():
        if asked:
            raise HybridModelUnsupportedError(
                f"{what} is not built for a hybrid model ({type(config).__name__}: {config.kinds_held}): "
                f"{_kept(config)}, "
                "which this feature would have to snapshot, roll back, shard or ship, and only keys and values by head can be")


def refuser(what: str, config):
    """What an engine of a hybrid model answers where a sequence would be moved as keys and
    values alone (``engine.KV_ONLY_METHODS``)."""
    def refused(*args, **kwargs):
        raise HybridModelUnsupportedError(
            f"{what} is not built for a hybrid model: {_kept(config)}, which no handoff, migration or "
            "KV-plane format carries, and keys and values by head alone do not resume the sequence")
    return refused


def prefill(params, tokens, length, cfg, mesh=None):
    """tokens [B, T_pad] right-padded, length [B] -> (last-token logits [B, vocab] f32,
    rows {name: [layers that keep it, B, T_pad, *shape]}: the per-position entries (``k`` and ``v``
    [La, B, T_pad, kv, hd] of an attention layer with heads), state {name: [Lm, B, ...]} at each
    prompt's true length, with PREFILL_STATS as float32 [3] or [4] beside the state under ``ROUTING``
    where the model routes)."""
    x, out = hybrid.forward_hidden(params, tokens, length, cfg, mesh, collect=True)
    with scope("head"):
        x_last = jnp.take_along_axis(x, (length - 1)[:, None, None], axis=1)[:, 0]
        logits = hybrid.head(x_last, params)
    if ROUTING in out:
        out[ROUTING] = jnp.mean(out[ROUTING], axis=0)
    return logits, {name: out.pop(name) for name in cfg.position_entries()}, out


def decode_step(params, cache, state, tokens, active, cfg):
    """Advance every slot one token. cache: the slot rows of the layers that keep something per
    position; state: the state cache of the layers that keep something per sequence; active [B]
    bool: lanes bound to a live sequence (the others compute garbage nobody reads, and are kept
    out of the routing counters).
    -> (logits [B, vocab] f32, cache, state, MOE_STATS as float32 [5])."""
    B = tokens.shape[0]
    lengths = cache["length"]
    per_position = frozenset(cfg.position_entries())
    rings = frozenset(cfg.ring_entries())
    # every entry is [layers, slots, rows, ...]: the horizon's rows where it spans the sequence, a window's where it is a ring
    # (``LayerCache.write`` takes a ring's position modulo its rows, so only the entries that span the sequence bound ``pos``)
    spans = sorted(per_position - rings)
    pos = jnp.minimum(lengths, cache[spans[0]].shape[2] - 1) if spans else lengths
    lanes = jnp.arange(B, dtype=jnp.int32)
    with scope("embed"):
        x = hybrid.embed_tokens(params, tokens, cfg)

    def layer(kind, w, i, riding, carry):
        arrays, stats = carry
        x, handed = riding  # what sub-blocks hand on rides the loop beside the stream (``hybrid.forward_hidden``)
        view = hybrid.LayerCache(arrays, per_position, i, lanes, pos, rings)
        y, s, *more = cfg.mixers[kind].step(w, cfg.norm(x, w["norm"]), view, hybrid.StepCtx(lengths, active, (params[kind], i), handed))
        if s is not None:
            stats = jnp.stack([stats[0] + s[0], stats[1] + s[1], jnp.maximum(stats[2], s[2]), stats[3] + s[3]])
        return (hybrid.add_branch(x, y, cfg), more[0] if cfg.mixers[kind].hands else handed), (view.arrays, stats)

    arrays = {**{name: cache[name] for name in per_position}, **state}
    handed = {n: jnp.zeros((B,) + tuple(shape), jnp.dtype(dt)) for n, (shape, dt) in cfg.handed.items()}
    (x, _), (arrays, stats) = hybrid.run_layers(cfg, params, (x, handed), (arrays, jnp.zeros((4,), jnp.float32)), layer)
    with scope("head"):
        logits = hybrid.head(hybrid.before_head(x, params, cfg), params)
    n = max(cfg.routing_layers, 1)
    total = cfg.expert_layer.top_k * jnp.sum(active.astype(jnp.float32)) if cfg.routing_layers else jnp.zeros((), jnp.float32)
    moe = jnp.stack([stats[0] / n, stats[1] / n, total, stats[2], stats[3] / n])
    cache = {**{name: arrays.pop(name) for name in per_position}, "length": lengths + 1}
    return logits, cache, arrays, moe


def fused_step(params, cache, state, tokens, keys, temps, top_k, top_p, active, cfg):  # tpulint: disable=JXC001 — tokens is the previous step's output, still held for the delayed readback (as in model_runner.fused_step); active is a fresh 1-byte-a-lane host mask
    """ONE program for the hybrid's decode hot path: decode -> sample -> append keys and values
    -> overwrite recurrent state -> advance lengths. Lanes are donated and handed back as in
    ``model_runner.fused_step``; the routing counters ride the same delayed readback as the
    tokens."""
    from ray_tpu.llm.sampling import sample

    logits, cache, state, moe = decode_step(params, cache, state, tokens, active, cfg)
    toks, logps, new_keys = sample(logits, keys, temps, top_k, top_p)
    return cache, state, toks, logps, moe, new_keys, temps, top_k, top_p


def make_hybrid_fns(cfg):
    """Jitted (prefill, kv insert, state insert, fused step) for an engine."""
    from ray_tpu.llm import kv_cache as kvc

    prefill_fn = named_jit("llm_hybrid_prefill", partial(prefill, cfg=cfg))
    insert_fn = named_jit("llm_kv_insert", scoped("cache", partial(kvc.insert_entries, rings=frozenset(cfg.ring_entries()))), donate_argnums=(0,))
    state_insert_fn = named_jit("llm_state_insert", scoped("cache", state_cache.insert_state), donate_argnums=(0,))
    step_fn = named_jit("llm_hybrid_fused_step", partial(fused_step, cfg=cfg), donate_argnums=(1, 2, 4, 5, 6, 7))
    return prefill_fn, insert_fn, state_insert_fn, step_fn


# ---------------------------------------------------------------------------
# jaxcheck shape buckets: tile-true widths at a size that traces in seconds
# ---------------------------------------------------------------------------
_trace_cfg = hybrid.trace_description


def _sds_caches(cfg, B: int, S: int):
    from ray_tpu.llm import kv_cache as kvc

    return (jax.eval_shape(lambda: kvc.alloc_entries(cfg.position_entries(), B, S, cfg.ring_entries())),
            jax.eval_shape(lambda: state_cache.alloc(cfg, B)))


def _bucket_prefill(B=4, T=128):
    cfg = _trace_cfg()
    params = jax.eval_shape(lambda: cfg.init_params(jax.random.PRNGKey(0)))
    return (params, _sds((B, T), jnp.int32), _sds((B,), jnp.int32), cfg), {}


def _bucket_fused(B=8, S=256):
    cfg = _trace_cfg()
    params = jax.eval_shape(lambda: cfg.init_params(jax.random.PRNGKey(0)))
    cache, state = _sds_caches(cfg, B, S)
    return (params, cache, state) + _sds_lanes(B) + (_sds((B,), jnp.bool_), cfg), {}


def _bucket_state_insert(B=8, Bp=4):
    cfg = _trace_cfg()
    state = jax.eval_shape(lambda: state_cache.alloc(cfg, B))
    new = jax.eval_shape(lambda: state_cache.alloc(cfg, Bp))
    return (state, _sds((), jnp.int32), _sds((), jnp.int32), new), {}


jaxcheck.entry(name="llm.hybrid_prefill", shapes={"b4_t128": _bucket_prefill})(prefill)
jaxcheck.entry(name="llm.hybrid_fused_step", shapes={"b8_s256": _bucket_fused},
               donate=("cache", "state", "keys", "temps", "top_k", "top_p"), donate_bytes=0)(fused_step)
jaxcheck.entry(name="llm.state_insert", shapes={"b8": _bucket_state_insert}, donate=("state",))(state_cache.insert_state)
