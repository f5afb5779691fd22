"""Cache-aware Llama forward passes: prefill and single-token decode.

Both are pure functions over the same parameter pytree as
ray_tpu.models.llama (training and serving share weights); layers are
iterated with `lax.scan` so compile time is constant in depth.

Where the slot cache goes through that scan decides what a decode step
costs. A cache that enters as the scan's `xs` and leaves as its `ys` is a
second stacked array to the compiler: it allocates one beside the donated
input (6.25 GiB of temporaries for a 6.0 GiB cache on a v5e), writes every
layer's full rows into it and copies the whole of it every step. So
`decode_step` keeps the stacked K and V (and an int8 cache's scales) in the
scan's CARRY and writes one token a layer into them in place; the donated
buffers are then the only cache there is. Its attention then reads the
positions each lane holds out of the stack where they lie
(`ops/slot_attention.attend`): on a TPU a kernel that streams a lane's live
blocks of layer `i` and nothing else; elsewhere (and for an int8 cache or a
partitioned program) the XLA form, which slices layer `i`'s rows out by
index and masks. `extend` and `spec/verify.py`'s block forward carry the
cache the same way and read one layer's rows back by index.

Prefill runs the causal flash path on one (padded) prompt and returns the
per-layer K/V to be inserted into a cache slot. Decode advances every slot
by one token against the full cache with a length mask. This replaces the
vLLM engine the reference wraps (ref: python/ray/llm/_internal/serve/
engines/vllm/vllm_engine.py) with a jit-native implementation.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.lint import jaxcheck
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops import slot_attention
from ray_tpu.ops.flash_attention import flash_attention_on_mesh
from ray_tpu.ops.layers import apply_rope, live_slabs, rms_norm, rotary_embedding
from ray_tpu.util.profiling import SCOPES, scope, scoped  # noqa: F401 - SCOPES: the table beside STEP_PROGRAM_NAMES, where a reader looks for it


# ---------------------------------------------------------------------------
# Step programs say their names. ``jax.jit(partial(...))`` has no ``__name__``
# to offer, so XLA called prefill, decode and extend alike ``jit__unknown``
# in profiler traces, compile logs and the persistent cache; every step
# program is jitted through ``named_jit`` instead and shows up as
# ``jit_<name>``. Trace readers rely on two words: exactly one program that
# runs once per decode step has ``fused`` in its name (the paged layout's
# second program a token is ``llm_kv_append``), every prefill program has
# ``prefill`` in its name whatever its bucket, and nothing else has either.
# ---------------------------------------------------------------------------
STEP_PROGRAM_NAMES = frozenset({
    "llm_prefill", "llm_kv_insert", "llm_extend",  # slot layout
    "llm_kv_insert_pages", "llm_kv_append",  # paged layout
    "llm_extend_paged_attn", "llm_kv_append_chunk",
    "llm_fused_step", "llm_fused_paged_step",  # the decode step (one per layout)
    "llm_verify_step", "llm_verify_paged_attn", "llm_verify_append",  # speculative verify
    "llm_draft_propose", "llm_draft_prefill", "llm_draft_kv_insert", "llm_draft_steps",
    # hybrid models (llm/hybrid_runner.py): recurrent state beside the slot KV rows
    "llm_hybrid_prefill", "llm_state_insert", "llm_hybrid_fused_step",
})


# Inside a step program the work says its names too: every ``jax.named_scope`` is one of
# ``SCOPES`` (scope name -> role; the table lives in ``util/profiling.py``, below the models that
# set the scopes and beside the reduction that reads a trace by them, and is imported here), set
# through ``scope()``, which refuses a name the table lacks as ``named_jit`` refuses a program's.


def named_jit(name: str, fn, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` under the stable program name ``name``
    (one of STEP_PROGRAM_NAMES). Arguments pass through by position, so
    ``donate_argnums`` mean what they meant on ``fn``."""
    if name not in STEP_PROGRAM_NAMES:
        raise ValueError(f"{name!r} is not a documented step program name")  # tpulint: disable=ERR002 — programmer error at engine construction, never client-visible

    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **jit_kwargs)


def program_bytes(fn, *args) -> int:
    """Bytes the jitted program ``fn`` takes for these arguments beside them, by the compiler's own
    account: its temporaries and what it hands back. After a call with the same arguments the
    executable is the one that ran and nothing compiles."""
    account = fn.lower(*args).compile().memory_analysis()
    return int(account.temp_size_in_bytes + account.output_size_in_bytes - account.alias_size_in_bytes)


def device_free_bytes(array) -> int | None:
    """Bytes free on the devices that hold ``array``, the least of them; None where the backend
    keeps no account of its memory (the CPU's)."""
    free = []
    for d in array.devices():
        stats = d.memory_stats()
        if not stats or "bytes_limit" not in stats:
            return None
        free.append(int(stats["bytes_limit"]) - int(stats["bytes_in_use"]))
    return min(free)


# ---------------------------------------------------------------------------
# Tensor parallelism over the ICI mesh: the fused decode hot path is
# re-expressed under shard_map so the per-layer TP all-reduce is an
# EXPLICIT psum the runtime controls (instead of a GSPMD-inserted
# collective), which is what makes the opt-in int8 quantized all-reduce
# (collective/ici.quantized_psum, EQuARX arxiv 2506.17615) expressible at
# all. tpc=None keeps every function byte-for-byte the single-device
# program it was — the tp=1 engine stays the token-identical oracle.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TpSpec:
    """Static description of the tensor-parallel axis a sharded step runs
    over: closed into the shard_map body, never traced."""

    axis: str = "tp"
    size: int = 1
    collective: str = "fp"  # "fp" (exact psum) | "int8" (quantized wire)


def _tp_reduce(x, tpc: TpSpec | None):
    """The per-layer TP all-reduce (attention-out and MLP-out partials).
    fp: exact lax.psum; int8: EQuARX-style quantized reduce-scatter +
    all-gather with int8 wire payload (~1/2 the ICI bytes at bf16)."""
    if tpc is None:
        return x
    if tpc.collective == "int8":
        from ray_tpu.collective.ici import quantized_psum

        return quantized_psum(x, tpc.axis)
    return jax.lax.psum(x, tpc.axis)


def _tp_embed(embed, tokens, tpc: TpSpec | None):
    """Token lookup against a vocab-row-sharded embedding: each shard
    gathers locally (clipped), masks out-of-shard rows, and one small
    [B, H] fp psum assembles the vectors — once per step, not per layer,
    so it stays full precision in both collective modes."""
    if tpc is None:
        return jnp.take(embed, tokens, axis=0)
    v_loc = embed.shape[0]
    loc = tokens - jax.lax.axis_index(tpc.axis) * v_loc
    ok = (loc >= 0) & (loc < v_loc)
    x = jnp.take(embed, jnp.clip(loc, 0, v_loc - 1), axis=0)
    x = jnp.where(ok[..., None], x, jnp.zeros((), x.dtype))
    return jax.lax.psum(x, tpc.axis)


def _tp_gather_logits(logits, tpc: TpSpec | None):
    """Vocab-sharded unembed partials -> full logits on every shard (the
    sampler needs the whole distribution). fp all-gather in both modes:
    it runs once per step and logit precision feeds top-k/top-p surgery."""
    if tpc is None:
        return logits
    return jax.lax.all_gather(logits, tpc.axis, axis=logits.ndim - 1, tiled=True)


def _shard_cfg(cfg: LlamaConfig, tp: int) -> LlamaConfig:
    """Per-shard view of the model config for shard_map bodies: head
    counts divide by tp (the local arrays carry the divided dims), and
    head_dim is pinned so the hd property stops deriving it from the
    now-wrong hidden/num_heads ratio."""
    return replace(
        cfg,
        num_heads=cfg.num_heads // tp,
        num_kv_heads=cfg.num_kv_heads // tp,
        head_dim=cfg.hd,
    )


def _tp_shard_map(f, mesh, in_specs, out_specs):
    """jax.shard_map over the tp axis. check_vma=False: lane outputs are
    replicated by construction (every shard computes the full sampler on
    the gathered logits), not by inference."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, axis_names={"tp"}, check_vma=False
    )


def _param_pspecs(cfg: LlamaConfig, mesh):
    """PartitionSpec pytree for the llama params over this mesh — the
    same logical-axes -> mesh-axes lowering the engine's GSPMD shardings
    use, so shard_map consumes the engine's arrays without resharding."""
    from ray_tpu.models.llama import param_logical_axes
    from ray_tpu.parallel.mesh import ShardingRules

    rules = ShardingRules()
    return jax.tree.map(
        lambda axes: rules.spec(axes, mesh),
        param_logical_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def _cache_pspecs(kv_layout: str, kv_quant: bool):
    """PartitionSpecs for the KV cache/pool pytree (kv_heads on tp; the
    int8 scale lanes shard their kv axis too) — mirrors
    engine._mesh_shardings."""
    from jax.sharding import PartitionSpec as P

    kv = P(None, None, None, "tp")
    specs = {"k": kv, "v": kv} if kv_layout == "paged" else {"k": kv, "v": kv, "length": P()}
    if kv_quant:
        specs["k_scale"] = specs["v_scale"] = P(None, None, "tp")
    return specs


# ---------------------------------------------------------------------------
# jaxcheck shape buckets: production-realistic abstract shapes (tile-true
# head_dim/hidden so JXC006's (8,128) math is meaningful; ShapeDtypeStructs
# only — nothing here allocates). B is the slot count, S the KV horizon.
# The _sds*/_trace_cfg helpers double as the bucket toolkit for the
# speculative entries in llm/spec/ (drafter.py / verify.py).
# ---------------------------------------------------------------------------
def _trace_cfg() -> LlamaConfig:
    return LlamaConfig(
        vocab_size=32256, hidden_size=1024, intermediate_size=2816,
        num_layers=4, num_heads=8, num_kv_heads=8, head_dim=128,
        max_seq_len=512, remat=False,
    )


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _sds_params(cfg: LlamaConfig):
    from ray_tpu.models.llama import init_params

    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def _sds_cache(cfg: LlamaConfig, B: int, S: int):
    dt = jnp.dtype(cfg.dtype)
    kv = _sds((cfg.num_layers, B, S, cfg.num_kv_heads, cfg.hd), dt)
    return {"k": kv, "v": kv, "length": _sds((B,), jnp.int32)}


def _sds_pool(cfg: LlamaConfig, pages: int, page: int):
    dt = jnp.dtype(cfg.dtype)
    kv = _sds((cfg.num_layers, pages, page, cfg.num_kv_heads, cfg.hd), dt)
    return {"k": kv, "v": kv}


def _sds_cache_q(cfg: LlamaConfig, B: int, S: int):
    """Int8-cache bucket twin of _sds_cache: int8 values + f32 per-head
    scales with the position axis last (the kv_quant.py tile layout)."""
    kv = _sds((cfg.num_layers, B, S, cfg.num_kv_heads, cfg.hd), jnp.int8)
    sc = _sds((cfg.num_layers, B, cfg.num_kv_heads, S), jnp.float32)
    return {"k": kv, "v": kv, "k_scale": sc, "v_scale": sc, "length": _sds((B,), jnp.int32)}


def _sds_pool_q(cfg: LlamaConfig, pages: int, page: int):
    kv = _sds((cfg.num_layers, pages, page, cfg.num_kv_heads, cfg.hd), jnp.int8)
    sc = _sds((cfg.num_layers, pages, cfg.num_kv_heads, page), jnp.float32)
    return {"k": kv, "v": kv, "k_scale": sc, "v_scale": sc}


def _sds_lanes(B: int):
    """(tokens, keys, temps, top_k, top_p) slot lanes."""
    return (
        _sds((B,), jnp.int32), _sds((B, 2), jnp.uint32), _sds((B,), jnp.float32),
        _sds((B,), jnp.int32), _sds((B,), jnp.float32),
    )


def _bucket_prefill(B=8, T=128):
    cfg = _trace_cfg()
    return (_sds_params(cfg), _sds((B, T), jnp.int32), _sds((B,), jnp.int32), cfg), {}


def _bucket_fused(B=8, S=256):
    cfg = _trace_cfg()
    return (_sds_params(cfg), _sds_cache(cfg, B, S)) + _sds_lanes(B) + (cfg,), {}


def _bucket_paged_fused(B=8, pages=64, page=16):
    cfg = _trace_cfg()
    tables = _sds((B, pages // B * 2), jnp.int32)
    lengths = _sds((B,), jnp.int32)
    tokens, keys, temps, top_k, top_p = _sds_lanes(B)
    return (
        _sds_params(cfg), _sds_pool(cfg, pages, page), tables, lengths,
        tokens, keys, temps, top_k, top_p, cfg,
    ), {}


def _bucket_fused_q(B=8, S=256):
    cfg = _trace_cfg()
    return (_sds_params(cfg), _sds_cache_q(cfg, B, S)) + _sds_lanes(B) + (cfg,), {}


def _bucket_paged_fused_q(B=8, pages=64, page=16):
    cfg = _trace_cfg()
    tables = _sds((B, pages // B * 2), jnp.int32)
    lengths = _sds((B,), jnp.int32)
    tokens, keys, temps, top_k, top_p = _sds_lanes(B)
    return (
        _sds_params(cfg), _sds_pool_q(cfg, pages, page), tables, lengths,
        tokens, keys, temps, top_k, top_p, cfg,
    ), {}


def _bucket_set_lanes(B=8, G=4):
    tokens, keys, temps, top_k, top_p = _sds_lanes(B)
    rows = (
        _sds((G,), jnp.int32), _sds((G,), jnp.int32), _sds((G, 2), jnp.uint32),
        _sds((G,), jnp.float32), _sds((G,), jnp.int32), _sds((G,), jnp.float32),
    )
    return (tokens, keys, temps, top_k, top_p) + rows, {}


def _qkv(xn, layer, cfg: LlamaConfig):
    B, T, _ = xn.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = jnp.dot(xn, layer["wq"]).reshape(B, T, nh, hd)
    k = jnp.dot(xn, layer["wk"]).reshape(B, T, nkv, hd)
    v = jnp.dot(xn, layer["wv"]).reshape(B, T, nkv, hd)
    return q, k, v


_MLP = ("mlp_norm", "w_gate", "w_up", "w_down")


def _mlp(x, layer, cfg: LlamaConfig, tpc: TpSpec | None = None, live=None):
    """``live`` = (the true lengths [B] of x [B,T,H]'s rows, the layers' stacked weights, this layer's
    index), from a prefill that wants the positions under the lengths and no others
    (``ops/layers.live_slabs``: norm and products a slab at a time, read where they lie in the stack)."""
    def branch(x, w=layer):
        xn = rms_norm(x, w["mlp_norm"], cfg.rms_eps)
        g = jnp.dot(xn, w["w_gate"])
        u = jnp.dot(xn, w["w_up"])
        return jnp.dot(jax.nn.silu(g) * u, w["w_down"])

    with scope("mlp"):
        if live is None:
            return x + _tp_reduce(branch(x), tpc)
        lengths, stacked, i = live
        return x + live_slabs(branch, x, lengths, layer, ({n: stacked[n] for n in _MLP}, i))


_layer_of = slot_attention.layer_of  # layer i of a stacked cache leaf; ``spec/verify.py`` takes it from here


def _scan_layers_carrying_cache(layer_fn, x, params, cache):
    """Run ``layer_fn(x, kv, layer, i) -> (x, kv)`` over the layers with the
    slot cache's stacked leaves ``kv = {k, v[, k_scale, v_scale]}`` in the
    scan's CARRY (never its xs/ys: see the module docstring), so a layer
    writes its tokens into the donated arrays where they lie and reads its
    rows back with ``_layer_of``. Returns (x, the updated leaves)."""
    def step(carry, xs):
        return layer_fn(carry[0], dict(carry[1]), *xs), None

    kv = {name: leaf for name, leaf in cache.items() if name != "length"}
    layer_ix = jnp.arange(cache["k"].shape[0], dtype=jnp.int32)
    with scope("cache"):  # the loop's own work is on its carry, the cache; a layer's stands under ``attn`` and ``mlp``
        return jax.lax.scan(step, (x, kv), (params["layers"], layer_ix))[0]


@jaxcheck.entry(
    name="llm.prefill",
    shapes={"b8_t128": _bucket_prefill, "b8_t256": lambda: _bucket_prefill(T=256)},
)
def prefill(params, tokens, length, cfg: LlamaConfig, mesh=None):
    """Run the prompt through the model, returning last-token logits + K/V.

    tokens: [B, T_pad] int32 (right-padded); length: [B] int32 real lengths.
    Returns (logits [B, vocab] f32, k [L, B, T_pad, kv, hd], v same).
    Padded positions produce garbage K/V that later attention masks out.
    ``mesh``: the engine's mesh when the program compiles SPMD over one —
    the flash kernel then runs under shard_map (heads over tp).
    """
    B, T = tokens.shape
    positions = jnp.arange(T, dtype=jnp.int32)
    cos, sin = rotary_embedding(positions, cfg.hd, cfg.rope_theta)
    with scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)

    def layer_fn(x, layer_and_index):
        layer, i = layer_and_index
        with scope("attn"):
            xn = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
            q, k, v = _qkv(xn, layer, cfg)
            qh = apply_rope(q.transpose(0, 2, 1, 3), cos, sin)
            kh = apply_rope(k.transpose(0, 2, 1, 3), cos, sin)
            o = flash_attention_on_mesh(qh, kh, v.transpose(0, 2, 1, 3), mesh, cfg.attention_impl, lengths=length)
            o = o.transpose(0, 2, 1, 3).reshape(B, T, cfg.num_heads * cfg.hd)
            x = x + jnp.dot(o, layer["wo"])
        x = _mlp(x, layer, cfg, live=(length, params["layers"], i) if mesh is None else None)
        # cache stores rope'd keys (decode appends rope'd keys too)
        return x, (kh.transpose(0, 2, 1, 3), v)

    if cfg.remat:
        layer_fn = jax.checkpoint(layer_fn, policy=getattr(jax.checkpoint_policies, cfg.remat_policy))
    with scope("cache"):  # the loop's own work: each layer's keys and values stacked as they leave it
        x, (ks, vs) = jax.lax.scan(layer_fn, x, (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))

    with scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        # only the last real token's logits matter: gather before the unembed
        # matmul so prefill does a [B, H] x [H, V] instead of [B*T, H] x [H, V]
        x_last = jnp.take_along_axis(x, (length - 1)[:, None, None], axis=1)[:, 0]
        unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = jnp.dot(x_last, unembed, preferred_element_type=jnp.float32)
    return logits, ks, vs


def decode_step(params, cache, tokens, cfg: LlamaConfig, tpc: TpSpec | None = None, live=None,
                partitioned: bool = False):
    """Advance every slot one token.

    tokens: [slots] int32 (next input token per slot, garbage for empty
    slots); cache: kv_cache pytree. Returns (logits [slots, vocab] f32,
    new cache). The new token is written at position cache.length[b] and
    attends to positions 0..length[b] inclusive.

    The layer loop carries ``(x, stacked cache leaves)`` and scans over
    ``(params["layers"], layer index)`` (_scan_layers_carrying_cache): each
    layer writes ONE token a lane into the stacked arrays
    (``.at[i, lanes, write_pos].set``) and hands the stack and its index to
    ``slot_attention.attend``, which reads positions 0..length of layer i
    from it (the new token among them). Nothing of the cache's size is
    returned from the body, so a donated cache is updated where it lies
    (why not xs/ys: the module docstring).

    ``live`` [slots] bool, where the caller knows it (the engine's lane
    table): lanes bound to a sequence. The attention kernel reads nothing
    for the others, whose stale lengths would otherwise have it stream rows
    nobody attends to; None means every lane. ``partitioned``: the program
    is compiled over a mesh by the SPMD partitioner, which cannot split a
    Mosaic kernel, so attention keeps its XLA form.

    An int8 cache (k_scale/v_scale present) quantizes the appended token
    INSIDE this program and dequantizes the row for attention at the f32
    compute dtype the score/value einsums already use (kv_quant.py) —
    same program count, roughly half the cache bytes streamed.

    With ``tpc`` set this is the per-shard body of a shard_map over the
    tp axis (cfg is the DIVIDED per-shard view from _shard_cfg): heads
    and the MLP hidden dim are local, and the attention-out / MLP-out
    partial sums all-reduce explicitly via _tp_reduce — the collective
    the runtime owns and (opt-in) quantizes. tpc=None is bit-for-bit the
    single-device program.

    CONTRACT: the speculative draft scan (llm/spec/drafter.py
    draft_steps) chains this k+1 times inside one program with an
    overridden length lane — masking must stay a pure function of the
    carried cache (no cross-call state), so chained and single-step use
    trace identically.
    """
    B = tokens.shape[0]
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    quant = "k_scale" in cache
    lengths = cache["length"]
    cos, sin = rotary_embedding(lengths[:, None], cfg.hd, cfg.rope_theta)  # [B, 1, hd/2]
    with scope("embed"):
        x = _tp_embed(params["embed"], tokens[:, None], tpc)  # [B, 1, H]
    S = cache["k"].shape[2]

    lanes = jnp.arange(B, dtype=jnp.int32)
    write_pos = jnp.minimum(lengths, S - 1)

    def layer_fn(x, kv, layer, i):  # kv: k, v [L, B, S, nkv, hd]; int8: scales [L, B, nkv, S]
        from ray_tpu.llm.kv_quant import quantize_heads

        with scope("attn"):
            xn = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
            q, k_t, v_t = _qkv(xn, layer, cfg)  # q: [B,1,nh,hd]
            qh = apply_rope(q.transpose(0, 2, 1, 3), cos, sin).transpose(0, 2, 1, 3)  # [B,1,nh,hd]
            kh = apply_rope(k_t.transpose(0, 2, 1, 3), cos, sin).transpose(0, 2, 1, 3)

            k_tok, v_tok = kh[:, 0], v_t[:, 0]
            with scope("cache"):
                if quant:
                    k_tok, sk = quantize_heads(k_tok)  # [B, kv, hd] i8, [B, kv] f32
                    v_tok, sv = quantize_heads(v_tok)
                    # the two index arrays are split by the kv slice, so the indexed slots are [B, kv]
                    kv["k_scale"] = kv["k_scale"].at[i, lanes, :, write_pos].set(sk)
                    kv["v_scale"] = kv["v_scale"].at[i, lanes, :, write_pos].set(sv)
                # ONE token a lane; inactive lanes are written too (at their stale
                # length): harmless, the mask never reads past `length`
                kv["k"] = kv["k"].at[i, lanes, write_pos].set(k_tok.astype(kv["k"].dtype))
                kv["v"] = kv["v"].at[i, lanes, write_pos].set(v_tok.astype(kv["v"].dtype))
            # GQA attention against the cache (head h uses kv head h // rep), the new token read
            # back from it: positions 0..length of layer i, out of the stack where they lie
            o = slot_attention.attend(qh[:, 0], kv["k"], kv["v"], i, lengths, nkv, live=live, k_scale=kv.get("k_scale"),
                                      v_scale=kv.get("v_scale"), sharded=partitioned or tpc is not None)
            o = o.reshape(B, 1, nh * hd).astype(x.dtype)
            x = x + _tp_reduce(jnp.dot(o, layer["wo"]), tpc)
        x = _mlp(x, layer, cfg, tpc)
        return x, kv

    x, kv = _scan_layers_carrying_cache(layer_fn, x, params, cache)
    with scope("head"):
        x = rms_norm(x[:, 0], params["final_norm"], cfg.rms_eps)
        unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = _tp_gather_logits(jnp.dot(x, unembed, preferred_element_type=jnp.float32), tpc)
    return logits, {**kv, "length": lengths + 1}


def extend(params, cache, slot, tokens, length, cfg: LlamaConfig):
    """Chunked prefill for ONE slot whose cache already holds a prefix.

    The primitive behind prefix-cache reuse and prefill/decode
    disaggregation (reference capabilities:
    python/ray/llm/_internal/serve/engines/vllm/vllm_models.py:215-228
    enable_prefix_caching, llm/tests/serve/.../prefill_decode_disagg/):
    the suffix attends to the already-cached prefix plus itself causally,
    with RoPE positions offset by the prefix length.

    tokens: [T_pad] int32 (right-padded suffix); length: [] int32 real
    suffix length; slot: [] int32. The cache's length[slot] is the prefix
    length `start`. Writes suffix K/V at start..start+length, returns
    (logits [vocab] f32 at the last real token, new cache) with
    length[slot] = start + length.
    """
    T = tokens.shape[0]
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    rep = nh // nkv
    quant = "k_scale" in cache
    S = cache["k"].shape[2]
    slot = jnp.asarray(slot, jnp.int32)
    start = cache["length"][slot]
    positions = start + jnp.arange(T, dtype=jnp.int32)
    cos, sin = rotary_embedding(positions, cfg.hd, cfg.rope_theta)
    with scope("embed"):
        x = jnp.take(params["embed"], tokens[None, :], axis=0)  # [1, T, H]
    # token i (at absolute pos start+i) sees cache pos j iff j <= start+i;
    # stale cache beyond the suffix is masked out by the same bound
    attn_ok = (jnp.arange(S, dtype=jnp.int32)[None, :] <= positions[:, None])[None, None]  # [1,1,T,S]
    zero = jnp.zeros((), jnp.int32)

    def row_of(a, i):  # layer i's rows of this slot: [S, nkv, hd] (scales: [nkv, S])
        return jax.lax.dynamic_slice(a, (i, slot) + (zero,) * (a.ndim - 2), (1, 1) + a.shape[2:])[0, 0]

    def layer_fn(x, kv, layer, i):  # the stacked leaves ride the carry, as in decode_step
        from ray_tpu.llm.kv_quant import quantize_heads

        with scope("attn"):
            xn = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
            q, k_t, v_t = _qkv(xn, layer, cfg)  # [1, T, nh/nkv, hd]
            qh = apply_rope(q.transpose(0, 2, 1, 3), cos, sin)  # [1, nh, T, hd]
            kh = apply_rope(k_t.transpose(0, 2, 1, 3), cos, sin).transpose(0, 2, 1, 3)  # [1, T, nkv, hd]
            k_suf, v_suf = kh[0], v_t[0]  # [T, nkv, hd]
            # a scatter by position, not a dynamic_update_slice at `start`: the
            # chip's compiler updates the carried cache in place for this and
            # copies the whole of it for that; and a padded tail that runs past
            # the horizon is dropped where the slice would be clamped and shift
            # the whole chunk over the prefix (engine._prefix_fits guards that)
            with scope("cache"):
                if quant:
                    k_suf, sk = quantize_heads(k_suf)  # sk: [T, nkv]
                    v_suf, sv = quantize_heads(v_suf)
                    # the index arrays are split by the kv slice: the indexed slots are [T, nkv]
                    kv["k_scale"] = kv["k_scale"].at[i, slot, :, positions].set(sk, mode="drop")
                    kv["v_scale"] = kv["v_scale"].at[i, slot, :, positions].set(sv, mode="drop")
                kv["k"] = kv["k"].at[i, slot, positions].set(k_suf.astype(kv["k"].dtype), mode="drop")
                kv["v"] = kv["v"].at[i, slot, positions].set(v_suf.astype(kv["v"].dtype), mode="drop")
            qg = qh[0].reshape(nkv, rep, T, hd)
            kc = row_of(kv["k"], i).transpose(1, 0, 2)  # [nkv, S, hd]
            vc = row_of(kv["v"], i).transpose(1, 0, 2)
            if quant:
                kc = kc.astype(jnp.float32) * row_of(kv["k_scale"], i)[..., None]
                vc = vc.astype(jnp.float32) * row_of(kv["v_scale"], i)[..., None]
            scores = jnp.einsum("grth,gsh->grts", qg, kc, preferred_element_type=jnp.float32) / jnp.sqrt(hd)
            scores = jnp.where(attn_ok[0], scores, -jnp.inf)  # [nkv, rep, T, S] vs [1, T, S]
            probs = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum("grts,gsh->grth", probs, vc.astype(jnp.float32))
            o = o.transpose(2, 0, 1, 3).reshape(1, T, nh * hd).astype(x.dtype)
            x = x + jnp.dot(o, layer["wo"])
        x = _mlp(x, layer, cfg)
        return x, kv

    x, kv = _scan_layers_carrying_cache(layer_fn, x, params, cache)
    with scope("head"):
        x = rms_norm(x[0], params["final_norm"], cfg.rms_eps)  # [T, H]
        x_last = x[jnp.maximum(length - 1, 0)]
        unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = jnp.dot(x_last, unembed, preferred_element_type=jnp.float32)
    with scope("cache"):
        return logits, {**kv, "length": cache["length"].at[slot].set(start + length)}


def decode_attn_paged(params, pool, tables, lengths, tokens, cfg: LlamaConfig, tpc: TpSpec | None = None,
                      attn_impl: str = "xla"):
    """READ-ONLY half of the paged decode step: attention over the cached
    pages plus the current token's K/V in registers. Returns
    (logits [slots, vocab] f32, k_new [L, slots, kv, hd], v_new same) —
    the scatter into the pool is a SEPARATE program (append_paged).

    The split is deliberate: a single program that both gathers from and
    scatters into the pool buffer was observed to corrupt reads
    nondeterministically on the XLA CPU runtime (in-place scatter racing
    page gathers). Keeping each program one-directional removes the
    aliasing hazard on every backend and costs one extra dispatch.

    ``tpc``: shard_map body mode, exactly as on decode_step — per-shard
    cfg, explicit all-reduce of the attention/MLP partials.

    ``attn_impl``: "xla" (default — the token-identical oracle) or
    "pallas" (llm/pallas/paged_attn.py: the page gather, int8 dequant
    and online-softmax attend fused into one HBM-streaming kernel; the
    scatter half below is untouched, so the aliasing split holds). A
    static string bound at jit time, engine-validated.
    """
    B = tokens.shape[0]
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    rep = nh // nkv
    quant = "k_scale" in pool
    cos, sin = rotary_embedding(lengths[:, None], cfg.hd, cfg.rope_theta)
    with scope("embed"):
        x = _tp_embed(params["embed"], tokens[:, None], tpc)  # [B, 1, H]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    from ray_tpu.llm.paged_kv import _paged_attn_batch

    def layer_fn(x, xs):
        if quant:
            layer, k_pool_l, v_pool_l, k_sc_l, v_sc_l = xs  # scales: [P, kv, page]
        else:
            layer, k_pool_l, v_pool_l = xs  # [P, page, kv, hd]
            k_sc_l = v_sc_l = None
        with scope("attn"):
            xn = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
            q, k_t, v_t = _qkv(xn, layer, cfg)  # [B, 1, nh/nkv, hd]
            qh = apply_rope(q.transpose(0, 2, 1, 3), cos, sin).transpose(0, 2, 1, 3)
            kh = apply_rope(k_t.transpose(0, 2, 1, 3), cos, sin).transpose(0, 2, 1, 3)
            qg = qh[:, 0].reshape(B, nkv, rep, hd)
            o = _paged_attn_batch(qg, k_pool_l, v_pool_l, tables, lengths, scale, k_self=kh[:, 0], v_self=v_t[:, 0],
                                  k_scale_l=k_sc_l, v_scale_l=v_sc_l, impl=attn_impl)
            o = o.reshape(B, 1, nh * hd).astype(x.dtype)
            x = x + _tp_reduce(jnp.dot(o, layer["wo"]), tpc)
        x = _mlp(x, layer, cfg, tpc)
        return x, (kh[:, 0], v_t[:, 0])

    xs = (params["layers"], pool["k"], pool["v"])
    if quant:
        xs += (pool["k_scale"], pool["v_scale"])
    with scope("cache"):  # the loop's own work: each layer's new key and value stacked as they leave it
        x, (k_new, v_new) = jax.lax.scan(layer_fn, x, xs)
    with scope("head"):
        x = rms_norm(x[:, 0], params["final_norm"], cfg.rms_eps)
        unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = _tp_gather_logits(jnp.dot(x, unembed, preferred_element_type=jnp.float32), tpc)
    return logits, k_new, v_new


def append_paged(pool, write_page, write_off, k_new, v_new):
    """Scatter-only half of the paged decode step: write each slot's new
    token K/V at (write_page[b], write_off[b]) for every layer. An int8
    pool quantizes here — the append program IS the quantizer, so the
    attention half stays read-only and the aliasing split holds."""
    return scoped("cache", _append_paged)(pool, write_page, write_off, k_new, v_new)


def _append_paged(pool, write_page, write_off, k_new, v_new):
    if "k_scale" in pool:
        from ray_tpu.llm.kv_quant import quantize_heads

        k_new, sk = quantize_heads(k_new)  # [L, B, kv, hd] i8, [L, B, kv] f32
        v_new, sv = quantize_heads(v_new)
        return {
            "k": pool["k"].at[:, write_page, write_off].set(k_new),
            "v": pool["v"].at[:, write_page, write_off].set(v_new),
            # scale layout [L, P, kv, page]: advanced indices split by the
            # kv slice, so the indexed result is [B, L, kv]
            "k_scale": pool["k_scale"].at[:, write_page, :, write_off].set(sk.transpose(1, 0, 2)),
            "v_scale": pool["v_scale"].at[:, write_page, :, write_off].set(sv.transpose(1, 0, 2)),
        }
    return {
        "k": pool["k"].at[:, write_page, write_off].set(k_new.astype(pool["k"].dtype)),
        "v": pool["v"].at[:, write_page, write_off].set(v_new.astype(pool["v"].dtype)),
    }


def decode_write_targets(tables, lengths, page: int):
    """(write_page [B], write_off [B]) for each slot's next token (trash
    page for rows past the table edge)."""
    B = lengths.shape[0]
    with scope("cache"):
        page_ix = jnp.minimum(lengths // page, tables.shape[1] - 1)
        write_page = tables[jnp.arange(B, dtype=jnp.int32), page_ix]
        return write_page, lengths % page


def extend_write_targets(table_row, start, T: int, page: int):
    """(write_page [T], write_off [T]) for a suffix chunk at absolute
    positions start..start+T-1."""
    positions = jnp.asarray(start, jnp.int32) + jnp.arange(T, dtype=jnp.int32)
    with scope("cache"):
        page_ix = jnp.minimum(positions // page, table_row.shape[0] - 1)
        return table_row[page_ix], positions % page


def decode_step_paged(params, pool, tables, lengths, tokens, cfg: LlamaConfig, attn_impl: str = "xla"):
    """Convenience wrapper: attention program + append program (two
    dispatches; see decode_attn_paged for why they must stay separate).
    Returns (logits, new pool, lengths+1)."""
    write_page, write_off = decode_write_targets(tables, lengths, pool["k"].shape[2])
    logits, k_new, v_new = decode_attn_paged(params, pool, tables, lengths, tokens, cfg, attn_impl=attn_impl)
    pool = append_paged(pool, write_page, write_off, k_new, v_new)
    return logits, pool, lengths + 1


def extend_attn_paged(params, pool, table_row, start, tokens, length, cfg: LlamaConfig,
                      attn_impl: str = "xla"):
    """READ-ONLY half of paged chunked-prefill: the suffix attends to the
    cached prefix pages plus itself causally (in registers). Returns
    (logits [vocab] f32 at the last real token, k_chunk [L, T, kv, hd],
    v_chunk same); the pool scatter is a separate program. ``attn_impl``
    "pallas" streams the prefix pages through the fused kernel (B=1 lane
    batch); the causal chunk stays in registers either way."""
    T = tokens.shape[0]
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    rep = nh // nkv
    quant = "k_scale" in pool
    start = jnp.asarray(start, jnp.int32)
    positions = start + jnp.arange(T, dtype=jnp.int32)
    cos, sin = rotary_embedding(positions, cfg.hd, cfg.rope_theta)
    with scope("embed"):
        x = jnp.take(params["embed"], tokens[None, :], axis=0)  # [1, T, H]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    from ray_tpu.llm.paged_kv import _paged_attn_seq, _paged_attn_seq_batch

    def layer_fn(x, xs):
        if quant:
            layer, k_pool_l, v_pool_l, k_sc_l, v_sc_l = xs
        else:
            layer, k_pool_l, v_pool_l = xs
            k_sc_l = v_sc_l = None
        with scope("attn"):
            xn = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
            q, k_t, v_t = _qkv(xn, layer, cfg)  # [1, T, nh/nkv, hd]
            qh = apply_rope(q.transpose(0, 2, 1, 3), cos, sin)  # [1, nh, T, hd]
            kh = apply_rope(k_t.transpose(0, 2, 1, 3), cos, sin).transpose(0, 2, 1, 3)  # [1, T, nkv, hd]
            qg = qh[0].reshape(nkv, rep, T, hd)
            if attn_impl == "pallas":
                o = _paged_attn_seq_batch(
                    qg[None], k_pool_l, v_pool_l, table_row[None], start[None], kh, v_t, scale,
                    k_scale_l=k_sc_l, v_scale_l=v_sc_l, impl=attn_impl,
                )[0]
            else:
                o = _paged_attn_seq(qg, k_pool_l, v_pool_l, table_row, start, kh[0], v_t[0], scale,
                                    k_scale_l=k_sc_l, v_scale_l=v_sc_l)
            o = o.transpose(2, 0, 1, 3).reshape(1, T, nh * hd).astype(x.dtype)
            x = x + jnp.dot(o, layer["wo"])
        x = _mlp(x, layer, cfg)
        return x, (kh[0], v_t[0])

    xs = (params["layers"], pool["k"], pool["v"])
    if quant:
        xs += (pool["k_scale"], pool["v_scale"])
    with scope("cache"):  # the loop's own work, as in ``decode_attn_paged``
        x, (k_chunk, v_chunk) = jax.lax.scan(layer_fn, x, xs)
    with scope("head"):
        x = rms_norm(x[0], params["final_norm"], cfg.rms_eps)  # [T, H]
        x_last = x[jnp.maximum(length - 1, 0)]
        unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = jnp.dot(x_last, unembed, preferred_element_type=jnp.float32)
    return logits, k_chunk, v_chunk


def append_chunk_paged(pool, write_page, write_off, k_chunk, v_chunk):
    """Scatter-only half of paged chunked-prefill: write the suffix K/V
    rows (write_page/write_off: [T]) for every layer. An int8 pool
    quantizes here, exactly as append_paged does for decode."""
    return scoped("cache", _append_chunk_paged)(pool, write_page, write_off, k_chunk, v_chunk)


def _append_chunk_paged(pool, write_page, write_off, k_chunk, v_chunk):
    if "k_scale" in pool:
        from ray_tpu.llm.kv_quant import quantize_heads

        k_chunk, sk = quantize_heads(k_chunk)  # [L, T, kv, hd] i8, [L, T, kv] f32
        v_chunk, sv = quantize_heads(v_chunk)
        return {
            "k": pool["k"].at[:, write_page, write_off].set(k_chunk),
            "v": pool["v"].at[:, write_page, write_off].set(v_chunk),
            "k_scale": pool["k_scale"].at[:, write_page, :, write_off].set(sk.transpose(1, 0, 2)),
            "v_scale": pool["v_scale"].at[:, write_page, :, write_off].set(sv.transpose(1, 0, 2)),
        }
    return {
        "k": pool["k"].at[:, write_page, write_off].set(k_chunk.astype(pool["k"].dtype)),
        "v": pool["v"].at[:, write_page, write_off].set(v_chunk.astype(pool["v"].dtype)),
    }


def extend_paged(params, pool, table_row, start, tokens, length, cfg: LlamaConfig, attn_impl: str = "xla"):
    """Convenience wrapper: attention program + chunk append program (two
    dispatches; see decode_attn_paged for the split rationale). Returns
    (logits [vocab] f32 at the last real token, new pool)."""
    write_page, write_off = extend_write_targets(table_row, start, tokens.shape[0], pool["k"].shape[2])
    logits, k_chunk, v_chunk = extend_attn_paged(params, pool, table_row, start, tokens, length, cfg,
                                                 attn_impl=attn_impl)
    pool = append_chunk_paged(pool, write_page, write_off, k_chunk, v_chunk)
    return logits, pool


@jaxcheck.entry(
    name="llm.fused_step",
    shapes={"b8_s256": _bucket_fused},
    donate=("cache", "keys", "temps", "top_k", "top_p"),
    donate_bytes=0,  # the whole hot loop is audited: every lane buffer counts
)
def fused_step(
    params,
    cache,
    tokens,  # tpulint: disable=JXC001 — the previous step's sampled-token output; the engine still holds it for the delayed host readback, so donating it would poison the in-flight transfer
    keys,
    temps,
    top_k,
    top_p,
    cfg: LlamaConfig,
    tpc: TpSpec | None = None,
    live=None,
    partitioned: bool = False,
):
    """ONE program for the slot layout's whole decode hot path: decode ->
    sample -> append-KV -> advance lengths. Nothing in it touches the
    host; the engine reads tokens back asynchronously one step behind the
    dispatch (device-resident loop).

    The sampling lanes (keys, temps, top_k, top_p) are donated and handed
    back as passthrough outputs — XLA aliases them in place (zero copies)
    and the engine rebinds its handles each step, so every buffer the
    loop touches stays device-resident with exactly one live copy.
    tokens is deliberately NOT donated (see inline disable above).

    With ``tpc`` this is the shard_map body over the tp mesh: the lanes
    are replicated, the sampler runs identically on every shard over the
    all-gathered logits, and the ONE-program-per-token invariant extends
    across chips — the all-reduce lives inside this jitted step.
    """
    from ray_tpu.llm.sampling import sample

    logits, cache = decode_step(params, cache, tokens, cfg, tpc, live, partitioned)
    toks, logps, new_keys = sample(logits, keys, temps, top_k, top_p)
    return cache, toks, logps, new_keys, temps, top_k, top_p


# int8-cache variant of the SAME program (quantize-on-append inside
# decode_step, dequantize-in-attention): its own registry entry so the
# donation audit and the JXC003 bf16->f32-before-dot trap are checked on
# the quantized hot path too (the dequant is an int8->f32 convert feeding
# the attention einsums at their existing compute dtype, and must never
# drift onto the flops-dominant dots — regression-locked in
# tests/test_lint_rules.py).
jaxcheck.entry(
    name="llm.fused_step_int8",
    shapes={"b8_s256": _bucket_fused_q},
    donate=("cache", "keys", "temps", "top_k", "top_p"),
    donate_bytes=0,
)(fused_step)


def _sharded_fused_slots(cfg: LlamaConfig, mesh, tp_collective: str, kv_quant: bool):
    """The slot fused step under shard_map over the tp axis (unjitted):
    params/cache enter at their engine shardings, lanes replicated, and
    the per-layer all-reduce is the explicit _tp_reduce psum."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import axis_size

    tp = axis_size(mesh, "tp")
    tpc = TpSpec("tp", tp, tp_collective)
    cache_sp = _cache_pspecs("slots", kv_quant)
    rep = P()
    return _tp_shard_map(
        partial(fused_step, cfg=_shard_cfg(cfg, tp), tpc=tpc),
        mesh,
        in_specs=(_param_pspecs(cfg, mesh), cache_sp, rep, rep, rep, rep, rep),
        out_specs=(cache_sp, rep, rep, rep, rep, rep, rep),
    )


def make_fused_fns(cfg: LlamaConfig, mesh=None, tp_collective: str = "fp", kv_quant: bool = False,
                   partitioned: bool = False):
    """Jit of fused_step with the production donation set. With a tp>1
    mesh the step compiles as ONE SPMD program via shard_map — the
    per-layer tp all-reduce is an explicit psum inside it, quantized to
    int8 on the wire when tp_collective="int8". ``partitioned``: the
    engine's arrays are sharded over some other mesh and the compiler
    partitions the plain program (no Mosaic kernel in it then)."""
    from ray_tpu.parallel.mesh import axis_size

    if mesh is not None and axis_size(mesh, "tp") > 1:
        return named_jit("llm_fused_step", _sharded_fused_slots(cfg, mesh, tp_collective, kv_quant),
                         donate_argnums=(1, 3, 4, 5, 6))
    return named_jit("llm_fused_step", partial(fused_step, cfg=cfg, partitioned=partitioned),
                     donate_argnums=(1, 3, 4, 5, 6))


@jaxcheck.entry(
    name="llm.paged_fused_step",
    shapes={"b8_p64": _bucket_paged_fused},
    donate=("lengths", "keys", "temps", "top_k", "top_p"),
    donate_bytes=0,
)
def paged_fused_step(
    params,
    pool,  # read-only by design (the gather/scatter aliasing hazard); donated by the append program instead
    tables,
    lengths,
    tokens,  # tpulint: disable=JXC001 — feeds the delayed host readback (same rationale as fused_step)
    keys,
    temps,
    top_k,
    top_p,
    cfg: LlamaConfig,
    tpc: TpSpec | None = None,
    attn_impl: str = "xla",
):
    """READ-ONLY half of the paged device-resident step: attention +
    sample + write-target math; the scatter-append into the pool is a
    SEPARATE program (append_paged) — see decode_attn_paged for the
    gather/scatter aliasing hazard that forbids fusing them. Sampling
    lanes are donated-and-passed-through exactly as in fused_step.
    ``tpc``: shard_map body mode (see fused_step). ``attn_impl``:
    "pallas" rides the fused HBM-streaming kernel for the page attention
    (engine opt-in, see decode_attn_paged); the append program is
    untouched either way."""
    from ray_tpu.llm.sampling import sample

    write_page, write_off = decode_write_targets(tables, lengths, pool["k"].shape[2])
    logits, k_new, v_new = decode_attn_paged(params, pool, tables, lengths, tokens, cfg, tpc,
                                             attn_impl=attn_impl)
    toks, logps, new_keys = sample(logits, keys, temps, top_k, top_p)
    return toks, logps, new_keys, k_new, v_new, write_page, write_off, lengths + 1, temps, top_k, top_p


# int8-pool variant (see llm.fused_step_int8's rationale); the pool stays
# undonated/read-only here — the append program is the quantizer
jaxcheck.entry(
    name="llm.paged_fused_step_int8",
    shapes={"b8_p64": _bucket_paged_fused_q},
    donate=("lengths", "keys", "temps", "top_k", "top_p"),
    donate_bytes=0,
)(paged_fused_step)


def _sharded_fused_paged(cfg: LlamaConfig, mesh, tp_collective: str, kv_quant: bool):
    """paged_fused_step under shard_map over the tp axis (unjitted). The
    pool enters read-only at its engine sharding; the new-token K/V
    leaves kv-sharded for the (GSPMD, collective-free) append program."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import axis_size

    tp = axis_size(mesh, "tp")
    tpc = TpSpec("tp", tp, tp_collective)
    pool_sp = _cache_pspecs("paged", kv_quant)
    kv_new = P(None, None, "tp", None)  # k_new/v_new: [L, B, kv, hd]
    rep = P()
    return _tp_shard_map(
        partial(paged_fused_step, cfg=_shard_cfg(cfg, tp), tpc=tpc),
        mesh,
        in_specs=(_param_pspecs(cfg, mesh), pool_sp, rep, rep, rep, rep, rep, rep, rep),
        out_specs=(rep, rep, rep, kv_new, kv_new, rep, rep, rep, rep, rep, rep),
    )


def make_fused_paged_fns(cfg: LlamaConfig, mesh=None, tp_collective: str = "fp", kv_quant: bool = False,
                         attn_impl: str = "xla"):
    """Device-resident decode step for the paged layout: TWO programs
    (attention+sample, then scatter-append), neither of which ever syncs
    with the host. tables is read every step and mutated only by
    scheduler deltas. With a tp>1 mesh the attention half compiles under
    shard_map (explicit per-layer all-reduce, optionally int8 on the
    wire); the append half stays a plain GSPMD jit — its scatter is
    elementwise per kv-head, so partitioning it needs no collectives and
    the documented gather/scatter program split is untouched.
    ``attn_impl="pallas"``: the attention half's page loop runs as the
    fused HBM-streaming kernel (single-device path only — the engine
    refuses the kernel on tp meshes)."""
    from ray_tpu.parallel.mesh import axis_size

    if mesh is not None and axis_size(mesh, "tp") > 1:
        attn_fn = named_jit("llm_fused_paged_step", _sharded_fused_paged(cfg, mesh, tp_collective, kv_quant),
                            donate_argnums=(3, 5, 6, 7, 8))
    else:
        attn_fn = named_jit("llm_fused_paged_step", partial(paged_fused_step, cfg=cfg, attn_impl=attn_impl),
                            donate_argnums=(3, 5, 6, 7, 8))
    append_fn = named_jit("llm_kv_append", append_paged, donate_argnums=(0,))
    return attn_fn, append_fn


@jaxcheck.entry(
    name="llm.delta_set_lanes",
    shapes={"b8_g4": _bucket_set_lanes},
    donate_bytes=0,
)
def set_lanes(tokens, keys, temps, top_k, top_p, slots, token, key, temp, tk, tp):  # tpulint: disable=JXC001 — delta fns deliberately donate nothing: the engine may still hold every one of these buffers for an in-flight step's delayed readback when a scheduler delta lands
    """Jitted scatter for admission: write a group's lane state, a row a slot (``slots`` [G]; the
    rest [G] or [G, 2]), from values that may never have left the device. A row whose slot is out
    of range (a group's padding) is dropped."""
    def put(lane, rows):
        return lane.at[slots].set(rows, mode="drop")

    return put(tokens, token), put(keys, key), put(temps, temp), put(top_k, tk), put(top_p, tp)


def set_table(tables, lengths, slot, row, length):
    return tables.at[slot].set(row), lengths.at[slot].set(length)


def set_table_cell(tables, slot, pg_ix, page):
    return tables.at[slot, pg_ix].set(page)


def make_delta_fns():
    """Jitted scatter updates for scheduler deltas on device-resident
    decode state (admission / eviction / page growth). The table writes
    compile once (slot/index are traced scalars), the lane write once a
    group size, and each touches O(group) elements — the replacement for
    re-uploading whole host arrays every step. Nothing is donated (see
    set_lanes' inline rationale)."""
    return jax.jit(set_lanes), jax.jit(set_table), jax.jit(set_table_cell)


# ---------------------------------------------------------------------------
# jaxcheck entries for the SHARDED serving path: the fused steps traced
# over a real 2-way tp mesh (the tracing env guarantees >= 8 virtual CPU
# devices), so JXC005 finally audits the serving-path collectives against
# their declared mesh axes — psum/all_gather/all_to_all/axis_index must
# all run over 'tp' and nothing else, and the donation/padding/upcast
# rules re-check the program in its multi-chip form.
# ---------------------------------------------------------------------------
def _tp2_mesh():
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError("tp entries trace over 2 devices; the tracing env provides 8 virtual CPU devices")
    return Mesh(np.asarray(devs[:2]), ("tp",))


def _bucket_fused_tp(B=8, S=256):
    cfg = _trace_cfg()
    return (_sds_params(cfg), _sds_cache(cfg, B, S)) + _sds_lanes(B), {}


def _bucket_paged_fused_tp(B=8, pages=64, page=16):
    cfg = _trace_cfg()
    tables = _sds((B, pages // B * 2), jnp.int32)
    lengths = _sds((B,), jnp.int32)
    tokens, keys, temps, top_k, top_p = _sds_lanes(B)
    return (
        _sds_params(cfg), _sds_pool(cfg, pages, page), tables, lengths,
        tokens, keys, temps, top_k, top_p,
    ), {}


@jaxcheck.entry(
    name="llm.fused_step_tp",
    shapes={"b8_s256_tp2": _bucket_fused_tp},
    donate=("cache", "keys", "temps", "top_k", "top_p"),
    donate_bytes=0,
    mesh_axes=("tp",),
)
def fused_step_tp(
    params,
    cache,
    tokens,  # tpulint: disable=JXC001 — same delayed-readback rationale as fused_step's token lane
    keys,
    temps,
    top_k,
    top_p,
):
    """make_fused_fns(mesh=2-way tp) in registry-traceable form: the fp
    collective schedule (explicit per-layer psum over 'tp')."""
    return _sharded_fused_slots(_trace_cfg(), _tp2_mesh(), "fp", False)(
        params, cache, tokens, keys, temps, top_k, top_p
    )


@jaxcheck.entry(
    name="llm.fused_step_tp_int8c",
    shapes={"b8_s256_tp2": _bucket_fused_tp},
    donate=("cache", "keys", "temps", "top_k", "top_p"),
    donate_bytes=0,
    mesh_axes=("tp",),
)
def fused_step_tp_int8c(
    params,
    cache,
    tokens,  # tpulint: disable=JXC001 — same delayed-readback rationale as fused_step's token lane
    keys,
    temps,
    top_k,
    top_p,
):
    """The int8-collective variant (tp_collective="int8"): the per-layer
    all-reduce ships int8 + f32 amax scales over ICI. The dequants feed
    residual adds and the exact f32 chunk accumulate — never a
    flops-dominant dot, so JXC003 stays clean by construction here."""
    return _sharded_fused_slots(_trace_cfg(), _tp2_mesh(), "int8", False)(
        params, cache, tokens, keys, temps, top_k, top_p
    )


@jaxcheck.entry(
    name="llm.paged_fused_step_tp",
    shapes={"b8_p64_tp2": _bucket_paged_fused_tp},
    donate=("lengths", "keys", "temps", "top_k", "top_p"),
    donate_bytes=0,
    mesh_axes=("tp",),
)
def paged_fused_step_tp(
    params,
    pool,  # read-only by design (the gather/scatter aliasing hazard); donated by the append program instead
    tables,
    lengths,
    tokens,  # tpulint: disable=JXC001 — same delayed-readback rationale as fused_step's token lane
    keys,
    temps,
    top_k,
    top_p,
):
    """make_fused_paged_fns(mesh=2-way tp)'s attention half in
    registry-traceable form (the append half is collective-free GSPMD)."""
    return _sharded_fused_paged(_trace_cfg(), _tp2_mesh(), "fp", False)(
        params, pool, tables, lengths, tokens, keys, temps, top_k, top_p
    )


def make_runner_fns(cfg: LlamaConfig, mesh=None):
    """Jitted (prefill, insert, extend) closures for an engine; the decode step is make_fused_fns'."""
    from ray_tpu.llm import kv_cache as kvc

    prefill_fn = named_jit("llm_prefill", partial(prefill, cfg=cfg, mesh=mesh))
    insert_fn = named_jit("llm_kv_insert", scoped("cache", kvc.insert_sequence), donate_argnums=(0,))
    extend_fn = named_jit("llm_extend", partial(extend, cfg=cfg), donate_argnums=(1,))
    return prefill_fn, insert_fn, extend_fn


def make_paged_runner_fns(cfg: LlamaConfig, attn_impl: str = "xla", mesh=None):
    """Jitted (prefill, insert_pages, extend) for a paged engine; the
    decode step is make_fused_paged_fns'.

    Extend compiles as TWO programs — read-only attention and
    scatter-only append — never fused (jitting the combined wrapper would
    reintroduce the same-program gather+scatter aliasing hazard; see
    decode_attn_paged). ``attn_impl`` selects the page-attention body of
    the read-only half ("xla" oracle / "pallas" fused kernel)."""
    from ray_tpu.llm import paged_kv as pkv

    prefill_fn = named_jit("llm_prefill", partial(prefill, cfg=cfg, mesh=mesh))
    insert_fn = named_jit("llm_kv_insert_pages", scoped("cache", pkv.insert_pages), donate_argnums=(0,))
    ext_attn_fn = named_jit("llm_extend_paged_attn", partial(extend_attn_paged, cfg=cfg, attn_impl=attn_impl))
    ext_append_fn = named_jit("llm_kv_append_chunk", append_chunk_paged, donate_argnums=(0,))

    def extend_fn(params, pool, table_row, start, tokens, length):
        write_page, write_off = extend_write_targets(table_row, start, tokens.shape[0], pool["k"].shape[2])
        logits, k_chunk, v_chunk = ext_attn_fn(params, pool, table_row, start, tokens, length)
        pool = ext_append_fn(pool, write_page, write_off, k_chunk, v_chunk)
        return logits, pool

    return prefill_fn, insert_fn, extend_fn
