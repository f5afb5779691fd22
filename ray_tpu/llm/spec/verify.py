"""The fused speculative verify step: one jitted program per engine tick.

Per lane the program takes the current input token t0 plus k proposals
d1..dk (padded to the STATIC width k so shapes never vary), runs the
target model over all k+1 positions in one wide forward, and:

- accepts the longest proposal prefix the target agrees with — greedy
  exact-match for temperature==0 lanes, one-hot rejection sampling
  (accept d with prob p(d), resample a rejection from p-with-d-masked)
  for temperature>0, where p is the target distribution AFTER the same
  temperature/top-k/top-p surgery `sampling.sample` applies;
- emits the accepted tokens plus one token from the target at the first
  disagreement (the bonus/replacement), so every round emits >= 1;
- appends the whole block's K/V (computed anyway) and rolls back
  rejections in O(1) by setting length = l + accepted + 1 — positions
  past the new length are dead until overwritten, exactly like the
  garbage tail of a padded prefill;
- advances the lane's token-history buffer (the drafter's input) on
  device, so draft -> verify chains without any host sync.

Layouts: the slot layout is ONE program (cache donated, functional
update inside); the paged layout splits attention+accept from the pool
scatter-append — a same-program gather+scatter on the pool buffer is
the aliasing hazard documented on `decode_attn_paged`, and speculation
does not change it. Writes past a slot row / page table land in dropped
scatters / the trash page: they can only occur in rounds whose tokens
the host has already discarded.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.lint import jaxcheck
from ray_tpu.llm.model_runner import (
    TpSpec,
    _cache_pspecs,
    _layer_of,
    _mlp,
    _param_pspecs,
    _qkv,
    _scan_layers_carrying_cache,
    _sds,
    _sds_cache,
    _sds_cache_q,
    _sds_lanes,
    _sds_params,
    _sds_pool,
    _sds_pool_q,
    _shard_cfg,
    _tp2_mesh,
    _tp_embed,
    _tp_gather_logits,
    _tp_reduce,
    _tp_shard_map,
    _trace_cfg,
    named_jit,
)
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.layers import apply_rope, rms_norm, rotary_embedding


def _wrap(kd):
    return jax.random.wrap_key_data(kd, impl="threefry2x32")


# ---------------------------------------------------------------------------
# acceptance + sampling (layout-independent)
# ---------------------------------------------------------------------------
def _accept_and_sample(logits, proposals, spec_k, keys, temps, top_k, top_p):
    """logits: [B, k+1, V] target logits over (t0, d1..dk); proposals:
    [B, k]. Returns (emit [B, k+1] i32, logps [B, k+1] f32, acc [B] i32,
    final [B] i32, new_keys [B, 2] u32) where emit[:, :acc] are accepted
    proposals, emit[:, acc] the bonus/replacement, and the rest garbage
    the host never reads."""
    from ray_tpu.llm.sampling import filter_logits

    B, T, V = logits.shape
    k = T - 1
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, T]
    logp_full = jax.nn.log_softmax(logits, axis=-1)
    # the SAME distribution surgery sample() applies, broadcast over T
    filt = filter_logits(logits, temps[:, None], top_k[:, None], top_p[:, None])
    probs = jax.nn.softmax(filt, axis=-1)  # [B, T, V]

    # per-lane randomness: k accept draws + 1 replacement draw + next key
    def _split(kd):
        return jax.random.key_data(jax.random.split(_wrap(kd), k + 2))

    subkeys = jax.vmap(_split)(keys)  # [B, k+2, 2]
    u = jax.vmap(jax.vmap(lambda kd: jax.random.uniform(_wrap(kd), ())))(subkeys[:, :k])  # [B, k]

    p_prop = jnp.take_along_axis(probs[:, :k], proposals[..., None], axis=-1)[..., 0]  # [B, k]
    accept_greedy = proposals == greedy[:, :k]
    accept_stoch = u < p_prop  # one-hot q: accept prob = p(d)
    accept = jnp.where(temps[:, None] == 0.0, accept_greedy, accept_stoch)
    accept = accept & (jnp.arange(k, dtype=jnp.int32)[None, :] < spec_k[:, None])
    acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1).astype(jnp.int32)  # [B]

    # final token from the first-disagreement position's target logits
    lg_a = jnp.take_along_axis(logits, acc[:, None, None], axis=1)[:, 0]  # [B, V]
    filt_a = jnp.take_along_axis(filt, acc[:, None, None], axis=1)[:, 0]
    rejected = acc < jnp.minimum(spec_k, k)  # a proposal was examined and refused
    d_rej = jnp.take_along_axis(proposals, jnp.minimum(acc, k - 1)[:, None], axis=1)[:, 0]
    # one-hot-q residual max(p - q, 0): p with the refused token masked out
    mask_rej = jax.nn.one_hot(d_rej, V, dtype=jnp.bool_) & rejected[:, None]
    stoch_tok = jax.vmap(lambda kd, lg: jax.random.categorical(_wrap(kd), lg))(
        subkeys[:, k], jnp.where(mask_rej, -jnp.inf, filt_a)
    ).astype(jnp.int32)
    greedy_tok = jnp.argmax(lg_a, axis=-1).astype(jnp.int32)
    final = jnp.where(temps == 0.0, greedy_tok, stoch_tok)
    new_keys = subkeys[:, k + 1]

    cols = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    props_pad = jnp.pad(proposals, ((0, 0), (0, 1)))
    emit = jnp.where(cols < acc[:, None], props_pad, 0)
    emit = jnp.where(cols == acc[:, None], final[:, None], emit).astype(jnp.int32)
    # logprobs from the UNfiltered distribution, as sample() reports them
    lp_pad = jnp.pad(jnp.take_along_axis(logp_full[:, :k], proposals[..., None], axis=-1)[..., 0], ((0, 0), (0, 1)))
    lp_a = jnp.take_along_axis(logp_full, acc[:, None, None], axis=1)[:, 0]
    lp_fin = jnp.take_along_axis(lp_a, final[:, None], axis=1)[:, 0]
    logps = jnp.where(cols < acc[:, None], lp_pad, 0.0)
    logps = jnp.where(cols == acc[:, None], lp_fin[:, None], logps)
    return emit, logps, acc, final, new_keys


def _update_hist(hist, hist_len, emit, acc):
    """Append the round's emitted tokens to the history lanes. All k+1
    slots are written (past-acceptance garbage sits beyond the new valid
    length and is overwritten by the next round before it could be read);
    writes past the buffer edge are dropped — they only occur in rounds
    whose tokens the host discards anyway."""
    B, Tp1 = emit.shape
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    hpos = hist_len[:, None] + jnp.arange(Tp1, dtype=jnp.int32)[None, :]
    return hist.at[rows, hpos].set(emit, mode="drop"), hist_len + acc + 1


# ---------------------------------------------------------------------------
# slot layout
# ---------------------------------------------------------------------------
def _forward_block_slots(params, cache, toks_blk, cfg: LlamaConfig, tpc: TpSpec | None = None):
    """Target forward over T=k+1 tokens per slot at positions
    length..length+T-1. Block K/V is written into the cache rows first
    (per-position scatter, OOB dropped) and attention reads the updated
    row with mask j <= position — the functional-update idiom
    decode_step/fused_step already rely on (no pool-style aliasing
    hazard in the slot layout). An int8 cache quantizes the block's K/V
    on the same scatter and dequantizes the row for attention, exactly
    as decode_step does per token. ``tpc``: shard_map body mode, as on
    decode_step — verify compiles SPMD like the fused step, with the
    per-layer all-reduce explicit (and optionally int8 on the wire).
    The stacked cache leaves ride the layer loop's carry and are written
    in place, as in decode_step (as the scan's xs/ys they were a second
    cache to the compiler). Returns (logits [B, T, V] f32, the updated
    leaves {k, v[, k_scale, v_scale]})."""
    B, T = toks_blk.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    rep = nh // nkv
    quant = "k_scale" in cache
    S = cache["k"].shape[2]
    lengths = cache["length"]
    positions = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # [B, T]
    cos, sin = rotary_embedding(positions, cfg.hd, cfg.rope_theta)  # [B, T, hd/2]
    x = _tp_embed(params["embed"], toks_blk, tpc)  # [B, T, H]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    # query i sits at position length+i and may attend cache 0..length+i
    attn_ok = (jnp.arange(S, dtype=jnp.int32)[None, None, :] <= positions[:, :, None])[:, None, None]  # [B,1,1,T,S]

    def layer_fn(x, kv, layer, i):
        from ray_tpu.llm.kv_quant import quantize_heads

        xn = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q, k_t, v_t = _qkv(xn, layer, cfg)  # [B, T, nh/nkv, hd]
        qh = apply_rope(q.transpose(0, 2, 1, 3), cos, sin)  # [B, nh, T, hd]
        kh = apply_rope(k_t.transpose(0, 2, 1, 3), cos, sin).transpose(0, 2, 1, 3)  # [B, T, nkv, hd]
        k_blk, v_blk = kh, v_t
        if quant:
            k_blk, sk = quantize_heads(k_blk)  # [B, T, kv] scales
            v_blk, sv = quantize_heads(v_blk)
            # mixed advanced/slice indexing puts the [B, T] index dims
            # first: the indexed scale slots are [B, T, kv]
            kv["k_scale"] = kv["k_scale"].at[i, rows, :, positions].set(sk, mode="drop")
            kv["v_scale"] = kv["v_scale"].at[i, rows, :, positions].set(sv, mode="drop")
        kv["k"] = kv["k"].at[i, rows, positions].set(k_blk.astype(kv["k"].dtype), mode="drop")
        kv["v"] = kv["v"].at[i, rows, positions].set(v_blk.astype(kv["v"].dtype), mode="drop")
        qg = qh.reshape(B, nkv, rep, T, hd)
        kc = _layer_of(kv["k"], i).transpose(0, 2, 1, 3)  # [B, nkv, S, hd]
        vc = _layer_of(kv["v"], i).transpose(0, 2, 1, 3)
        if quant:
            kc = kc.astype(jnp.float32) * _layer_of(kv["k_scale"], i)[..., None]
            vc = vc.astype(jnp.float32) * _layer_of(kv["v_scale"], i)[..., None]
        scores = jnp.einsum("bgrth,bgsh->bgrts", qg, kc, preferred_element_type=jnp.float32) / jnp.sqrt(hd)
        scores = jnp.where(attn_ok, scores, -jnp.inf)
        o = jnp.einsum("bgrts,bgsh->bgrth", jax.nn.softmax(scores, axis=-1), vc.astype(jnp.float32))
        o = o.transpose(0, 3, 1, 2, 4).reshape(B, T, nh * hd).astype(x.dtype)
        x = x + _tp_reduce(jnp.dot(o, layer["wo"]), tpc)
        x = _mlp(x, layer, cfg, tpc)
        return x, kv

    x, kv = _scan_layers_carrying_cache(layer_fn, x, params, cache)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = _tp_gather_logits(jnp.einsum("bth,hv->btv", x, unembed, preferred_element_type=jnp.float32), tpc)
    return logits, kv


def _bucket_spec_verify(B=8, S=256, k=4, H=517):
    cfg = _trace_cfg()
    tokens, keys, temps, top_k, top_p = _sds_lanes(B)
    return (
        _sds_params(cfg), _sds_cache(cfg, B, S), _sds((B, k), jnp.int32),
        tokens, keys, temps, top_k, top_p, _sds((B,), jnp.int32),
        _sds((B, H), jnp.int32), _sds((B,), jnp.int32), cfg,
    ), {}


@jaxcheck.entry(
    name="llm.spec_verify",
    shapes={"b8_s256": _bucket_spec_verify},
    donate=("cache", "tokens", "keys", "temps", "top_k", "top_p", "spec_k", "hist", "hist_len"),
    donate_bytes=0,  # the spec hot loop is audited like fused_step's
)
def spec_verify_slots(
    params,
    cache,
    proposals,  # fresh drafter output, never re-read by the host: no buffer to save by donating
    tokens,
    keys,
    temps,
    top_k,
    top_p,
    spec_k,
    hist,
    hist_len,
    cfg: LlamaConfig,
    tpc: TpSpec | None = None,
):
    """ONE program for the slot layout's speculative tick: wide target
    forward over (t0, d1..dk) -> accept/sample -> append block KV ->
    length rollback -> history append. Unlike fused_step, the sampled
    TOKEN lane is also donated: the host reads the round's results from
    the dedicated emit/logps/acc outputs, never from the token lane."""
    toks_blk = jnp.concatenate([tokens[:, None], proposals], axis=1)
    logits, kv = _forward_block_slots(params, cache, toks_blk, cfg, tpc)
    emit, logps, acc, final, new_keys = _accept_and_sample(
        logits, proposals, spec_k, keys, temps, top_k, top_p
    )
    hist, hist_len = _update_hist(hist, hist_len, emit, acc)
    new_cache = {**kv, "length": cache["length"] + acc + 1}  # an int8 cache's scale lanes ride the rollback too
    return new_cache, emit, logps, acc, final, new_keys, temps, top_k, top_p, spec_k, hist, hist_len


def _bucket_spec_verify_q(B=8, S=256, k=4, H=517):
    cfg = _trace_cfg()
    tokens, keys, temps, top_k, top_p = _sds_lanes(B)
    return (
        _sds_params(cfg), _sds_cache_q(cfg, B, S), _sds((B, k), jnp.int32),
        tokens, keys, temps, top_k, top_p, _sds((B,), jnp.int32),
        _sds((B, H), jnp.int32), _sds((B,), jnp.int32), cfg,
    ), {}


# int8-cache variant (see model_runner's llm.fused_step_int8 rationale:
# donation + the JXC003 dequant trap audited on the quantized spec path)
jaxcheck.entry(
    name="llm.spec_verify_int8",
    shapes={"b8_s256": _bucket_spec_verify_q},
    donate=("cache", "tokens", "keys", "temps", "top_k", "top_p", "spec_k", "hist", "hist_len"),
    donate_bytes=0,
)(spec_verify_slots)


def _sharded_spec_verify_slots(cfg: LlamaConfig, mesh, tp_collective: str, kv_quant: bool):
    """spec_verify_slots under shard_map over the tp axis (unjitted) —
    the verify step compiles SPMD exactly like the fused decode step."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import axis_size

    tp = axis_size(mesh, "tp")
    tpc = TpSpec("tp", tp, tp_collective)
    cache_sp = _cache_pspecs("slots", kv_quant)
    rep = P()
    return _tp_shard_map(
        partial(spec_verify_slots, cfg=_shard_cfg(cfg, tp), tpc=tpc),
        mesh,
        in_specs=(_param_pspecs(cfg, mesh), cache_sp) + (rep,) * 9,
        out_specs=(cache_sp,) + (rep,) * 11,
    )


def make_spec_verify_slots(cfg: LlamaConfig, k: int, mesh=None, tp_collective: str = "fp", kv_quant: bool = False):
    """Jit of spec_verify_slots with the production donation set (the
    static width k is baked into the proposals shape by the caller).
    With a tp>1 mesh the tick compiles under shard_map — same explicit
    collective schedule as make_fused_fns."""
    del k  # shapes carry it; one compile per configured width
    from ray_tpu.parallel.mesh import axis_size

    if mesh is not None and axis_size(mesh, "tp") > 1:
        body = _sharded_spec_verify_slots(cfg, mesh, tp_collective, kv_quant)
        return named_jit("llm_verify_step", body, donate_argnums=(1, 3, 4, 5, 6, 7, 8, 9, 10))
    return named_jit("llm_verify_step", partial(spec_verify_slots, cfg=cfg),
                     donate_argnums=(1, 3, 4, 5, 6, 7, 8, 9, 10))


# ---------------------------------------------------------------------------
# paged layout
# ---------------------------------------------------------------------------
def _bucket_spec_verify_paged(B=8, pages=64, page=16, k=4, H=517):
    cfg = _trace_cfg()
    tokens, keys, temps, top_k, top_p = _sds_lanes(B)
    return (
        _sds_params(cfg), _sds_pool(cfg, pages, page), _sds((B, pages // B * 2), jnp.int32),
        _sds((B,), jnp.int32), _sds((B, k), jnp.int32),
        tokens, keys, temps, top_k, top_p, _sds((B,), jnp.int32),
        _sds((B, H), jnp.int32), _sds((B,), jnp.int32), cfg,
    ), {}


@jaxcheck.entry(
    name="llm.spec_verify_paged",
    shapes={"b8_p64": _bucket_spec_verify_paged},
    donate=("lengths", "tokens", "keys", "temps", "top_k", "top_p", "spec_k", "hist", "hist_len"),
    donate_bytes=0,
)
def spec_verify_paged(
    params,
    pool,  # read-only by design (the gather/scatter aliasing hazard); donated by the append program instead
    tables,
    lengths,
    proposals,  # fresh drafter output (see spec_verify_slots)
    tokens,
    keys,
    temps,
    top_k,
    top_p,
    spec_k,
    hist,
    hist_len,
    cfg: LlamaConfig,
    tpc: TpSpec | None = None,
    attn_impl: str = "xla",
):
    """READ-ONLY half of the paged speculative tick: block attention over
    the cached pages (prefix from the pool, the block itself in
    registers via `_paged_attn_seq`, vmapped over lanes) + accept/sample
    + write-target math; the pool scatter is spec_append_paged. Rows past
    a lane's table edge redirect to the trash page — those positions only
    arise in rounds whose tokens the host already discarded. ``tpc``:
    shard_map body mode, as on decode_step/_forward_block_slots.
    ``attn_impl``: "pallas" streams the prefix pages through the fused
    kernel (llm/pallas/paged_attn.py) — the wide-block verify rides the
    same HBM-streaming path as decode; "xla" stays the oracle."""
    from ray_tpu.llm.paged_kv import _paged_attn_seq_batch

    B, k = proposals.shape
    T = k + 1
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    rep = nh // nkv
    quant = "k_scale" in pool
    page = pool["k"].shape[2]
    max_pg = tables.shape[1]
    toks_blk = jnp.concatenate([tokens[:, None], proposals], axis=1)
    positions = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # [B, T]
    cos, sin = rotary_embedding(positions, cfg.hd, cfg.rope_theta)
    x = _tp_embed(params["embed"], toks_blk, tpc)  # [B, T, H]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    def layer_fn(x, xs):
        if quant:
            layer, k_pool_l, v_pool_l, k_sc_l, v_sc_l = xs
        else:
            layer, k_pool_l, v_pool_l = xs
            k_sc_l = v_sc_l = None
        xn = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q, k_t, v_t = _qkv(xn, layer, cfg)  # [B, T, nh/nkv, hd]
        qh = apply_rope(q.transpose(0, 2, 1, 3), cos, sin)  # [B, nh, T, hd]
        kh = apply_rope(k_t.transpose(0, 2, 1, 3), cos, sin).transpose(0, 2, 1, 3)  # [B, T, nkv, hd]
        qg = qh.reshape(B, nkv, rep, T, hd)
        o = _paged_attn_seq_batch(
            qg, k_pool_l, v_pool_l, tables, lengths, kh, v_t, scale, k_sc_l, v_sc_l,
            impl=attn_impl,
        )  # [B, nkv, rep, T, hd]
        o = o.transpose(0, 3, 1, 2, 4).reshape(B, T, nh * hd).astype(x.dtype)
        x = x + _tp_reduce(jnp.dot(o, layer["wo"]), tpc)
        x = _mlp(x, layer, cfg, tpc)
        return x, (kh, v_t)

    xs = (params["layers"], pool["k"], pool["v"])
    if quant:
        xs += (pool["k_scale"], pool["v_scale"])
    x, (k_blk, v_blk) = jax.lax.scan(layer_fn, x, xs)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = _tp_gather_logits(jnp.einsum("bth,hv->btv", x, unembed, preferred_element_type=jnp.float32), tpc)
    emit, logps, acc, final, new_keys = _accept_and_sample(
        logits, proposals, spec_k, keys, temps, top_k, top_p
    )
    hist, hist_len = _update_hist(hist, hist_len, emit, acc)
    pg_ix = positions // page
    wp = jnp.where(
        pg_ix < max_pg,
        tables[jnp.arange(B, dtype=jnp.int32)[:, None], jnp.minimum(pg_ix, max_pg - 1)],
        0,
    )
    wo = positions % page
    return (
        emit, logps, acc, final, new_keys, k_blk, v_blk, wp, wo,
        lengths + acc + 1, temps, top_k, top_p, spec_k, hist, hist_len,
    )


def spec_append_paged(pool, wp, wo, k_blk, v_blk):
    """Scatter-only half of the paged speculative tick: write the whole
    block's K/V ([L, B, T, kv, hd]) at (wp, wo) [B, T] for every layer.
    Rejected positions land in the lane's own dead tail (or the trash
    page) and are overwritten before the length rollback could expose
    them. An int8 pool quantizes here — the append program is the
    quantizer, mirroring append_paged."""
    if "k_scale" in pool:
        from ray_tpu.llm.kv_quant import quantize_heads

        k_blk, sk = quantize_heads(k_blk)  # [L, B, T, kv] scales
        v_blk, sv = quantize_heads(v_blk)
        return {
            "k": pool["k"].at[:, wp, wo].set(k_blk),
            "v": pool["v"].at[:, wp, wo].set(v_blk),
            # [L, P, kv, page] indexed at [:, wp, :, wo] -> [B, T, L, kv]
            "k_scale": pool["k_scale"].at[:, wp, :, wo].set(sk.transpose(1, 2, 0, 3)),
            "v_scale": pool["v_scale"].at[:, wp, :, wo].set(sv.transpose(1, 2, 0, 3)),
        }
    return {
        "k": pool["k"].at[:, wp, wo].set(k_blk.astype(pool["k"].dtype)),
        "v": pool["v"].at[:, wp, wo].set(v_blk.astype(pool["v"].dtype)),
    }


def _bucket_spec_verify_paged_q(B=8, pages=64, page=16, k=4, H=517):
    cfg = _trace_cfg()
    tokens, keys, temps, top_k, top_p = _sds_lanes(B)
    return (
        _sds_params(cfg), _sds_pool_q(cfg, pages, page), _sds((B, pages // B * 2), jnp.int32),
        _sds((B,), jnp.int32), _sds((B, k), jnp.int32),
        tokens, keys, temps, top_k, top_p, _sds((B,), jnp.int32),
        _sds((B, H), jnp.int32), _sds((B,), jnp.int32), cfg,
    ), {}


jaxcheck.entry(
    name="llm.spec_verify_paged_int8",
    shapes={"b8_p64": _bucket_spec_verify_paged_q},
    donate=("lengths", "tokens", "keys", "temps", "top_k", "top_p", "spec_k", "hist", "hist_len"),
    donate_bytes=0,
)(spec_verify_paged)


def _sharded_spec_verify_paged(cfg: LlamaConfig, mesh, tp_collective: str, kv_quant: bool):
    """spec_verify_paged under shard_map over the tp axis (unjitted); the
    block K/V leaves kv-sharded for the collective-free GSPMD append."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import axis_size

    tp = axis_size(mesh, "tp")
    tpc = TpSpec("tp", tp, tp_collective)
    pool_sp = _cache_pspecs("paged", kv_quant)
    kv_blk = P(None, None, None, "tp", None)  # k_blk/v_blk: [L, B, T, kv, hd]
    rep = P()
    return _tp_shard_map(
        partial(spec_verify_paged, cfg=_shard_cfg(cfg, tp), tpc=tpc),
        mesh,
        in_specs=(_param_pspecs(cfg, mesh), pool_sp) + (rep,) * 11,
        out_specs=(rep,) * 5 + (kv_blk, kv_blk) + (rep,) * 9,
    )


def make_spec_verify_paged(cfg: LlamaConfig, k: int, mesh=None, tp_collective: str = "fp", kv_quant: bool = False,
                           attn_impl: str = "xla"):
    """(attention+accept program, scatter-append program) for the paged
    layout — two dispatches, never fused (see decode_attn_paged). With a
    tp>1 mesh the attention half compiles under shard_map, same explicit
    collective schedule as the fused step. ``attn_impl="pallas"`` puts
    the wide-block prefix attention on the fused kernel (single-device
    path only, matching make_fused_paged_fns)."""
    del k
    from ray_tpu.parallel.mesh import axis_size

    if mesh is not None and axis_size(mesh, "tp") > 1:
        attn_fn = named_jit(
            "llm_verify_paged_attn", _sharded_spec_verify_paged(cfg, mesh, tp_collective, kv_quant),
            donate_argnums=(3, 5, 6, 7, 8, 9, 10, 11, 12),
        )
    else:
        attn_fn = named_jit("llm_verify_paged_attn", partial(spec_verify_paged, cfg=cfg, attn_impl=attn_impl),
                            donate_argnums=(3, 5, 6, 7, 8, 9, 10, 11, 12))
    append_fn = named_jit("llm_verify_append", spec_append_paged, donate_argnums=(0,))
    return attn_fn, append_fn


# ---------------------------------------------------------------------------
# jaxcheck entries for the SHARDED verify steps (see model_runner's tp
# entries): JXC005 audits the spec tick's collectives against the
# declared tp axis, and the donation/upcast rules re-check the SPMD form.
# ---------------------------------------------------------------------------
def _bucket_spec_verify_tp(B=8, S=256, k=4, H=517):
    cfg = _trace_cfg()
    tokens, keys, temps, top_k, top_p = _sds_lanes(B)
    return (
        _sds_params(cfg), _sds_cache(cfg, B, S), _sds((B, k), jnp.int32),
        tokens, keys, temps, top_k, top_p, _sds((B,), jnp.int32),
        _sds((B, H), jnp.int32), _sds((B,), jnp.int32),
    ), {}


@jaxcheck.entry(
    name="llm.spec_verify_tp",
    shapes={"b8_s256_tp2": _bucket_spec_verify_tp},
    donate=("cache", "tokens", "keys", "temps", "top_k", "top_p", "spec_k", "hist", "hist_len"),
    donate_bytes=0,
    mesh_axes=("tp",),
)
def spec_verify_tp(
    params,
    cache,
    proposals,  # fresh drafter output, never re-read by the host: no buffer to save by donating
    tokens,
    keys,
    temps,
    top_k,
    top_p,
    spec_k,
    hist,
    hist_len,
):
    """make_spec_verify_slots(mesh=2-way tp) in registry-traceable form."""
    return _sharded_spec_verify_slots(_trace_cfg(), _tp2_mesh(), "fp", False)(
        params, cache, proposals, tokens, keys, temps, top_k, top_p, spec_k, hist, hist_len
    )


def _bucket_spec_verify_paged_tp(B=8, pages=64, page=16, k=4, H=517):
    cfg = _trace_cfg()
    tokens, keys, temps, top_k, top_p = _sds_lanes(B)
    return (
        _sds_params(cfg), _sds_pool(cfg, pages, page), _sds((B, pages // B * 2), jnp.int32),
        _sds((B,), jnp.int32), _sds((B, k), jnp.int32),
        tokens, keys, temps, top_k, top_p, _sds((B,), jnp.int32),
        _sds((B, H), jnp.int32), _sds((B,), jnp.int32),
    ), {}


@jaxcheck.entry(
    name="llm.spec_verify_paged_tp",
    shapes={"b8_p64_tp2": _bucket_spec_verify_paged_tp},
    donate=("lengths", "tokens", "keys", "temps", "top_k", "top_p", "spec_k", "hist", "hist_len"),
    donate_bytes=0,
    mesh_axes=("tp",),
)
def spec_verify_paged_tp(
    params,
    pool,  # read-only by design (the gather/scatter aliasing hazard); donated by the append program instead
    tables,
    lengths,
    proposals,  # fresh drafter output (see spec_verify_slots)
    tokens,
    keys,
    temps,
    top_k,
    top_p,
    spec_k,
    hist,
    hist_len,
):
    """make_spec_verify_paged(mesh=2-way tp)'s attention half in
    registry-traceable form (the append half is collective-free GSPMD)."""
    return _sharded_spec_verify_paged(_trace_cfg(), _tp2_mesh(), "fp", False)(
        params, pool, tables, lengths, proposals, tokens, keys, temps, top_k, top_p, spec_k, hist, hist_len
    )


# ---------------------------------------------------------------------------
# O(1) scheduler deltas for the spec lanes
# ---------------------------------------------------------------------------
def set_hist_row(hist, hist_len, spec_k, slot, row, n, k0):  # deltas donate nothing, as make_delta_fns documents
    """Admission delta: one lane's token history, valid count and
    effective k (the row upload is one [H] int32 — tiny)."""
    return hist.at[slot].set(row), hist_len.at[slot].set(n), spec_k.at[slot].set(k0)


def set_slot_scalar(arr, slot, val):
    """O(1) jitted scatter: the controller's per-lane effective-k moves."""
    return arr.at[slot].set(val)
