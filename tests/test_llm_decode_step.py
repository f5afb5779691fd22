"""The slot layout's ``decode_step`` against an independent statement of the
same step (PR 30): the program carries the stacked cache through a
``lax.scan`` and writes one token a layer in place; the statement below is a
plain Python loop over layers with no scan, no scatter and no in-place update
(a new row is built with ``where``), and tensor parallelism written out as
"each shard holds some heads and a slice of the MLP; partial sums add".
Both are compiled by the same compiler (eager dispatch rounds a fused
multiply-add differently than a compiled fusion does, by 1e-6), and at
float32 the two then agree bit for bit: logits and every cache leaf.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

from functools import partial  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import Mesh  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from ray_tpu.llm import model_runner as mr  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, forward, init_params  # noqa: E402

CFG = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=3, num_heads=4, num_kv_heads=2,
                  head_dim=8, max_seq_len=16, dtype="float32", remat=False)
S = CFG.max_seq_len
# an empty lane, a lane at the last position, a lane past it (a finished sequence the engine has
# not recycled yet: the write clamps to S - 1), and two in between
LENGTHS = (0, 5, S - 1, S, 9)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(3))


def _cache(kind: str, lengths, seed=0):
    """A slot cache full of random rows (stale garbage past each length included)."""
    B = len(lengths)
    shape = (CFG.num_layers, B, S, CFG.num_kv_heads, CFG.hd)
    k, v, ks, vs = jax.random.split(jax.random.PRNGKey(seed), 4)
    length = jnp.asarray(lengths, jnp.int32)
    if kind == "int8":
        sshape = (CFG.num_layers, B, CFG.num_kv_heads, S)
        return {"k": jax.random.randint(k, shape, -127, 128, jnp.int8), "v": jax.random.randint(v, shape, -127, 128, jnp.int8),
                "k_scale": jax.random.uniform(ks, sshape, jnp.float32, 0.001, 0.02),
                "v_scale": jax.random.uniform(vs, sshape, jnp.float32, 0.001, 0.02), "length": length}
    dt = jnp.dtype(kind)
    return {"k": jax.random.normal(k, shape, jnp.float32).astype(dt), "v": jax.random.normal(v, shape, jnp.float32).astype(dt),
            "length": length}


# ---------------------------------------------------------------------------
# the statement: nothing below is imported from the program
# ---------------------------------------------------------------------------
def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):  # x [B, heads, hd], pos [B]: split-half rotation
    half = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * (1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half)))
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _int8(x):  # one scale a head: amax / 127
    amax = jnp.max(jnp.abs(x), axis=-1)
    inv = jnp.where(amax > 0.0, 127.0 / jnp.maximum(amax, 1e-30), 0.0)
    return jnp.clip(jnp.round(x * inv[..., None]), -127.0, 127.0).astype(jnp.int8), amax / 127.0


@partial(jax.jit, static_argnames=("cfg", "tp"))
def plain_step(params, cache, tokens, cfg, tp=1):
    """One decode step, layer by layer: (logits [B, V], new cache)."""
    B, L, hd = tokens.shape[0], cfg.num_layers, cfg.hd
    nh, nkv, V, F = cfg.num_heads // tp, cfg.num_kv_heads // tp, cfg.vocab_size // tp, cfg.intermediate_size // tp
    quant, length = "k_scale" in cache, cache["length"]
    pos = jnp.minimum(length, S - 1)
    here = (jnp.arange(S)[None, :] == pos[:, None])  # [B, S]: the position this step writes
    seen = (jnp.arange(S)[None, :] <= length[:, None])  # [B, S]: what the new token may attend to
    x = params["embed"][tokens]
    new = {name: [] for name in cache if name != "length"}
    for i in range(L):
        w = {name: leaf[i] for name, leaf in params["layers"].items()}
        xn = _norm(x, w["attn_norm"], cfg.rms_eps)
        shards, attn_out = {name: [] for name in new}, 0.0
        for s in range(tp):
            q = _rope((xn @ w["wq"][:, s * nh * hd:(s + 1) * nh * hd]).reshape(B, nh, hd), length, cfg.rope_theta)
            k = _rope((xn @ w["wk"][:, s * nkv * hd:(s + 1) * nkv * hd]).reshape(B, nkv, hd), length, cfg.rope_theta)
            v = (xn @ w["wv"][:, s * nkv * hd:(s + 1) * nkv * hd]).reshape(B, nkv, hd)
            rows = {name: cache[name][i][:, :, s * nkv:(s + 1) * nkv] if name in ("k", "v")
                    else cache[name][i][:, s * nkv:(s + 1) * nkv] for name in new}
            if quant:
                (k, sk), (v, sv) = _int8(k), _int8(v)
                rows["k_scale"] = jnp.where(here[:, None, :], sk[:, :, None], rows["k_scale"])
                rows["v_scale"] = jnp.where(here[:, None, :], sv[:, :, None], rows["v_scale"])
            rows["k"] = jnp.where(here[:, :, None, None], k[:, None].astype(rows["k"].dtype), rows["k"])
            rows["v"] = jnp.where(here[:, :, None, None], v[:, None].astype(rows["v"].dtype), rows["v"])
            kc, vc = rows["k"].transpose(0, 2, 1, 3), rows["v"].transpose(0, 2, 1, 3)  # [B, nkv, S, hd]
            if quant:
                kc = kc.astype(jnp.float32) * rows["k_scale"][..., None]
                vc = vc.astype(jnp.float32) * rows["v_scale"][..., None]
            qg = q.reshape(B, nkv, nh // nkv, hd)
            scores = jnp.einsum("bgrh,bgsh->bgrs", qg, kc, preferred_element_type=jnp.float32) / jnp.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(seen[:, None, None], scores, -jnp.inf), axis=-1)
            o = jnp.einsum("bgrs,bgsh->bgrh", probs, vc.astype(jnp.float32)).reshape(B, nh * hd)
            attn_out = attn_out + o @ w["wo"][s * nh * hd:(s + 1) * nh * hd]
            for name in new:
                shards[name].append(rows[name])
        x = x + attn_out
        xn = _norm(x, w["mlp_norm"], cfg.rms_eps)
        x = x + sum((jax.nn.silu(xn @ w["w_gate"][:, s * F:(s + 1) * F]) * (xn @ w["w_up"][:, s * F:(s + 1) * F]))
                    @ w["w_down"][s * F:(s + 1) * F] for s in range(tp))
        for name in new:
            new[name].append(jnp.concatenate(shards[name], axis=2 if name in ("k", "v") else 1))
    x = _norm(x, params["final_norm"], cfg.rms_eps)
    logits = jnp.concatenate([x @ params["unembed"][:, s * V:(s + 1) * V] for s in range(tp)], axis=-1)
    return logits, {**{name: jnp.stack(rows) for name, rows in new.items()}, "length": length + 1}


# ---------------------------------------------------------------------------
def _compiled(fn, tp: int, quant: bool, out_cache_specs=None):
    """``fn(params, cache, tokens, cfg[, tpc])`` as its callers compile it: plain, or as the
    shard_map body over ``tp`` CPU devices (jit places the arguments)."""
    if tp == 1:
        return jax.jit(partial(fn, cfg=CFG))
    mesh = Mesh(np.asarray(jax.devices()[:tp]), ("tp",))
    cache_sp = mr._cache_pspecs("slots", quant)
    return jax.jit(mr._tp_shard_map(
        partial(fn, cfg=mr._shard_cfg(CFG, tp), tpc=mr.TpSpec("tp", tp, "fp")), mesh,
        in_specs=(mr._param_pspecs(CFG, mesh), cache_sp, P()), out_specs=(P(), out_cache_specs or cache_sp)))


def _assert_same(got, want):
    assert set(got) == set(want)
    for name in want:
        g, w = np.asarray(got[name]), np.asarray(want[name])
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("kind", ["float32", "bfloat16", "int8"])
def test_carried_decode_step_equals_the_plain_loop_bit_for_bit(params, kind, tp):
    quant = kind == "int8"
    cache = _cache(kind, LENGTHS)
    tokens = jnp.asarray([7, 0, 63, 21, 40], jnp.int32)
    want_logits, want_cache = plain_step(params, cache, tokens, CFG, tp)
    logits, new_cache = _compiled(mr.decode_step, tp, quant)(params, cache, tokens)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    _assert_same(new_cache, want_cache)
    # one token a lane and a layer changed, nothing else
    changed = np.asarray(new_cache["k"] != cache["k"]).any(axis=(0, 3, 4))  # [B, S]
    assert not changed[~np.asarray(jnp.arange(S)[None] == jnp.minimum(cache["length"], S - 1)[:, None])].any()


def test_chained_through_draft_steps_equals_the_plain_loop_chained(params):
    """``spec/drafter.py::draft_steps`` chains k + 1 steps inside one program with the length lane
    overridden: the same proposals and the same cache as the plain step called k + 1 times."""
    from ray_tpu.llm.spec.drafter import draft_steps

    k, lengths = 3, jnp.asarray([4, 0, S - 5, 8, 2], jnp.int32)
    cache = _cache("float32", (9, 9, 9, 9, 9), seed=1)  # the stored lane is stale: draft_steps overwrites it
    hist = jax.random.randint(jax.random.PRNGKey(5), (5, 12), 0, CFG.vocab_size, jnp.int32)
    hist_len = jnp.asarray([4, 1, 11, 8, 2], jnp.int32)
    proposals, new_cache = jax.jit(partial(draft_steps, cfg=CFG, k=k))(params, cache, hist, hist_len, lengths)
    tok = hist[jnp.arange(5), hist_len - 1]
    want, c = [], {**cache, "length": lengths}
    for _ in range(k + 1):
        logits, c = plain_step(params, c, tok, CFG)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        want.append(tok)
    np.testing.assert_array_equal(np.asarray(proposals), np.stack(want[:k], axis=1))
    _assert_same(new_cache, c)


@pytest.mark.parametrize("kind", ["float32", "int8"])
def test_prefill_then_decode_steps_follow_one_causal_forward(params, kind):
    """prefill + n carried decode steps on lanes of different lengths against the model's own
    causal forward over the whole sequence (teacher-forced): the cache the steps leave is read
    back correctly by the steps after them."""
    from ray_tpu.llm import kv_cache as kvc

    n, prompt_lens = 5, (3, 7, 1)
    seqs = jax.random.randint(jax.random.PRNGKey(11), (len(prompt_lens), 7 + n), 0, CFG.vocab_size, jnp.int32)
    cache = kvc.alloc(kvc.CacheConfig(num_layers=CFG.num_layers, num_slots=len(prompt_lens), max_seq_len=S,
                                      num_kv_heads=CFG.num_kv_heads, head_dim=CFG.hd, dtype=kind))
    for b, T in enumerate(prompt_lens):
        toks = jnp.zeros((1, 8), jnp.int32).at[0, :T].set(seqs[b, :T])
        _, ks, vs = mr.prefill(params, toks, jnp.asarray([T], jnp.int32), CFG)
        cache = kvc.insert_sequence(cache, b, ks[:, 0], vs[:, 0], T)
    step = jax.jit(partial(mr.decode_step, cfg=CFG), donate_argnums=(1,))
    full = forward(params, seqs, CFG)  # [B, T, V]
    tol = 0.05 if kind == "int8" else 2e-5
    for j in range(n):
        at = jnp.asarray(prompt_lens, jnp.int32) + j
        logits, cache = step(params, cache, seqs[jnp.arange(len(prompt_lens)), at])
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full[jnp.arange(len(prompt_lens)), at]), atol=tol, rtol=0)
    np.testing.assert_array_equal(np.asarray(cache["length"]), np.asarray(prompt_lens) + n)


# ---------------------------------------------------------------------------
# the two other programs whose layer loop carries the slot cache: a chunk for one lane (extend)
# and a block for every lane (speculative verify) are the plain step, chained token by token
# ---------------------------------------------------------------------------
def _chained(params, cache, tokens):
    """tokens [B, T] teacher-forced through T plain steps -> (logits [B, T, V], cache)."""
    out = []
    for t in range(tokens.shape[1]):
        logits, cache = plain_step(params, cache, tokens[:, t], CFG)
        out.append(logits)
    return jnp.stack(out, axis=1), cache


def _assert_close_where_written(got, want, before, tol):
    """Cache leaves: what the chained steps wrote agrees to ``tol`` (int8 values to one step of the
    grid: a product that rounds the other way at 1e-7), and what they left alone is left alone bit for bit."""
    for name in want:
        g, w, b = (np.asarray(a[name]).astype(np.float32) for a in (got, want, before))
        kept = w == b
        np.testing.assert_array_equal(g[kept], b[kept], err_msg=name)
        np.testing.assert_allclose(g[~kept], w[~kept], atol=1.0 if want[name].dtype == jnp.int8 else tol, rtol=0, err_msg=name)


@pytest.mark.parametrize("kind", ["float32", "int8"])
def test_extend_equals_the_plain_step_chained_on_one_lane(params, kind):
    lengths, slot, n = (6, 3, 0, 9, 12), 1, 5
    cache = _cache(kind, lengths)
    chunk = jnp.asarray([11, 2, 50, 33, 7, 0, 0, 0], jnp.int32)  # 5 real tokens in a bucket of 8
    logits, new_cache = jax.jit(partial(mr.extend, cfg=CFG))(params, cache, jnp.int32(slot), chunk, jnp.int32(n))
    # the chained steps advance every lane; only `slot`'s rows and logits are the chunk's
    want_logits, want_cache = _chained(params, cache, jnp.zeros((len(lengths), n), jnp.int32).at[slot].set(chunk[:n]))
    tol = 0.05 if kind == "int8" else 2e-5
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits[slot, n - 1]), atol=tol, rtol=0)
    np.testing.assert_array_equal(np.asarray(new_cache["length"]), np.asarray(cache["length"].at[slot].add(n)))
    real = slice(lengths[slot], lengths[slot] + n)  # the padded tail's rows are garbage the mask hides
    for name in ("k", "v"):
        got, want, was = (np.asarray(c[name]).astype(np.float32) for c in (new_cache, want_cache, cache))
        np.testing.assert_allclose(got[:, slot, real], want[:, slot, real], atol=1.0 if kind == "int8" else tol, rtol=0)
        others = np.arange(len(lengths)) != slot
        np.testing.assert_array_equal(got[:, others], was[:, others])
        np.testing.assert_array_equal(got[:, slot, :lengths[slot]], was[:, slot, :lengths[slot]])


@pytest.mark.parametrize("kind", ["float32", "int8"])
def test_extend_drops_a_padded_tail_that_runs_past_the_horizon(params, kind):
    """A prefix of 13 and 2 real tokens in a bucket of 8 end at 21 of 16 positions: the real tokens
    land at 13 and 14 and the cached prefix stays (a dynamic_update_slice at `start` is clamped to
    8 and writes the chunk over positions 8-15, prefix included)."""
    cache = _cache(kind, (13, 4))
    chunk = jnp.asarray([11, 2, 0, 0, 0, 0, 0, 0], jnp.int32)
    step = jax.jit(partial(mr.extend, cfg=CFG))
    logits, new_cache = step(params, cache, jnp.int32(0), chunk, jnp.int32(2))
    fits_logits, fits_cache = step(params, cache, jnp.int32(0), chunk[:2], jnp.int32(2))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(fits_logits), atol=2e-5, rtol=0)
    for name in new_cache:
        pos_last = name.endswith("_scale")
        got, want = ((np.moveaxis(np.asarray(c[name]), -1, 2) if pos_last else np.asarray(c[name])) for c in (new_cache, fits_cache))
        if name != "length":
            got, want = got[:, :, :15], want[:, :, :15]  # position 15 holds padded garbage in one and not the other
        np.testing.assert_array_equal(got, want, err_msg=name)


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("kind", ["float32", "int8"])
def test_verify_block_forward_equals_the_plain_step_chained(params, kind, tp):
    from ray_tpu.llm.spec.verify import _forward_block_slots

    quant, lengths = kind == "int8", (0, 5, S - 2, 9, 3)  # the lane at S - 2 runs past the horizon: dropped
    cache = _cache(kind, lengths)
    block = jax.random.randint(jax.random.PRNGKey(21), (len(lengths), 4), 0, CFG.vocab_size, jnp.int32)
    kv_specs = {name: sp for name, sp in mr._cache_pspecs("slots", quant).items() if name != "length"}
    logits, kv = _compiled(_forward_block_slots, tp, quant, kv_specs)(params, cache, block)
    fits = np.asarray(lengths)[:, None] + np.arange(4)[None] < S  # [B, T]: tokens whose position exists
    want_logits, want_cache = _chained(params, cache, block)
    tol = 0.05 if quant else 2e-5
    np.testing.assert_allclose(np.asarray(logits)[fits], np.asarray(want_logits)[fits], atol=tol, rtol=0)
    lane = np.asarray(lengths) + 4 <= S  # a lane that ran past the horizon clamped its last writes in the chained steps
    got, want, was = ({n: np.asarray(c[n])[:, lane] for n in kv} for c in (kv, want_cache, cache))
    _assert_close_where_written(got, want, was, tol)
