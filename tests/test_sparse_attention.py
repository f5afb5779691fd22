"""``ops/sparse_attention.py`` and ``ops/slot_attention.attend_blocks``: steps 1-5 of the block
selection against what the sentences say, written out position by position in numpy; the decode
form (a table of blocks, gathered where they lie in the stack) against ``attend_rows`` over the same
positions; tables with fewer live blocks than places, an unbound lane, and the selection's
causality (a key after the query moves nothing, which a compressed key read before its window is
whole would break)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import slot_attention as sa
from ray_tpu.ops import sparse_attention as spa

SP = spa.SparseConfig(kernel=4, stride=2, block=8, topk=4, window=16, init_blocks=1, dense_len=32)


def _qkv(T, seed=0, B=2, nh=4, G=2, hd=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, T, nh, hd)), jax.random.normal(ks[1], (B, T, G, hd)), jax.random.normal(ks[2], (B, T, G, hd)))


def _by_the_sentences(q, k, v, n, sp):
    """One sequence of true length n, query by query: -> (o [n, nh, hd], chosen [n, G, blocks])."""
    T, nh, hd = q.shape
    G, rep, nb = k.shape[1], nh // k.shape[1], -(-T // sp.block)
    out, sets = np.zeros((n, nh, hd)), np.zeros((n, G, nb), bool)
    for t in range(n):
        own = t // sp.block
        usable = [j for j in range(T) if sp.stride * j + sp.kernel <= t + 1]
        for g in range(G):
            if n > sp.dense_len:
                R = np.zeros(len(usable))
                for h in range(g * rep, (g + 1) * rep):
                    s = np.array([q[t, h] @ k[sp.stride * j:sp.stride * j + sp.kernel, g].mean(0) for j in usable]) / np.sqrt(hd)
                    R += np.exp(s - s.max()) / np.exp(s - s.max()).sum() if usable else 0.0
                score = np.full(own + 1, -1.0)
                for b in range(own + 1):
                    over = [R[i] for i, j in enumerate(usable) if sp.stride * j < sp.block * (b + 1) and sp.stride * j + sp.kernel > sp.block * b]
                    score[b] = max(over, default=-1.0)
                    if b < sp.init_blocks or b > own - sp.window // sp.block:
                        score[b] = np.inf
                chosen = sorted(range(own + 1), key=lambda b: (-score[b], b))[:sp.topk]
            else:
                chosen = list(range(own + 1))
            sets[t, g, chosen] = True
            at = [s for s in range(t + 1) if s // sp.block in chosen]
            for h in range(g * rep, (g + 1) * rep):
                s = np.array([q[t, h] @ k[p, g] for p in at]) / np.sqrt(hd)
                w = np.exp(s - s.max()) / np.exp(s - s.max()).sum()
                out[t, h] = (w[:, None] * v[at, g]).sum(0)
    return out, sets


@pytest.mark.parametrize("T, lengths", [(64, (64, 45)), (40, (40, 33)), (48, (30, 48))])
def test_the_sequence_form_is_the_five_steps_query_by_query(T, lengths):
    q, k, v = _qkv(T, seed=T)
    o, kc = spa.sparse_attention_seq(q, k, v, jnp.asarray(lengths, jnp.int32), SP, tile=16)
    assert o.shape == (2, T, 4, 16) and kc.shape == (2, T // 2, 2, 16)
    for b, n in enumerate(lengths):
        want, _ = _by_the_sentences(*(np.asarray(a[b], np.float64) for a in (q, k, v)), n, SP)
        np.testing.assert_allclose(o[b, :n], want, atol=2e-5)
        whole = (n - SP.kernel) // SP.stride + 1
        np.testing.assert_allclose(kc[b, whole - 1], np.asarray(k[b, 2 * (whole - 1):2 * (whole - 1) + 4]).mean(0), atol=1e-6)
        assert not np.asarray(kc[b, whole:]).any()


def test_the_chosen_sets_hold_the_forced_blocks_and_no_more_than_top_k():
    q, k, _ = _qkv(64, seed=3)
    kc = spa.compress_keys(k, jnp.asarray([64, 64], jnp.int32), SP)
    t = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (2, 64))
    blocks, ok = spa.choose_blocks(spa.block_scores(q.reshape(2, 64, 2, 2, 16), kc, t, SP), t, SP)
    read = np.asarray(spa.chosen_mask(blocks, ok, 8))
    _, want = _by_the_sentences(*(np.asarray(a[0], np.float64) for a in (q, k, k)), 64, SP)
    assert (read[0] == want).all()
    for pos in range(64):
        own = pos // 8
        assert read[0, pos, :, 0].all() and read[0, pos, :, max(own - 1, 0):own + 1].all() and not read[0, pos, :, own + 1:].any()
        assert (read[0, pos].sum(-1) == min(own + 1, 4)).all()
    # fewer blocks at or before the query's than places in the table: the rest of the table names nothing
    assert (np.asarray(ok)[0, 10].sum(-1) == 2).all() and (np.asarray(ok)[0, 63].sum(-1) == 4).all()


def test_a_key_after_the_query_moves_nothing_and_an_unfinished_window_would():
    """Causality of the selection: the output at position t is the same whatever stands after t,
    because a compressed key is usable only once its whole window lies at or before t. Read one
    stride early (a window that is not whole) the chosen sets depend on keys the query must not see."""
    q, k, v = _qkv(64, seed=5)
    k2 = k.at[:, 41:].set(jax.random.normal(jax.random.PRNGKey(9), k[:, 41:].shape))
    v2 = v.at[:, 41:].set(0.0)
    lengths = jnp.asarray([64, 64], jnp.int32)
    a, _ = spa.sparse_attention_seq(q, k, v, lengths, SP)
    b, _ = spa.sparse_attention_seq(q, k2, v2, lengths, SP)
    np.testing.assert_allclose(a[:, :41], b[:, :41], atol=1e-6)
    t = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (2, 64))
    qg = q.reshape(2, 64, 2, 2, 16)
    early = [spa.block_scores(qg, spa.compress_keys(kk, lengths, SP), t + SP.stride, SP) for kk in (k, k2)]
    assert float(jnp.abs(early[0][:, :41] - early[1][:, :41]).max()) > 1e-3
    honest = [spa.block_scores(qg, spa.compress_keys(kk, lengths, SP), t, SP) for kk in (k, k2)]
    np.testing.assert_allclose(honest[0][:, :41], honest[1][:, :41], atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_decode_form_is_attend_rows_over_the_positions_of_the_tables_blocks(dtype):
    """A stack of 3 layers x 4 lanes x 64 positions: lane 0 reads a table of 4 of its 6 live blocks,
    lane 1 a table with one live block and three places that name nothing, lane 2 is bound to no
    sequence (no place names anything: zeros), lane 3 names ALL its live blocks and reads what the
    dense form reads."""
    L, B, S, G, hd, nh, N = 3, 4, 64, 2, 16, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    k_stack, v_stack = (jax.random.normal(kk, (L, B, S, G, hd)).astype(dtype) for kk in ks[:2])
    q = jax.random.normal(ks[2], (B, nh, hd)).astype(dtype)
    lengths = jnp.asarray([43, 5, 0, 30], jnp.int32)
    blocks = jnp.asarray([[[0, 5, 4, 2], [5, 0, 1, 4]], [[0, 3, 2, 1], [0, 1, 2, 3]], [[0, 0, 0, 0], [0, 0, 0, 0]], [[3, 2, 1, 0], [0, 1, 2, 3]]], jnp.int32)
    ok = jnp.asarray([[[True] * 4] * 2, [[True, False, False, False]] * 2, [[False] * 4] * 2, [[True] * 4] * 2])
    got = sa.attend_blocks(q, k_stack, v_stack, jnp.int32(1), lengths, blocks, ok, 8)
    assert got.shape == (B, nh * hd) and got.dtype == jnp.float32
    # the kernel form, interpreted: the same table through the index maps, the places that name nothing not read
    count = jnp.sum(ok[:, 0], axis=-1)
    kernel = sa.attend_blocks_kernel(q, k_stack, v_stack, jnp.int32(1), lengths, blocks, count, 8, interpret=True)
    np.testing.assert_allclose(kernel, got, atol=2e-5 if dtype == jnp.float32 else 2e-2)
    unread = sa.attend_blocks_kernel(q, k_stack, v_stack, jnp.int32(1), lengths, blocks, jnp.where(jnp.arange(B) == 0, count, 0), 8, interpret=True)
    np.testing.assert_allclose(unread[0], kernel[0], atol=0)
    assert not np.asarray(unread[1:]).any(), "a lane that is not live reads nothing and gets zeros"
    k_rows, v_rows = (np.asarray(a[1], np.float32) for a in (k_stack, v_stack))
    qf = np.asarray(q, np.float32)
    for b in (0, 1):
        for g in range(G):
            at = [p for p in range(int(lengths[b]) + 1) if any(bool(ok[b, g, n]) and p // 8 == int(blocks[b, g, n]) for n in range(N))]
            for h in range(g * 2, g * 2 + 2):
                s = k_rows[b, at, g] @ qf[b, h] / 4.0
                w = np.exp(s - s.max()) / np.exp(s - s.max()).sum()
                np.testing.assert_allclose(got[b, h * hd:(h + 1) * hd], w @ v_rows[b, at, g], atol=2e-5 if dtype == jnp.float32 else 2e-2)
    assert not np.asarray(got[2]).any(), "an unbound lane reads nothing and gets zeros"
    dense = sa.attend_rows(q, k_stack[1], v_stack[1], lengths, G)
    np.testing.assert_allclose(got[3], dense[3], atol=2e-5 if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("T, tiles", [(64, (16, 32)), (128, (32, 64)), (96, (32, 32))])
def test_the_kernel_of_step_five_is_the_masked_tiles(T, tiles, monkeypatch):
    """``attend_chosen`` (interpreted off the TPU) against the XLA form's masked tiles over the same
    table of chosen blocks, at tiles of keys that end before, on and after a tile of queries' last
    query, with a dense sequence beside one that chooses; then the sequence form through its gate."""
    q, k, v = _qkv(T, seed=T + 1)
    lengths = jnp.asarray([T, 30], jnp.int32)
    want, kc = spa.sparse_attention_seq(q, k, v, lengths, SP, tile=16)
    t = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (2, T))
    blocks, ok = spa.choose_blocks(spa.block_scores(q.reshape(2, T, 2, 2, 16), spa.compress_keys(k, lengths, SP), t, SP), t, SP)
    read = spa.chosen_mask(blocks, ok, T // 8) | ((lengths <= 32)[:, None, None, None] & (jnp.arange(T // 8) <= (t // 8)[..., None])[:, :, None])
    got = spa.attend_chosen(q, k, v, read, 8, tile_q=tiles[0], tile_k=tiles[1], interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)
    monkeypatch.setattr(spa, "refusal", lambda *a, **kw: None)
    monkeypatch.setattr(spa, "_TILE_Q", tiles[0])
    monkeypatch.setattr(spa, "_TILE_K", tiles[1])
    through, kc2 = spa.sparse_attention_seq(q, k, v, lengths, SP, tile=16)
    np.testing.assert_allclose(through, want, atol=2e-5)
    np.testing.assert_allclose(kc2, kc, atol=0)


def test_the_kernels_gate_says_why_by_name(monkeypatch):
    assert "backend" in spa.refusal(jnp.bfloat16, 128, 12288, 64)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert spa.refusal(jnp.bfloat16, 128, 12288, 64) is None and spa.refusal(jnp.bfloat16, 128, 16384, 64) is None
    assert "float32" in spa.refusal(jnp.float32, 128, 12288, 64) and "head_dim 64" in spa.refusal(jnp.bfloat16, 64, 12288, 64)
    assert "whole tiles" in spa.refusal(jnp.bfloat16, 128, 12288 + 64, 64) and "whole tiles" in spa.refusal(jnp.bfloat16, 128, 12288, 96)
