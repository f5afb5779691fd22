"""The flash forward kernel with each sequence's TRUE length (``ops/flash_attention``, ``lengths=``),
interpreted on the CPU: a query tile that starts at or past a row's length is skipped whole and comes
out as zeros, every tile before it is what it is without ``lengths``, bit for bit; the XLA form keeps
the same contract; and a call of ONE query tile never learns the lengths: it is the call without them.
The tile itself is the shape's too (``_fwd_blocks``, PR 61): heads up to 256 wide run 1,024 x 1,024 where
a call holds eight such query tiles or more, and the counter, the rule and the XLA form's zeros follow it.
Nothing here says anything of a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as fa

T, TILE = 512, 128  # the bucket and the tile: four query tiles a row
# a length of 1, one under a tile's edge, on it, one over it, and the bucket itself
LENGTHS = [1, 2 * TILE - 1, 2 * TILE, 2 * TILE + 1, T]
SHAPES = [(128, None), (128, 160), (256, None), (256, TILE)]  # head width, window


def _operands(head_dim, rows=len(LENGTHS), length=T, heads=2):
    """Two heads: a row's length is repeated over them."""
    return tuple(jax.random.normal(jax.random.PRNGKey(n), (rows, heads, length, head_dim), jnp.float32) for n in (1, 2, 3))


def _live(n, tile=TILE, length=T):
    """Positions of the tiles that start under a length of ``n``: the tile that holds position n - 1 is computed whole."""
    return min(-(-n // tile) * tile, length)


def _pallas_calls(fn, *args):
    """The ``pallas_call`` equations that ``fn(*args)`` traces."""
    def find(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e.params
            for v in e.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    yield from find(getattr(inner, "jaxpr", inner))

    return list(find(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("head_dim, window", SHAPES)
def test_query_tiles_past_a_true_length_are_zeros_and_the_rest_is_the_call_without_lengths(head_dim, window):
    from jax.experimental.pallas import tpu as pltpu

    q, k, v = _operands(head_dim)
    kw = dict(causal=True, window=window, block_q=TILE, block_k=TILE)
    with pltpu.force_tpu_interpret_mode():
        plain, plain_lse = fa._fwd_pallas(q, k, v, **kw)
        out, lse = fa._fwd_pallas(q, k, v, lengths=jnp.asarray(LENGTHS, jnp.int32), **kw)
    ref, ref_lse = fa._fwd_xla_with_lse(q, k, v, True, None, window)
    np.testing.assert_allclose(plain, ref, atol=2e-3)
    # ``lse`` is the [B, H, T] it is by contract, and every row's is its own keys': under a window the
    # rows at a query tile's end meet their first computed key tile with every pair outside (a maximum of _NEG_INF over a
    # state of _NEG_INF: exp(0) is 1 a pair), which would count 128 keys more in ``l``, log(2) or more in ``lse``
    assert plain_lse.shape == q.shape[:3]
    np.testing.assert_allclose(plain_lse, ref_lse, atol=2e-3)
    if window is not None:
        first_tile = (np.arange(T) // TILE * TILE - window + 1).clip(0) // TILE  # the first key tile a row's query tile computes
        unread = (np.arange(T) - window + 1).clip(0) >= (first_tile + 1) * TILE  # rows whose own window starts past it
        assert unread[T - 1] and unread.sum() >= T // TILE - 1, "the case is in the operands: the last row of every query tile but the first"
    plain, plain_lse, out, lse = (np.asarray(a) for a in (plain, plain_lse, out, lse))
    for b, n in enumerate(LENGTHS):
        live = _live(n)
        assert np.array_equal(out[b, :, :live], plain[b, :, :live]), f"row {b}: positions under {live} are the call's without lengths, bit for bit"
        assert np.array_equal(lse[b, :, :live], plain_lse[b, :, :live])
        assert not out[b, :, live:].any(), f"row {b}: every position from {live} on is exactly zero"
    assert np.isfinite(out).all() and np.isfinite(lse).all()

    # without lengths the call is what it was: no prefetched scalar, the same grid, blocks, name and cost; with them, one more operand
    B, H = q.shape[:2]
    shape = jax.ShapeDtypeStruct(q.shape, jnp.bfloat16)
    (was,) = _pallas_calls(lambda q, k, v: fa._fwd_pallas(q, k, v, **kw), shape, shape, shape)
    (now,) = _pallas_calls(lambda q, k, v, n: fa._fwd_pallas(q, k, v, lengths=n, **kw), shape, shape, shape, jax.ShapeDtypeStruct((B,), jnp.int32))
    assert (was["grid_mapping"].num_index_operands, now["grid_mapping"].num_index_operands) == (0, 1)
    for params in (was, now):
        gm = params["grid_mapping"]
        assert gm.grid == (B * H, T // TILE, T // TILE)
        assert [tuple(getattr(d, "block_size", d) for d in bm.block_shape) for bm in gm.block_mappings] == (
            [(1, TILE, head_dim)] * 4 + [(1, 1, TILE)])
        cost = params["cost_estimate"]
        assert (cost.flops, cost.transcendentals, cost.bytes_accessed) == (4 * B * H * T * T * head_dim, B * H * T * T, 3 * B * H * T * head_dim * 2)
        assert ("window_flash_attention" in str(params["name"])) == (window is not None)
    # a query tile's index map without lengths is the identity it was
    assert str(was["grid_mapping"].block_mappings[0].index_map_jaxpr.jaxpr).replace(" ", "").endswith("in(a,b,0:i32[])}")


@pytest.mark.parametrize("head_dim, window", SHAPES)
def test_the_xla_form_and_the_kernel_agree_on_which_rows_are_zeros(head_dim, window, monkeypatch):
    """Through ``flash_attention`` as a model calls it, at one tile size for both forms: the rows that
    come out as zeros are the same rows, and the live ones are the same attention."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(fa, "_default_blocks", lambda head_dim: (TILE, TILE))
    q, k, v = _operands(head_dim)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    xla = np.asarray(fa.flash_attention(q, k, v, True, None, "xla", window, lengths))
    with pltpu.force_tpu_interpret_mode():
        kernel = np.asarray(fa.flash_attention(q, k, v, True, None, "pallas", window, lengths))
    ref = np.asarray(fa.flash_attention(q, k, v, True, None, "xla", window))
    for b, n in enumerate(LENGTHS):
        live = _live(n)
        assert not xla[b, :, live:].any() and not kernel[b, :, live:].any()
        assert np.array_equal(xla[b, :, :live], ref[b, :, :live]), "the XLA form's live rows are its rows without lengths, bit for bit"
        assert xla[b, :, :live].all(axis=-1).all() and kernel[b, :, :live].all(axis=-1).all(), "no live row is a row of zeros"
    np.testing.assert_allclose(kernel, xla, atol=2e-3)


@pytest.mark.parametrize("head_dim", [128, 256])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_a_call_of_one_query_tile_never_learns_the_lengths(head_dim, impl):
    """THE rule, from the shape alone (``_skippable``): at the default tile (1,024 positions at 128
    columns, 512 at 256) a bucket of one tile traces to the SAME jaxpr with ``lengths`` as without
    them, with no prefetched scalar and no zeros; one position more and the call takes them."""
    one = fa._default_blocks(head_dim)[0]
    for length, taken in ((one // 2, False), (one, False), (2 * one, True)):
        shape = jax.ShapeDtypeStruct((2, 2, length, head_dim), jnp.bfloat16)
        n = jax.ShapeDtypeStruct((2,), jnp.int32)
        with_n = lambda q, k, v, n: fa.flash_attention(q, k, v, True, None, impl, None, n)  # noqa: E731
        without = lambda q, k, v, n: fa.flash_attention(q, k, v, True, None, impl, None, None)  # noqa: E731
        same = str(jax.make_jaxpr(with_n)(shape, shape, shape, n)) == str(jax.make_jaxpr(without)(shape, shape, shape, n))
        assert same != taken, f"{length} positions in tiles of {one}"
        if impl == "pallas":
            (call,) = _pallas_calls(with_n, shape, shape, shape, n)
            assert call["grid_mapping"].num_index_operands == int(taken)
            # and called by itself the jitted kernel's wrapper drops them under the same rule
            (call,) = _pallas_calls(lambda q, k, v, n: fa._fwd_pallas(q, k, v, lengths=n), shape, shape, shape, n)
            assert call["grid_mapping"].num_index_operands == int(taken)
    # a row of length 1 in a bucket of one tile: attention over the padding, as the parent computed it, not zeros
    q, k, v = _operands(head_dim, rows=1, length=64, heads=1)
    got = fa.flash_attention(q, k, v, True, None, "xla", None, jnp.asarray([1], jnp.int32))
    assert np.array_equal(got, fa.flash_attention(q, k, v, True, None, "xla")) and np.asarray(got)[0, :, 1:].any()


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_a_differentiated_call_that_takes_lengths_refuses(impl, monkeypatch):
    """No backward pass knows a length: a call that takes them refuses at trace time. A bucket of
    one tile does not take them: it IS the call without lengths, its gradient too."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(fa, "_default_blocks", lambda head_dim: (TILE, TILE))
    q, k, v = _operands(128, rows=2, heads=1)
    with pytest.raises(NotImplementedError, match="true length"), pltpu.force_tpu_interpret_mode():
        jax.grad(lambda q: fa.flash_attention(q, k, v, True, None, impl, None, jnp.asarray([1, T], jnp.int32)).sum())(q)
    if impl == "xla":
        q, k, v = _operands(128, rows=2, length=TILE, heads=1)
        g = jax.grad(lambda q: fa.flash_attention(q, k, v, True, None, impl, None, jnp.asarray([1, TILE], jnp.int32)).sum())(q)
        assert np.array_equal(g, jax.grad(lambda q: fa.flash_attention(q, k, v, True, None, impl).sum())(q))
        np.testing.assert_allclose(g, jax.grad(lambda q: fa.attention_xla(q, k, v, causal=True).sum())(q), atol=2e-4)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_lengths_are_causal(impl):
    """Right-padded lengths mean something under a causal mask only: a non-causal call refuses them, in any bucket."""
    from jax.experimental.pallas import tpu as pltpu

    for length in (64, T):
        q, k, v = _operands(128, rows=1, length=length, heads=1)
        with pytest.raises(ValueError, match="causal"), pltpu.force_tpu_interpret_mode():
            fa.flash_attention(q, k, v, False, None, impl, None, jnp.asarray([3], jnp.int32))
    with pytest.raises(ValueError, match="causal"), pltpu.force_tpu_interpret_mode():
        fa._fwd_pallas(q, k, v, causal=False, lengths=jnp.asarray([3], jnp.int32))


@pytest.mark.parametrize("head_dim, length, tiles, live", [
    (128, 1024, 3 * 5 * 1, 3 * 5 * 1),  # one tile of 1,024: every row counts it in both, a padding row of length 1 too
    (128, 2048, 3 * 5 * 2, 3 * (1 + 1 + 1 + 2 + 2)),
    (256, 2048, 3 * 5 * 4, 3 * (1 + 1 + 2 + 3 + 4)),
    (256, 512, 3 * 5 * 1, 3 * 5 * 1),
    (256, 4096, 3 * 5 * 8, 3 * (1 + 1 + 2 + 3 + 8)),  # under eight tiles of 1,024: heads 256 wide keep tiles of 512
    (256, 8192, 3 * 5 * 8, 3 * (1 + 1 + 1 + 2 + 8)),  # eight of them: the long call runs tiles of 1,024 (``_fwd_blocks``)
    (512, 8192, 3 * 5 * 16, 3 * (1 + 1 + 2 + 3 + 16)),  # wider heads keep 512 at any length
])
def test_the_counter_is_the_shapes_arithmetic(head_dim, length, tiles, live):
    """``attn_q_tiles``: calls x rows x tiles of the bucket; ``attn_q_tiles_live``: the tiles that start under a length."""
    lengths = [1, 512, 513, 1025, length]
    assert fa.query_tiles({head_dim: 3}, length, lengths) == {"attn_q_tiles": tiles, "attn_q_tiles_live": live}
    assert fa.query_tiles({}, length, lengths) == {"attn_q_tiles": 0, "attn_q_tiles_live": 0}


@pytest.mark.parametrize("head_dim, length, tile", [(128, 2048, 1024), (128, 16384, 1024), (256, 4096, 512), (256, 8192, 1024), (256, 16384, 1024), (512, 8192, 512)])
def test_the_forward_tile_is_the_shapes(head_dim, length, tile):
    """``_fwd_blocks``: the tile of a head width (``_default_blocks``), and 1,024 x 1,024 up to heads 256 wide where a call
    holds eight such query tiles or more; the kernel's grid and scratch and the tile of the rule, the counter and the XLA
    form's zeros (``_query_tile``) are the same, and ``_default_blocks``, which the backward kernels ask, is what it was."""
    assert fa._fwd_blocks(head_dim, length) == (tile, tile) and fa._query_tile(head_dim, length) == tile
    assert fa._default_blocks(head_dim) == ((1024, 1024) if head_dim <= 128 else (512, 512))
    shape = jax.ShapeDtypeStruct((1, 2, length, head_dim), jnp.bfloat16)
    n = jax.ShapeDtypeStruct((1,), jnp.int32)
    (call,) = _pallas_calls(lambda q, k, v, n: fa._fwd_pallas(q, k, v, lengths=n), shape, shape, shape, n)
    assert call["grid_mapping"].grid == (2, length // tile, length // tile)
    assert [a.shape for a in call["grid_mapping"].scratch_avals] == [(tile, 1), (tile, 1), (tile, head_dim)]
