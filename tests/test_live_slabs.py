"""A position-wise function over the slabs of positions under each row's TRUE length
(``ops/layers.live_slabs``): every row under a length is what the plain form gives, whole slabs
past it are zeros, and where there is nothing to skip (no lengths, a bucket of one slab, a bucket
that is no whole number of them) the plain form IS what runs. The slab is 512 positions on the
chip; here it is patched to 16. Nothing here says anything of a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import layers

S, T, H, F = 16, 64, 32, 48  # the slab (patched), the bucket (four slabs), the widths of a small SwiGLU with its norm


def _operands(B, length, dtype):
    keys = jax.random.split(jax.random.PRNGKey(54), 5)
    x = jax.random.normal(keys[0], (B, length, H), jnp.float32).astype(dtype)
    w = {"norm": 1 + 0.1 * jax.random.normal(keys[1], (H,)), "gate": jax.random.normal(keys[2], (H, F)) * H ** -0.5,
         "up": jax.random.normal(keys[3], (H, F)) * H ** -0.5, "down": jax.random.normal(keys[4], (F, 2 * H)) * F ** -0.5}
    return x, {n: a.astype(dtype) for n, a in w.items()}


def _mlp(x, w):
    """[.., H] -> [.., 2H]: a norm and three products, a position at a time."""
    return layers.swiglu(layers.rms_norm(x, w["norm"]), w["gate"], w["up"], w["down"])


def _loops(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("while[")


# lengths of the rows of one [B, T] bucket -> the slabs each row runs; None: the plain form runs (and why)
CASES = {
    "one-row-ragged": (T, [37], [3]),
    "ragged-with-a-padding-row-of-1": (T, [23, 1, 50], [2, 1, 4]),
    "exactly-k-slabs": (T, [2 * S, S, 3 * S], [2, 1, 3]),
    "the-bucket-itself": (T, [T, 5, T], [4, 1, 4]),
    "one-over-an-edge": (T, [S + 1, 2 * S + 1, 1], [2, 3, 1]),
    "longer-than-the-bucket-is-the-bucket": (T, [T + 9], [4]),
    "no-lengths": (T, None, None),
    "a-bucket-of-one-slab": (S, [7, S, 1], None),
    "a-bucket-of-no-whole-slabs": (T + 8, [7, T + 8, 40], None),
}


@pytest.mark.parametrize("dtype, stacked", [("float32", False), ("bfloat16", False), ("float32", True)])
@pytest.mark.parametrize("case", list(CASES))
def test_rows_under_a_length_are_the_plain_forms_and_whole_slabs_past_it_are_zeros(case, dtype, stacked, monkeypatch):
    """``stacked``: the weights are layer 1 of three, and a slab reads them where they lie in the stack."""
    monkeypatch.setattr(layers, "LIVE_SLAB", S)
    length, lengths, slabs = CASES[case]
    x, w = _operands(3 if lengths is None else len(lengths), length, jnp.dtype(dtype))
    lens = None if lengths is None else jnp.asarray(lengths, jnp.int32)
    stack = (jax.tree.map(lambda a: jnp.stack([a + 1, a, a - 1]), w), jnp.int32(1)) if stacked else None
    plain = np.asarray(jax.jit(_mlp)(x, w).astype(jnp.float32))
    run = jax.jit(lambda x, lens, w, stack: layers.live_slabs(_mlp, x, lens, w, stack))
    out = run(x, lens, w, stack)
    assert out.shape == plain.shape and out.dtype == jnp.dtype(dtype)
    out = np.asarray(out.astype(jnp.float32))
    if slabs is None:  # nothing to skip, by the shape alone: the plain form, and no loop in the program
        assert np.array_equal(out, plain) and _loops(run, x, lens, w, stack) == 0
        assert layers.live_rows(length, lengths or [length] * 3) == 3 * length
        return
    assert _loops(run, x, lens, w, stack) == 1, "the slabs of all rows are ONE loop, whatever the batch"
    one = jax.jit(_mlp)
    for b, n in enumerate(slabs):
        live = n * S
        # a product over 16 rows sums in another order than one over the bucket's 64 (the CPU's blocking): to the last
        # bit a slab is ``fn`` of that slab, and the bucket's plain form to float32's rounding, bfloat16's where it is the dtype
        np.testing.assert_allclose(out[b, :live], plain[b, :live], **(dict(atol=2e-6, rtol=2e-6) if dtype == "float32" else dict(atol=3e-2, rtol=3e-2)), err_msg=f"row {b}")
        for at in range(0, live, S):
            assert np.array_equal(out[b, at:at + S], np.asarray(one(x[b, at:at + S], w).astype(jnp.float32))), f"row {b}, slab at {at}: the plain form of these rows, bit for bit"
        assert not out[b, live:].any(), f"row {b}: every position from {live} on is exactly zero"
    assert layers.live_rows(length, lengths) == sum(slabs) * S


@pytest.mark.parametrize("lengths, bucket, loops", [(None, T, 0), ([5, 40], S, 0), ([5, 40], T, 1)])
def test_the_dense_layer_keeps_its_rows_at_a_time_where_there_is_nothing_to_skip(lengths, bucket, loops, monkeypatch):
    """``glm4_moe_lite.ffn`` hands ``live_slabs`` its own plain form (``FFN_ROWS`` rows at a time, here 16): the
    call without lengths and the bucket of one slab trace that ``lax.map`` over the whole of x and no loop of
    data length; the bucket of four slabs traces the loop over slabs, each under ``FFN_ROWS`` and so one
    product, and the rows under a length are the plain form's either way."""
    from ray_tpu.models import glm4_moe_lite as glm

    monkeypatch.setattr(layers, "LIVE_SLAB", S)
    monkeypatch.setattr(glm, "FFN_ROWS", 16)
    x, w = _operands(2, bucket, jnp.float32)
    w = {"w_gate": w["gate"], "w_up": w["up"], "w_down": w["down"][:, :H]}
    lens = None if lengths is None else jnp.asarray(lengths, jnp.int32)
    stack = ({n: jnp.stack([a + 1, a]) for n, a in w.items()}, jnp.int32(1))
    run = jax.jit(lambda x, lens: glm.ffn(w, x, lens, stack))
    traced = str(jax.make_jaxpr(run)(x, lens))
    assert traced.count("while[") == loops and ("scan[" in traced) == (not loops)
    out, plain = np.asarray(run(x, lens)), np.asarray(layers.swiglu(x, w["w_gate"], w["w_up"], w["w_down"]))
    for b, n in enumerate(lengths or [bucket] * 2):
        np.testing.assert_allclose(out[b, :n], plain[b, :n], atol=2e-6, rtol=2e-6)
    assert np.asarray(glm.ffn(w, x[:, 0])).shape == (2, H)  # a decode step's [B, H]: no lengths, no bucket asked about


@pytest.mark.parametrize("model", ["llama", "glm4_moe_lite"])
def test_a_backward_pass_still_goes_through_the_forward_that_training_traces(model, monkeypatch):
    """``models/llama.forward`` has its own MLP and ``hybrid.forward`` hands no lengths on (``SeqCtx.skippable`` is
    None without the serving path's stacked weights): both trace the plain form at a bucket of four slabs, no
    loop of data length stands in the program, and ``jax.grad`` goes through."""
    monkeypatch.setattr(layers, "LIVE_SLAB", S)
    if model == "llama":
        from ray_tpu.models import llama as m

        cfg = m.LlamaConfig.tiny(dtype="float32", num_layers=1, max_seq_len=T)
    else:
        from ray_tpu.models import glm4_moe_lite as m

        cfg = m.Glm4MoeLiteConfig.tiny(num_hidden_layers=2, max_seq_len=T)
    params = m.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 1, cfg.vocab_size - 1)

    def loss(p):
        return jnp.mean(jax.nn.logsumexp(m.forward(p, tokens, cfg), axis=-1))

    assert "while[" not in str(jax.make_jaxpr(loss)(params))
    grads = jax.grad(loss)(params)
    norms = [float(jnp.linalg.norm(g.astype(jnp.float32))) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(norms)) and sum(n > 0 for n in norms) > len(norms) // 2
