"""``ops/delta_rule``: the chunked delta rule as one kernel, for a gate by key channel (Kimi Delta
Attention) and for one gate a head (Gated DeltaNet): every case runs for both. The kernel (run by
the Pallas interpreter: the body the TPU compiles) against BOTH of its oracles, the XLA lines of
that gate it stands in for (``models/qwen3_next.delta_rule_chunked``, whose lines run here on the
CPU) and the position-by-position recurrence (``delta_rule_step``), at the tolerance
``tests/test_kimi_linear.py`` holds the XLA form to; the gate that chooses between the two forms;
and the caller with the kernel forced on."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import qwen3_next as qn
from ray_tpu.ops import delta_rule as dr

GATES = {
    "mild": lambda key, shape: -0.1 * jax.random.uniform(key, shape),
    # exp(A_log) 16 on a step of 0.1, every position of every channel: 64 x 1.6 = 102 > 88, where exp(-gc) leaves float32 inside one chunk
    "the_initialisations_strongest": lambda key, shape: jnp.full(shape, -1.6),
    # a channel forgets within ONE position (40 a position), and its neighbour not at all
    "forgets_in_one_position": lambda key, shape: -40.0 * jax.random.uniform(key, shape) ** 4,
}
# T, chunk, true lengths: one chunk; an odd number of whole chunks; several and a length the chunk does
# not divide; a chunk the sub-blocks do not divide; a chunk longer than the sequence; ragged true
# lengths in one batch (a whole chunk of padding, a chunk cut by the length, a length of one)
SHAPES = {"one_chunk": (32, 32, None), "three_chunks": (48, 16, None), "a_last_chunk_not_whole": (150, 64, None), "chunk_of_5": (17, 5, None),
          "a_chunk_longer_than_the_sequence": (11, 64, None), "ragged": (150, 64, (150, 70, 1))}


# the log-decay's rank beside beta's: [B,T,G,R,K], or [B,T,G,R]
RANKS = ("by_channel", "a_head")


def _inputs(T, gate, lengths=None, seed=2, G=2, R=1, K=16, V=8, rank="by_channel"):
    """q, k [B,T,G,K], v [B,T,G,R,V], g [B,T,G,R,K] or (``rank`` "a_head") [B,T,G,R], beta [B,T,G,R]; every seventh real position
    writes nothing (beta 0) and a position past its sequence's true length neither writes nor decays."""
    B = 3  # one compiled kernel a shape, ragged or not
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = unit(jax.random.normal(ks[0], (B, T, G, K))) * K ** -0.5, unit(jax.random.normal(ks[1], (B, T, G, K)))
    v, beta = 0.5 * jax.random.normal(ks[2], (B, T, G, R, V)), jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, G, R)))
    g = gate(ks[4], (B, T, G, R) + (K,) * (rank == "by_channel"))
    beta = jnp.where((jnp.arange(T) % 7 == 3)[None, :, None, None], 0.0, beta)
    if lengths:
        real = (jnp.arange(T)[None, :] < jnp.asarray(lengths)[:, None])[..., None, None]
        beta, g = jnp.where(real, beta, 0.0), jnp.where(real.reshape(real.shape[:2] + (1,) * (g.ndim - 2)), g, 0.0)
    return q, k, v, g, beta


# one compiled program a shape, whatever the gate: the kernel's body through the Pallas interpreter, and
# ``delta_rule_chunked`` where the kernel's gate refuses (here, the CPU): the XLA lines
_kernel = jax.jit(partial(dr.delta_rule, interpret=True), static_argnums=(5, 6))
_xla_form = jax.jit(partial(qn.delta_rule_chunked, name="kda"), static_argnums=(5, 6))  # whose scopes the lines stand under is nothing to their numbers


@jax.jit
def _recurrence(q, k, v, g, beta):
    """``delta_rule_step`` position by position from a zero state over one sequence's real positions:
    q, k [T,G,K], v [T,G,R,V], g [T,G,R,K] or [T,G,R], beta [T,G,R] -> (o [T,G,R,V], S [G,R,K,V])."""
    R = v.shape[2]
    heads = lambda a: jnp.repeat(a, R, axis=0)  # noqa: E731 — a key head serves R value heads

    def one(S, at):
        q_t, k_t, v_t, g_t, beta_t = at
        o, S = qn.delta_rule_step(S, heads(q_t)[None], heads(k_t)[None], v_t.reshape(1, -1, v.shape[-1]), g_t.reshape(1, q.shape[1] * R, *g.shape[3:]), beta_t.reshape(1, -1))
        return S, o[0]

    S, o = jax.lax.scan(one, jnp.zeros((1, q.shape[1] * R, q.shape[-1], v.shape[-1])), (q, k, v, g, beta))
    return o.reshape(v.shape), S[0].reshape(q.shape[1], R, q.shape[-1], v.shape[-1])


@pytest.mark.parametrize("rank", RANKS)
@pytest.mark.parametrize("gate", list(GATES))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_equals_the_xla_form_and_the_recurrence_with_the_state_at_each_true_length(shape, gate, rank):
    T, chunk, lengths = SHAPES[shape]
    q, k, v, g, beta = _inputs(T, GATES[gate], lengths, rank=rank)
    o, S = _kernel(q, k, v, g, beta, chunk)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()
    want_o, want_S = _xla_form(q, k, v, g, beta, chunk)
    # one gate a head forgetting within a position (the log of the decay reaches -307 in a chunk, where a float32's last bit is
    # 3e-5): both forms take ``gc_C - gc_s`` from two sums, each lands 5.3e-6 from the recurrence and they land on either side
    between = 2e-5 if (rank, gate, shape) == ("a_head", "forgets_in_one_position", "one_chunk") else 1e-5
    np.testing.assert_allclose(o, want_o, atol=1e-5, rtol=0)
    np.testing.assert_allclose(S, want_S, atol=between, rtol=0)
    for b, n in enumerate(lengths or (T,) * q.shape[0]):
        step_o, step_S = _recurrence(*(a[b, :n] for a in (q, k, v, g, beta)))
        np.testing.assert_allclose(o[b, :n], step_o, atol=1e-5, rtol=0)
        np.testing.assert_allclose(S[b], step_S, atol=1e-5, rtol=0, err_msg=f"the state of sequence {b} AT its true length {n}")
    assert float(jnp.abs(S).max()) > 1e-3


@pytest.mark.parametrize("rank", RANKS)
def test_a_key_head_serves_its_value_heads_each_under_its_own_gate(rank):
    """Two value heads a key head (what ``qwen3-next-ep4.longdoc`` runs, with one gate a head: 16 key
    heads under 32 value heads; no cell has them with a gate by channel, and the XLA form takes
    both): the grid walks the value heads and reads a key head's q and k once for each."""
    q, k, v, g, beta = _inputs(40, GATES["mild"], R=2, seed=3, rank=rank)
    o, S = _kernel(q, k, v, g, beta, 16)
    want_o, want_S = _xla_form(q, k, v, g, beta, 16)
    np.testing.assert_allclose(o, want_o, atol=1e-5, rtol=0)
    np.testing.assert_allclose(S, want_S, atol=1e-5, rtol=0)
    for b in range(q.shape[0]):
        step_o, step_S = _recurrence(*(a[b] for a in (q, k, v, g, beta)))
        np.testing.assert_allclose(o[b], step_o, atol=1e-5, rtol=0)
        np.testing.assert_allclose(S[b], step_S, atol=1e-5, rtol=0)


@pytest.mark.parametrize("rank", RANKS)
@pytest.mark.parametrize("gate", list(GATES))
def test_bfloat16_operands_round_where_the_xla_form_rounds(gate, rank):
    """The cells' precision: every product but the inverse and (a gate by channel) the pairs inside a
    sub-block takes bfloat16 operands; against the XLA form WITH THE SAME OPERANDS the kernel differs by a rounding
    that fell the other way now and then (an operand's last bit is 2^-8 of it), not by a precision."""
    q, k, v, g, beta = _inputs(150, GATES[gate], (150, 70, 1), rank=rank)
    o, S = _kernel(q, k, v, g, beta, 64, jnp.bfloat16)
    want_o, want_S = _xla_form(q, k, v, g, beta, 64, jnp.bfloat16)
    exact_o, _ = _xla_form(q, k, v, g, beta, 64)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()
    np.testing.assert_allclose(o, want_o, atol=2e-3, rtol=0)
    np.testing.assert_allclose(S, want_S, atol=2e-3, rtol=0)
    assert float(jnp.abs(o - want_o).max()) < 0.5 * float(jnp.abs(want_o - exact_o).max()), "closer to the XLA form at bfloat16 than that is to float32"


@pytest.mark.parametrize("rank", RANKS)
def test_the_gate_gives_a_reason_and_the_xla_lines_run(monkeypatch, rank):
    """Off the TPU the gate refuses whatever the call; as on a TPU it lets the cells' tile through and
    refuses a mesh, float32 operands, another head width and another chunk. A refused call runs the
    XLA lines (the kernel is never entered); a call let through runs the kernel under ``<name>.chunk``,
    with a gate by channel and with one gate a head alike."""
    cell = dict(operand_dtype=jnp.bfloat16, K=128, V=128, chunk=64)
    assert "backend 'cpu'" in dr.refusal(**cell)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dr.refusal(**cell) is None and dr.refusal(**cell, mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("x",))) is None
    assert "mesh" in dr.refusal(**cell, mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("x",)))
    assert "float32 operands" in dr.refusal(**{**cell, "operand_dtype": None}) and "float32 operands" in dr.refusal(**{**cell, "operand_dtype": jnp.float32})
    assert "compiled at 128 and 128" in dr.refusal(**{**cell, "K": 64}) and "compiled at 64" in dr.refusal(**{**cell, "chunk": 32})
    monkeypatch.undo()

    entered, name = [], {"by_channel": "kda", "a_head": "gdn"}[rank]  # the scopes of the model that brings such a gate
    real = dr.delta_rule
    monkeypatch.setattr(dr, "delta_rule", lambda *a, **kw: entered.append(kw) or real(*a, **kw))
    q, k, v, g, beta = _inputs(40, GATES["mild"], rank=rank)
    want = qn.delta_rule_chunked(q, k, v, g, beta, 16, name=name)
    assert not entered, "the CPU without a test's asking: the XLA lines"
    text = jax.jit(lambda *a: qn.delta_rule_chunked(*a, 16, name=name)).lower(q, k, v, g, beta).as_text(debug_info=True)
    assert f"{name}.chunk" in text and f"{name}.scan" in text and "pallas" not in text
    monkeypatch.setattr(dr, "refusal", lambda *a, **kw: None)
    got = qn.delta_rule_chunked(q, k, v, g, beta, 16, name=name)
    assert entered == [{"interpret": True}]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
    text = jax.jit(lambda *a: qn.delta_rule_chunked(*a, 16, name=name)).lower(q, k, v, g, beta).as_text(debug_info=True)
    assert f"{name}.chunk" in text and f"{name}.scan" not in text and "cumsum" not in text, "the kernel's call and what feeds it under <name>.chunk, no line of the XLA form"
    assert {"by_channel": "delta_rule_by_channel", "a_head": "delta_rule_by_head"}[rank] in text, "a name of its own in a trace"
