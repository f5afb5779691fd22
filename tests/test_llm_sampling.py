"""The sampler selects by threshold, not by sort (PR 32). The sort-based filters it replaced live
on here as the oracle: the kept sets must be theirs wherever no tie sits at the k-th value and the
nucleus' boundary is more than 1e-5 of mass from top_p, tied tokens are all kept, and a seeded
lane's stream of tokens and keys does not depend on what its neighbours ask for.

CPU, float32. What the step programs compile to on the chip is in tests/test_chip_compile.py.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.llm.sampling import filter_logits, sample  # noqa: E402

VOCABS = (7, 1_000, 92_544)
TOP_P = (0.1, 0.5, 0.95, 1.0)
TEMPS = (0.5, 0.8, 1.0, 1.3)


# --------------------------------------------------------------------------- the oracle
def _oracle_top_k(logits, top_k):
    """The parent's filter: ranks by two stable argsorts. Among tokens tied at the k-th value it
    keeps those of highest index."""
    vocab = logits.shape[-1]
    order = jnp.argsort(logits, axis=-1)[..., ::-1]
    ranks = jnp.argsort(order, axis=-1)
    k = jnp.where(top_k <= 0, vocab, top_k)[..., None]
    return jnp.where(ranks < k, logits, -jnp.inf)


def _oracle_top_p(logits, top_p):
    """The parent's filter: keep tokens while the cumulative mass before them is < top_p."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < top_p[..., None]
    thresh = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(logits >= thresh, logits, -jnp.inf)


def _oracle_sample(logits, key, temperature, top_k, top_p):
    """The parent's sample(): every lane filtered and drawn under vmap, greedy picked afterwards."""
    greedy_tok = jnp.argmax(logits, axis=-1)

    def _one(lg, k, temp, tk, tp):
        k1, k2 = jax.random.split(jax.random.wrap_key_data(k, impl="threefry2x32"))
        scaled = _oracle_top_p(_oracle_top_k((lg / jnp.maximum(temp, 1e-6))[None], tk[None]), tp[None])[0]
        return jax.random.categorical(k1, scaled), jax.random.key_data(k2)

    sampled_tok, new_keys = jax.vmap(_one)(logits, key, temperature, top_k, top_p)
    tokens = jnp.where(temperature == 0.0, greedy_tok, sampled_tok).astype(jnp.int32)
    logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), tokens[:, None], axis=-1)[:, 0]
    return tokens, logp, new_keys


# --------------------------------------------------------------------------- inputs
def _logits(kind: str, rows: int, vocab: int, seed: int):
    """peaked: a handful of tokens hold the mass; flat: what random weights give, a 0.95 nucleus
    holds most of the vocabulary; bf16: rounded to 8 bits of mantissa, so values repeat. A tenth
    of the entries are -inf (a mask applied upstream), never a row's every entry."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, vocab)).astype(np.float32) * {"peaked": 6.0, "flat": 0.3, "bf16": 1.0}[kind]
    if kind == "bf16":
        x = np.array(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    x[rng.random(size=x.shape) < 0.1] = -np.inf
    x[:, 0] = np.where(np.isinf(x[:, 0]), 0.0, x[:, 0])
    return x


def _lanes(vocab: int):
    """Every (top_k, top_p) pair once, one a lane, temperatures going round."""
    pairs = [(k, p) for k in (0, 1, 5, 50, vocab) for p in TOP_P]
    top_k = np.array([k for k, _ in pairs], np.int32)
    top_p = np.array([p for _, p in pairs], np.float32)
    temps = np.array([TEMPS[i % len(TEMPS)] for i in range(len(pairs))], np.float32)
    return temps, top_k, top_p


def _effective_k(top_k: np.ndarray, vocab: int) -> np.ndarray:
    return np.where((top_k <= 0) | (top_k > vocab), vocab, top_k)


def _kth_largest(scaled: np.ndarray, top_k: np.ndarray) -> np.ndarray:
    k = _effective_k(top_k, scaled.shape[-1])
    return np.take_along_axis(np.sort(scaled, axis=-1)[:, ::-1], (k - 1)[:, None], axis=-1)


def _near_the_boundary(x: np.ndarray, top_p: np.ndarray) -> np.ndarray:
    """Tokens whose decision rests on a mass within 1e-5 of top_p, reckoned in float64: the mass of
    the strictly more probable tokens is what decides whether a token is kept. None where top_p is
    1: that disables the filter, where the sorted cumulative sum would drop whatever tail its own
    rounding had pushed to 1.0."""
    out = np.zeros(x.shape, bool)
    for r, row in enumerate(x.astype(np.float64)):
        if top_p[r] >= 1.0:
            continue
        vals, inverse, counts = np.unique(row, return_inverse=True, return_counts=True)  # ascending
        p = np.exp(vals - vals.max())
        p = p * counts / np.sum(p * counts)
        above = np.cumsum(p[::-1])[::-1] - p  # mass strictly above each distinct value
        out[r] = np.abs(above[inverse] - np.float64(top_p[r])) <= 1e-5
    return out & np.isfinite(x)  # a token at -inf is at -inf whichever way it is judged


# --------------------------------------------------------------------------- the kept sets
@pytest.mark.parametrize("kind", ["peaked", "flat", "bf16"])
@pytest.mark.parametrize("vocab", VOCABS)
def test_kept_sets_are_the_sort_based_oracles(vocab, kind):
    temps, top_k, top_p = _lanes(vocab)
    x = _logits(kind, len(temps), vocab, seed=vocab + len(kind))
    got = np.asarray(jax.jit(filter_logits)(x, temps, top_k, top_p))
    scaled = np.asarray(jnp.asarray(x) / jnp.maximum(temps, 1e-6)[:, None])

    # top-k: everything at or above the k-th largest value, so every token tied with it
    kth = _kth_largest(scaled, top_k)
    tied = (scaled >= kth).sum(-1) > _effective_k(top_k, vocab)
    after_k = np.where(scaled >= kth, scaled, -np.inf)
    sorted_k = np.asarray(_oracle_top_k(jnp.asarray(scaled), jnp.asarray(top_k)))
    assert np.array_equal(after_k[~tied], sorted_k[~tied]), "no tie at the k-th value: exactly the sorted ranks' set"
    assert kind != "bf16" or vocab < 1000 or tied.any(), "the bf16 rows are there for their ties"
    assert np.all((sorted_k > -np.inf) <= (after_k > -np.inf)), "at a tie the threshold keeps a superset"

    # top-p over what top-k left, against the sorted cumulative sum
    want = np.where((top_p >= 1.0)[:, None], after_k, np.asarray(_oracle_top_p(jnp.asarray(after_k), jnp.asarray(top_p))))
    judged = ~_near_the_boundary(after_k, top_p)
    assert np.array_equal(got[judged], want[judged])
    assert judged.mean() > 0.99 or vocab == 7
    # whatever the boundary did, a row keeps its best token, only ever masks, and keeps ties whole
    assert np.all(got.max(-1) == scaled.max(-1)) and np.all((got == scaled) | (got == -np.inf))
    for g, s in zip(got, scaled):
        kept = g > -np.inf
        assert s[kept].min() > s[~kept & (s > -np.inf)].max(initial=-np.inf), "kept and dropped values interleave"


@pytest.mark.parametrize("vocab", VOCABS)
def test_top_k_1_with_distinct_logits_keeps_the_argmax(vocab):
    x = np.random.default_rng(vocab).permutation(vocab).astype(np.float32)[None] * 0.01
    got = np.asarray(filter_logits(x, np.ones(1, np.float32), np.ones(1, np.int32), np.ones(1, np.float32)))
    assert (got > -np.inf).sum() == 1 and got.argmax() == x.argmax()


@pytest.mark.parametrize("top_k, top_p", [(0, 1.0), (3, 1.0), (0, 0.6), (3, 0.6)])
def test_tokens_tied_at_the_threshold_are_all_kept(top_k, top_p):
    x = np.array([[2.0, 1.0, 2.0, 1.0, 1.0, -np.inf, 0.5, 1.0]], np.float32)  # two at 2.0, four at 1.0
    got = np.asarray(filter_logits(x, np.ones(1, np.float32), np.array([top_k], np.int32), np.array([top_p], np.float32)))
    # the third largest is a 1.0; two 2.0s hold 0.51 of the mass, so 0.6 reaches into the 1.0s
    want = x if (top_k, top_p) == (0, 1.0) else np.where(x >= 1.0, x, -np.inf)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("vocab", [7, 1_000])
def test_a_block_of_rows_is_filtered_row_by_row(vocab):
    """The speculative verify step hands over [B, T, V] with the lanes' parameters as [B, 1]."""
    B, T = 3, 5
    x = _logits("flat", B * T, vocab, seed=3).reshape(B, T, vocab)
    temps, top_k, top_p = np.array([0.8, 1.0, 0.0], np.float32), np.array([0, 5, 0], np.int32), np.array([0.9, 1.0, 0.5], np.float32)
    got = np.asarray(jax.jit(filter_logits)(x, temps[:, None], top_k[:, None], top_p[:, None]))
    rows = np.asarray(filter_logits(x.reshape(B * T, vocab), *(np.repeat(a, T) for a in (temps, top_k, top_p))))
    assert np.array_equal(got, rows.reshape(B, T, vocab))


@pytest.mark.parametrize("top_k, top_p", [(0, 1.0), (40, 1.0), (0, 0.9), (40, 0.9)])
def test_a_rows_result_does_not_depend_on_which_filters_its_neighbours_ask_for(top_k, top_p):
    """The passes nobody asks for are skipped by a conditional on the whole batch: a row comes out
    the same from either side of it."""
    x = _logits("flat", 4, 1_000, seed=5)
    alone = np.asarray(filter_logits(x[:1], np.ones(1, np.float32), np.array([top_k], np.int32), np.array([top_p], np.float32)))
    among = np.asarray(filter_logits(x, np.ones(4, np.float32), np.array([top_k, 7, 0, 0], np.int32), np.array([top_p, 1.0, 0.3, 1.0], np.float32)))
    assert np.array_equal(alone[0], among[0])
    if (top_k, top_p) == (0, 1.0):
        assert np.array_equal(alone, x[:1])


def test_no_filter_asked_for_means_no_pass_is_run():
    """In the jaxpr each filter sits under its own conditional, and no sort is anywhere."""
    txt = str(jax.make_jaxpr(filter_logits)(np.zeros((2, 64), np.float32), np.ones(2, np.float32), np.zeros(2, np.int32), np.ones(2, np.float32)))
    assert txt.count("cond[") == 2 and "sort[" not in txt and "top_k[" not in txt
    txt = str(jax.make_jaxpr(sample)(np.zeros((2, 64), np.float32), np.zeros((2, 2), np.uint32), np.ones(2, np.float32), np.zeros(2, np.int32), np.ones(2, np.float32)))
    assert txt.count("cond[") == 2 and "sort[" not in txt and "top_k[" not in txt


# --------------------------------------------------------------------------- sample
def _keys(rows: int, seed: int):
    return np.random.default_rng(seed).integers(0, 2**32, size=(rows, 2), dtype=np.uint32)


@pytest.mark.parametrize("kind", ["peaked", "flat"])
def test_sample_draws_what_the_sort_based_sampler_drew(kind):
    """Same keys, same filtered distribution, so the same token, bit for bit, on rows without a tie
    at the k-th value; greedy rows, log-probabilities and advanced keys always."""
    vocab = 1_000
    temps, top_k, top_p = _lanes(vocab)
    temps[::3] = 0.0
    x, keys = _logits(kind, len(temps), vocab, seed=11), _keys(len(temps), 12)
    got = jax.jit(sample)(x, keys, temps, top_k, top_p)
    want = jax.jit(_oracle_sample)(x, keys, temps, top_k, top_p)
    near = _near_the_boundary(np.asarray(filter_logits(x, temps, top_k, np.ones_like(top_p))), top_p).any(-1)
    assert near.sum() <= 2
    assert np.array_equal(np.asarray(got[0])[~near], np.asarray(want[0])[~near])
    assert np.array_equal(np.asarray(got[1])[~near], np.asarray(want[1])[~near])
    assert np.array_equal(np.asarray(got[2]), np.asarray(want[2]))


def _stream(lane_logits, lane_key, lane, neighbours):
    """16 steps of ``sample`` on a batch that holds the seeded lane (temperature 0.8, top_k 40,
    top_p 0.9) at row ``lane`` among ``neighbours`` = (temps, top_k, top_p) of the other rows
    (None: the lane is alone). -> (its tokens, its keys after each step)."""
    steps, vocab = lane_logits.shape
    rows = 1 if neighbours is None else len(neighbours[0])
    temps, top_k, top_p = (np.zeros(rows, np.float32), np.zeros(rows, np.int32), np.ones(rows, np.float32)) if neighbours is None else (a.copy() for a in neighbours)
    temps[lane], top_k[lane], top_p[lane] = 0.8, 40, 0.9
    keys = _keys(rows, 21)
    keys[lane] = lane_key
    step, toks, seen = jax.jit(sample), [], []
    for t in range(steps):
        x = _logits("flat", rows, vocab, seed=100 + t)
        x[lane] = lane_logits[t]
        tok, _, keys = step(x, keys, temps, top_k, top_p)
        toks.append(int(tok[lane]))
        seen.append(np.asarray(keys)[lane].tolist())
    return toks, seen


def test_a_seeded_lanes_stream_does_not_depend_on_its_neighbours():
    lane_logits, lane_key = _logits("flat", 16, 1_000, seed=7), _keys(1, 8)[0]
    z, o = np.zeros(4, np.float32), np.ones(4, np.float32)
    alone = _stream(lane_logits, lane_key, 0, None)
    greedy = _stream(lane_logits, lane_key, 2, (z, np.zeros(4, np.int32), o))
    sampling = _stream(lane_logits, lane_key, 2, (np.array([1.0, 0.7, 0.0, 1.2], np.float32), np.array([0, 5, 0, 0], np.int32), np.array([1.0, 0.5, 1.0, 0.95], np.float32)))
    assert alone == greedy == sampling
    assert len(set(alone[0])) > 4, "sixteen draws from a flat distribution are not one token"


def test_an_all_greedy_step_gives_what_a_step_with_a_sampling_lane_gives_greedy_lanes():
    x, keys = _logits("flat", 6, 1_000, seed=31), _keys(6, 32)
    z, tk, tp = np.zeros(6, np.float32), np.array([0, 5, 0, 0, 50, 0], np.int32), np.array([1.0, 1.0, 0.5, 1.0, 0.9, 1.0], np.float32)
    tok_g, logp_g, keys_g = jax.jit(sample)(x, keys, z, tk, tp)
    assert np.array_equal(np.asarray(tok_g), x.argmax(-1))
    one = z.copy()
    one[3] = 0.8  # one sampling lane, with no top-k or top-p of its own: lanes 2 and 4 bring those
    tok_s, logp_s, keys_s = jax.jit(sample)(x, keys, one, tk, tp)
    others = np.arange(6) != 3
    assert np.array_equal(np.asarray(tok_g)[others], np.asarray(tok_s)[others])
    assert np.array_equal(np.asarray(logp_g)[others], np.asarray(logp_s)[others])
    assert np.array_equal(np.asarray(keys_g), np.asarray(keys_s)), "the keys advance the same whatever the lanes ask for, lane 3's too"
    want = jax.nn.log_softmax(jnp.asarray(x), axis=-1)[np.arange(6), x.argmax(-1)]
    assert np.array_equal(np.asarray(logp_g), np.asarray(want))
