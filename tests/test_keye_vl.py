"""An eighth description over the one layer loop (``models/keye_vl.py``: grouped-query attention under
a LEARNED index, softmax experts in every layer) through the engine, against the plain reference of
``benchmark/families/keye_vl.py`` (float32, the index scores of a block of queries against every
position, a full stable sort a query, the choice as a mask, written from the published equations):
logits, not tokens. What is this file's own: THREE per-position entries from ``cache_spec()`` (keys
and values by head and the indexer's one key), a lane that crosses ``topk`` while it decodes, a batch
that holds a dense and a selecting sequence, padding never chosen, M-RoPE with three unequal
position streams, the threshold's two bisections against a sort with ties at the threshold, both
prefill kernels interpreted against the XLA form, the four wrong KINDS of selection. Toy widths
(hidden 64, 4 heads of 16 over 2, an indexer of 2 x 8, top-k 16, 8 experts top 2), float32."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_battery as battery
from benchmark.families import keye_vl as family
from hybrid_battery import *  # noqa: F401,F403 - the tests every description is held to, collected here against DESC
from plain_reference import indexed_attention_by_hand
from ray_tpu.llm import SamplingParams
from ray_tpu.llm import hybrid_runner as hr
from ray_tpu.llm import kv_cache as kvc
from ray_tpu.models import hybrid
from ray_tpu.models import keye_vl as kv
from ray_tpu.ops import indexed_attention as ia

PUBLISHED = {"attention_bias": False, "decoder_sparse_step": 1, "hidden_act": "silu", "mlp_only_layers": [], "norm_topk_prob": True,
             "rms_norm_eps": 1e-6, "rope_theta": 10000000, "tie_word_embeddings": False, "use_sliding_window": False, "family": "keye_vl"}
C = family.rehearsal(PUBLISHED)  # the configuration file's side of the toy model: 3 layers, top-k 16 of prompts up to 61 positions
CFG = family.program_config(C, 128, remat=False)


def _config(**changed):
    """A fault in the description: an engine of the same weights under another ``KeyeVLConfig``."""
    return lambda desc, params, eng, monkeypatch: battery.least_engine(dataclasses.replace(desc.cfg, **changed), params)


def _keys(change):
    return battery.patched(ia, "index_keys", lambda real: lambda dots, w, heads: change(real, dots, w, heads))


def _one_indexer_head(params):
    """The index from the indexer's FIRST head alone: the other heads' weights are products with zero columns of ``w_idx``, in prefill and in the step alike."""
    return battery.in_kind(params, "indexed", w_idx=params["indexed"]["w_idx"].at[..., 1:].set(0.0))


def _no_relu(real, dots, w, heads):
    return ia.sort_keys(sum(w[:, j:j + 1] * dots(j) for j in range(heads)))


def _rows_past_the_length(real):
    """Every lane's candidates end three rows past its new token: what the slot held before, or nothing yet."""
    return lambda q, qi, w, k, v, ki, layer, pos, topk: real(q, qi, w, k, v, ki, layer, pos + 3, topk)


# float32 program against float32 reference: the same mathematics summed in another order (tiles of
# queries, the grouped matmul, the indexer's heads one after another). They agree to 1e-5 in a
# log-probability; a wrong KIND of selection is over 2e-4 (the faults below)
DESC = battery.Description(
    family=family, c=C, cfg=CFG, tol=2e-4, agrees_to=1e-5, state_bytes_per_slot=0,
    kv_bytes_per_token=3 * (2 * 2 * 16 + 8) * 4,  # three layers, a key and a value of 2 heads x 16 and the indexer's key of 8
    poison={"k": jnp.nan, "v": 1e4, "k_idx": 1e4},
    faults={"no_selection": battery.Fault(_config(index_topk=1 << 20)),  # dense above topk
            "topk_halved": battery.Fault(_config(index_topk=8)),
            "score_from_one_indexer_head": battery.Fault(battery.with_params(_one_indexer_head)),
            "relu_dropped": battery.Fault(_keys(_no_relu)),
            "rows_past_the_length": battery.Fault(battery.patched(ia, "indexed_attention_step", _rows_past_the_length))},
    refusal_says=("its attention layers keep k_idx per position, not keys and values by head",),
    refusal_says_not=("recurrent", "ring", "c_kv"))


@pytest.fixture(scope="module")
def params():
    return battery.jiggled(jax.jit(lambda k: kv.init_params(CFG, k))(jax.random.PRNGKey(7)))


# ------------------------------------------------------------------------------ the description
def test_the_description_is_one_period_of_two_sub_blocks_and_keeps_three_entries_a_position():
    assert CFG.layer_kinds == ("indexed", "moe") * 3 and CFG.layer_plan == hybrid.LayerPlan(("indexed", "moe"), 3, ())
    published = kv.KeyeVLConfig()
    assert published.num_params() == 30_640_656_384 and (published.count("indexed"), published.count("moe")) == (48, 48)
    cut = dataclasses.replace(published, num_hidden_layers=6)
    assert cut.num_params() == 4_374_622_464 and cut.kinds_held == "6 x indexed, 6 x moe" and cut.layer_plan == hybrid.LayerPlan(("indexed", "moe"), 6, ())
    assert (cut.num_kv_layers, cut.routing_layers, cut.num_layers) == (6, 6, 12)
    assert {k: (m.scope, m.routes, m.hands) for k, m in cut.mixers.items()} == {"indexed": ("indexed", False, False), "moe": ("moe", True, False)}
    s = cut.expert_layer
    assert (s.num_experts, s.held, s.top_k, s.score, s.bias, s.norm_topk, s.scale, s.act, s.shared) == (128, 128, 8, "softmax", False, True, 1.0, "swiglu", False)
    head = ((4, 128), "bfloat16", "position")
    assert cut.cache_spec() == {"indexed": {"k": head, "v": head, "k_idx": ((64,), "bfloat16", "position")}, "moe": {}}
    assert cut.position_entries() == {"k": (6, (4, 128), "bfloat16"), "v": (6, (4, 128), "bfloat16"), "k_idx": (6, (64,), "bfloat16")}
    assert kvc.entry_bytes_per_token(cut.position_entries()) == 13_056 == family.kv_bytes_per_token({**C, **_cell()})
    # the step's attention is not the live-block kernel's: the tile asked about is the indexer's key, which that kernel refuses
    from ray_tpu.ops import slot_attention as sa
    assert cut.slot_attention_tile == dict(num_heads=16, num_kv_heads=1, head_dim=64) and sa.refusal(jnp.bfloat16, **cut.slot_attention_tile, S=24576) is not None
    assert cut.flash_calls(2048) == {128: 6} and cut.flash_calls(4096) == {}
    # the counters, from lengths alone: every causal pair scored in a bucket over topk, min(t + 1, topk) read a query
    n, k = 20500, 2048
    chosen = n * (n + 1) // 2 - (n - k) * (n - k + 1) // 2
    assert cut.prefill_counters(1, 24576, lengths=[n]) == {"pairs_scored": 6 * n * (n + 1) // 2, "pairs_chosen": 6 * chosen}  # off the TPU the XLA form runs: no ``choice_bytes``
    assert chosen == sum(min(t + 1, k) for t in range(n))
    assert cut.prefill_counters(2, 2048, lengths=[100, 2000]) == {"pairs_scored": 0, "pairs_chosen": 6 * (5050 + 2000 * 2001 // 2)}
    assert cut.decode_counters([20000, 2048, 17]) == {"rows_scored": 6 * 22065, "rows_chosen": 6 * (2048 + 2048 + 17)}
    with pytest.raises(ValueError, match="mrope_section counts"):
        dataclasses.replace(cut, mrope_section=(16, 24, 20))


def _cell():
    import json
    import os

    from benchmark import common
    with open(os.path.join(common.HERE, "configs", "keye-vl-2.0-30b-a3b-d6.json")) as f:
        return json.load(f)


def test_the_counts_are_the_programs(params):
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == CFG.num_params() == family.parameters_held(C)
    cell = _cell()
    assert family.parameters_held(cell) == cell["parameters"] and family.parameters_published(cell) == cell["parameters_published"]


# ------------------------------------------------------------------------------ the op
def _operands(B, nh, G, T, hd, J, d, seed, ties=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(kk, (B, n, T, hd)) for kk, n in zip(ks[:3], (nh, G, G)))
    qi, ki, w = jax.random.normal(ks[3], (B, J, T, d)), jax.random.normal(ks[4], (B, T, d)), jax.random.normal(ks[5], (B, T, J))
    if ties:  # small whole numbers: many positions hold exactly the threshold's score, zeros among them
        qi, ki, w = jnp.round(qi), jnp.round(ki), jnp.round(w)
    return q, k, v, qi, w, ki


def _small_tiles(monkeypatch):
    """The kernels' tiles and lanes at 32: a word's 32 bits are 32 chunks of 32 positions, a group 1,024."""
    for name in ("_TILE_Q", "_TILE_K", "_LANES"):
        monkeypatch.setattr(ia, name, 32)


@pytest.mark.parametrize("ties", [False, True])
def test_the_threshold_chooses_what_a_stable_sort_chooses_with_ties_at_the_threshold(ties):
    """``threshold``'s two bisections (32 passes over the keys' bits, one a bit of a position) and
    ``chosen`` against a full stable sort, for rows with fewer candidates than top-k, exactly top-k,
    and more; with whole-number scores most rows hold their threshold's score several times."""
    _, _, _, qi, w, ki = _operands(1, 4, 2, 96, 16, 2, 8, 3, ties)
    keys = ia.index_keys(lambda j: ia._dot_nt(qi[0, j], ki[0]), w[0], 2)
    at = jnp.arange(96)
    keys = jnp.where(at[None, :] <= at[:, None], keys, ia.INT_MIN)
    got = np.asarray(ia.chosen(keys, *ia.threshold(keys, 16)))
    index = np.asarray(sum(w[0, :, j:j + 1] * jnp.maximum(qi[0, j] @ ki[0].T, 0.0) for j in range(2)))
    tied = 0
    for t in range(96):
        want = np.zeros(96, bool)
        want[np.arange(t + 1) if t < 16 else np.argsort(-index[t, :t + 1], kind="stable")[:16]] = True
        assert (got[t] == want).all(), t
        tied += t >= 16 and (index[t, :t + 1] == np.sort(index[t, :t + 1])[-16]).sum() > 1
    assert not ties or tied > 20  # (a continuous score ties only at zero: both heads' products negative)


@pytest.mark.parametrize("ties", [False, True])
def test_both_forms_of_the_sequence_op_are_attention_under_the_choice_by_hand(ties, monkeypatch):
    """The XLA form (tiles of queries, a mask) and the two kernels interpreted (``indexer_thresholds``,
    ``indexed_prefill_attention``: tiles of 32 queries and 32 positions, the second sequence's last
    tile all padding and skipped) against one query at a time in float64."""
    args = _operands(2, 4, 2, 96, 16, 2, 8, 5, ties)
    lengths = jnp.asarray([96, 60], jnp.int32)
    want = indexed_attention_by_hand(*args, 16)
    np.testing.assert_allclose(ia.indexed_attention_seq(*args, lengths, 16, tile=32), want, atol=2e-5)
    monkeypatch.setattr(ia, "refusal", lambda *a, **kw: None)
    _small_tiles(monkeypatch)
    got = np.asarray(ia.indexed_attention_seq(*args, lengths, 16))
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    np.testing.assert_allclose(got[1, :, :60], want[1, :, :60], atol=2e-5)
    assert not got[1, :, 64:].any(), "a tile of queries past the true length is skipped: zeros"


def _unpacked(table):
    """int32 [B,T,words] -> bool [B,T,T]: bit ``c`` of lane ``l`` of a group's words is the group's position
    ``c * lanes + l`` (``ia.choice_words``); what a last group holds past T is zeros."""
    B, T, _ = table.shape
    words = np.asarray(table).view(np.uint32).reshape(B, T, -1, 1, ia._LANES)
    bits = ((words >> np.arange(ia._WORD, dtype=np.uint32)[:, None]) & 1).reshape(B, T, -1)  # [B,T,groups x 32 chunks x lanes]
    assert not bits[..., T:].any()
    return bits[..., :T].astype(bool)


def _packed(mask):
    """bool [B,T,T] -> int32 [B,T,words]: ``_unpacked``'s inverse."""
    B, T, _ = mask.shape
    whole = np.zeros((B, T, ia.choice_words(T) * ia._WORD), np.uint32)
    whole[..., :T] = mask
    chunks = whole.reshape(B, T, -1, ia._WORD, ia._LANES) << np.arange(ia._WORD, dtype=np.uint32)[:, None]
    return jnp.asarray(np.bitwise_or.reduce(chunks, axis=3).reshape(B, T, -1).view(np.int32))


def _causal_keys(qi, w, ki):
    """One sequence's index keys [T,T], ``INT_MIN`` after a query: what ``threshold`` and ``chosen`` take."""
    at = jnp.arange(ki.shape[0])
    return jnp.where(at[None, :] <= at[:, None], ia.index_keys(lambda j: ia._dot_nt(qi[j], ki), w, qi.shape[0]), ia.INT_MIN)


@pytest.mark.parametrize("ties,topk", [(False, 16), (True, 16), (False, 40)])
def test_the_thresholds_kernel_hands_on_the_choice_a_bit_a_pair(ties, topk, monkeypatch):
    """The table of the thresholds' kernel (interpreted, tiles of 32), unpacked, IS ``chosen`` of the
    keys and their ``threshold``, for two sequences of unequal length: rows with fewer candidates
    than top-k (all of them, and nothing after the query), rows with ties at the threshold, a first
    tile that holds no more positions than top-k (top-k 40: the pass without the ties' cut); zeros
    in the second sequence's tile of padding and at every position after a query."""
    _small_tiles(monkeypatch)
    _, _, _, qi, w, ki = _operands(2, 4, 2, 96, 16, 2, 8, 9, ties)
    lengths = (96, 60)
    table = ia.thresholds_kernel(qi, w, ki, jnp.asarray(lengths, jnp.int32), topk, interpret=True)
    assert table.shape == (2, 96, ia.choice_words(96)) == (2, 96, 32) and table.dtype == jnp.int32
    got = _unpacked(table)
    for b, n in enumerate(lengths):
        keys = _causal_keys(qi[b], w[b], ki[b])
        want = np.asarray(ia.chosen(keys, *ia.threshold(keys, topk)))
        live = -(-n // 32) * 32
        assert (got[b, :live] == want[:live]).all() and not got[b, live:].any()
        assert (got[b, :live].sum(-1) == np.minimum(np.arange(live) + 1, topk)).all()
    assert not np.triu(got, 1).any()


def test_the_attention_kernel_under_a_handed_table_gives_no_weight_to_tiles_that_hold_no_chosen_position(monkeypatch):
    """An index that grows with the position: every query's 16 best are the 16 LAST positions at or
    before it, so a query of the last tile of 32 meets two or three tiles of keys in which nothing
    is chosen before it meets a chosen position: its running maximum stays at the floor through
    them, its sums stay zeros, and the values there (1e30, a NaN times zero away from the result)
    leave no trace. Under a table packed by hand the kernel (interpreted) is the XLA form and the
    sum by hand to float32's rounding, and the thresholds' kernel hands on that very table."""
    _small_tiles(monkeypatch)
    B, nh, G, T, hd, topk = 2, 4, 2, 128, 16, 16
    q, k, v, _, _, _ = _operands(B, nh, G, T, hd, 1, 8, 11)
    v = v.at[:, :, :64].set(1e30)
    qi, w = jnp.ones((B, 1, T, 8)), jnp.ones((B, T, 1))
    ki = jnp.broadcast_to((jnp.arange(T, dtype=jnp.float32) / T)[None, :, None], (B, T, 8))
    lengths = jnp.asarray([T, T], jnp.int32)
    keys = _causal_keys(qi[0], w[0], ki[0])  # both sequences' index is the same
    mask = np.broadcast_to(np.asarray(ia.chosen(keys, *ia.threshold(keys, topk))), (B, T, T))
    at = np.arange(T)
    assert (mask[0] == ((at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - topk))).all() and not mask[:, 111:, :96].any()
    got = np.asarray(ia.attend_indexed_kernel(q, k, v, _packed(mask), lengths, interpret=True))
    assert np.isfinite(got).all() and np.abs(got[:, :, 96:]).max() < 10.0
    want = indexed_attention_by_hand(q, k, v, qi, w, ki, topk)
    np.testing.assert_allclose(got[:, :, 96:], want[:, :, 96:], atol=2e-6)
    np.testing.assert_allclose(got[:, :, 96:], np.asarray(ia.indexed_attention_seq(q, k, v, qi, w, ki, lengths, topk, tile=32))[:, :, 96:], atol=2e-6)  # the gate refuses here: the XLA form
    assert (np.asarray(ia.thresholds_kernel(qi, w, ki, lengths, topk, interpret=True)) == np.asarray(_packed(mask))).all()


def test_the_choice_tables_bytes_are_counted_from_the_programs_shape(monkeypatch):
    """``choice_bytes``: a bit a pair of the bucket, a layer and a row of the program, where the two
    kernels run; none (0 to the step row's sum) for a bucket of at most ``index_topk`` (the flash
    kernel), for one the packing does not take and off the TPU (the XLA form)."""
    from ray_tpu.llm import telemetry

    cut = dataclasses.replace(kv.KeyeVLConfig(), num_hidden_layers=6)
    table_bytes = lambda *a, **kw: cut.prefill_counters(*a, **kw).get("choice_bytes", 0)  # noqa: E731
    assert table_bytes(2, 24576, lengths=[20500, 17000]) == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert table_bytes(1, 24576, lengths=[20500]) == 6 * 24576 * 24576 // 8 == 452_984_832
    assert table_bytes(2, 24576, lengths=[20500, 17000]) == 2 * 452_984_832  # by shape: the lengths do not enter
    assert table_bytes(2, 4096, lengths=[3000, 2100]) == 6 * 2 * 4096 * 4096 // 8
    assert table_bytes(4, 2048, lengths=[2048] * 4) == 0 and table_bytes(1, 6144, lengths=[6000]) == 0
    assert "whole groups" in ia.refusal(jnp.bfloat16, 128, 64, 6144) and "choice_bytes" in telemetry.PREFILL_COUNTERS


def test_the_decode_step_attends_to_the_chosen_rows_of_the_stacked_cache():
    """One token a lane against three layers' stacked rows: a lane under top-k (all its rows), one at
    exactly top-k and one far over, each against the by-hand form's last query."""
    B, nh, G, S, hd, J, d, topk = 3, 4, 2, 64, 16, 2, 8, 16
    q, k, v, qi, w, ki = _operands(B, nh, G, S, hd, J, d, 9)
    pos = jnp.asarray([9, 15, 50], jnp.int32)
    stack = lambda a: jnp.stack([jnp.zeros_like(a), a, jnp.ones_like(a)])  # noqa: E731 - the layer asked for is the middle one
    k_stack, v_stack = (stack(a.transpose(0, 2, 1, 3)) for a in (k, v))
    lanes = jnp.arange(B)
    got = ia.indexed_attention_step(q[lanes, :, pos], qi[lanes, :, pos], w[lanes, pos], k_stack, v_stack, stack(ki), jnp.int32(1), pos, topk)
    want = indexed_attention_by_hand(q, k, v, qi, w, ki, topk)
    np.testing.assert_allclose(got.reshape(B, nh, hd), want[lanes, :, pos], atol=2e-5)


def test_the_gate_says_why_by_name(monkeypatch):
    assert "backend" in ia.refusal(jnp.bfloat16, 128, 64, 24576)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ia.refusal(jnp.bfloat16, 128, 64, 24576) is None and ia.refusal(jnp.bfloat16, 128, 64, 4096) is None
    assert "float32" in ia.refusal(jnp.float32, 128, 64, 4096) and "heads of 64" in ia.refusal(jnp.bfloat16, 64, 64, 4096)
    assert "not whole tiles" in ia.refusal(jnp.bfloat16, 128, 64, 4096 + 128)


# ------------------------------------------------------------------------------ the model's own
def test_mrope_with_three_unequal_streams_is_the_references(params):
    """The sequence form of one layer with positions [3, T] whose streams differ (a picture's rows and
    columns beside the text's index) against the reference's layer, and not what equal streams give."""
    T = 40
    x = jax.random.normal(jax.random.PRNGKey(11), (1, T, 64))
    positions = jnp.stack([jnp.arange(T), jnp.arange(T) // 5, (jnp.arange(T) * 3) % 7]).astype(jnp.int32)
    w = jax.tree.map(lambda a: a[1], params["indexed"])
    sa_ = C["sa_config"]
    heads = dict(nh=4, kv=2, hd=16, J=sa_["indexer_num_heads"], d=sa_["indexer_head_dim"], topk=sa_["topk"], eps=1e-6, theta=1e7, sections=(2, 3, 3))
    with jax.default_matmul_precision("highest"):
        want = family._attention(x[0], params["indexed"], 1, positions, **heads) - x[0]
        text = family._attention(x[0], params["indexed"], 1, jnp.broadcast_to(jnp.arange(T), (3, T)), **heads) - x[0]
    got, k, v, k_idx = kv.indexed_seq(w, CFG.norm(x, w["norm"]), jnp.asarray([T], jnp.int32), CFG, positions=positions)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    assert float(jnp.abs(want - text).max()) > 1e-2 and k.shape == v.shape == (1, T, 2, 16) and k_idx.shape == (1, T, 8)
    # the three streams reach the frequencies mrope_section gives them: the head's 8 go 2, 3, 3 and the indexer's 4 go 1, 2, 1 (every second one)
    cos, _ = kv.mrope_tables(jnp.asarray([[1], [2], [3]]), 16, CFG)
    freqs = 1e7 ** (-np.arange(8) / 8)
    np.testing.assert_allclose(cos[0], np.cos(np.asarray([1, 1, 2, 2, 2, 3, 3, 3]) * freqs), rtol=1e-6)
    cos, _ = kv.mrope_tables(jnp.asarray([[1], [2], [3]]), 8, CFG)
    np.testing.assert_allclose(cos[0], np.cos(np.asarray([1, 2, 2, 3]) * 1e7 ** (-np.arange(4) / 4)), rtol=1e-6)


def test_prefill_keeps_keys_values_and_the_indexers_key_of_every_position(params):
    ps = battery.prompts(DESC, 21, (50, 37))
    toks = np.zeros((2, 64), np.int32)
    for i, p in enumerate(ps):
        toks[i, :len(p)] = p
    _, rows, kept = hr.prefill(params, jnp.asarray(toks), jnp.asarray([50, 37], jnp.int32), CFG)
    assert {n: a.shape for n, a in rows.items()} == {"k": (3, 2, 64, 2, 16), "v": (3, 2, 64, 2, 16), "k_idx": (3, 2, 64, 8)}
    assert set(kept) == {hybrid.ROUTING}


def test_a_lane_crosses_topk_while_it_decodes_beside_a_selecting_and_a_dense_lane(params, eng):
    """Prompts of 10 (crosses top-k 16 at its seventh token), 50 (most of its queries choose from its
    prefill on) and 3 (dense to its end) in one batch, 12 tokens each, against the reference; the
    flight log's decode rows count the rows scored and chosen, and the two part once a lane holds
    more than top-k; its admitting rows the pairs."""
    mark = eng.telemetry()["step_count"]
    ps = battery.prompts(DESC, 22, (10, 50, 3))
    sp = [SamplingParams(max_tokens=12, temperature=0.0, logprobs=True)] * 3
    res = battery.check(DESC, params, battery.served(eng.generate(ps, sp), ps, sp))
    assert res["ok"] and res["tokens"] == 36 and res["max_abs_dlogprob"] < DESC.agrees_to, res
    rows = [r for r in battery.steps_after(eng, mark) if "rows_scored" in r]
    assert len(rows) >= 11 and all(0 < r["rows_chosen"] <= r["rows_scored"] for r in rows) and "attn_blocks_read" not in rows[0]
    assert any(r["rows_chosen"] < r["rows_scored"] for r in rows)
    admitting = [r for r in battery.steps_after(eng, mark) if r.get("admitted")]
    assert sum(r["pairs_chosen"] for r in admitting) == 3 * sum(sum(min(t + 1, 16) for t in range(len(p))) for p in ps)
    assert sum(r["pairs_scored"] for r in admitting) == 3 * (50 * 51 // 2)  # the buckets of 16 hold no query that chooses: the flash kernel's


def test_padding_is_never_chosen(params):
    """A prompt of 40 in a bucket of 64 beside one of 64: the short row's queries end their candidates
    at their own position, so what it is served is what it is served alone in a bucket of its own."""
    ps = battery.prompts(DESC, 23, (40, 64))
    sp = [SamplingParams(max_tokens=6, temperature=0.0, logprobs=True)] * 2
    both = battery.engine(CFG, params, prefill_buckets=(64,)).generate(ps, sp)
    alone = battery.engine(CFG, params, prefill_buckets=(40, 64)).generate(ps[:1], sp[:1])
    assert both[0].token_ids == alone[0].token_ids
    np.testing.assert_allclose(both[0].logprobs, alone[0].logprobs, atol=1e-5)
    assert battery.check(DESC, params, battery.served(both, ps, sp))["ok"]


def test_both_kernels_interpreted_serve_what_the_xla_form_serves(params, monkeypatch):
    """Off the TPU the gate refuses; swapped open, the thresholds' kernel and the attention kernel run
    interpreted through the engine's prefill (tiles of 32), a dense lane beside two that choose; the
    admitting rows of the flight log carry the tables' bytes, a group of 1,024 positions a query,
    layer and row of the two programs over top-k (buckets of 64 and 32) and none for the bucket of 16."""
    monkeypatch.setattr(ia, "refusal", lambda *a, **kw: None)
    _small_tiles(monkeypatch)
    ps = battery.prompts(DESC, 24, (50, 28, 9))
    sp = [SamplingParams(max_tokens=8, temperature=0.0, logprobs=True)] * 3
    eng = battery.engine(CFG, params)
    res = battery.check(DESC, params, battery.served(eng.generate(ps, sp), ps, sp))
    assert res["ok"] and res["tokens"] == 24 and res["max_abs_dlogprob"] < DESC.agrees_to, res
    admitting = [r for r in battery.steps_after(eng, 0) if r.get("admitted")]
    assert sum(r.get("choice_bytes", 0) for r in admitting) == 3 * (64 + 32) * ia.choice_words(64) * 4 == 3 * 96 * 128
