"""A fifth description over the one layer loop (``models/minicpm_sala.py``: InfLLM-v2 block-sparse
attention, Lightning linear attention, a dense SwiGLU in every layer, the muP scalings) through the
engine, against the plain reference of ``benchmark/families/minicpm_sala.py`` (Lightning one
position at a time, the sparse layer's selection and attention a block of queries at a time with a
mask, float32, written from the published equations): logits, not tokens. What is its own: the
chunked Lightning rule against the recurrence at every slope and at lengths off the chunk, three
caches side by side from ``cache_spec()`` (keys and values per position, a state and the
COMPRESSED KEYS per sequence), a lane that crosses ``dense_len`` while it decodes, a batch that
holds a dense and a sparse lane, a pattern with no period. Toy widths, float32, a small
``sparse_config``: kernel 4, stride 2, block 8, top-k 4, window 16, dense below 32."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_battery as battery
from benchmark.families import minicpm_sala as family
from hybrid_battery import *  # noqa: F401,F403 - the tests every description is held to, collected here against DESC
from ray_tpu.llm import SamplingParams, state_cache
from ray_tpu.llm import hybrid_runner as hr
from ray_tpu.llm import kv_cache as kvc
from ray_tpu.models import hybrid
from ray_tpu.models import minicpm_sala as ms
from ray_tpu.ops import slot_attention as sa
from ray_tpu.ops import sparse_attention as spa

PUBLISHED = {"rms_norm_eps": 1e-6, "attn_use_rope": False, "lightning_use_rope": True, "qk_norm": True, "use_output_gate": True,
             "use_output_norm": True, "attn_use_output_gate": True, "lightning_scale": "1/sqrt(d)", "tie_word_embeddings": False,
             "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4, "family": "minicpm_sala"}
# the configuration file's side of the toy model: layers 2-9 of 12 (S L L L L L L S), the cell's own shape
C = family.rehearsal(PUBLISHED)
CFG = family.program_config(C, 128, remat=False)


def _choose(change):
    return lambda real: lambda scores, t, sp: real(scores, t, change(sp))


def _scores(change):
    return lambda real: lambda q, kc, t, sp: change(real, q, kc, t, sp)


def _property(cls, name, change):
    """``cls.name`` (a property) as ``change(config, its real value)``."""
    def plant(desc, params, eng, monkeypatch):
        real = getattr(cls, name)
        monkeypatch.setattr(cls, name, property(lambda self: change(self, real.fget(self))))
        return battery.least_engine(desc.cfg, params)
    return plant


def _reversed_slopes(params):
    """The heads' decays in the opposite order: the slowest head forgets fastest. The slopes are an operand of the programs, not a constant in them."""
    return battery.in_kind(params, "lightning", slope=params["lightning"]["slope"][:, ::-1])


def _rows_past_the_length(real):
    """Every lane that chooses attends three rows past its new token: what the slot held before, or nothing yet."""
    return lambda q, k, v, layer, lengths, blocks, ok, block, **kw: real(q, k, v, layer, lengths + 3, blocks, ok, block, **kw)


# float32 program against float32 reference: the same mathematics summed in another order (chunks of
# the rule, tiles of queries, the cache's compressed keys). They agree to 1e-5 in a log-probability
# (the logits are a quarter of the stream's spread here: hidden_size / dim_model_base = 4); what
# breaks a selection, a state or a cache row is over 2e-4 (the faults below)
DESC = battery.Description(
    family=family, c=C, cfg=CFG, tol=2e-4, agrees_to=1e-5,
    state_bytes_per_slot=family.state_bytes_per_slot(C, 128, itemsize=4),
    kv_bytes_per_token=2 * 2 * (2 * 16) * 4,  # two sparse layers, a key and a value of 2 heads x 16
    poison={"k": jnp.nan, "v": 1e4},
    faults={"bf16_state": battery.Fault(battery.bf16_state("lightning", "S")),
            "no_forced_window": battery.Fault(battery.patched(spa, "choose_blocks", _choose(lambda sp: sp._replace(window=0)))),
            "topk_short_by_one": battery.Fault(battery.patched(spa, "choose_blocks", _choose(lambda sp: sp._replace(topk=sp.topk - 1)))),
            # a group's blocks chosen by ONE of its heads' scores, not by the sum over its heads: a selection a head, as far as a shared table can hold one
            "selection_by_one_head": battery.Fault(battery.patched(spa, "block_scores", _scores(lambda real, q, kc, t, sp: real(q[:, :, :, :1], kc, t, sp)))),
            "slopes_reversed": battery.Fault(battery.with_params(_reversed_slopes)),
            "rotated_sparse_layer": battery.Fault(_property(ms.MiniCPMSALAConfig, "sparse_heads", lambda c, h: h._replace(rot_dim=c.head_dim))),
            "a_from_the_held_depth": battery.Fault(_property(ms.MiniCPMSALAConfig, "stream_scales",
                                                             lambda c, s: (s[0], c.scale_depth / c.num_hidden_layers ** 0.5, s[2]))),
            "slot_not_reset": battery.Fault(battery.slot_not_reset),
            "padded_length": battery.Fault(battery.padded_length),
            "rows_past_the_length": battery.Fault(battery.patched(sa, "attend_blocks", _rows_past_the_length))},
    refusal_says=("its recurrent layers keep a state per sequence (S, kc)",),
    refusal_says_not=("gdn", "mamba", "c_kv"))


@pytest.fixture(scope="module")
def params():
    return battery.jiggled(jax.jit(lambda k: ms.init_params(CFG, k))(jax.random.PRNGKey(7)))


# ------------------------------------------------------------------------------ the description
def test_the_description_has_no_period_and_keeps_three_kinds_of_cache():
    assert CFG.layer_kinds == ("sparse", "ffn") + ("lightning", "ffn") * 6 + ("sparse", "ffn")
    assert CFG.layer_plan == hybrid.LayerPlan(period=("ffn", "lightning"), repeats=6, tail=("ffn", "sparse", "ffn"), head=("sparse",))
    published = ms.MiniCPMSALAConfig()
    assert (published.count("sparse"), published.count("lightning"), published.count("ffn")) == (8, 24, 32)
    assert published.num_params() == 9_477_206_016
    plan = published.layer_plan  # S L8 S L6 S S L4 S L6 S S S: no period; the stretch L4 S L6 S S happens to stand twice, 38 bodies in all
    assert (len(plan.head), len(plan.period), plan.repeats, len(plan.tail)) == (9, 26, 2, 3) and 9 + 26 + 3 == 38
    cut = dataclasses.replace(published, num_hidden_layers=8, first_layer=9)
    assert cut.held == tuple(range(9, 17)) and cut.kinds_held == "2 x sparse, 8 x ffn, 6 x lightning" and cut.num_params() == 2_820_569_088
    assert cut.layer_plan == hybrid.LayerPlan(period=("ffn", "lightning"), repeats=6, tail=("ffn", "sparse", "ffn"), head=("sparse",))
    assert (cut.num_kv_layers, cut.routing_layers, cut.num_layers) == (2, 0, 16)
    assert {k: m.scope for k, m in cut.mixers.items()} == {"sparse": "sparse", "lightning": "lightning", "ffn": "ffn"}
    assert cut.stream_scales == (12.0, 1.4 / 32 ** 0.5, 1 / 16) and hybrid.trace_description().stream_scales == (1.0, 1.0, 1.0)
    spec = cut.cache_spec()
    assert spec["sparse"] == {"k": ((2, 128), "bfloat16", "position"), "v": ((2, 128), "bfloat16", "position"),
                              "kc": ((768, 2, 128), "bfloat16", "sequence")}
    assert spec["lightning"] == {"S": ((32, 128, 128), "float32", "sequence")} and spec["ffn"] == {}
    assert state_cache.sequence_entries(cut) == {"kc": (2, (768, 2, 128), "bfloat16"), "S": (6, (32, 128, 128), "float32")}
    assert state_cache.bytes_per_slot(cut) == 13_369_344 and kvc.entry_bytes_per_token(cut.position_entries()) == 2_048
    assert cut.slot_attention_tile == dict(num_heads=32, num_kv_heads=2, head_dim=128)
    # the slopes follow the PUBLISHED index: layer 10 is the first Lightning layer held
    slopes = np.asarray(cut.lightning_slopes())
    assert slopes.shape == (6, 32) and np.allclose(slopes[0, 0], 2 ** -0.25 * (1 - 10 / 31 + 1e-5)) and np.allclose(slopes[5, 31], 2 ** -8 * (1 - 15 / 31 + 1e-5))
    # host arithmetic of the counters: a prompt of 8,960 chooses (min(t // 64 + 1, 64) blocks a query, group and layer), one of 4,096 reads all
    chooses = 64 * sum(min(b + 1, 64) for b in range(140))
    assert cut.prefill_counters(2, 12288, lengths=[8960, 4096]) == {"prefill_sparse_pairs": 2 * 2 * (chooses + 64 * sum(range(1, 65)))}
    assert cut.decode_counters([12000, 8192, 100]) == {"sparse_blocks_read": 4 * (64 + 128 + 2), "sparse_blocks_live": 4 * (188 + 128 + 2)}
    assert hybrid.trace_description().decode_counters([5]) == {} and hybrid.trace_description().prefill_counters(1, 16, lengths=[5]) == {}


def test_the_counts_are_the_programs(params):
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) - params["lightning"]["slope"].size  # the fixed slopes are no parameters
    assert n == CFG.num_params() == family.parameters_held(C)
    assert family.state_bytes_per_slot(C, 128, itemsize=4) == state_cache.bytes_per_slot(CFG)


# ------------------------------------------------------------------------------ the Lightning rule
@pytest.mark.parametrize("T, chunk, lengths", [(64, 8, (64, 37)), (37, 8, (37, 5)), (40, 16, (33, 40)), (24, 64, (24, 1))])
def test_the_chunked_lightning_rule_is_the_recurrence_at_every_slope_and_true_length(T, chunk, lengths):
    """Chunks of the rule against ``lightning_step``'s one position at a time, from the fastest
    slope (a head that forgets in one position) to the slowest, with the state AT each true length."""
    ks = jax.random.split(jax.random.PRNGKey(T), 3)
    N, D = 4, 8
    q, k, v = (jax.random.normal(kk, (2, T, N, D)) for kk in ks)
    slope = jnp.asarray([4.0, 0.84, 0.05, 0.004])
    o, S = ms.lightning_chunked(q, k, v, slope, jnp.asarray(lengths, jnp.int32), chunk)
    for b, n in enumerate(lengths):
        state, outs = jnp.zeros((N, D, D)), []
        for t in range(n):
            state = state * jnp.exp(-slope)[:, None, None] + k[b, t][..., None] * v[b, t][..., None, :]
            outs.append(jnp.sum(state * q[b, t][..., None], axis=-2))
        np.testing.assert_allclose(o[b, :n], jnp.stack(outs), atol=2e-5)
        np.testing.assert_allclose(S[b], state, atol=2e-5)


# ------------------------------------------------------------------------------ the three caches
def test_prefill_keeps_keys_values_a_state_and_the_whole_compressed_keys_of_each_true_length(params):
    ps = battery.prompts(DESC, 21, (50, 37))
    toks = np.zeros((2, 64), np.int32)
    for i, p in enumerate(ps):
        toks[i, :len(p)] = p
    _, rows, kept = hr.prefill(params, jnp.asarray(toks), jnp.asarray([50, 37], jnp.int32), CFG)
    assert rows["k"].shape == rows["v"].shape == (2, 2, 64, 2, 16) and kept["S"].shape == (6, 2, 4, 8, 8) and kept["kc"].shape == (2, 2, 64, 2, 16)
    kc, k = np.asarray(kept["kc"]), np.asarray(rows["k"])
    for b, n in enumerate((50, 37)):
        whole = (n - 4) // 2 + 1  # windows of 4 every 2 positions that lie inside the true length
        for j in (0, 7, whole - 1):
            np.testing.assert_allclose(kc[:, b, j], k[:, b, 2 * j:2 * j + 4].mean(axis=1), atol=1e-6)
        assert not kc[:, b, whole:].any(), "no row of padding, none of a window that is not whole"


def test_a_lane_crosses_dense_len_while_it_decodes_beside_a_sparse_and_a_dense_lane(params, eng):
    """Prompts of 26 (crosses 32 at its seventh token), 50 (chooses from its prefill on) and 9
    (dense to its end) in one batch, 20 tokens each, against the reference; the flight log's decode
    rows count the blocks read and live, and the two part once a lane holds more than top-k blocks."""
    mark = eng.telemetry()["step_count"]
    ps = battery.prompts(DESC, 22, (26, 50, 9))
    sp = [SamplingParams(max_tokens=20, temperature=0.0, logprobs=True)] * 3
    res = battery.check(DESC, params, battery.served(eng.generate(ps, sp), ps, sp))
    assert res["ok"] and res["tokens"] == 60 and res["max_abs_dlogprob"] < DESC.agrees_to, res
    rows = [r for r in battery.steps_after(eng, mark) if "sparse_blocks_read" in r]
    assert len(rows) >= 19 and all(0 < r["sparse_blocks_read"] <= r["sparse_blocks_live"] for r in rows)
    assert any(r["sparse_blocks_read"] < r["sparse_blocks_live"] for r in rows)
    admitting = [r for r in battery.steps_after(eng, mark) if r.get("admitted")]
    assert sum(r["prefill_sparse_pairs"] for r in admitting) == sum(CFG.prefill_counters(1, 64, lengths=[len(p)])["prefill_sparse_pairs"] for p in ps)


def test_when_a_query_is_computed_decides_whether_it_chooses():
    """``dense_len`` 48 holds six blocks of 8, more than top-k 4: a prompt of 60 chooses for ALL its
    queries (those under 48 too), a prompt of 40 for none, and a lane that decodes past 48 from
    there on. Program (prefill, then decode through the engine) and reference agree on each."""
    c = {**C, "assumed": {**C["assumed"], "sparse_config": {**C["assumed"]["sparse_config"], "dense_len": 48, "window_size": 8}}}
    cfg = family.program_config(c, 128, remat=False)
    params = battery.jiggled(jax.jit(lambda k: ms.init_params(cfg, k))(jax.random.PRNGKey(9)))
    desc = dataclasses.replace(DESC, c=c, cfg=cfg)
    ps = battery.prompts(desc, 23, (60, 40))
    sp = [SamplingParams(max_tokens=14, temperature=0.0, logprobs=True)] * 2
    outs = battery.engine(cfg, params).generate(ps, sp)
    res = battery.check(desc, params, battery.served(outs, ps, sp))
    assert res["ok"] and res["tokens"] == 28 and res["max_abs_dlogprob"] < DESC.agrees_to, res
    # the same tokens read as if every query were computed at the END (one prompt of all of them): not the same model
    toks = ps[1] + outs[1].token_ids
    late = family.reference_logprobs(params, toks, c, len(toks) - 1, len(toks))  # a prompt of 54 > 48: every query chooses
    then = family.reference_logprobs(params, toks, c, len(ps[1]) - 1, len(toks))[-1:]  # a prompt of 40, then 14 decoded
    assert float(jnp.abs(late - then).max()) > 10 * DESC.tol


def test_both_kernels_interpreted_serve_what_the_xla_forms_serve(params, monkeypatch):
    """Off the TPU the gates refuse; swapped open, prefill's step 5 (``sparse_prefill_attention``)
    and the decode step's table of blocks (``sparse_decode_attention``) run interpreted through the
    engine, a dense lane beside two that choose, against the reference."""
    monkeypatch.setattr(spa, "refusal", lambda *a, **kw: None)
    monkeypatch.setattr(sa, "refusal_blocks", lambda *a, **kw: None)
    ps = battery.prompts(DESC, 24, (50, 28, 9))
    sp = [SamplingParams(max_tokens=8, temperature=0.0, logprobs=True)] * 3
    eng = battery.engine(CFG, params, prefill_buckets=(64,))  # the three prompts as ONE prefill program's rows: the kernel interpreted is traced once
    res = battery.check(DESC, params, battery.served(eng.generate(ps, sp), ps, sp))
    assert res["ok"] and res["tokens"] == 24 and res["max_abs_dlogprob"] < DESC.agrees_to, res


def test_the_decode_kernels_gate_says_why_by_name(monkeypatch):
    assert "backend" in sa.refusal_blocks(jnp.bfloat16, 32, 2, 128, 64)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sa.refusal_blocks(jnp.bfloat16, 32, 2, 128, 64) is None
    assert "float32" in sa.refusal_blocks(jnp.float32, 32, 2, 128, 64) and "head_dim 256" in sa.refusal_blocks(jnp.bfloat16, 32, 2, 256, 64)
    assert "query heads" in sa.refusal_blocks(jnp.bfloat16, 24, 2, 128, 64) and "whole bfloat16 tiles" in sa.refusal_blocks(jnp.bfloat16, 32, 2, 128, 4)
