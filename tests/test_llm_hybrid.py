"""A hybrid model (Mamba-2 + routed experts + attention, ``models/nemotron_h.py``) through the
engine: the program against the plain reference of ``benchmark/families/nemotron_h.py`` (written
from the published equations, float32), the state cache beside the KV rows, the expert layer that
drops nothing and is told its experts, and every refusal by its name. Toy widths, all three kinds
of layer, a pattern with a repeated period (the loop's scan) and a tail (its unrolled part)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_battery as battery
from benchmark.families import nemotron_h as family
from hybrid_battery import *  # noqa: F401,F403 - the tests every description is held to, collected here against DESC
from hybrid_battery import test_the_chips_shares_add_up_to_the_uncut_expert_layer  # noqa: F401 - chip 0 of two
from hybrid_battery import test_the_grouped_matmul_places_the_pairs_held_here_whatever_their_number  # noqa: F401 - it routes experts
from ray_tpu.models import experts
from ray_tpu.models import nemotron_h as nh

# the configuration file's side of the toy model: chip 0 of two, experts 0-3 of 8
C = family.rehearsal({"conv_kernel": 4, "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
                      "routed_scaling_factor": 2.5, "norm_topk_prob": True, "layer_norm_epsilon": 1e-5,
                      "n_shared_experts": 1})
CFG = family.program_config(C, 128, remat=False)


def _a_dropped_token(real):
    """A capacity fault: one lane's token gets nothing from its experts, every decode step."""
    return lambda stacked, layer, x, idx, wt, active, c: real(stacked, layer, x, idx, wt.at[0].set(0.0), active, c)


DESC = battery.Description(
    family=family, c=C, cfg=CFG,
    tol=1e-3, agrees_to=1e-4,  # float32 program against float32 reference: they agree to 1e-5; what breaks the state is far over
    state_bytes_per_slot=family.state_bytes_per_slot(C, itemsize=4), kv_bytes_per_token=family.kv_bytes_per_token(C, itemsize=4),
    poison={"k": jnp.nan, "v": 1e4},
    faults={"bf16_state": battery.Fault(battery.bf16_state("mamba", "ssm")),
            "slot_not_reset": battery.Fault(battery.slot_not_reset, over=100),
            "padded_length": battery.Fault(battery.padded_length, over=100),
            "dropped_token": battery.Fault(battery.patched(experts, "experts_step", _a_dropped_token), over=100)},
    refusal_says=("its recurrent layers keep a state per sequence (conv, ssm)",), refusal_says_not=("per position",),
    shares=("n_routed_experts", 2, {"scale": 2.5, "norm": True, "eps": 1e-5}))


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: nh.init_params(CFG, k))(jax.random.PRNGKey(7))


def test_layer_plan_scans_the_repeated_period_and_unrolls_the_tail():
    assert CFG.layer_plan == (("mamba", "moe", "attn"), 2, ("mamba", "moe"), ())
    published = nh.NemotronHConfig()
    period, repeats, tail, head = published.layer_plan
    assert head == ()
    assert "".join(k[0] for k in period) == "mmmmmam" and repeats == 5 and len(tail) == 52 - 35
    cut = dataclasses.replace(published, layer_pattern="MEMEM*EMEMEM*EME")
    assert cut.layer_plan[1] == 2 and len(cut.layer_plan[0]) == 7 and cut.layer_plan[2] == ("mamba", "moe")
    assert (cut.count("mamba"), cut.count("moe"), cut.count("attn")) == (7, 7, 2)
    assert nh.NemotronHConfig(layer_pattern="M*E").layer_plan == ((), 0, ("mamba", "attn", "moe"), ())


def test_chunked_scan_equals_the_token_by_token_recurrence(params):
    w = jax.tree.map(lambda a: a[1], params["mamba"])
    xn = jax.random.normal(jax.random.PRNGKey(3), (2, 21, CFG.hidden_size))  # 21: two whole chunks of 8 and a rest
    lengths = jnp.asarray([21, 13])
    y, ssm, conv = nh.mamba2_seq(w, xn, lengths, CFG)
    for b, n in enumerate((21, 13)):
        s = jnp.zeros((1,) + ssm.shape[1:])
        cv = jnp.zeros((1,) + conv.shape[1:])
        for t in range(n):
            y_t, s, cv = nh.mamba2_step(w, xn[b:b + 1, t], s, cv, CFG)
            np.testing.assert_allclose(y_t[0], y[b, t], atol=1e-5)
        # the state handed to decode is the state at the TRUE length, not at the padded one
        np.testing.assert_allclose(s[0], ssm[b], atol=1e-5)
        np.testing.assert_allclose(cv[0], conv[b], atol=1e-6)


def test_every_token_routed_to_one_expert_loses_nothing(params):
    w = jax.tree.map(lambda a: a[0], params["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (200, CFG.hidden_size))
    idx = jnp.tile(jnp.asarray([[2, 1]], jnp.int32), (200, 1))  # all 200 tokens at experts 2 and 1, two blocks each: no capacity
    wt = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (200, 2))) + 0.1
    want = battery.one_by_one(w, x, idx, wt, CFG)
    assert np.abs(want).min(axis=1).max() > 0
    valid = jnp.ones((200,), bool)
    np.testing.assert_allclose(nh.experts_grouped(params["moe"], 0, x, idx, wt, valid, CFG), want, atol=1e-4)
    np.testing.assert_allclose(nh.experts_dense(w, x, idx, wt, CFG), want, atol=1e-4)
    # the router's own choices, some of them held on the other chip; padding rows are in no group
    idx, wt = nh.route(w, x, CFG)
    assert (np.asarray(idx) >= CFG.local_experts).any() and (np.asarray(idx) < CFG.local_experts).any()
    want = battery.one_by_one(w, x, idx, wt, CFG)
    np.testing.assert_allclose(nh.experts_dense(w, x, idx, wt, CFG), want, atol=1e-4)
    half = jnp.arange(200) < 24
    got = nh.experts_grouped(params["moe"], 0, x, idx, wt, half, CFG)
    np.testing.assert_allclose(got[:24], want[:24], atol=1e-4)
    assert not np.asarray(got[24:]).any()


def test_an_anchored_router_is_decisive_and_the_key_chooses_the_streams_dtype():
    """``router_anchor``: every token id's k-th score leads its (k+1)-th by about the anchor in
    every expert layer (with plain N(0, fan_in^-1/2) routers the two lie close), the routers'
    columns are orthonormal over all expert layers together, and program and reference still
    agree. The published ``residual_in_fp32`` chooses the stream's dtype."""
    def gaps(p):
        logits = jnp.einsum("vh,lhe->lve", p["embed"], p["moe"]["router"])
        top = jax.lax.top_k(logits, CFG.num_experts_per_tok + 1)[0]
        return np.asarray(top[..., -2] - top[..., -1])

    plain = gaps(jax.jit(CFG.init_params)(jax.random.PRNGKey(1)))
    anchored_cfg = family.program_config({**C, "init_router_anchor": 8.0}, 128, remat=False)
    assert anchored_cfg.router_anchor == 8.0
    p = jax.jit(anchored_cfg.init_params)(jax.random.PRNGKey(1))
    g = gaps(p)
    assert np.median(plain) < 1.0 and np.median(g) > 5.0 and (g > 1.0).mean() > 0.99, (np.median(plain), np.median(g))
    cols = np.asarray(p["moe"]["router"]).transpose(0, 2, 1).reshape(-1, CFG.hidden_size)
    np.testing.assert_allclose(cols @ cols.T, np.eye(len(cols)), atol=1e-5)
    toks = np.asarray(battery.prompts(DESC, 2, (29,)), np.int32)
    ref = family.reference_logprobs(p, toks[0], C, 0, 29)
    np.testing.assert_allclose(jax.nn.log_softmax(nh.forward(p, jnp.asarray(toks), anchored_cfg)[0], -1), ref, atol=1e-4)
    with pytest.raises(ValueError, match="orthogonal router columns"):
        jax.eval_shape(dataclasses.replace(anchored_cfg, hidden_size=16, n_groups=1, mamba_num_heads=2).init_params, jax.random.PRNGKey(0))
    # the stream's dtype: false (as published) is the weights' dtype, true is float32
    low = {**C, "torch_dtype": "bfloat16"}
    assert family.program_config(low, 128).stream_dtype == jnp.bfloat16
    wide = family.program_config({**low, "residual_in_fp32": True}, 128)
    assert wide.stream_dtype == jnp.float32 and wide.residual_in_fp32
    seen = []
    real = nh.rms_norm
    try:
        nh.rms_norm = lambda x, w, eps: (seen.append(x.dtype), real(x, w, eps))[1]
        for cfg_ in (family.program_config(low, 128), wide):
            jax.eval_shape(lambda pp, cfg_=cfg_: nh.forward(pp, jnp.zeros((1, 8), jnp.int32), cfg_),
                           jax.eval_shape(lambda: nh.init_params(cfg_, jax.random.PRNGKey(0))))
    finally:
        nh.rms_norm = real
    assert set(seen[:len(seen) // 2]) == {jnp.dtype("bfloat16")} and set(seen[len(seen) // 2:]) == {jnp.dtype("float32")}, seen


def test_neither_the_runner_nor_the_engine_names_a_model_or_a_kind_of_layer():
    """ROADMAP C1 after PR 36: three descriptions over one loop. What a kind of layer is called,
    computes and keeps comes from the description; the step programs and the engine ask it."""
    import re

    from ray_tpu.llm import engine as engine_module
    from ray_tpu.llm import hybrid_runner

    for module in (hybrid_runner, engine_module):
        with open(module.__file__) as f:
            text = f.read()
        found = re.findall(r"nemotron|qwen|glm|keye[_v-]|jamba|afmoe|trinity|mamba|selscan|gdn|deltanet|\"attn\"|\"moe\"|\"mla\"|\"indexed\"|'moe'|'attn'|'indexed'|c_kv|k_r\b|k_idx", text, flags=re.IGNORECASE)
        assert not found, (module.__name__, found)


# ---------------------------------------------------------------------------
# PR 37: a decode step's routed experts are a loop over the held experts that a BOUND lane chose
# (``experts.experts_step``), held to ``experts_dense`` for each description's expert layer
# ---------------------------------------------------------------------------
@functools.cache
def _expert_layer_of(name):
    """(a config object for ``experts``, its expert layers' stacked weights [layers, ...]) at toy
    widths: each description's own ``ExpertLayer`` as chip 0 of two (experts 0-3 of 8); GLM's
    description holds every expert (as its cell does), so its layer is cut to the same share."""
    import importlib
    from types import SimpleNamespace

    config = {"nemotron_h": "NemotronHConfig", "qwen3_next": "Qwen3NextConfig", "glm4_moe_lite": "Glm4MoeLiteConfig"}[name]
    tiny = getattr(importlib.import_module(f"ray_tpu.models.{name}"), config).tiny
    cfg = tiny() if name == "glm4_moe_lite" else tiny(num_local_experts=4)
    stacked = jax.jit(cfg.init_params)(jax.random.PRNGKey(3))["moe"]
    s = cfg.expert_layer
    if s.held == s.num_experts:
        s = dataclasses.replace(s, local_experts=s.num_experts // 2)
        stacked = {n: a[:, :s.held] if n in s.matrices else a for n, a in stacked.items()}
    assert (s.held, s.num_experts) == (4, 8) and stacked["w_up"].shape[:2] == (cfg.count("moe"), 4) and cfg.count("moe") >= 2
    return SimpleNamespace(expert_layer=s, hidden_size=cfg.hidden_size), stacked


@pytest.mark.parametrize("case", ["no_lane_active", "one_lane", "every_held_expert_hit", "all_choices_on_another_chip", "unbound_lanes_hold_garbage"])
@pytest.mark.parametrize("form", ["loop", "kernel"])
@pytest.mark.parametrize("name", ["nemotron_h", "qwen3_next", "glm4_moe_lite"])
def test_the_step_form_reads_the_experts_its_bound_lanes_hit_and_equals_the_dense_form(name, form, case, monkeypatch):
    """Both forms of ``experts_step``: the loop that runs off the TPU, and the TPU's kernel run by
    the Pallas interpreter (asked for by swapping its gate, as ``tests/test_slot_attention.py`` does)."""
    from ray_tpu.ops import step_experts

    assert "backend 'cpu'" in step_experts.refusal(jnp.bfloat16, 2688, 1856, 2)
    c, stacked = _expert_layer_of(name)
    if form == "kernel":  # with a buffer so small that an expert's 32 rows go through in two tiles of 16
        monkeypatch.setattr(step_experts, "refusal", lambda *a: None)
        monkeypatch.setattr(step_experts, "_TILE_BYTES", 3 * c.hidden_size * 4 * 16)
        assert step_experts.tile_rows(stacked["w_up"].shape[2], c.hidden_size, len(c.expert_layer.matrices), 4) == 16 < stacked["w_up"].shape[2]
    s, layer, B = c.expert_layer, 1, 8
    w = jax.tree.map(lambda a: a[layer], stacked)
    x = jax.random.normal(jax.random.PRNGKey(21), (B, c.hidden_size))
    idx, wt = experts.route(w, x, c)
    active = jnp.ones((B,), bool)
    if case == "no_lane_active":
        active = jnp.zeros((B,), bool)
    elif case == "one_lane":  # the last lane that chose an expert held here
        lane = int(np.flatnonzero(((np.asarray(idx) >= s.expert_start) & (np.asarray(idx) < s.expert_start + s.held)).any(axis=1))[-1])
        active = jnp.arange(B) == lane
    elif case == "every_held_expert_hit":  # lane b chooses held experts b*k .. b*k+k-1 (mod held): 8 lanes cover all of them
        idx = s.expert_start + (jnp.arange(B * s.top_k, dtype=jnp.int32) % s.held).reshape(B, s.top_k)
    elif case == "all_choices_on_another_chip":
        idx = (s.expert_start + s.held + idx % (s.num_experts - s.held)).astype(jnp.int32)
    else:
        active = jnp.arange(B) % 2 == 0
    step = jax.jit(lambda x, idx, wt, active: experts.experts_step(stacked, layer, x, idx, wt, active, c))
    got, read = step(x, idx, wt, active)
    want = experts.experts_dense(w, x, idx, wt * active[:, None], c)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    chosen = (np.asarray(idx) - s.expert_start)[np.asarray(active)]
    assert int(read) == len(np.unique(chosen[(chosen >= 0) & (chosen < s.held)])), "the walk's length is the held experts that a bound lane chose"
    if case in ("no_lane_active", "all_choices_on_another_chip"):
        assert int(read) == 0 and not np.asarray(got).any()
    elif case == "every_held_expert_hit":
        assert int(read) == s.held and np.abs(np.asarray(got)).min(axis=1).max() > 0
    elif case == "one_lane":
        assert 1 <= int(read) <= s.top_k and not np.asarray(got)[np.arange(B) != lane].any() and np.abs(np.asarray(got)[lane]).max() > 0
    else:  # what an unbound lane holds, and so where its garbage routes, changes nothing for the bound ones
        garbage = jnp.where(active[:, None], x, 1e4 * jax.random.normal(jax.random.PRNGKey(22), x.shape))
        idx2, wt2 = experts.route(w, garbage, c)
        assert (np.asarray(idx2) != np.asarray(idx)).any()
        got2, read2 = step(garbage, idx2, wt2, active)
        np.testing.assert_array_equal(np.asarray(got2)[::2], np.asarray(got)[::2])
        assert int(read2) == int(read) > 0 and not np.asarray(got2)[1::2].any()


# what ``experts.blocks_plan`` says of a call of N rows in each cell that routes experts, at the cell's own (k, E, El, H, F, matrices):
# cell -> (configuration file, MiB an expert, {N: (rows of a block, the kernel runs them), ...}); every call of the cell's warm programs is among the N
# (a program of more than ``SLAB_ROWS`` rows that is a whole number of slabs calls with 8,192: ``experts._call_rows``)
RULE_AT_THE_CELLS = {
    # 10 choices over 512 experts, 128 held, 3 x 512 x 2,048: 20-160 rows an expert: low fill, small experts, the kernel (PR 57) at every program
    "qwen3_next": ("qwen3-next-80b-a3b-ep4.json", 6.0, {64: (128, True), 1024: (128, True), 2048: (128, True), 4096: (128, True), 8192: (128, True)}),
    # 8 over 256, 64 held, 3 x 1,024 x 2,304: 128 rows an expert at 4,096; 256 at 8,192, ONE tall block: neither low fill nor full blocks, the loop as before
    "kimi_linear": ("kimi-linear-48b-a3b-ep4.json", 13.5, {64: (128, True), 1024: (128, True), 2048: (128, True), 4096: (128, True), 8192: (256, False)}),
    # every expert held, 6 over 64, 3 x 768 x 2,560: the cell's traffic runs 12,288 rows (1,152 an expert: four and a half tall blocks) and slabs of 8,192 (768): full blocks, the kernel (PR 63)
    "smallthinker": ("smallthinker-21b-a3b-d8.json", 11.25, {64: (128, True), 2048: (128, True), 4096: (128, True), 8192: (256, True), 12288: (256, True)}),
    # 6 over 128, 64 held, 2 x 1,856 x 2,688: a chat prompt's buckets expect under 200 rows an expert up to 4,096 rows and the expert is too large for low fill; 384 at 8,192, a tall block and a half
    "nemotron_h": ("nemotron-3-nano-30b-a3b-ep2.json", 19.03, {64: (128, False), 128: (128, False), 256: (128, False), 512: (128, False), 1024: (128, False), 2048: (128, False), 4096: (128, False), 8192: (256, False)}),
    # every expert held, 4 over 64, 3 x 1,536 x 2,048: too large for low fill (the first request's 64 rows); 512 rows an expert in a slab of 8,192, 768 at 12,288: full blocks, the kernel (PR 63)
    "glm4_moe_lite": ("glm-4.7-flash-d8.json", 18.0, {64: (128, False), 2048: (128, False), 4096: (128, True), 8192: (256, True)}),
    "lfm2": ("lfm2-24b-a2b-d10.json", 18.0, {64: (128, False), 2048: (128, False), 4096: (128, True), 8192: (256, True), 12288: (256, True)}),
    # every expert held, 8 over 128, 3 x 768 x 2,048: the cell's 24,576-position prefill goes through in slabs of 8,192 rows (512 an expert: two tall blocks, the kernel); 256 at 4,096, one tall block, the loop
    "keye_vl": ("keye-vl-2.0-30b-a3b-d6.json", 9.0, {64: (128, True), 2048: (128, True), 4096: (256, False), 8192: (256, True)}),
}
# the rows of the calls of each cell's warm prefill programs (``scripts/warm_texts.py`` lists the programs: the server's first request of 64 rows, then the cell's plan)
CALLS_OF_THE_WARM_PROGRAMS = {"qwen3_next": {64, 1024, 2048, 4096, 8192}, "kimi_linear": {64, 1024, 2048, 4096, 8192}, "smallthinker": {64, 12288, 8192}, "glm4_moe_lite": {64, 8192},
                              "nemotron_h": {64, 128, 256, 512, 1024, 2048, 4096, 8192}, "lfm2": {64, 12288, 8192}, "keye_vl": {64, 8192}}


@pytest.mark.parametrize("cell", sorted(RULE_AT_THE_CELLS))
def test_the_blocks_height_and_the_kernel_follow_the_rows_an_expert_expects_and_its_size_at_each_cells_shapes(cell, monkeypatch):
    """PRs 56, 57 and 63: a call takes tall blocks where it has ``TALL_FROM`` pairs AND an expert expects a tall
    block's rows of them (pairs over the router's width), never anything taller than before PR 56; on a TPU the
    kernel runs the blocks where an expert expects two blocks' rows or more of the call's own height (FULL
    blocks: an expert's run of them holds its matrices, whatever their size), and where it expects less than
    two short blocks' rows AND is of 16 MiB or less (low fill); the loop where a large expert's fill is low and
    where an expert expects one tall block and less than a second. At every cell's own configuration, for every
    call its warm programs make: a later edit that moves a cell's path fails here, not in a driver's run."""
    import json
    import os

    from benchmark import common
    from ray_tpu.ops import grouped_experts

    config, mib, plan = RULE_AT_THE_CELLS[cell]
    with open(os.path.join(common.HERE, "configs", config)) as f:
        c = json.load(f)
    cfg = common.load_family(c["family"]).program_config(c, c["serving"]["max_seq_len"])
    s = cfg.expert_layer
    group = next(g for g in jax.eval_shape(lambda: cfg.init_params(jax.random.PRNGKey(0))).values() if isinstance(g, dict) and "w_down" in g and g["w_down"].ndim == 4)
    mats = [group[n] for n in s.matrices]
    F, H = mats[0].shape[2:]
    assert mats[0].dtype == jnp.bfloat16 and mats[0].shape[1] == s.held and len(mats) * F * H * 2 / 2**20 == pytest.approx(mib, abs=0.01)
    assert CALLS_OF_THE_WARM_PROGRAMS[cell] <= set(plan) and all(experts._call_rows(N) == N for N in plan) and experts._call_rows(4 * 12288) == experts._call_rows(2 * 8192) == 8192
    why = lambda N: grouped_experts.refusal(jnp.bfloat16, H, F, len(mats), N * s.top_k // s.num_experts, plan[N][0], plan[N][0] > experts.BLOCK)  # noqa: E731
    off_the_tpu = {N: experts.blocks_plan(s, N, mats) for N in plan}
    assert off_the_tpu == {N: (block, False) for N, (block, _) in plan.items()} and all("backend 'cpu'" in why(N) for N in plan)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert {N: experts.blocks_plan(s, N, mats) for N in plan} == plan
    for N, (block, kernel) in plan.items():
        expects, parent = N * s.top_k // s.num_experts, 2 * experts.BLOCK if N * s.top_k >= experts.TALL_FROM else experts.BLOCK
        assert block <= parent and (block == parent or expects < 2 * experts.BLOCK)
        if expects >= 2 * block:  # full blocks: the kernel, whatever the expert's size
            assert kernel and why(N) is None
        elif block > experts.BLOCK:  # one tall block and less than a second
            assert not kernel and "under two tall blocks of 256" in why(N)
        else:  # low fill: the expert's size speaks
            assert kernel == (mib <= 16) and (kernel or f"an expert of {mib:.2f} MiB, over 16 MiB" in why(N))
        assert experts.seq_counters(cfg, group, N) == 3 + kernel and experts.seq_counters(cfg, group, 2 * experts.SLAB_ROWS) == experts.seq_counters(cfg, group, experts.SLAB_ROWS)
    # what the kernel turns down besides, by shape and dtype
    assert "float32" in grouped_experts.refusal(jnp.float32, H, F, len(mats), 80, 128, False) and "128 lanes" in grouped_experts.refusal(jnp.bfloat16, H + 64, F, len(mats), 800, 128, False)
    assert "over 16 MiB" in grouped_experts.refusal(jnp.bfloat16, H, 8 * F, len(mats), 80, 128, False) and grouped_experts.refusal(jnp.bfloat16, H, 8 * F, len(mats), 256, 128, False) is None
