"""CPU tests of the benchmark's own code (benchmark/): the traffic generator, the arithmetic from
samples to metrics, the trace reduction, the plain reference against models/llama.py at a tiny
size (through ``benchmark/families/llama.py``), the FLOP counts, and that every name in
BENCHMARK.json resolves to its file. No timing asserts, no sockets, no chip."""

import json
import os
import re
import statistics
import subprocess
import sys

import numpy as np
import pytest

from benchmark import common, flops, stats, traffic, xplane
from benchmark.peaks import PEAKS, peaks_of
from benchmark.serve_cell import default_buckets, warm_plan

ROOT = common.ROOT
BENCH = common.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TRAFFIC = sorted(f[:-5] for f in os.listdir(os.path.join(common.HERE, "traffic")) if f.endswith(".json"))
SERVE_MIXES = [t for t in TRAFFIC if traffic.load_mix(t)["kind"] == "serve"]
CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(common.HERE, "configs")) if f.endswith(".json"))
READERS = sorted(f[:-3] for f in os.listdir(os.path.join(common.HERE, "metrics")) if f.endswith(".py"))


def _config_file(config):
    with open(os.path.join(common.HERE, "configs", config + ".json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------------- traffic
@pytest.mark.parametrize("mix_name", SERVE_MIXES)
def test_requests_are_a_pure_function_of_the_seed(mix_name):
    mix = traffic.load_mix(mix_name)
    a = traffic.make_requests(mix, 64, 1000, seed=3000000019)
    assert a == traffic.make_requests(mix, 64, 1000, seed=3000000019)
    b = traffic.make_requests(mix, 64, 1000, seed=7)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    # every seed does the same work: the same multiset of lengths, in another order
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_tokens"]):
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert list(map(key, a)) != list(map(key, b))


@pytest.mark.parametrize("mix_name", SERVE_MIXES)
@pytest.mark.parametrize("field", ["prompt_len", "output_len"])
def test_lengths_hit_their_median_and_clips(mix_name, field):
    spec = traffic.load_mix(mix_name)[field]
    xs = traffic.quantile_lengths(spec, 400)
    assert min(xs) >= spec["min"] and max(xs) <= spec["max"]
    want = spec["median"] if spec["dist"] == "lognormal" else (spec["min"] + spec["max"]) / 2
    assert abs(statistics.median(xs) - want) <= 0.03 * want + 1
    if spec["dist"] == "lognormal":  # heavy tail: the clips are reached
        assert xs[-1] == spec["max"] or spec["median"] * np.exp(3 * spec["sigma"]) < spec["max"]


@pytest.mark.parametrize("mix_name", SERVE_MIXES)
def test_sampled_share_and_token_range(mix_name):
    mix = traffic.load_mix(mix_name)
    reqs = traffic.make_requests(mix, 200, 512, seed=11)
    sampled = [r for r in reqs if r["sampling"].get("temperature", 0.0) > 0]
    assert len(sampled) == round(mix["sampled_share"] * 200)
    assert len({r["sampling"]["seed"] for r in sampled}) == len(sampled)
    assert all(0 < t < 511 for r in reqs for t in r["prompt"])


@pytest.mark.parametrize("rate,horizon", [(3.0, 46.0), (5.5, 10.0)])
def test_open_loop_schedule_fills_the_horizon_at_the_rate(rate, horizon):
    mix = traffic.load_mix("chat") | {"rate_per_s": rate}
    a = traffic.open_loop_schedule(mix, horizon, seed=1)
    assert a == traffic.open_loop_schedule(mix, horizon, seed=1)
    b = traffic.open_loop_schedule(mix, horizon, seed=2)
    assert len(a) == len(b) == round(rate * horizon)
    assert a != b and a == sorted(a) and a[0] == 0.0 and a[-1] < horizon
    gaps = lambda xs: sorted(round(y - x, 9) for x, y in zip(xs, xs[1:] + [horizon]))  # noqa: E731
    assert gaps(a) == pytest.approx(gaps(b))  # the same gaps, permuted
    # exponential gaps: the coefficient of variation of a Poisson process's gaps is 1
    g = np.diff(a)
    assert 0.8 < g.std() / g.mean() < 1.2


@pytest.mark.parametrize("mix_name", SERVE_MIXES)
def test_a_mix_holds_no_key_the_generator_does_not_read(mix_name):
    """A parameter that no code reads is a promise nothing keeps (``why*``/``what`` are prose)."""
    known = {"kind", "loop", "rate_per_s", "clients", "ramp_s", "drain_s", "prompt_len", "output_len", "sampled_share", "sampled"}
    mix = traffic.load_mix(mix_name)
    assert {k for k in mix if not k.startswith(("why", "what"))} <= known
    assert {mix[k]["dist"] for k in ("prompt_len", "output_len")} <= {"lognormal", "uniform"}


def test_closed_loop_plan_gives_every_caller_its_own_requests():
    mix = traffic.load_mix("longdoc")
    plans = traffic.closed_loop_plan(mix, 1000, seed=9, n_per_client=8)
    assert len(plans) == mix["clients"] and all(len(p) == 8 for p in plans)
    idx = [r["index"] for p in plans for r in p]
    assert len(set(idx)) == len(idx)
    assert plans == traffic.closed_loop_plan(mix, 1000, seed=9, n_per_client=8)
    # any block of 2 x clients requests has the same lengths
    flat = sorted((r["index"], len(r["prompt"])) for p in plans for r in p)
    block = 2 * mix["clients"]
    assert sorted(n for _, n in flat[:block]) == sorted(n for _, n in flat[block:2 * block])


def test_train_batch_is_seeded_and_shifted():
    a = traffic.train_batch(3000000019, 4, 2, 16, 100)
    b = traffic.train_batch(3000000019, 4, 2, 16, 100)
    assert (a["tokens"] == b["tokens"]).all() and a["tokens"].dtype == np.int32
    assert (a["targets"][:, :-1] == a["tokens"][:, 1:]).all() and (a["targets"][:, -1] == -100).all()
    assert (traffic.train_batch(3000000019, 5, 2, 16, 100)["tokens"] != a["tokens"]).any()


# ------------------------------------------------------------------------------------ stats
@pytest.mark.parametrize("q", [50, 90, 95, 99])
def test_percentile_is_numpys(q):
    xs = list(np.random.default_rng(q).lognormal(3, 1, 137))
    assert stats.percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)))


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def _rec(due, stamps, done, prompt=10, max_tokens=None, error=None, sent=None):
    return {"due": due, "sent": due + 0.002 if sent is None else sent, "stamps": stamps, "done": done, "error": error,
            "prompt_tokens": prompt, "max_tokens": len(stamps) if max_tokens is None else max_tokens}


def test_serve_summary_on_recorded_samples():
    recs = [
        _rec(9.0, [9.5, 9.6], 9.6),                         # due before the window, completed before it
        _rec(9.9, [10.4, 10.5, 10.6], 10.6),                # due before, COMPLETED inside: tokens count, latency does not
        _rec(10.0, [10.1, 10.2, 10.4], 10.4),               # ttft 100, gaps 100, 200
        _rec(12.0, [12.3, 12.35], 12.35, sent=12.05),       # ttft 300 from DUE, gap 50, late 50
        _rec(19.0, [19.2, 21.0], 21.0),                     # due inside, completed after: latency counts, tokens do not
        _rec(15.0, [15.1], None, max_tokens=4),             # never finished: failed
        _rec(16.0, [], None, max_tokens=4, error="OverloadedError: 429"),  # shed: failed
        _rec(20.0, [20.1, 20.2], 20.2),                     # due after the window
    ]
    s = stats.serve_summary(recs, 10.0, 20.0, miss_ms=30000.0)
    assert (s["attempted"], s["failed"]) == (5, 2)
    assert s["completed_in_window"] == 3 and s["tokens_completed"] == (10 + 3) + (10 + 3) + (10 + 2)
    assert s["serve_tokens_per_s"] == pytest.approx(3.8)
    assert s["ttft_p50_ms"] == pytest.approx(300.0)  # [100, 200, 300, 30000, 30000]
    assert s["ttft_p95_ms"] == pytest.approx(30000.0)  # a failure misses every limit
    assert s["n_gaps"] == 4 + 2 and s["itl_p50_ms"] == pytest.approx(statistics.median([100, 200, 50, 1800, 30000, 30000]))
    assert s["gen_late_p95_ms"] == pytest.approx(stats.percentile([2, 50, 2, 2, 2], 95))
    # printed beside them and, by their names, on offer to no cell: gaps over twice the median gap (1000: the two failures), requests in flight
    assert s["stalled_gap_share"] == pytest.approx(2 / 6)
    assert s["in_flight_mean"] == pytest.approx((0.6 + 0.398 + 0.3 + 0.998 + 4.998) / 10.0)
    assert s["itl_quantiles"]["99"] == pytest.approx(30000.0) and s["itl_quantiles"]["90"] <= s["itl_quantiles"]["97.5"]


def test_train_summary_counts_all_the_steps_and_all_the_time():
    s = stats.train_summary([101.0, 102.0, 103.5], 100.0, tokens_per_step=16384)
    assert s["steps"] == 3 and s["window_s"] == pytest.approx(3.5)
    assert s["train_tokens_per_s"] == pytest.approx(3 * 16384 / 3.5)
    with pytest.raises(ValueError):
        stats.train_summary([], 100.0, 16384)


# ----------------------------------------------------------------------------- trace reduction
def _planes():
    ops0 = [("%while.3 = (s32[]) while(...)", 1000, 1100), ("%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128] %p0)", 1000, 400),
            ("%fusion.2 = f32[] fusion()", 1300, 300), ("%all-reduce.7 = bf16[8] all-reduce(%x)", 2000, 100),
            ("%_fwd_pallas.5 = bf16[8,128]{1,0} custom-call(bf16[8,128] %p0)", 4000, 500)]
    mods0 = [("jit_fused_step(123)", 1000, 1100), ("jit__unknown(77)", 4000, 500)]
    ops1 = [("%fusion.1 = f32[] fusion()", 1000, 1000)]
    return [{"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": ops1}]},
            {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": mods0}, {"name": "XLA Ops", "events": ops0}]},
            {"name": "/host:CPU", "lines": [{"name": "python", "events": [("x", 0, 10**9)]}]}]


def test_reduction_of_synthetic_planes():
    red = xplane.reduce_planes(_planes())
    assert red["chips"] == 2 and red["window_s"] == pytest.approx(3500e-9)
    # chip 0 busy 1100 (the loop spans its operations) + 500, chip 1 busy 1000: the mean
    assert red["busy_s"] == pytest.approx((1600 + 1000) / 2 * 1e-9)
    assert "while.3" not in red["ops"] and "while" not in red["op_kinds"]
    assert red["programs"] == {"jit_fused_step": [1, pytest.approx(1100e-9)], "jit__unknown(77)": [1, pytest.approx(500e-9)]}
    assert red["flash_programs"] == ["jit__unknown(77)"]
    assert red["ops"]["fusion.1"] == [1, pytest.approx(400e-9)] and red["ops"]["_fwd_pallas.5"][0] == 1
    assert red["op_kinds"]["fusion"][0] == 2 and "all-reduce" in red["op_kinds"]
    assert red["gaps"] == [(2100, 4000)]
    assert xplane.top(red["ops"], 1) == [["_fwd_pallas.5", pytest.approx(500e-9)]]


def test_a_trace_that_ran_on_is_cut_to_its_stretch():
    cut = xplane.reduce_planes(xplane.clip_planes(_planes(), 1200))  # first event at 1000: keep [1000, 2200)
    assert cut["window_s"] == pytest.approx(1100e-9) and "_fwd_pallas.5" not in cut["ops"]
    assert cut["programs"] == {"jit_fused_step": [1, pytest.approx(1100e-9)]}
    assert cut["ops"]["all-reduce.7"] == [1, pytest.approx(100e-9)] and cut["busy_s"] == pytest.approx(1050e-9)


def test_gaps_go_to_what_the_host_was_doing():
    gaps = [(2_100, 4_000), (1_600, 2_000)]
    spans = [("engine step: decode", 1.0e-6, 2.05e-6), ("between engine steps", 2.05e-6, 5e-6)]
    out = dict(xplane.attribute_gaps(gaps, spans, offset_ns=0))
    assert out == {"between engine steps": pytest.approx(1900e-9), "engine step: decode": pytest.approx(400e-9)}
    assert dict(xplane.attribute_gaps(gaps, [], 0)) == {"unattributed": pytest.approx(2300e-9)}


def test_no_device_plane_reduces_to_nothing():
    assert xplane.reduce_planes([p for p in _planes() if p["name"].startswith("/host")]) == {}


def test_reduction_of_the_recorded_tpu_trace():
    """benchmark/testdata/small_tpu.xplane.pb: three calls of one small program on one v5e chip."""
    path = os.path.join(common.HERE, "testdata", "small_tpu.xplane.pb")
    red = xplane.reduce_planes(xplane.read_planes(path))
    assert red["chips"] == 1 and 0 < red["busy_s"] < red["window_s"]
    assert [k for k in red["programs"] if "small_step" in k] and sum(v[0] for v in red["programs"].values()) == 3
    assert sum(v[1] for v in red["ops"].values()) == pytest.approx(red["busy_s"], rel=0.05)
    assert len(red["gaps"]) >= 2


# ---------------------------------------------------------------------------------- reference
@pytest.fixture(scope="module")
def tiny():
    import jax

    llama = common.load_family("llama")
    c = llama.rehearsal({"family": "llama", "rope_theta": 1e6, "rms_norm_eps": 1e-5, "tie_word_embeddings": False})
    cfg = llama.program_config(c, 64, remat=False, attention_impl="xla")
    return c, cfg, llama.init_params(cfg, jax.random.PRNGKey(3))


def test_reference_logprobs_agree_with_the_programs_forward(tiny):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import forward

    c, cfg, params = tiny
    toks = np.random.default_rng(0).integers(1, 500, 48)
    want = jax.nn.log_softmax(forward(params, jnp.asarray(toks[None], jnp.int32), cfg)[0], axis=-1)
    got = common.load_family("llama").reference_logprobs(params, list(toks), c, 10, 40)
    assert np.abs(np.asarray(got) - np.asarray(want[10:40])).max() < 2e-4


def test_reference_loss_agrees_with_the_programs_loss(tiny):
    from benchmark import reference

    llama = common.load_family("llama")
    c, cfg, params = tiny
    batch = traffic.train_batch(5, 0, 2, 32, c["vocab_size"])
    assert reference.loss(llama.reference_logprobs, params, batch, c) == pytest.approx(float(llama.loss_fn(params, batch, cfg)), abs=2e-4)


@pytest.mark.parametrize("wrong", ["seed", "token", "logprob"])
def test_check_served_notices(tiny, wrong):
    import jax

    from benchmark import reference

    llama = common.load_family("llama")
    logprobs, init_params = llama.reference_logprobs, llama.init_params
    c, cfg, params = tiny
    prompt = [int(t) for t in np.random.default_rng(1).integers(1, 500, 20)]
    toks, lps = [], []
    for _ in range(6):  # greedy decoding by the reference itself
        lp = np.asarray(logprobs(params, prompt + toks, c, len(prompt) + len(toks) - 1, len(prompt) + len(toks)))[0]
        toks.append(int(lp.argmax()))
        lps.append(float(lp.max()))
    sample = {"prompt": prompt, "tokens": toks, "logprobs": lps, "greedy": True}
    good = reference.check_served(logprobs, params, c, [sample], tol=0.05)
    assert good["ok"] and good["greedy_top1"] == 6 and good["max_abs_dlogprob"] < 1e-4
    if wrong == "seed":
        other = {**params, "embed": init_params(cfg, jax.random.PRNGKey(4))["embed"]}
        assert not reference.check_served(logprobs, other, c, [sample], tol=0.05)["ok"]
    elif wrong == "token":
        bad = {**sample, "tokens": [toks[0], (toks[1] + 1) % 500] + toks[2:]}
        assert not reference.check_served(logprobs, params, c, [bad], tol=0.05)["ok"]
    else:
        bad = {**sample, "logprobs": [lps[0] - 0.2] + lps[1:]}
        assert not reference.check_served(logprobs, params, c, [bad], tol=0.05)["ok"]


# -------------------------------------------------------------------------------- flops, peaks
def llama_configs(configs):
    """The configurations whose block is the ``llama`` family's: the only ones the Llama block's identities are asked of."""
    return [c for c in configs if _config_file(c)["family"] == "llama"]


@pytest.mark.parametrize("config", CONFIGS)
def test_matmul_params_and_published_count(config):
    """Whatever its family, a configuration is held to what ITS family says of it: the program's
    config built from the file counts the parameters the file states, and its heads are as wide as
    the file says, where the file says (a latent-attention block has no one head width to state)."""
    c = _config_file(config)
    cfg = common.load_family(c["family"]).program_config(c, 2048)
    assert cfg.num_params() == c["parameters"]
    if "head_dim" in c:
        assert cfg.hd == c["head_dim"]


@pytest.mark.parametrize("config", llama_configs(CONFIGS))
def test_the_llama_familys_matmul_params_are_all_but_the_table_and_the_norms(config):
    """Only of the ``llama`` family: a block of two norm vectors a layer and a final one, whose
    every other parameter but the embedding table multiplies each token; heads 128 wide, the width
    ``flops.flash_roofline`` and the flash kernel's compile tests were written at."""
    c = _config_file(config)
    family = common.load_family("llama")
    cfg = family.program_config(c, 2048)
    norms = c["num_hidden_layers"] * 2 * c["hidden_size"] + c["hidden_size"]
    assert family.matmul_params(c) == cfg.num_params() - c["vocab_size"] * c["hidden_size"] - norms
    assert cfg.hd == c["head_dim"] == 128


def test_train_flops_and_flash_roofline():
    c = _config_file("mistral-7b-v0.3-d6")
    family = common.load_family(c["family"])
    per_tok = family.train_flops_per_token(c, 2048)
    assert per_tok == pytest.approx(6 * family.matmul_params(c) + 3 * c["num_hidden_layers"] * 2 * 32 * 2048 * 128)
    r = flops.flash_roofline(c, 8, 2048, peaks_of("TPU v5 lite"))
    assert r["fwd"]["flops"] == pytest.approx(4 * 8 * 32 * 2048 * 2048 * 128 / 2) and r["bwd"]["flops"] == pytest.approx(2.5 * r["fwd"]["flops"])
    assert r["fwd"]["bound"] == r["bwd"]["bound"] == "compute"
    assert r["fwd"]["bytes"] == 2 * 8 * 2048 * 128 * 2 * (32 + 8)


def test_an_unknown_device_is_an_error():
    assert PEAKS["TPU v5 lite"]["bf16_flops"] == 197e12 and PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_of("cpu")


# ------------------------------------------------------------------------------ BENCHMARK.json
def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in BENCH["paths"])
    assert any(w.startswith(tuple(BENCH["paths"])) for w in BENCH["command"])
    names = [m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k]]
    assert len(set(names)) == len(names) and "setup_s" in names
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 4)
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_to_its_files(cell):
    r = common.resolve_cell(BENCH, cell)
    w = r["cell"]
    assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4) and len(w["why"]) <= 200
    mix = traffic.load_mix(w["traffic"], w["name"])
    assert mix["kind"] in ("serve", "train")
    if mix.get("loop") == "open":
        assert mix["rate_per_s"] > 0, "an open-loop cell fixes its rate in benchmark/cells/<cell>.json"
    e2e = {m["name"] for m in r["metrics"]["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and r["metrics"]["per_layer"]
    for m in r["metrics"]["per_layer"]:
        assert m["moves"] in e2e, f"{m['name']} moves {m['moves']}, which {cell} does not report"
        assert common.load_reader(m["name"]) is not None


@pytest.mark.parametrize("metric", [m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k]])
def test_metric_entry(metric):
    m = next(x for k in ("end_to_end", "per_layer") for x in BENCH[k] if x["name"] == metric)
    assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    if "bound" in m:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert "\n" not in m["layer"]
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_config_entry_and_file(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    assert NAME.match(config) and entry["file"].startswith(tuple(BENCH["paths"])) and entry["source"].startswith("https://")
    with open(os.path.join(ROOT, entry["file"])) as f:
        c = json.load(f)
    assert c["source"] == entry["source"] and c["reduced"] == entry["reduced"]
    forbidden = re.compile(r"hidden_size|intermediate|latent|state_size|proj|_dim$|_rank$|head_dim|expan|experts_per")
    assert not [k for k in entry["reduced"] if forbidden.search(k)]
    assert any(w["config"] == config for w in BENCH["workloads"])
    for k in entry["reduced"]:
        assert c["reduced_from"][k] != c[k]


@pytest.mark.parametrize("reader", READERS)
def test_reader_returns_nothing_when_there_is_nothing_to_read(reader):
    assert common.load_reader(reader)({"window": [0.0, 1.0]}) is None


def test_readers_on_recorded_observations():
    obs = {"window": [10.0, 20.0],
           "client": {"summary": {"gen_late_p95_ms": 1.5},
                      "records": [{"rid": "req-1", "due": 11.0, "sent": 11.001, "stamps": [11.3, 11.4]},
                                  {"rid": "req-2", "due": 12.0, "sent": 12.0, "stamps": [12.5]},
                                  {"rid": "req-9", "due": 25.0, "sent": 25.0, "stamps": [25.5]}]},
           "worker": {"compiles_in_window": 0,
                      "steps": [[11.0, "decode", 20.0, 0, 4], [11.1, "idle", 0.1, 0, 0], [11.2, "mixed", 90.0, 1, 5]],
                      "requests": {"req-1": {"submit_t": 11.01, "admit_t": 11.1, "first_token_t": 11.29, "queue_wait_s": 0.04, "prompt_tokens": 500, "tokens": 2},
                                   "req-2": {"submit_t": 12.01, "admit_t": 12.2, "first_token_t": 12.47, "queue_wait_s": 0.10, "prompt_tokens": 1500, "tokens": 1}},
                      "trace": {"trace_host": [11.0, 13.0], "flash_programs": ["jit__unknown(77)"],
                                "programs": {"jit__unknown(123)": [100, 1.2], "jit__unknown(77)": [2, 0.5], "jit_sample": [300, 0.1]},
                                "op_kinds": {"all-reduce": [6400, 0.2], "fusion": [1, 1.0]}}}}
    read = lambda name: common.load_reader(name)(obs)  # noqa: E731
    assert read("compiles_in_window") == 0.0
    assert read("client_overhead_ms") == pytest.approx(statistics.median([299 - 280, 500 - 460]))
    assert read("queue_wait_p50_ms") == pytest.approx(70.0) and read("engine_step_ms") == pytest.approx(55.0)
    assert read("decode_device_ms") == pytest.approx(12.0) and read("prefill_ms_per_ktok") == pytest.approx(250.0)
    assert read("tp_allreduce_ms") == pytest.approx(100.0)  # 0.2 s of all-reduce over the 2 non-idle steps of the stretch
    assert read("decode_device_ms.longdoc") == read("decode_device_ms") and read("prefill_ms_per_ktok.longdoc") == read("prefill_ms_per_ktok")


@pytest.mark.parametrize("second,ok", [([40, 0.5], True), ([50, 0.5], False), ([100, 1.0], False)])
def test_decode_reader_does_not_guess_between_two_frequent_programs(second, ok):
    """Prefill, decode and extend are all ``jit__unknown`` in the trace: where a second program without a flash
    kernel ran at least half as often as the first, the reader fails the run and does not pick one."""
    trace = {"flash_programs": ["jit__unknown(77)"],
             "programs": {"jit__unknown(123)": [100, 1.2], "jit__unknown(77)": [90, 0.5], "jit__unknown(9)": second}}
    read = common.load_reader("decode_device_ms")
    if ok:
        assert read({"worker": {"trace": trace}}) == pytest.approx(12.0)
    else:
        with pytest.raises(ValueError, match="more than one candidate"):
            read({"worker": {"trace": trace}})
    # a program that says what it is is taken by its name, whatever else ran
    trace["programs"]["jit_fused_step"] = [10, 0.3]
    assert read({"worker": {"trace": trace}}) == pytest.approx(30.0)


def test_every_reader_has_an_entry_or_waits_for_a_named_cell():
    listed = {m["name"] for m in BENCH["per_layer"]}
    assert set(READERS) - listed <= {"tp_allreduce_ms"}  # mistral-7b-tp4.chat: PERF.md, Open questions 1


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"] if m["name"] not in ("setup_s", "train_tokens_per_s")])
def test_serve_summary_offers_every_serving_end_to_end_metric(metric):
    s = stats.serve_summary([_rec(10.0, [10.1, 10.2, 10.4], 10.4)], 10.0, 20.0, miss_ms=30000.0)
    assert s[metric] > 0


def test_training_readers_on_recorded_observations():
    c = _config_file("mistral-7b-v0.3-d6")
    peaks = peaks_of("TPU v5 lite")
    r = flops.flash_roofline(c, 8, 2048, peaks)
    L = c["num_hidden_layers"]
    secs = 2 * (2 * L * r["fwd"]["min_s"] + L * r["bwd"]["min_s"])  # kernels at half of their roofline, remat: 2 forwards
    obs = {"config": c, "mix": {"global_batch": 8, "seq_len": 2048}, "peaks": peaks, "device": {"count": 1},
           "train": {"summary": {"train_tokens_per_s": 20000.0}},
           "worker": {"trace": {"traced_steps": 1, "ops": {"_fwd_pallas.1": [2 * L, secs / 4], "_bwd_pallas_with_delta.2": [L, secs / 4],
                                                          "_bwd_pallas_with_delta.3": [L, secs / 2], "fusion.9": [1, 1.0]}}}}
    assert common.load_reader("flash_roofline")(obs) == pytest.approx(50.0)


@pytest.mark.parametrize("mix_name,want", [("chat", [64, 128, 256, 512, 1024, 2048]), ("longdoc", [1024, 2048, 4096])])
def test_warm_plan_covers_the_buckets_the_mix_reaches(mix_name, want):
    plan = warm_plan(traffic.load_mix(mix_name), default_buckets(4096))
    assert [b for b, _ in plan] == want
    for b, lengths in plan:
        assert lengths[0] <= b and all(n > b // 2 or b == 64 for n in lengths)
        assert len(lengths) == (1 if b == 64 else 2) or lengths[0] == b // 2 + 1


def _run_py(args, cwd, script=os.path.join(ROOT, "benchmark", "run.py")):
    return subprocess.run([sys.executable, script, *args], cwd=cwd, capture_output=True, text=True, timeout=120,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_run_py_takes_the_contracts_flags_and_the_three_switches_the_issue_asked_for(tmp_path):
    out = _run_py(["--help"], tmp_path).stdout
    assert set(re.findall(r"--[a-z]+", out)) == {"--help", "--workload", "--seed", "--seconds", "--trace",
                                                  "--rehearse", "--sabotage", "--sweep"}


def test_no_result_where_only_the_benchmark_is(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under ``paths``: non-zero, no result line."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_py(["--workload", BENCH["workloads"][0]["name"], "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
                  tmp_path, script=str(tmp_path / "benchmark" / "run.py"))
    assert out.returncode != 0 and "correct" not in out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("cell", ["mistral-7b-d6.sft-2k", "internlm2-1.8b.chat"])
def test_rehearsal_runs_the_wiring_and_never_says_correct(cell, tmp_path):
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", cell, "--seed", "3000000019",
                          "--seconds", "3", "--trace", "1", "--rehearse"], cwd=tmp_path, capture_output=True, text=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
                         timeout=300)
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert out.returncode == 1 and last["correct"] is False and last["metrics"] == {} and last["device"]["platform"] == "cpu"
