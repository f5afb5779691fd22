"""A serving cell, from the driver's side. This process never initialises a JAX backend: it
starts the runtime, deploys ``BenchServer`` through ``serve.run`` (the replica worker the
scheduler binds is the only holder of the chips), offers the load through the normal streaming
path (``handle.options(stream=True)``: one stamp per token at the client), and reduces what it
recorded. The load is a fixed schedule drawn from the seed, never a search.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from benchmark import common, stats, traffic
from benchmark.peaks import peaks_of


# requests made for each caller of a closed loop: several times what a caller gets through in the
# longest window (51 s), so that no caller runs dry; what is not sent costs only its token ids
PER_CALLER = 64


def say(msg: str) -> None:
    print(f"[serve] {msg}", flush=True)


def warm_plan(mix: dict, buckets: list[int]) -> list:
    """[(bucket, [lengths])] for the buckets the mix's prompt lengths reach: the bucket's own
    length and, above 64, just past half of it (the prefix cache then stores at half width)."""
    lo, hi = int(mix["prompt_len"]["min"]), int(mix["prompt_len"]["max"])
    plan, prev = [], 0
    for b in buckets:
        if prev < hi and b >= lo:
            # the longest prompt the mix sends into this bucket: above it prompt + output may
            # not fit max_seq_len, and it pads to the same shape anyway
            lengths = [min(b, hi)] + ([max(prev + 1, min(b // 2 + 1, hi))] if b > 64 else [])
            plan.append((b, sorted(set(lengths), reverse=True)))
        prev = b
    return plan


def default_buckets(max_seq_len: int) -> list[int]:
    """The engine's default prefill buckets (``llm/engine.py``): powers of two from 64, then the maximum."""
    b, out = 64, []
    while b < max_seq_len:
        out.append(b)
        b *= 2
    return out + [max_seq_len]


def engine_kwargs(sv: dict, seed: int) -> dict:
    """The engine's keywords: what the configuration's ``serving`` block says under ``engine_kwargs``
    (a cache layout, the sizing of a second kind of state), and beside it the seed and the sizes
    every serving cell states."""
    return {**sv.get("engine_kwargs", {}), "seed": seed, "max_num_seqs": sv["max_num_seqs"], "max_seq_len": sv["max_seq_len"]}


class Client:
    """Sends one request through the streaming handle and stamps every token it receives."""

    def __init__(self, handle, drain_s: float):
        self.stream = handle.options(stream=True)
        self.drain_s = drain_s
        self.records: list[dict] = []
        self.lock = threading.Lock()

    def send(self, req: dict, due: float) -> dict:
        rec = {"index": req["index"], "due": due, "sent": time.time(), "stamps": [], "done": None, "error": None,
               "ended": None, "rid": None, "prompt_tokens": len(req["prompt"]), "max_tokens": req["max_tokens"],
               "greedy": req["sampling"].get("temperature", 0.0) == 0.0}
        with self.lock:
            self.records.append(rec)
        body = {"prompt": req["prompt"], "max_tokens": req["max_tokens"], "stream": True, **req["sampling"]}
        try:
            gen = self.stream.remote(body)
            gen.item_timeout_s = self.drain_s
            for chunk in gen:
                now = time.time()
                if chunk.startswith("data: [DONE]"):
                    rec["done"] = now
                    break
                if rec["rid"] is None:
                    rec["rid"] = json.loads(chunk[6:])["id"]
                rec["stamps"].append(now)
        except Exception as e:  # noqa: BLE001 - a shed (429), a timeout, a dead replica: all count as failed
            text = f"{type(e).__name__}: {e}"
            rec["error"] = text if len(text) <= 600 else text[:150] + " [...] " + text[-450:]  # a traceback's cause is at its end
        rec["ended"] = time.time()
        return rec


def offer_open_loop(client: Client, reqs: list[dict], offsets: list[float], start: float, stop_at: float) -> list:
    """Send request i at start + offsets[i], whatever the earlier ones are doing."""
    pool = ThreadPoolExecutor(max_workers=256, thread_name_prefix="bench-req")
    futures = []
    for req, off in zip(reqs, offsets):
        due = start + off
        if due >= stop_at:
            break
        delay = due - time.time()
        if delay > 0:
            time.sleep(delay)
        futures.append(pool.submit(client.send, req, due))
    return [pool, futures]


def offer_closed_loop(client: Client, plans: list[list[dict]], start: float, ramp_s: float, stop_at: float) -> list:
    """Each caller starts at its own offset inside the ramp, then sends its next request as soon
    as the last one's stream ended, until the window closes."""
    def caller(k: int, plan: list[dict]):
        delay = start + ramp_s * k / len(plans) - time.time()
        if delay > 0:
            time.sleep(delay)
        for req in plan:
            if time.time() >= stop_at:
                return
            client.send(req, time.time())
        say(f"caller {k} ran out of its {len(plan)} requests before the window closed")

    pool = ThreadPoolExecutor(max_workers=len(plans), thread_name_prefix="bench-caller")
    return [pool, [pool.submit(caller, k, plan) for k, plan in enumerate(plans)]]


@contextlib.contextmanager
def deployed(a, cell: dict):
    """The runtime up, ``BenchServer`` deployed and warm: yields (handle, info, config, mix, requests plan)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMConfig

    from benchmark.serve_worker import BenchServer

    name, chips = cell["cell"]["name"], int(cell["cell"]["chips"])
    family = common.load_family(cell["config"]["family"])
    config = family.rehearsal(cell["config"]) if a.rehearse else cell["config"]
    mix = traffic.load_mix(cell["cell"]["traffic"], name)
    sv = dict(cell["config"]["serving"])
    if a.rehearse:
        sv.update(max_num_seqs=4, max_seq_len=256, warm_batch_max=4)
        for k in ("prompt_len", "output_len"):
            scale = 3584 / 160 if k == "prompt_len" else 512 / 24
            mix[k] = {**mix[k], **{f: max(2, int(mix[k][f] / scale)) for f in ("median", "min", "max") if f in mix[k]}}
        mix.update(ramp_s=1.0, drain_s=20.0, rate_per_s=min(float(mix.get("rate_per_s") or 4.0), 4.0), clients=min(int(mix.get("clients", 4)), 4))
    if mix["loop"] == "open" and not mix.get("rate_per_s") and not a.sweep:
        raise SystemExit(f"cell {name}: no rate_per_s in benchmark/cells/{name}.json (found by a sweep on the chip)")
    tp = int(cell["config"]["layout"].get("tensor_parallel_size", 1))
    ray_tpu.init(num_cpus=4)
    try:
        have = ray_tpu.cluster_resources().get("TPU", 0)
        if have < chips and not a.rehearse:
            raise SystemExit(f"cell {name} needs {chips} TPU chip(s); the runtime found {have}")
        on_tpu = have >= chips
        llm = LLMConfig(
            model_config=family.program_config(config, sv["max_seq_len"], remat=False),
            engine_kwargs=engine_kwargs(sv, a.seed % (2**31)),
            tensor_parallel_size=tp, max_ongoing_requests=sv["max_ongoing_requests"],
            model_id=cell["cell"]["config"])
        bench = {"seed": a.seed % (2**31), "family": config["family"], "warm": warm_plan(mix, default_buckets(sv["max_seq_len"])),
                 "warm_batch_max": sv["warm_batch_max"]}
        opts = {"name": "BenchServer", "max_ongoing_requests": sv["max_ongoing_requests"], "num_replicas": 1,
                # construction compiles every warm shape; the controller must not replace the replica meanwhile
                "health_check_timeout_s": 1500.0, "health_check_period_s": 2.0}
        if on_tpu or tp > 1:
            opts["num_tpus"] = float(max(1, tp)) if on_tpu else 0.0
        app = serve.deployment(**{k: v for k, v in opts.items() if v != 0.0})(BenchServer).bind(llm, bench)

        t_up = time.time()
        h = serve.run(app, name="bench", _blocking=False)
        while True:  # serve.run's own wait would sit out a replica that dies and is replaced, over and over
            st = serve.status()["applications"]["bench"]
            dep = st["deployments"]["BenchServer"]
            if st["status"] == "RUNNING":
                break
            if dep["version"] >= 2 and dep["running_replicas"] == 0:
                raise SystemExit(f"the replica failed to start and was replaced (its traceback is above): {st}")
            if time.time() - t_up > 1100.0:
                raise SystemExit(f"the replica was not up after 1100 s: {st}")
            time.sleep(0.5)
        info = h.bench_info.remote().result(timeout_s=120)
        say(f"family {config['family']}; replica up in {time.time() - t_up:.1f}s (weights {info['weights_s']:.1f}s, engine+warm-up {info['init_s']:.1f}s, "
            f"warm-up {info['warm']}); device {info['device']}; weights {info['weights_bytes'] / 1e9:.2f} GB; "
            f"kv {info['kv']['layout']}/{info['kv']['dtype']} {info['kv']['bytes_per_token']} B/token, "
            f"{info['kv']['allocated_bytes'] / 1e9:.2f} GB for {info['kv']['slots_total']} x {sv['max_seq_len']}; buckets {info['prefill_buckets']}")
        dev = info["device"]
        if dev["platform"] != "tpu" and not a.rehearse:
            raise SystemExit(f"the replica runs on {dev['platform']}, not a TPU")
        if dev["platform"] == "tpu":
            peaks_of(dev["kind"])  # an unknown device is an error before anything is measured
        yield h, info, config, mix
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def settle(client: Client, pool, futures, deadline: float) -> list[dict]:
    """Wait, at most until ``deadline``, for what was sent to finish streaming; -> a snapshot of
    the client's records. What still runs then keeps its record unfinished (it counts as failed)."""
    for f in futures:
        try:
            f.result(timeout=max(0.1, deadline - time.time()))
        except Exception:  # noqa: BLE001 - a timeout here, or a caller's own error: the records tell
            pass
    pool.shutdown(wait=False, cancel_futures=True)
    with client.lock:
        return [dict(r, stamps=list(r["stamps"])) for r in client.records]


def run(a, cell: dict, t_proc0: float) -> dict:
    with deployed(a, cell) as (h, info, config, mix):
        dev, name = info["device"], cell["cell"]["name"]
        ramp_s, drain_s, seconds = float(mix["ramp_s"]), float(mix["drain_s"]), float(a.seconds)
        # the traffic, made while nothing is timed
        if mix["loop"] == "open":
            offsets = traffic.open_loop_schedule(mix, ramp_s + seconds, a.seed)
            reqs = traffic.make_requests(mix, len(offsets), config["vocab_size"], a.seed)
        else:
            plans = traffic.closed_loop_plan(mix, config["vocab_size"], a.seed, n_per_client=PER_CALLER)
            reqs = [r for p in plans for r in p]

        start = time.time() + 0.5
        t0, t1 = start + ramp_s, start + ramp_s + seconds
        h.bench_window.remote(t0, t1, bool(a.trace)).result(timeout_s=60)
        client = Client(h, drain_s)
        tracer = None
        if a.trace:
            # the traced stretch is the window's last seconds, and the profiler is stopped as the window closes:
            # stopping costs about 8 s for every second traced (a trace left running through chat's drain made
            # the run take 429 s, my chip run, PR 23), and streams may stall meanwhile (see the counts below)
            trace_dir, trace_len = a.trace_dir, min(5.0, seconds / 3)

            def trace_stretch():
                time.sleep(max(0.0, t1 - trace_len - time.time()))
                h.bench_trace.remote("start", trace_dir, trace_len).result(timeout_s=120)
                time.sleep(max(0.0, t1 - time.time()))
                h.bench_trace.remote("stop", trace_dir).result(timeout_s=300)

            tracer = threading.Thread(target=trace_stretch, name="bench-trace")
            tracer.start()
        if mix["loop"] == "open":
            pool, futures = offer_open_loop(client, reqs, offsets, start, t1)
        else:
            pool, futures = offer_closed_loop(client, plans, start, ramp_s, t1)
        time.sleep(max(0.0, t1 - time.time()))
        # the window is closed; what was due in it may still be streaming: wait, at most drain_s
        records = settle(client, pool, futures, t1 + drain_s)
        counted = records
        if tracer is not None:
            tracer.join(timeout=330)
            # a traced run counts the requests whose streams had ended when the window closed: the others
            # stream on under the profiler's stop. Its end-to-end numbers are printed, never reported.
            counted = [r for r in records if r["ended"] is not None and r["ended"] < t1]
            say(f"traced run: {len(records) - len(counted)} request(s) still streaming as the window closed are left out of the counts")
        summary = stats.serve_summary(counted, t0, t1, miss_ms=drain_s * 1e3)
        errors = sorted({r["error"] for r in records if r["error"]})
        say(f"window {seconds:.0f}s after a ramp of {ramp_s:.0f}s: {json.dumps(summary)}")
        if errors:
            say(f"errors: {errors[:5]}")

        worker = h.bench_observe.remote().result(timeout_s=600)
        say(f"compiles in the window: {worker['compiles_in_window']} {worker['compiled_in_window']}; "
            f"recompile sentinel {worker['recompiles']}; peak memory {worker['memory_peak_bytes'] / 1e9:.2f} GB; "
            f"prefix cache {worker['prefix_cache']}")

        # correctness, outside the window: a seeded sample served again with log-probabilities
        in_window = [r for r in records if t0 <= r["due"] < t1]
        by_index = {r["index"]: r for r in reqs}
        greedy = [by_index[r["index"]] for r in in_window if r["greedy"]][:4]
        sampled = [by_index[r["index"]] for r in in_window if not r["greedy"]][:1]
        samples = [{"prompt": r["prompt"], "sampling": r["sampling"], "max_tokens": min(r["max_tokens"], 48)}
                   for r in greedy + sampled]
        ref = {"ok": False, "failures": ["no request was due in the window"]}
        if samples:
            tol = float(cell["config"]["tolerance"]["logprob_abs"])
            ref = h.bench_reference.remote(samples, config, tol, a.sabotage == "reference").result(timeout_s=900)
        say(f"reference: {json.dumps(ref)}")

    obs = {"cell": cell["cell"], "config": config, "mix": mix, "seconds": seconds, "window": [t0, t1],
           "client": {"records": records, "summary": summary}, "worker": worker, "device": dev}
    # every statistic of the summary is on offer; the cell's entries in BENCHMARK.json choose
    e2e = {"setup_s": t0 - t_proc0, **{k: v for k, v in summary.items() if k.endswith(("_ms", "_per_s"))}}
    correct = bool(ref["ok"]) and summary["attempted"] > summary["failed"]
    per_request = [{k: r[k] for k in ("index", "rid", "due", "sent", "done", "error", "prompt_tokens", "max_tokens")}
                   | {"first": r["stamps"][0] if r["stamps"] else None, "engine": worker["requests"].get(r["rid"])}
                   for r in records]
    compared = [["served logprob, max |served - reference|", ref.get("max_abs_dlogprob"), ref.get("tolerance")],
                ["greedy token, max gap below the reference's best", ref.get("max_margin"), ref.get("tolerance")],
                ["requests of the window that failed", summary["failed"], f"under {summary['attempted']} attempted"]]
    return {"correct": correct, "attempted": summary["attempted"], "failed": summary["failed"], "end_to_end": e2e,
            "requests": per_request, "compared": compared,
            "obs": obs, "device": dev, "memory_peak_bytes": worker["memory_peak_bytes"], "trace": worker.get("trace")}


def sweep(a, cell: dict) -> int:
    """Find the knee once, when a cell is defined: one replica, one set-up, the open loop at each
    of ``--sweep``'s rates for ``--seconds`` each, with a pause to drain between. Prints a table
    row per rate (also to ``chiprun_out``); the cell's file then takes 0.8 x the highest rate at
    which at least 90% of the requests finished and the backlog at the step's end was small."""
    rows = []
    with deployed(a, cell) as (h, info, config, mix):
        seconds, drain_s = float(a.seconds), float(mix["drain_s"])
        for k, rate in enumerate(float(x) for x in a.sweep.split(",")):
            step_mix = {**mix, "rate_per_s": rate}
            offsets = traffic.open_loop_schedule(step_mix, seconds, a.seed + k)
            reqs = traffic.make_requests(step_mix, len(offsets), config["vocab_size"], a.seed + k)
            client = Client(h, drain_s)
            t0 = time.time() + 0.5
            pool, futures = offer_open_loop(client, reqs, offsets, t0, t0 + seconds)
            time.sleep(max(0.0, t0 + seconds - time.time()))
            backlog = sum(1 for f in futures if not f.done())
            t_end = time.time()
            records = settle(client, pool, futures, t_end + drain_s)
            sm = stats.serve_summary(records, t0, t0 + seconds, miss_ms=drain_s * 1e3)
            finished_in_step = sum(1 for r in records if r["done"] is not None and r["done"] < t0 + seconds)
            row = {"rate_per_s": rate, "sent": sm["attempted"], "failed": sm["failed"],
                   "finished_by_step_end_share": finished_in_step / max(1, sm["attempted"]),
                   "in_flight_at_step_end": backlog, "drain_s": time.time() - t_end,
                   **{k2: sm.get(k2) for k2 in ("ttft_p50_ms", "ttft_p95_ms", "itl_p50_ms", "itl_p95_ms", "stalled_gap_share", "in_flight_mean",
                                                   "serve_tokens_per_s", "gen_late_p95_ms")}}
            rows.append(row)
            say(f"sweep: {json.dumps(row)}")
    print(json.dumps({"sweep": rows, "device": info["device"]}), flush=True)
    return 0
