"""What runs inside the replica worker, the one process that holds the chip(s): a subclass of
the program's ``OpenAIServer`` deployed through ``serve.run``. It adds nothing to the request
path. It makes the weights in one jitted call, warms the shapes the cell's traffic will use,
counts compiles, polls the flight recorder, starts and stops the profiler, and checks served
outputs against the plain reference, all from here because only this process can.
"""

from __future__ import annotations

import random
import sys
import threading
import time

from benchmark import common
from ray_tpu.serve.llm import LLMConfig, OpenAIServer


class BenchServer(OpenAIServer):
    def __init__(self, llm_config: LLMConfig, bench: dict):
        self._bench = bench
        self._compiles = common.record_lowerings()  # (host time, function name) of every lowering from now on
        self._window: list[float | None] = [None, None]
        self._poll_stop = threading.Event()
        self._poll_thread = None
        self._steps: dict[int, dict] = {}
        self._requests: dict[str, dict] = {}
        self._trace_dir = None
        self._trace_host = [0.0, 0.0]
        try:
            self._build(llm_config)
        except BaseException:
            # the controller replaces a replica whose constructor raised and logs no reason: say it here
            import traceback

            print("BenchServer failed to start:\n" + traceback.format_exc(), file=sys.stderr, flush=True)
            raise

    def _build(self, llm_config: LLMConfig):
        import jax

        t0 = time.time()
        self._family = common.load_family(self._bench["family"])
        if int(llm_config.tensor_parallel_size or 1) == 1 and llm_config.params is None:
            from ray_tpu.util.compile_cache import enable_compile_cache

            enable_compile_cache()
            cfg, init_params = llm_config.model_config, self._family.init_params
            # one jitted call from the seed, in the dtype served; a tp engine does the same itself, sharded
            llm_config.params = jax.jit(lambda k: init_params(cfg, k))(
                jax.random.PRNGKey(int(llm_config.engine_kwargs.get("seed", 0))))
            jax.block_until_ready(llm_config.params)
        self._weights_s = time.time() - t0
        super().__init__(llm_config)
        self._init_s = time.time() - t0

    def _prewarm_compile(self):
        """Every prefill shape the cell's traffic can reach, through the engine's own entry:
        for each prompt bucket, full groups of 1, 2, 4 ... prompts (the engine pads a group to
        the next power of two), at the bucket's length and just past half of it (the prefix
        cache stores at either width). The decode step has one shape."""
        from ray_tpu.llm import SamplingParams

        super()._prewarm_compile()
        sp = SamplingParams(max_tokens=2, temperature=0.0)
        t0, shapes, rnd, vocab = time.time(), 0, random.Random(20260927), self.engine.config.vocab_size
        for bucket, lengths in self._bench["warm"]:
            b = 1
            while b <= self._bench["warm_batch_max"] and b <= self.engine.max_num_seqs:
                for n in (lengths if b == 1 else lengths[:1]):
                    # distinct prompts: a prefix-cache hit would take another path than the plain prefill
                    self.engine.generate([[rnd.randrange(1, vocab - 1) for _ in range(n)] for _ in range(b)], sp)
                    shapes += 1
                b *= 2
        # a sampled lane next to greedy ones: the fused step's sampling branch runs warm
        self.engine.generate([[1, 2, 3, 4]], SamplingParams(max_tokens=2, temperature=0.8, top_p=0.95, seed=1))
        self._warm = {"shapes": shapes, "seconds": time.time() - t0}

    # ---- called by the driver, before and after the window ----------------------------------
    def bench_info(self) -> dict:
        import jax

        devs = jax.local_devices()
        stats = self.engine.kv_cache_stats()
        weights = sum(int(x.nbytes) for x in jax.tree.leaves(self.engine.params))
        return {"device": {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)},
                "kv": stats, "weights_bytes": weights, "prefill_buckets": list(self.engine.prefill_buckets),
                "weights_s": self._weights_s, "init_s": self._init_s, "warm": self._warm,
                "compiles_so_far": len(self._compiles)}

    def bench_window(self, t0: float, t1: float, poll: bool) -> None:
        """The window's edges on the host clock; with ``poll``, drain the flight recorder's rings
        (shorter than a window) into this process until ``bench_observe``."""
        self._window = [t0, t1]
        if poll and self._poll_thread is None:
            self._poll_thread = threading.Thread(target=self._poll_loop, daemon=True, name="bench-poll")
            self._poll_thread.start()

    def _poll_loop(self):
        while not self._poll_stop.wait(2.0):
            self._poll_once()

    def _poll_once(self):
        snap = self.engine.telemetry()
        for s in snap.get("steps", ()):
            self._steps[s["step"]] = s
        for r in snap.get("requests", ()):
            self._requests[r["request_id"]] = r

    def bench_trace(self, action: str, trace_dir: str, stretch_s: float = 0.0) -> float:
        """``start``: the traced stretch is the next ``stretch_s`` seconds; ``stop`` comes as it ends.
        The reduction cuts the trace to the stretch (the stop call arrives a little after it)."""
        import jax

        from benchmark import xplane

        if action == "start":
            self._trace_dir = trace_dir
            self._trace_host = [time.time(), time.time() + stretch_s]
            jax.profiler.start_trace(trace_dir, profiler_options=xplane.device_only_options())
        else:
            jax.profiler.stop_trace()
        return time.time()

    def bench_observe(self) -> dict:
        """Everything the per-layer readers need, reduced here so that little crosses the runtime."""
        import jax

        from benchmark import xplane

        self._poll_stop.set()
        if self._poll_thread is not None:
            self._poll_thread.join(timeout=10)
            self._poll_once()
        t0, t1 = self._window
        in_window = [c for c in self._compiles if t0 is not None and t0 <= c[0] < t1]
        steps = [s for _, s in sorted(self._steps.items()) if t0 <= s["t"] < t1]
        obs = {
            "compiles_in_window": len(in_window), "compiled_in_window": sorted({c[1] for c in in_window})[:20],
            "recompiles": self.engine.telemetry().get("recompiles", {}),
            "steps": [[s["t"], s["phase"], s["wall_ms"], s.get("admitted", 0), s.get("batch", 0)] for s in steps],
            "requests": {rid: {"submit_t": r["submit_t"], "admit_t": r["admit_t"], "first_token_t": r["first_token_t"],
                               "queue_wait_s": r["queue_wait_s"], "prompt_tokens": r["prompt_tokens"],
                               "tokens": r["tokens"]} for rid, r in self._requests.items()},
            "memory_peak_bytes": max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.local_devices()),
            "kv": self.engine.kv_cache_stats(), "prefix_cache": self.engine.prefix_cache_stats(),
        }
        if self._trace_dir is not None:
            spans = [(f"engine step: {s['phase']}", s["t"] - s["wall_ms"] * 1e-3, s["t"]) for s in self._steps.values()]
            ends = sorted(s["t"] for s in self._steps.values())
            spans += [("between engine steps (stepper waits for work)", a, b) for a, b in zip(ends, ends[1:])]
            obs["trace"] = xplane.reduce_trace_dir(self._trace_dir, spans, self._trace_host[0],
                                                   self._trace_host[1] - self._trace_host[0])
            obs["trace"]["trace_host"] = list(self._trace_host)
        return obs

    def bench_reference(self, samples: list[dict], config: dict, tol: float, sabotage: bool) -> dict:
        """Serve ``samples`` again with log-probabilities (greedy ones as they were, concurrently,
        through this replica's own generate), then teacher-force what was served through the plain
        reference on the weights this engine serves. ``sabotage`` gives the reference an embedding
        table from another seed: the comparison must notice."""
        from concurrent.futures import ThreadPoolExecutor

        import jax

        from benchmark import reference

        def serve_one(s):
            out = self.generate(s["prompt"], {**s["sampling"], "max_tokens": s["max_tokens"], "logprobs": True})
            return {"prompt": s["prompt"], "tokens": out["token_ids"], "logprobs": out["logprobs"],
                    "greedy": s["sampling"].get("temperature", 0.0) == 0.0}

        with ThreadPoolExecutor(len(samples)) as pool:
            served = list(pool.map(serve_one, samples))
        params = dict(self.engine.params)
        if sabotage:
            cfg, init_params = self.engine.config, self._family.init_params
            seed = int(self._bench["seed"]) + 1
            params["embed"] = jax.jit(lambda k: init_params(cfg, k)["embed"])(jax.random.PRNGKey(seed))
        t0 = time.time()
        res = reference.check_served(self._family.reference_logprobs, params, config, served, tol)
        res["seconds"] = time.time() - t0
        res["lengths"] = [[len(s["prompt"]), len(s["tokens"])] for s in served]
        return res
