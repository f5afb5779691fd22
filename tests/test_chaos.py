"""Chaos sweep: parameterized fault injection across the distributed
paths — transport dispatch/results, streaming, object transfer, health
checking + failover, GCS-FT reconnect, and Serve routing.

Reference strategy: src/ray/rpc/rpc_chaos.h:24 (per-method delay/failure
injection) + python/ray/tests/test_core_worker_fault_tolerance.py:26
(RpcFailure-driven liveness+correctness tests). Assertions are about
RESULTS, not just no-crash: every request completes with the right value
under the fault.

Fault model notes: the agent links are in-order reliable channels, so
DELAY chaos applies to any message type, while DROP chaos is meaningful
only where a recovery mechanism exists — pings/pongs (health checker ->
node death -> retry elsewhere) and transfer chunks (pull retry, then
lineage reconstruction). Dropping a 'done' on a reliable channel models
a fault the transport layer itself rules out.
"""

import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core import context, rpc_chaos


@pytest.fixture
def rt():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    rpc_chaos.seed(7)
    yield context.get_client()
    rpc_chaos.clear()
    ray_tpu.shutdown()


# ------------------------------------------------------------- transport path


@pytest.mark.parametrize(
    "msg_type,delay",
    [("to_worker", 0.05), ("done", 0.05), ("from_worker", 0.05)],
)
def test_delay_sweep_tasks_correct(rt, msg_type, delay):
    """Delays on dispatch, completion, and the whole inbound envelope:
    every task still returns the right answer."""
    node = rt.add_node({"CPU": 2, "pin": 1})

    @ray_tpu.remote(resources={"pin": 1}, num_cpus=0)
    def sq(x):
        return x * x

    assert ray_tpu.get(sq.remote(3), timeout=60) == 9  # warm
    rpc_chaos.inject(msg_type, delay_s=delay)
    try:
        assert ray_tpu.get([sq.remote(i) for i in range(12)], timeout=120) == [i * i for i in range(12)]
    finally:
        rpc_chaos.clear()
        rt.remove_node(node.node_id)


def test_stream_items_survive_delay(rt):
    """Streaming generator under per-item delay: all items, in order."""

    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * 10

    ray_tpu.get(next(iter(gen.remote(1))))  # warm the worker
    rpc_chaos.inject("stream_item", delay_s=0.05)
    try:
        assert [ray_tpu.get(r) for r in gen.remote(8)] == [i * 10 for i in range(8)]
    finally:
        rpc_chaos.clear()


# --------------------------------------------------------- transfer chunk path


def test_transfer_chunk_abort_retries_then_succeeds(rt):
    """A mid-transfer abort on the serving side (the HEAD, where this
    test's chaos rules live) is retried by the consumer's pull_segment —
    the object arrives without lineage recomputation."""
    node = rt.add_node({"CPU": 2, "remote_res": 2}, remote=True, shm_isolation=True)
    big = np.arange(3 << 20, dtype=np.uint8)
    ref = ray_tpu.put(big)  # head-namespace segment: the head SERVES it

    @ray_tpu.remote(resources={"remote_res": 1})
    def consume(x):
        return int(x[min(12345, x.shape[0] - 1)]), x.nbytes

    # warm the remote worker without chaos
    assert ray_tpu.get(consume.remote(ray_tpu.put(np.ones(1, np.uint8))), timeout=120) == (1, 1)
    rpc_chaos.inject("transfer_chunk", drop_prob=1.0, max_hits=1)
    try:
        val, nbytes = ray_tpu.get(consume.remote(ref), timeout=120)
        assert (val, nbytes) == (12345 % 256, 3 << 20)
        # the abort really fired — success therefore proves the retry
        assert rpc_chaos._rules["transfer_chunk"].hits == 1
    finally:
        rpc_chaos.clear()
        rt.remove_node(node.node_id)


def test_transfer_failure_falls_back_to_reconstruction(rt, tmp_path):
    """When pulls keep dying past the retry budget, the consumer marks
    the object lost and lineage reconstruction re-produces it — liveness
    AND correctness."""
    node = rt.add_node({"CPU": 2, "remote_res": 2}, remote=True, shm_isolation=True)
    marker = str(tmp_path / "runs")

    @ray_tpu.remote(max_retries=3)  # runs on the head node (its server has chaos)
    def produce():
        with open(marker, "a") as f:
            f.write("x")
        return np.full(1 << 20, 7, dtype=np.uint8)

    @ray_tpu.remote(resources={"remote_res": 1}, max_retries=2)
    def consume(x):
        return int(x[0]), x.nbytes

    ref = produce.remote()
    # produced before the chaos starts. Not ``ray_tpu.wait``: on a result that lives in shared memory and that
    # nothing has fetched yet it reports nothing ready and sits out its whole timeout (ROADMAP.md C11)
    assert ray_tpu.get(ref, timeout=60)[0] == 7
    # enough hits to exhaust one full pull-retry budget and then some:
    # the consumer must go through mark-lost -> reconstruction
    rpc_chaos.inject("transfer_chunk", drop_prob=1.0, max_hits=4)
    try:
        assert ray_tpu.get(consume.remote(ref), timeout=180) == (7, 1 << 20)
        assert rpc_chaos._rules["transfer_chunk"].hits >= 4
    finally:
        rpc_chaos.clear()
        rt.remove_node(node.node_id)


# ------------------------------------------------------- health/failover path


def test_pong_drop_task_fails_over_with_result():
    """Starved health checks kill the node mid-flight; the queued work
    retries on a replacement node and still returns correct values."""
    ray_tpu.shutdown()
    ray_tpu.init(
        num_cpus=2,
        _system_config={"health_check_period_s": 0.2, "health_check_failure_threshold": 4},
    )
    rpc_chaos.seed(7)
    try:
        client = context.get_client()
        node = client.add_node({"CPU": 2, "pin": 1})

        @ray_tpu.remote(resources={"pin": 1}, num_cpus=0, max_retries=3)
        def slow_sq(x):
            import time as _t

            _t.sleep(0.5)
            return x * x

        assert ray_tpu.get(slow_sq.remote(2), timeout=60) == 4  # warm
        refs = [slow_sq.remote(i) for i in range(4)]
        rpc_chaos.inject("pong", drop_prob=1.0)
        deadline = time.time() + 30
        while time.time() < deadline and node.alive:
            time.sleep(0.1)
        assert not node.alive
        rpc_chaos.clear()
        client.add_node({"CPU": 2, "pin": 1})
        assert ray_tpu.get(refs, timeout=120) == [i * i for i in range(4)]
    finally:
        rpc_chaos.clear()
        ray_tpu.shutdown()


def test_ping_delay_does_not_kill_healthy_node():
    """Delays BELOW the failure threshold must not trigger failover
    (no false positives from slow links)."""
    ray_tpu.shutdown()
    ray_tpu.init(
        num_cpus=2,
        _system_config={"health_check_period_s": 0.3, "health_check_failure_threshold": 6},
    )
    rpc_chaos.seed(7)
    try:
        client = context.get_client()
        node = client.add_node({"CPU": 2, "pin": 1})
        rpc_chaos.inject("ping", delay_s=0.1)
        rpc_chaos.inject("pong", delay_s=0.1)

        @ray_tpu.remote(resources={"pin": 1}, num_cpus=0)
        def f(x):
            return x + 1

        for i in range(5):
            assert ray_tpu.get(f.remote(i), timeout=60) == i + 1
            time.sleep(0.3)
        assert node.alive, "healthy-but-slow node was wrongly declared dead"
    finally:
        rpc_chaos.clear()
        ray_tpu.shutdown()


# ------------------------------------------------------------------ serve path


def test_serve_routing_under_inbound_delay(rt):
    """Serve requests route and complete correctly while every inbound
    worker message is delayed."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    class Doubler:
        def __call__(self, x):
            return x * 2

    h = serve.run(Doubler.bind(), name="chaos_app")
    assert h.remote(1).result(timeout_s=60) == 2  # replicas warm
    # results arrive as head-path 'done' messages or direct-plane result
    # frames (core/direct.py) — delay both inbound paths
    rpc_chaos.inject("done", delay_s=0.03)
    rpc_chaos.inject("direct_result", delay_s=0.03)
    try:
        lat0 = time.perf_counter()
        results = [h.remote(i).result(timeout_s=120) for i in range(10)]
        assert results == [2 * i for i in range(10)]
        assert time.perf_counter() - lat0 >= 0.03 * 10  # the delay really applied
    finally:
        rpc_chaos.clear()
        serve.shutdown()
