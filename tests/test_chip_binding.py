"""One process per chip, on a CPU box: what the runtime puts in a worker's
environment, who reserves a chip, and where the compile cache goes. The
chips here are a resource count (``init(num_tpus=...)``); nothing opens a
device. ``chip_smoke.py`` shows the same rules against a real libtpu lock."""

import os

import pytest

import ray_tpu
from ray_tpu.accelerators.tpu import TPUAcceleratorManager
from ray_tpu.train import ScalingConfig


def _env():
    return {"pid": os.getpid(), "visible": os.environ.get("TPU_VISIBLE_CHIPS"),
            "platforms": os.environ.get("JAX_PLATFORMS"), "bounds": os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS")}


@pytest.fixture
def tpu_node():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.mark.parametrize("chips, bounds", [([0], "1,1,1"), ([2, 3], "1,2,1"), ([0, 1, 2, 3], "2,2,1")])
def test_chip_env_makes_a_missing_chip_an_error(chips, bounds):
    env = TPUAcceleratorManager.worker_env_for_chips(chips)
    assert env["TPU_VISIBLE_CHIPS"] == ",".join(map(str, chips))
    assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == bounds
    assert env["JAX_PLATFORMS"] == "tpu"  # never a CPU run in the chip's place


def test_chip_bound_workers_are_single_use_and_chipless_ones_stay_off_the_chip(tpu_node):
    bound = ray_tpu.remote(num_cpus=0, num_tpus=1)(_env)
    plain = ray_tpu.remote(num_cpus=1)(_env)
    a, b = ray_tpu.get(bound.remote()), ray_tpu.get(bound.remote())
    assert a["platforms"] == b["platforms"] == "tpu" and a["bounds"] == "1,1,1"
    assert a["visible"] is not None and b["visible"] is not None
    assert a["pid"] != b["pid"]  # the binding is baked into the process: never reused
    c = ray_tpu.get(plain.remote())
    assert c["visible"] is None and c["platforms"] == "cpu"  # no chip given, none can be opened


def test_back_to_back_chip_tasks_on_one_chip_each_get_the_chip():
    """The TPU count is held back with the chip ids until the retired
    worker's process has exited. Released alone it let the next task be
    placed with no chip id to bind: on the v5e the second of two
    num_tpus=1 tasks ran chipless, on the CPU backend."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:
        bound = ray_tpu.remote(num_cpus=0, num_tpus=1)(_env)
        seen = [ray_tpu.get(bound.remote(), timeout=60) for _ in range(4)]
        assert [e["visible"] for e in seen] == ["0"] * 4 and {e["platforms"] for e in seen} == {"tpu"}
        assert len({e["pid"] for e in seen}) == 4
    finally:
        ray_tpu.shutdown()


def test_four_chip_worker_gets_the_whole_host(tpu_node):
    env = ray_tpu.get(ray_tpu.remote(num_cpus=0, num_tpus=4)(_env).remote())
    assert env["visible"] == "0,1,2,3" and env["bounds"] == "2,2,1" and env["platforms"] == "tpu"


@pytest.mark.parametrize(
    "kw, want",
    [
        (dict(use_tpu=True), {"CPU": 1.0, "TPU": 1.0}),
        (dict(use_tpu=True, resources_per_worker={"TPU": 4.0}), {"CPU": 1.0, "TPU": 4.0}),
        (dict(use_tpu=False), {"CPU": 1.0}),
    ],
    ids=["use_tpu_reserves_one", "explicit_count_kept", "cpu_worker_reserves_none"],
)
def test_train_worker_chip_reservation(kw, want):
    assert ScalingConfig(num_workers=1, **kw)._worker_resources == want


@pytest.mark.parametrize(
    "tpus, tp, explicit, want",
    [(4, 1, -1, 1.0), (4, 4, -1, 4.0), (0, 1, -1, 0.0), (0, 2, -1, 2.0), (4, 2, 0.0, 0.0)],
    ids=["tp1_on_tpu_node", "tp4_on_tpu_node", "tp1_cpu_only", "tp2_cpu_only", "explicit_opt_out"],
)
def test_llm_replica_chip_reservation(tpus, tp, explicit, want):
    from ray_tpu.serve.llm import LLMConfig, build_llm_deployment

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, num_tpus=tpus)
    try:
        app = build_llm_deployment(LLMConfig(tensor_parallel_size=tp, num_tpus_per_replica=explicit))
        assert app.deployment.replica_config.num_tpus == want
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize(
    "env, want_dir, sets_config",
    [
        ({"JAX_COMPILATION_CACHE_DIR": "/somewhere/else", "JAX_PLATFORMS": "tpu"}, "/somewhere/else", False),
        ({"JAX_PLATFORMS": "tpu"}, "default", True),
        ({"JAX_PLATFORMS": "tpu,cpu"}, "default", True),
        ({"JAX_PLATFORMS": "cpu"}, "", False),
    ],
    ids=["placed_from_outside", "fixed_in_checkout", "chip_machine_default", "cpu_pinned_off"],
)
def test_compile_cache_placement(monkeypatch, env, want_dir, sets_config):
    import jax

    from ray_tpu.util import compile_cache as cc

    for k in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS"):
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append((k, v)))
    got = cc.enable_compile_cache()
    assert got == (cc.DEFAULT_CACHE_DIR if want_dir == "default" else want_dir)
    assert updates == ([("jax_compilation_cache_dir", cc.DEFAULT_CACHE_DIR)] if sets_config else [])
    # fixed path inside the checkout: no temp name, pid or timestamp in it
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.DEFAULT_CACHE_DIR == os.path.join(root, ".jax_compile_cache")
