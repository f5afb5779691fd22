"""CPU tests of the seam PR 28 cut into the harness and of how a run ends: a configuration names
its model family and ``benchmark/families/<family>.py`` holds everything the harness asks about
the model's block (a second family is new files only: a test builds one in a temporary copy and
rehearses it), and ``benchmark/reaper.py`` finds, waits for and kills the processes that carry a
run's marker, and no others."""

import filecmp
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import common, reaper, reference, traffic
from benchmark.families import NAMES
from benchmark.serve_cell import engine_kwargs

ROOT = common.ROOT
CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(common.HERE, "configs")) if f.endswith(".json"))

# the Llama block with its output head tied to the embedding table, in program and reference alike:
# the program's config can say so, the llama family's reference (which reads ``unembed``) cannot
TOY_FAMILY = '''"""A family for the tests: Llama's block with the output head tied to the embedding table."""
from benchmark.families import llama as _llama
from benchmark.families.llama import init_params, kernels_expected, loss_fn, param_logical_axes, train_flops_per_token  # noqa: F401


def program_config(c, max_seq_len, **extra):
    return _llama.program_config({**c, "tie_word_embeddings": True}, max_seq_len, **extra)


def reference_logprobs(params, tokens, c, start, stop):
    return _llama.reference_logprobs({**params, "unembed": params["embed"].T}, tokens, c, start, stop)


def rehearsal(c):
    return {**_llama.rehearsal(c), "num_hidden_layers": 3}
'''

# a block that is not the Llama block in any count: three kinds of layer, a gate norm, a convolution and per-head
# scalars in one of them, and attention heads 256 wide. Its wiring and reference are the hybrid family's, under a
# name of its own, as a model_config PR would bring them: a family file and a configuration file, nothing else
WIDE_FAMILY = '''"""A family for the tests: the hybrid block under another name, for a configuration whose heads are 256 wide."""
from benchmark.families import nemotron_h as _hybrid
from benchmark.families.nemotron_h import (init_params, kernels_expected, loss_fn, param_logical_axes, program_config,  # noqa: F401
                                           reference_logprobs, train_flops_per_token)


def rehearsal(c):
    return {**_hybrid.rehearsal(c), "head_dim": 32, "num_attention_heads": 2, "num_key_value_heads": 1}
'''


def _config(name):
    with open(os.path.join(common.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _wide_config():
    """``configs/nemotron-3-nano-30b-a3b-ep2.json`` with heads 256 wide, counted by its own family's rule."""
    from benchmark.families import nemotron_h

    c = {**_config("nemotron-3-nano-30b-a3b-ep2"), "family": "wide", "head_dim": 256, "training": {"remat": False, "attention_impl": "xla"}}
    c["parameters"] = nemotron_h.parameters_held(c)
    c["tolerance"] = {**c["tolerance"], "loss_abs": 0.002}
    return c


def _copy_with(tmp_path, name: str, family_source: str, c: dict, traffics: tuple) -> dict:
    """A copy of the benchmark under ``tmp_path`` with one more family, configuration and a cell of it
    under each of ``traffics``: new files and new entries, as a later PR may bring them. -> its BENCHMARK.json."""
    bench = common.load_benchmark()
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "benchmark" / "families" / f"{name}.py").write_text(family_source)
    (tmp_path / "benchmark" / "configs" / f"{name}.json").write_text(json.dumps(c))
    bench["configs"].append({"name": name, "source": c["source"], "file": f"benchmark/configs/{name}.json", "reduced": c["reduced"], "why": "a test's"})
    bench["workloads"] += [{"name": f"{name}.{t}", "config": name, "traffic": t, "chips": 1, "why": "a test's"} for t in traffics]
    if "chat" in traffics:  # an open-loop cell brings its rate
        (tmp_path / "benchmark" / "cells" / f"{name}.chat.json").write_text(json.dumps({"rate_per_s": 2.0, "why_rate": "a test's"}))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("itl_p95_ms", "engine_step_ms") and "chat" in traffics:
            m["workloads"].append(f"{name}.chat")
        if m["name"] in ("train_tokens_per_s", "flash_roofline") and "sft-2k" in traffics:
            m["workloads"].append(f"{name}.sft-2k")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


def _nothing_that_was_there_was_touched(tmp_path, bench):
    for p in bench["paths"]:
        stack = [filecmp.dircmp(os.path.join(ROOT, p), tmp_path / p, ignore=["__pycache__"])]
        while stack:
            d = stack.pop()
            assert not d.diff_files and not d.left_only, (d.left, d.diff_files, d.left_only)
            stack += d.subdirs.values()



# ----------------------------------------------------------------------------------- the seam
@pytest.mark.parametrize("name", NAMES)
def test_the_llama_family_gives_every_name_the_harness_asks_for(name):
    assert callable(getattr(common.load_family("llama"), name))


@pytest.mark.parametrize("config", CONFIGS)
def test_every_configuration_names_a_family_whose_file_exists(config):
    c = _config(config)
    assert os.path.exists(os.path.join(common.HERE, "families", c["family"] + ".py"))
    family = common.load_family(c["family"])
    assert family.program_config(c, 2048).num_params() == c["parameters"]
    assert family.rehearsal(c)["family"] == c["family"] and family.kernels_expected(c)


def test_a_configuration_without_a_family_is_an_error_not_a_default(tmp_path, monkeypatch):
    bench = common.load_benchmark()
    entry = bench["configs"][0]
    c = _config(os.path.basename(entry["file"])[:-5])
    del c["family"]
    os.makedirs(tmp_path / os.path.dirname(entry["file"]))
    (tmp_path / entry["file"]).write_text(json.dumps(c))
    monkeypatch.setattr(common, "ROOT", str(tmp_path))
    cell = next(w["name"] for w in bench["workloads"] if w["config"] == entry["name"])
    with pytest.raises(SystemExit, match="names no \"family\""):
        common.resolve_cell(bench, cell)


def test_a_family_that_is_not_there_or_lacks_a_name_is_an_error(tmp_path, monkeypatch):
    with pytest.raises(SystemExit, match="no family 'mamba'"):
        common.load_family("mamba")
    # a family file that defines only some of the names
    shutil.copytree(os.path.join(common.HERE, "families"), tmp_path / "families", ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "families" / "half.py").write_text("def program_config(c, n, **kw):\n    return None\n")
    monkeypatch.setattr(common, "HERE", str(tmp_path))
    import benchmark.families as pkg

    monkeypatch.setattr(pkg, "__path__", [str(tmp_path / "families")])
    with pytest.raises(SystemExit, match="lacks .*init_params"):
        common.load_family("half")
    sys.modules.pop("benchmark.families.half", None)


@pytest.mark.parametrize("needle", ["ray_tpu.models", "LlamaConfig", "llama_kwargs"])
def test_outside_families_no_file_of_the_benchmark_names_the_programs_model(needle):
    """``git grep`` in test form: the family is the one place that knows which model module the
    program has, so the next architecture is a new file there and no edit elsewhere."""
    hits = []
    for d, dirs, files in os.walk(common.HERE):
        dirs[:] = [x for x in dirs if x not in ("__pycache__", "families")]
        for f in files:
            if f.endswith((".py", ".json", ".md", ".toml", ".txt")):
                with open(os.path.join(d, f), errors="replace") as fh:
                    if needle in fh.read():
                        hits.append(os.path.relpath(os.path.join(d, f), ROOT))
    assert not hits


def test_the_reference_file_holds_no_layer_equations():
    with open(os.path.join(common.HERE, "reference.py")) as f:
        src = f.read()
    assert not [w for w in ("rsqrt", "softmax", "silu", "einsum", "jnp.", "import jax") if w in src]


def test_a_serving_blocks_engine_kwargs_reach_the_engine_beside_the_sizes():
    sv = _config("internlm2-1.8b")["serving"]
    sizes = {"max_num_seqs": sv["max_num_seqs"], "max_seq_len": 4096}
    assert engine_kwargs(sv, 7) == {"seed": 7, **sizes}  # the files that are there: nothing but the seed and the sizes
    paged = engine_kwargs({**sv, "engine_kwargs": {"kv_layout": "paged", "seed": 1}}, 7)
    assert paged == {"kv_layout": "paged", "seed": 7, **sizes}  # the run's seed wins


# ------------------------------------------------------------------- a second family: new files only
def _toy_module(tmp_path):
    path = tmp_path / "toy.py"
    path.write_text(TOY_FAMILY)
    spec = importlib.util.spec_from_file_location("benchmark_family_toy_for_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_toy_family_holds_its_loss_and_its_reference_together(tmp_path):
    """The quick twin of the slow test below: the toy family's four functions, called directly at
    toy size. Its reference agrees with its program; the llama family's reference cannot even read
    its weights, so the agreement is the toy file's doing."""
    import jax

    toy, llama = _toy_module(tmp_path), common.load_family("llama")
    assert not [n for n in NAMES if not callable(getattr(toy, n, None))]
    c = toy.rehearsal({"family": "toy", "rope_theta": 1e6, "rms_norm_eps": 1e-5, "tie_word_embeddings": False})
    cfg = toy.program_config(c, 64, remat=False, attention_impl="xla")
    params = toy.init_params(cfg, jax.random.PRNGKey(11))
    assert cfg.num_layers == 3 and "unembed" not in params
    batch = traffic.train_batch(5, 0, 2, 32, c["vocab_size"])
    want = float(toy.loss_fn(params, batch, cfg))
    assert reference.loss(toy.reference_logprobs, params, batch, c) == pytest.approx(want, abs=2e-4)
    with pytest.raises(KeyError):
        reference.loss(llama.reference_logprobs, params, batch, c)
    lp = np.asarray(toy.reference_logprobs(params, list(range(1, 33)), c, 4, 20))
    assert lp.shape == (16, c["vocab_size"]) and np.allclose(np.exp(lp).sum(-1), 1.0, atol=1e-4)


def _marked(prefix: str) -> list[int]:
    """pids of live processes whose environment holds a run marker that starts with ``prefix``."""
    out = []
    for name in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{name}/environ", "rb") as f:
                if any(e.startswith(f"{reaper.ENV}={prefix}".encode()) for e in f.read().split(b"\0")):
                    out.append(int(name))
        except OSError:
            pass
    return out


def _rehearse_both_drivers(tmp_path, name: str):
    """``--rehearse`` of the training and the serving driver in the copy: each prints the family it used and
    agrees with that family's reference, and nothing that carried a run's marker is alive afterwards."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=1", "PYTHONPATH": ROOT}
    for cell, says in ((f"{name}.sft-2k", f"[train] family {name};"), (f"{name}.chat", f"[serve] family {name};")):
        proc = subprocess.Popen([sys.executable, str(tmp_path / "benchmark" / "run.py"), "--workload", cell, "--seed", "3000000019",
                                 "--seconds", "3", "--trace", "0", "--rehearse"], cwd=tmp_path, env=env, text=True,
                                stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        out, _ = proc.communicate(timeout=600)
        assert proc.returncode == 1 and says in out, out[-3000:]
        lines = out.strip().splitlines()
        assert json.loads(lines[-1])["rehearsal"] is True
        assert any(ln.startswith("[run] processes: ") and "SIGKILL after 20 s to 0 []" in ln for ln in lines), out[-3000:]
        assert not _marked(f"{proc.pid}-")
        if cell.endswith(".chat"):
            assert '[serve] reference: {"ok": true' in out, out[-3000:]
        else:
            assert "(d 0.0000), step after the window" in out, out[-3000:]


@pytest.mark.slow
def test_a_second_family_is_new_files_only_and_a_rehearsal_leaves_no_process(tmp_path):
    """Copies the benchmark, drops in ``families/toy.py``, a configuration file and two cells'
    entries, and rehearses both drivers there: each prints the family it used, the reference that
    ran was the toy's, no file that was there changed, and nothing that carried a run's marker is
    alive afterwards."""
    c = {**_config("internlm2-1.8b"), "family": "toy", "training": {"remat": False, "attention_impl": "xla"}}
    c["tolerance"] = {**c["tolerance"], "loss_abs": 0.002}
    bench = _copy_with(tmp_path, "toy", TOY_FAMILY, c, ("chat", "sft-2k"))
    _rehearse_both_drivers(tmp_path, "toy")
    _nothing_that_was_there_was_touched(tmp_path, bench)


# ------------------------------------------ a family that is nothing like Llama's: still new files only
def _held_to_every_configuration(module) -> list:
    """The test functions of ``module`` that are parametrised over every file in ``configs/``."""
    out = []
    for name, fn in vars(module).items():
        for mark in getattr(fn, "pytestmark", []) if name.startswith("test_") else []:
            if mark.name == "parametrize" and mark.args[0] == "config" and list(mark.args[1]) == CONFIGS:
                out.append(fn)
    return out


def test_a_family_with_heads_256_wide_and_another_block_passes_what_every_configuration_is_held_to(tmp_path, monkeypatch):
    """The quick twin of the slow test below. A configuration whose family is not ``llama``, whose
    heads are 256 wide and whose layers are of three kinds (one with a gate norm, a convolution and
    per-head scalars beside its matrices) is dropped into a copy with its family file; every test
    that is parametrised over the files in ``configs/`` is then called on it, and passes: none asks
    a head width or a parameter identity of a block that is not the family's own."""
    import test_benchmark_harness as harness  # the sibling file: pytest put this directory on sys.path

    import benchmark.families as pkg

    c = _wide_config()
    _copy_with(tmp_path, "wide", WIDE_FAMILY, c, ("chat", "sft-2k"))
    monkeypatch.setattr(common, "HERE", str(tmp_path / "benchmark"))
    monkeypatch.setattr(pkg, "__path__", [str(tmp_path / "benchmark" / "families")])
    try:
        held = _held_to_every_configuration(harness) + _held_to_every_configuration(sys.modules[__name__])
        assert {f.__name__ for f in held} >= {"test_matmul_params_and_published_count", "test_every_configuration_names_a_family_whose_file_exists"}
        for test in held:
            test("wide")
        cfg = common.load_family("wide").program_config(c, 2048)
        assert cfg.hd == 256 and cfg.num_params() == c["parameters"] != _config("nemotron-3-nano-30b-a3b-ep2")["parameters"]
        # what only the llama family is asked stays with the llama family, in the copy too
        copied = sorted(f[:-5] for f in os.listdir(tmp_path / "benchmark" / "configs"))
        assert "wide" in copied and "wide" not in harness.llama_configs(copied) and harness.llama_configs(copied) == harness.llama_configs(CONFIGS)
        # the Llama block's identity, asked of this block as it was of every configuration until PR 33, does not hold
        norms = (2 * c["num_hidden_layers"] + 1) * c["hidden_size"]
        assert common.load_family("llama").matmul_params(c) != cfg.num_params() - c["vocab_size"] * c["hidden_size"] - norms
    finally:
        for name in [m for m in sys.modules if m.startswith("benchmark.families.wide")]:
            sys.modules.pop(name)


@pytest.mark.slow
def test_a_family_with_heads_256_wide_passes_every_collected_test_and_rehearses_both_drivers(tmp_path):
    """In a copy with ``families/wide.py``, ``configs/wide.json`` and two cells' entries: every test
    collected from the copy's ``tests/benchmark`` (the slow ones aside) passes with the new
    configuration among its cases, both drivers rehearse it against its own reference, and no file
    that was there changed."""
    bench = _copy_with(tmp_path, "wide", WIDE_FAMILY, _wide_config(), ("chat", "sft-2k"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": os.pathsep.join([str(tmp_path), ROOT])}
    out = subprocess.run([sys.executable, "-m", "pytest", "tests/benchmark", "-q", "-m", "not slow", "-p", "no:cacheprovider", "-rf"],
                         cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0 and " passed" in out.stdout, out.stdout[-3000:] + out.stderr[-2000:]
    _rehearse_both_drivers(tmp_path, "wide")
    _nothing_that_was_there_was_touched(tmp_path, bench)


# ----------------------------------------------------------------------------- how a run ends
def _child(marker: str | None, ignore_sigterm: bool):
    code = ("import signal, time\n" + ("signal.signal(signal.SIGTERM, signal.SIG_IGN)\n" if ignore_sigterm else "")
            + "print('up', flush=True)\ntime.sleep(120)\n")
    env = {k: v for k, v in os.environ.items() if k != reaper.ENV} | ({reaper.ENV: marker} if marker else {})
    p = subprocess.Popen([sys.executable, "-c", code], env=env, stdout=subprocess.PIPE, text=True)
    assert p.stdout.readline().strip() == "up"
    return p


def test_a_marked_child_that_ignores_sigterm_is_found_killed_and_counted_and_no_other_is_touched():
    r = reaper.Reaper()
    stubborn, bystander, other_run = _child(r.marker, True), _child(None, False), _child(r.marker + "x", False)
    try:
        stubborn.send_signal(signal.SIGTERM)  # what Node.shutdown's terminate() does: not enough
        found = r.close()
        assert found == [stubborn.pid] and f"{stubborn.pid} (child of {os.getpid()}): {sys.executable} -c import signal" in r.seen[stubborn.pid]["who"]
        t = time.time()
        res = r.reap(found, t, wait_s=0.3, killed_wait_s=20.0)
        assert res["found"] == 1 and len(res["killed"]) == 1 and res["left"] == [] and res["ever"] == 1
        assert 0.3 <= res["outlived_s"] < 15.0
        assert not os.path.exists(f"/proc/{stubborn.pid}")  # killed AND collected: the reaper is its parent here
        assert bystander.poll() is None and other_run.poll() is None
    finally:
        for p in (stubborn, bystander, other_run):
            p.kill()
            p.wait(timeout=10)


def test_a_marked_process_that_ends_by_itself_is_waited_for_not_killed():
    r = reaper.Reaper()
    p = subprocess.Popen([sys.executable, "-c", "import time; print('up', flush=True); time.sleep(1.0)"],
                         env={**os.environ, reaper.ENV: r.marker}, stdout=subprocess.PIPE, text=True)
    try:
        assert p.stdout.readline().strip() == "up"
        found = r.close()
        res = r.reap(found, time.time(), wait_s=20.0, killed_wait_s=1.0)
        assert res["found"] == 1 and res["killed"] == [] and res["left"] == [] and 0.0 < res["outlived_s"] < 10.0
        assert p.wait(timeout=10) == 0
    finally:
        p.kill()
        p.wait(timeout=10)


def test_the_marker_is_inherited_through_the_environment_and_the_watcher_remembers_who_has_gone():
    """``start()`` puts the marker where children inherit it; the watcher remembers a process it saw
    even after that process has ended (an exiting process has no environment left to read)."""
    assert reaper.ENV not in os.environ
    r = reaper.Reaper(period_s=0.05)
    r.start()
    try:
        assert os.environ[reaper.ENV] == r.marker
        p = subprocess.Popen([sys.executable, "-c", "import time; print('up', flush=True); time.sleep(0.5)"],
                             stdout=subprocess.PIPE, text=True)
        assert p.stdout.readline().strip() == "up"
        deadline = time.time() + 10
        while p.pid not in r.seen and time.time() < deadline:
            time.sleep(0.02)
        assert p.pid in r.seen
        assert p.wait(timeout=10) == 0
        found = r.close()
        assert found == [] and r.reap(found, time.time(), wait_s=1.0)["ever"] == 1
    finally:
        r.close()
        os.environ.pop(reaper.ENV, None)
        reaper.adopt_orphans(False)


def test_an_orphan_of_the_run_is_handed_to_the_run_and_collected_when_it_has_ended():
    """A worker is the forkserver's child; when the forkserver goes first, the worker is handed
    to ``run.py`` (``adopt_orphans``), which waits for it by pid and collects it itself."""
    assert reaper.ENV not in os.environ
    r = reaper.Reaper(period_s=0.05)
    r.start()
    try:
        grandchild = "import time; time.sleep(1.5)"
        middle = f"import subprocess, sys; print(subprocess.Popen([sys.executable, '-c', {grandchild!r}]).pid, flush=True)"
        p = subprocess.Popen([sys.executable, "-c", middle], stdout=subprocess.PIPE, text=True)
        orphan = int(p.stdout.readline())
        assert p.wait(timeout=10) == 0
        deadline = time.time() + 10
        while orphan not in r.seen and time.time() < deadline:
            time.sleep(0.02)
        found = r.close()
        assert found == [orphan] and reaper._stat(orphan)[1] == os.getpid()  # this process is its parent now
        res = r.reap(found, time.time(), wait_s=20.0, killed_wait_s=1.0)
        assert res["killed"] == [] and res["left"] == [] and 0.0 < res["outlived_s"] < 10.0
        assert not os.path.exists(f"/proc/{orphan}")
    finally:
        r.close()
        os.environ.pop(reaper.ENV, None)
        reaper.adopt_orphans(False)
