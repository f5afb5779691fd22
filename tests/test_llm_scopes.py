"""The scope vocabulary is whole (PR 39): for a toy configuration of each of the descriptions below, every step program an engine builds and runs, lowered on the CPU, has every
``dot_general``, convolution, custom call, scatter, gather and ``dynamic_update_slice`` under a
scope of ``util/profiling.SCOPES``; and a scope outside the table raises where it is traced."""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.llm import LLMEngine, SamplingParams, hybrid_runner, model_runner  # noqa: E402
from ray_tpu.llm.model_runner import STEP_PROGRAM_NAMES  # noqa: E402
from ray_tpu.models import hybrid  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.util import profiling  # noqa: E402
from ray_tpu.util.profiling import SCOPES, UNSCOPED, scope, scope_of  # noqa: E402

HEAVY = ("stablehlo.dot_general", "stablehlo.convolution", "stablehlo.custom_call", "stablehlo.scatter",
         "stablehlo.gather", "stablehlo.dynamic_update_slice")

SLOTS = ["llm_prefill", "llm_kv_insert", "llm_fused_step", "llm_extend"]
PAGED = ["llm_kv_insert_pages", "llm_fused_paged_step", "llm_kv_append", "llm_extend_paged_attn", "llm_kv_append_chunk"]
HYBRID = ["llm_hybrid_prefill", "llm_kv_insert", "llm_state_insert", "llm_hybrid_fused_step"]
PROGRAMS = {"llama": SLOTS, "llama_paged": PAGED, "nemotron_h": HYBRID, "qwen3_next": HYBRID,
            "glm4_moe_lite": [p for p in HYBRID if p != "llm_state_insert"],  # latent attention keeps nothing per sequence
            "kimi_linear": HYBRID,  # a state a sequence AND a latent a position
            "minicpm_sala": HYBRID,  # keys and values a position, a state and the compressed keys a sequence
            "keye_vl": [p for p in HYBRID if p != "llm_state_insert"],  # keys, values and the indexer's key a position, nothing a sequence
            "jamba": HYBRID,  # keys and values a position in two layers, a state and a window a sequence in the rest
            "afmoe": [p for p in HYBRID if p != "llm_state_insert"]}  # keys and values a position, in rows and in rings; nothing a sequence


class Recording:
    """A step program that keeps the lowering of its first call."""

    def __init__(self, name, jitted, sink):
        self._name, self._jitted, self._sink = name, jitted, sink

    def __call__(self, *args, **kwargs):
        if self._name not in self._sink:
            self._sink[self._name] = self._jitted.lower(*args, **kwargs)
        return self._jitted(*args, **kwargs)

    def __getattr__(self, attr):
        return getattr(self._jitted, attr)


def _config(description):
    if description.startswith("llama"):
        return LlamaConfig.tiny(dtype="float32", remat=False, max_seq_len=256)
    if description == "nemotron_h":
        from ray_tpu.models.nemotron_h import NemotronHConfig

        return NemotronHConfig.tiny(num_local_experts=4)
    if description == "qwen3_next":
        from ray_tpu.models.qwen3_next import Qwen3NextConfig

        return Qwen3NextConfig.tiny(num_local_experts=4)
    if description == "kimi_linear":
        from ray_tpu.models.kimi_linear import KimiLinearConfig

        return KimiLinearConfig.tiny(num_hidden_layers=5, full_attn_layers=(2, 4), num_local_experts=4)
    if description == "minicpm_sala":
        from ray_tpu.models.minicpm_sala import MiniCPMSALAConfig

        return MiniCPMSALAConfig.tiny()  # dense below 32: the prompt of 40 chooses its blocks, the one of 9 does not
    if description == "keye_vl":
        from ray_tpu.models.keye_vl import KeyeVLConfig

        return KeyeVLConfig.tiny()  # top-k 16: the prompt of 40 goes through the index (its bucket holds 64), the one of 9 through the flash call
    if description == "jamba":
        from ray_tpu.models.jamba import JambaConfig

        return JambaConfig.tiny()
    if description == "afmoe":
        from ray_tpu.models.afmoe import AfmoeConfig

        return AfmoeConfig.tiny(num_local_experts=4)  # a dense layer, three window layers and a full one; the prompt of 40 is over two windows of 16
    from ray_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig

    return Glm4MoeLiteConfig.tiny()


@pytest.fixture(scope="module")
def lowered(shared_step_programs):
    """description -> {program name: its lowering}, each description's engines run once."""
    done: dict = {}

    def of(description):
        if description in done:
            return done[description]
        sink: dict = {}
        real = model_runner.named_jit

        def recording(name, fn, **kw):
            return Recording(name, real(name, fn, **kw), sink)

        patch = pytest.MonkeyPatch()
        patch.setattr(model_runner, "named_jit", recording)
        patch.setattr(hybrid_runner, "named_jit", recording)
        try:
            cfg = _config(description)
            kw = {"max_num_seqs": 2, "max_seq_len": 128, "prefill_buckets": (16, 32, 64)}
            if description == "llama_paged":
                kw.update(kv_layout="paged", page_size=16)
            hybrid_model = hasattr(cfg, "layer_kinds")
            if not hybrid_model:
                kw.update(enable_prefix_caching=True, prefix_block=16)
            prompt = list(np.random.default_rng(0).integers(1, cfg.vocab_size - 1, size=40))
            sp = SamplingParams(max_tokens=3)
            eng = LLMEngine(cfg, **kw)
            eng.generate([prompt, prompt[:9]], sp)
            if not hybrid_model:
                eng.generate([prompt[:33] + [5, 6, 7]], sp)  # a cached prefix: the suffix goes through extend
        finally:
            patch.undo()
        done[description] = sink
        return sink

    return of


def _name(op) -> str:
    """The name at the head of an operation's location: its path of scopes, or '' where it has none."""
    text = str(op.location)
    return text[5:text.index('"', 5)] if text.startswith('loc("') else ""


def unscoped_ops(lowering) -> list[str]:
    """The heavy operations of a lowered program that stand under no scope of the table. An
    operation inside a private function (a jitted helper, a loop's body) carries the path from
    that function's start: it counts as scoped where its own path holds a table name or every
    call of its function, up to the program's entry, stands under one."""
    from jax._src.lib.mlir import ir

    module = lowering.compiler_ir()
    heavy, calls, public = [], {}, set()
    for func in module.body.operations:
        fname = str(func.attributes["sym_name"]).strip('"')
        if "sym_visibility" not in func.attributes or "private" not in str(func.attributes["sym_visibility"]):
            public.add(fname)

        def visit(op, fname=fname):
            kind = op.operation.name
            if kind in HEAVY:
                heavy.append((fname, kind, _name(op)))
            elif kind == "func.call":
                calls.setdefault(str(op.attributes["callee"]).lstrip("@"), []).append((fname, _name(op)))
            return ir.WalkResult.ADVANCE

        func.operation.walk(visit)

    seen: dict = {}

    def covered(fname) -> bool:
        if fname in public or fname not in calls:
            return False
        if fname not in seen:
            seen[fname] = False  # a cycle cannot cover itself
            seen[fname] = all(scope_of(path) != UNSCOPED or covered(caller) for caller, path in calls[fname])
        return seen[fname]

    return [f"{kind} at {path!r} in @{fname}" for fname, kind, path in heavy if scope_of(path) == UNSCOPED and not covered(fname)]


@pytest.mark.parametrize("description,program", [(d, p) for d, ps in PROGRAMS.items() for p in ps])
def test_every_heavy_operation_of_a_step_program_stands_under_a_table_scope(lowered, description, program):
    programs = lowered(description)
    assert program in programs and program in STEP_PROGRAM_NAMES, f"{description} never ran {program}: it ran {sorted(programs)}"
    assert unscoped_ops(programs[program]) == []


@pytest.mark.parametrize("description", ["nemotron_h", "qwen3_next", "glm4_moe_lite", "kimi_linear"])
def test_a_prefills_placement_stands_under_its_three_parts_by_name(lowered, description):
    """``moe.place`` in a prefill is ``moe.place.count`` / ``.into`` / ``.out`` (PR 47), so that a traced
    run says which part of the placement costs what: every gather and scatter of the expert layer
    outside the blocks' loop stands under one of the three, set INSIDE ``moe.place``."""
    from jax._src.lib.mlir import ir

    paths = set()

    def visit(op):
        paths.add(_name(op))
        return ir.WalkResult.ADVANCE

    lowered(description)["llm_hybrid_prefill"].compiler_ir().operation.walk(visit)
    placed = {scope_of(p) for p in paths if "/moe.place/" in p}
    assert placed == {"moe.place.count", "moe.place.into", "moe.place.out"}


@pytest.mark.parametrize("runs", ["loop", "kernel"])
@pytest.mark.parametrize("description", ["nemotron_h", "qwen3_next", "glm4_moe_lite", "kimi_linear"])
def test_a_prefills_blocks_stand_under_moe_blocks_whichever_way_they_are_run(description, runs, monkeypatch):
    """The experts' matmuls of a prefill stand under ``moe.blocks`` as the loop of ``experts._grouped`` and as
    the kernel of ``ops/grouped_experts.py`` (PR 57; the test answers for its ``refusal`` and the body lowers as
    the interpreter runs it), so ``moe_blocks_share`` and ``prefill_ffn_ms_per_ktok`` read either; the kernel's
    program hands back a fourth routing counter, and nothing of it stands outside the table's scopes."""
    from functools import partial

    from jax._src.lib.mlir import ir

    from ray_tpu.ops import grouped_experts

    if runs == "kernel":
        monkeypatch.setattr(grouped_experts, "refusal", lambda *a: None)
    cfg = _config(description)
    params = jax.eval_shape(lambda: cfg.init_params(jax.random.PRNGKey(0)))
    lowering = jax.jit(partial(hybrid_runner.prefill, cfg=cfg)).lower(params, jax.ShapeDtypeStruct((2, 32), "int32"), jax.ShapeDtypeStruct((2,), "int32"))
    under = {}

    def visit(op):
        if "/moe.blocks/" in _name(op):
            under.setdefault(op.operation.name, []).append(_name(op))
        return ir.WalkResult.ADVANCE

    lowering.compiler_ir().operation.walk(visit)
    assert "stablehlo.dot_general" in under and all(scope_of(path) == "moe.blocks" for paths in under.values() for path in paths)
    # the loop's blocks are a ``while`` of its own under the scope; the kernel's are the interpreter's walk of the grid
    assert any("/moe.blocks/while/" in path for path in under["stablehlo.dot_general"]) == (runs == "loop")
    assert lowering.out_info[2][hybrid.ROUTING].shape == (4 if runs == "kernel" else 3,)
    assert unscoped_ops(lowering) == []


def scopes_of(lowering) -> dict:
    """scope -> the names of the operations that stand under it (the deepest name of the table on their path) in a lowered program."""
    from jax._src.lib.mlir import ir

    found = {}

    def visit(op):
        found.setdefault(scope_of(_name(op)), set()).add(op.operation.name)
        return ir.WalkResult.ADVANCE

    lowering.compiler_ir().operation.walk(visit)
    return found


@pytest.mark.parametrize("runs", ["xla", "kernel"])
def test_a_mamba1_layers_parts_stand_under_their_four_scopes_whichever_way_the_scan_is_run(lowered, runs, monkeypatch):
    """PR 60: in a prefill the convolution stands under ``mamba1.conv`` and the recurrence under ``mamba1.scan`` (as XLA's
    ``while`` over positions, and as the kernel of ``ops/selective_scan.py``: the test answers for its ``refusal`` and the
    body lowers as the interpreter runs it), both INSIDE ``mamba1``, whose own operations are the projections; in the
    fused step the state's and the window's read, decay and write stand under ``mamba1.state``: what the three new
    readers and ``decode_state_ms`` go by."""
    from functools import partial

    from ray_tpu.ops import selective_scan

    if runs == "kernel":
        monkeypatch.setattr(selective_scan, "refusal", lambda *a, **kw: None)
    cfg = _config("jamba")
    params = jax.eval_shape(lambda: cfg.init_params(jax.random.PRNGKey(0)))
    prefill = jax.jit(partial(hybrid_runner.prefill, cfg=cfg)).lower(params, jax.ShapeDtypeStruct((2, 32), "int32"), jax.ShapeDtypeStruct((2,), "int32"))
    found = scopes_of(prefill)
    assert {"mamba1", "mamba1.conv", "mamba1.scan", "attn", "ffn"} <= set(found) and "mamba1.state" not in found
    # XLA's form is the recurrence's own operations under the scope; the interpreter walks the kernel's grid in functions it calls from there
    assert ("stablehlo.exponential" if runs == "xla" else "func.call") in found["mamba1.scan"] and "stablehlo.dot_general" in found["mamba1"] and "stablehlo.dot_general" not in found["mamba1.scan"] | found["mamba1.conv"]
    assert unscoped_ops(prefill) == []
    step = scopes_of(lowered("jamba")["llm_hybrid_fused_step"])
    assert {"mamba1", "mamba1.conv", "mamba1.state"} <= set(step) and "mamba1.scan" not in step
    assert {"stablehlo.exponential", "stablehlo.dynamic_update_slice"} <= step["mamba1.state"]
    assert all(SCOPES[n] == role for n, role in (("mamba1", "mixer"), ("mamba1.conv", "mixer"), ("mamba1.scan", "mixer"), ("mamba1.state", "state")))


def test_an_afmoe_layers_gate_stands_under_a_scope_of_its_own_and_its_second_norm_under_its_sub_blocks(lowered):
    """PR 64: in the prefill and in the fused step alike, the output gate's projection and its product with the heads'
    output stand under ``swa.gate`` in a window layer and ``attn.gate`` in a full one, INSIDE the layer's scope (what
    ``prefill_gate_ms_per_ktok`` goes by: a trace says what the gate costs), the other projections under the layer's own;
    the sandwich's second norm is the sub-block's own operation (a ``rsqrt`` under ``mlp`` and under ``moe``, which hold no
    other norm: the loop's pre-norm stands there too, so under each at least two)."""
    programs = lowered("afmoe")
    for name in ("llm_hybrid_prefill", "llm_hybrid_fused_step"):
        found = scopes_of(programs[name])
        assert {"swa", "swa.gate", "attn", "attn.gate", "mlp", "moe", "moe.route", "moe.blocks", "moe.shared"} <= set(found), name
        for kind in ("swa", "attn"):
            assert {"stablehlo.dot_general", "stablehlo.exponential", "stablehlo.multiply"} <= found[kind + ".gate"] and "stablehlo.rsqrt" not in found[kind + ".gate"]
            assert {"stablehlo.dot_general", "stablehlo.rsqrt"} <= found[kind]
        assert "stablehlo.rsqrt" in found["mlp"] and "stablehlo.rsqrt" in found["moe"]
        assert unscoped_ops(programs[name]) == []
    text = programs["llm_hybrid_prefill"].as_text()
    assert text.count("stablehlo.rsqrt") >= 2 * 4 + 2 * 2  # a branch a kind: two stream norms in each of the four, two head norms in each attention kind
    assert all(SCOPES[n] == "mixer" for n in ("swa", "swa.gate", "attn", "attn.gate")) and SCOPES["mlp"] == SCOPES["moe"] == "ffn"


@pytest.mark.parametrize("description", sorted(PROGRAMS))
def test_the_engine_ran_no_step_program_the_cases_above_leave_out(lowered, description):
    assert set(lowered(description)) <= set(PROGRAMS[description]) | {"llm_prefill"}  # the paged engine prefills by the slot program


def test_the_training_step_shares_the_blocks_scopes():
    """``models/llama.py``'s block is the one the trainer differentiates: forward and backward
    stand under ``attn`` and ``mlp``."""
    from ray_tpu.models import llama

    cfg = LlamaConfig.tiny(dtype="float32", remat=False)
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), "int32"), "targets": jax.ShapeDtypeStruct((2, 16), "int32")}
    lowering = jax.jit(jax.grad(lambda p, b: llama.loss_fn(p, b, cfg))).lower(params, batch)
    # what stays outside: the layer scan stacking what the backward pass needs (``jvp()/while/body``): autodiff's, not the block's
    assert [u for u in unscoped_ops(lowering) if "dynamic_update_slice at 'jit(<lambda>)/jvp()/while/body" not in u
            and "dynamic_update_slice at 'jit(<lambda>)/transpose(jvp())/while/body" not in u] == []
    # where the transformations wrap a scope's name instead of the path around it, the name is read through them
    assert scope_of("jit(step)/jit(main)/jvp(mlp)/dot_general") == "mlp"
    assert scope_of("jit(step)/jit(main)/transpose(jvp(attn))/dot_general") == "attn"


def test_a_scope_outside_the_table_raises_where_it_is_traced():
    with pytest.raises(ValueError, match="not a documented scope"):
        scope("mystery")
    from ray_tpu.models.nemotron_h import NemotronHConfig

    cfg = NemotronHConfig.tiny()

    class Renamed(type(cfg)):
        @property
        def mixers(self):
            return {k: m._replace(scope="mamba3") if k == "mamba" else m for k, m in super().mixers.items()}

    bad = Renamed(**{f: getattr(cfg, f) for f in cfg.__dataclass_fields__})
    params = jax.eval_shape(lambda: bad.init_params(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="'mamba3' is not a documented scope"):
        jax.eval_shape(lambda p: hybrid.forward(p, jax.numpy.zeros((1, 8), "int32"), bad), params)


def test_the_table_says_a_role_for_every_name_and_sub_scopes_name_their_kind():
    assert set(SCOPES.values()) == {"mixer", "ffn", "state", "embed", "head", "sample", "cache"}
    for name in SCOPES:
        if "." in name:
            assert name.split(".")[0] in SCOPES and profiling.under(name, name.split(".")[0])
    assert scope_of("jit(llm_hybrid_prefill)/jit(main)/while/body/cond/branch_1_fun/moe/moe.blocks/while/body/dot_general") == "moe.blocks"
    assert scope_of("jit(llm_fused_step)/jit(main)/while/body/attn/cache/scatter") == "cache"
    assert scope_of("jit(set_lane)/jit(main)/scatter") == UNSCOPED
    assert model_runner.SCOPES is SCOPES  # the table a reader finds beside STEP_PROGRAM_NAMES


def test_step_program_names_holds_the_names_that_are_jitted_and_no_other():
    """Every name of ``STEP_PROGRAM_NAMES`` is the literal first argument of a ``named_jit`` call somewhere in the
    package, and every such literal is in the table: a program deleted with its name left behind, or jitted under a
    name the table lacks (which ``named_jit`` refuses only once it runs), shows here."""
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(model_runner.__file__)))  # ray_tpu/
    jitted = set()
    for base, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(base, f)) as fh:
                    jitted |= set(re.findall(r'named_jit\(\s*"(\w+)"', fh.read()))
    assert jitted == set(STEP_PROGRAM_NAMES), sorted(jitted ^ set(STEP_PROGRAM_NAMES))
