"""Compile the main path's kernels for a TPU v5e that is described, not
attached (guide on-chip-measurement, section 2): Mosaic and the TPU
compiler are installed here, so what they refuse costs no chip time.
Nothing runs — these say "the chip's compiler accepts this program", never
a result or a time.

The topology is described inside a module-scoped fixture (never at
import, in a skipif or in parametrize): only the xdist worker that is
handed this file loads the TPU library, and every worker collects the same
tests. All such tests live in this one file for the same reason. The
persistent compile cache is off around the compiles: an executable built
for a described chip is written to it but cannot be read back here.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


# the training shape of chip_smoke.py phase 3 (8 x 2048 SFT). VMEM is checked by
# the compile itself (Mosaic refuses a kernel that scopes more than the chip
# allows); memory_analysis() below checks what the program takes in HBM
_B, _H, _T, _D = 8, 16, 2048, 128


def test_flash_fwd_compiles_for_v5e(one_chip):
    from ray_tpu.ops.flash_attention import _fwd_pallas

    q = jax.ShapeDtypeStruct((_B, _H, _T, _D), jnp.bfloat16, sharding=one_chip)
    compiled, txt = _compile(lambda q, k, v: _fwd_pallas(q, k, v, True, None), q, q, q)
    assert "tpu_custom_call" in txt
    # outputs + arguments only: the kernel must not need an HBM temp the size of the scores
    assert compiled.memory_analysis().temp_size_in_bytes < _B * _H * _T * _T


@pytest.mark.parametrize("shape, window", [((2, 20, 16384, 256), None), ((2, 16, 12288, 128), 4096), ((4, 16, 4096, 128), None)])
def test_flash_fwd_with_true_lengths_compiles_for_v5e(one_chip, shape, window):
    """The serving prefill's form where a bucket has a tile to skip (PR 52): the lengths prefetched, index
    maps that read them. At GLM's expanded MLA (20 heads x 256 over 16,384), a SmallThinker window layer
    and InternLM2's longdoc bucket."""
    from ray_tpu.ops.flash_attention import _fwd_pallas

    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    n = jax.ShapeDtypeStruct(shape[:1], jnp.int32, sharding=one_chip)
    compiled, txt = _compile(lambda q, k, v, n: _fwd_pallas(q, k, v, True, None, window=window, lengths=n), q, q, q, n)
    assert "tpu_custom_call" in txt and ("window_flash_attention" in txt) == (window is not None)
    assert compiled.memory_analysis().temp_size_in_bytes < shape[0] * shape[1] * shape[2] * 4  # the repeated lengths, no scores


@pytest.mark.parametrize("bucket, learns", [(1024, False), (2048, True)])
def test_llama_prefill_learns_the_lengths_only_where_its_bucket_has_a_tile_to_skip(one_chip, monkeypatch, bucket, learns):
    """The rule is the shape's (``flash_attention._skippable``): at InternLM2's widths (128 columns,
    tiles of 1,024) the 1,024 bucket's prefill lowers for the chip to the SAME text whether the
    kernel may learn the lengths or never does, so chat's one-tile programs are the parent's; the
    2,048 bucket's takes them."""
    from ray_tpu.llm.model_runner import _sds_params, prefill
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.ops import flash_attention as fa

    cfg = LlamaConfig(vocab_size=92544, hidden_size=2048, intermediate_size=8192, num_layers=2, num_heads=16,
                      num_kv_heads=8, max_seq_len=4096, remat=False, attention_impl="pallas")
    args = _on((_sds_params(cfg), jax.ShapeDtypeStruct((4, bucket), jnp.int32), jax.ShapeDtypeStruct((4,), jnp.int32)), one_chip)

    def lowered():
        return jax.jit(partial(prefill, cfg=cfg)).lower(*args).as_text()

    with_the_rule = lowered()
    with monkeypatch.context() as m:
        m.setattr(fa, "_skippable", lambda lengths, *shape: None)  # the parent: no call learns a length
        never = lowered()
    assert "tpu_custom_call" in with_the_rule
    assert (with_the_rule != never) == learns


def _loops(txt: str, under: str) -> list:
    """The compiled ``while`` operations whose name ends under the scope ``under``."""
    import re

    return [line for line in txt.splitlines() if " while(" in line and re.search(rf'op_name="[^"]*/{under}/while"', line)]


def test_llama_prefill_runs_its_mlp_over_live_slabs_and_holds_a_slabs_hidden_rows(one_chip):
    """PR 54: InternLM2-1.8B's 1 x 4,096 prefill, the shape that decides the long-document cell, compiles
    for the chip with the loop over live slabs of 512 positions in its MLP (``ops/layers.live_slabs``), and
    its temporaries are under ONE bucket's hidden activations (4,096 x 8,192 in bfloat16: 64 MiB; the plain
    form's program took 64.5 MiB, this one 16.9): a slab's are 512 x 8,192, and the loop reads the layer's
    matrices where they lie in the stack, so no layer's 96 MiB of them is copied to become its operand."""
    from ray_tpu.llm.model_runner import _sds_params, prefill

    cfg = dataclasses.replace(_internlm2_1_8b(), attention_impl="pallas")
    args = _on((_sds_params(cfg), jax.ShapeDtypeStruct((1, 4096), jnp.int32), jax.ShapeDtypeStruct((1,), jnp.int32)), one_chip)
    compiled, txt = _compile(partial(prefill, cfg=cfg), *args)
    (loop,) = _loops(txt, "mlp")
    assert "bf16[24,2048,8192]" in loop and "bf16[1,2048,8192]" not in loop and "tpu_custom_call" in txt
    assert compiled.memory_analysis().temp_size_in_bytes < 4096 * 8192 * 2


def test_flash_bwd_compiles_for_v5e(one_chip):
    from ray_tpu.ops.flash_attention import _bwd_pallas

    q = jax.ShapeDtypeStruct((_B, _H, _T, _D), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((_B, _H, _T), jnp.float32, sharding=one_chip)
    _, txt = _compile(lambda q, k, v, o, lse, g: _bwd_pallas(q, k, v, o, lse, g, True, None), q, q, q, q, lse, q)
    assert txt.count("tpu_custom_call") >= 2  # the dq kernel and the dk/dv kernel


@pytest.mark.parametrize(
    "T, page, quant",
    [(1, 16, False), (5, 16, False), (1, 128, True), (5, 128, True)],
    ids=["fp_decode_T1", "fp_verify_T5", "int8_decode_T1", "int8_verify_T5"],
)
def test_paged_partials_compile_for_v5e(one_chip, T, page, quant):
    """The paged-attention kernel at nkv/hd/page of the 1B serving shape
    (16 kv heads x 128, 8 lanes, 64 pages per lane), bf16 and int8 pools."""
    from ray_tpu.llm.pallas.paged_attn import kernel_supported, paged_attn_partials

    B, nkv, rep, hd, max_pg = 8, 16, 1, 128, 64
    P = B * max_pg + 1
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    pool = sds((P, page, nkv, hd), jnp.int8 if quant else jnp.bfloat16)
    args = [sds((B, nkv, rep, T, hd), jnp.float32), pool, pool, sds((B, max_pg), jnp.int32), sds((B,), jnp.int32)]
    if quant:
        args += [sds((P, nkv, page), jnp.float32)] * 2
    compiled, txt = _compile(partial(paged_attn_partials, interpret=False), *args)
    assert "tpu_custom_call" in txt
    # the pool streams through the kernel: no relaid-out copy of it in HBM
    assert compiled.memory_analysis().temp_size_in_bytes < pool.size * pool.dtype.itemsize // 8
    # and the engine's gate promises exactly this shape on a TPU
    real = jax.default_backend
    try:
        jax.default_backend = lambda: "tpu"
        assert kernel_supported(page, nkv, hd, quantized=quant) == (True, "")
        assert not kernel_supported(1024, 64, 128)[0]  # past what has been compiled: refused, with a reason
    finally:
        jax.default_backend = real


def test_fused_slot_decode_step_compiles_for_v5e(one_chip):
    """One fused slot decode step (decode -> sample -> append KV) at the
    real widths of the 1B serving shape, depth cut to 2 layers."""
    from ray_tpu.llm.model_runner import _sds_cache, _sds_lanes, _sds_params, fused_step
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632, num_layers=2,
                      num_heads=16, num_kv_heads=16, max_seq_len=2048, remat=False)
    B = 8
    args = _on((_sds_params(cfg), _sds_cache(cfg, B, cfg.max_seq_len)) + _sds_lanes(B), one_chip)
    compiled, _ = _compile(partial(fused_step, cfg=cfg), *args)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 << 30  # fits the chip's HBM


def _internlm2_1_8b():
    """InternLM2-1.8B at its published sizes (benchmark/configs/internlm2-1.8b.json), 4096 positions a slot."""
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig(vocab_size=92544, hidden_size=2048, intermediate_size=8192, num_layers=24, num_heads=16,
                       num_kv_heads=8, head_dim=128, max_seq_len=4096, rope_theta=1e6, remat=False)


@pytest.mark.parametrize("slots", [12, 16])
@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
def test_fused_slot_decode_step_updates_its_cache_in_place_at_internlm2_sizes(one_chip, cache_dtype, slots):
    """PR 30: the cache rides the layer loop's carry, so the compiler aliases every leaf to the
    donated input and keeps no copy of it. InternLM2-1.8B at its published sizes (the benchmark's
    `internlm2-1.8b` cells run 12 x 4096): with the cache as the scan's xs and ys the same program
    held a second cache as temporaries, 6.25 GiB for 6.0 GiB, and 16 x 4096 was refused at 15.77
    of 15.75 GiB (PERF.md sections 4 and 6)."""
    from ray_tpu.llm.model_runner import _sds_cache, _sds_cache_q, _sds_lanes, _sds_params, fused_step

    cfg = _internlm2_1_8b()
    cache = (_sds_cache_q if cache_dtype == "int8" else _sds_cache)(cfg, slots, cfg.max_seq_len)
    args = _on((_sds_params(cfg), cache) + _sds_lanes(slots), one_chip)
    step = jax.jit(partial(fused_step, cfg=cfg), donate_argnums=(1, 3, 4, 5, 6))  # make_fused_fns' donation set
    mem = step.lower(*args).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert mem.temp_size_in_bytes < 0.5 * 2**30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30


@pytest.mark.parametrize("program", ["extend", "spec_verify"])
def test_extend_and_verify_update_the_slot_cache_in_place_at_internlm2_sizes(one_chip, program):
    """The two other programs whose layer loop carries the slot cache (PR 30), 12 x 4096 in bf16:
    the speculative verify step held a second cache as the fused step did (4.74 GiB of
    temporaries), and a chunk written by dynamic_update_slice at a traced (layer, slot, start)
    makes this compiler copy the whole carried cache (4.50 GiB) where a scatter by position is
    done in place (0.13 GiB)."""
    from ray_tpu.llm.model_runner import _sds, _sds_cache, _sds_lanes, _sds_params, extend
    from ray_tpu.llm.spec.verify import spec_verify_slots

    cfg = _internlm2_1_8b()
    B, k, cache = 12, 4, _sds_cache(cfg, 12, 4096)
    if program == "extend":
        args = (_sds_params(cfg), cache, _sds((), jnp.int32), _sds((512,), jnp.int32), _sds((), jnp.int32))
        step = jax.jit(partial(extend, cfg=cfg), donate_argnums=(1,))
    else:
        lanes = _sds_lanes(B)
        args = (_sds_params(cfg), cache, _sds((B, k), jnp.int32), *lanes, _sds((B,), jnp.int32),
                _sds((B, 517), jnp.int32), _sds((B,), jnp.int32))
        step = jax.jit(partial(spec_verify_slots, cfg=cfg), donate_argnums=(1, 3, 4, 5, 6, 7, 8, 9, 10))
    mem = step.lower(*_on(args, one_chip)).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert mem.temp_size_in_bytes < 0.5 * 2**30


def _vocabulary_wide_selections(hlo_text: str, vocab: int) -> list[str]:
    """Instructions of an HLO module that order or select over an array with an axis of ``vocab``
    entries: a sort, or a top-k however it is spelled (exact or approximate, instruction or custom
    call). The text names operands without their shapes, so each is looked up where it is defined."""
    import re

    shaped = re.compile(r"\[(?:\d+,)*%d(?:,\d+)*\]" % vocab)
    call = re.compile(r"\b(sort|topk|top-k|custom-call)\(([^)]*)\)", re.IGNORECASE)
    lines = hlo_text.splitlines()
    defined = {m.group(1): ln for ln in lines if (m := re.match(r"\s*(?:ROOT )?(\S+) = ", ln))}
    found = []
    for ln in lines:
        m = call.search(ln)
        if m is None or (m.group(1) == "custom-call" and not re.search(r'custom_call_target="[^"]*(?:TopK|Sort)', ln, re.IGNORECASE)):
            continue
        operands = [defined.get(name.lstrip("%"), "") for name in re.findall(r"%?[\w.\-]+", m.group(2))]
        if any(shaped.search(text) for text in [ln, *operands]):
            found.append(ln.strip()[:160])
    return found


@pytest.mark.parametrize("program", ["llm_fused_step", "llm_hybrid_fused_step", "llm_fused_paged_step", "llm_verify_step"])
def test_no_step_program_sorts_the_vocabulary(one_chip, program):
    """PR 32: the sampler finds its top-k and top-p thresholds in counting passes, so no step
    program holds a sort (or a top-k) whose operand has the vocabulary as an axis, on either side
    of its conditionals: the text is the lowered module's, before the compiler drops anything. On
    the parent each of these held three (`sort.2`, `sort`, `sort.11`: 12 of chat's 27 ms a step).
    The hybrid router's choice of 6 among 128 experts is not the vocabulary and stays."""
    from ray_tpu.llm.model_runner import _sds, _sds_cache, _sds_lanes, _sds_params, _sds_pool, fused_step, paged_fused_step
    from ray_tpu.llm.spec.verify import spec_verify_slots

    cfg, B = _internlm2_1_8b(), 12
    if program == "llm_fused_step":
        fn, args = partial(fused_step, cfg=cfg), (_sds_params(cfg), _sds_cache(cfg, B, 4096)) + _sds_lanes(B)
    elif program == "llm_fused_paged_step":
        page, max_pg = 16, 4096 // 16
        fn = partial(paged_fused_step, cfg=cfg)
        args = (_sds_params(cfg), _sds_pool(cfg, B * max_pg + 1, page), _sds((B, max_pg), jnp.int32), _sds((B,), jnp.int32)) + _sds_lanes(B)
    elif program == "llm_verify_step":
        fn = partial(spec_verify_slots, cfg=cfg)
        args = (_sds_params(cfg), _sds_cache(cfg, B, 4096), _sds((B, 4), jnp.int32), *_sds_lanes(B), _sds((B,), jnp.int32),
                _sds((B, 517), jnp.int32), _sds((B,), jnp.int32))
    else:
        from ray_tpu.llm import hybrid_runner as hr

        cfg, params, cache, state = _cell_at_its_size(one_chip, "nemotron")
        fn, B = partial(hr.fused_step, cfg=cfg), 32
        args = (params, cache, state, *_sds_lanes(B), _sds((B,), jnp.bool_))
    txt = jax.jit(fn).lower(*_on(args, one_chip)).compiler_ir(dialect="hlo").as_hlo_text()
    assert _vocabulary_wide_selections(txt, cfg.vocab_size) == []
    assert "conditional(" in txt, "each filter sits under a conditional on the whole batch"
    if program == "llm_hybrid_fused_step":
        assert _vocabulary_wide_selections(txt, cfg.n_routed_experts), "the reader sees the router's selection over 128 experts"


# ---------------------------------------------------------------------------
# four chips: GSPMD cannot partition a Mosaic kernel on its own, so the flash
# kernel must sit under shard_map wherever a program spans several devices
# ---------------------------------------------------------------------------
def _sharded(tree, mesh, specs):
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, sp)), tree, specs
    )


@pytest.mark.parametrize("bucket", [512, 2048])
def test_tp4_prefill_with_flash_kernel_compiles_for_v5e(topo, bucket):
    """The engine's prefill, SPMD over a tp=4 mesh, with the Pallas flash
    kernel selected as it is on a TPU (heads over tp under shard_map): a
    bucket of one query tile, and one of two, whose call takes the rows' true
    lengths into the shard_map beside q, k and v (replicated: no batch axis)."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.llm.model_runner import _param_pspecs, _sds_params, prefill
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel.mesh import create_mesh

    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632, num_layers=2, num_heads=16,
                      num_kv_heads=16, max_seq_len=2048, remat=False, attention_impl="pallas")
    mesh = create_mesh(tp=4, devices=topo.devices)
    params = _sharded(_sds_params(cfg), mesh, _param_pspecs(cfg, mesh))
    toks, lens = _sharded((jax.ShapeDtypeStruct((4, bucket), jnp.int32), jax.ShapeDtypeStruct((4,), jnp.int32)),
                          mesh, (P(), P()))
    _, txt = _compile(partial(prefill, cfg=cfg, mesh=mesh), params, toks, lens)
    assert "tpu_custom_call" in txt and "all-reduce" in txt


def test_tp4_fused_slot_decode_step_updates_its_cache_in_place(topo):
    """The fused step as a shard_map body over tp=4 (no cell runs it yet): Mistral-7B-v0.3 at its
    published sizes and full depth, 16 x 4096 (benchmark/configs/mistral-7b-v0.3-tp4.json). A chip's
    2.0 GiB of cache are aliased and its temporaries are 0.003 GiB, where they were 2.07 GiB with the
    cache as the layer scan's xs and ys (PR 30)."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.llm.model_runner import (
        _cache_pspecs, _param_pspecs, _sds_cache, _sds_lanes, _sds_params, _sharded_fused_slots,
    )
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel.mesh import create_mesh

    cfg = LlamaConfig(vocab_size=32768, hidden_size=4096, intermediate_size=14336, num_layers=32, num_heads=32,
                      num_kv_heads=8, head_dim=128, max_seq_len=4096, rope_theta=1e6, remat=False)
    mesh, slots = create_mesh(tp=4, devices=topo.devices), 16
    cache = _sds_cache(cfg, slots, cfg.max_seq_len)
    args = (_sharded(_sds_params(cfg), mesh, _param_pspecs(cfg, mesh)), _sharded(cache, mesh, _cache_pspecs("slots", False)),
            *_sharded(_sds_lanes(slots), mesh, (P(),) * 5))
    step = jax.jit(_sharded_fused_slots(cfg, mesh, "fp", False), donate_argnums=(1, 3, 4, 5, 6))
    mem = step.lower(*args).compile().memory_analysis()  # bytes on each device
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache)) // 4
    assert mem.temp_size_in_bytes < 0.25 * 2**30


def test_fsdp4_loss_and_grad_with_flash_kernel_compile_for_v5e(topo):
    """Forward and backward of the train step's loss over an fsdp=4 mesh
    (batch over fsdp under shard_map), widths of the SFT shape, depth 2."""
    from jax.sharding import NamedSharding

    from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn, param_logical_axes
    from ray_tpu.parallel.mesh import DEFAULT_RULES, create_mesh, shard_batch_spec

    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632, num_layers=2, num_heads=16,
                      num_kv_heads=8, max_seq_len=2048, attention_impl="pallas")
    mesh = create_mesh(fsdp=4, devices=topo.devices)
    shapes = jax.eval_shape(partial(init_params, cfg), jax.random.PRNGKey(0))
    shardings = DEFAULT_RULES.tree_shardings(param_logical_axes(cfg), mesh)
    params = jax.tree.map(lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h), shapes, shardings)
    tok = jax.ShapeDtypeStruct((8, 2048), jnp.int32, sharding=NamedSharding(mesh, shard_batch_spec(mesh)))
    _, txt = _compile(jax.value_and_grad(partial(loss_fn, config=cfg, mesh=mesh)), params, {"tokens": tok, "targets": tok})
    assert txt.count("tpu_custom_call") >= 3  # forward, dq and dk/dv kernels


# the hybrid cells' configuration files (benchmark/configs/) and what the harness passes their families beside them
CELLS = {"nemotron": ("nemotron-3-nano-30b-a3b-ep2.json", {}),  # published widths, 16 layers, 64 of 128 experts, 32 slots x 4096
         "qwen3_next": ("qwen3-next-80b-a3b-ep4.json", {"remat": False}),  # 12 of 48 layers, 128 of 512 experts, 16 slots x 4096
         "glm": ("glm-4.7-flash-d8.json", {"remat": False}),  # 8 of 47 layers whole, 16 slots x 16,384
         "kimi": ("kimi-linear-48b-a3b-ep4.json", {"remat": False}),  # 9 of 27 layers, 64 of 256 experts, 16 slots x 4096
         "sala": ("minicpm-sala-9b-d8.json", {"remat": False}),  # layers 9-16 of 32, the whole vocabulary, 16 slots x 12,288
         "smallthinker": ("smallthinker-21b-a3b-d8.json", {"remat": False}),  # layers 0-7 of 52, every expert, the whole vocabulary, 16 slots x 12,288
         "lfm2": ("lfm2-24b-a2b-d10.json", {"remat": False}),  # layers 0-9 of 40, every expert, the whole vocabulary, 16 slots x 12,288
         "keye": ("keye-vl-2.0-30b-a3b-d6.json", {"remat": False}),  # layers 24-29 of 48, every expert, the whole vocabulary, 12 slots x 24,576
         "jamba": ("jamba2-3b.json", {"remat": False}),  # the published model whole: 28 layers, the whole vocabulary, 16 slots x 12,288
         "trinity": ("trinity-large-preview-ep8-d5.json", {"remat": False})}  # a dense layer and one period of four, 32 of 256 experts, an eighth of the vocabulary, 16 slots x 12,288


def _cell_at_its_size(one_chip, cell):
    """``(cfg, params, cache, state)`` of a hybrid cell as shapes on the chip: the cell's own
    configuration file, its slots and horizon, the slot cache allocated from the description's
    per-position entries as the engine allocates it."""
    import json
    import os

    from benchmark import common
    from ray_tpu.llm import kv_cache as kvc
    from ray_tpu.llm import state_cache

    config, program_kw = CELLS[cell]
    with open(os.path.join(common.HERE, "configs", config)) as f:
        c = json.load(f)
    slots, S = c["serving"]["max_num_seqs"], c["serving"]["max_seq_len"]
    # off the TPU "auto" picks the XLA attention; the chip runs the flash kernel
    cfg = common.load_family(c["family"]).program_config(c, S, attention_impl="pallas", **program_kw)
    params = _on(jax.eval_shape(lambda: cfg.init_params(jax.random.PRNGKey(0))), one_chip)
    cache = _on(jax.eval_shape(lambda: kvc.alloc_entries(cfg.position_entries(), slots, S, cfg.ring_entries())), one_chip)
    return cfg, params, cache, _on(jax.eval_shape(lambda: state_cache.alloc(cfg, slots)), one_chip)


def test_hybrid_fused_step_fits_one_v5e_and_updates_its_caches_in_place(one_chip):
    """PR 29: 9.84 GiB of weights, 0.25 GiB of KV rows and 0.45 GiB of recurrent state in one
    decode program. Both caches ride the layer loop's carry: the compiler must alias them to the
    donated inputs and need no temporary of their size (the Llama slot step holds a second copy
    of its cache, PERF.md section 7), and must not copy a layer's experts out of the stack."""
    from ray_tpu.llm import hybrid_runner as hr

    cfg, params, cache, state = _cell_at_its_size(one_chip, "nemotron")
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    lanes = (s((32,), jnp.int32), s((32, 2), jnp.uint32), s((32,), jnp.float32), s((32,), jnp.int32), s((32,), jnp.float32))
    step = jax.jit(partial(hr.fused_step, cfg=cfg), donate_argnums=(1, 2, 4, 5, 6, 7))
    mem = step.lower(params, cache, state, *lanes, s((32,), jnp.bool_)).compile().memory_analysis()
    caches = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves((cache, state)))
    assert 10.4 * 2**30 < mem.argument_size_in_bytes < 10.7 * 2**30 and 0.69 * 2**30 < caches < 0.71 * 2**30
    assert mem.alias_size_in_bytes >= caches
    assert mem.temp_size_in_bytes < 0.1 * 2**30


def test_hybrid_prefill_fits_beside_weights_and_caches_on_one_v5e(one_chip):
    """The largest prefill the cell warms (4 x 2048, 49,152 routed pairs through the grouped
    matmul) beside 10.54 GiB of weights and caches: under 15.75 GiB, with the flash kernel."""
    from ray_tpu.llm import hybrid_runner as hr

    cfg, params, _, _ = _cell_at_its_size(one_chip, "nemotron")
    tokens = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    compiled, txt = _compile(partial(hr.prefill, cfg=cfg), params, tokens, lengths)
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" in txt
    assert mem.temp_size_in_bytes < 2.0 * 2**30  # no copy of the experts (4.3 GiB), no layer's worth of them (0.6 GiB x 4)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes + 0.70 * 2**30 < 15.0 * 2**30


# ---------------------------------------------------------------------------
# a second description over the same step programs: the cell qwen3-next-ep4.longdoc
# ---------------------------------------------------------------------------
def test_qwen3_next_fused_step_fits_one_v5e_and_updates_both_caches_in_place(one_chip):
    """PR 34: 10.10 GiB of weights, 0.375 GiB of KV rows (3 layers of heads 256 wide) and 0.29 GiB
    of recurrent state (9 layers of 32 x 128 x 128 float32 a slot) in one decode program, through
    the SAME ``hybrid_runner.fused_step`` and layer loop as the Nemotron-H step above: both caches
    aliased to the donated inputs, and temporaries under ONE layer's rows (128 MiB of K and V)."""
    from ray_tpu.llm import hybrid_runner as hr

    cfg, params, cache, state = _cell_at_its_size(one_chip, "qwen3_next")
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    lanes = (s((16,), jnp.int32), s((16, 2), jnp.uint32), s((16,), jnp.float32), s((16,), jnp.int32), s((16,), jnp.float32))
    step = jax.jit(partial(hr.fused_step, cfg=cfg), donate_argnums=(1, 2, 4, 5, 6, 7))
    mem = step.lower(params, cache, state, *lanes, s((16,), jnp.bool_)).compile().memory_analysis()
    caches = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves((cache, state)))
    one_layers_rows = 2 * 16 * 4096 * cfg.num_kv_heads * cfg.hd * 2
    assert 10.7 * 2**30 < mem.argument_size_in_bytes < 10.85 * 2**30 and 0.65 * 2**30 < caches < 0.68 * 2**30
    assert mem.alias_size_in_bytes >= caches
    assert mem.temp_size_in_bytes < one_layers_rows == 128 * 2**20


def _no_row_for_every_pair(txt, cfg, positions):
    """The expert layer's placement follows the pairs held HERE (PR 47): the compiled prefill holds
    no array with a row of the residual width for every (token, choice) pair the router made
    (``bf16[40960,2048]`` at Qwen3-Next's 4096 bucket, ``bf16[32768,2304]`` at Kimi's), and none
    with the k choices of a token on the sublanes (``[4096,10,2048]``, ``[4096,8,2304]``: the copy
    that the sum over k used to read)."""
    from ray_tpu.models import experts

    N, k, H = min(positions, experts.SLAB_ROWS), cfg.expert_layer.top_k, cfg.hidden_size
    assert f"[{N * k},{H}]" not in txt and f"[{N},{k},{H}]" not in txt and f"[{k},{N},{H}]" not in txt


def _blocks_by_the_kernel(txt, cfg, kernel: bool):
    """PRs 56 and 57: where a small expert expects less than two blocks' rows of a call, the compiled prefill
    holds the blocks' kernel by name under ``moe.blocks``, reads the experts' matrices where they lie (no copy
    of a layer's experts is among its operands: the caller bounds the temporaries) and no block of it is 256
    rows tall; where an expert expects two blocks' rows the loop's tall blocks stand as they stood."""
    ran = [line for line in txt.splitlines() if "custom-call(" in line and "grouped_experts" in line]
    assert bool(ran) == kernel and all("tpu_custom_call" in line and "moe.blocks" in line for line in ran)
    assert (f"bf16[256,{cfg.hidden_size}]" in txt) != kernel


@pytest.mark.parametrize("prompts, most_gib", [(1, 0.45), (8, 2.85)])
def test_qwen3_next_prefill_of_the_4096_bucket_fits_beside_weights_and_caches_on_one_v5e(one_chip, as_on_a_tpu, prompts, most_gib):
    """The 4096-bucket prefill (the delta rule with one gate a head as ONE kernel under ``gdn.chunk``,
    PR 46, a few sequences at a time; the flash kernel at heads 256 wide; the grouped matmul over 128
    small experts in slabs, its blocks run by the kernel of ``ops/grouped_experts.py`` and none of them
    256 rows tall, PR 57) for one prompt (0.443 GiB of temporaries as compiled for PRs 56 and 57, whose kernel
    is handed every row's weight; 0.397 for PR 47, whose expert layer lays out the pairs held here;
    0.615 for PR 46; 0.65 for PR 34, with the XLA lines)
    and for the largest group the cell warms, 8 x 4096 (2.73 GiB, where the rule's groups set the
    peak; 3.23 for PR 34; and 0.33 GiB of output), beside 10.10 GiB of weights and 0.66 GiB of
    caches: under 15.75 GiB. No ``[.., 64, 64]`` float32 square of a chunk's pairs is left among the
    program's arrays, no line of the XLA form's scan, and neither the gather of EVERY (token,
    choice) pair's row out of the blocks nor its copy padded for the sum over k."""
    import re

    from ray_tpu.llm import hybrid_runner as hr

    cfg, params, _, _ = _cell_at_its_size(one_chip, "qwen3_next")
    tokens = jax.ShapeDtypeStruct((prompts, 4096), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((prompts,), jnp.int32, sharding=one_chip)
    compiled, txt = _compile(partial(hr.prefill, cfg=cfg), params, tokens, lengths)
    mem = compiled.memory_analysis()
    print("qwen3-next prefill:", prompts, mem.argument_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**30, mem.output_size_in_bytes / 2**30)
    assert "tpu_custom_call" in txt, "the flash kernel, 256 wide"
    rule = [line for line in txt.splitlines() if "custom-call(" in line and "delta_rule_by_head" in line]
    assert rule and all("tpu_custom_call" in line and "gdn.chunk" in line for line in rule), "the rule's kernel, under its scope"
    assert "gdn.scan" not in txt and not re.search(r"f32\[[0-9,]*64,64\]", txt)
    _no_row_for_every_pair(txt, cfg, prompts * 4096)
    _blocks_by_the_kernel(txt, cfg, kernel=True)  # 80 rows an expert at 4,096 rows, 160 in a slab of 8,192
    assert mem.temp_size_in_bytes < most_gib * 2**30  # no copy of the experts (4.5 GiB), no layer's worth of them (0.375 GiB x 12)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes + 0.67 * 2**30 < 15.0 * 2**30


# ---------------------------------------------------------------------------
# PR 35: the slot decode step's attention as a kernel over the stacked cache
# (ops/slot_attention.py). ``refusal`` asks jax.default_backend(), which says
# "cpu" here: the tests answer for the chip, as the guide says a test may
# ---------------------------------------------------------------------------
@pytest.fixture
def as_on_a_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _kv_bytes(cache):
    return sum(a.size * a.dtype.itemsize for n, a in cache.items() if n != "length")


@pytest.fixture(scope="module")
def fused_step_for_the_chip(one_chip):
    """``cell -> (cfg, params, cache, state, compiled)``: a hybrid cell's fused step at the cell's
    own size, with the forms the chip runs (the caller holds ``as_on_a_tpu``) and its caches
    donated as the engine donates them, compiled ONCE for the tests that read it."""
    from ray_tpu.llm import hybrid_runner as hr

    memo = {}

    def compiled_for(cell):
        assert jax.default_backend() == "tpu", "the gates are asked as on the chip"
        if cell not in memo:
            cfg, params, cache, state = _cell_at_its_size(one_chip, cell)
            s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
            n = cache["length"].shape[0]
            lanes = (s((n,), jnp.int32), s((n, 2), jnp.uint32), s((n,), jnp.float32), s((n,), jnp.int32), s((n,), jnp.float32))
            step = jax.jit(partial(hr.fused_step, cfg=cfg), donate_argnums=(1, 2, 4, 5, 6, 7))
            memo[cell] = (cfg, params, cache, state, step.lower(params, cache, state, *lanes, s((n,), jnp.bool_)).compile())
        return memo[cell]

    return compiled_for


@pytest.mark.parametrize("kv, hd, nh, slots, layers", [(8, 128, 16, 16, 24), (2, 128, 32, 32, 2)],
                         ids=["internlm2_kv8_hd128", "nemotron_kv2_hd128"])
def test_slot_attention_kernel_compiles_for_v5e_and_copies_nothing(one_chip, as_on_a_tpu, kv, hd, nh, slots, layers):
    """The tiles the gate lets through, at the cells' sizes: a Mosaic kernel, the stack read where
    it lies (seen as [L, slots, S*kv, hd] by a bitcast), no temporary of any size to speak of."""
    from ray_tpu.ops import slot_attention as sa

    assert sa.refusal(jnp.bfloat16, nh, kv, hd, 4096) is None
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    stack = sds((layers, slots, 4096, kv, hd), jnp.bfloat16)
    compiled, txt = _compile(sa.attend_kernel, sds((slots, nh, hd), jnp.bfloat16), stack, stack, sds((), jnp.int32), sds((slots,), jnp.int32))
    assert "tpu_custom_call" in txt
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_slot_attention_gate_refuses_qwen3_nexts_tile_for_the_copy_it_would_force(one_chip, as_on_a_tpu):
    """2 kv heads x 256: the compiler lays a position's heads out in (2, 128) tiles, and seeing them
    as rows costs a copy of K and V (402 MB here). The gate says so and the XLA form stays."""
    from ray_tpu.ops import slot_attention as sa

    assert "copy of the whole cache" in sa.refusal(jnp.bfloat16, 16, 2, 256, 4096)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    stack = sds((3, 16, 4096, 2, 256), jnp.bfloat16)
    compiled, _ = _compile(sa.attend_kernel, sds((16, 16, 256), jnp.bfloat16), stack, stack, sds((), jnp.int32), sds((16,), jnp.int32))
    assert compiled.memory_analysis().temp_size_in_bytes >= 2 * stack.size * 2


@pytest.mark.parametrize("slots", [16, 24])
def test_fused_slot_decode_step_holds_no_layers_rows_at_internlm2_sizes(one_chip, as_on_a_tpu, slots):
    """PR 35: attention reads the stacked cache through the kernel, so the step holds no
    ``[1, slots, 4096, 8, 128]`` slice of a layer (the 3.74 ms x 2 of every step at 14 slots, an HBM
    temporary from 15 slots on: PERF.md section 6), whatever the slot count: the whole cache aliased,
    temporaries far under ONE layer's K rows (128 MiB at 16 slots), 13.4 GiB of arguments at 24."""
    import re

    from ray_tpu.llm.model_runner import _sds_cache, _sds_lanes, _sds_params, fused_step

    cfg = _internlm2_1_8b()
    cache = _sds_cache(cfg, slots, cfg.max_seq_len)
    args = _on((_sds_params(cfg), cache) + _sds_lanes(slots), one_chip)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)
    step = jax.jit(partial(fused_step, cfg=cfg), donate_argnums=(1, 3, 4, 5, 6))
    compiled = step.lower(*args, live=live).compile()
    mem, txt = compiled.memory_analysis(), compiled.as_text()
    assert "tpu_custom_call" in txt and not re.search(r"bf16\[1,%d,4096,8,128\]" % slots, txt)
    assert mem.alias_size_in_bytes >= _kv_bytes(cache)
    assert mem.temp_size_in_bytes < 32 * 2**20 < slots * 4096 * 8 * 128 * 2
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30


def test_nemotron_fused_step_holds_no_layers_rows_at_its_cells_size(fused_step_for_the_chip, as_on_a_tpu):
    """The hybrid's two attention layers through the same op (``hybrid.attend_slot`` hands it the
    stacked leaf and the layer's index): a kernel in the step, no ``[1, 32, 4096, 2, 128]`` slice,
    both caches still aliased."""
    import re

    _, _, cache, state, compiled = fused_step_for_the_chip("nemotron")
    mem, txt = compiled.memory_analysis(), compiled.as_text()
    assert "slot_decode_attention" in txt and not re.search(r"bf16\[1,32,4096,2,128\]", txt)
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize for a in jax.tree.leaves((cache, state)))
    assert mem.temp_size_in_bytes < 0.1 * 2**30


def test_qwen3_next_fused_step_keeps_the_xla_form(fused_step_for_the_chip, as_on_a_tpu):
    """The gate's refusal at work: no kernel in the step of the tile it refused."""
    assert "slot_decode_attention" not in fused_step_for_the_chip("qwen3_next")[-1].as_text()


# ---------------------------------------------------------------------------
# PR 36: a third description, latent attention (models/glm4_moe_lite.py): the cell
# glm-4.7-flash-d8.longdoc-16k. The slot cache holds a latent row and one rotated key a
# position; the decode step attends on them where they lie (ops/slot_attention.attend_latent)
# ---------------------------------------------------------------------------
def test_latent_attention_kernel_compiles_for_v5e_and_copies_nothing(one_chip, as_on_a_tpu):
    """The latent tile passes the gate (and PR 35's tiles still do): a Mosaic kernel under its own
    name, the latent rows and the rotated keys read where they lie, no temporary to speak of."""
    from ray_tpu.ops import slot_attention as sa

    assert sa.refusal(jnp.bfloat16, 20, 1, 640, 16384, value_dim=512) is None
    assert sa.refusal(jnp.bfloat16, 16, 8, 128, 4096) is None and sa.refusal(jnp.bfloat16, 32, 2, 128, 4096) is None
    assert "copy of the whole cache" in sa.refusal(jnp.bfloat16, 16, 2, 256, 4096)
    assert "copy of its whole stack" in sa.refusal(jnp.bfloat16, 20, 1, 576, 16384, value_dim=512)
    assert "query heads" in sa.refusal(jnp.bfloat16, 40, 1, 640, 16384, value_dim=512)
    assert "int8" in sa.refusal(jnp.bfloat16, 20, 1, 640, 16384, value_dim=512, quantized=True)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    c_stack, r_stack = sds((8, 16, 16384, 512), jnp.bfloat16), sds((8, 16, 16384, 128), jnp.bfloat16)
    compiled, txt = _compile(partial(sa.attend_latent_kernel, scale=1 / 16), sds((16, 20, 512), jnp.bfloat16), sds((16, 20, 128), jnp.bfloat16),
                             c_stack, r_stack, sds((), jnp.int32), sds((16,), jnp.int32))
    assert "tpu_custom_call" in txt and "latent_decode_attention" in txt
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_glm_fused_step_reads_the_latent_rows_where_they_lie(fused_step_for_the_chip, as_on_a_tpu):
    """The fused step at 16 x 16,384 through the SAME ``hybrid_runner.fused_step`` and layer loop as
    the two hybrids (head ``mla ffn``, then ``mla moe`` x 7 scanned): the whole latent cache aliased
    to the donated input, under 32 MiB of temporaries, and no slice of a layer's rows (256 MiB of
    latents at 16 x 16,384) in the compiled text."""
    import re

    cfg, _, cache, state, compiled = fused_step_for_the_chip("glm")
    assert state == {} and cfg.layer_plan == (("mla", "moe"), 7, (), ("mla", "ffn"))
    mem, txt = compiled.memory_analysis(), compiled.as_text()
    assert "latent_decode_attention" in txt and not re.search(r"bf16\[1,16,16384,(512|128|640)\]", txt)
    assert _kv_bytes(cache) == 16 * 16384 * 10240 and mem.alias_size_in_bytes >= _kv_bytes(cache)
    assert mem.temp_size_in_bytes < 32 * 2**20
    print("glm fused step:", mem.argument_size_in_bytes / 2**30, mem.alias_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**20)


@pytest.mark.parametrize("prompts", [1, 2])
def test_glm_prefill_of_the_16384_bucket_fits_beside_weights_and_cache_on_one_v5e(one_chip, prompts):
    """The 16,384-bucket prefill in the EXPANDED form (the flash kernel at 20 heads, keys and values
    256 wide; the dense layer and the grouped matmul in slabs of 8,192 rows) for one prompt and for
    the largest group the cell warms, beside 9.62 GiB of weights and the cache: under 15.75 GiB."""
    from ray_tpu.llm import hybrid_runner as hr

    cfg, params, cache, _ = _cell_at_its_size(one_chip, "glm")
    tokens = jax.ShapeDtypeStruct((prompts, 16384), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((prompts,), jnp.int32, sharding=one_chip)
    compiled, txt = _compile(partial(hr.prefill, cfg=cfg), params, tokens, lengths)
    mem = compiled.memory_analysis()
    print("glm prefill:", prompts, mem.argument_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**30, mem.output_size_in_bytes / 2**30)
    assert "tpu_custom_call" in txt, "the flash kernel, 20 heads of 256"
    cache_on_chip = _compile(lambda c: c, cache)[0].memory_analysis().argument_size_in_bytes
    print("cache on chip:", cache_on_chip / 2**30)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes + cache_on_chip < 15.75 * 2**30


# ---------------------------------------------------------------------------
# PR 42: a fourth description, Kimi Linear (models/kimi_linear.py): the cell kimi-linear-ep4.longdoc.
# A state cache (7 layers of Kimi Delta Attention) BESIDE a latent slot cache (2 layers), the latent
# kernel at 32 heads, the chunked delta rule with a gate by key channel in the prefill
# ---------------------------------------------------------------------------
def test_latent_attention_kernel_at_32_heads_compiles_for_v5e_and_the_gate_lets_kimis_tile_through(one_chip, as_on_a_tpu):
    """32 query heads (two whole bfloat16 tiles, where GLM's 20 are padded to them) on rows of 512 + 128,
    two latent layers of 16 x 4,096 positions: the gate lets the tile through, a Mosaic kernel under
    the same name, nothing copied."""
    from ray_tpu.models.kimi_linear import KimiLinearConfig
    from ray_tpu.ops import slot_attention as sa

    tile = KimiLinearConfig().slot_attention_tile
    assert tile == dict(num_heads=32, num_kv_heads=1, head_dim=640, value_dim=512) and sa.refusal(jnp.bfloat16, **tile, S=4096) is None
    assert "compiled at 20 and 32" in sa.refusal(jnp.bfloat16, **{**tile, "num_heads": 48}, S=4096)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    c_stack, r_stack = sds((2, 16, 4096, 512), jnp.bfloat16), sds((2, 16, 4096, 128), jnp.bfloat16)
    compiled, txt = _compile(partial(sa.attend_latent_kernel, scale=192 ** -0.5), sds((16, 32, 512), jnp.bfloat16), sds((16, 32, 128), jnp.bfloat16),
                             c_stack, r_stack, sds((), jnp.int32), sds((16,), jnp.int32))
    assert "tpu_custom_call" in txt and "latent_decode_attention" in txt
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_kimi_fused_step_fits_one_v5e_aliases_both_caches_and_slices_no_layers_rows(fused_step_for_the_chip, as_on_a_tpu):
    """The fused step at 16 x 4,096 through the SAME ``hybrid_runner.fused_step`` and layer loop as the
    three other descriptions (head ``kda ffn``, then ``kda moe kda moe mla moe kda moe`` x 2 scanned):
    7.96 GiB of weights, 0.16 GiB of latent rows and 0.23 GiB of state; both caches aliased to the
    donated inputs, under 32 MiB of temporaries, the latent kernel in the step and no slice of a
    layer's rows (64 MiB of latents at 16 x 4,096) in the compiled text."""
    import re

    cfg, _, cache, state, compiled = fused_step_for_the_chip("kimi")
    assert cfg.layer_plan == (("kda", "moe", "kda", "moe", "mla", "moe", "kda", "moe"), 2, (), ("kda", "ffn"))
    assert set(state) == {"S", "conv"} and set(cache) == {"c_kv", "k_r", "length"}
    mem, txt = compiled.memory_analysis(), compiled.as_text()
    caches = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves((cache, state)))
    assert _kv_bytes(cache) == 16 * 4096 * 2560 and caches - _kv_bytes(cache) - 64 == 16 * 15_196_160
    assert 8.3 * 2**30 < mem.argument_size_in_bytes < 8.4 * 2**30 and mem.alias_size_in_bytes >= caches
    assert "latent_decode_attention" in txt and not re.search(r"bf16\[1,16,4096,(512|128|640)\]", txt)
    assert mem.temp_size_in_bytes < 32 * 2**20
    print("kimi fused step:", mem.argument_size_in_bytes / 2**30, mem.alias_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**20)


@pytest.mark.parametrize("prompts, most_gib", [(1, 0.56), (8, 2.8)])
def test_kimi_prefill_of_the_4096_bucket_fits_beside_weights_and_caches_on_one_v5e(one_chip, as_on_a_tpu, prompts, most_gib):
    """The 4096-bucket prefill (the delta rule with its gate by channel as ONE kernel under
    ``kda.chunk``, PR 44, a few sequences at a time; the flash kernel at 32 heads, keys and values
    padded to 256; the grouped matmul over 64 experts in slabs) for one prompt (0.52 GiB of
    temporaries as compiled for PR 44, under PR 42's 0.70; 0.514 for PR 47: the rule's operands set
    the peak, not the expert layer) and for the largest group the cell warms, 8 x 4096 (2.67 GiB,
    under PR 42's 2.93, and 0.19 GiB of output), beside 7.96 GiB of weights and 0.38 GiB of caches:
    under 15.75 GiB. No ``[.., 64, 64]`` float32 square of a chunk's pairs is left among the
    program's arrays, no line of the XLA form's scan, and neither the gather of EVERY (token,
    choice) pair's row out of the blocks nor its copy padded for the sum over k (PR 47)."""
    import re

    from ray_tpu.llm import hybrid_runner as hr

    cfg, params, _, _ = _cell_at_its_size(one_chip, "kimi")
    tokens = jax.ShapeDtypeStruct((prompts, 4096), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((prompts,), jnp.int32, sharding=one_chip)
    compiled, txt = _compile(partial(hr.prefill, cfg=cfg), params, tokens, lengths)
    mem = compiled.memory_analysis()
    print("kimi prefill:", prompts, mem.argument_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**30, mem.output_size_in_bytes / 2**30)
    assert "tpu_custom_call" in txt, "the flash kernel, 32 heads padded to 256"
    rule = [line for line in txt.splitlines() if "custom-call(" in line and "delta_rule_by_channel" in line]
    assert rule and all("tpu_custom_call" in line and "kda.chunk" in line for line in rule), "the rule's kernel, under its scope"
    assert "kda.scan" not in txt and not re.search(r"f32\[[0-9,]*64,64\]", txt)
    _no_row_for_every_pair(txt, cfg, prompts * 4096)
    _blocks_by_the_kernel(txt, cfg, kernel=prompts == 1)  # 128 rows an expert at 4,096 rows; 256 in a slab of 8,192: full tall blocks, the loop
    assert mem.temp_size_in_bytes < most_gib * 2**30  # no copy of the experts (3.4 GiB), no layer's worth of them (0.42 GiB x 8)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes + 0.39 * 2**30 < 15.0 * 2**30


@pytest.mark.parametrize("rank", ["by_channel", "a_head"])
def test_delta_rule_kernel_compiles_for_v5e_within_its_vmem_and_copies_nothing(one_chip, as_on_a_tpu, rank):
    """PR 44, and PR 46 for one gate a head: the kernel alone at the cells' tile (32 heads x 128, chunk
    64; for a gate a head 16 key heads under 32 value heads, as ``qwen3-next-ep4.longdoc`` has them)
    and their longest bucket, two sequences as ``a_few_at_a_time`` hands them over: the gate lets the
    tile through, Mosaic takes the kernel inside the VMEM a kernel may scope (it refuses one that
    asks for more), and q, k, v, the gate and beta go in where a position's heads lie side by side
    (a gate a head as beta does, ``[B, T, 32]``, not broadcast to a head's channels):
    nothing is transposed or copied on the way in or out."""
    from ray_tpu.ops import delta_rule as dr

    B, T, N, K = 2, 4096, 32, 128
    G, R = (N, 1) if rank == "by_channel" else (N // 2, 2)
    assert dr.refusal(jnp.bfloat16, K, K, 64) is None
    flat = lambda heads: jax.ShapeDtypeStruct((B, T, heads * K), jnp.float32, sharding=one_chip)  # noqa: E731
    a_head = jax.ShapeDtypeStruct((B, T, N), jnp.float32, sharding=one_chip)

    def rule(q, k, v, g, beta):
        q, k = (a.reshape(B, T, G, K) for a in (q, k))
        o, S = dr.delta_rule(q, k, v.reshape(B, T, G, R, K), g.reshape(B, T, G, R, *((K,) if rank == "by_channel" else ())), beta.reshape(B, T, G, R), 64, jnp.bfloat16)
        return o.reshape(B, T, N * K), S

    compiled, txt = _compile(rule, flat(G), flat(G), flat(N), flat(N) if rank == "by_channel" else a_head, a_head)
    assert "tpu_custom_call" in txt and {"by_channel": "delta_rule_by_channel", "a_head": "delta_rule_by_head"}[rank] in txt
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_a_gate_a_head_lowers_to_the_kernel_as_on_a_tpu_and_to_the_text_it_had_off_it(as_on_a_tpu):
    """Qwen3-Next's rule (one gate a head) at its cell's prefill shape (2 x 4096, 16 key heads under 32
    value heads x 128), PR 46: as on a TPU with the cell's bfloat16 operands it is the kernel under
    ``gdn.chunk`` and no line of the XLA form; off the TPU it is the XLA lines, and float32 operands
    (every CPU test, the plain reference) keep those lines on both, text for text."""
    from ray_tpu.models import qwen3_next as qn
    from ray_tpu.ops import delta_rule as dr

    B, T, G, R, K = 2, 4096, 16, 2, 128
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    args = (s(B, T, G, K), s(B, T, G, K), s(B, T, G, R, K), s(B, T, G, R), s(B, T, G, R))
    rule = lambda operands: lambda *a: qn.delta_rule_chunked(*a, 64, operands)  # noqa: E731
    lowered = lambda operands, **kw: jax.jit(rule(operands)).lower(*args).as_text(**kw)  # noqa: E731
    as_on_the_chip, in_float32 = str(jax.make_jaxpr(rule(jnp.bfloat16))(*args)), lowered(None)  # a Mosaic kernel does not lower for the CPU: its jaxpr
    assert dr.refusal(jnp.bfloat16, K, K, 64) is None and "pallas_call" in as_on_the_chip and "delta_rule_by_head" in as_on_the_chip
    assert "cumsum" not in as_on_the_chip and "cumsum" in in_float32, "no line of the XLA form"
    assert "pallas" not in in_float32 and all(name in lowered(None, debug_info=True) for name in ("gdn.chunk", "gdn.scan"))
    jax.default_backend = lambda: "cpu"  # the fixture's monkeypatch puts the real one back
    assert "pallas" not in lowered(jnp.bfloat16) and all(name in lowered(jnp.bfloat16, debug_info=True) for name in ("gdn.chunk", "gdn.scan"))
    assert lowered(None) == in_float32


# ---------------------------------------------------------------------------
# PR 37: the decode step's routed experts are the experts a bound lane chose, one after another
# (models/experts.experts_step): on a TPU one kernel whose grid walks their ids, each expert's
# matrices read from the stacked weights where they lie (ops/step_experts.py)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cell, lanes_n, a_layers_experts_gb", [("nemotron", 32, 1.28), ("qwen3_next", 16, 0.81), ("glm", 16, 1.21), ("kimi", 16, 0.91)])
def test_fused_step_walks_the_experts_hit_and_copies_no_layers_experts(fused_step_for_the_chip, as_on_a_tpu, cell, lanes_n, a_layers_experts_gb):
    """Each hybrid cell's fused step at its own size: the gate lets the cell's expert through whole,
    the kernel is in the step, both caches are aliased to the donated inputs, temporaries stay
    under 0.1 GiB where one layer's held experts are 1.28 / 0.81 / 1.21 / 0.91 GB, and no array of a
    layer's experts (``[held, F, H]``: the dense form's operand, or a slice made for the kernel)
    is anywhere in the program."""
    import re

    from ray_tpu.ops import step_experts

    cfg, params, cache, state, compiled = fused_step_for_the_chip(cell)
    layers, held, F, H = params["moe"]["w_up"].shape
    matrices = len(cfg.expert_layer.matrices)
    assert held == cfg.expert_layer.held and layers == cfg.count("moe") and cache["length"].shape == (lanes_n,)
    assert abs(matrices * held * F * H * 2 / 1e9 - a_layers_experts_gb) < 0.01
    assert step_experts.refusal(jnp.bfloat16, H, F, matrices) is None and step_experts.tile_rows(F, H, matrices, 2) == F
    assert "float32" in step_experts.refusal(jnp.float32, H, F, matrices) and "128-lane" in step_experts.refusal(jnp.bfloat16, H + 64, F, matrices)
    assert step_experts.tile_rows(4 * 1856, 2688, 2, 2) == 1856 and "no tile" in step_experts.refusal(jnp.bfloat16, 128, 65537, 2)
    mem, txt = compiled.memory_analysis(), compiled.as_text()
    assert "step_experts" in txt
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize for a in jax.tree.leaves((cache, state)) if a.ndim > 1)
    assert mem.temp_size_in_bytes < 0.1 * 2**30
    assert not re.search(rf"bf16\\[(1,)?{held},{F},{H}\\]", txt), "a layer's experts, sliced out of the stack"


# ---------------------------------------------------------------------------
# PR 45: a fifth description, MiniCPM-SALA (models/minicpm_sala.py): the cell minicpm-sala-d8.longdoc-12k.
# ---------------------------------------------------------------------------
def test_sala_fused_step_fits_one_v5e_aliases_its_three_caches_and_slices_no_layers_rows(fused_step_for_the_chip, as_on_a_tpu):
    """The fused step at 16 x 12,288 through the SAME ``hybrid_runner.fused_step`` and layer loop as
    the four other descriptions (head ``sparse``, then ``ffn lightning`` x 6 scanned, tail ``ffn sparse
    ffn``): 5.25 GiB of weights, 0.375 GiB of keys and values, 0.19 GiB of Lightning state and 12 MiB
    of compressed keys; all aliased to the donated inputs; the live-block kernel in the step for the
    lanes that attend densely, the table's kernel for the others (each place's block streamed from
    where it lies), and neither a slice of a layer's rows (96 MiB of keys at 16 x 12,288) nor a copy
    of a stack in blocks (the XLA form's 0.2 GiB, four times a step) in the compiled text."""
    import re

    cfg, _, cache, state, compiled = fused_step_for_the_chip("sala")
    assert cfg.layer_plan == (("ffn", "lightning"), 6, ("ffn", "sparse", "ffn"), ("sparse",))
    assert set(state) == {"S", "kc"} and set(cache) == {"k", "v", "length"}
    mem, txt = compiled.memory_analysis(), compiled.as_text()
    caches = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves((cache, state)))
    assert _kv_bytes(cache) == 16 * 12288 * 2048 and caches - _kv_bytes(cache) - 64 == 16 * 13_369_344
    print("sala fused step:", mem.argument_size_in_bytes / 2**30, mem.alias_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**20)
    assert 5.8 * 2**30 < mem.argument_size_in_bytes < 5.9 * 2**30 and mem.alias_size_in_bytes >= caches
    assert "slot_decode_attention" in txt and "sparse_decode_attention" in txt and not re.search(r"bf16\[(1,16,12288|2,16,192,64),2,128\]", txt)
    assert mem.temp_size_in_bytes < 160 * 2**20  # 143 MiB: a Lightning layer's projections copied out of the stack inside the scan (PERF.md section 7)


@pytest.mark.parametrize("prompts, most_gib", [(1, 3.0), (2, 4.0)])
def test_sala_prefill_of_the_12288_bucket_fits_beside_weights_and_caches_on_one_v5e(one_chip, as_on_a_tpu, prompts, most_gib):
    """The 12,288-bucket prefill (the selection and the masked attention a tile of 128 queries at a
    time under ``sparse.select`` and ``sparse.attend``, the Lightning rule in chunks of 128 under
    ``lightning.chunk``, both a sequence at a time; a 16,384-wide SwiGLU over every position) for one
    prompt and for the largest group the cell warms, 2 x 12,288, beside 5.25 GiB of weights and 0.58
    GiB of caches: under 15.75 GiB."""
    from ray_tpu.llm import hybrid_runner as hr

    cfg, params, _, _ = _cell_at_its_size(one_chip, "sala")
    tokens = jax.ShapeDtypeStruct((prompts, 12288), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((prompts,), jnp.int32, sharding=one_chip)
    compiled, txt = _compile(partial(hr.prefill, cfg=cfg), params, tokens, lengths)
    mem = compiled.memory_analysis()
    print("sala prefill:", prompts, mem.argument_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**30, mem.output_size_in_bytes / 2**30)
    assert all(name in txt for name in ("sparse.select", "sparse.attend", "lightning.chunk"))
    (loop,) = _loops(txt, "ffn")  # the dense SwiGLU over the slabs of 512 positions under a true length (PR 54): one loop for all layers and rows,
    assert "bf16[8,4096,16384]" in loop and "bf16[1,4096,16384]" not in loop  # its matrices read where they lie in the stack
    kernel = [line for line in txt.splitlines() if "custom-call(" in line and "sparse_prefill_attention" in line]
    assert kernel and all("tpu_custom_call" in line and "sparse.attend" in line for line in kernel), "step 5 as one kernel, under its scope"
    assert mem.temp_size_in_bytes < most_gib * 2**30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes + 0.58 * 2**30 < 15.0 * 2**30


# ---------------------------------------------------------------------------
# PR 49: a sixth description, SmallThinker (models/smallthinker.py): the cell smallthinker-21b-d8.longdoc-12k.
# ---------------------------------------------------------------------------
def test_smallthinker_fused_step_fits_one_v5e_aliases_rows_and_rings_and_slices_no_layers_rows(fused_step_for_the_chip, as_on_a_tpu):
    """The fused step at 16 x 12,288 through the SAME ``hybrid_runner.fused_step`` and layer loop as
    the five other descriptions (``attn moe swa moe swa moe swa moe`` twice, scanned, the routing
    riding the loop beside the stream): 7.39 GiB of weights and 1.50 GiB of cache, half of it the two
    global layers' rows for every position and half the six window layers' rings of 4,096 rows; all
    of it aliased to the donated inputs; the live-block kernel under two names (28 query heads over
    4 go as 32 rows), the experts' step kernel with the ReLU gate, and no slice of a layer's rows
    (192 MiB of keys at 16 x 12,288, 64 MiB a ring) in the compiled text."""
    import re

    cfg, _, cache, state, compiled = fused_step_for_the_chip("smallthinker")
    assert cfg.layer_plan == (("attn", "moe", "swa", "moe", "swa", "moe", "swa", "moe"), 2, (), ()) and state == {}
    assert {n: a.shape for n, a in cache.items() if n != "length"} == {
        "k": (2, 16, 12288, 4, 128), "v": (2, 16, 12288, 4, 128), "k_w": (6, 16, 4096, 4, 128), "v_w": (6, 16, 4096, 4, 128)}
    mem, txt = compiled.memory_analysis(), compiled.as_text()
    assert _kv_bytes(cache) == 1_610_612_736 == 2 * (2 * 16 * 12288 + 6 * 16 * 4096) * 1024
    print("smallthinker fused step:", mem.argument_size_in_bytes / 2**30, mem.alias_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**20)
    assert 8.85 * 2**30 < mem.argument_size_in_bytes < 8.95 * 2**30 and mem.alias_size_in_bytes >= _kv_bytes(cache)
    assert all(name in txt for name in ("slot_decode_attention", "window_decode_attention", "step_experts"))
    assert not re.search(r"bf16\[(1,)?16,(12288|4096),4,128\]", txt)
    assert mem.temp_size_in_bytes < 140 * 2**20  # 123 MiB: the window layers' query projections copied out of the stack inside the scan, as SALA's are (PERF.md section 7)


@pytest.mark.parametrize("prompts, most_gib", [(1, 1.1), (4, 3.8)])
def test_smallthinker_prefill_of_the_12288_bucket_fits_beside_weights_and_cache_on_one_v5e(one_chip, as_on_a_tpu, prompts, most_gib):
    """The 12,288-bucket prefill (the flash kernel with a window under ``swa``, without one under
    ``attn``; 73,728 routed pairs a prompt through the grouped matmul) for one prompt and for the
    largest group the cell warms, 4 x 12,288, beside 7.39 GiB of weights and 1.50 GiB of cache:
    under 15.75 GiB. Eight prompts at once would not fit (6.6 GiB of temporaries and 1.5 GiB handed
    to the cache): the engine's reckoning of the device's free memory halves such a wave."""
    from ray_tpu.llm import hybrid_runner as hr

    cfg, params, _, _ = _cell_at_its_size(one_chip, "smallthinker")
    tokens = jax.ShapeDtypeStruct((prompts, 12288), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((prompts,), jnp.int32, sharding=one_chip)
    compiled, txt = _compile(partial(hr.prefill, cfg=cfg), params, tokens, lengths)
    mem = compiled.memory_analysis()
    print("smallthinker prefill:", prompts, mem.argument_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**30, mem.output_size_in_bytes / 2**30)
    kernels = [line for line in txt.splitlines() if "custom-call(" in line and "tpu_custom_call" in line]
    assert any("window_flash_attention" in line and "swa" in line for line in kernels), "the window layers' attention as the flash kernel, under its scope"
    assert any("window_flash_attention" not in line and "/attn/" in line for line in kernels), "and the global layers' without a window"
    assert mem.temp_size_in_bytes < most_gib * 2**30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes + 1.5 * 2**30 < 15.75 * 2**30


def _prefill_text(cfg, params, one_chip, prompts, bucket):
    """A prefill of ``prompts`` x ``bucket`` positions lowered for the chip, the Mosaic kernels' payloads cut
    out (they hold the checkout's path and line numbers): what a warm-up program's compile-cache key follows."""
    import re

    from ray_tpu.llm import hybrid_runner as hr

    shapes = (jax.ShapeDtypeStruct((prompts, bucket), jnp.int32, sharding=one_chip), jax.ShapeDtypeStruct((prompts,), jnp.int32, sharding=one_chip))
    return re.sub(r'\\22body\\22: \\22[A-Za-z0-9+/=]+', "BODY", jax.jit(partial(hr.prefill, cfg=cfg)).lower(params, *shapes).as_text())


def _the_rule_the_blocks_had(monkeypatch):
    """``experts.blocks_plan`` as the blocks ran before PRs 56 and 57: tall from ``TALL_FROM`` pairs, the loop, three counters."""
    from ray_tpu.models import experts

    monkeypatch.setattr(experts, "blocks_plan", lambda s, N, mats: (2 * experts.BLOCK if N * s.top_k >= experts.TALL_FROM else experts.BLOCK, False))


def _the_trips_the_gather_out_had(monkeypatch):
    """``experts.out_plan`` as the gather out walked a tile's pairs before PR 65: 512 a trip, whatever the call."""
    from ray_tpu.models import experts

    monkeypatch.setattr(experts, "out_plan", lambda *_: 512)


@pytest.mark.parametrize("cell, pairs_a_tile, trip", [("glm", 512, 512), ("lfm2", 512, 512), ("qwen3_next", 320, 512), ("nemotron", 384, 512), ("kimi", 256, 512),
                                                      ("smallthinker", 768, 768), ("keye", 1024, 512), ("trinity", 64, 128)])
def test_the_gather_out_takes_a_tiles_pairs_in_trips_sized_by_the_layer(one_chip, cell, pairs_a_tile, trip):
    """PR 65: ``experts.out_plan`` at the eight cells that route experts, from each cell's own configuration file. Where
    every published expert is held a tile of 128 tokens holds 128 k pairs and ONE trip takes them all at 4 and 6
    choices (512 and 768 rows; two trips of 512 until PR 65 at 6, the second half empty), two equal trips at 8 (Keye:
    a trip of 1,024 entries costs more than two of 512); where a share is held, what a tile is expected to hold and a
    third more, in whole pairs of 128s above one (Qwen3-Next 320, Nemotron 384 and Kimi 256 expected: the 512 they had;
    Trinity 64: 128, where 512 rows were gathered for 64)."""
    from ray_tpu.models import experts

    cfg, _, _, _ = _cell_at_its_size(one_chip, cell)
    s = cfg.expert_layer
    assert experts.TILE * s.top_k * s.held // s.num_experts == pairs_a_tile and experts.out_plan(s) == trip


@pytest.mark.parametrize("cell, prompts, bucket", [("nemotron", 1, 2048), ("nemotron", 4, 512), ("nemotron", 4, 2048), ("glm", 1, 64), ("lfm2", 1, 64), ("kimi", 2, 4096), ("kimi", 8, 1024)])
def test_a_prefill_that_the_kernel_does_not_serve_lowers_to_the_text_it_had(one_chip, as_on_a_tpu, monkeypatch, cell, prompts, bucket):
    """PRs 56, 57 and 63 changed how the blocks of ``experts._grouped`` run only where an expert of 16 MiB or less
    expects less than two short blocks' rows of a call, and where any expert expects two blocks' rows or more.
    Nemotron's prefills (19 MiB an expert: the chat cell's 18 warm programs, of which PR 56 renewed 17 for a kernel
    that no metric of the cell reads; its 8,192 rows of 4 x 2,048 are 384 an expert, a tall block and a half), the
    first request of the GLM and LFM2 cells (64 rows against experts of 18 MiB) and Kimi's calls of 8,192 rows (256 an
    expert, one tall block) lower for the chip to the text they lower to under the rule the blocks had before, without
    the kernel: no warm-up program of theirs is a new one (``ROADMAP.md`` A7; ``scripts/warm_texts.py`` makes the
    comparison against another tree, every warm shape of every cell). PR 65 sized the gather out's trips by the layer:
    these cells' rule still says the 512 rows a trip they had, so the text is held with the trips put back too."""
    cfg, params, _, _ = _cell_at_its_size(one_chip, cell)
    now = _prefill_text(cfg, params, one_chip, prompts, bucket)
    _the_rule_the_blocks_had(monkeypatch)
    _the_trips_the_gather_out_had(monkeypatch)
    assert "tpu_custom_call" in now and "grouped_experts" not in now and now == _prefill_text(cfg, params, one_chip, prompts, bucket)


@pytest.mark.parametrize("cell, prompts, bucket", [("smallthinker", 1, 2048), ("glm", 1, 8192), ("glm", 1, 16384), ("smallthinker", 1, 12288), ("lfm2", 1, 12288), ("lfm2", 2, 12288), ("keye", 1, 24576)])
def test_a_prefill_that_the_kernel_serves_does_not(one_chip, as_on_a_tpu, monkeypatch, cell, prompts, bucket):
    """The same comparison where the kernel does serve, so the comparison above can fail: few rows an expert over
    small experts (SmallThinker's bucket of 2,048 rows, which its traffic never admits: 192 rows an expert of
    11.25 MiB; PR 57), and FULL blocks whatever the expert's size (PR 63): the prefills that the traffic of the GLM,
    SmallThinker, LFM2 and Keye cells runs, 8,192 rows a call and more, 512-1,152 rows an expert. Their text is a
    new one and holds the kernel by name. And the trips of the gather out (PR 65), where the rule says another size
    than the 512 rows they had (SmallThinker's six choices a token: one trip of 768): with the blocks' rule as it is
    and the trips put back, the text is another, so the comparison above can fail on the trips too."""
    from ray_tpu.models import experts

    cfg, params, _, _ = _cell_at_its_size(one_chip, cell)
    now = _prefill_text(cfg, params, one_chip, prompts, bucket)
    if experts.out_plan(cfg.expert_layer) != 512:
        with monkeypatch.context() as trips:
            _the_trips_the_gather_out_had(trips)
            assert cell == "smallthinker" and now != _prefill_text(cfg, params, one_chip, prompts, bucket)
    _the_rule_the_blocks_had(monkeypatch)
    assert "grouped_experts" in now and "grouped_experts" not in _prefill_text(cfg, params, one_chip, prompts, bucket)


def test_smallthinker_ring_insertion_updates_the_cache_in_place_and_gathers_only_the_windows_rows(one_chip):
    """``insert_entries`` for one prefilled prompt of the 12,288 bucket: the global layers' rows
    written whole, the window layers' LAST 4,096 positions gathered by the true length into their
    ring: the donated cache aliased (1.50 GiB), and no temporary of a layer's rows or of a ring."""
    from ray_tpu.llm import kv_cache as kvc

    cfg, _, cache, _ = _cell_at_its_size(one_chip, "smallthinker")
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    new = {n: s((a.shape[0], 12288) + a.shape[3:], a.dtype) for n, a in cache.items() if n != "length"}
    insert = jax.jit(partial(kvc.insert_entries, rings=frozenset(cfg.ring_entries())), donate_argnums=(0,))
    mem = insert.lower(cache, s((), jnp.int32), new, s((), jnp.int32)).compile().memory_analysis()
    print("smallthinker insert:", mem.argument_size_in_bytes / 2**30, mem.alias_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**20)
    assert mem.alias_size_in_bytes >= _kv_bytes(cache) and mem.temp_size_in_bytes < 8 * 2**20


# ---------------------------------------------------------------------------
# PR 53: a seventh description, LFM2 with experts (models/lfm2.py): the cell lfm2-24b-d10.longdoc-12k.
# ---------------------------------------------------------------------------
def test_narrow_slot_attention_kernel_compiles_for_v5e_and_copies_nothing(one_chip, as_on_a_tpu):
    """32 query heads over 8 key-value heads 64 wide at 16 x 12,288: the gate lets the tile through,
    the cache keeps a position as 4 rows of 128 lanes (two heads a row), and the live-block kernel
    reads them where they lie (seen as [L, slots, S*4, 128] by a bitcast) under a name of its own:
    no temporary of any size to speak of. A cache of (8, 64) tiles would be twice the bytes."""
    from ray_tpu.ops import slot_attention as sa

    assert sa.refusal(jnp.bfloat16, 32, 8, 64, 12288) is None and sa.position_tile(8, 64) == (4, 128)
    assert sa.position_tile(8, 128) == (8, 128) and sa.position_tile(2, 16) == (2, 16) and sa.position_tile(2, 64) == (1, 128)
    for heads, kv in ((32, 1), (24, 8), (8, 8)):
        assert f"{heads} query heads over {kv} kv heads x head_dim 64" in sa.refusal(jnp.bfloat16, heads, kv, 64, 12288)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    stack = sds((2, 16, 12288, 4, 128), jnp.bfloat16)
    compiled, txt = _compile(partial(sa.attend_narrow_kernel, num_kv_heads=8), sds((16, 32, 64), jnp.bfloat16), stack, stack, sds((), jnp.int32), sds((16,), jnp.int32))
    assert "tpu_custom_call" in txt and sa.KERNEL_NARROW in txt
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_flash_fwd_at_heads_64_wide_compiles_for_v5e(one_chip):
    """The flash call at 32 heads of 64 over 12,288 positions with the true lengths, the kernel's tiles
    1,024 x 64: what ``models/lfm2.py`` does NOT run. Mosaic takes an operand whose rows are 64 wide
    only in whole 128-lane tiles: the compiler copies q, k and v into that layout and the output out
    of it (four arrays of 2 x 32 x 12,288 x 128 bfloat16, 0.75 GiB here), and on the chip those
    copies stood the core idle 4 ms apiece (PERF.md section 6, PR 53). The model pads a head to 128
    where it is made instead (``Lfm2Config.flash_width``): the prefill case below compiles that."""
    from ray_tpu.ops.flash_attention import _fwd_pallas

    q = jax.ShapeDtypeStruct((2, 32, 12288, 64), jnp.bfloat16, sharding=one_chip)
    n = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    compiled, txt = _compile(lambda q, k, v, n: _fwd_pallas(q, k, v, True, None, lengths=n), q, q, q, n)
    assert "tpu_custom_call" in txt and "window_flash_attention" not in txt
    assert compiled.memory_analysis().temp_size_in_bytes < 4.05 * 2 * 32 * 12288 * 128 * 2


def test_lfm2_fused_step_fits_one_v5e_aliases_rows_and_windows_and_slices_no_layers_rows(fused_step_for_the_chip, as_on_a_tpu):
    """The fused step at 16 x 12,288 through the SAME ``hybrid_runner.fused_step`` and layer loop as
    the six other descriptions (two dense layers unrolled, then ``attn moe shortconv moe shortconv
    moe shortconv moe`` twice, scanned): 9.81 GiB of weights (no ``unembed``: the head is the table),
    0.75 GiB of keys and values at 4,096 B a position and 1 MiB of convolution windows, all of it
    aliased to the donated inputs; the live-block kernel at heads 64 wide by its own name, the
    experts' step kernel, and no slice of a layer's rows (96 MiB of keys at 16 x 12,288) in the
    compiled text; the temporaries under the bound the file holds for the others."""
    import re

    cfg, params, cache, state, compiled = fused_step_for_the_chip("lfm2")
    assert cfg.layer_plan == (("attn", "moe", "shortconv", "moe", "shortconv", "moe", "shortconv", "moe"), 2, (), ("shortconv", "ffn", "shortconv", "ffn"))
    assert "unembed" not in params and {n: a.shape for n, a in cache.items() if n != "length"} == {"k": (2, 16, 12288, 4, 128), "v": (2, 16, 12288, 4, 128)}
    assert {n: (a.shape, str(a.dtype)) for n, a in state.items()} == {"conv": ((8, 16, 2, 2048), "bfloat16")}
    mem, txt = compiled.memory_analysis(), compiled.as_text()
    assert _kv_bytes(cache) == 805_306_368 == 16 * 12288 * 4096
    print("lfm2 fused step:", mem.argument_size_in_bytes / 2**30, mem.alias_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**20)
    assert 10.5 * 2**30 < mem.argument_size_in_bytes < 10.65 * 2**30 and mem.alias_size_in_bytes >= _kv_bytes(cache) + _kv_bytes(state)
    assert all(name in txt for name in ("slot_decode_attention_narrow", "step_experts", "shortconv.state", "shortconv.conv"))
    assert not re.search(r"bf16\[(1,)?16,12288,(4,128|8,64)\]", txt)
    assert mem.temp_size_in_bytes < 16 * 2**20  # 7.6 MiB


@pytest.mark.parametrize("prompts, most_gib", [(1, 0.7), (4, 2.9)])
def test_lfm2_prefill_of_the_12288_bucket_fits_beside_weights_and_cache_on_one_v5e(one_chip, as_on_a_tpu, prompts, most_gib):
    """The 12,288-bucket prefill (the short convolution a sequence at a time under ``shortconv``, the
    flash kernel at heads 64 wide under ``attn``, an 11,776-wide SwiGLU in slabs, 49,152 routed
    pairs a prompt through the grouped matmul, its full blocks run by the kernel) for one prompt and for the largest group the cell
    warms, 4 x 12,288, beside 9.81 GiB of weights and 0.75 GiB of cache: under 15.75 GiB."""
    from ray_tpu.llm import hybrid_runner as hr

    cfg, params, _, _ = _cell_at_its_size(one_chip, "lfm2")
    tokens = jax.ShapeDtypeStruct((prompts, 12288), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((prompts,), jnp.int32, sharding=one_chip)
    compiled, txt = _compile(partial(hr.prefill, cfg=cfg), params, tokens, lengths)
    mem = compiled.memory_analysis()
    print("lfm2 prefill:", prompts, mem.argument_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**30, mem.output_size_in_bytes / 2**30)
    kernels = [line for line in txt.splitlines() if "custom-call(" in line and "tpu_custom_call" in line]
    assert {"/attn/" in line or ("grouped_experts" in line and "moe.blocks" in line) for line in kernels} == {True}, "the flash kernel and the full blocks' kernel (PR 63), each under its scope"
    assert any("/attn/" in line for line in kernels) and any("grouped_experts" in line for line in kernels)
    assert "shortconv.conv" in txt
    assert mem.temp_size_in_bytes < most_gib * 2**30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes + 0.75 * 2**30 < 15.75 * 2**30


# ---------------------------------------------------------------------------
# PR 58: an eighth description, Keye-VL-2.0's language model (models/keye_vl.py): the cell keye-vl-2.0-d6.longdoc-24k.
# ---------------------------------------------------------------------------
def test_both_kernels_of_the_indexed_prefill_compile_for_v5e_at_24576_positions(one_chip, as_on_a_tpu):
    """The thresholds' kernel (a tile of 256 queries' index keys against every earlier position in
    25 MB of fast memory, the two bisections, then the choice packed a bit a pair) and the attention
    kernel that reads a tile's bits and does attention's work alone, at 32 heads over 4 of 128 under
    16 x 64 at 24,576 positions: the gate lets the ladder's buckets through and no other, both lower
    to Mosaic under their names, the table is T x T / 8 bytes a sequence (75.5 MB) and all the first
    kernel hands on, and neither holds a [T, T] array of scores, keys or bytes."""
    from ray_tpu.ops import indexed_attention as ia

    assert all(ia.refusal(jnp.bfloat16, 128, 64, T) is None for T in (4096, 8192, 16384, 24576))
    assert "whole groups of 4096" in ia.refusal(jnp.bfloat16, 128, 64, 6144)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    T = 24576
    qi, w, ki, n = sds((1, 16, T, 64), jnp.bfloat16), sds((1, T, 16), jnp.float32), sds((1, T, 64), jnp.bfloat16), sds((1,), jnp.int32)
    compiled, txt = _compile(lambda qi, w, ki, n: ia.thresholds_kernel(qi, w, ki, n, 2048), qi, w, ki, n)
    assert "tpu_custom_call" in txt and "indexer_thresholds" in txt
    assert T * T // 8 == 75_497_472 <= compiled.memory_analysis().output_size_in_bytes < T * T // 8 + 4096
    q, k, table = sds((1, 32, T, 128), jnp.bfloat16), sds((1, 4, T, 128), jnp.bfloat16), sds((1, T, ia.choice_words(T)), jnp.int32)
    compiled, txt = _compile(ia.attend_indexed_kernel, q, k, k, table, n)
    assert "tpu_custom_call" in txt and "indexed_prefill_attention" in txt and compiled.memory_analysis().output_size_in_bytes < 32 * T * 128 * 2 + 4096


def test_keye_fused_step_fits_one_v5e_aliases_its_three_entries_and_gathers_the_chosen_rows(fused_step_for_the_chip, as_on_a_tpu):
    """The fused step at 12 x 24,576 through the SAME ``hybrid_runner.fused_step`` and layer loop as
    the seven other descriptions (``indexed moe`` six times, scanned): 8.15 GiB of weights, 3.59 GiB
    of keys, values and the indexer's keys at 13,056 B a position, all of it aliased to the donated
    inputs; no live-block kernel (the step gathers the rows its lanes chose), the experts' step
    kernel, and no slice of a layer's keys or values (288 MiB at 12 x 24,576) in the compiled text:
    the one layer's rows it reads whole are the indexer's keys, 36 MiB."""
    import re

    cfg, params, cache, state, compiled = fused_step_for_the_chip("keye")
    assert cfg.layer_plan == (("indexed", "moe"), 6, (), ()) and state == {}
    assert {n: a.shape for n, a in cache.items() if n != "length"} == {"k": (6, 12, 24576, 4, 128), "v": (6, 12, 24576, 4, 128), "k_idx": (6, 12, 24576, 64)}
    mem, txt = compiled.memory_analysis(), compiled.as_text()
    assert _kv_bytes(cache) == 3_850_371_072 == 12 * 24576 * 13056
    print("keye fused step:", mem.argument_size_in_bytes / 2**30, mem.alias_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**20)
    assert 11.6 * 2**30 < mem.argument_size_in_bytes < 11.85 * 2**30 and mem.alias_size_in_bytes >= _kv_bytes(cache)
    assert all(name in txt for name in ("step_experts", "indexed.score", "indexed.select", "indexed.attend")) and "slot_decode_attention" not in txt
    assert not re.search(r"bf16\[(1,)?12,24576,4,128\]", txt)
    assert mem.temp_size_in_bytes < 64 * 2**20  # 10.9 MiB


def test_keye_prefill_of_the_24576_bucket_fits_beside_weights_and_cache_on_one_v5e(one_chip, as_on_a_tpu):
    """The 24,576-bucket prefill of one prompt (the indexer's projections under ``indexed.score``, the
    thresholds' kernel under ``indexed.select``, the attention kernel under ``indexed.attend``, 196,608
    routed pairs through the grouped matmul in slabs) beside 8.15 GiB of weights and 3.59 GiB of cache:
    under 15.75 GiB. (Two prompts take 2.74 GiB of temporaries and 0.6 of results: they fit too, and
    the cell warms that shape; four do not, and the engine never asks for them.) What passes from
    the first kernel to the second is a layer's choice table, a bit a pair (72 MiB): the program holds
    no array with both a query's and a position's axis (a byte a pair would be 576 MiB)."""
    import re
    from ray_tpu.llm import hybrid_runner as hr

    cfg, params, _, _ = _cell_at_its_size(one_chip, "keye")
    tokens = jax.ShapeDtypeStruct((1, 24576), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    compiled, txt = _compile(partial(hr.prefill, cfg=cfg), params, tokens, lengths)
    mem = compiled.memory_analysis()
    print("keye prefill:", mem.argument_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**30, mem.output_size_in_bytes / 2**30)
    kernels = [line for line in txt.splitlines() if "custom-call(" in line and "tpu_custom_call" in line]
    assert kernels and any("indexed.select" in line for line in kernels) and any("indexed.attend" in line for line in kernels)
    assert "s32[1,24576,768]" in txt, "the choice table: 24,576 / 32 words a query"
    pairs = sorted(set(re.findall(r"\b\w+\[(?:\d+,)*24576,(?:\d+,)*24576(?:,\d+)*\]", txt)))
    assert not pairs, f"an array a (query, position) pair: {pairs}"
    assert mem.temp_size_in_bytes < 1.7 * 2**30  # 1.50 GiB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes + 3.59 * 2**30 < 15.75 * 2**30


# ---------------------------------------------------------------------------
# PR 60: a ninth description, AI21-Jamba2-3B (models/jamba.py): the cell jamba2-3b.longdoc-12k.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("prompts", [1, 2])
def test_selective_scan_kernel_compiles_for_v5e_at_the_cells_bucket(one_chip, as_on_a_tpu, prompts):
    """[12288, 5120] x 16 states in bfloat16: the gate lets it through, Mosaic takes the body (the state in VMEM
    across a sequence's 96 blocks of 128 positions, ten runs of 512 channels a block, B and C broadcast over one
    row of lanes), under its own name; what XLA makes beside it is the two broadcasts, 4 KB a position each."""
    from ray_tpu.ops import selective_scan as ss

    assert ss.refusal(jnp.bfloat16, 5120, 16) is None and ss.refusal(jnp.bfloat16, 5120 + 128, 16) is not None
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    bf, f32, T, W, N = jnp.bfloat16, jnp.float32, 12288, 5120, 16
    args = (sds((prompts, T, W), bf), sds((prompts, T, W), bf), sds((W, N), f32), sds((prompts, T, N), bf), sds((prompts, T, N), bf), sds((W,), f32), sds((W,), f32), sds((prompts,), jnp.int32))
    compiled, txt = _compile(ss.selective_scan, *args)
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" in txt and ss.KERNEL in txt
    assert mem.output_size_in_bytes < prompts * (T * W * 2 + N * W * 4) + 4096 and mem.temp_size_in_bytes <= 2 * prompts * T * N * 128 * 2 + (1 << 20)


def test_slot_attention_kernel_at_20_heads_over_one_compiles_for_v5e_and_copies_nothing(one_chip, as_on_a_tpu):
    """20 query heads over ONE key-value head 128 wide at 16 x 12,288 (32 rows of queries a lane, as padded): the gate
    lets the tile through and Mosaic compiles it, the stack read where it lies."""
    from ray_tpu.ops import slot_attention as sa

    assert sa.refusal(jnp.bfloat16, 20, 1, 128, 12288) is None
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    stack = sds((2, 16, 12288, 1, 128), jnp.bfloat16)
    compiled, txt = _compile(sa.attend_kernel, sds((16, 20, 128), jnp.bfloat16), stack, stack, sds((), jnp.int32), sds((16,), jnp.int32))
    assert "tpu_custom_call" in txt and sa.KERNEL in txt
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_jamba_fused_step_fits_one_v5e_aliases_rows_state_and_windows(fused_step_for_the_chip, as_on_a_tpu):
    """The fused step at 16 x 12,288 through the SAME ``hybrid_runner.fused_step`` and layer loop as the eight other
    descriptions, its scan body 28 sub-blocks long (the longest period any description has had), twice: 5.64 GiB
    of weights (no ``unembed``: the head is the table), 0.19 GiB of keys and values at 1,024 B a position and 0.14 GiB
    of state (states x channels, float32: dense in the 128 lanes) and windows, all of it aliased to the donated inputs;
    the live-block kernel in the two attention layers, the state's read, decay and write under ``mamba1.state``."""
    cfg, params, cache, state, compiled = fused_step_for_the_chip("jamba")
    assert len(cfg.layer_plan.period) == 28 and cfg.layer_plan.repeats == 2 and "unembed" not in params
    assert {n: a.shape for n, a in cache.items() if n != "length"} == {"k": (2, 16, 12288, 1, 128), "v": (2, 16, 12288, 1, 128)}
    assert {n: (a.shape, str(a.dtype)) for n, a in state.items()} == {"ssm": ((26, 16, 16, 5120), "float32"), "conv": ((26, 16, 3, 5120), "bfloat16")}
    mem, txt = compiled.memory_analysis(), compiled.as_text()
    assert _kv_bytes(cache) == 201_326_592 and _kv_bytes(state) == 16 * 9_318_400
    print("jamba fused step:", mem.argument_size_in_bytes / 2**30, mem.alias_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**20, mem.generated_code_size_in_bytes / 2**20)
    assert 5.9 * 2**30 < mem.argument_size_in_bytes < 6.1 * 2**30 and mem.alias_size_in_bytes >= _kv_bytes(cache) + _kv_bytes(state)
    assert all(name in txt for name in ("slot_decode_attention", "mamba1.state", "mamba1.conv"))
    assert mem.temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("prompts, most_gib", [(1, 1.0), (4, 3.2)])
def test_jamba_prefill_of_the_12288_bucket_fits_beside_weights_and_cache_on_one_v5e(one_chip, as_on_a_tpu, prompts, most_gib):
    """The 12,288-bucket prefill (the selective scan's kernel a sequence at a time under ``mamba1.scan``, the flash kernel
    at 20 heads over one under ``attn``, an 8,192-wide SwiGLU in slabs) for one prompt and for the largest group the
    cell warms, 4 x 12,288, beside 5.64 GiB of weights and 0.33 GiB of caches: under 15.75 GiB."""
    from ray_tpu.llm import hybrid_runner as hr

    cfg, params, _, _ = _cell_at_its_size(one_chip, "jamba")
    tokens = jax.ShapeDtypeStruct((prompts, 12288), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((prompts,), jnp.int32, sharding=one_chip)
    compiled, txt = _compile(partial(hr.prefill, cfg=cfg), params, tokens, lengths)
    mem = compiled.memory_analysis()
    print("jamba prefill:", prompts, mem.argument_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**30, mem.output_size_in_bytes / 2**30)
    kernels = [line for line in txt.splitlines() if "custom-call(" in line and "tpu_custom_call" in line]
    assert any("/attn/" in line for line in kernels) and any("mamba1.scan" in line and "selective_scan" in line for line in kernels)
    assert all("/attn/" in line or "mamba1.scan" in line for line in kernels), "two kernels, each under its scope"
    assert "mamba1.conv" in txt
    assert mem.temp_size_in_bytes < most_gib * 2**30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes + 0.33 * 2**30 < 15.75 * 2**30


# ---------------------------------------------------------------------------
# PR 64: a tenth description, Arcee Trinity-Large-Preview (models/afmoe.py): the cell trinity-large-ep8-d5.longdoc-12k.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layers, S", [(1, 12288), (4, 4096)])
def test_slot_attention_kernel_at_48_heads_over_8_compiles_for_v5e_and_copies_nothing(one_chip, as_on_a_tpu, layers, S):
    """48 query heads over 8 key-value heads 128 wide are THREE bfloat16 tiles of 16 query rows a lane, where every cell before
    had at most two: the gate lets the tile through (PR 64; it refuses four) and Mosaic compiles the kernel's body as it stands,
    over the full layer's rows of 16 x 12,288 and over the window layers' rings of 16 x 4,096, each stack read where it lies."""
    from ray_tpu.ops import slot_attention as sa

    assert sa.refusal(jnp.bfloat16, 48, 8, 128, S) is None and sa.padded_heads(48, 8) == 48 and "56 query heads over 8" in sa.refusal(jnp.bfloat16, 56, 8, 128, S)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    stack = sds((layers, 16, S, 8, 128), jnp.bfloat16)
    compiled, txt = _compile(partial(sa.attend_kernel, name="window_decode_attention"), sds((16, 48, 128), jnp.bfloat16), stack, stack, sds((), jnp.int32), sds((16,), jnp.int32))
    assert "tpu_custom_call" in txt and "window_decode_attention" in txt
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_trinitys_calls_take_blocks_of_128_through_the_loop(as_on_a_tpu):
    """Which branch ``experts.blocks_plan`` takes at Trinity's shapes, asked as on the chip: a 12,288-row call makes 49,152 pairs,
    under ``2 * BLOCK * num_experts`` = 65,536, so blocks are 128 rows; an expert of 54 MiB expects 192 rows: under two blocks'
    (not FULL) and over ``ops/grouped_experts``'s 16 MiB (not LOW fill): the XLA loop, a fetch of the expert's matrices a block.
    The same for the 8,192-row slabs of a two-prompt wave (128 rows an expert). A later change of the rule shows here."""
    from ray_tpu.models import experts
    from ray_tpu.models.afmoe import AfmoeConfig
    from ray_tpu.ops import grouped_experts

    s = dataclasses.replace(AfmoeConfig(), num_local_experts=32).expert_layer
    mats = [jax.ShapeDtypeStruct((4, 32, 3072, 3072), jnp.bfloat16)] * 3
    assert experts.blocks_plan(s, 12288, mats) == (128, False) and experts.blocks_plan(s, 8192, mats) == (128, False)
    assert experts._call_rows(12288) == 12288 and experts._call_rows(2 * 12288) == 8192
    assert "an expert of 54.00 MiB, over 16 MiB, expects 192 rows, under two blocks of 128" in grouped_experts.refusal(jnp.bfloat16, 3072, 3072, 3, 192, 128, False)
    # the same expert at FULL blocks would go to the kernel: the refusal is of the fill, not of the shape
    assert grouped_experts.refusal(jnp.bfloat16, 3072, 3072, 3, 256, 128, False) is None


def test_trinity_fused_step_fits_one_v5e_aliases_rows_and_rings_and_slices_no_layers_rows(fused_step_for_the_chip, as_on_a_tpu):
    """The fused step at 16 x 12,288 through the SAME ``hybrid_runner.fused_step`` and layer loop as the nine other descriptions
    (``swa mlp`` unrolled, ``swa moe`` scanned three times, ``attn moe`` unrolled): 8.05 GiB of weights and 1.75 GiB of cache, 0.75 of
    it the full layer's rows for every position and 1.0 the four window layers' rings of 4,096 rows; all of it aliased to the
    donated inputs; the live-block kernel under two names at three tiles of query rows, the experts' step kernel, the gate under
    its own scope, and no slice of a layer's rows (384 MiB of keys at 16 x 12,288, 128 MiB a ring) in the compiled text."""
    import re

    cfg, _, cache, state, compiled = fused_step_for_the_chip("trinity")
    assert cfg.layer_plan == (("swa", "moe"), 3, ("attn", "moe"), ("swa", "mlp")) and state == {}
    assert {n: a.shape for n, a in cache.items() if n != "length"} == {
        "k": (1, 16, 12288, 8, 128), "v": (1, 16, 12288, 8, 128), "k_w": (4, 16, 4096, 8, 128), "v_w": (4, 16, 4096, 8, 128)}
    mem, txt = compiled.memory_analysis(), compiled.as_text()
    assert _kv_bytes(cache) == 1_879_048_192 == 2 * (1 * 16 * 12288 + 4 * 16 * 4096) * 2048
    print("trinity fused step:", mem.argument_size_in_bytes / 2**30, mem.alias_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**20)
    assert 9.75 * 2**30 < mem.argument_size_in_bytes < 9.85 * 2**30 and mem.alias_size_in_bytes >= _kv_bytes(cache)
    assert all(name in txt for name in ("slot_decode_attention", "window_decode_attention", "step_experts", "swa.gate", "attn.gate"))
    # the full layer's stack is ONE layer deep: [1,16,12288,8,128] is the entry itself and [16,12288,8,128] a bitcast of it (the
    # new row's scatter is in place); a copy of it would be 384 MiB of temporaries, and the step's are 39
    assert not re.search(r"bf16\[(1,)?16,4096,8,128\]", txt)
    assert mem.temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("prompts, most_gib", [(1, 1.4), (2, 2.9)])
def test_trinity_prefill_of_the_12288_bucket_fits_beside_weights_and_cache_on_one_v5e(one_chip, as_on_a_tpu, prompts, most_gib):
    """The 12,288-bucket prefill (the flash kernel with a window under ``swa``, without one under ``attn``, at 48 heads over 8; the
    gate's projection as wide as the queries; 49,152 routed pairs a prompt through the grouped matmul's LOOP: no ``grouped_experts``
    kernel in the text) for one prompt and for two, beside 8.05 GiB of weights and 1.75 GiB of cache: under 15.75 GiB. Four prompts
    at once do not fit (5.4 GiB of temporaries and 0.94 GiB handed to the cache: 16.1 GiB in all): the engine's reckoning of the
    device's free memory halves such a wave, so the cell's ``warm_batch_max`` of 4 warms groups of 1 and 2."""
    from ray_tpu.llm import hybrid_runner as hr

    cfg, params, _, _ = _cell_at_its_size(one_chip, "trinity")
    tokens = jax.ShapeDtypeStruct((prompts, 12288), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((prompts,), jnp.int32, sharding=one_chip)
    compiled, txt = _compile(partial(hr.prefill, cfg=cfg), params, tokens, lengths)
    mem = compiled.memory_analysis()
    print("trinity prefill:", prompts, mem.argument_size_in_bytes / 2**30, mem.temp_size_in_bytes / 2**30, mem.output_size_in_bytes / 2**30)
    kernels = [line for line in txt.splitlines() if "custom-call(" in line and "tpu_custom_call" in line]
    assert any("window_flash_attention" in line and "/swa/" in line for line in kernels), "the window layers' attention as the flash kernel, under its scope"
    assert any("window_flash_attention" not in line and "/attn/" in line for line in kernels), "and the full layer's without a window"
    assert not any("grouped_experts" in line for line in kernels) and "swa.gate" in txt and "attn.gate" in txt
    assert mem.temp_size_in_bytes < most_gib * 2**30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes + 1.75 * 2**30 < 15.75 * 2**30
