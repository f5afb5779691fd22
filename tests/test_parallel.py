"""Mesh, sharded train step, ring/ulysses attention tests (8-dev CPU mesh)."""

from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from ray_tpu.models.llama import (  # noqa: E402
    LlamaConfig,
    forward,
    init_params,
    loss_fn,
    param_logical_axes,
)
from ray_tpu.ops.flash_attention import attention_xla, flash_attention  # noqa: E402
from ray_tpu.parallel.mesh import MeshConfig, create_mesh, mesh_axes  # noqa: E402
from ray_tpu.parallel.ring_attention import sp_attention  # noqa: E402
from ray_tpu.parallel.train_step import make_train_step, shard_batch  # noqa: E402


def test_mesh_builder():
    mesh = create_mesh(dp=2, tp=4)
    assert mesh_axes(mesh) == {"dp": 2, "tp": 4}
    mesh = create_mesh(dp=-1, tp=2)
    assert mesh_axes(mesh) == {"dp": 4, "tp": 2}
    with pytest.raises(ValueError):
        MeshConfig(dp=3, tp=3).resolve(8)


def test_llama_forward_shapes():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert jnp.isfinite(logits).all()


def test_llama_causality():
    """Changing a future token must not affect earlier logits."""
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    t1 = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    t2 = t1.at[0, 5].set(100)
    l1 = forward(params, t1, cfg)
    l2 = forward(params, t2, cfg)
    np.testing.assert_allclose(l1[0, :5], l2[0, :5], atol=1e-4)
    assert not np.allclose(l1[0, 5:], l2[0, 5:], atol=1e-4)


def test_flash_attention_matches_reference():
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (2, 4, 64, 32), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 64, 32))
    v = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 64, 32))
    out = flash_attention(q, k, v, True, None)  # xla fallback on cpu
    ref = attention_xla(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_flash_attention_grads():
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 32, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 32, 16))
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 32, 16))

    def f(q, k, v):
        return flash_attention(q, k, v, True, None).sum()

    def ref(q, k, v):
        return attention_xla(q, k, v, causal=True).sum()

    g1 = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_flash_attention_gqa():
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 32, 16))
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 32, 16))
    out = flash_attention(q, k, v, True, None)
    kb = jnp.repeat(k, 4, axis=1)
    vb = jnp.repeat(v, 4, axis=1)
    ref = attention_xla(q, kb, vb, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_pallas_flash_interpret_matches():
    """Pallas kernel correctness via interpreter mode (no TPU needed)."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops.flash_attention import _flash_fwd_pallas

    q = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 256, 128), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 256, 128))
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 256, 128))
    with pltpu.force_tpu_interpret_mode():
        out, lse = _flash_fwd_pallas(q, k, v, causal=True)
    ref = attention_xla(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3)
    ref_lse = jax.nn.logsumexp(
        jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * q.shape[-1] ** -0.5
        + jnp.where(
            jnp.tril(jnp.ones((256, 256), bool))[None, None], 0.0, -1e30
        ),
        axis=-1,
    )
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-3)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_sequence_parallel_attention(impl):
    mesh = create_mesh(dp=2, sp=4)
    B, H, T, D = 2, 8, 64, 16
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, T, D))
    k = jax.random.normal(jax.random.PRNGKey(2), (B, H, T, D))
    v = jax.random.normal(jax.random.PRNGKey(3), (B, H, T, D))
    out = sp_attention(q, k, v, mesh, impl=impl, causal=True)
    ref = attention_xla(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_ring_attention_grads_match_reference():
    """Custom-VJP ring backward (second ring pass rotating k/v/dk/dv)
    matches full-attention autodiff."""
    mesh = create_mesh(dp=2, sp=4)
    B, H, T, D = 2, 4, 64, 16
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, T, D))
    k = jax.random.normal(jax.random.PRNGKey(2), (B, H, T, D))
    v = jax.random.normal(jax.random.PRNGKey(3), (B, H, T, D))

    def f(q, k, v):
        return (sp_attention(q, k, v, mesh, impl="ring", causal=True) ** 2).sum()

    def ref(q, k, v):
        return (attention_xla(q, k, v, causal=True) ** 2).sum()

    g1 = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_ring_attention_chunked_path():
    """Multi-chunk local attention (chunk < T/sp) stays exact: the local
    [Tl, Tl] score matrix is never built, only [Tl, chunk] slabs."""
    import functools

    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.ring_attention import ring_attention_local

    mesh = create_mesh(sp=8)
    B, H, T, D = 1, 2, 128, 16
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, T, D))
    k = jax.random.normal(jax.random.PRNGKey(2), (B, H, T, D))
    v = jax.random.normal(jax.random.PRNGKey(3), (B, H, T, D))
    fn = jax.shard_map(
        functools.partial(ring_attention_local, axis_name="sp", causal=True, chunk=4),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
        check_vma=False,
    )
    out = jax.jit(fn)(q, k, v)
    ref = attention_xla(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    g = jax.jit(jax.grad(lambda q, k, v: (fn(q, k, v) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(lambda q, k, v: (attention_xla(q, k, v, causal=True) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize(
    "axes",
    [dict(dp=8), dict(dp=2, fsdp=4), dict(fsdp=8), dict(dp=2, fsdp=2, tp=2), dict(dp=2, tp=4)],
)
def test_train_step_sharding_configs(axes):
    """DP/FSDP/TP configs all converge on the virtual mesh."""
    cfg = LlamaConfig.tiny()
    mesh = create_mesh(**axes)
    init_fn, compile_step, _ = make_train_step(
        partial(loss_fn, config=cfg), optax.adamw(1e-3), mesh, param_logical_axes(cfg)
    )
    state, shardings = init_fn(jax.random.PRNGKey(0), partial(init_params, cfg))
    step = compile_step(shardings)
    rng = np.random.default_rng(0)
    batch = shard_batch(
        {
            "tokens": rng.integers(0, 512, (8, 32)).astype(np.int32),
            "targets": rng.integers(0, 512, (8, 32)).astype(np.int32),
        },
        mesh,
    )
    state, m0 = step(state, batch)
    for _ in range(5):
        state, m = step(state, batch)
    assert float(m["loss"]) < float(m0["loss"])


def test_pipeline_parallel_parity_and_training():
    """GPipe pipeline over the pp axis: logits/grads match the non-pp
    model, a full sharded train step converges, and stage weights are
    actually sharded 1/pp per device. (f32 on CPU: XLA's CPU backend
    crashes promoting bf16 all-reduces; TPU runs bf16.)"""
    import optax

    from ray_tpu.parallel.pipeline import (
        from_stage_stacked,
        pp_forward,
        pp_init_params,
        pp_loss_fn,
        pp_param_logical_axes,
        to_stage_stacked,
    )

    cfg = LlamaConfig.tiny(num_layers=4, dtype="float32")
    mesh = create_mesh(pp=4, dp=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    pp_params = {**params, "layers": to_stage_stacked(params["layers"], 4)}
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32)
    batch = {"tokens": tokens, "targets": targets}

    np.testing.assert_allclose(
        np.asarray(jax.jit(lambda p: pp_forward(p, tokens, cfg, mesh, num_microbatches=4))(pp_params)),
        np.asarray(forward(params, tokens, cfg)),
        atol=1e-5,
    )
    # one compiled program each: taken op by op, the pipeline's backward pass alone was 30 s of this test
    g_ref = jax.jit(jax.grad(lambda p: loss_fn(p, batch, cfg)))(params)
    g_pp = jax.jit(jax.grad(lambda p: pp_loss_fn(p, batch, cfg, mesh, num_microbatches=4)))(pp_params)
    g_pp = {**g_pp, "layers": from_stage_stacked(g_pp["layers"])}
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5),
        g_ref,
        g_pp,
    )

    init_fn, compile_step, _ = make_train_step(
        partial(pp_loss_fn, config=cfg, mesh=mesh, num_microbatches=4),
        optax.adamw(1e-3),
        mesh,
        pp_param_logical_axes(cfg, 4),
    )
    state, shardings = init_fn(jax.random.PRNGKey(0), partial(pp_init_params, cfg, n_stages=4))
    step = compile_step(shardings)
    from ray_tpu.parallel.train_step import shard_batch as _sb

    sbatch = _sb({"tokens": np.asarray(tokens), "targets": np.asarray(targets)}, mesh)
    state, m0 = step(state, sbatch)
    for _ in range(4):
        state, m = step(state, sbatch)
    assert float(m["loss"]) < float(m0["loss"])
    wq = state.params["layers"]["wq"]
    assert wq.addressable_shards[0].data.nbytes * 4 == wq.nbytes  # 1/pp per device


def test_interleaved_pipeline_parity_and_training():
    """Interleaved (virtual-stage) schedule: device d owns chunks d, d+n,
    ...; activation ring with zero-idle handoffs cuts the pipeline
    fill/drain bubble by the virtual factor ((n-1)/v stage-times vs
    GPipe's (n-1)). Logits and grads must match the plain model AND the
    GPipe schedule exactly."""
    import optax

    from ray_tpu.parallel.pipeline import (
        from_stage_stacked,
        pp_forward,
        pp_init_params,
        pp_loss_fn,
        pp_param_logical_axes,
        to_stage_stacked,
    )

    cfg = LlamaConfig.tiny(num_layers=8, dtype="float32")
    mesh = create_mesh(pp=2, dp=4)
    params = init_params(cfg, jax.random.PRNGKey(1))
    v = 2  # 2 virtual stages x 2 devices = 4 chunks of 2 layers
    pp_params = {**params, "layers": to_stage_stacked(params["layers"], 2, v)}
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32)
    batch = {"tokens": tokens, "targets": targets}

    # round-robin layout roundtrip is lossless
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        from_stage_stacked(pp_params["layers"]),
        params["layers"],
    )

    np.testing.assert_allclose(
        np.asarray(jax.jit(lambda p: pp_forward(p, tokens, cfg, mesh, num_microbatches=4, virtual_stages=v))(pp_params)),
        np.asarray(forward(params, tokens, cfg)),
        atol=1e-5,
    )
    g_ref = jax.jit(jax.grad(lambda p: loss_fn(p, batch, cfg)))(params)
    g_pp = jax.jit(jax.grad(lambda p: pp_loss_fn(p, batch, cfg, mesh, num_microbatches=4, virtual_stages=v)))(pp_params)
    g_pp = {**g_pp, "layers": from_stage_stacked(g_pp["layers"])}
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5),
        g_ref,
        g_pp,
    )

    # a full sharded train step converges under the interleaved schedule
    init_fn, compile_step, _ = make_train_step(
        partial(pp_loss_fn, config=cfg, mesh=mesh, num_microbatches=4, virtual_stages=v),
        optax.adamw(1e-3),
        mesh,
        pp_param_logical_axes(cfg, 2, v),
    )
    state, shardings = init_fn(
        jax.random.PRNGKey(0), partial(pp_init_params, cfg, n_stages=2, virtual_stages=v)
    )
    step = compile_step(shardings)
    from ray_tpu.parallel.train_step import shard_batch as _sb

    sbatch = _sb({"tokens": np.asarray(tokens), "targets": np.asarray(targets)}, mesh)
    state, m0 = step(state, sbatch)
    for _ in range(4):
        state, m = step(state, sbatch)
    assert float(m["loss"]) < float(m0["loss"])

    # microbatch count must group by pp size under interleaving
    with pytest.raises(ValueError, match="divisible by pp"):
        pp_forward(pp_params, tokens, cfg, mesh, num_microbatches=1, virtual_stages=v)


def test_pp_sp_ring_attention_parity():
    """pp x sp composition: ONE shard_map region manual over {pp, sp}
    runs ring attention inside each pipeline stage (pipeline_apply
    sp_axis). Forward logits and layer grads match the unsharded model
    exactly — the config the reference cannot express at all (it has no
    sequence parallelism, SURVEY.md §5.7)."""
    from ray_tpu.parallel.pipeline import (
        from_stage_stacked,
        pp_forward,
        pp_loss_fn,
        to_stage_stacked,
    )

    cfg = LlamaConfig.tiny(num_layers=4, dtype="float32", max_seq_len=64)
    mesh = create_mesh(pp=2, sp=2, dp=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    pp_params = {**params, "layers": to_stage_stacked(params["layers"], 2)}
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 64)), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}

    np.testing.assert_allclose(
        np.asarray(jax.jit(lambda p, t: pp_forward(p, t, cfg, mesh, num_microbatches=4))(pp_params, tokens)),
        np.asarray(forward(params, tokens, cfg)),
        atol=2e-4,
    )
    g_ref = jax.jit(jax.grad(lambda p: loss_fn(p, batch, cfg)))(params)
    g_pp = jax.jit(jax.grad(lambda p: pp_loss_fn(p, batch, cfg, mesh, num_microbatches=4)))(pp_params)
    g_pp = {**g_pp, "layers": from_stage_stacked(g_pp["layers"])}
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4),
        g_ref,
        g_pp,
    )


def test_pp_tp_long_sequence_head_sharded_attention():
    """Head sharding over tp remains an alternative to pp x sp for long
    sequences in pipelined configs (Ulysses-style resharding is what
    GSPMD inserts for the sharded attention). End-to-end: a pp=2 x tp=2
    x dp=2 train step at a long-for-tests sequence length runs and
    converges."""
    import optax

    from ray_tpu.parallel.pipeline import pp_init_params, pp_loss_fn, pp_param_logical_axes

    cfg = LlamaConfig.tiny(num_layers=4, dtype="float32", max_seq_len=512)
    mesh = create_mesh(pp=2, dp=2, tp=2)
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, cfg.vocab_size, (4, 512)).astype(np.int32)
    targets = rng.integers(0, cfg.vocab_size, (4, 512)).astype(np.int32)

    init_fn, compile_step, _ = make_train_step(
        partial(pp_loss_fn, config=cfg, mesh=mesh, num_microbatches=2),
        optax.adamw(1e-3),
        mesh,
        pp_param_logical_axes(cfg, 2),
    )
    state, shardings = init_fn(jax.random.PRNGKey(0), partial(pp_init_params, cfg, n_stages=2))
    step = compile_step(shardings)
    from ray_tpu.parallel.train_step import shard_batch as _sb

    sbatch = _sb({"tokens": tokens, "targets": targets}, mesh)
    state, m0 = step(state, sbatch)
    state, m1 = step(state, sbatch)
    assert np.isfinite(float(m1["loss"])) and float(m1["loss"]) < float(m0["loss"])
    # attention weights genuinely head-sharded over tp (1/(pp*tp) bytes per device)
    wq = state.params["layers"]["wq"]
    assert wq.addressable_shards[0].data.nbytes * 4 == wq.nbytes


def test_fsdp_actually_shards_params():
    cfg = LlamaConfig.tiny()
    mesh = create_mesh(fsdp=8)
    init_fn, _, _ = make_train_step(
        partial(loss_fn, config=cfg), optax.adamw(1e-3), mesh, param_logical_axes(cfg)
    )
    state, _ = init_fn(jax.random.PRNGKey(0), partial(init_params, cfg))
    wq = state.params["layers"]["wq"]
    # embed dim sharded 8-ways: each device holds 1/8 of the bytes
    shard_bytes = wq.addressable_shards[0].data.nbytes
    assert shard_bytes * 8 == wq.nbytes
