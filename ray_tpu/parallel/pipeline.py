"""Pipeline parallelism: GPipe-style microbatch pipelining over the `pp`
mesh axis, inside ONE jitted SPMD program.

TPU-native replacement for the reference's compiled-graph pipelines
(python/ray/dag/compiled_dag_node.py + experimental/channel/
torch_tensor_accelerator_channel.py): where the reference wires actor
stages together with NCCL channels and a compiled schedule, here the
schedule IS the XLA program — stages are devices along the `pp` mesh
axis, activations hop stage-to-stage with `lax.ppermute` (a neighbor
copy on ICI/DCN), and the whole (M + n - 1)-tick loop is a `lax.scan`
that jax.grad differentiates into the reverse pipeline automatically.

Design:
- layer-stacked params [L, ...] are reshaped to [n_stages, v, L/(n*v), ...]
  (v = virtual_stages, 1 for GPipe) and sharded `P('pp')` on the leading
  dim: each device materializes only its own chunks' weights (the pp
  memory win). With v > 1 the chunks are placed round-robin: device d
  owns model chunks d, d+n, ..., d+(v-1)n.
- the batch is split into M microbatches. GPipe (v=1): at tick t, stage 0
  feeds microbatch t; every stage applies its L/n layers; the result hops
  to the next stage; total ticks = M + n - 1, bubble fraction
  (n-1)/(M+n-1). Interleaved (v>1): the activation stream rides a RING
  (wraparound n-1 -> 0 between chunk rounds); total ticks = M*v + n - 1
  in 1/v-sized chunk-times, so the fill/drain bubble shrinks to
  (n-1)/v stage-times — the Megatron-style virtual-pipeline schedule.
- shard_map is manual ONLY over `pp` (`axes` arg) — dp/fsdp/tp stay
  auto, so XLA still shards batch/params inside each stage exactly as in
  the non-pp program.
- embedding/unembedding stay OUTSIDE the pipeline region (auto-sharded;
  their FLOPs are marginal), which keeps their gradients trivially
  correct: the transpose of the replicated-in/psum-out shard_map handles
  the stage-gated activations.

Composition notes: pp × {dp, fsdp, tp, sp} are all supported. pp × sp
does NOT nest shard_maps (JAX forbids that): pipeline_apply(sp_axis=...)
makes the ONE region manual over {pp, sp} and runs ring attention's
local form (manual ppermute collectives, ring_attention_local) inside
the stage body, with activations sequence-sharded and RoPE tables passed
as sp-sharded seq_inputs. dp/fsdp/tp stay auto inside either way.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.lint import jaxcheck


def to_stage_stacked(layer_params, n_stages: int, virtual_stages: int = 1):
    """[L, ...]-stacked layer params -> [n_stages, v, L/(n*v), ...].

    With virtual_stages v > 1 (interleaved schedule), device d owns model
    chunks d, d+n, ..., d+(v-1)n — round-robin layer placement, so chunk
    r on device d covers layers [(r*n + d) * L/(nv), ...). Dim 0 shards
    P('pp'); dim 1 indexes the device's local chunk round."""

    def reshape(leaf):
        L = leaf.shape[0]
        if L % (n_stages * virtual_stages):
            raise ValueError(f"num_layers {L} not divisible by pp*virtual = {n_stages}*{virtual_stages}")
        per = L // (n_stages * virtual_stages)
        # chunk k covers layers [k*per, (k+1)*per); chunk k lives on
        # device k % n as local round k // n
        chunked = leaf.reshape(n_stages * virtual_stages, per, *leaf.shape[1:])
        return (
            chunked.reshape(virtual_stages, n_stages, per, *leaf.shape[1:])
            .swapaxes(0, 1)  # [n, v, per, ...]
        )

    return jax.tree.map(reshape, layer_params)


def from_stage_stacked(layer_params):
    """[n_stages, v, L/(n*v), ...] -> [L, ...] (inverse chunk layout)."""

    def restore(leaf):
        n, v, per = leaf.shape[:3]
        return leaf.swapaxes(0, 1).reshape(n * v * per, *leaf.shape[3:])

    return jax.tree.map(restore, layer_params)


def pipeline_apply(
    stage_params,
    x,
    *,
    mesh: Mesh,
    layer_fn: Callable,
    num_microbatches: int,
    virtual_stages: int = 1,
    axis_name: str = "pp",
    sp_axis: str | None = None,
    seq_inputs: tuple = (),
):
    """Run stage-stacked layers over x with microbatch pipelining.

    virtual_stages=1 is the GPipe schedule: M microbatches flow through n
    device-stages; bubble fraction (n-1)/(M+n-1) in stage-time units.

    virtual_stages=v>1 is the INTERLEAVED schedule (Megatron-style virtual
    pipeline, reference capability: compiled multi-stage pipelines in
    dag/compiled_dag_node.py): device d owns model chunks d, d+n, ...,
    d+(v-1)n, each 1/v of a stage. Microbatch m of group g runs chunk
    round r on device d at tick d + g*v*n + r*n + m; the activation ring
    (ppermute with wraparound n-1 -> 0) hands off with zero idle ticks,
    so total ticks = M*v + (n-1) CHUNK-times — the pipeline fill/drain
    costs (n-1)/v stage-times instead of GPipe's (n-1): the bubble
    shrinks by the virtual-stage factor. Requires M % n == 0 (microbatch
    groups of n keep every device on exactly one chunk per tick).

    stage_params: pytree with leading [n_stages, v, L/(n*v), ...] dims,
      sharded P('pp') on dim 0. layer_fn(x, layer, *seq_locals) applies
      ONE layer. x: [B, ...] activations (NOT sharded over pp).
    Returns [B, ...] outputs (replicated over pp after the closing psum).

    pp x sp composition: with ``sp_axis`` set, the ONE shard_map region
    goes manual over BOTH axes — ring attention cannot nest its own
    shard_map inside the pp region, but its local form
    (ring_attention_local, manual ppermute collectives over sp) runs
    directly in the stage body. Activations shard their sequence dim
    (axis 2 of the microbatched [M, mb, T, ...]) over sp; ``seq_inputs``
    are per-position arrays ([T, ...], e.g. RoPE cos/sin) sharded over
    sp on dim 0 and handed to layer_fn as extra args. dp/fsdp/tp stay
    auto inside, exactly as without sp. (The reference cannot compose
    these at all — SURVEY.md §5.7: it has no sequence parallelism.)
    """
    n = mesh.shape[axis_name]
    B = x.shape[0]
    M = num_microbatches
    v = int(virtual_stages)
    if B % M:
        raise ValueError(f"batch {B} not divisible by num_microbatches {M}")
    if v > 1 and M % n:
        raise ValueError(f"interleaved schedule needs num_microbatches ({M}) divisible by pp ({n})")
    mb = B // M
    x_mb = x.reshape(M, mb, *x.shape[1:])

    def local(stage_p, xs, *seq_locals):
        # stage_p: [1, v, L/(n*v), ...] (this device's chunks); xs: [M, mb, ...]
        my = lax.axis_index(axis_name)
        stage_p = jax.tree.map(lambda t: t[0], stage_p)  # [v, per, ...]

        def apply_chunk(act, r):
            chunk = jax.tree.map(lambda t: lax.dynamic_index_in_dim(t, r, axis=0, keepdims=False), stage_p)

            def body(carry, layer):
                return layer_fn(carry, layer, *seq_locals), None

            out, _ = lax.scan(body, act, chunk)
            return out

        # interleaved: a ring — device n-1's output wraps to device 0 as
        # the next chunk round's input. GPipe (v=1) never reads the
        # wrapped value, so drop that edge and save the hop.
        ring_perm = [(i, (i + 1) % n) for i in range(n if v > 1 else n - 1)]
        jobs = M * v  # chunk applications per device

        def tick(carry, t):
            state, outputs = carry
            j = jnp.clip(t - my, 0, jobs - 1)  # this device's job index
            active = jnp.logical_and(t >= my, t - my < jobs)
            g = j // (v * n)  # microbatch group
            jj = j % (v * n)
            r = jj // n  # chunk round
            m = jj % n  # member within the group
            mb_idx = jnp.minimum(g * n + m, M - 1)
            feed = lax.dynamic_index_in_dim(xs, mb_idx, axis=0, keepdims=False)
            inp = jnp.where(jnp.logical_and(my == 0, r == 0), feed, state)
            out = apply_chunk(inp, r)
            # the final logical stage (chunk round v-1 on device n-1)
            # banks its microbatch's output
            bank = jnp.logical_and(jnp.logical_and(my == n - 1, r == v - 1), active)
            cur = lax.dynamic_index_in_dim(outputs, mb_idx, axis=0, keepdims=False)
            outputs = lax.dynamic_update_index_in_dim(
                outputs, jnp.where(bank, out, cur), mb_idx, axis=0
            )
            state = lax.ppermute(out, axis_name, ring_perm) if n > 1 else out
            return (state, outputs), None

        init = jax.tree.map(
            lambda t: lax.pcast(t, (axis_name,), to="varying"),
            (jnp.zeros_like(xs[0]), jnp.zeros_like(xs)),
        )
        (_, outputs), _ = lax.scan(tick, init, jnp.arange(M * v + n - 1))
        # only the last stage holds real outputs; psum broadcasts them so
        # the (auto-sharded) unembed/loss outside sees one consistent value.
        # f32 for the wire: XLA's bf16 all-reduce promotion pass crashes on
        # CPU, and f32 costs nothing extra on TPU (promotion does it anyway)
        gated = jnp.where(my == n - 1, outputs, jnp.zeros_like(outputs)).astype(jnp.float32)
        return lax.psum(gated, axis_name).astype(outputs.dtype)

    if sp_axis is None:
        x_spec = P()
        seq_specs = tuple(P() for _ in seq_inputs)
        manual = {axis_name}
    else:
        # [M, mb, T, ...]: sequence dim sharded over sp
        x_spec = P(None, None, sp_axis)
        seq_specs = tuple(P(sp_axis) for _ in seq_inputs)
        manual = {axis_name, sp_axis}
    # vma checking stays on: the psum'd output is inferred replicated, and
    # the transpose (grad) relies on that typing
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis_name), x_spec) + seq_specs,
        out_specs=x_spec,
        axis_names=manual,
    )
    out_mb = fn(stage_params, x_mb, *seq_inputs)
    return out_mb.reshape(B, *x.shape[1:])


# ----------------------------------------------------------------------
# Llama integration: pipelined forward/loss drop-ins
# ----------------------------------------------------------------------
def pp_param_logical_axes(config, n_stages: int, virtual_stages: int = 1):
    """param_logical_axes for pp: layer leaves are [n_stages, v, L/(n*v),
    *dims], logical axes ('stage', None, None, *per-layer axes)."""
    from ray_tpu.models.llama import PARAM_AXES, param_logical_axes

    axes = param_logical_axes(config)
    axes["layers"] = {
        k: ("stage", None, None) + tuple(v[1:]) for k, v in PARAM_AXES["layers"].items()
    }
    return axes


def pp_init_params(config, key, n_stages: int, virtual_stages: int = 1):
    """init_params with the layer stack reshaped to [n_stages, v, L/(n*v), ...]."""
    from ray_tpu.models.llama import init_params

    params = init_params(config, key)
    params["layers"] = to_stage_stacked(params["layers"], n_stages, virtual_stages)
    return params


def _sp_local_layer_fn(x, layer, cos_l, sin_l, *, config):
    """One llama layer on a LOCAL sequence shard, inside a region manual
    over {pp, sp}: per-token ops (norms, projections, MLP) need no
    communication; attention is the manual-collective ring
    (ring_attention_local — ppermute over sp on ICI). cos_l/sin_l are
    this shard's RoPE tables."""
    from ray_tpu.ops.layers import apply_rope, rms_norm
    from ray_tpu.parallel.ring_attention import ring_attention_local

    B, Tl, H = x.shape
    nh, nkv, hd = config.num_heads, config.num_kv_heads, config.hd
    xn = rms_norm(x, layer["attn_norm"], config.rms_eps)
    q = jnp.dot(xn, layer["wq"]).reshape(B, Tl, nh, hd).transpose(0, 2, 1, 3)
    k = jnp.dot(xn, layer["wk"]).reshape(B, Tl, nkv, hd).transpose(0, 2, 1, 3)
    v = jnp.dot(xn, layer["wv"]).reshape(B, Tl, nkv, hd).transpose(0, 2, 1, 3)
    q = apply_rope(q, cos_l, sin_l)
    k = apply_rope(k, cos_l, sin_l)
    rep = nh // nkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    o = ring_attention_local(q, k, v, axis_name="sp", causal=True)
    o = o.transpose(0, 2, 1, 3).reshape(B, Tl, nh * hd)
    x = x + jnp.dot(o, layer["wo"])
    xn = rms_norm(x, layer["mlp_norm"], config.rms_eps)
    g = jnp.dot(xn, layer["w_gate"])
    u = jnp.dot(xn, layer["w_up"])
    return x + jnp.dot(jax.nn.silu(g) * u, layer["w_down"])


def _bucket_pp_forward(B=8, T=128, n_stages=2):
    """Tile-true abstract shapes on a pp-only mesh (fully manual shard_map,
    so the trace works on any >=2-device backend)."""
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel.mesh import create_mesh

    cfg = LlamaConfig(
        vocab_size=32256, hidden_size=1024, intermediate_size=2816,
        num_layers=4, num_heads=8, num_kv_heads=8, head_dim=128, remat=False,
    )
    mesh = create_mesh(pp=n_stages)
    params = jax.eval_shape(lambda: pp_init_params(cfg, jax.random.PRNGKey(0), n_stages))
    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32)
    return (params, tokens, cfg, mesh, 4), {}


@jaxcheck.entry(
    name="parallel.pipeline_forward",
    shapes={"pp2_b8_t128": _bucket_pp_forward},
    mesh_axes=("pp", "sp"),
)
def pp_forward(params, tokens, config, mesh: Mesh, num_microbatches: int, virtual_stages: int = 1):
    """Pipelined llama forward: embed -> pp pipeline over layers -> unembed.
    When the mesh also has an `sp` axis, the pipeline region goes manual
    over {pp, sp} and runs ring attention per stage (pp x sp — see
    pipeline_apply; the reference has no sequence parallelism at all)."""
    from ray_tpu.models.llama import _layer_fn
    from ray_tpu.ops.layers import rms_norm, rotary_embedding

    B, T = tokens.shape
    positions = jnp.arange(T, dtype=jnp.int32)
    cos, sin = rotary_embedding(positions, config.hd, config.rope_theta, dtype=jnp.float32)
    x = jnp.take(params["embed"], tokens, axis=0)

    sp = "sp" if "sp" in mesh.axis_names and mesh.shape.get("sp", 1) > 1 else None
    if sp is not None:
        layer_fn = functools.partial(_sp_local_layer_fn, config=config)
        seq_inputs = (cos, sin)
    else:
        layer_fn = functools.partial(_layer_fn, config=config, cos=cos, sin=sin, positions=positions)
        seq_inputs = ()
    if config.remat:
        policy = getattr(jax.checkpoint_policies, config.remat_policy)
        layer_fn = jax.checkpoint(layer_fn, policy=policy)

    x = pipeline_apply(
        params["layers"], x, mesh=mesh, layer_fn=layer_fn,
        num_microbatches=num_microbatches, virtual_stages=virtual_stages,
        sp_axis=sp, seq_inputs=seq_inputs,
    )
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    unembed = params["embed"].T if config.tie_embeddings else params["unembed"]
    return jnp.dot(x, unembed, preferred_element_type=jnp.float32)


def pp_loss_fn(params, batch, config, mesh: Mesh, num_microbatches: int, virtual_stages: int = 1):
    from ray_tpu.ops.layers import cross_entropy_loss

    logits = pp_forward(params, batch["tokens"], config, mesh, num_microbatches, virtual_stages)
    return cross_entropy_loss(logits, batch["targets"])
