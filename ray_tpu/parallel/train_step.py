"""Sharded training step construction.

Lowers a (model loss_fn, optax optimizer, mesh, sharding rules) tuple to a
single jitted SPMD program: parameters/optimizer state sharded per the
logical rules (FSDP/TP), batch sharded over (dp, fsdp) x sp, gradients
reduced by XLA-inserted collectives over ICI. This is the TPU-native
replacement for the reference's DDP/FSDP wrap + NCCL allreduce
(train/torch/train_loop_utils.py:153,374).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.lint import jaxcheck
from ray_tpu.parallel.mesh import DEFAULT_RULES, ShardingRules, shard_batch_spec
from ray_tpu.util.compile_cache import enable_compile_cache


@dataclass
class TrainState:
    step: Any
    params: Any
    opt_state: Any

    def tree_flatten(self):
        return (self.step, self.params, self.opt_state), None


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.step, s.params, s.opt_state), None),
    lambda _, c: TrainState(*c),
)


def _bucket_train_step(B=32, D=1024):
    """Linear-regression probe state: the donation/dtype/collective
    contracts under test are model-independent."""
    tx = optax.adam(1e-3)
    w = jax.ShapeDtypeStruct((D, D), jnp.float32)
    params = {"w": w}
    opt_state = jax.eval_shape(tx.init, params)
    state = TrainState(step=jax.ShapeDtypeStruct((), jnp.int32), params=params, opt_state=opt_state)
    batch = {
        "x": jax.ShapeDtypeStruct((B, D), jnp.float32),
        "y": jax.ShapeDtypeStruct((B, D), jnp.float32),
    }

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)

    return (state, batch), {"loss_fn": loss_fn, "tx": tx}


@jaxcheck.entry(
    name="parallel.train_step",
    shapes={"b32_d1024": _bucket_train_step},
    donate=("state",),
)
def train_step(state: TrainState, batch, *, loss_fn: Callable, tx: optax.GradientTransformation):
    """One optimizer step — the body every make_train_step program jits
    (state donated; XLA shards it per the caller's in_shardings)."""
    loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
    updates, new_opt = tx.update(grads, state.opt_state, state.params)
    new_params = optax.apply_updates(state.params, updates)
    gnorm = optax.global_norm(grads)
    return (
        TrainState(step=state.step + 1, params=new_params, opt_state=new_opt),
        {"loss": loss, "grad_norm": gnorm, "step": state.step + 1},
    )


def make_train_step(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    param_axes,
    rules: ShardingRules = DEFAULT_RULES,
    donate: bool = True,
):
    """Returns (init_fn, step_fn, state_shardings).

    - init_fn(rng) -> TrainState, sharded at creation (no host gather)
    - step_fn(state, batch) -> (state, metrics); jitted with donation
    """
    enable_compile_cache()
    param_shardings = rules.tree_shardings(param_axes, mesh)
    batch_sharding = NamedSharding(mesh, shard_batch_spec(mesh))
    repl = NamedSharding(mesh, P())

    def _opt_shardings(params_shape, p_shardings):
        # optimizer-state subtrees that mirror the param tree structure
        # (adam mu/nu, momentum, ...) get the param shardings; everything
        # else (step counts, scalars) replicates. Structural matching —
        # NOT shape matching — so same-shaped params with different
        # shardings (e.g. wq vs wo) keep their own layout.
        opt_shape = jax.eval_shape(tx.init, params_shape)
        params_treedef = jax.tree.structure(params_shape)

        def is_param_mirror(sub):
            return jax.tree.structure(sub) == params_treedef

        return jax.tree.map(
            lambda sub: p_shardings if is_param_mirror(sub) else jax.tree.map(lambda _: repl, sub),
            opt_shape,
            is_leaf=is_param_mirror,
        )

    def init_fn(rng, init_params_fn):
        params_shape = jax.eval_shape(init_params_fn, rng)
        opt_shard = _opt_shardings(params_shape, param_shardings)
        state_shardings = TrainState(step=repl, params=param_shardings, opt_state=opt_shard)

        def _init(r):
            params = init_params_fn(r)
            return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params))

        init_jit = jax.jit(_init, out_shardings=state_shardings)
        return init_jit(rng), state_shardings

    def _step(state: TrainState, batch):
        return train_step(state, batch, loss_fn=loss_fn, tx=tx)

    def compile_step(state_shardings):
        return jax.jit(
            _step,
            in_shardings=(state_shardings, batch_sharding),
            out_shardings=(state_shardings, repl),
            donate_argnums=(0,) if donate else (),
        )

    return init_fn, compile_step, batch_sharding


def shard_batch(batch, mesh: Mesh):
    """Device-put a host batch with the canonical batch sharding."""
    sharding = NamedSharding(mesh, shard_batch_spec(mesh))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)
