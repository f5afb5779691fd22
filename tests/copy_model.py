"""The deterministic 'copy model' the serving tests share."""

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.llama import init_params


def copy_model_params(cfg, period: int = 16, seed: int = 0):
    """Same architecture and per-step FLOPs as the random-weight model
    (zeroed weights still multiply at full cost), but greedy decode
    provably follows a fixed successor map with short cycles — attention
    and MLP blocks are zeroed so the residual stream carries the token
    embedding to an unembed matrix wired column-for-column to each
    token's successor. Top-1 margins are O(1), not O(1e-3): the
    repetitive-suffix regime prompt-lookup drafting exploits, and the
    one where a bounded-drift cache or collective must keep argmax."""
    params = init_params(cfg, jax.random.PRNGKey(seed))
    E = np.asarray(params["embed"], np.float32)
    ids = np.arange(cfg.vocab_size)
    succ = (ids // period) * period + (ids % period + 1) % period  # cycle inside period-blocks
    U = np.zeros((E.shape[1], cfg.vocab_size), np.float32)
    U[:, succ] = E.T  # argmax(rms(E[t]) @ U) = succ(t): |E[t]|^2 dominates cross terms
    zero_layers = jax.tree.map(jnp.zeros_like, params["layers"])
    return {**params, "layers": zero_layers, "unembed": jnp.asarray(U, dtype=params["unembed"].dtype)}
