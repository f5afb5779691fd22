"""Env runners: CPU rollout workers (reference:
rllib/env/single_agent_env_runner.py:68, sample() :147 and
rllib/env/env_runner_group.py:70).

TPU-native split: rollouts stay on CPU (gymnasium vector envs + a jitted
CPU forward of the same functional RLModule the TPU learner trains);
weight sync ships a params pytree — there is no separate inference model
class to keep in lockstep.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import ray_tpu
from ray_tpu.rllib.env.episode import Episode


def _make_env(env_id, env_config, num_envs):
    import gymnasium as gym

    return gym.make_vec(env_id, num_envs=num_envs, vectorization_mode="sync", **(env_config or {}))


class SingleAgentEnvRunner:
    """Steps `num_envs` vectorized envs; actions from the module's
    exploration pass. Runs inline (local mode) or as a remote actor."""

    def __init__(self, module_spec, env_id: str, env_config: dict | None = None, num_envs: int = 1, seed: int = 0, worker_idx: int = 0, env_to_module=None, module_to_env=None):
        self.envs = _make_env(env_id, env_config, num_envs)
        self.num_envs = num_envs
        self.module = module_spec.build()
        # connector pipelines (rllib/connectors/connector.py, reference
        # connector_v2.py): obs transform applied ONCE at receipt so the
        # module forward AND the learner (via stored episode obs) see the
        # same representation; action transform applied only on the way
        # into env.step (episodes keep module-space actions so replayed
        # logp/Q inputs stay consistent)
        self._env_to_module = env_to_module
        self._module_to_env = module_to_env
        self.params = None
        # rollouts are latency-bound host loops: keep them on the CPU
        # backend even where the process default is the TPU. Asking for
        # the cpu backend initialises every platform JAX_PLATFORMS allows,
        # so a remote runner relies on the runtime's pin (a worker that
        # holds no chip runs with JAX_PLATFORMS=cpu) to stay off the chip;
        # a local runner shares its learner's process, which may hold it.
        try:
            self._device = jax.local_devices(backend="cpu")[0]
        except RuntimeError:
            # JAX_PLATFORMS names only the process's chip: no cpu backend
            self._device = None
        self._key = self._put(jax.random.PRNGKey(seed + 10_000 * worker_idx))
        self._fwd = jax.jit(self.module.forward_exploration)
        obs, _ = self.envs.reset(seed=seed + 10_000 * worker_idx)
        obs = self._obs_transform(obs)
        self._obs = obs
        self._building = [Episode() for _ in range(num_envs)]
        for ep, o in zip(self._building, obs):
            ep.obs.append(np.asarray(o))
        # gymnasium >=1.0 NEXT_STEP autoreset: the step after a terminal
        # ignores the action and returns the reset obs — not a transition
        self._pending_reset = np.zeros(num_envs, dtype=bool)
        # true per-env episode return, accumulated across segment cuts
        self._return_acc = np.zeros(num_envs, dtype=np.float64)
        from collections import deque

        self._episode_returns: deque = deque(maxlen=100)
        self._episodes_this_sample = 0

    def _obs_transform(self, obs):
        if self._env_to_module is None:
            return obs
        return self._env_to_module(obs, action_space=self.envs.single_action_space)

    def _action_transform(self, actions):
        if self._module_to_env is None:
            return actions
        return self._module_to_env(actions, action_space=self.envs.single_action_space)

    def get_connector_states(self) -> dict:
        return {
            "env_to_module": self._env_to_module.get_state() if self._env_to_module else {},
            "module_to_env": self._module_to_env.get_state() if self._module_to_env else {},
        }

    def _put(self, x):
        return jax.device_put(x, self._device) if self._device is not None else jnp.asarray(x)

    def _on_device(self):
        import contextlib

        return jax.default_device(self._device) if self._device is not None else contextlib.nullcontext()

    def set_weights(self, params):
        self.params = jax.tree.map(self._put, params)

    def set_exploration(self, **kw):
        """Push exploration knobs (e.g. an annealed epsilon) onto the
        module's action-distribution class (reference: exploration config
        updates pushed to workers)."""
        cls = self.module.action_dist_cls
        for k, v in kw.items():
            if hasattr(cls, k):
                setattr(cls, k, v)

    def get_spaces(self):
        return self.envs.single_observation_space, self.envs.single_action_space

    def sample(self, num_steps: int, explore: bool = True) -> tuple[list[dict], dict]:
        """Collect ~num_steps env steps (across vector envs); returns
        (episode segment batches, metrics). Segments end at terminal,
        truncation, or collection cut; each carries a bootstrap obs row."""
        with self._on_device():
            return self._sample(num_steps, explore)

    def _sample(self, num_steps: int, explore: bool = True) -> tuple[list[dict], dict]:
        assert self.params is not None, "set_weights before sample"
        segments: list[Episode] = []
        steps_left = num_steps
        self._episodes_this_sample = 0
        dist = self.module.action_dist_cls
        while steps_left > 0:
            out = self._fwd(self.params, jnp.asarray(self._obs))
            inputs = out["action_dist_inputs"]
            if explore:
                self._key, k = jax.random.split(self._key)
                actions = dist.sample(k, inputs)
            else:
                actions = dist.deterministic(inputs)
            logp = dist.logp(inputs, actions)
            actions_np = np.asarray(actions)
            logp_np = np.asarray(logp)
            vf_np = np.asarray(out["vf"])
            obs, rewards, terms, truncs, _ = self.envs.step(self._action_transform(actions_np))
            obs = self._obs_transform(obs)
            for i in range(self.num_envs):
                if self._pending_reset[i]:
                    # this step reset env i: obs[i] is the new episode's
                    # initial obs, the action was ignored — record nothing
                    fresh = Episode()
                    fresh.obs.append(np.asarray(obs[i]))
                    self._building[i] = fresh
                    self._pending_reset[i] = False
                    continue
                ep = self._building[i]
                ep.actions.append(actions_np[i])
                ep.rewards.append(float(rewards[i]))
                ep.logp.append(float(logp_np[i]))
                ep.vf_preds.append(float(vf_np[i]))
                ep.obs.append(np.asarray(obs[i]))  # NEXT_STEP mode: true final obs at a terminal
                self._return_acc[i] += float(rewards[i])
                if terms[i] or truncs[i]:
                    ep.is_terminated = bool(terms[i])
                    self._episode_returns.append(float(self._return_acc[i]))
                    self._episodes_this_sample += 1
                    self._return_acc[i] = 0.0
                    segments.append(ep)
                    self._pending_reset[i] = True
            self._obs = obs
            steps_left -= self.num_envs
        # cut still-running episodes into segments (bootstrap from last obs)
        for i in range(self.num_envs):
            if self._pending_reset[i]:
                continue  # episode already emitted; env resets next step
            ep = self._building[i]
            if len(ep) > 0:
                segments.append(ep)
                fresh = Episode()
                fresh.obs.append(ep.obs[-1])
                self._building[i] = fresh
        returns = list(self._episode_returns)
        metrics = {
            "episode_return_mean": float(np.mean(returns)) if returns else float("nan"),
            "num_episodes": self._episodes_this_sample,
            "num_env_steps": int(num_steps - steps_left),
        }
        return [s.to_batch() for s in segments], metrics


@ray_tpu.remote
class _EnvRunnerActor(SingleAgentEnvRunner):
    pass


class EnvRunnerGroup:
    """N remote env-runner actors, or one local runner when
    num_env_runners == 0 (reference env_runner_group.py local-worker
    semantics)."""

    def __init__(self, module_spec, env_id, env_config=None, num_env_runners: int = 0, num_envs_per_env_runner: int = 1, seed: int = 0, output: str | None = None, env_to_module=None, module_to_env=None):
        self.num_env_runners = num_env_runners
        # offline-data recording (reference: offline/json_writer.py via
        # config.offline_data(output=...)): every collected episode batch
        # is appended to JSONL shards as it arrives at the driver
        self._writer = None
        if output:
            from ray_tpu.rllib.offline import JsonWriter

            self._writer = JsonWriter(output)
        if num_env_runners == 0:
            self._local = SingleAgentEnvRunner(
                module_spec, env_id, env_config, num_envs_per_env_runner, seed,
                env_to_module=env_to_module, module_to_env=module_to_env,
            )
            self._actors = []
        else:
            self._local = None
            self._actors = [
                _EnvRunnerActor.remote(
                    module_spec, env_id, env_config, num_envs_per_env_runner, seed, worker_idx=i + 1,
                    env_to_module=env_to_module, module_to_env=module_to_env,
                )
                for i in range(num_env_runners)
            ]

    def get_spaces(self):
        if self._local is not None:
            return self._local.get_spaces()
        return ray_tpu.get(self._actors[0].get_spaces.remote())

    def sync_weights(self, params):
        params = jax.tree.map(np.asarray, params)
        if self._local is not None:
            self._local.set_weights(params)
        else:
            ray_tpu.get([a.set_weights.remote(params) for a in self._actors])

    def set_exploration(self, **kw):
        if self._local is not None:
            self._local.set_exploration(**kw)
        else:
            ray_tpu.get([a.set_exploration.remote(**kw) for a in self._actors])

    def sample(self, num_steps: int, explore: bool = True):
        """Returns (all segment batches, per-runner metrics list)."""
        if self._local is not None:
            segs, m = self._local.sample(num_steps, explore)
            self._record(segs)
            return segs, [m]
        return self.collect(self.sample_async(num_steps, explore))

    def sample_async(self, num_steps: int, explore: bool = True):
        """Kick off sampling on every remote runner; returns refs for
        collect() (lets IMPALA overlap sampling with the learner update)."""
        assert self._actors, "sample_async requires remote env runners"
        per = max(1, num_steps // len(self._actors))
        return [a.sample.remote(per, explore) for a in self._actors]

    def collect(self, refs):
        outs = ray_tpu.get(refs)
        segments: list[dict] = []
        metrics = []
        for segs, m in outs:
            segments.extend(segs)
            metrics.append(m)
        self._record(segments)
        return segments, metrics

    def _record(self, segments):
        if self._writer is not None:
            for s in segments:
                self._writer.write(s)

    def stop(self):
        if self._writer is not None:
            self._writer.close()
            self._writer = None
        for a in self._actors:
            ray_tpu.kill(a)
        self._actors = []
