"""APPO (async clipped-surrogate PPO) + MARWIL (offline advantage-weighted
imitation).

Reference test strategy: rllib/algorithms/appo/tests/test_appo.py
(compilation + learning + target-net/kl-coeff mechanics) and
rllib/algorithms/marwil/tests/test_marwil.py (learning from recorded
data; beta separates it from BC).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
gym = pytest.importorskip("gymnasium")


# ------------------------------------------------------------------- APPO


def _appo_config(**kw):
    from ray_tpu.rllib import APPOConfig

    cfg = (
        APPOConfig()
        .environment("CartPole-v1")
        .env_runners(num_env_runners=0, num_envs_per_env_runner=8)
        .training(lr=1e-3, train_batch_size=4000, entropy_coeff=0.005, rollout_fragment_length=100, vf_loss_coeff=0.25)
        .debugging(seed=0)
    )
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def test_appo_loss_matches_ppo_surrogate_on_policy():
    """With target == behavior == current policy, the IMPACT ratio is 1
    everywhere, so the surrogate term equals the plain V-trace policy
    gradient at ratio 1 and mean_kl is 0."""
    import jax.numpy as jnp

    from ray_tpu.rllib import APPOConfig

    cfg = APPOConfig().environment("CartPole-v1").debugging(seed=0)
    cfg.model = {"fcnet_hiddens": (16,)}
    algo = cfg.build_algo()
    try:
        learner = algo.learner_group._local
        segments, _ = algo.env_runner_group.sample(200)
        batch = algo._build_sequences(segments)
        # target net was just initialized == params; sampler logp is the
        # same policy, so all three logps coincide
        old_logp, old_inputs = learner._target_forward(
            learner.target_params, jnp.asarray(batch["obs"]), jnp.asarray(batch["actions"])
        )
        np.testing.assert_allclose(np.asarray(old_logp)[batch["mask"] > 0], batch["logp"][batch["mask"] > 0], atol=1e-4)
        b = dict(batch)
        b["old_logp"] = np.asarray(old_logp)
        b["old_inputs"] = np.asarray(old_inputs)
        b["kl_coeff"] = np.full((len(b["old_logp"]),), 1.0, np.float32)
        _, aux = learner.compute_losses(learner.params, {k: jnp.asarray(v) for k, v in b.items()})
        assert float(aux["mean_kl"]) < 1e-6
        assert np.isfinite(float(aux["total_loss"]))
    finally:
        algo.stop()


def test_appo_target_network_refresh_and_kl_adaptation():
    from ray_tpu.rllib import APPOConfig

    cfg = APPOConfig().environment("CartPole-v1").debugging(seed=0)
    cfg.model = {"fcnet_hiddens": (16,)}
    cfg.use_kl_loss = True
    cfg.kl_target = 1e-12  # any real KL overshoots -> coeff must grow
    cfg.target_network_update_freq = 2
    cfg.train_batch_size = 400
    cfg.rollout_fragment_length = 50
    algo = cfg.build_algo()
    try:
        learner = algo.learner_group._local
        leaf0 = jax.tree.leaves(learner.target_params)[0].copy()
        algo.train()  # update #1: target NOT refreshed yet (freq=2)
        leaf1 = jax.tree.leaves(learner.target_params)[0]
        np.testing.assert_array_equal(np.asarray(leaf0), np.asarray(leaf1))
        # update #1's loss saw target == current (KL 0 -> coeff halved);
        # update #2 measures the REAL lag between the frozen target and
        # the once-updated policy, overshooting the impossible target ->
        # the 1.5x rule must kick in
        coeff_after_1 = learner._kl_coeff
        algo.train()  # update #2: KL > target -> coeff grows; then hard refresh (tau=1)
        assert learner._kl_coeff > coeff_after_1
        for t, p in zip(jax.tree.leaves(learner.target_params), jax.tree.leaves(learner.params)):
            np.testing.assert_array_equal(np.asarray(t), np.asarray(p))
    finally:
        algo.stop()


def test_appo_cartpole_learns():
    algo = _appo_config().build_algo()
    best = 0.0
    for _ in range(22):
        r = algo.train()
        best = max(best, r["env_runners"]["episode_return_mean"])
        if best >= 40:  # the assertion below holds: further iterations cannot change the verdict
            break
    assert best >= 40, f"APPO failed to learn: best={best}"
    algo.stop()


# ----------------------------------------------------------------- MARWIL


def _mixed_quality_dataset(tmp_path, n_episodes=200, T=8, seed=0):
    """Recorded behavior is a 50/50 coin flip; reward == action. An
    imitator that clones the behavior (BC / beta=0) stays near 50/50;
    advantage re-weighting must tilt toward action 1."""
    from ray_tpu.rllib.offline import write_episodes

    rng = np.random.default_rng(seed)
    episodes = []
    for _ in range(n_episodes):
        obs = rng.uniform(-1, 1, (T + 1, 4)).astype(np.float32)
        actions = rng.integers(0, 2, T)
        episodes.append(
            {
                "obs": obs,
                "actions": actions,
                "rewards": actions.astype(np.float32),
                "logp": np.full(T, np.log(0.5), np.float32),
                "terminated": True,
            }
        )
    ds = str(tmp_path / "mixed")
    write_episodes(ds, episodes)
    return ds


def test_marwil_requires_offline_input():
    from ray_tpu.rllib import MARWILConfig

    cfg = MARWILConfig().environment("CartPole-v1")
    with pytest.raises(ValueError, match="offline"):
        cfg.build_algo()


def test_marwil_upweights_high_advantage_actions(tmp_path):
    """MARWIL with beta>0 beats the behavior policy it was trained from:
    on held-out obs the policy picks the rewarded action far more often
    than the dataset's 50/50 (reference: marwil learning tests)."""
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu.rllib import MARWILConfig

    ds = _mixed_quality_dataset(tmp_path)
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    try:
        cfg = MARWILConfig().environment("CartPole-v1").training(lr=3e-3, train_batch_size=256)
        cfg.input_ = ds
        cfg.beta = 2.0
        cfg.updates_per_iter = 120
        cfg.model = {"fcnet_hiddens": (32, 32)}
        cfg.seed = 0
        algo = cfg.build_algo()
        r = None
        for _ in range(4):
            r = algo.train()
        assert r["dataset_transitions"] == 200 * 8
        assert np.isfinite(r["learner"]["ma_adv_norm"])

        learner = algo.learner_group._local
        rng = np.random.default_rng(7)
        obs = rng.uniform(-1, 1, (256, 4)).astype(np.float32)
        out = learner.module.forward(learner.params, jnp.asarray(obs))
        probs = np.asarray(jax.nn.softmax(out["action_dist_inputs"], axis=-1))
        p1 = float(probs[:, 1].mean())
        assert p1 > 0.75, f"MARWIL stayed near behavior policy: P(a=1)={p1:.3f}"
        algo.stop()
    finally:
        ray_tpu.shutdown()


def test_marwil_beta_zero_reduces_to_cloning(tmp_path):
    """beta=0 removes the advantage weighting: the policy must stay close
    to the recorded 50/50 behavior (the BC degenerate case the reference
    encodes by subclassing BC from MARWIL)."""
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu.rllib import MARWILConfig

    ds = _mixed_quality_dataset(tmp_path)
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    try:
        cfg = MARWILConfig().environment("CartPole-v1").training(lr=3e-3, train_batch_size=256)
        cfg.input_ = ds
        cfg.beta = 0.0
        cfg.updates_per_iter = 120
        cfg.model = {"fcnet_hiddens": (32, 32)}
        cfg.seed = 0
        algo = cfg.build_algo()
        for _ in range(3):
            algo.train()
        learner = algo.learner_group._local
        rng = np.random.default_rng(7)
        obs = rng.uniform(-1, 1, (256, 4)).astype(np.float32)
        out = learner.module.forward(learner.params, jnp.asarray(obs))
        probs = np.asarray(jax.nn.softmax(out["action_dist_inputs"], axis=-1))
        p1 = float(probs[:, 1].mean())
        assert 0.35 < p1 < 0.65, f"beta=0 should clone the 50/50 behavior, got P(a=1)={p1:.3f}"
        algo.stop()
    finally:
        ray_tpu.shutdown()
