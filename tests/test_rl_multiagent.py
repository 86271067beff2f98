"""Multi-agent RL tests (VERDICT r2 #6; reference:
``rllib/env/multi_agent_env_runner.py`` + multi-agent Algorithm paths)."""

import numpy as np
import pytest

import ray_tpu  # noqa: F401 (fixture wiring)
from ray_tpu.rl.multi_agent import (
    GuideFollowEnv,
    MultiAgentPPOConfig,
)


def test_guide_follow_env_contract():
    env = GuideFollowEnv(episode_length=4)
    obs, _ = env.reset()
    assert set(obs) == {"guide", "follower"}
    total = {"guide": 0.0, "follower": 0.0}
    for t in range(4):
        obs, rew, term, trunc, _ = env.step(
            {"guide": t % 2, "follower": t % 2})
        for a in total:
            total[a] += rew[a]
    assert term["__all__"]
    assert total == {"guide": 4.0, "follower": 4.0}  # optimal play


def test_multi_agent_runner_maps_policies(ray_start_regular):
    """Trajectories group under the MAPPED policy ids, one trajectory per
    agent per episode."""
    from ray_tpu.rl.multi_agent import MultiAgentPPO

    algo = MultiAgentPPOConfig(
        num_env_runners=1, episodes_per_sample=3, seed=0,
        policy_mapping_fn=lambda a: f"{a}_policy").build()
    try:
        assert set(algo.policy_specs) == {"guide_policy", "follower_policy"}
        sample = ray_tpu.get(algo.runners[0].sample.remote())
        trajs = sample["trajectories"]
        assert set(trajs) == {"guide_policy", "follower_policy"}
        assert len(trajs["guide_policy"]) == 3
        traj = trajs["guide_policy"][0]
        assert traj["obs"].shape == (6, 6)  # episode_length x one-hot
        assert traj["rewards"].shape == (6,)
    finally:
        algo.stop()


def test_shared_policy_mapping(ray_start_regular):
    """All agents can share one policy (parameter sharing)."""
    algo = MultiAgentPPOConfig(
        num_env_runners=1, episodes_per_sample=2, seed=0,
        policy_mapping_fn=lambda a: "shared").build()
    try:
        assert set(algo.policy_specs) == {"shared"}
        m = algo.train()
        assert m["env_steps_this_iter"] > 0
    finally:
        algo.stop()


@pytest.mark.timeout_s(400)
def test_multi_agent_ppo_learns_guide_follow(ray_start_regular):
    """Run-to-reward: both policies approach optimal (6.0 each) — the
    follower can only score by learning the guide's pattern, so this fails
    if per-policy updates or weight routing are broken. Seeded; generous
    budget for loaded CI boxes."""
    algo = MultiAgentPPOConfig(
        seed=0, num_env_runners=2, episodes_per_sample=16,
        policy_mapping_fn=lambda a: f"{a}_policy").build()
    try:
        best = {}
        for _ in range(60):
            m = algo.train()
            for a, v in (m.get("agent_return_mean") or {}).items():
                best[a] = max(best.get(a, -np.inf), v)
            if best.get("guide", 0) >= 5.5 and best.get("follower", 0) >= 5.0:
                break
        assert best.get("guide", 0) >= 5.5, best
        assert best.get("follower", 0) >= 5.0, best
    finally:
        algo.stop()
