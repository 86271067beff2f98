"""Off-policy RL tests: replay buffers, DQN (run-to-reward), offline BC.

Reference model: rllib per-algorithm test dirs + replay-buffer unit tests
+ offline BC from logged data.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rl import BCConfig, DQNConfig, ReplayBuffer, SumTree
from ray_tpu.rl.dqn import rollout_to_transitions


def test_sum_tree_proportional():
    tree = SumTree(8)
    tree.set([0, 1, 2], [1.0, 3.0, 6.0])
    assert tree.total == pytest.approx(10.0)
    rng = np.random.default_rng(0)
    counts = np.zeros(8)
    draws = 4000
    idx = np.concatenate([tree.sample(8, rng) for _ in range(draws // 8)])
    for i in idx:
        counts[i] += 1
    freq = counts / draws
    assert freq[2] > freq[1] > freq[0] > 0
    assert freq[2] == pytest.approx(0.6, abs=0.05)
    assert counts[3:].sum() == 0  # zero-priority slots never sampled


def test_replay_buffer_wraparound_and_sampling():
    buf = ReplayBuffer(capacity=10)
    for start in range(0, 25, 5):
        buf.add({"x": np.arange(start, start + 5, dtype=np.int64)})
    assert len(buf) == 10
    batch, idx, w = buf.sample(32)
    # Only the newest 10 values survive the ring.
    assert batch["x"].min() >= 15
    assert np.all(w == 1.0)


def test_prioritized_replay_prefers_high_td():
    buf = ReplayBuffer(capacity=16, prioritized=True, seed=1)
    buf.add({"x": np.arange(16, dtype=np.int64)})
    # Slot 5 gets a huge TD error, everything else tiny.
    buf.update_priorities(np.arange(16), np.full(16, 1e-3))
    buf.update_priorities(np.array([5]), np.array([10.0]))
    batch, idx, w = buf.sample(256)
    frac5 = np.mean(batch["x"] == 5)
    assert frac5 > 0.5  # dominates sampling
    assert w.min() > 0 and w.max() == pytest.approx(1.0)
    # IS weight of the over-sampled slot is the smallest.
    assert w[batch["x"] == 5].mean() < w[batch["x"] != 5].mean()


def test_rollout_to_transitions_drops_synthetic_rows():
    T, N = 4, 1
    obs = np.arange(T * N).reshape(T, N).astype(np.float32)[..., None]
    ro = {
        "obs": obs,
        "actions": np.zeros((T, N), np.int64),
        "rewards": np.ones((T, N), np.float32),
        "dones": np.array([[0], [1], [0], [0]], np.float32),
        "valids": np.array([[1], [1], [0], [1]], np.float32),
    }
    out = rollout_to_transitions(ro)
    # Row 2 is the autoreset step -> dropped; row 3 has no successor.
    assert len(out["rewards"]) == 2
    np.testing.assert_allclose(out["dones"], [0, 1])
    np.testing.assert_allclose(out["next_obs"][:, 0], [1, 2])


def test_dqn_single_iteration(ray_start_regular):
    algo = DQNConfig().environment("CartPole-v1").env_runners(
        2, num_envs_per_runner=2).training(
        rollout_length=16, learning_starts=32, batch_size=32,
        train_batches_per_iter=4).build()
    try:
        m1 = algo.train()
        assert m1["env_steps_this_iter"] > 0
        assert m1["buffer_size"] > 0
        for _ in range(3):
            m = algo.train()
        assert m["learner_steps"] > 0 and "loss" in m
        assert m["epsilon"] < algo.config.epsilon_initial
    finally:
        algo.stop()


@pytest.mark.slow  # 38 s: DQN replay-buffer convergence soak
@pytest.mark.timeout_s(420)
def test_dqn_learns_cartpole(ray_start_regular):
    """Run-to-reward, UN-SKIPPED in PR 10: the PR 3 triage was right
    that the 2-runner plateau (best=52 over 80 iterations) was not a
    budget problem — it was replay-stream correlation. On the Podracer
    substrate (4 RolloutActors x 4 envs feeding prioritized replay
    through the object plane, one pjit learner, pubsub weight fan-out)
    the SAME hyperparameters and seed clear the bar: probed best=151
    at iteration 33, ~19 s wall on the 1-core CI box.

    This is also the off-policy half of the ISSUE 10 acceptance e2e:
    >= 4 RolloutActors + pjit learner to the reward bar, with the
    object-plane descriptor contract, per-actor version monotonicity,
    and leak-free shutdown asserted on the REAL learning run."""
    from ray_tpu.rl.distributed import DESCRIPTOR_BYTE_BUDGET

    algo = DQNConfig().environment("CartPole-v1").distributed_rollouts(
        4, num_envs_per_actor=4).training(
        rollout_length=64, lr=1e-3, batch_size=128,
        learning_starts=500, train_batches_per_iter=48,
        target_update_interval=100, epsilon_decay_steps=6000,
        prioritized_replay=True, seed=2).build()
    try:
        best, first = 0.0, None
        metrics = {}
        for _ in range(60):
            metrics = algo.train()
            ret = metrics.get("episode_return_mean")
            if ret is not None:
                if first is None:
                    first = ret
                best = max(best, ret)
            if best >= 120.0:
                break
        assert first is not None
        assert best >= 100.0, f"DQN failed to learn: first={first}, best={best}"
        # Acceptance contracts, asserted on the learning run itself:
        assert algo.plane.monotonic_violations == 0
        # Fan-out version clock: initial publish + one per iteration.
        assert metrics["weights_version"] == \
            metrics["training_iteration"] + 1
        rl = metrics["rl"]
        assert rl["env_steps"] > 0 and "queue_depth" in rl
        assert rl["shard_desc_bytes"]["p99"] <= DESCRIPTOR_BYTE_BUDGET
        assert rl["shard_desc_bytes"]["count"] >= rl["shards"]
        assert rl["staleness"]["count"] == rl["shards"]
        assert rl["learner_update_s"]["count"] == metrics["learner_steps"]
    finally:
        algo.stop()
    assert algo.last_leak_report["queue_depth"] == 0
    assert algo.last_leak_report["intake_alive"] is False


@pytest.mark.timeout_s(420)
def test_bc_clones_policy_offline(ray_start_regular):
    """Offline pipeline: train PPO briefly, record its experience into a
    Dataset, clone with BC, and check the clone acts like the teacher
    (action accuracy high, eval return >= random baseline)."""
    from ray_tpu.rl import PPOConfig, collect_dataset

    teacher = PPOConfig().environment("CartPole-v1").env_runners(
        2, num_envs_per_runner=4).training(
        rollout_length=128, minibatch_size=256, seed=11).build()
    try:
        for _ in range(8):
            teacher.train()
        ds = collect_dataset(teacher, num_rollouts=2)
        assert ds.count() > 500
    finally:
        teacher.stop()

    bc = BCConfig().environment("CartPole-v1").training(
        epochs=6, batch_size=256, seed=11).build(ds)
    metrics = bc.train()
    assert metrics["rows_trained"] > 0
    assert metrics["action_accuracy"] is not None
    # The teacher is stochastic: a deterministic clone's accuracy on
    # SAMPLED teacher actions is capped by teacher entropy — well above
    # chance (0.5) is the meaningful bar.
    assert metrics["action_accuracy"] > 0.55
    ev = bc.evaluate(num_episodes=5)
    assert ev["episode_return_mean"] > 40.0


# ------------------------------------------------------------------- SAC
# (VERDICT r2 #6: an off-policy continuous-control algorithm.
# Reference: rllib/algorithms/sac/sac.py)


def test_sac_single_iteration(ray_start_regular):
    from ray_tpu.rl import SACConfig

    algo = SACConfig(env="Pendulum-v1", seed=3, num_env_runners=1,
                     warmup_steps=64, updates_per_iteration=4).build()
    try:
        m1 = algo.train()
        assert m1["env_steps_this_iter"] > 0
        m2 = algo.train()
        assert m2["env_steps_total"] > m1["env_steps_total"]
        assert "critic_loss" in m2  # learning began after warmup
        # Continuous actions flow end-to-end: buffer holds float actions.
        batch, _, _ = algo.buffer.sample(8)
        assert batch["actions"].dtype == np.float32
        assert batch["actions"].shape[1:] == (1,)
    finally:
        algo.stop()


# Tier-1 budget triage (ISSUE 11): this was the single slowest tier-1
# test at 51.9 s (2026-08-05 profile, suite 801 s vs the 870 s cap) —
# run-to-reward SAC is ~5k jitted updates + env steps on the 1-core
# box, and like CQL above it is update-bound, so parallel rollouts
# can't shrink the wall. Verified passing (best > -600 within budget)
# before slow-marking; it still runs (and passes) outside tier-1, and
# SAC's machinery stays covered in tier-1 by the action-space /
# replay / offline-roundtrip tests in this file.
@pytest.mark.slow
@pytest.mark.timeout_s(400)
def test_sac_learns_pendulum(ray_start_regular):
    """Run-to-reward: SAC pulls Pendulum well above the random baseline
    (~-1220) within a bounded budget. Seeded; the threshold is generous
    because this suite runs on loaded CI boxes."""
    from ray_tpu.rl import SACConfig

    algo = SACConfig(env="Pendulum-v1", seed=1, num_env_runners=2,
                     updates_per_iteration=48, warmup_steps=800).build()
    try:
        best = -float("inf")
        for _ in range(110):
            m = algo.train()
            best = max(best, m.get("episode_return_mean", -float("inf")))
            if best > -600:
                break
        assert best > -600, f"SAC stuck at {best}"
    finally:
        algo.stop()


# ------------------------------------------------------------------- CQL
# (VERDICT r3 #6: offline pipeline + an offline algorithm beyond BC.
# Reference: rllib/algorithms/cql/cql.py + rllib/offline/)


def test_offline_transitions_roundtrip_parquet(ray_start_regular, tmp_path):
    """Transitions Dataset -> parquet -> Dataset -> ReplayBuffer keeps
    every canonical column and row count (reference: offline output
    writers + input readers over ray.data)."""
    from ray_tpu.rl import SACConfig
    from ray_tpu.rl.offline import (TRANSITION_COLUMNS, dataset_to_buffer,
                                    load_transitions, rollouts_to_dataset,
                                    save_transitions)

    algo = SACConfig(env="Pendulum-v1", num_env_runners=1,
                     num_envs_per_runner=2, rollout_length=16,
                     seed=3).build()
    try:
        ds = rollouts_to_dataset(algo, num_rollouts=2)
    finally:
        algo.stop()
    n = ds.count()
    assert n > 30
    save_transitions(ds, str(tmp_path / "logs"))
    back = load_transitions(str(tmp_path / "logs"))
    assert back.count() == n
    buf = dataset_to_buffer(back, seed=0)
    assert len(buf) == n
    batch, _idx, _w = buf.sample(16)
    for col in TRANSITION_COLUMNS:
        assert col in batch and len(batch[col]) == 16
    # Obs keep their feature shape through the tabular round-trip.
    assert batch["obs"].shape[1:] == batch["next_obs"].shape[1:]
    assert batch["obs"].shape[1:] == (3,)


def _scripted_pendulum_dataset(n_episodes: int, noise: float, seed: int):
    """Near-expert behavior data from an energy swing-up + PD-catch
    controller (mean return ~ -135), with Gaussian action noise for state
    coverage. Stored actions use the runner convention ([-1, 1])."""
    import gymnasium as gym

    from ray_tpu import data as rdata

    env = gym.make("Pendulum-v1")
    rng = np.random.default_rng(seed)
    cols = {c: [] for c in ("obs", "actions", "rewards", "next_obs",
                            "terminateds")}
    for ep in range(n_episodes):
        obs, _ = env.reset(seed=seed + ep)
        done = False
        while not done:
            cos_th, sin_th, thdot = obs
            th = np.arctan2(sin_th, cos_th)
            energy = 0.5 * thdot ** 2 + 10.0 * (cos_th - 1.0)
            # SMOOTH blend of PD-catch and energy pumping: a hard switch
            # would make the behavior multi-modal near the switching
            # surface, and no unimodal clone (BC or CQL actor) can fit
            # opposing torques averaged to zero.
            pd = -(10.0 * th + 2.0 * thdot)
            pump = -thdot * energy
            w = (1.0 / (1.0 + np.exp(-10.0 * (cos_th - 0.8)))
                 * 1.0 / (1.0 + np.exp(-4.0 * (4.0 - abs(thdot)))))
            u = w * pd + (1.0 - w) * pump
            u = float(np.clip(u / 2.0 + rng.normal(0.0, noise), -1.0, 1.0))
            nobs, reward, term, trunc, _ = env.step([u * 2.0])
            cols["obs"].append(obs.astype(np.float32))
            cols["actions"].append(np.float32(u))
            cols["rewards"].append(np.float32(reward))
            cols["next_obs"].append(nobs.astype(np.float32))
            cols["terminateds"].append(np.float32(term))
            obs = nobs
            done = term or trunc
    env.close()
    return rdata.from_numpy({
        "obs": np.stack(cols["obs"]),
        "actions": np.asarray(cols["actions"])[:, None],
        "rewards": np.asarray(cols["rewards"]),
        "next_obs": np.stack(cols["next_obs"]),
        "terminateds": np.asarray(cols["terminateds"]),
    })


# Re-probed in PR 10 (the DQN un-skip pass): CQL now PASSES the -900
# bar on this image — first eval -1544, adaptive budget recovers to
# -792 on the 3rd extension — but takes ~144 s wall on the 1-core box,
# which does not fit the tier-1 870 s envelope (suite baseline ~770 s).
# Slow-marked instead of skipped: it runs (and passes) outside tier-1.
# Unlike DQN, CQL is OFFLINE — parallel rollouts cannot speed it up;
# the wall time is 1600+ jitted updates on one core.
@pytest.mark.slow
@pytest.mark.timeout_s(500)
def test_cql_learns_pendulum_offline(ray_start_regular):
    """Run-to-reward OFFLINE: train CQL purely from a logged near-expert
    dataset (no env interaction during learning) and check the offline
    policy lands far above random and near the behavior policy."""
    from ray_tpu.rl import CQLConfig

    ds = _scripted_pendulum_dataset(n_episodes=30, noise=0.15, seed=7)
    assert ds.count() == 30 * 200

    cql = CQLConfig(env="Pendulum-v1", seed=7).training(
        updates_per_iteration=400, cql_alpha=10.0, bc_iters=1200).build(ds)
    for _ in range(4):
        m = cql.train()
    assert np.isfinite(m["critic_loss"])
    # Behavior mean ~ -160, random ~ -1200, untrained actor ~ -1400.
    # Measured: ~ -600..-700 after 1600 updates (BC warm start reaches
    # it; the conservative fine-tune HOLDS it — without the CQL term the
    # flat-Q entropy gradient diffuses the policy back to random).
    # XLA-CPU reduction order varies run-to-run under load, so the budget
    # is ADAPTIVE: train a bit more if the first eval misses the bar.
    best = cql.evaluate(num_episodes=5)["episode_return_mean"]
    for _extra in range(3):
        if best > -900.0:
            break
        cql.train()
        best = max(best, cql.evaluate(num_episodes=5)
                   ["episode_return_mean"])
    assert best > -900.0, best


# --------------------------------------------- connector pipelines (r5)
# Module-to-env action connectors + learner connectors (VERDICT r4 Weak #6
# / Next #9; reference: rllib/connectors/module_to_env/, connectors/learner/)


def test_action_connector_units():
    from ray_tpu.rl.connectors import ClipAction, RescaleAction, UnsquashAction

    uns = UnsquashAction(low=[-2.0], high=[2.0])
    out = uns(np.array([[-1.0], [0.0], [1.0], [3.0]]))  # 3.0 clips to 1
    assert np.allclose(out, [[-2.0], [0.0], [2.0], [2.0]])
    clip = ClipAction(low=[-0.5], high=[0.5])
    assert np.allclose(clip(np.array([[-2.0], [0.2]])), [[-0.5], [0.2]])
    res = RescaleAction(scale=2.0, shift=1.0)
    assert np.allclose(res(np.array([[1.0]])), [[3.0]])
    with pytest.raises(ValueError):
        UnsquashAction(low=[-np.inf], high=[np.inf])


def test_learner_connector_normalizes_advantages():
    from ray_tpu.rl.connectors import (NormalizeAdvantages,
                                       apply_learner_connectors)

    batch = {"advantages": np.array([1.0, 2.0, 3.0, 4.0], np.float32),
             "obs": np.zeros((4, 2))}
    out = apply_learner_connectors([NormalizeAdvantages()], batch)
    assert abs(float(out["advantages"].mean())) < 1e-6
    assert abs(float(out["advantages"].std()) - 1.0) < 1e-5
    assert out["obs"] is batch["obs"]  # other keys untouched
    # Original batch not mutated.
    assert batch["advantages"][0] == 1.0


class _RecordingActionConnector:
    """Test connector: counts calls, passes actions through."""

    def __init__(self):
        self.calls = 0
        self.last_min = None
        self.last_max = None

    def __call__(self, actions):
        self.calls += 1
        self.last_min = float(np.min(actions))
        self.last_max = float(np.max(actions))
        return actions


@pytest.mark.timeout_s(240)
def test_sac_runs_through_action_connector_chain(ray_start_regular):
    """SAC's continuous actions flow through an explicit module-to-env
    chain (unsquash to env bounds, then clip tighter) — structural
    continuous-control support, not per-policy rescale hacks."""
    from ray_tpu.rl import SACConfig
    from ray_tpu.rl.connectors import ClipAction, UnsquashAction

    algo = SACConfig(env="Pendulum-v1", seed=3, num_env_runners=1,
                     warmup_steps=64, updates_per_iteration=2).training(
        action_connectors=[UnsquashAction(low=[-2.0], high=[2.0]),
                           ClipAction(low=[-1.5], high=[1.5])]).build()
    try:
        m = algo.train()
        assert m["env_steps_this_iter"] > 0
        # Policy-space actions ([-1, 1]) are what the buffer stores; the
        # clip applies only on the env side.
        batch, _, _ = algo.buffer.sample(8)
        assert np.abs(batch["actions"]).max() <= 1.0 + 1e-6
    finally:
        algo.stop()


@pytest.mark.timeout_s(240)
def test_cql_evaluate_uses_action_connectors(ray_start_regular):
    """CQL's evaluation rollouts map actions through the connector chain
    (observable in-process: the recorder sees every step)."""
    from ray_tpu import data as rdata
    from ray_tpu.rl import CQLConfig
    from ray_tpu.rl.connectors import UnsquashAction

    rec = _RecordingActionConnector()
    n = 64
    ds = rdata.from_numpy({
        "obs": np.random.default_rng(0).normal(size=(n, 3)).astype(
            np.float32),
        "actions": np.zeros((n, 1), np.float32),
        "rewards": np.zeros(n, np.float32),
        "next_obs": np.zeros((n, 3), np.float32),
        "terminateds": np.zeros(n, np.float32),
    }, num_blocks=2)
    cql = CQLConfig(env="Pendulum-v1", seed=0).training(
        updates_per_iteration=2,
        action_connectors=[rec, UnsquashAction(low=[-2.0],
                                               high=[2.0])]).build(ds)
    cql.train()
    out = cql.evaluate(num_episodes=1)
    assert rec.calls >= 200  # one Pendulum episode = 200 steps
    assert "episode_return_mean" in out
    # Recorder saw POLICY-space actions (inside [-1, 1], pre-unsquash).
    assert -1.0 - 1e-6 <= rec.last_min and rec.last_max <= 1.0 + 1e-6
