import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.agent import (PPOAgent, PPOConfig, actor_logits, greedy_step,
                              init_params, policy_step)
from repro.core.features import CV_SIZE, MAX_QUEUE_SIZE, OV_SIZE


def _state(n=16, seed=0):
    rng = np.random.default_rng(seed)
    ov = np.zeros((MAX_QUEUE_SIZE, OV_SIZE), np.float32)
    cv = np.zeros((MAX_QUEUE_SIZE, CV_SIZE), np.float32)
    ov[:n] = rng.random((n, OV_SIZE))
    cv[:n] = rng.random((n, CV_SIZE))
    mask = np.zeros((MAX_QUEUE_SIZE,), np.float32)
    mask[:n] = 1
    return ov, cv, mask


def test_masked_actions_never_selected():
    agent = PPOAgent(PPOConfig(seed=0))
    ov, cv, mask = _state(5)
    for _ in range(20):
        a, logits = agent.act(ov, cv, mask, explore=True, record=False)
        assert a < 5
    assert (logits[5:] < -1e8).all()


def test_greedy_is_argsort():
    params = init_params(PPOConfig())
    ov, cv, mask = _state(8)
    order = np.asarray(greedy_step(params, jnp.asarray(ov), jnp.asarray(mask)))
    lg = np.asarray(actor_logits(params, jnp.asarray(ov), jnp.asarray(mask)))
    assert order[0] == int(np.argmax(lg))


def _greedy_act_reference(params, ov, mask):
    """Greedy ``act`` as separate steps: the rows and mask put on the
    device, the order read back, the rank-encoded logits rebuilt on the
    host.  Returns ``(order, action, logits)``."""
    order = greedy_step(params, jnp.asarray(ov), jnp.asarray(mask))
    logits = np.zeros(mask.shape, dtype=np.float32)
    logits[np.asarray(order)] = -np.arange(len(mask), dtype=np.float32)
    return np.asarray(order), int(order[0]), logits


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("n", [1, 17, 255, 256])
def test_greedy_act_matches_reference(n, ties):
    agent = PPOAgent(PPOConfig(seed=4))
    ov, cv, mask = _state(n, seed=n)
    if ties:        # exact logit ties: rows drawn from three distinct ones
        pick = np.random.default_rng(n).integers(0, 3, n)
        ov[:n] = ov[pick % min(n, 3)]
    order, want_action, want_logits = _greedy_act_reference(
        agent.params, ov, mask)
    action, logits = agent.act(ov, cv, mask, explore=False, record=False)
    assert type(action) is int
    assert action == want_action == int(order[0])
    assert action < n
    assert logits.dtype == np.float32 and logits.shape == mask.shape
    np.testing.assert_array_equal(logits, want_logits)
    assert agent.rollout_len == 0


def test_greedy_act_reads_params_at_call_time():
    """Callers swap ``params["actor"]`` after construction; the next call
    ranks with the new weights."""
    agent = PPOAgent(PPOConfig(seed=0))
    other = init_params(PPOConfig(seed=7))
    ov, cv, mask = _state(40, seed=1)
    before = agent.act(ov, cv, mask, explore=False, record=False)[1]
    agent.params["actor"] = other["actor"]
    _, want_action, want = _greedy_act_reference(agent.params, ov, mask)
    action, logits = agent.act(ov, cv, mask, explore=False, record=False)
    assert action == want_action
    np.testing.assert_array_equal(logits, want)
    assert not np.array_equal(logits, before)


def test_logp_matches_softmax():
    params = init_params(PPOConfig())
    ov, cv, mask = _state(6)
    out = policy_step(params, jnp.asarray(ov), jnp.asarray(cv),
                      jnp.asarray(mask), jax.random.PRNGKey(0))
    lg = actor_logits(params, jnp.asarray(ov), jnp.asarray(mask))
    want = jax.nn.log_softmax(lg)[out["action"]]
    assert abs(float(out["logp"] - want)) < 1e-5


def test_ppo_update_changes_params():
    agent = PPOAgent(PPOConfig(seed=1))
    before = jax.tree.map(np.array, agent.params)
    ov, cv, mask = _state(10)
    for _ in range(8):
        agent.act(ov, cv, mask, explore=True, record=True)
    stats = agent.finish_episode(reward=1.0)
    assert stats["steps"] == 8
    after = agent.params
    diffs = jax.tree.map(lambda a, b: float(np.abs(a - b).max()), before, after)
    assert max(jax.tree.leaves(diffs)) > 0


def test_positive_reward_reinforces_actions():
    """Positive-reward episodes on action 2 must raise its probability.
    (Episodes where other actions were sampled are dropped, isolating the
    reinforcement property from Adam's sign-noise under per-episode updates.)"""
    agent = PPOAgent(PPOConfig(seed=2, lr=3e-3, entropy_coef=0.0))
    ov, cv, mask = _state(4, seed=3)
    lg0 = actor_logits(agent.params, jnp.asarray(ov), jnp.asarray(mask))
    p0 = float(np.asarray(jax.nn.softmax(lg0))[2])
    updates = 0
    while updates < 12:
        agent.reset_buffer()
        a, _ = agent.act(ov, cv, mask, explore=True, record=True)
        if a == 2:
            agent.finish_episode(reward=1.0)
            updates += 1
        else:
            agent.reset_buffer()
    lg = actor_logits(agent.params, jnp.asarray(ov), jnp.asarray(mask))
    probs = np.asarray(jax.nn.softmax(lg))[:4]
    assert probs[2] > p0, (p0, probs)
    assert probs[2] == probs.max()


def test_state_dict_roundtrip():
    a = PPOAgent(PPOConfig(seed=0))
    b = PPOAgent(PPOConfig(seed=9))
    b.load_state_dict(a.state_dict())
    ov, cv, mask = _state(5)
    la = actor_logits(a.params, jnp.asarray(ov), jnp.asarray(mask))
    lb = actor_logits(b.params, jnp.asarray(ov), jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb))


def test_episodes_per_update_pooling():
    """With episodes_per_update=3, updates trigger only every 3rd episode."""
    agent = PPOAgent(PPOConfig(seed=5, episodes_per_update=3))
    ov, cv, mask = _state(6)
    updated = []
    for ep in range(7):
        agent.reset_buffer()
        for _ in range(3):
            agent.act(ov, cv, mask, explore=True, record=True)
        st = agent.finish_episode(reward=0.5)
        updated.append(st["updated"])
    assert updated == [0.0, 0.0, 1.0, 0.0, 0.0, 1.0, 0.0]
