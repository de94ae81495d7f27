"""PPO Actor-Critic agent in JAX (Sec. 3.2, Fig. 9).

The actor is a 3-layer MLP applied per-job with shared weights (the paper's
"sliding-window" evaluation) over the 8-feature Observation Vector; a softmax
over the queue yields normalized priorities.  The critic is a 3-layer MLP over
the flattened 5-feature Critic Vector (all jobs at once) estimating the batch
return.  MAX_QUEUE_SIZE = 256 with zero-padding keeps state/action spaces
fixed.  Training uses PPO-clip over one of two reward pathways:

- **terminal** (paper-faithful, ``finish_episode``): the sparse batch reward
  is the normalized base-vs-RL performance gap, assigned to every step
  (gamma = 1); pinned bit-identical for the legacy batch trainer.
- **dense** (``finish_episode_dense``, used by ``repro.rl``): per-step shaped
  rewards from rolling-telemetry deltas with GAE(gamma, lambda) advantages —
  the streaming-episode pathway.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.features import CV_SIZE, MAX_QUEUE_SIZE, OV_SIZE
from repro.obs.spans import span

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class PPOConfig:
    actor_hidden: tuple[int, int] = (64, 32)
    critic_hidden: tuple[int, int] = (128, 64)
    lr: float = 3e-4
    clip_eps: float = 0.2
    value_coef: float = 0.5
    entropy_coef: float = 0.01
    update_epochs: int = 4
    max_grad_norm: float = 0.5
    max_steps: int = 512          # trajectory padding length
    episodes_per_update: int = 1  # >1: batch episodes before PPO (beyond-paper
    #                               variance reduction; 1 = paper-faithful)
    gamma: float = 0.99           # dense-reward discount (GAE pathway only;
    gae_lambda: float = 0.95      #  the terminal pathway stays gamma = 1)
    seed: int = 0


# ------------------------------------------------------------------ networks ----


def _mlp_init(key: jax.Array, sizes: list[int], scale: float = 1.0) -> list[dict]:
    layers = []
    for i, (fan_in, fan_out) in enumerate(zip(sizes[:-1], sizes[1:])):
        key, sub = jax.random.split(key)
        s = scale if i == len(sizes) - 2 else 1.0
        w = jax.random.normal(sub, (fan_in, fan_out)) * s * jnp.sqrt(2.0 / fan_in)
        layers.append({"w": w, "b": jnp.zeros((fan_out,))})
    return layers


def _mlp_apply(layers: list[dict], x: jnp.ndarray) -> jnp.ndarray:
    # full f32 dots: the TPU default (one bf16 pass) reorders near-tied
    # queue logits against the CPU and the float64 reference
    for i, lyr in enumerate(layers):
        x = jnp.dot(x, lyr["w"], precision=jax.lax.Precision.HIGHEST) \
            + lyr["b"]
        if i < len(layers) - 1:
            x = jnp.tanh(x)
    return x


def init_params(cfg: PPOConfig, key: jax.Array | None = None) -> Params:
    key = jax.random.PRNGKey(cfg.seed) if key is None else key
    ka, kc = jax.random.split(key)
    h1, h2 = cfg.actor_hidden
    c1, c2 = cfg.critic_hidden
    return {
        "actor": _mlp_init(ka, [OV_SIZE, h1, h2, 1], scale=0.01),
        "critic": _mlp_init(kc, [MAX_QUEUE_SIZE * CV_SIZE, c1, c2, 1], scale=0.1),
    }


def actor_logits(params: Params, ov: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """(Q, 8), (Q,) -> masked logits (Q,).  Shared MLP per job (sliding window)."""
    logits = _mlp_apply(params["actor"], ov)[..., 0]
    return jnp.where(mask > 0, logits, -1e9)


def value(params: Params, cv: jnp.ndarray) -> jnp.ndarray:
    """(Q, 5) -> scalar value estimate."""
    return _mlp_apply(params["critic"], cv.reshape(-1))[0]


@functools.partial(jax.jit, static_argnames=())
def policy_step(params: Params, ov: jnp.ndarray, cv: jnp.ndarray,
                mask: jnp.ndarray, key: jax.Array) -> dict[str, jnp.ndarray]:
    """One decision: sample an action (job index), return logp/value/logits."""
    logits = actor_logits(params, ov, mask)
    action = jax.random.categorical(key, logits)
    logp = jax.nn.log_softmax(logits)[action]
    return {"action": action, "logp": logp, "value": value(params, cv),
            "logits": logits}


@jax.jit
def greedy_step(params: Params, ov: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Deterministic ranking (descending priority) for evaluation."""
    logits = actor_logits(params, ov, mask)
    return jnp.argsort(-logits)


# ---------------------------------------------------------------------- Adam -----


def adam_init(params: Params) -> dict:
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"m": zeros, "v": jax.tree.map(jnp.zeros_like, params), "t": jnp.zeros(())}


def adam_update(params: Params, grads: Params, state: dict, lr: float,
                b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                max_norm: float = 0.5) -> tuple[Params, dict]:
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (gnorm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    t = state["t"] + 1
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state["v"], grads)
    mhat = jax.tree.map(lambda x: x / (1 - b1 ** t), m)
    vhat = jax.tree.map(lambda x: x / (1 - b2 ** t), v)
    params = jax.tree.map(lambda p, mh, vh: p - lr * mh / (jnp.sqrt(vh) + eps),
                          params, mhat, vhat)
    return params, {"m": m, "v": v, "t": t}


# ----------------------------------------------------------------- PPO update ----


def _ppo_loss(params: Params, batch: dict, clip_eps: float, value_coef: float,
              entropy_coef: float) -> jnp.ndarray:
    def per_step(ov, cv, mask, action, old_logp, ret, adv, valid):
        logits = actor_logits(params, ov, mask)
        logp_all = jax.nn.log_softmax(logits)
        logp = logp_all[action]
        ratio = jnp.exp(logp - old_logp)
        clipped = jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps)
        pg = -jnp.minimum(ratio * adv, clipped * adv)
        v = value(params, cv)
        v_loss = (v - ret) ** 2
        probs = jax.nn.softmax(logits)
        ent = -jnp.sum(jnp.where(mask > 0, probs * logp_all, 0.0))
        return valid * (pg + value_coef * v_loss - entropy_coef * ent)

    losses = jax.vmap(per_step)(
        batch["ov"], batch["cv"], batch["mask"], batch["action"],
        batch["logp"], batch["ret"], batch["adv"], batch["valid"])
    return jnp.sum(losses) / jnp.maximum(jnp.sum(batch["valid"]), 1.0)


@functools.partial(jax.jit, static_argnames=("clip_eps", "value_coef",
                                             "entropy_coef", "lr", "max_norm"))
def ppo_update_step(params: Params, opt_state: dict, batch: dict, *,
                    clip_eps: float, value_coef: float, entropy_coef: float,
                    lr: float, max_norm: float) -> tuple[Params, dict, jnp.ndarray]:
    loss, grads = jax.value_and_grad(_ppo_loss)(
        params, batch, clip_eps, value_coef, entropy_coef)
    params, opt_state = adam_update(params, grads, opt_state, lr,
                                    max_norm=max_norm)
    return params, opt_state, loss


def gae_advantages(rewards: np.ndarray, values: np.ndarray,
                   bootstrap_value: float, gamma: float,
                   lam: float) -> np.ndarray:
    """Generalized Advantage Estimation over one episode.

    ``bootstrap_value`` is V(s_{T+1}) for truncated episodes (0.0 for
    terminal ones): adv_t = sum_l (gamma*lam)^l * delta_{t+l} with
    delta_t = r_t + gamma * V_{t+1} - V_t.
    """
    T = len(rewards)
    adv = np.zeros(T, dtype=np.float32)
    last = 0.0
    nxt = float(bootstrap_value)
    for t in range(T - 1, -1, -1):
        delta = float(rewards[t]) + gamma * nxt - float(values[t])
        last = delta + gamma * lam * last
        adv[t] = last
        nxt = float(values[t])
    return adv


_TRAJ_KEYS = ("ov", "cv", "mask", "action", "logp", "value")


class PPOAgent:
    """Stateful wrapper: rollout recording + PPO updates."""

    def __init__(self, cfg: PPOConfig | None = None, key: jax.Array | None = None):
        self.cfg = cfg or PPOConfig()
        self.params = init_params(self.cfg, key)
        self.opt_state = adam_init(self.params)
        self._key = jax.random.PRNGKey(self.cfg.seed + 1)
        self.reset_buffer()

    # ------------------------------------------------------------- rollout ----
    def reset_buffer(self) -> None:
        self._traj: dict[str, list] = {k: [] for k in _TRAJ_KEYS}
        if not hasattr(self, "_episodes"):
            self._episodes: list[tuple[dict, float]] = []
        if not hasattr(self, "_dense"):
            # (traj, per-step rewards, bootstrap value) per dense episode
            self._dense: list[tuple[dict, np.ndarray, float]] = []

    @property
    def rollout_len(self) -> int:
        """Steps recorded in the open (unfinished) episode."""
        return len(self._traj["action"])

    @property
    def rollout_values(self) -> list[float]:
        """Critic value estimates of the open episode's recorded steps."""
        return list(self._traj["value"])

    def act(self, ov: np.ndarray, cv: np.ndarray, mask: np.ndarray,
            explore: bool = True, record: bool = True) -> tuple[int, np.ndarray]:
        """Returns (chosen index, full logits) and records the step."""
        with span("rank.actor"):
            if explore:
                self._key, sub = jax.random.split(self._key)
                out = policy_step(self.params, jnp.asarray(ov),
                                  jnp.asarray(cv), jnp.asarray(mask), sub)
                action = int(out["action"])
                if record:
                    self._traj["ov"].append(ov)
                    self._traj["cv"].append(cv)
                    self._traj["mask"].append(mask)
                    self._traj["action"].append(action)
                    self._traj["logp"].append(float(out["logp"]))
                    self._traj["value"].append(float(out["value"]))
                return action, np.asarray(out["logits"])
            # host arrays go straight to the jitted call, and the order
            # is read back once: the head index comes from that copy
            order = np.asarray(greedy_step(self.params, ov, mask))
            logits = np.zeros(mask.shape, dtype=np.float32)
            logits[order] = -np.arange(len(mask), dtype=np.float32)
            return int(order[0]), logits

    # -------------------------------------------------------------- update ----
    def _run_update(self, cat: dict[str, list], rets: np.ndarray,
                    adv: np.ndarray, Tc: int) -> float:
        """Pad the concatenated rollout to ``max_steps`` and run the PPO-clip
        epochs.  Shared by the terminal and dense reward pathways; the ops
        are exactly the pre-refactor ``finish_episode`` tail, so the terminal
        path remains bit-identical on fixed seeds."""
        cfg = self.cfg
        P = cfg.max_steps

        def padded(arr, shape, dtype=np.float32):
            out = np.zeros((P,) + shape, dtype=dtype)
            out[:Tc] = np.asarray(arr[:Tc], dtype=dtype)
            return out

        batch = {
            "ov": padded(cat["ov"], (MAX_QUEUE_SIZE, OV_SIZE)),
            "cv": padded(cat["cv"], (MAX_QUEUE_SIZE, CV_SIZE)),
            "mask": padded(cat["mask"], (MAX_QUEUE_SIZE,)),
            "action": padded(cat["action"], (), np.int32),
            "logp": padded(cat["logp"], ()),
            "ret": padded(rets, ()),
            "adv": padded(adv, ()),
            "valid": padded(np.ones((Tc,)), ()),
        }
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        loss = 0.0
        for _ in range(cfg.update_epochs):
            self.params, self.opt_state, loss = ppo_update_step(
                self.params, self.opt_state, batch,
                clip_eps=cfg.clip_eps, value_coef=cfg.value_coef,
                entropy_coef=cfg.entropy_coef, lr=cfg.lr,
                max_norm=cfg.max_grad_norm)
        return float(loss)

    def finish_episode(self, reward: float) -> dict[str, float]:
        """Assign the terminal batch reward to every step (gamma = 1, sparse
        terminal reward => return_t = R).  With episodes_per_update > 1,
        episodes are pooled before the PPO update (variance reduction)."""
        T = len(self._traj["action"])
        steps = T
        if T:
            self._episodes.append((self._traj, reward))
        self._traj = {k: [] for k in _TRAJ_KEYS}
        if not self._episodes or \
                len(self._episodes) < self.cfg.episodes_per_update:
            return {"loss": 0.0, "steps": steps, "updated": 0.0}
        cfg = self.cfg
        P = cfg.max_steps

        # concatenate pooled episodes (truncate to the padding budget)
        cat: dict[str, list] = {k: [] for k in _TRAJ_KEYS}
        rets_l: list[float] = []
        for traj, rew in self._episodes:
            n = len(traj["action"])
            for k in cat:
                cat[k].extend(traj[k])
            rets_l.extend([rew] * n)
        Tc = min(len(cat["action"]), P)

        values = np.asarray(cat["value"][:Tc], dtype=np.float32)
        rets = np.asarray(rets_l[:Tc], dtype=np.float32)
        # NOTE: no per-episode advantage normalization — with a constant
        # terminal reward it would divide by the (tiny) std of the value
        # net's noise and blow up the gradient.  The critic is the baseline.
        adv = np.clip(rets - values, -5.0, 5.0)

        loss = self._run_update(cat, rets, adv, Tc)
        self._episodes = []
        return {"loss": loss, "steps": steps, "updated": 1.0}

    def finish_episode_dense(self, rewards, *,
                             bootstrap_value: float = 0.0) -> dict[str, float]:
        """Close the open episode with **per-step dense rewards** and run a
        GAE(gamma, lambda) PPO update (the streaming pathway, ``repro.rl``).

        ``rewards`` must have one entry per recorded step;
        ``bootstrap_value`` is V(s_{T+1}) for truncated (non-terminal)
        episodes.  Advantages are normalized per update — safe here because
        shaped rewards vary step to step (contrast the terminal pathway's
        constant-reward note) — then clipped like the terminal path.
        Respects ``episodes_per_update`` pooling.
        """
        T = len(self._traj["action"])
        rewards = np.asarray(rewards, dtype=np.float32)
        if rewards.shape != (T,):
            raise ValueError(f"got {rewards.shape[0] if rewards.ndim else 0} "
                             f"rewards for {T} recorded steps")
        steps = T
        if T:
            self._dense.append((self._traj, rewards, float(bootstrap_value)))
        self._traj = {k: [] for k in _TRAJ_KEYS}
        if not self._dense or len(self._dense) < self.cfg.episodes_per_update:
            return {"loss": 0.0, "steps": steps, "updated": 0.0,
                    "mean_reward": float(rewards.mean()) if T else 0.0}
        cfg = self.cfg

        cat: dict[str, list] = {k: [] for k in _TRAJ_KEYS}
        rets_l: list[np.ndarray] = []
        advs_l: list[np.ndarray] = []
        rews_l: list[np.ndarray] = []
        for traj, rews, boot in self._dense:
            vals = np.asarray(traj["value"], dtype=np.float32)
            adv = gae_advantages(rews, vals, boot, cfg.gamma, cfg.gae_lambda)
            rets_l.append(adv + vals)
            advs_l.append(adv)
            rews_l.append(rews)
            for k in cat:
                cat[k].extend(traj[k])
        Tc = min(len(cat["action"]), cfg.max_steps)
        rets = np.concatenate(rets_l)[:Tc].astype(np.float32)
        adv = np.concatenate(advs_l)[:Tc].astype(np.float32)
        std = float(adv.std())
        if std > 1e-6:
            adv = (adv - float(adv.mean())) / (std + 1e-8)
        adv = np.clip(adv, -5.0, 5.0)

        loss = self._run_update(cat, rets, adv, Tc)
        mean_r = float(np.concatenate(rews_l).mean())
        self._dense = []
        return {"loss": loss, "steps": steps, "updated": 1.0,
                "mean_reward": mean_r}

    # ------------------------------------------------------------- persist ----
    def state_dict(self) -> dict:
        return {"params": jax.tree.map(np.asarray, self.params)}

    def load_state_dict(self, state: dict) -> None:
        self.params = jax.tree.map(jnp.asarray, state["params"])
        self.opt_state = adam_init(self.params)
