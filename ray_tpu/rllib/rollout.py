"""Shared rollout machinery for off-policy / async algorithms.

Reference: rllib/env/env_runner.py:36 (`EnvRunner` actor) and
rllib/utils/replay_buffers/. The split is the same as PPO's
(ray_tpu/rllib/ppo.py): tiny numpy policy inference on CPU actors, all
learning in one jitted program on the TPU. This module generalizes the
runner so DQN (epsilon-greedy over Q-values), SAC (categorical sample)
and IMPALA (categorical + behavior logp, fragment-ordered) share it.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

import ray_tpu
from ray_tpu.rllib.env import Env, make_env


def worker_seed(base_seed: int, worker_index: int) -> int:
    """THE seed fan-out: every per-worker RNG in rllib (env runners,
    pod actors, replay buffers, learner ranks) derives its seed from
    the config seed and its worker index through this one function.
    A multiplicative split keeps streams distinct across BOTH axes —
    the naive ``seed + i`` collides (seed=0, i=1) with (seed=1, i=0),
    so two configs differing only in seed could share runner streams."""
    return (int(base_seed) * 1_000_003 + 15_485_863 * (int(worker_index) + 1)) \
        % (2 ** 31 - 1)


def mlp_forward(layers: Dict, x: np.ndarray, n_hidden: int) -> np.ndarray:
    for i in range(n_hidden):
        x = np.tanh(x @ layers[f"w{i}"] + layers[f"b{i}"])
    return x @ layers["head_w"] + layers["head_b"]


# JAX twins of the numpy forward above — the single definition every
# learner (ppo/dqn/sac/impala) builds its networks from.
def init_mlp_params(key, obs_dim: int, hidden: Tuple[int, ...], out_dim: int):
    import jax
    import jax.numpy as jnp

    sizes = (obs_dim,) + tuple(hidden)
    keys = jax.random.split(key, len(sizes))
    layers = {}
    for i in range(len(sizes) - 1):
        layers[f"w{i}"] = jax.random.normal(
            keys[i], (sizes[i], sizes[i + 1])) * (2.0 / sizes[i]) ** 0.5
        layers[f"b{i}"] = jnp.zeros((sizes[i + 1],))
    layers["head_w"] = jnp.zeros((sizes[-1], out_dim))
    layers["head_b"] = jnp.zeros((out_dim,))
    return layers


def mlp_apply(layers: Dict, x, n_hidden: int):
    import jax.numpy as jnp

    for i in range(n_hidden):
        x = jnp.tanh(x @ layers[f"w{i}"] + layers[f"b{i}"])
    return x @ layers["head_w"] + layers["head_b"]


@ray_tpu.remote
class SampleRunner:
    """Env-runner actor collecting transition fragments.

    mode="categorical": sample from softmax(logits of params[net_key]),
    also records behavior log-probs (IMPALA's v-trace needs them).
    mode="epsilon": epsilon-greedy argmax over params[net_key] outputs
    (Q-values; DQN).
    """

    def __init__(self, env_spec, hidden: Tuple[int, ...], seed: int,
                 mode: str = "categorical", net_key: str = "pi"):
        self.env: Env = make_env(env_spec)
        self.n_hidden = len(hidden)
        self.mode = mode
        self.net_key = net_key
        self.rng = np.random.RandomState(seed)
        self.obs, _ = self.env.reset(seed=seed)
        self.episode_return = 0.0
        self.completed_returns: List[float] = []

    def sample(self, params_np: Dict, num_steps: int,
               epsilon: float = 0.0) -> Dict[str, np.ndarray]:
        net = params_np[self.net_key]
        obs_b, act_b, rew_b, next_b, term_b, trunc_b, logp_b = \
            [], [], [], [], [], [], []
        for _ in range(num_steps):
            out = mlp_forward(net, self.obs, self.n_hidden)
            if self.mode == "epsilon":
                if self.rng.rand() < epsilon:
                    a = int(self.rng.randint(len(out)))
                else:
                    a = int(np.argmax(out))
                logp = 0.0
            else:
                z = out - out.max()
                p = np.exp(z) / np.exp(z).sum()
                a = int(self.rng.choice(len(p), p=p))
                logp = float(np.log(p[a] + 1e-10))
            nobs, rew, term, trunc, _ = self.env.step(a)
            obs_b.append(self.obs)
            act_b.append(a)
            rew_b.append(rew)
            next_b.append(nobs)
            term_b.append(term)
            trunc_b.append(bool(trunc and not term))
            logp_b.append(logp)
            self.episode_return += rew
            if term or trunc:
                self.completed_returns.append(self.episode_return)
                self.episode_return = 0.0
                self.obs, _ = self.env.reset()
            else:
                self.obs = nobs
        rets = self.completed_returns
        self.completed_returns = []
        return {
            "obs": np.asarray(obs_b, np.float32),
            "actions": np.asarray(act_b, np.int32),
            "rewards": np.asarray(rew_b, np.float32),
            "next_obs": np.asarray(next_b, np.float32),
            "terminateds": np.asarray(term_b, np.bool_),
            "truncs": np.asarray(trunc_b, np.bool_),
            "logp": np.asarray(logp_b, np.float32),
            # V(s_T) bootstrap obs for the fragment tail (IMPALA)
            "last_obs": np.asarray(self.obs, np.float32),
            "episode_returns": np.asarray(rets, np.float32),
        }


class ReplayBuffer:
    """Uniform ring buffer (reference:
    rllib/utils/replay_buffers/replay_buffer.py)."""

    def __init__(self, capacity: int, obs_dim: int, seed: int = 0):
        self.capacity = capacity
        self.obs = np.zeros((capacity, obs_dim), np.float32)
        self.next_obs = np.zeros((capacity, obs_dim), np.float32)
        self.actions = np.zeros(capacity, np.int32)
        self.rewards = np.zeros(capacity, np.float32)
        self.terminateds = np.zeros(capacity, np.bool_)
        self._idx = 0
        self._size = 0
        self._rng = np.random.RandomState(seed)

    def __len__(self) -> int:
        return self._size

    def add_batch(self, frag: Dict[str, np.ndarray]) -> None:
        n = len(frag["obs"])
        for k, buf in (("obs", self.obs), ("next_obs", self.next_obs),
                       ("actions", self.actions), ("rewards", self.rewards),
                       ("terminateds", self.terminateds)):
            data = frag[k]
            idx = (self._idx + np.arange(n)) % self.capacity
            buf[idx] = data
        self._idx = (self._idx + n) % self.capacity
        self._size = min(self._size + n, self.capacity)

    def sample(self, batch_size: int) -> Dict[str, np.ndarray]:
        idx = self._rng.randint(0, self._size, size=batch_size)
        return {
            "obs": self.obs[idx],
            "next_obs": self.next_obs[idx],
            "actions": self.actions[idx],
            "rewards": self.rewards[idx],
            "terminateds": self.terminateds[idx],
        }
