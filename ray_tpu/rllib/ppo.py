"""PPO — rollout actors (CPU) + jitted JAX learner (TPU).

Reference: rllib/algorithms/ppo/ppo.py:365 (`PPO`, training_step :391),
Learner (rllib/core/learner/learner.py:112), EnvRunner
(rllib/env/env_runner.py:36). The architecture survives: CPU env-runner
actors collect trajectories in parallel; the learner is ONE jitted
program (policy+value MLP, clipped-surrogate loss, GAE) so the update
runs on the TPU MXU; scaling the learner = mesh data-parallel sharding,
not DDP (SURVEY.md §2.3).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import ray_tpu
from ray_tpu.rllib.algorithm import AlgorithmConfigBase
from ray_tpu.rllib.env import Env, make_env
from ray_tpu.rllib.rollout import worker_seed


# ---------------------------------------------------------------------------
# Policy/value network (shared MLP definition, rollout.py)
# ---------------------------------------------------------------------------
def init_policy(key, obs_dim: int, num_actions: int, hidden: Tuple[int, ...] = (64, 64)):
    import jax

    from ray_tpu.rllib.rollout import init_mlp_params

    k_pi, k_vf = jax.random.split(key)
    return {"pi": init_mlp_params(k_pi, obs_dim, hidden, num_actions),
            "vf": init_mlp_params(k_vf, obs_dim, hidden, 1)}


def policy_logits(params, obs, n_hidden: int = 2):
    from ray_tpu.rllib.rollout import mlp_apply

    return mlp_apply(params["pi"], obs, n_hidden)


def value_fn(params, obs, n_hidden: int = 2):
    from ray_tpu.rllib.rollout import mlp_apply

    return mlp_apply(params["vf"], obs, n_hidden)[..., 0]


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class PPOConfig(AlgorithmConfigBase):
    """Reference: AlgorithmConfig + PPOConfig (ppo.py). Builder-style:
    PPOConfig().environment("CartPole-v1").env_runners(2).training(lr=3e-4)."""

    env: Any = "CartPole-v1"
    num_env_runners: int = 2
    rollout_fragment_length: int = 256
    lr: float = 3e-4
    gamma: float = 0.99
    lambda_: float = 0.95
    clip_param: float = 0.2
    vf_coeff: float = 0.5
    entropy_coeff: float = 0.01
    num_epochs: int = 4
    minibatch_size: int = 128
    hidden: Tuple[int, ...] = (64, 64)
    seed: int = 0



# ---------------------------------------------------------------------------
# Env runner actor (reference: SingleAgentEnvRunner)
# ---------------------------------------------------------------------------
@ray_tpu.remote
class EnvRunner:
    def __init__(self, env_spec, hidden, seed: int):
        self.env: Env = make_env(env_spec)
        self.hidden = hidden
        self.n_hidden = len(hidden)
        self.rng = np.random.RandomState(seed)
        self.obs, _ = self.env.reset(seed=seed)
        self.episode_return = 0.0
        self.completed_returns: List[float] = []

    def _value(self, obs, params_np: Dict) -> float:
        from ray_tpu.rllib.rollout import mlp_forward

        return float(mlp_forward(params_np["vf"], obs, self.n_hidden)[0])

    def sample(self, params_np: Dict, num_steps: int) -> Dict[str, np.ndarray]:
        """Collect a fragment with the given policy weights (numpy inference
        on CPU — tiny nets; the TPU does the learning)."""
        obs_buf, act_buf, rew_buf, done_buf, logp_buf, val_buf = [], [], [], [], [], []
        trunc_buf, boot_buf = [], []
        from ray_tpu.rllib.rollout import mlp_forward

        for _ in range(num_steps):
            logits = mlp_forward(params_np["pi"], self.obs, self.n_hidden)
            z = logits - logits.max()
            p = np.exp(z) / np.exp(z).sum()
            a = int(self.rng.choice(len(p), p=p))
            val = self._value(self.obs, params_np)

            nobs, rew, term, trunc, _ = self.env.step(a)
            obs_buf.append(self.obs)
            act_buf.append(a)
            rew_buf.append(rew)
            done_buf.append(term)
            logp_buf.append(np.log(p[a] + 1e-10))
            val_buf.append(val)
            truncated = bool(trunc and not term)
            trunc_buf.append(truncated)
            # a truncated (not terminated) episode bootstraps from V(s_T)
            # of the state it was cut off at, computed BEFORE the reset
            # (reference rllib postprocessing: truncations bootstrap with
            # the value of the final observation — advisor finding, r1)
            boot_buf.append(self._value(nobs, params_np) if truncated else 0.0)
            self.episode_return += rew
            if term or trunc:
                self.completed_returns.append(self.episode_return)
                self.episode_return = 0.0
                self.obs, _ = self.env.reset()
            else:
                self.obs = nobs
        # bootstrap value for the final state
        last_val = self._value(self.obs, params_np)
        rets = self.completed_returns
        self.completed_returns = []
        return {
            "obs": np.asarray(obs_buf, np.float32),
            "actions": np.asarray(act_buf, np.int32),
            "rewards": np.asarray(rew_buf, np.float32),
            "dones": np.asarray(done_buf, np.bool_),
            "truncs": np.asarray(trunc_buf, np.bool_),
            "bootstrap_values": np.asarray(boot_buf, np.float32),
            "logp": np.asarray(logp_buf, np.float32),
            "values": np.asarray(val_buf, np.float32),
            "last_value": np.float32(last_val),
            "episode_returns": np.asarray(rets, np.float32),
        }


def compute_gae(rewards, values, dones, last_value, gamma, lambda_,
                truncs=None, bootstrap_values=None):
    """Generalized advantage estimation (reference:
    rllib/evaluation/postprocessing.py compute_advantages).

    Truncated-but-not-terminated steps bootstrap from V(s_{t+1}) recorded
    before the env reset, and the lambda accumulation stops at the boundary
    (the following buffer row belongs to a different episode)."""
    T = len(rewards)
    adv = np.zeros(T, np.float32)
    last = 0.0
    next_v = last_value
    for t in reversed(range(T)):
        if truncs is not None and truncs[t]:
            delta = rewards[t] + gamma * float(bootstrap_values[t]) - values[t]
            last = delta
        else:
            nonterminal = 1.0 - float(dones[t])
            delta = rewards[t] + gamma * next_v * nonterminal - values[t]
            last = delta + gamma * lambda_ * nonterminal * last
        adv[t] = last
        next_v = values[t]
    returns = adv + values
    return adv, returns


# ---------------------------------------------------------------------------
# Learner (one jitted update; reference: learner.py:112)
# ---------------------------------------------------------------------------
class PPOLearner:
    def __init__(self, cfg: PPOConfig, obs_dim: int, num_actions: int):
        import jax
        import optax

        self.cfg = cfg
        self.n_hidden = len(cfg.hidden)
        self.params = init_policy(
            jax.random.key(cfg.seed), obs_dim, num_actions, cfg.hidden
        )
        self.tx = optax.adam(cfg.lr)
        self.opt_state = self.tx.init(self.params)
        self._update = jax.jit(self._make_update())

    def _make_update(self):
        import jax
        import jax.numpy as jnp
        import optax

        cfg = self.cfg
        nh = self.n_hidden

        def loss_fn(params, batch):
            logits = policy_logits(params, batch["obs"], nh)
            logp_all = jax.nn.log_softmax(logits)
            logp = jnp.take_along_axis(logp_all, batch["actions"][:, None], axis=1)[:, 0]
            ratio = jnp.exp(logp - batch["logp"])
            adv = batch["adv"]
            surr = jnp.minimum(
                ratio * adv,
                jnp.clip(ratio, 1 - cfg.clip_param, 1 + cfg.clip_param) * adv,
            )
            v = value_fn(params, batch["obs"], nh)
            vf_loss = jnp.mean((v - batch["returns"]) ** 2)
            entropy = -jnp.mean(jnp.sum(jnp.exp(logp_all) * logp_all, axis=1))
            loss = -jnp.mean(surr) + cfg.vf_coeff * vf_loss - cfg.entropy_coeff * entropy
            return loss, {"policy_loss": -jnp.mean(surr), "vf_loss": vf_loss,
                          "entropy": entropy}

        def update(params, opt_state, batch):
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            updates, opt_state = self.tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, dict(aux, total_loss=loss)

        return update

    def update(self, batch_np: Dict[str, np.ndarray]) -> Dict[str, float]:
        import jax.numpy as jnp

        cfg = self.cfg
        n = len(batch_np["obs"])
        idx = np.arange(n)
        metrics = {}
        adv = batch_np["adv"]
        batch_np = dict(batch_np, adv=(adv - adv.mean()) / (adv.std() + 1e-8))
        rng = np.random.RandomState(cfg.seed)
        mb = min(cfg.minibatch_size, n)
        for _ in range(cfg.num_epochs):
            rng.shuffle(idx)
            for s in range(0, n - mb + 1, mb):
                sel = idx[s : s + mb]
                mbatch = {k: jnp.asarray(v[sel]) for k, v in batch_np.items()
                          if k in ("obs", "actions", "logp", "adv", "returns")}
                self.params, self.opt_state, metrics = self._update(
                    self.params, self.opt_state, mbatch
                )
        return {k: float(v) for k, v in metrics.items()}

    def get_weights_np(self) -> Dict:
        import jax

        return jax.tree.map(lambda x: np.asarray(x), self.params)


# ---------------------------------------------------------------------------
# Algorithm (reference: algorithm.py:208; train() = :1169 step)
# ---------------------------------------------------------------------------
class PPO:
    def __init__(self, cfg: PPOConfig):
        probe = make_env(cfg.env)
        self.cfg = cfg
        self.obs_dim = probe.observation_dim
        self.num_actions = probe.num_actions
        self.learner = PPOLearner(cfg, self.obs_dim, self.num_actions)
        self.runners = [
            EnvRunner.remote(cfg.env, cfg.hidden, worker_seed(cfg.seed, i))
            for i in range(cfg.num_env_runners)
        ]
        self.iteration = 0
        self._recent_returns: List[float] = []

    def train(self) -> Dict[str, Any]:
        """One training iteration (reference: ppo.py:391 training_step)."""
        cfg = self.cfg
        weights = self.learner.get_weights_np()
        frags = ray_tpu.get(
            [r.sample.remote(weights, cfg.rollout_fragment_length) for r in self.runners]
        )
        parts = []
        for f in frags:
            adv, rets = compute_gae(
                f["rewards"], f["values"], f["dones"], f["last_value"],
                cfg.gamma, cfg.lambda_,
                truncs=f.get("truncs"), bootstrap_values=f.get("bootstrap_values"),
            )
            parts.append(dict(f, adv=adv, returns=rets))
            self._recent_returns.extend(f["episode_returns"].tolist())
        batch = {
            k: np.concatenate([p[k] for p in parts])
            for k in ("obs", "actions", "logp", "adv", "returns")
        }
        metrics = self.learner.update(batch)
        self.iteration += 1
        self._recent_returns = self._recent_returns[-100:]
        mean_ret = float(np.mean(self._recent_returns)) if self._recent_returns else 0.0
        return {
            "training_iteration": self.iteration,
            "episode_return_mean": mean_ret,
            "num_env_steps_sampled": cfg.rollout_fragment_length * cfg.num_env_runners,
            **metrics,
        }

    def stop(self) -> None:
        for r in self.runners:
            try:
                ray_tpu.kill(r)
            except Exception:
                pass  # runner already dead — kill is best-effort

    # checkpointing (reference: Checkpointable, algorithm.py:208)
    def save(self, path: str) -> None:
        from ray_tpu.train.checkpoint import save_state

        save_state({"params": self.learner.params,
                    "opt_state": self.learner.opt_state}, path)

    def restore(self, path: str) -> None:
        from ray_tpu.train.checkpoint import restore_state

        state = restore_state(
            path,
            target={"params": self.learner.params, "opt_state": self.learner.opt_state},
        )
        self.learner.params = state["params"]
        self.learner.opt_state = state["opt_state"]


PPOConfig.algo_cls = PPO
