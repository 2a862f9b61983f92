"""Decentralized-DP PPO: every device is a learner, no driver SGD.

Capability mirror of the reference's DDPPO
(`rllib/algorithms/ddppo/ddppo.py:270` — workers compute gradients locally
and allreduce them via torch distributed; the driver never touches a
sample batch).  TPU-native answer: ONE `shard_map` program over a "dp"
mesh axis where each device rolls out its own vectorized envs, computes
GAE, and runs the epoch/minibatch SGD with `jax.lax.pmean` gradient
sync before every apply — params stay bit-identical across devices and
rollout + learn is a single XLA program, so "no driver SGD" is literal:
the host only dispatches the compiled iteration.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .algorithm import Algorithm
from .policy import MLPPolicy
from .ppo import PPOConfig, compute_gae, make_rollout_fn, make_update_fn


@dataclasses.dataclass
class DDPPOConfig(PPOConfig):
    num_learners: Optional[int] = None  # None → every visible device

    def build(self) -> "DDPPO":
        return DDPPO(self)


class DDPPO(Algorithm):
    """num_envs is PER LEARNER; global batch = learners*num_envs*rollout."""

    _config_cls = DDPPOConfig

    def __init__(self, config: DDPPOConfig):
        super().__init__(config)
        cfg = config
        if cfg.env is None:
            raise ValueError("DDPPOConfig.env required (an env factory)")
        if cfg.num_workers:
            raise ValueError(
                "DDPPO has no rollout-worker actors: every mesh device is "
                "a learner+sampler (set num_learners, not num_workers)")
        devices = jax.devices()
        n = cfg.num_learners or len(devices)
        if n > len(devices):
            raise ValueError(f"num_learners={n} > {len(devices)} devices")
        self.num_learners = n
        self.mesh = Mesh(np.asarray(devices[:n]), ("dp",))

        self.env = cfg.env()
        if (cfg.model or {}).get("use_lstm"):
            raise ValueError("use_lstm is not supported by DDPPO: its "
                             "per-device learners are feedforward-only "
                             "(use PPO's local path for recurrence)")
        self.policy = MLPPolicy(self.env.observation_size,
                                self.env.action_size,
                                discrete=self.env.discrete,
                                hidden=cfg.hidden)
        key = jax.random.PRNGKey(cfg.seed)
        key, pkey, ekey = jax.random.split(key, 3)
        self.params = self.policy.init(pkey)
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(cfg.max_grad_norm),
            optax.adam(cfg.lr))
        self.opt_state = self.optimizer.init(self.params)

        # global env state: leading axis n*num_envs, sharded over dp
        total_envs = n * cfg.num_envs
        ekeys = jax.random.split(ekey, total_envs)
        env_states, obs = jax.vmap(self.env.reset)(ekeys)
        shard = NamedSharding(self.mesh, P("dp"))
        repl = NamedSharding(self.mesh, P())
        self.env_states = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, shard), env_states)
        self.obs = jax.device_put(obs, shard)
        self.keys = jax.device_put(jax.random.split(key, n), shard)
        self.params = jax.device_put(self.params, repl)
        self.opt_state = jax.device_put(self.opt_state, repl)

        self._train_iter = self._build_train_iter()
        self._init_episode_tracking(total_envs)

    def _build_train_iter(self):
        cfg = self.config
        local_batch = cfg.num_envs * cfg.rollout_length
        rollout = make_rollout_fn(self.env, self.policy, cfg.num_envs,
                                  cfg.rollout_length,
                                  env_chunk=cfg.env_chunk)
        update = make_update_fn(self.policy, self.optimizer, cfg,
                                local_batch, axis_name="dp")
        discrete = self.env.discrete

        def body(params, opt_state, env_states, obs, keys):
            key = keys[0]
            traj, env_states, obs, _, last_value, key = rollout(
                params, env_states, obs, (), key)
            adv, ret = compute_gae(traj, last_value, cfg.gamma,
                                   cfg.gae_lambda)
            flat = {
                "obs": traj["obs"].reshape(local_batch, -1),
                "action": traj["action"].reshape(
                    (local_batch,) if discrete else (local_batch, -1)),
                "logp": traj["logp"].reshape(local_batch),
                "adv": adv.reshape(local_batch),
                "ret": ret.reshape(local_batch),
            }
            params, opt_state, key, metrics = update(
                params, opt_state, flat, key)
            # params are identical across dp after pmean'd grads; metrics
            # are averaged so every device reports the same numbers
            metrics = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, "dp"), metrics)
            metrics["reward_sum"] = jax.lax.psum(traj["reward"].sum(), "dp")
            return (params, opt_state, env_states, obs, key[None],
                    metrics, traj["reward"], traj["done"])

        repl = P()
        sh = P("dp")
        state_specs = jax.tree_util.tree_map(lambda _: sh, self.env_states)
        fn = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: repl, self.params),
                      jax.tree_util.tree_map(lambda _: repl,
                                             self.opt_state),
                      state_specs, sh, sh),
            out_specs=(jax.tree_util.tree_map(lambda _: repl, self.params),
                       jax.tree_util.tree_map(lambda _: repl,
                                              self.opt_state),
                       state_specs, sh, sh,
                       repl, P(None, "dp"), P(None, "dp")))
        return jax.jit(fn)

    # -- Trainable interface ------------------------------------------------
    def training_step(self) -> Dict[str, Any]:
        import time
        cfg = self.config
        t0 = time.perf_counter()
        (self.params, self.opt_state, self.env_states, self.obs,
         self.keys, metrics, rewards, dones) = self._train_iter(
            self.params, self.opt_state, self.env_states, self.obs,
            self.keys)
        metrics = {k: float(v) for k, v in metrics.items()}
        dt = time.perf_counter() - t0
        env_steps = self.num_learners * cfg.num_envs * cfg.rollout_length
        self._track_episodes(np.asarray(rewards), np.asarray(dones))
        metrics.update({
            "env_steps_this_iter": env_steps,
            "env_steps_per_s": env_steps / dt,
            "episode_reward_mean": self.episode_reward_mean(),
            "num_learners": self.num_learners,
        })
        return metrics

    # -- checkpointing ------------------------------------------------------
    def get_state(self) -> Dict[str, Any]:
        return {"params": self.policy.get_weights(self.params),
                "iteration": self.iteration}

    def set_state(self, state: Dict[str, Any]) -> None:
        self.params = self.policy.set_weights(self.params, state["params"])
        self.iteration = state.get("iteration", 0)
