"""Anakin-style on-TPU RL learner (PAPERS.md "Podracer architectures for
scalable Reinforcement Learning").

The Anakin wing of Podracer fuses acting and learning into one compiled
program: a `lax.scan` rolls the jit-compiled batched environment forward
`T` steps (policy forward + categorical sample + env physics, all on
device), GAE and the PPO update run on the freshly collected on-device
trajectory, and the whole thing is ONE `jax.jit` step — zero host↔device
transfers per environment step, the property that made Anakin saturate
TPU pods. Sharding rides the existing `parallel/` idioms: the env batch
axis is laid over the mesh's data axis (`batch_sharding`), params are
replicated, and XLA inserts the gradient all-reduce.

A2C is the degenerate config (`clip_eps=None`): the plain policy-gradient
surrogate with a single pass over the rollout.

Everything numerical (GAE, the clipped surrogate, the entropy bonus) is a
pure function pinned by hand-computed records in tests/test_rl_anakin.py;
the seeded end-to-end run is bitwise deterministic — same seed, same
params after N updates.
"""

from __future__ import annotations

import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax

from kubeflow_tpu.parallel import (MeshConfig, batch_sharding, make_mesh,
                                   replicated, validate_divisibility)
from kubeflow_tpu.rl.config import REWARD_METRIC, AnakinConfig
from kubeflow_tpu.rl.envs import make_env

# -- policy/value network (shared torso MLP) ---------------------------------


def init_net(key: jax.Array, obs_dim: int, hidden: tuple[int, ...],
             num_actions: int) -> dict[str, Any]:
    """Tanh MLP torso + linear policy/value heads. The policy head is
    initialized small (0.01 scale) so the initial policy is near-uniform —
    early exploration does not depend on init luck."""
    keys = jax.random.split(key, len(hidden) + 2)
    torso = []
    d_in = obs_dim
    for i, d_out in enumerate(hidden):
        w = jax.random.normal(keys[i], (d_in, d_out)) * (1.0 / d_in) ** 0.5
        torso.append({"w": w, "b": jnp.zeros((d_out,))})
        d_in = d_out
    return {
        "torso": torso,
        "policy": {"w": jax.random.normal(keys[-2], (d_in, num_actions))
                   * 0.01, "b": jnp.zeros((num_actions,))},
        "value": {"w": jax.random.normal(keys[-1], (d_in, 1))
                  * (1.0 / d_in) ** 0.5, "b": jnp.zeros((1,))},
    }


def net_apply(params: dict[str, Any], obs: jax.Array
              ) -> tuple[jax.Array, jax.Array]:
    """obs [..., obs_dim] -> (logits [..., A], value [...])."""
    h = obs
    for layer in params["torso"]:
        h = jnp.tanh(h @ layer["w"] + layer["b"])
    logits = h @ params["policy"]["w"] + params["policy"]["b"]
    value = (h @ params["value"]["w"] + params["value"]["b"])[..., 0]
    return logits, value


# -- pure math: GAE + the PPO/A2C surrogate ----------------------------------


def gae_advantages(rewards: jax.Array, dones: jax.Array, values: jax.Array,
                   last_value: jax.Array, gamma: float, lam: float
                   ) -> tuple[jax.Array, jax.Array]:
    """Generalized Advantage Estimation over the time axis.

    rewards/dones/values: [T, ...]; last_value: [...] (the bootstrap for
    the state AFTER the last step). `dones` masks both the bootstrap and
    the recursion at episode boundaries (auto-reset envs: the next row
    belongs to a new episode). Returns (advantages, returns) with
    returns = advantages + values (the TD(lambda) value target)."""
    nonterm = 1.0 - dones.astype(rewards.dtype)
    values_next = jnp.concatenate([values[1:], last_value[None]], axis=0)

    def back(adv, x):
        r, nt, v, v_next = x
        delta = r + gamma * v_next * nt - v
        adv = delta + gamma * lam * nt * adv
        return adv, adv

    _, advs = jax.lax.scan(back, jnp.zeros_like(last_value),
                           (rewards, nonterm, values, values_next),
                           reverse=True)
    return advs, advs + values


def ppo_loss(params: dict[str, Any], batch: dict[str, jax.Array], *,
             clip_eps: float | None, entropy_coef: float, value_coef: float,
             apply_fn: Callable = net_apply
             ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Clipped-surrogate PPO objective (A2C when clip_eps is None).

    batch: obs [N, d], action [N], logp [N] (behavior log-probs),
    advantage [N], return [N]. Pure in (params, batch) — the hand-pinned
    unit tests call this directly."""
    logits, values = apply_fn(params, batch["obs"])
    logp_all = jax.nn.log_softmax(logits)
    logp = jnp.take_along_axis(
        logp_all, batch["action"][..., None], axis=-1)[..., 0]
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1).mean()
    adv = batch["advantage"]
    if clip_eps is None:
        pg = -(logp * adv).mean()
    else:
        ratio = jnp.exp(logp - batch["logp"])
        pg = -jnp.minimum(
            ratio * adv,
            jnp.clip(ratio, 1.0 - clip_eps, 1.0 + clip_eps) * adv).mean()
    v_loss = jnp.mean((values - batch["return"]) ** 2)
    loss = pg + value_coef * v_loss - entropy_coef * entropy
    return loss, {"pg_loss": pg, "value_loss": v_loss, "entropy": entropy}


class Transition(NamedTuple):
    obs: jax.Array
    action: jax.Array
    logp: jax.Array
    value: jax.Array
    reward: jax.Array
    done: jax.Array


# -- the fused learner --------------------------------------------------------


class AnakinLearner:
    """Batched-env rollout fused with the PPO update in one compiled step.

    `init(seed)` builds the train state (params replicated, env batch
    sharded over the mesh data axis); `step(state)` runs rollout+update;
    `train(state, n)` loops with host-side metric fetches only at the
    logging cadence."""

    def __init__(self, cfg: AnakinConfig):
        self.cfg = cfg
        self.env = make_env(cfg.env, **cfg.env_kwargs)
        self.mesh = (make_mesh(MeshConfig(**cfg.mesh)) if cfg.mesh
                     else None)
        if self.mesh is not None:
            validate_divisibility(self.mesh, batch=cfg.n_envs)
        chain = []
        if cfg.max_grad_norm is not None:
            chain.append(optax.clip_by_global_norm(cfg.max_grad_norm))
        chain.append(optax.adam(cfg.learning_rate))
        self.tx = optax.chain(*chain)
        self._step = jax.jit(self._outer_step)

    # -- state ----------------------------------------------------------------

    def init(self, seed: int | None = None) -> dict[str, Any]:
        cfg = self.cfg
        key = jax.random.key(cfg.seed if seed is None else seed)
        k_net, k_env, k_run = jax.random.split(key, 3)
        params = init_net(k_net, self.env.obs_dim, cfg.hidden,
                          self.env.num_actions)
        env_state, obs = jax.vmap(self.env.reset)(
            jax.random.split(k_env, cfg.n_envs))
        state = {
            "params": params,
            "opt_state": self.tx.init(params),
            "env_state": env_state,
            "obs": obs,
            "ep_ret": jnp.zeros((cfg.n_envs,), jnp.float32),
            "last_mean_return": jnp.zeros((), jnp.float32),
            "key": k_run,
            "update": jnp.zeros((), jnp.int32),
        }
        if self.mesh is not None:
            batched = batch_sharding(self.mesh)
            repl = replicated(self.mesh)
            state = {
                k: jax.device_put(
                    v, batched if k in ("env_state", "obs", "ep_ret")
                    else repl)
                for k, v in state.items()}
        return state

    # -- one fused rollout+update ---------------------------------------------

    def _outer_step(self, state: dict[str, Any]
                    ) -> tuple[dict[str, Any], dict[str, jax.Array]]:
        cfg = self.cfg
        params = state["params"]

        def env_step(carry, key):
            env_state, obs, ep_ret = carry
            k_act, k_env = jax.random.split(key)
            logits, value = net_apply(params, obs)
            action = jax.random.categorical(k_act, logits)
            logp = jnp.take_along_axis(
                jax.nn.log_softmax(logits), action[..., None], -1)[..., 0]
            env_state, next_obs, reward, done = jax.vmap(self.env.step)(
                env_state, action, jax.random.split(k_env, cfg.n_envs))
            ep_ret = ep_ret + reward
            completed = jnp.where(done, ep_ret, 0.0)
            ep_ret = jnp.where(done, 0.0, ep_ret)
            tr = Transition(obs, action, logp, value, reward, done)
            return (env_state, next_obs, ep_ret), (tr, completed)

        key, k_roll = jax.random.split(state["key"])
        (env_state, obs, ep_ret), (traj, completed) = jax.lax.scan(
            env_step, (state["env_state"], state["obs"], state["ep_ret"]),
            jax.random.split(k_roll, cfg.rollout_len))
        _, last_value = net_apply(params, obs)
        adv, returns = gae_advantages(traj.reward, traj.done, traj.value,
                                      last_value, cfg.gamma, cfg.gae_lambda)
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        flat = {
            "obs": traj.obs.reshape(-1, self.env.obs_dim),
            "action": traj.action.reshape(-1),
            "logp": traj.logp.reshape(-1),
            "advantage": adv.reshape(-1),
            "return": returns.reshape(-1),
        }

        def update(carry, _):
            p, opt = carry
            (loss, aux), grads = jax.value_and_grad(
                ppo_loss, has_aux=True)(
                    p, flat, clip_eps=cfg.clip_eps,
                    entropy_coef=cfg.entropy_coef,
                    value_coef=cfg.value_coef)
            updates, opt = self.tx.update(grads, opt, p)
            return (optax.apply_updates(p, updates), opt), (loss, aux)

        (params, opt_state), (losses, auxes) = jax.lax.scan(
            update, (params, state["opt_state"]), None,
            length=cfg.ppo_epochs)

        n_done = traj.done.sum()
        mean_ret = jnp.where(n_done > 0,
                             completed.sum() / jnp.maximum(n_done, 1),
                             state["last_mean_return"])
        metrics = {
            REWARD_METRIC: mean_ret,
            "rollout_reward": traj.reward.mean(),
            "episodes": n_done,
            "loss": losses[-1],
            "entropy": auxes["entropy"][-1],
            "pg_loss": auxes["pg_loss"][-1],
            "value_loss": auxes["value_loss"][-1],
        }
        new_state = {
            "params": params, "opt_state": opt_state,
            "env_state": env_state, "obs": obs, "ep_ret": ep_ret,
            "last_mean_return": mean_ret, "key": key,
            "update": state["update"] + 1,
        }
        return new_state, metrics

    def step(self, state: dict[str, Any]
             ) -> tuple[dict[str, Any], dict[str, jax.Array]]:
        return self._step(state)

    # -- convenience loops ----------------------------------------------------

    def train(self, state: dict[str, Any], num_updates: int, *,
              log_every: int = 10,
              callback: Callable[[int, dict[str, float]], None] | None = None,
              should_stop: Callable[[], bool] | None = None
              ) -> tuple[dict[str, Any], list[dict[str, float]]]:
        """Run `num_updates` fused steps; fetch metrics to the host only at
        the logging cadence (device-bound between logs, the Anakin way).
        `should_stop` is consulted EVERY update (a cheap host-side flag
        read — the pod-cancellation hook; raising from it aborts with the
        dispatched work left to the runtime)."""
        history: list[dict[str, float]] = []
        for u in range(1, num_updates + 1):
            if should_stop is not None and should_stop():
                break
            state, metrics = self.step(state)
            if u % log_every == 0 or u == num_updates:
                scalars = {k: float(v) for k, v in metrics.items()}
                scalars["update"] = u
                history.append(scalars)
                if callback is not None:
                    callback(u, scalars)
        return state, history

    def env_steps_per_update(self) -> int:
        return self.cfg.n_envs * self.cfg.rollout_len

    def measure_steps_per_s(self, state: dict[str, Any], *,
                            iters: int = 10, warmup: int = 2
                            ) -> tuple[dict[str, Any], float]:
        """Sustained env-steps/s of the fused step (bench helper). The
        final metric fetch syncs the chain."""
        if iters < 1:
            raise ValueError("iters must be >= 1")
        for _ in range(warmup):
            state, _ = self.step(state)
        float(state["update"])   # sync the warmup chain
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = self.step(state)
        float(metrics["loss"])
        dt = (time.perf_counter() - t0) / iters
        return state, self.env_steps_per_update() / dt
