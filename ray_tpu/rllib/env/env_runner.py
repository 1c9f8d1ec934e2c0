"""SingleAgentEnvRunner: samples episodes from vectorized gymnasium envs.

Parity with the reference (ref: rllib/env/single_agent_env_runner.py:68 —
vectorized gym envs + RLModule forward_exploration; EnvRunnerGroup ref:
rllib/env/env_runner_group.py:71 with fault-tolerant actor management).
Runs as a plain class (local mode) or behind `ray_tpu.remote` actors.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .episodes import Episode

logger = logging.getLogger(__name__)


def _apply_platform(platform: Optional[str]) -> None:
    """Pin this WORKER process's JAX backend before first use. RL env
    stepping and small policy nets belong on CPU even when an accelerator
    is visible — a per-step forward on an accelerator pays a dispatch and
    a device sync each. Never touches the driver process (local mode): that
    would silently hide the TPU from the user's own JAX code."""
    if not platform or platform == "default":
        return
    from ...runtime.core import get_core

    core = get_core(required=False)
    if core is None or getattr(core, "mode", "driver") != "worker":
        return
    import jax

    try:
        jax.config.update("jax_platforms", platform)
    except RuntimeError:
        pass


def _make_env(env_spec, seed: int):
    if callable(env_spec):
        env = env_spec()
    else:
        import gymnasium as gym

        env = gym.make(env_spec)
    env.reset(seed=seed)
    return env


class SingleAgentEnvRunner:
    def __init__(self, env_spec, module_spec, config: Dict[str, Any],
                 seed: int = 0, worker_index: int = 0):
        import jax

        _apply_platform(config.get("jax_platform", "cpu"))
        self.config = config
        self.num_envs = config.get("num_envs_per_env_runner", 1)
        base_seed = seed + worker_index * 10_000
        self.envs = [_make_env(env_spec, base_seed + i)
                     for i in range(self.num_envs)]
        self.obs_space = self.envs[0].observation_space
        self.act_space = self.envs[0].action_space
        # ConnectorV2 pipelines (ref: rllib/connectors/): observations
        # are transformed ONCE at ingestion so episodes, bootstraps, and
        # learner batches all share the representation
        from ..connectors import build_pipeline

        self._env_to_module = build_pipeline(
            config.get("env_to_module_connectors"))
        self._module_to_env = build_pipeline(
            config.get("module_to_env_connectors"))
        self.module_obs_space = self.obs_space
        if self._env_to_module is not None:
            self.module_obs_space = self._env_to_module.\
                recompute_observation_space(self.obs_space)
        self.module = module_spec.build(self.module_obs_space,
                                        self.act_space)
        self.params = self.module.init(jax.random.PRNGKey(base_seed))
        self._rng = jax.random.PRNGKey(base_seed + 1)
        self._np_rng = np.random.default_rng(base_seed + 2)
        self._jit_fwd = jax.jit(self.module.forward_train)
        # stateful modules (recurrent world models: DreamerV3) carry an
        # acting state across steps; rows reset on episode boundaries
        self._stateful = hasattr(self.module, "initial_state")
        if self._stateful:
            self._jit_fwd_state = jax.jit(self.module.forward_inference)
            self._act_state = self.module.initial_state(self.num_envs)
        self._cur_obs: List[np.ndarray] = []
        self._episodes: List[Episode] = []
        self._reset_all()

    def _transform_obs(self, obs):
        if self._env_to_module is None:
            return np.asarray(obs, np.float32)
        if isinstance(obs, dict):
            batched = {k: np.asarray(v)[None] for k, v in obs.items()}
        elif isinstance(obs, (tuple, list)):
            batched = [np.asarray(v)[None] for v in obs]
        else:
            batched = np.asarray(obs, np.float32)[None]
        return np.asarray(self._env_to_module(batched)[0], np.float32)

    def _reset_all(self):
        self._cur_obs = []
        self._episodes = []
        for env in self.envs:
            obs, _ = env.reset()
            self._cur_obs.append(self._transform_obs(obs))
            self._episodes.append(Episode())

    def set_weights(self, weights) -> None:
        self.params = weights

    def get_spaces(self) -> Tuple[Any, Any]:
        # the MODULE-side observation space: the learner must build its
        # module against what the connectors emit, not the raw env space
        return self.module_obs_space, self.act_space

    def sample(self, num_timesteps: int, explore: bool = True,
               epsilon: float = 0.0, weights=None) -> List[Episode]:
        """Collect ~num_timesteps env steps (across the vector); returns
        finished + truncated episode fragments, each with GAE bootstrap
        values filled in."""
        import jax

        if weights is not None:
            self.params = weights
        out: List[Episode] = []
        steps = 0
        while steps < num_timesteps:
            obs = np.stack(self._cur_obs)
            if self._stateful:
                self._rng, sub = jax.random.split(self._rng)
                fwd = self._jit_fwd_state(self.params, obs,
                                          self._act_state, sub)
                self._act_state = fwd["state"]
            else:
                fwd = self._jit_fwd(self.params, obs)
            continuous = "mean" in fwd
            if self._stateful:
                # the module already sampled an action INTO its acting
                # state (h advances conditioned on it); the env must
                # receive that same action, not an independent re-sample
                actions = np.asarray(fwd["state"]["a"])
                logits = np.asarray(fwd["logits"], np.float32)
                logp_all = logits - _logsumexp(logits)
                logps = logp_all[np.arange(len(actions)), actions]
                vf = np.zeros(len(actions), np.float32)
            elif continuous:
                # tanh-squashed gaussian (Box action spaces). Canonical
                # actions in [-1, 1] are what learners consume; the env
                # sees them rescaled to its [low, high].
                from ..core.rl_module import squashed_gaussian_sample

                n = len(np.asarray(fwd["mean"]))
                if explore:
                    self._rng, sub = jax.random.split(self._rng)
                    act_j, logp_j = squashed_gaussian_sample(
                        sub, fwd["mean"], fwd["log_std"])
                    actions = np.asarray(act_j, np.float32)
                    logps = np.asarray(logp_j, np.float32)
                else:
                    actions = np.tanh(np.asarray(fwd["mean"], np.float32))
                    logps = np.zeros(n, np.float32)
                vf = np.asarray(fwd.get("vf", np.zeros(n)), np.float32)
            elif "logits" in fwd:
                logits = np.asarray(fwd["logits"], np.float32)
                vf = np.asarray(fwd.get("vf", np.zeros(len(logits))),
                                np.float32)
                if explore:
                    self._rng, sub = jax.random.split(self._rng)
                    actions = np.asarray(jax.random.categorical(
                        sub, fwd["logits"], axis=-1))
                else:
                    actions = logits.argmax(-1)
                logp_all = logits - _logsumexp(logits)
                logps = logp_all[np.arange(len(actions)), actions]
            else:  # Q-values: epsilon-greedy
                q = np.asarray(fwd["q"], np.float32)
                actions = q.argmax(-1)
                rand = self._np_rng.random(len(actions)) < epsilon
                actions = np.where(
                    rand,
                    self._np_rng.integers(0, q.shape[-1], len(actions)),
                    actions)
                vf = np.zeros(len(actions), np.float32)
                logps = np.zeros(len(actions), np.float32)
            for i, env in enumerate(self.envs):
                episode = self._episodes[i]
                episode.obs.append(self._cur_obs[i])
                if continuous:
                    action = actions[i]
                    low = self.module.act_low
                    high = self.module.act_high
                    # rescale only finitely-bounded dims; unbounded Box
                    # dims (gym's default is +-inf) pass through the raw
                    # tanh action — inf bounds would rescale to nan
                    bounded = np.isfinite(low) & np.isfinite(high)
                    safe_low = np.where(bounded, low, -1.0)
                    safe_high = np.where(bounded, high, 1.0)
                    env_action = safe_low + (action + 1.0) * 0.5 \
                        * (safe_high - safe_low)
                else:
                    action = env_action = int(actions[i])
                if self._module_to_env is not None:
                    # transforms apply to what the ENV sees only; the
                    # episode stores the module's raw action so stored
                    # (action, logp) pairs stay consistent for learners
                    env_action = self._module_to_env(
                        np.asarray(env_action)[None])[0]
                next_obs, reward, terminated, truncated, _ = env.step(
                    env_action)
                episode.actions.append(action)
                episode.rewards.append(float(reward))
                episode.logp.append(float(logps[i]))
                episode.vf_preds.append(float(vf[i]))
                steps += 1
                if terminated or truncated:
                    if self._stateful:
                        self._act_state = self.module.reset_state_row(
                            self._act_state, i)
                    episode.terminated = bool(terminated)
                    episode.truncated = bool(truncated)
                    if truncated:
                        t_next = self._transform_obs(next_obs)
                        episode.last_value = self._value_of(t_next)
                        episode.last_obs = t_next
                    out.append(episode)
                    next_obs, _ = env.reset()
                    self._episodes[i] = Episode()
                self._cur_obs[i] = self._transform_obs(next_obs)
        # Truncate in-flight fragments into the batch (bootstrapped).
        for i in range(self.num_envs):
            episode = self._episodes[i]
            if len(episode) > 0:
                episode.truncated = True
                episode.cut = True
                episode.last_value = self._value_of(self._cur_obs[i])
                episode.last_obs = np.asarray(self._cur_obs[i], np.float32)
                out.append(episode)
                # the continuation fragment carries the running return so
                # the eventual terminal fragment reports the FULL episode
                self._episodes[i] = Episode(
                    prior_reward=episode.full_return)
        return out

    def _value_of(self, obs) -> float:
        if self._stateful:
            # world-model modules bootstrap inside their own imagined
            # rollouts, not from a GAE value head
            return 0.0
        fwd = self._jit_fwd(self.params,
                            np.asarray(obs, np.float32)[None])
        if "vf" in fwd:
            return float(np.asarray(fwd["vf"])[0])
        return 0.0

    def ping(self) -> str:
        return "pong"


def _logsumexp(logits: np.ndarray) -> np.ndarray:
    m = logits.max(-1, keepdims=True)
    return m + np.log(np.exp(logits - m).sum(-1, keepdims=True))


class EnvRunnerGroup:
    """Local runner or N remote runner actors with restart-on-failure
    (ref: rllib/env/env_runner_group.py:71 + utils/actor_manager.py
    FaultTolerantActorManager)."""

    def __init__(self, env_spec, module_spec, config: Dict[str, Any],
                 num_env_runners: int = 0, seed: int = 0):
        self._args = (env_spec, module_spec, dict(config), seed)
        self.num_env_runners = num_env_runners
        if num_env_runners == 0:
            self._local = SingleAgentEnvRunner(env_spec, module_spec,
                                              config, seed)
            self._remote = None
        else:
            self._local = None
            self._remote = [self._spawn(i) for i in range(num_env_runners)]

    def _spawn(self, index: int):
        import ray_tpu

        env_spec, module_spec, config, seed = self._args
        cls = ray_tpu.remote(SingleAgentEnvRunner)
        return cls.remote(env_spec, module_spec, config, seed,
                          worker_index=index + 1)

    def get_spaces(self):
        if self._local is not None:
            return self._local.get_spaces()
        import ray_tpu

        return ray_tpu.get(self._remote[0].get_spaces.remote())

    def sample(self, num_timesteps: int, weights=None, explore: bool = True,
               epsilon: float = 0.0) -> List[Episode]:
        if self._local is not None:
            return self._local.sample(num_timesteps, explore=explore,
                                      epsilon=epsilon, weights=weights)
        import ray_tpu

        share = -(-num_timesteps // len(self._remote))
        refs = [runner.sample.remote(share, explore=explore,
                                     epsilon=epsilon, weights=weights)
                for runner in self._remote]
        episodes: List[Episode] = []
        last_error: Optional[Exception] = None
        for i, ref in enumerate(refs):
            try:
                episodes.extend(ray_tpu.get(ref, timeout=120))
            except Exception as e:
                # Restart the failed runner (fault-tolerant manager) —
                # loudly, and escalate if NO runner produced data for
                # several consecutive rounds (deterministic failures like a
                # bad env spec must not silently spin forever).
                logger.exception("env runner %d failed; restarting", i)
                last_error = e
                self._remote[i] = self._spawn(i)
        if episodes:
            self._empty_rounds = 0
        else:
            self._empty_rounds = getattr(self, "_empty_rounds", 0) + 1
            if self._empty_rounds >= 3:
                raise RuntimeError(
                    "all env runners failed for 3 consecutive sample "
                    "rounds; last error below") from last_error
        return episodes
