"""Algorithm + AlgorithmConfig: the training driver.

Parity with the reference (ref: rllib/algorithms/algorithm.py:207 Algorithm
extends Tune's Trainable; step :986 calls training_step :2004; fluent
config ref: rllib/algorithms/algorithm_config.py — .environment()
.training() .env_runners() .learners() .build_algo()). `Algorithm.train()`
returns one iteration's result dict, and instances plug into
ray_tpu.tune.Tuner as a trainable.
"""

from __future__ import annotations

import copy
import pickle
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

from ..core.learner_group import LearnerGroup
from ..core.rl_module import RLModuleSpec
from ..env.env_runner import EnvRunnerGroup


class AlgorithmConfig:
    algo_class: Optional[type] = None

    def __init__(self):
        self.env = None
        self.env_config: Dict[str, Any] = {}
        self.num_env_runners = 0
        self.num_envs_per_env_runner = 1
        self.num_learners = 0
        self.lr = 3e-4
        self.gamma = 0.99
        self.grad_clip = 10.0
        self.train_batch_size = 2000
        self.seed = 0
        # backend for env-runner/learner ACTORS ("cpu" | "tpu" | "default"
        # = inherit). Sampling + small nets default to CPU: a per-step
        # forward on an accelerator pays a dispatch and a device sync each.
        self.jax_platform = "cpu"
        self.module_spec = RLModuleSpec()
        # ConnectorV2 pipelines (ref: rllib/connectors/): lists of
        # connector instances or zero-arg factories
        self.env_to_module_connectors = None
        self.module_to_env_connectors = None

    # fluent builders (ref: algorithm_config.py)
    def environment(self, env=None, *, env_config=None) -> "AlgorithmConfig":
        if env is not None:
            self.env = env
        if env_config is not None:
            self.env_config = env_config
        return self

    def env_runners(self, *, num_env_runners: Optional[int] = None,
                    num_envs_per_env_runner: Optional[int] = None,
                    env_to_module_connectors=None,
                    module_to_env_connectors=None,
                    **_ignored) -> "AlgorithmConfig":
        if num_env_runners is not None:
            self.num_env_runners = num_env_runners
        if num_envs_per_env_runner is not None:
            self.num_envs_per_env_runner = num_envs_per_env_runner
        if env_to_module_connectors is not None:
            self.env_to_module_connectors = env_to_module_connectors
        if module_to_env_connectors is not None:
            self.module_to_env_connectors = module_to_env_connectors
        return self

    def learners(self, *, num_learners: Optional[int] = None,
                 **_ignored) -> "AlgorithmConfig":
        if num_learners is not None:
            self.num_learners = num_learners
        return self

    def training(self, **kwargs) -> "AlgorithmConfig":
        for key, value in kwargs.items():
            if not hasattr(self, key):
                raise AttributeError(f"unknown training param {key!r}")
            setattr(self, key, value)
        return self

    def rl_module(self, *, module_spec=None, hidden=None
                  ) -> "AlgorithmConfig":
        if module_spec is not None:
            self.module_spec = module_spec
        if hidden is not None:
            self.module_spec.hidden = tuple(hidden)
        return self

    def debugging(self, *, seed: Optional[int] = None) -> "AlgorithmConfig":
        if seed is not None:
            self.seed = seed
        return self

    def copy(self) -> "AlgorithmConfig":
        return copy.deepcopy(self)

    def build_algo(self) -> "Algorithm":
        assert self.algo_class is not None, "use a concrete config"
        return self.algo_class(self.copy())

    # legacy alias
    build = build_algo

    def learner_config(self) -> Dict[str, Any]:
        return {"lr": self.lr, "grad_clip": self.grad_clip,
                "gamma": self.gamma}


class Algorithm:
    """Drives sample → update → weight-sync iterations."""

    learner_class: type = None

    def __init__(self, config: AlgorithmConfig):
        self.config = config
        self.iteration = 0
        self._timesteps_total = 0
        self._episode_returns: list = []
        self.env_runner_group = EnvRunnerGroup(
            config.env, config.module_spec,
            {"num_envs_per_env_runner": config.num_envs_per_env_runner,
             "jax_platform": config.jax_platform,
             "env_to_module_connectors": config.env_to_module_connectors,
             "module_to_env_connectors": config.module_to_env_connectors},
            num_env_runners=config.num_env_runners, seed=config.seed)
        obs_space, act_space = self.env_runner_group.get_spaces()
        self.obs_space, self.act_space = obs_space, act_space
        module_spec = config.module_spec
        learner_cls = self.learner_class
        learner_cfg = config.learner_config()
        seed = config.seed

        def learner_factory():
            module = module_spec.build(obs_space, act_space)
            return learner_cls(module, learner_cfg, seed=seed)

        self.learner_group = LearnerGroup(
            learner_factory, num_learners=config.num_learners,
            jax_platform=config.jax_platform)

    # ------------------------------------------------------------ train

    def training_step(self) -> Dict[str, Any]:
        raise NotImplementedError

    def train(self) -> Dict[str, Any]:
        """One iteration (ref: algorithm.py:986 step)."""
        t0 = time.time()
        metrics = self.training_step()
        self.iteration += 1
        recent = self._episode_returns[-100:]
        result = {
            "training_iteration": self.iteration,
            "timesteps_total": self._timesteps_total,
            "time_this_iter_s": time.time() - t0,
            "episode_return_mean": (float(np.mean(recent))
                                    if recent else np.nan),
            "num_episodes": len(self._episode_returns),
            **metrics,
        }
        return result

    def _record_episodes(self, episodes) -> None:
        for episode in episodes:
            self._timesteps_total += len(episode)
            # Sampler-cut fragments are partial; only real episode ends
            # (env terminated or env-truncated at horizon) count, and they
            # report the FULL return including pre-cut fragments.
            if not episode.cut:
                self._episode_returns.append(episode.full_return)

    # ----------------------------------------------------- checkpointing

    def save_to_path(self, path: str) -> str:
        import os

        os.makedirs(path, exist_ok=True)
        state = {"weights": self.learner_group.get_weights(),
                 "iteration": self.iteration,
                 "timesteps_total": self._timesteps_total}
        with open(os.path.join(path, "algorithm_state.pkl"), "wb") as f:
            pickle.dump(state, f)
        return path

    def restore_from_path(self, path: str) -> None:
        import os

        with open(os.path.join(path, "algorithm_state.pkl"), "rb") as f:
            state = pickle.load(f)
        self.learner_group.set_weights(state["weights"])
        self.iteration = state["iteration"]
        self._timesteps_total = state["timesteps_total"]

    def get_weights(self):
        return self.learner_group.get_weights()

    def stop(self) -> None:
        pass


def as_trainable(config: AlgorithmConfig,
                 num_iterations: Optional[int] = None) -> Callable:
    """Wrap for ray_tpu.tune: trainable(trial_config) reporting once per
    iteration. With num_iterations=None it runs until an external stop
    (RunConfig.stop criteria or a scheduler decision) — pass a bound if
    the run uses neither, or the trial never ends."""

    def trainable(trial_config: Dict[str, Any]):
        from ray_tpu import tune as rtune

        cfg = config.copy()
        for key, value in trial_config.items():
            if hasattr(cfg, key):
                setattr(cfg, key, value)
        algo = cfg.build_algo()
        i = 0
        while num_iterations is None or i < num_iterations:
            result = algo.train()
            rtune.report(result)
            i += 1

    return trainable
