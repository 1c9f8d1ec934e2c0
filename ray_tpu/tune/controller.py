"""Tune controller: the trial event loop.

ref: python/ray/tune/execution/tune_controller.py (TuneController :68 — an
actor event loop over Trainables). Trials here are TrainWorker actors
(world_size=1) reusing the train session/report plumbing; the controller
polls them, feeds results to the scheduler/searcher, applies STOP
decisions, PBT exploits, retries, and assembles the ResultGrid.
"""

from __future__ import annotations

import logging
import os
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from ..train.checkpoint import Checkpoint, CheckpointManager
from ..train.config import Result
from ..train.worker_group import ERRORED, FINISHED, RUNNING, TrainWorker
from .schedulers import (CONTINUE, STOP, FIFOScheduler,
                         PopulationBasedTraining, TrialScheduler)

logger = logging.getLogger(__name__)

PENDING = "PENDING"
TERMINATED = "TERMINATED"


@dataclass
class Trial:
    trial_id: str
    config: Dict[str, Any]
    status: str = PENDING
    actor: Any = None
    metrics_history: List[Dict[str, Any]] = field(default_factory=list)
    error: Optional[str] = None
    checkpoint_manager: Optional[CheckpointManager] = None
    num_failures: int = 0
    stopped_by_scheduler: bool = False
    stop_reason: Optional[str] = None
    resume_checkpoint: Optional[Checkpoint] = None
    # the outstanding `poll` call and when it was issued: a trial whose
    # actor is still waiting for a CPU answers late, and must not hold
    # up the polls (and so the stops that free CPUs) of the others
    poll_ref: Any = None
    poll_since: float = 0.0

    @property
    def last_metrics(self) -> Dict[str, Any]:
        return self.metrics_history[-1] if self.metrics_history else {}


class TuneController:
    def __init__(self, trainable: Callable,
                 configs: Optional[List[Dict[str, Any]]] = None,
                 *, experiment_dir: str,
                 scheduler: Optional[TrialScheduler] = None,
                 searcher: Optional[Any] = None,
                 num_trials: Optional[int] = None,
                 max_concurrent: Optional[int] = None,
                 max_failures: int = 0,
                 resources_per_trial: Optional[Dict[str, float]] = None,
                 stop: Optional[Dict[str, Any]] = None,
                 poll_interval: float = 0.1):
        from ..runtime import serialization
        from .searchers import ListSearcher

        self.trainable_blob = serialization.dumps_inline(trainable)
        self.stop_criteria = stop or {}
        self.scheduler = scheduler or FIFOScheduler()
        self.experiment_dir = experiment_dir
        self.max_concurrent = max_concurrent or _default_concurrency()
        self.max_failures = max_failures
        self.resources = resources_per_trial or {"CPU": 1.0}
        self.poll_interval = poll_interval
        os.makedirs(experiment_dir, exist_ok=True)
        # Everything runs through the Searcher protocol: a static config
        # list (BasicVariantGenerator output) becomes a ListSearcher;
        # adaptive searchers (TPE, optuna) suggest lazily as capacity
        # frees so completed results inform later trials.
        if searcher is None:
            assert configs is not None, "configs or searcher required"
            searcher = ListSearcher(configs)
            num_trials = len(configs)
        self.searcher = searcher
        self.num_trials = num_trials if num_trials is not None else 10**9
        self.trials: List[Trial] = []
        self._created = 0
        self._last_save = 0.0

    # ------------------------------------------------------ persistence

    STATE_FILE = "experiment_state.pkl"

    def _save_state(self) -> None:
        """Atomically persist the experiment: trial table + searcher +
        scheduler + trainable (ref: tune/execution/tune_controller.py
        experiment checkpointing feeding Tuner.restore, tuner.py:312).
        Actors are process state and excluded; a restore resumes their
        trials from each trial's latest checkpoint."""
        import pickle

        trial_rows = []
        for t in self.trials:
            trial_rows.append({
                "trial_id": t.trial_id, "config": t.config,
                "status": t.status,
                "metrics_history": t.metrics_history,
                "error": t.error, "num_failures": t.num_failures,
                "stopped_by_scheduler": t.stopped_by_scheduler,
                "stop_reason": t.stop_reason,
            })
        state = {
            "trials": trial_rows, "created": self._created,
            "num_trials": self.num_trials,
            "max_concurrent": self.max_concurrent,
            "stop_criteria": self.stop_criteria,
            "resources": self.resources,
            "max_failures": self.max_failures,
            "trainable_blob": self.trainable_blob,
            "searcher": self.searcher, "scheduler": self.scheduler,
        }
        path = os.path.join(self.experiment_dir, self.STATE_FILE)
        tmp = path + f".tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                pickle.dump(state, f)
            os.replace(tmp, path)
        except Exception:
            logger.exception("experiment state save failed")
            try:
                os.unlink(tmp)
            except OSError:
                pass
        self._last_save = time.monotonic()

    @classmethod
    def restore(cls, experiment_dir: str,
                poll_interval: float = 0.1) -> "TuneController":
        """Rebuild a controller from a saved experiment. Trials that were
        PENDING or RUNNING when the driver died become PENDING and resume
        from their latest checkpoint; completed trials keep their results
        (ref: tune/tuner.py:312 Tuner.restore)."""
        import pickle

        with open(os.path.join(experiment_dir, cls.STATE_FILE), "rb") as f:
            state = pickle.load(f)
        self = cls.__new__(cls)
        self.trainable_blob = state["trainable_blob"]
        self.stop_criteria = state["stop_criteria"]
        self.scheduler = state["scheduler"]
        self.searcher = state["searcher"]
        self.experiment_dir = experiment_dir
        self.max_concurrent = state.get("max_concurrent",
                                        _default_concurrency())
        self.max_failures = state["max_failures"]
        self.resources = state["resources"]
        self.poll_interval = poll_interval
        self.num_trials = state["num_trials"]
        self._created = state["created"]
        self._last_save = 0.0
        self.trials = []
        for row in state["trials"]:
            manager = CheckpointManager(os.path.join(
                experiment_dir, row["trial_id"], "checkpoints"))
            manager.restore_from_disk()
            trial = Trial(
                trial_id=row["trial_id"], config=row["config"],
                status=row["status"],
                metrics_history=row["metrics_history"],
                error=row["error"], num_failures=row["num_failures"],
                stopped_by_scheduler=row["stopped_by_scheduler"],
                stop_reason=row["stop_reason"],
                checkpoint_manager=manager)
            if trial.status in (PENDING, RUNNING):
                trial.status = PENDING
                trial.resume_checkpoint = manager.latest_checkpoint
            self.trials.append(trial)
        return self

    # ------------------------------------------------------------------ run
    def _make_trial(self) -> Optional[Trial]:
        trial_id = f"trial_{self._created:05d}"
        config = self.searcher.suggest(trial_id)
        if config is None:
            return None
        self._created += 1
        trial = Trial(
            trial_id=trial_id, config=config,
            checkpoint_manager=CheckpointManager(
                os.path.join(self.experiment_dir, trial_id,
                             "checkpoints")))
        self.trials.append(trial)
        if isinstance(self.scheduler, PopulationBasedTraining):
            self.scheduler.register(trial_id, config)
        return trial

    def run(self) -> List[Trial]:
        import ray_tpu

        # restored experiments re-queue their interrupted trials
        pending: List[Trial] = [t for t in self.trials
                                if t.status == PENDING]
        running: List[Trial] = []
        exhausted = False
        self._save_state()
        while True:
            while pending and len(running) < self.max_concurrent:
                trial = pending.pop(0)
                self._start_trial(trial)
                running.append(trial)
            while (not exhausted and self._created < self.num_trials
                   and len(running) < self.max_concurrent):
                trial = self._make_trial()
                if trial is None:
                    # a ConcurrencyLimiter returns None while throttled;
                    # with nothing running it can only mean exhaustion
                    if not running:
                        exhausted = True
                    break
                self._start_trial(trial)
                running.append(trial)
            if self._created >= self.num_trials:
                exhausted = True
            if not pending and not running and exhausted:
                break
            time.sleep(self.poll_interval)
            changed = False
            for trial in running:
                if trial.poll_ref is None:
                    trial.poll_ref = trial.actor.poll.remote()
                    trial.poll_since = time.monotonic()
            ready, _ = ray_tpu.wait(
                [t.poll_ref for t in running], num_returns=len(running),
                timeout=self.poll_interval)
            answered = {t.trial_id for t in running if t.poll_ref in ready}
            for trial in list(running):
                if (trial.trial_id not in answered
                        and time.monotonic() - trial.poll_since < 60):
                    continue
                done = self._poll_trial(trial)
                if done:
                    changed = True
                    running.remove(trial)
                    if (trial.status == ERRORED
                            and trial.num_failures <= self.max_failures):
                        trial.status = PENDING
                        trial.error = None
                        trial.resume_checkpoint = (
                            trial.checkpoint_manager.latest_checkpoint)
                        pending.append(trial)
                    else:
                        self.searcher.on_trial_complete(
                            trial.trial_id, trial.last_metrics)
            # persist on every completion and at least every 5s while
            # trials report (a killed driver resumes from here)
            if changed or time.monotonic() - self._last_save > 5.0:
                self._save_state()
        self._save_state()
        return self.trials

    # ------------------------------------------------------------ internals
    def _start_trial(self, trial: Trial):
        import ray_tpu

        trial_dir = os.path.join(self.experiment_dir, trial.trial_id)
        os.makedirs(trial_dir, exist_ok=True)
        actor_cls = ray_tpu.remote(TrainWorker)
        num_cpus = self.resources.get("CPU", 1)
        res = {k: v for k, v in self.resources.items() if k != "CPU"}
        trial.actor = actor_cls.options(
            num_cpus=num_cpus, resources=res or None,
        ).remote(0, 1, trial.trial_id, trial_dir, None)
        ckpt = trial.resume_checkpoint
        trial.actor.start_training.remote(
            self.trainable_blob, trial.config,
            ckpt.path if ckpt else None)
        trial.status = RUNNING

    def _poll_trial(self, trial: Trial) -> bool:
        """Returns True when the trial left the running set."""
        import ray_tpu

        ref, trial.poll_ref = trial.poll_ref, None
        try:
            poll = ray_tpu.get(ref, timeout=10)
        except Exception as e:
            trial.status = ERRORED
            trial.error = f"poll failed: {e!r}"
            trial.num_failures += 1
            self._stop_actor(trial)
            self.scheduler.on_complete(trial.trial_id)
            return True
        sched_stop = criteria_stop = False
        for rep in poll["reports"]:
            metrics = dict(rep["metrics"])
            metrics.setdefault("training_iteration",
                               len(trial.metrics_history) + 1)
            trial.metrics_history.append(metrics)
            if rep["checkpoint_path"]:
                trial.checkpoint_manager.register(
                    Checkpoint(rep["checkpoint_path"]), metrics)
            if self.scheduler.on_result(trial.trial_id, metrics) == STOP:
                sched_stop = True
            if self._meets_stop_criteria(metrics):
                criteria_stop = True
        decision = STOP if (sched_stop or criteria_stop) else CONTINUE
        if decision == STOP and poll["state"] == RUNNING:
            # keep scheduler stops distinct from RunConfig.stop criteria
            trial.stopped_by_scheduler = sched_stop
            trial.stop_reason = ("scheduler" if sched_stop
                                 else "stop_criteria")
            try:
                trial.actor.stop.remote()
            except Exception:  # rtpulint: ignore[RTPU006] — graceful-stop escalation: _stop_actor force-kills right after
                pass
            self._stop_actor(trial)
            trial.status = TERMINATED
            self.scheduler.on_complete(trial.trial_id)
            self._discard_pending_exploit(trial)
            return True
        if poll["state"] in (FINISHED, ERRORED):
            trial.status = poll["state"]
            if poll["state"] == ERRORED:
                trial.error = poll["error"]
                trial.num_failures += 1
            self._stop_actor(trial)
            self.scheduler.on_complete(trial.trial_id)
            self._discard_pending_exploit(trial)
            return True
        # Exploit only trials that are still running — a perturbation that
        # landed on the trial's final report must not restart it (and must
        # not rewrite its config after the fact).
        self._apply_pbt(trial)
        return False

    def _meets_stop_criteria(self, metrics: Dict[str, Any]) -> bool:
        """RunConfig.stop: {metric: threshold} — stop once any metric
        reaches its threshold (ref: air RunConfig.stop dict form)."""
        for key, threshold in self.stop_criteria.items():
            value = metrics.get(key)
            if value is not None and value >= threshold:
                return True
        return False

    def _discard_pending_exploit(self, trial: Trial):
        sched = self.scheduler
        if isinstance(sched, PopulationBasedTraining):
            sched.pending_exploits.pop(trial.trial_id, None)

    def _apply_pbt(self, trial: Trial):
        sched = self.scheduler
        if not isinstance(sched, PopulationBasedTraining):
            return
        exploit = sched.pending_exploits.pop(trial.trial_id, None)
        if exploit is None:
            return
        donor_id, new_cfg = exploit
        donor = next(t for t in self.trials if t.trial_id == donor_id)
        donor_ckpt = (donor.checkpoint_manager.latest_checkpoint
                      if donor.checkpoint_manager else None)
        logger.info("PBT exploit: %s <- %s (cfg %s)", trial.trial_id,
                    donor_id, new_cfg)
        self._stop_actor(trial)
        trial.config = new_cfg
        sched.register(trial.trial_id, new_cfg)
        trial.resume_checkpoint = donor_ckpt
        self._start_trial(trial)

    def _stop_actor(self, trial: Trial):
        import ray_tpu

        if trial.actor is not None:
            try:
                ray_tpu.kill(trial.actor)
            except Exception:  # rtpulint: ignore[RTPU006] — kill of an already-dead trial actor is the expected teardown race
                pass
            trial.actor = None


def _default_concurrency() -> int:
    try:
        import ray_tpu

        return max(int(ray_tpu.cluster_resources().get("CPU", 2)), 1)
    except Exception:
        return 2
