"""Worker group: N training actors, gang-placed, polled by the controller.

ref: python/ray/train/_internal/worker_group.py (WorkerGroup) and
train/v2/_internal/execution/worker_group/worker_group.py. Each worker is
an actor hosting the user's train fn on a thread; the controller drains
report queues via poll() RPCs. TPU twist: the group is placed with a
placement group in PACK/STRICT_SPREAD so each worker lands on its own host
of a slice (gang scheduling, SURVEY.md §7 "TPU twist on scheduling").
"""

from __future__ import annotations

import logging
import threading
import traceback
from typing import Any, Callable, Dict, List, Optional

from .checkpoint import Checkpoint
from .session import TrainContext, init_session, shutdown_session

RUNNING = "RUNNING"
FINISHED = "FINISHED"
ERRORED = "ERRORED"
PENDING = "PENDING"


class TrainWorker:
    """Actor hosting one training process (ref: worker_group.py Worker)."""

    def __init__(self, rank: int, world_size: int, experiment_name: str,
                 trial_dir: str, backend_env: Optional[Dict[str, str]] = None):
        import os

        self.rank = rank
        self.world_size = world_size
        self.experiment_name = experiment_name
        self.trial_dir = trial_dir
        self.state = PENDING
        self.error: Optional[str] = None
        self.result: Any = None
        self._thread: Optional[threading.Thread] = None
        self._session = None
        for k, v in (backend_env or {}).items():
            os.environ[k] = v

    def node_info(self) -> Dict[str, Any]:
        import os
        import socket

        return {"rank": self.rank, "hostname": socket.gethostname(),
                "pid": os.getpid()}

    def start_training(self, train_fn_blob: bytes, config: Dict[str, Any],
                       checkpoint_path: Optional[str] = None) -> None:
        from ..runtime import serialization

        train_fn = serialization.loads_inline(train_fn_blob)
        ckpt = Checkpoint(checkpoint_path) if checkpoint_path else None
        context = TrainContext(
            world_size=self.world_size, world_rank=self.rank,
            local_rank=0, local_world_size=1, node_rank=self.rank,
            experiment_name=self.experiment_name, trial_dir=self.trial_dir)
        self._session = init_session(context, ckpt)
        self.state = RUNNING
        self.error = None

        def _run():
            try:
                self._maybe_init_jax_distributed()
                if _accepts_config(train_fn):
                    self.result = train_fn(config)
                else:
                    self.result = train_fn()
                self.state = FINISHED
            except SystemExit:
                self.state = FINISHED
            except BaseException:  # noqa: BLE001
                self.error = traceback.format_exc()
                self.state = ERRORED

        self._thread = threading.Thread(target=_run, daemon=True,
                                        name=f"train-worker-{self.rank}")
        self._thread.start()

    def _maybe_init_jax_distributed(self):
        """Multi-host SPMD bootstrap: worker 0 publishes a coordinator
        address in the cluster KV; everyone enters
        jax.distributed.initialize (the MASTER_ADDR rendezvous of ref
        train/torch/config.py:66, with the cluster KV as the store)."""
        import os
        import socket
        import time

        plat = os.environ.get("RTPU_JAX_PLATFORMS")
        distributed = os.environ.get("RTPU_JAX_DISTRIBUTED") == "1"
        if plat or distributed:
            import jax

            from ..util.compile_cache import enable_compile_cache

            enable_compile_cache()
            if plat:
                jax.config.update("jax_platforms", plat)
        if not distributed:
            return
        num = int(os.environ.get("RTPU_JAX_NUM_PROCESSES",
                                 str(self.world_size)))
        from ..runtime.core import get_core

        core = get_core()
        ns = f"__train_coord:{self.experiment_name}"
        key = f"coordinator:{num}"
        if self.rank == 0:
            s = socket.socket()
            s.bind(("", 0))
            port = s.getsockname()[1]
            s.close()
            host = socket.gethostbyname(socket.gethostname())
            addr = f"{host}:{port}"
            core.controller.call("kv_put", ns=ns, key=key,
                                 value=addr.encode(), overwrite=True)
        else:
            deadline = time.monotonic() + 120
            addr = None
            while time.monotonic() < deadline:
                raw = core.controller.call("kv_get", ns=ns, key=key)
                if raw:
                    addr = raw.decode() if isinstance(raw, bytes) else raw
                    break
                time.sleep(0.2)
            if addr is None:
                raise TimeoutError("jax coordinator address never published")
        jax.distributed.initialize(coordinator_address=addr,
                                   num_processes=num,
                                   process_id=self.rank)

    def poll(self) -> Dict[str, Any]:
        """Drain queued reports + current state (controller heartbeat).

        State is read BEFORE draining: if it was already terminal, every
        report is guaranteed enqueued, so the final report can't be lost to
        a race with the training thread."""
        state, error = self.state, self.error
        reports = []
        if self._session is not None:
            while not self._session.reports.empty():
                r = self._session.reports.get_nowait()
                ckpt = r["checkpoint"]
                reports.append({
                    "metrics": r["metrics"],
                    "checkpoint_path": ckpt.path if ckpt else None,
                })
        return {"state": state, "error": error,
                "reports": reports, "rank": self.rank}

    def stop(self) -> None:
        if self._session is not None:
            self._session.stop_event.set()

    def shutdown(self) -> None:
        shutdown_session()


def _accepts_config(fn: Callable) -> bool:
    import inspect

    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return True
    return len(sig.parameters) >= 1


class WorkerGroup:
    """Creates/destroys the gang of TrainWorker actors."""

    def __init__(self, num_workers: int, resources_per_worker: Dict[str, float],
                 experiment_name: str, trial_dir: str,
                 placement_strategy: str = "PACK",
                 backend_env: Optional[Dict[str, str]] = None):
        self.num_workers = num_workers
        self.resources = resources_per_worker
        self.experiment_name = experiment_name
        self.trial_dir = trial_dir
        self.placement_strategy = placement_strategy
        self.backend_env = backend_env or {}
        self.workers: List[Any] = []
        self._pg = None

    def start(self):
        import ray_tpu
        from ray_tpu.util.placement_group import placement_group
        from ray_tpu.util.scheduling_strategies import (
            PlacementGroupSchedulingStrategy)

        actor_cls = ray_tpu.remote(TrainWorker)
        bundles = [dict(self.resources) for _ in range(self.num_workers)]
        try:
            self._pg = placement_group(bundles,
                                       strategy=self.placement_strategy)
            if not self._pg.ready(timeout=60):
                raise TimeoutError("placement group not ready")
            strategies = [PlacementGroupSchedulingStrategy(
                placement_group=self._pg, placement_group_bundle_index=i)
                for i in range(self.num_workers)]
        except Exception as e:
            # no capacity for a gang on this cluster shape — fall back to
            # plain resource scheduling. STRICT strategies must not degrade
            # silently: a multi-host jax gang mis-placed would deadlock.
            self._remove_pg()  # never leak the half-reserved bundles
            if self.placement_strategy.startswith("STRICT"):
                raise
            logging.getLogger(__name__).warning(
                "placement group (%s) unavailable (%r); falling back to "
                "unplaced scheduling", self.placement_strategy, e)
            strategies = [None] * self.num_workers

        num_cpus = self.resources.get("CPU", 1)
        res = {k: v for k, v in self.resources.items() if k != "CPU"}
        try:
            self.workers = [
                actor_cls.options(
                    num_cpus=num_cpus, resources=res or None,
                    scheduling_strategy=strategies[i],
                ).remote(i, self.num_workers, self.experiment_name,
                         self.trial_dir, self.backend_env)
                for i in range(self.num_workers)
            ]
            # barrier on construction
            ray_tpu.get([w.node_info.remote() for w in self.workers],
                        timeout=120)
        except BaseException:
            self.shutdown()  # don't leak a partially-constructed gang
            raise
        return self

    def run_async(self, method: str, *args, **kwargs):
        return [getattr(w, method).remote(*args, **kwargs)
                for w in self.workers]

    def run(self, method: str, *args, timeout: float = 300.0, **kwargs):
        import ray_tpu

        return ray_tpu.get(self.run_async(method, *args, **kwargs),
                           timeout=timeout)

    def shutdown(self):
        import ray_tpu

        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:  # rtpulint: ignore[RTPU006] — gang teardown is best-effort; a worker already dead is the common case here
                pass
        self.workers = []
        self._remove_pg()

    def _remove_pg(self):
        if self._pg is not None:
            try:
                from ray_tpu.util.placement_group import (
                    remove_placement_group)

                remove_placement_group(self._pg)
            except Exception:  # rtpulint: ignore[RTPU006] — teardown: the controller reclaims bundles of a dead owner regardless
                pass
            self._pg = None
