"""Serve controller actor: owns app/deployment state and reconciles replicas.

Parity with the reference's control plane (ref:
python/ray/serve/_private/controller.py ServeController :87, control loop
:373; application state ref: serve/_private/application_state.py;
replica reconciliation ref: serve/_private/deployment_state.py — scaled down
to a single reconcile loop per controller). Autoscaling decisions poll
replica metrics (ref: serve/_private/autoscaling_state.py).
"""

from __future__ import annotations

import asyncio
import itertools
import time
from typing import Any, Dict, List, Optional

from .config import replica_actor_name


class _ReplicaState:
    def __init__(self, replica_id: str, handle, pg=None):
        self.replica_id = replica_id
        self.handle = handle
        # per-replica placement group (tp-sized TPU gang reservation);
        # removed with the replica
        self.pg = pg
        self.started_at = time.time()
        self.healthy = True
        # A replica is "ready" after its first successful health check
        # (i.e. its constructor finished). Unready replicas are exempt
        # from health-check kills until REPLICA_STARTUP_TIMEOUT_S — the
        # reference models this as the STARTING replica state
        # (ref: deployment_state.py ReplicaState.STARTING).
        self.ready = False
        self.last_health_check = 0.0
        self.ongoing = 0
        # In-flight health probe (checks never block the reconcile loop).
        self.check_task = None
        self.check_started = 0.0


REPLICA_STARTUP_TIMEOUT_S = 600.0
# A replica whose constructor raises (its actor dies with "creation
# failed": a device that cannot be initialised, a bad config) is retried
# this many times; then the deployment is DEPLOY_FAILED and carries the
# constructor's error, so serve.run raises it instead of waiting out its
# timeout on a replica that is forever STARTING.
MAX_REPLICA_START_FAILURES = 3

# cluster prefix-cache registry: poll cadence for replica frontiers and
# the staleness TTL past which an entry stops influencing routing
KV_POLL_INTERVAL_S = 1.0
KV_REGISTRY_TTL_S = 15.0


class _DeploymentState:
    def __init__(self, app_name: str, spec_blob: bytes, config):
        self.app_name = app_name
        self.spec_blob = spec_blob
        self.config = config
        self.replicas: Dict[str, _ReplicaState] = {}
        self.target_replicas = config.initial_replicas()
        self.version = 0
        self.is_ingress = False
        self.name = ""
        # consecutive constructor failures with no replica reaching READY
        # in between, and the last one's error (see
        # MAX_REPLICA_START_FAILURES)
        self.start_failures = 0
        self.start_error: Optional[str] = None
        # autoscaling smoothing state
        self._scale_up_since: Optional[float] = None
        self._scale_down_since: Optional[float] = None
        # overload (brownout) state: EWMA of the shed FRACTION reported
        # by routers with their routing-table polls (+ replica-side shed
        # deltas folded in by _autoscale). Published back on the routing
        # table so every router sees cluster-wide saturation, and fed to
        # the autoscaler so it scales on rejects, not just queue depth.
        self.shed_rate_ewma = 0.0
        self._last_stats_at = 0.0
        # sheds accumulated since the autoscaler last consumed them
        self._shed_window = 0
        # cumulative per-replica shed counters already consumed
        self._replica_sheds_seen: Dict[str, int] = {}
        # prefix-cache registry polling state: None = unknown (probe),
        # False = replicas expose no KV frontier (stop probing)
        self._kv_enabled: Optional[bool] = None
        self._kv_next_poll = 0.0


class ServeControllerActor:
    """Named actor `SERVE_CONTROLLER`. Runs `run_control_loop` fire-and-
    forget after creation (the reference does the same, controller.py:373)."""

    def __init__(self, http_host: str = "127.0.0.1", http_port: int = 0):
        self._apps: Dict[str, Dict[str, _DeploymentState]] = {}
        self._ingress: Dict[str, str] = {}  # app -> ingress deployment name
        self._route_prefixes: Dict[str, str] = {}  # app -> route prefix
        self._id_counter = itertools.count()
        self._running = True
        self._http = (http_host, http_port)
        self._reconcile_wakeup = asyncio.Event()
        self._stop_tasks: set = set()
        # cluster prefix-cache registry (KV plane): (app, deployment) ->
        # {replica actor_id: {hashes, rev, page_size, ts}}; fed by the
        # reconcile loop's frontier polls (or kv_registry_publish pushes)
        # and served to routers via kv_registry_get
        self._kv_registry: Dict[tuple, Dict[str, dict]] = {}

    # ------------------------------------------------------------- deploy

    async def deploy_app(self, app_name: str, route_prefix: str,
                         deployments: List[dict]) -> None:
        """deployments: [{name, spec_blob, config_blob, is_ingress}]"""
        from ..runtime import serialization

        old = self._apps.get(app_name, {})
        new_states: Dict[str, _DeploymentState] = {}
        for item in deployments:
            config = serialization.loads_inline(item["config_blob"])
            state = old.get(item["name"])
            if state is None:
                state = _DeploymentState(app_name, item["spec_blob"], config)
            else:
                # Redeploy. Code/init-arg changes replace every replica;
                # config-only changes apply in place (num_replicas adjusts
                # target, user_config reconfigures live replicas) — the
                # reference's lightweight-update path (ref:
                # deployment_state.py deployment version diffing).
                old_blob = state.spec_blob
                old_cfg = state.config
                state.spec_blob = item["spec_blob"]
                state.config = config
                state.target_replicas = config.initial_replicas()
                state.start_failures = 0  # a redeploy gets fresh tries
                state.start_error = None
                if not _same_code(old_blob, item["spec_blob"]):
                    self._stop_all_replicas(state)
                elif old_cfg.user_config != config.user_config:
                    for rep in state.replicas.values():
                        rep.handle.reconfigure.remote(config.user_config)
                state.version += 1
            state.name = item["name"]
            state.is_ingress = item["is_ingress"]
            if item["is_ingress"]:
                self._ingress[app_name] = item["name"]
            new_states[item["name"]] = state
        # Tear down deployments dropped from the app.
        for name, state in old.items():
            if name not in new_states:
                self._stop_all_replicas(state)
        self._apps[app_name] = new_states
        self._route_prefixes[app_name] = route_prefix
        self._reconcile_wakeup.set()

    async def delete_app(self, app_name: str) -> None:
        states = self._apps.pop(app_name, {})
        self._ingress.pop(app_name, None)
        self._route_prefixes.pop(app_name, None)
        for name, state in states.items():
            self._stop_all_replicas(state)
            self._kv_registry.pop((app_name, name), None)

    async def shutdown(self) -> None:
        self._running = False
        for app in list(self._apps):
            await self.delete_app(app)
        if self._stop_tasks:  # let graceful drains finish before we die
            await asyncio.wait(self._stop_tasks, timeout=30)

    # ---------------------------------------------------------- reconcile

    async def run_control_loop(self) -> None:
        if getattr(self, "_loop_started", False):
            return  # idempotent: every _get_controller() call fires this
        self._loop_started = True
        while self._running:
            try:
                from ..runtime import faults

                faults.syncpoint("serve.reconcile")
                await self._reconcile_once()
            except Exception:  # keep the loop alive (ref: controller.py:373)
                import traceback

                traceback.print_exc()
            try:
                await asyncio.wait_for(self._reconcile_wakeup.wait(),
                                       timeout=0.25)
            except asyncio.TimeoutError:
                pass
            self._reconcile_wakeup.clear()

    async def _reconcile_once(self) -> None:
        for app_name, states in list(self._apps.items()):
            for state in list(states.values()):
                self._decay_overload(state)
                await self._autoscale(state)
                await self._health_check(state)
                await self._kv_poll(state)
                # Scale up (not past the start-failure bound: a
                # constructor that keeps raising is not retried forever)
                while (len(state.replicas) < state.target_replicas
                       and state.start_failures
                       < MAX_REPLICA_START_FAILURES):
                    self._start_replica(state)
                # Scale down (newest first, like the reference's default)
                while len(state.replicas) > state.target_replicas:
                    replica_id = max(state.replicas,
                                     key=lambda r: state.replicas[r].started_at)
                    await self._stop_replica(state, replica_id)

    def _start_replica(self, state: _DeploymentState) -> None:
        from ..actor import ActorClass
        from .replica import ReplicaActor

        replica_id = f"r{next(self._id_counter)}"
        name = replica_actor_name(state.app_name, state.name, replica_id)
        opts = dict(state.config.ray_actor_options)
        pg = None
        if (getattr(state.config, "placement_bundles", None)
                and "scheduling_strategy" not in opts):
            # gang reservation (tensor-parallel replicas ask for a
            # tp-chip SLICE_PACK bundle): the group is created
            # non-blocking — the replica actor stays PENDING until its
            # bundle commits, exactly like any unschedulable actor. An
            # explicit scheduling_strategy in ray_actor_options wins;
            # creating a group the replica would never use would pin
            # idle chips for its whole lifetime.
            from ..util.placement_group import placement_group
            from ..util.scheduling_strategies import (
                PlacementGroupSchedulingStrategy)

            pg = placement_group(
                [dict(b) for b in state.config.placement_bundles],
                strategy=state.config.placement_strategy,
                name=f"{name}-pg")
            opts["scheduling_strategy"] = PlacementGroupSchedulingStrategy(
                pg, placement_group_bundle_index=0)
        try:
            handle = ActorClass(ReplicaActor, name=name,
                                max_concurrency=state.config.max_concurrency,
                                max_restarts=0, **opts).remote(
                state.app_name, state.name, replica_id, state.spec_blob)
        except Exception:
            # actor creation failed before any _ReplicaState could own
            # the group: release it now, or every reconcile retry would
            # strand another tp-chip reservation nothing can ever use
            if pg is not None:
                try:
                    from ..util.placement_group import (
                        remove_placement_group)

                    remove_placement_group(pg)
                except Exception:  # rtpulint: ignore[RTPU006] — rollback of a group that may not have committed; the raise below carries the real error
                    pass
            raise
        state.replicas[replica_id] = _ReplicaState(replica_id, handle,
                                                   pg=pg)
        state.version += 1

    def _stop_replica(self, state: _DeploymentState,
                      replica_id: str) -> None:
        """Remove the replica from routing now; drain + kill in the
        background so one slow drain can't stall reconciliation."""
        rep = state.replicas.pop(replica_id)
        state.version += 1
        task = asyncio.ensure_future(
            self._drain_and_kill(rep, state.config))
        self._stop_tasks.add(task)
        task.add_done_callback(self._stop_tasks.discard)

    async def _drain_and_kill(self, rep: _ReplicaState, config) -> None:
        import ray_tpu

        try:
            await asyncio.wait_for(
                asyncio.wrap_future(
                    rep.handle.prepare_for_shutdown.remote().future()),
                timeout=config.graceful_shutdown_timeout_s + 1)
        except Exception:  # rtpulint: ignore[RTPU006] — graceful-drain timeout/refusal falls through to the hard kill below
            pass
        try:
            ray_tpu.kill(rep.handle)
        except Exception:  # rtpulint: ignore[RTPU006] — replica already dead; pg cleanup below still runs
            pass
        self._remove_replica_pg(rep)

    @staticmethod
    def _remove_replica_pg(rep: _ReplicaState) -> None:
        if rep.pg is None:
            return
        try:
            from ..util.placement_group import remove_placement_group

            remove_placement_group(rep.pg)
        except Exception:  # rtpulint: ignore[RTPU006] — group may already be removed with the session; leaking it here only outlives us by the session
            pass
        rep.pg = None

    def _stop_all_replicas(self, state: _DeploymentState) -> None:
        for replica_id in list(state.replicas):
            self._stop_replica(state, replica_id)

    async def _health_check(self, state: _DeploymentState) -> None:
        """Fully non-blocking: probes run as background tasks and results
        are consumed on later ticks, so a hung/slow-starting replica never
        stalls reconciliation of other replicas or apps."""
        now = time.time()
        for replica_id, rep in list(state.replicas.items()):
            if rep.check_task is not None:
                if rep.check_task.done():
                    exc = (None if rep.check_task.cancelled()
                           else rep.check_task.exception())
                    failed = rep.check_task.cancelled() or exc is not None
                    rep.check_task = None
                    if not failed:
                        rep.healthy = True
                        if not rep.ready:
                            rep.ready = True
                            state.start_failures = 0
                            state.version += 1  # newly routable replica
                    else:
                        self._on_check_failure(state, replica_id, rep, now,
                                               exc)
                elif (now - rep.check_started
                        > state.config.health_check_timeout_s):
                    rep.check_task.cancel()
                    rep.check_task = None
                    self._on_check_failure(state, replica_id, rep, now)
                continue
            # Unready (starting) replicas are probed aggressively so
            # readiness is noticed quickly; ready ones on the period.
            period = (0.1 if not rep.ready
                      else state.config.health_check_period_s)
            if now - rep.last_health_check < period:
                continue
            rep.last_health_check = now
            rep.check_started = now
            rep.check_task = asyncio.ensure_future(
                asyncio.wrap_future(
                    rep.handle.check_health.remote().future()))

    def _on_check_failure(self, state: _DeploymentState, replica_id: str,
                          rep: _ReplicaState, now: float,
                          exc: Optional[BaseException] = None) -> None:
        from ..exceptions import ActorDiedError

        if not rep.ready:
            if isinstance(exc, ActorDiedError):
                # the constructor raised (or the worker died under it):
                # count it and keep the error for status()/serve.run
                state.start_failures += 1
                state.start_error = str(exc)
            elif now - rep.started_at < REPLICA_STARTUP_TIMEOUT_S:
                return  # constructor may still be running
        rep.healthy = False
        # Replace the dead replica (ref: deployment_state.py replica
        # recovery path).
        state.replicas.pop(replica_id, None)
        state.version += 1
        try:
            import ray_tpu

            ray_tpu.kill(rep.handle)
        except Exception:  # rtpulint: ignore[RTPU006] — the replica just failed its health check; it is usually already dead
            pass
        self._remove_replica_pg(rep)

    async def _kv_poll(self, state: _DeploymentState) -> None:
        """Poll ready replicas' KV prefix-cache frontiers into the
        cluster registry (KV plane). Piggybacks on the reconcile loop so
        publication is naturally batched (one snapshot per replica per
        interval) and the registry TTLs on the poll timestamps. A
        deployment whose replicas expose no frontier (ReplicaActor
        kv_frontier -> None) is marked off after the first answer and
        never polled again."""
        if state._kv_enabled is False:
            return
        now = time.time()
        if now < state._kv_next_poll:
            return
        state._kv_next_poll = now + KV_POLL_INTERVAL_S
        reps = [rep for rep in state.replicas.values()
                if rep.ready and rep.healthy]
        if not reps:
            return
        key = (state.app_name, state.name)
        entry = self._kv_registry.setdefault(key, {})
        # send each replica the rev we already hold: an unchanged
        # frontier answers WITHOUT its hash list (O(1) steady state)
        futs = {}
        for rep in reps:
            aid = rep.handle.actor_id
            prev = entry.get(aid)
            futs[aid] = asyncio.wrap_future(rep.handle.kv_frontier.remote(
                prev.get("rev") if prev else None).future())
        await asyncio.wait(futs.values(), timeout=2.0)
        answered, any_kv = False, False
        for aid, fut in futs.items():
            if not fut.done():
                fut.cancel()
                continue
            if fut.exception() is not None:
                continue
            answered = True
            snap = fut.result()
            if not isinstance(snap, dict) or "rev" not in snap:
                continue
            any_kv = True
            prev = entry.get(aid)
            if "hashes" in snap:
                entry[aid] = {"hashes": list(snap["hashes"]),
                              "rev": snap.get("rev"),
                              "page_size": snap.get("page_size"),
                              "ts": now}
            elif prev is not None and prev.get("rev") == snap.get("rev"):
                prev["ts"] = now  # unchanged frontier: refresh TTL only
            # hashes omitted with a rev we do not hold: stale protocol
            # answer — drop it; the next poll sends rev=None and gets
            # the full list
        if state._kv_enabled is None and answered:
            state._kv_enabled = any_kv
        # prune replicas that left the deployment
        live = {rep.handle.actor_id for rep in state.replicas.values()}
        for aid in list(entry):
            if aid not in live:
                del entry[aid]
        if not entry:
            self._kv_registry.pop(key, None)

    def kv_registry_publish(self, app_name: str, deployment_name: str,
                            replica_actor_id: str, snapshot: dict) -> None:
        """Push-side registry entry (tests / external publishers; the
        normal path is the _kv_poll pull)."""
        entry = self._kv_registry.setdefault(
            (app_name, deployment_name), {})
        entry[replica_actor_id] = {
            "hashes": list(snapshot.get("hashes", ())),
            "rev": snapshot.get("rev"),
            "page_size": snapshot.get("page_size"),
            "ts": time.time()}

    def kv_registry_get(self, app_name: str,
                        deployment_name: str) -> Optional[dict]:
        """Router-facing registry view: {actor_id: [hashes]} with stale
        (TTL-expired) entries pruned."""
        entry = self._kv_registry.get((app_name, deployment_name))
        if not entry:
            return None
        now = time.time()
        for aid in list(entry):
            if now - entry[aid]["ts"] > KV_REGISTRY_TTL_S:
                del entry[aid]
        if not entry:
            return None
        page_sizes = {e["page_size"] for e in entry.values()
                      if e.get("page_size")}
        return {
            "replicas": {aid: e["hashes"] for aid, e in entry.items()},
            "page_size": next(iter(page_sizes)) if page_sizes else None,
        }

    def _note_router_stats(self, state: _DeploymentState,
                           stats: dict) -> None:
        """Fold one router's shed/admit deltas (piggybacked on its
        routing-table poll) into the deployment's overload state."""
        sheds = int(stats.get("shed", 0)) + int(stats.get("expired", 0))
        admits = int(stats.get("admitted", 0))
        if sheds + admits <= 0:
            return
        from ..runtime.config import get_config

        alpha = get_config().serve_ewma_alpha
        rate = sheds / (sheds + admits)
        state.shed_rate_ewma += alpha * (rate - state.shed_rate_ewma)
        state._shed_window += sheds
        state._last_stats_at = time.time()

    def _decay_overload(self, state: _DeploymentState) -> None:
        """Brownout must clear itself: with no shed reports for a few
        seconds (traffic stopped, or admission is succeeding again) the
        published shed rate decays toward zero each reconcile tick
        instead of pinning routers in brownout forever."""
        if state.shed_rate_ewma <= 0.0:
            return
        if time.time() - state._last_stats_at > 5.0:
            state.shed_rate_ewma *= 0.95
            if state.shed_rate_ewma < 0.01:
                state.shed_rate_ewma = 0.0

    async def _autoscale(self, state: _DeploymentState) -> None:
        cfg = state.config.autoscaling_config
        if cfg is None or not state.replicas:
            # Zero-replica deployments are woken by get_routing_table's
            # scale-from-zero path; nothing to measure here.
            return
        futs = {rep.replica_id: asyncio.wrap_future(
            rep.handle.get_metrics.remote().future())
            for rep in state.replicas.values()}
        if futs:  # poll all replicas concurrently, bounded wait
            await asyncio.wait(futs.values(), timeout=2.0)
        total = 0.0
        for rep in state.replicas.values():
            fut = futs.get(rep.replica_id)
            if fut is not None and fut.done() and fut.exception() is None:
                metrics = fut.result()
                rep.ongoing = metrics["ongoing"]
                # replica-side sheds (multi-router overcommit net) join
                # the shed window as their delta since the last poll
                sheds = int(metrics.get("shed_total", 0) or 0)
                seen = state._replica_sheds_seen.get(rep.replica_id, 0)
                if sheds > seen:
                    state._shed_window += sheds - seen
                state._replica_sheds_seen[rep.replica_id] = sheds
            elif fut is not None and not fut.done():
                fut.cancel()
            total += rep.ongoing
        for rid in list(state._replica_sheds_seen):
            if rid not in state.replicas:
                del state._replica_sheds_seen[rid]
        # Scale on REJECTS, not just queue depth: a shed request never
        # shows up in `ongoing`, so a saturated deployment shedding 90%
        # of its traffic would otherwise look exactly at target. Inflate
        # observed demand by the shed fraction (bounded 20x), and let a
        # non-empty shed window force at least target-exceeding demand.
        if state.shed_rate_ewma > 0.0:
            total = total / max(0.05, 1.0 - min(0.95, state.shed_rate_ewma))
        if state._shed_window > 0:
            total = max(total, len(state.replicas)
                        * cfg.target_ongoing_requests + 1)
            state._shed_window = 0
        desired = cfg.desired_replicas(total, len(state.replicas))
        now = time.time()
        if desired > state.target_replicas:
            state._scale_down_since = None
            if state._scale_up_since is None:
                state._scale_up_since = now
            if now - state._scale_up_since >= cfg.upscale_delay_s:
                state.target_replicas = desired
                state._scale_up_since = None
        elif desired < state.target_replicas:
            state._scale_up_since = None
            if state._scale_down_since is None:
                state._scale_down_since = now
            if now - state._scale_down_since >= cfg.downscale_delay_s:
                state.target_replicas = desired
                state._scale_down_since = None
        else:
            state._scale_up_since = None
            state._scale_down_since = None

    # ------------------------------------------------------------ queries

    def get_routing_table(self, app_name: str, deployment_name: str,
                          for_request: bool = False,
                          router_stats: Optional[dict] = None,
                          ) -> Optional[dict]:
        state = self._apps.get(app_name, {}).get(deployment_name)
        if state is None:
            return None
        if router_stats:
            # shed/admit deltas ride the poll the router makes anyway;
            # they feed the brownout EWMA published right back below
            self._note_router_stats(state, router_stats)
        if for_request and state.target_replicas == 0:
            # Scale-from-zero: a router asked on behalf of a live request
            # (ref: autoscaling wakes on handle queue metrics).
            state.target_replicas = 1
            self._reconcile_wakeup.set()
        return {
            "version": state.version,
            "max_ongoing_requests": state.config.max_ongoing_requests,
            "max_queued_requests": getattr(
                state.config, "max_queued_requests", -1),
            "shed_rate": round(state.shed_rate_ewma, 4),
            "replicas": [rep.handle.actor_id
                         for rep in state.replicas.values()
                         if rep.healthy and rep.ready],
        }

    def get_ingress(self, app_name: str) -> Optional[str]:
        return self._ingress.get(app_name)

    def list_routes(self) -> Dict[str, dict]:
        """route_prefix -> {app, ingress}, for the HTTP proxy (carrying the
        ingress deployment lets the proxy route with zero extra controller
        round-trips)."""
        return {prefix: {"app": app, "ingress": self._ingress.get(app)}
                for app, prefix in self._route_prefixes.items()}

    def status(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"applications": {}}
        for app_name, states in self._apps.items():
            deployments = {}
            for name, state in states.items():
                n_ready = sum(1 for rep in state.replicas.values()
                              if rep.ready)
                failed = (n_ready < state.target_replicas
                          and state.start_failures
                          >= MAX_REPLICA_START_FAILURES)
                deployments[name] = {
                    "status": ("HEALTHY" if n_ready >= state.target_replicas
                               else "DEPLOY_FAILED" if failed
                               else "UPDATING"),
                    "message": state.start_error if failed else "",
                    "replicas": n_ready,
                    "target_replicas": state.target_replicas,
                    # overload observability: the published brownout EWMA
                    "shed_rate": round(state.shed_rate_ewma, 4),
                }
            app_ok = all(d["status"] == "HEALTHY"
                         for d in deployments.values())
            app_failed = any(d["status"] == "DEPLOY_FAILED"
                             for d in deployments.values())
            out["applications"][app_name] = {
                "status": ("RUNNING" if app_ok
                           else "DEPLOY_FAILED" if app_failed
                           else "DEPLOYING"),
                "route_prefix": self._route_prefixes.get(app_name, "/"),
                "deployments": deployments,
            }
        return out

    def ping(self) -> str:
        return "pong"


def _same_code(blob_a: bytes, blob_b: bytes) -> bool:
    """True when two deployment specs carry the same callable code and init
    args (cloudpickle captures class bodies, so code edits change the
    bytes). False on any doubt — the safe direction is a full replica
    replacement."""
    from ..runtime import serialization

    try:
        a = serialization.loads_inline(blob_a)
        b = serialization.loads_inline(blob_b)
        return (serialization.dumps_inline((a.func_or_class, a.init_args,
                                            a.init_kwargs))
                == serialization.dumps_inline((b.func_or_class, b.init_args,
                                               b.init_kwargs)))
    except Exception:
        return False
