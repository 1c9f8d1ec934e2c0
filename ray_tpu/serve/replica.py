"""Replica actor: hosts one instance of a deployment's user callable.

Parity with the reference's replica runtime (ref:
python/ray/serve/_private/replica.py — UserCallableWrapper, request metric
tracking, reconfigure, health checks), minus the ASGI machinery: HTTP
requests arrive as plain `Request` objects from the proxy.
"""

from __future__ import annotations

import asyncio
import contextvars
import inspect
import time
from typing import Any, Dict, Optional

# The ABSOLUTE deadline (time.time() domain) of the request currently
# being handled, set for the duration of handle_request so user code —
# and any downstream DeploymentHandle.remote() it makes — inherits it
# (deadline PROPAGATION: one budget end-to-end, not per-hop resets).
_request_deadline: contextvars.ContextVar = contextvars.ContextVar(
    "rtpu_serve_request_deadline", default=None)


def get_request_deadline() -> Optional[float]:
    """Absolute wall-clock deadline of the request being handled (None
    outside a request, or when default deadlines are disabled)."""
    return _request_deadline.get()


class Request:
    """Minimal HTTP request view handed to deployments by the proxy
    (stand-in for the reference's starlette.Request)."""

    def __init__(self, method: str = "GET", path: str = "/",
                 query_params: Optional[Dict[str, str]] = None,
                 headers: Optional[Dict[str, str]] = None,
                 body: bytes = b""):
        self.method = method
        self.path = path
        self.query_params = query_params or {}
        self.headers = headers or {}
        self.body = body

    def json(self):
        import json

        return json.loads(self.body or b"null")

    def text(self) -> str:
        return self.body.decode()


class ReplicaActor:
    """One replica. Created by the controller; called by routers/handles.

    Tracks in-flight request count for autoscaling (ref: replica.py request
    metrics pushed to controller; here the controller polls get_metrics)."""

    def __init__(self, app_name: str, deployment_name: str, replica_id: str,
                 spec_blob: bytes):
        from ..runtime import serialization

        spec = serialization.loads_inline(spec_blob)
        self._app = app_name
        self._deployment = deployment_name
        self._replica_id = replica_id
        self._config = spec.config
        self._user_callable = spec.func_or_class(*spec.init_args,
                                                 **spec.init_kwargs)
        self._ongoing = 0
        self._total = 0
        self._started_at = time.time()
        # admission-plane accounting (polled by the controller via
        # get_metrics; the autoscaler scales on rejects, not only depth)
        self._admitted_total = 0
        self._shed_total = 0
        self._expired_total = 0
        from .admission import ServiceTimeEWMA

        self._service_ewma = ServiceTimeEWMA()
        if (spec.config.user_config is not None
                and hasattr(self._user_callable, "reconfigure")):
            self._user_callable.reconfigure(spec.config.user_config)

    def _admit(self, deadline: Optional[float]) -> None:
        """Replica-side admission: a request whose deadline already
        expired is dead work — shed it; and ongoing beyond
        max_ongoing + max_queued_requests means several routers
        overcommitted this replica past its bounded queue — shed typed
        instead of letting the pile ripen into a timeout storm. Health
        checks, metrics polls, and frontier polls are separate actor
        methods: saturation never sheds them (saturation != death)."""
        from ..exceptions import RequestExpiredError, ServiceOverloadedError
        from . import admission

        if admission.expired(deadline):
            self._expired_total += 1
            admission.count_shed(admission.SHED_EXPIRED)
            raise RequestExpiredError(
                f"deadline expired on arrival at replica "
                f"{self._replica_id} of {self._app}#{self._deployment}",
                where=f"replica {self._replica_id}")
        cfg = self._config
        cap = getattr(cfg, "max_queued_requests", -1)
        max_ongoing = getattr(cfg, "max_ongoing_requests", 0)
        if cap >= 0 and max_ongoing > 0 \
                and self._ongoing >= max_ongoing + cap:
            self._shed_total += 1
            admission.count_shed(admission.SHED_REPLICA_QUEUE)
            raise ServiceOverloadedError(
                f"replica {self._replica_id} of "
                f"{self._app}#{self._deployment} at capacity "
                f"({self._ongoing} ongoing >= {max_ongoing}+{cap})",
                reason=admission.SHED_REPLICA_QUEUE,
                retry_after_s=self._service_ewma.value)

    async def handle_request(self, method_name: str, args: tuple,
                             kwargs: dict,
                             deadline: Optional[float] = None,
                             budget_s: Optional[float] = None) -> Any:
        from . import admission

        # re-derive the absolute deadline against THIS replica's clock
        # from the relative budget stamped at send: cross-host clock
        # skew on the bare wall deadline shed requests early (receiver
        # clock ahead) or executed dead work late (behind). The
        # re-derived value also seeds the contextvar, so downstream
        # handle.remote() calls re-stamp a consistent local budget.
        deadline = admission.derive_deadline(deadline, budget_s)
        self._admit(deadline)
        self._admitted_total += 1
        self._ongoing += 1
        self._total += 1
        started = time.time()
        model_id = kwargs.pop("_multiplexed_model_id", None)
        token = None
        if model_id is not None:
            from .multiplex import _set_model_id

            token = _set_model_id(model_id)
        deadline_token = _request_deadline.set(deadline)
        try:
            if method_name in ("__call__", ""):
                target = self._user_callable
            else:
                target = getattr(self._user_callable, method_name)
            out = target(*args, **kwargs)
            if inspect.isawaitable(out):
                out = await out
            if inspect.isgenerator(out):
                out = list(out)  # streaming is materialized at the replica
            return out
        finally:
            self._ongoing -= 1
            self._service_ewma.update(time.time() - started)
            _request_deadline.reset(deadline_token)
            if token is not None:
                from .multiplex import _current_model_id

                _current_model_id.reset(token)

    def reconfigure(self, user_config: Any) -> None:
        self._config.user_config = user_config
        if hasattr(self._user_callable, "reconfigure"):
            self._user_callable.reconfigure(user_config)

    def get_metrics(self) -> Dict[str, Any]:
        return {"ongoing": self._ongoing, "total": self._total,
                "admitted_total": self._admitted_total,
                "shed_total": self._shed_total,
                "expired_total": self._expired_total,
                "service_ewma_s": self._service_ewma.value,
                "uptime_s": time.time() - self._started_at}

    async def kv_frontier(self, known_rev: Any = None
                          ) -> Optional[Dict[str, Any]]:
        """KV prefix-cache frontier of the hosted callable (None when the
        deployment exposes none — the controller stops polling then).
        `known_rev` is forwarded when the callable accepts it, letting it
        omit the hash list for an unchanged frontier."""
        fn = getattr(self._user_callable, "kv_frontier", None)
        if fn is None:
            return None
        try:
            takes_rev = bool(inspect.signature(fn).parameters)
        except (TypeError, ValueError):
            takes_rev = False
        out = fn(known_rev) if takes_rev else fn()
        if inspect.isawaitable(out):
            out = await out
        return out

    async def check_health(self) -> bool:
        fn = getattr(self._user_callable, "check_health", None)
        if fn is not None:
            out = fn()
            if inspect.isawaitable(out):
                out = await out
        return True

    async def prepare_for_shutdown(self) -> None:
        """Drain: wait (bounded) for in-flight requests to finish
        (ref: replica.py graceful shutdown)."""
        deadline = time.time() + self._config.graceful_shutdown_timeout_s
        while self._ongoing > 0 and time.time() < deadline:
            await asyncio.sleep(0.02)
        # then the callable's own leave-taking, where it has one (an LLM
        # server leaves its engine with nothing queued on the device)
        fn = getattr(self._user_callable, "shutdown", None)
        if fn is not None:
            out = fn()
            if inspect.isawaitable(out):
                await out
