"""Public Serve API.

Parity with the reference (ref: python/ray/serve/api.py — serve.run :687,
serve.start, serve.status, serve.delete, serve.shutdown,
serve.get_app_handle / get_deployment_handle; client ref:
serve/_private/client.py deploy_apps :328).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

from ..runtime.procutil import log
from .config import (CONTROLLER_NAME, DEFAULT_APP_NAME, DEFAULT_HTTP_PORT,
                     GRPC_PROXY_NAME, PROXY_NAME, HTTPOptions, gRPCOptions)
from .deployment import Application, flatten_app
from .handle import DeploymentHandle, _Router


def _warn_admission_pool_sizing(specs) -> list:
    """Config sanity at deploy time (PR 13 known gap): every queued
    picker parks one thread in handle._SUBMIT_POOL, so with
    max_queued_requests >= the pool size the bounded-queue cap is
    UNREACHABLE — overflow .remote() calls wait in the executor's own
    unbounded queue where no admission or deadline logic runs, which is
    exactly the timeout storm the admission plane exists to prevent.
    Returns the offending deployment names (unit-testable)."""
    from .handle import _SUBMIT_POOL

    pool = _SUBMIT_POOL._max_workers
    offenders = []
    for spec in specs:
        cap = getattr(spec.config, "max_queued_requests", -1)
        if cap is not None and cap >= pool:
            offenders.append(spec.name)
            log.warning(
                "serve deployment %r: max_queued_requests=%d >= the "
                "submit/call pool size (%d) — queued requests beyond "
                "the pool park in an unbounded executor queue where no "
                "admission or deadline logic runs; lower the cap below "
                "the pool size", spec.name, cap, pool)
    return offenders


def _get_controller(create: bool = True):
    """Get a LIVE controller handle, creating one if needed. A freshly
    killed controller can linger in the name registry until its death
    notification lands, so ping-validate and retry (ref: the reference
    avoids this by making the controller detached + lifetime-owned)."""
    import ray_tpu
    from ..actor import ActorClass
    from .controller import ServeControllerActor

    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            handle = ray_tpu.get_actor(CONTROLLER_NAME)
        except Exception:
            handle = None
        if handle is None:
            if not create:
                raise ValueError("Serve is not running")
            handle = ActorClass(ServeControllerActor, name=CONTROLLER_NAME,
                                get_if_exists=True,
                                max_concurrency=64).remote()
        try:
            ray_tpu.get(handle.ping.remote(), timeout=10)
        except Exception:
            time.sleep(0.1)  # dying controller still registered; wait
            continue
        handle.run_control_loop.remote()  # idempotent fire-and-forget
        return handle
    raise RuntimeError("could not obtain a live Serve controller")


def start(http_options: Optional[HTTPOptions] = None,
          grpc_options: Optional[gRPCOptions] = None, **_ignored) -> None:
    """Start the Serve control plane + ingress proxies (ref: api.py
    serve.start — HTTP always, gRPC when grpc_options given)."""
    import ray_tpu
    from ..actor import ActorClass
    from .proxy import ProxyActor

    _get_controller()
    opts = http_options or HTTPOptions(port=DEFAULT_HTTP_PORT)
    try:
        ray_tpu.get_actor(PROXY_NAME)
    except Exception:
        proxy = ActorClass(ProxyActor, name=PROXY_NAME, get_if_exists=True,
                           max_concurrency=256).remote(opts.host, opts.port)
        proxy.run.remote()  # fire-and-forget server loop
        ray_tpu.get(proxy.get_port.remote())  # wait until listening
    if grpc_options is not None:
        from .grpc_proxy import GrpcProxyActor

        try:
            ray_tpu.get_actor(GRPC_PROXY_NAME)
        except Exception:
            gproxy = ActorClass(
                GrpcProxyActor, name=GRPC_PROXY_NAME, get_if_exists=True,
                max_concurrency=256).remote(grpc_options.host,
                                            grpc_options.port)
            gproxy.run.remote()  # fire-and-forget server loop
            ray_tpu.get(gproxy.get_port.remote())


def get_proxy_url() -> str:
    import ray_tpu

    proxy = ray_tpu.get_actor(PROXY_NAME)
    port = ray_tpu.get(proxy.get_port.remote())
    return f"http://127.0.0.1:{port}"


def get_grpc_address() -> str:
    """host:port of the gRPC ingress (requires serve.start(
    grpc_options=...))."""
    import ray_tpu

    proxy = ray_tpu.get_actor(GRPC_PROXY_NAME)
    port = ray_tpu.get(proxy.get_port.remote())
    return f"127.0.0.1:{port}"


def run(app: Application, *, name: str = DEFAULT_APP_NAME,
        route_prefix: str = "/", blocking: bool = False,
        _start_http: bool = False, wait_timeout_s: float = 180.0,
        local_testing_mode: bool = False,
        ) -> DeploymentHandle:
    """Deploy an application and wait for it to be RUNNING
    (ref: serve/api.py:687). With ``local_testing_mode=True`` every
    replica runs in-process — no cluster, no controller, no actors
    (ref: serve/_private/local_testing_mode.py)."""
    if local_testing_mode:
        from .local_mode import run_local

        return run_local(app, name)
    from ..runtime import serialization

    controller = _get_controller()
    if _start_http:
        start()
    specs = flatten_app(app, name)
    _warn_admission_pool_sizing(specs)
    payload = []
    for spec in specs:
        cfg_blob = serialization.dumps_inline(spec.config)
        payload.append({
            "name": spec.name,
            "spec_blob": serialization.dumps_inline(spec),
            "config_blob": cfg_blob,
            "is_ingress": spec.is_ingress,
        })
    import ray_tpu

    ray_tpu.get(controller.deploy_app.remote(name, route_prefix, payload))
    _Router.reset_all()  # old routing tables may reference dead replicas
    # Wait for the app to become RUNNING (reuse the live controller handle
    # rather than re-running the _get_controller handshake per poll).
    deadline = time.time() + wait_timeout_s
    st = None
    while time.time() < deadline:
        st = ray_tpu.get(controller.status.remote())["applications"].get(name)
        if st and st["status"] == "RUNNING":
            break
        if st and st["status"] == "DEPLOY_FAILED":
            # a replica's constructor keeps raising (e.g. it cannot
            # initialise its device): surface ITS error now
            errors = "\n".join(
                f"{dep}: {d['message']}"
                for dep, d in st["deployments"].items()
                if d["status"] == "DEPLOY_FAILED")
            raise RuntimeError(
                f"app {name!r} failed to deploy: replica constructor "
                f"failed {errors}")
        time.sleep(0.05)
    else:
        raise RuntimeError(
            f"app {name!r} did not become RUNNING within {wait_timeout_s}s; "
            f"status: {st}")
    ingress = ray_tpu.get(controller.get_ingress.remote(name))
    handle = DeploymentHandle(name, ingress)
    if blocking:
        while True:
            time.sleep(1)
    return handle


def status() -> Dict[str, Any]:
    import ray_tpu

    controller = _get_controller()
    return ray_tpu.get(controller.status.remote())


def get_app_handle(name: str = DEFAULT_APP_NAME) -> DeploymentHandle:
    import ray_tpu

    from .local_mode import get_local_app

    local = get_local_app(name)
    if local is not None:
        return local
    controller = _get_controller(create=False)
    ingress = ray_tpu.get(controller.get_ingress.remote(name))
    if ingress is None:
        raise ValueError(f"no application named {name!r}")
    return DeploymentHandle(name, ingress)


def get_deployment_handle(deployment_name: str,
                          app_name: str = DEFAULT_APP_NAME,
                          ) -> DeploymentHandle:
    return DeploymentHandle(app_name, deployment_name)


def delete(name: str) -> None:
    import ray_tpu

    from .local_mode import delete_local_app

    if delete_local_app(name):
        return
    controller = _get_controller(create=False)
    ray_tpu.get(controller.delete_app.remote(name))
    _Router.reset_all()


def shutdown() -> None:
    import ray_tpu

    try:
        controller = _get_controller(create=False)
        ray_tpu.get(controller.shutdown.remote(), timeout=30)
    except Exception:  # rtpulint: ignore[RTPU006] — controller already gone; proxy cleanup below still runs
        pass
    for actor_name in (PROXY_NAME, GRPC_PROXY_NAME, CONTROLLER_NAME):
        try:
            ray_tpu.kill(ray_tpu.get_actor(actor_name))
        except Exception:  # rtpulint: ignore[RTPU006] — actor may never have been started (no grpc proxy, already-dead controller)
            pass
    # Wait for the names to clear so a subsequent serve.start() is clean.
    deadline = time.time() + 15
    for actor_name in (PROXY_NAME, GRPC_PROXY_NAME, CONTROLLER_NAME):
        while time.time() < deadline:
            try:
                if ray_tpu.get_actor(actor_name) is None:
                    break
            except Exception:
                break
            time.sleep(0.05)
    _Router.reset_all()
