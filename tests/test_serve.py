"""Serve tests.

Mirrors the reference's serve test strategy (ref: python/ray/serve/tests/
test_api.py, test_autoscaling_policy.py, test_proxy.py): deploy apps, call
through handles and HTTP, verify reconciliation/upgrade/autoscaling.
"""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_cluster(shared_cluster):
    yield shared_cluster
    serve.shutdown()


def _http_json(url, payload=None, timeout=30):
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(url, data=data, method="POST" if data else "GET")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read()


def test_deploy_and_call_handle(serve_cluster):
    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return 2 * x

        def triple(self, x):
            return 3 * x

    handle = serve.run(Doubler.bind(), name="doubler")
    assert handle.remote(21).result(timeout_s=30) == 42
    # Named-method routing via handle.options / attribute access.
    assert handle.options(method_name="triple").remote(5).result(30) == 15
    assert handle.triple.remote(7).result(30) == 21
    serve.delete("doubler")


def test_function_deployment_and_composition(serve_cluster):
    @serve.deployment
    def adder(x):
        return x + 1

    @serve.deployment
    class Pipeline:
        def __init__(self, downstream):
            self.downstream = downstream

        async def __call__(self, x):
            out = await self.downstream.remote(x)
            return out * 10

    handle = serve.run(Pipeline.bind(adder.bind()), name="pipe")
    assert handle.remote(4).result(timeout_s=30) == 50
    serve.delete("pipe")


def test_multiple_replicas_spread_load(serve_cluster):
    @serve.deployment(num_replicas=3, max_ongoing_requests=2)
    class Who:
        def __init__(self):
            import os

            self.me = f"{os.getpid()}-{id(self)}"

        def __call__(self):
            return self.me

    handle = serve.run(Who.bind(), name="who")
    seen = {handle.remote().result(timeout_s=30) for _ in range(30)}
    assert len(seen) >= 2, f"expected >=2 replicas used, saw {seen}"
    st = serve.status()["applications"]["who"]["deployments"]["Who"]
    assert st["replicas"] == 3
    serve.delete("who")


def test_user_config_reconfigure(serve_cluster):
    @serve.deployment(user_config={"threshold": 1})
    class Configurable:
        def __init__(self):
            self.threshold = None

        def reconfigure(self, config):
            self.threshold = config["threshold"]

        def __call__(self):
            return self.threshold

    handle = serve.run(Configurable.bind(), name="cfg")
    assert handle.remote().result(timeout_s=30) == 1
    serve.delete("cfg")


def test_status_and_redeploy(serve_cluster):
    @serve.deployment
    class V:
        def __call__(self):
            return "v1"

    serve.run(V.bind(), name="app_v")
    st = serve.status()
    assert st["applications"]["app_v"]["status"] == "RUNNING"

    @serve.deployment(name="V")
    class V2:
        def __call__(self):
            return "v2"

    handle = serve.run(V2.bind(), name="app_v")
    deadline = time.time() + 30
    while time.time() < deadline:
        if handle.remote().result(timeout_s=30) == "v2":
            break
        time.sleep(0.2)
    assert handle.remote().result(timeout_s=30) == "v2"
    serve.delete("app_v")


def test_http_proxy_routes(serve_cluster):
    @serve.deployment
    class Echo:
        def __call__(self, request):
            body = request.json()
            return {"path": request.path, "x": body["x"] * 2}

    serve.run(Echo.bind(), name="echo", route_prefix="/echo",
              _start_http=True)
    url = serve.get_proxy_url()
    status_code, raw = _http_json(f"{url}/echo/sub", {"x": 5})
    assert status_code == 200
    out = json.loads(raw)
    assert out == {"path": "/sub", "x": 10}
    # Unknown route → 404
    try:
        urllib.request.urlopen(f"{url}/nope", timeout=10)
        assert False, "expected 404"
    except urllib.error.HTTPError as e:
        assert e.code == 404
    serve.delete("echo")


def test_autoscaling_scales_up(serve_cluster):
    @serve.deployment(autoscaling_config={
        "min_replicas": 1, "max_replicas": 3,
        "target_ongoing_requests": 1, "upscale_delay_s": 0.2,
        "downscale_delay_s": 60}, max_ongoing_requests=100)
    class Slow:
        async def __call__(self):
            import asyncio

            await asyncio.sleep(1.5)
            return "ok"

    handle = serve.run(Slow.bind(), name="slow")
    # Flood with concurrent requests; replica count should rise above 1.
    responses = [handle.remote() for _ in range(12)]
    deadline = time.time() + 25
    max_replicas_seen = 1
    while time.time() < deadline:
        st = serve.status()["applications"]["slow"]["deployments"]["Slow"]
        max_replicas_seen = max(max_replicas_seen, st["replicas"])
        if max_replicas_seen >= 2:
            break
        time.sleep(0.2)
    for r in responses:
        assert r.result(timeout_s=60) == "ok"
    assert max_replicas_seen >= 2
    serve.delete("slow")


@pytest.mark.slow
def test_replica_failure_recovers(serve_cluster):
    @serve.deployment(num_replicas=1, health_check_period_s=0.3)
    class Fragile:
        def __call__(self):
            return "alive"

        def die(self):
            import os

            os._exit(1)

    handle = serve.run(Fragile.bind(), name="fragile")
    assert handle.remote().result(timeout_s=30) == "alive"
    try:
        handle.die.remote().result(timeout_s=10)
    except Exception:
        pass
    # Controller's health check should replace the replica.
    deadline = time.time() + 40
    ok = False
    while time.time() < deadline:
        try:
            if handle.remote().result(timeout_s=5) == "alive":
                ok = True
                break
        except Exception:
            time.sleep(0.3)
    assert ok, "replica was not replaced after failure"
    serve.delete("fragile")


def test_replica_device_init_failure_surfaces_in_serve_run(serve_cluster):
    """A replica that cannot initialise its device (injected: the
    constructor raises what jax raises when no chip can be had) must not
    sit in STARTING until serve.run's timeout: the caller gets the device
    error itself, well inside the start-up timeout."""
    @serve.deployment
    class NoChip:
        def __init__(self):
            raise RuntimeError(
                "Unable to initialize backend 'tpu': UNAVAILABLE: No TPU "
                "device found (injected)")

        def __call__(self):
            return "unreachable"

    t0 = time.time()
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        serve.run(NoChip.bind(), name="nochip", wait_timeout_s=60)
    assert time.time() - t0 < 30
    st = serve.status()["applications"]["nochip"]
    assert st["status"] == "DEPLOY_FAILED"
    serve.delete("nochip")


def test_model_multiplexing(serve_cluster):
    """@serve.multiplexed LRU-caches models per replica; the request's
    model id routes with affinity and is visible via
    get_multiplexed_model_id (ref: serve multiplex API)."""

    @serve.deployment(num_replicas=2)
    class MultiModel:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id: str):
            self.loads.append(model_id)
            return {"id": model_id, "weights": len(model_id)}

        async def __call__(self, x):
            model_id = serve.get_multiplexed_model_id()
            model = await self.get_model()
            return {"model": model["id"], "out": x * model["weights"],
                    "loads": list(self.loads)}

    handle = serve.run(MultiModel.bind(), name="mux")
    try:
        out1 = handle.options(multiplexed_model_id="abc").remote(2)\
            .result(timeout_s=60)
        assert out1["model"] == "abc" and out1["out"] == 6
        # same model id -> same replica, loader NOT re-run (LRU hit)
        out2 = handle.options(multiplexed_model_id="abc").remote(3)\
            .result(timeout_s=60)
        assert out2["out"] == 9
        assert out2["loads"].count("abc") == 1
        # different model id loads separately
        out3 = handle.options(multiplexed_model_id="wxyz").remote(1)\
            .result(timeout_s=60)
        assert out3["model"] == "wxyz" and out3["out"] == 4
    finally:
        serve.delete("mux")


def test_grpc_and_http_share_one_deployment(serve_cluster):
    """ref: serve/_private/proxy.py gRPCProxy :417 — one deployment
    served over BOTH ingress protocols through the shared router. The
    generic gRPC handler passes raw bytes; the deployment sees the same
    Request object either way."""
    import grpc

    @serve.deployment
    class Echo:
        def __call__(self, request):
            if request.method == "GRPC":
                x = json.loads(request.body)["x"]
                return {"proto": "grpc", "path": request.path, "x": x * 2}
            x = request.json()["x"]
            return {"proto": "http", "path": request.path, "x": x * 2}

    serve.start(grpc_options=serve.gRPCOptions(port=0))
    serve.run(Echo.bind(), name="dual", route_prefix="/dual",
              _start_http=True)

    # HTTP leg
    url = serve.get_proxy_url()
    status_code, raw = _http_json(f"{url}/dual", {"x": 4})
    assert status_code == 200
    assert json.loads(raw) == {"proto": "http", "path": "/", "x": 8}

    # gRPC leg: generic bytes-in/bytes-out unary call
    addr = serve.get_grpc_address()
    with grpc.insecure_channel(addr) as channel:
        call = channel.unary_unary(
            "/user.EchoService/Predict",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b)
        raw = call(json.dumps({"x": 4}).encode(),
                   metadata=(("application", "dual"),), timeout=60)
        assert json.loads(raw) == {
            "proto": "grpc", "path": "/user.EchoService/Predict", "x": 8}
        # single app deployed: application metadata is optional
        raw = call(json.dumps({"x": 6}).encode(), timeout=60)
        assert json.loads(raw)["x"] == 12
        # wrong application -> NOT_FOUND
        with pytest.raises(grpc.RpcError) as ei:
            call(b"{}", metadata=(("application", "nope"),), timeout=60)
        assert ei.value.code() == grpc.StatusCode.NOT_FOUND
        # standard health check answers SERVING without generated stubs
        health = channel.unary_unary(
            "/grpc.health.v1.Health/Check",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b)
        assert health(b"", timeout=60) == b"\x08\x01"
    serve.delete("dual")


def test_local_testing_mode_no_cluster():
    """ref: serve/_private/local_testing_mode.py — serve.run(app,
    local_testing_mode=True) executes replicas in-process: no
    controller, no actors, handles still compose (incl. async methods
    and multiplexed model ids)."""

    @serve.deployment
    def adder(x):
        return x + 1

    @serve.deployment
    class Pipeline:
        def __init__(self, downstream):
            self.downstream = downstream
            self.scale = 10

        def reconfigure(self, cfg):
            self.scale = cfg["scale"]

        async def __call__(self, x):
            out = await self.downstream.remote(x)
            return out * self.scale

        def which_model(self):
            return serve.get_multiplexed_model_id()

    app = Pipeline.options(user_config={"scale": 100}).bind(adder.bind())
    handle = serve.run(app, name="localapp", local_testing_mode=True)
    assert type(handle).__name__ == "LocalDeploymentHandle"
    assert handle.remote(4).result(timeout_s=10) == 500  # (4+1)*100
    # named-method + multiplexed model id context
    got = (handle.options(method_name="which_model",
                          multiplexed_model_id="m7")
           .remote().result(timeout_s=10))
    assert got == "m7"
    assert handle.which_model.remote().result(timeout_s=10) == ""
    # registry surface
    assert serve.get_app_handle("localapp") is handle
    serve.delete("localapp")


def test_replica_placement_bundle_lifecycle():
    """A deployment with placement_bundles gets one placement group per
    replica (the tensor-parallel LLM gang-reservation path) and the
    group is removed with the replica."""
    from ray_tpu.util.placement_group import placement_group_table

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, num_tpus=2)
    try:
        @serve.deployment
        class Gang:
            def __call__(self, x):
                return x * 3

        app = Gang.options(placement_bundles=[{"TPU": 2.0}],
                           placement_strategy="PACK").bind()
        handle = serve.run(app, name="gang", wait_timeout_s=180)
        assert handle.remote(7).result(timeout_s=60) == 21
        pgs = [pg for pg in placement_group_table()
               if pg.get("state") == "CREATED"
               and pg.get("bundles") == [{"TPU": 2.0}]]
        assert pgs, placement_group_table()
        serve.delete("gang")
        deadline = time.time() + 60
        while time.time() < deadline:
            left = [pg for pg in placement_group_table()
                    if pg.get("state") == "CREATED"
                    and pg.get("bundles") == [{"TPU": 2.0}]]
            if not left:
                break
            time.sleep(0.5)
        assert not left, left
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
