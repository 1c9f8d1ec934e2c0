"""Asyncio message-passing RPC over unix/TCP sockets.

TPU-native replacement for the reference's gRPC layer (ref:
src/ray/rpc/grpc_server.h:88, grpc_client.h:96, client_call.h:203). The
control plane does not need gRPC's HTTP/2 machinery on a single fabric;
length-prefixed pickle frames over asyncio sockets give the same
request/response + server-push semantics with far less overhead per call.

Includes the probabilistic fault-injection hook equivalent to the reference's
RpcFailureManager (ref: src/ray/rpc/rpc_chaos.cc:30-49), driven by
RuntimeConfig.testing_rpc_failure ("Method=max_failures:req_prob:resp_prob").

Every process owns one background event-loop thread (`EventLoopThread`);
synchronous callers bridge onto it with run_coroutine_threadsafe.
"""

from __future__ import annotations

import asyncio
import collections
import itertools
import os
import struct
import threading
import traceback
from typing import Any, Awaitable, Callable, Dict, List, Optional

from . import faults, serialization
from .procutil import spawn_logged

_LEN = struct.Struct(">Q")

REQ, RES, NTF = 0, 1, 2


class RpcError(Exception):
    pass


class RemoteHandlerError(RpcError):
    """The remote handler raised; carries the remote traceback."""

    def __init__(self, method: str, exc_repr: str, tb: str):
        self.method = method
        self.exc_repr = exc_repr
        self.tb = tb
        super().__init__(f"rpc handler {method!r} failed: {exc_repr}\n{tb}")


class ConnectionLost(RpcError):
    pass


class RpcTimeoutError(RpcError, asyncio.TimeoutError):
    """A call exceeded its deadline (the default rpc_call_timeout_s or
    an explicit _timeout) with the retry budget exhausted. Subclasses
    asyncio.TimeoutError so existing wait_for-style handlers keep
    working; the typed name is what drills and operators see instead of
    an unbounded hang."""


class NodeUnreachableError(ConnectionLost):
    """The peer could not be reached (connect failed or the connection
    died) after the retry budget. Subclasses ConnectionLost so every
    redial/re-resolve handler keeps working."""


# --------------------------------------------------------------------------
# Failure-bounding policy: which control-plane methods may be retried
# transparently (idempotent per their handler's semantics — registration
# dedupes, reads re-read, reports overwrite) and which long-poll methods
# are exempt from the DEFAULT call deadline (their callers bound them
# explicitly or legitimately park: an owner fetch waits for the producing
# task, however long it runs).
# --------------------------------------------------------------------------
IDEMPOTENT_METHODS = frozenset({
    "ping", "heartbeat", "register_node", "list_nodes", "cluster_status",
    "get_actor", "list_actors", "register_actor", "actor_ready",
    "reattach_actor",
    # NOT actor_died: its restart branch bumps num_restarts and spawns a
    # scheduler pass per delivery — a retried-but-executed report would
    # double-restart the actor
    "kv_get", "kv_put", "kv_del",
    "get_node_info", "get_metrics", "report_metrics",
    "list_jobs", "register_job", "mark_job_finished",
    "list_placement_groups", "get_placement_group",
    "list_task_events", "list_tasks", "get_task", "list_trace_spans",
    "om_meta", "om_endpoint", "om_read", "chan_endpoint", "view_update",
    # pick_nodes' optimistic table debits are advisory and overwritten
    # by the next resource report — a duplicated wave plan only
    # under-packs, never double-runs anything
    "pick_node", "pick_nodes", "subscribe",
    # storage reads (controller persistence tier): re-reading re-reads
    "st_load_meta", "st_load_kv",
    # client-proxy liveness touch: a duplicated beat is a no-op
    "c_heartbeat",
    # warm standby: re-subscribing re-registers the same connection and
    # re-snapshots; status is a read
    "journal_subscribe", "standby_status",
})

# long-poll methods whose wait is the PRODUCT, not a failure: no default
# deadline (explicit _timeout still applies). om_pull (broadcast-tree
# landing) runs a whole multi-chunk transfer inside one call — its
# duration is the object size over the fabric, and broadcast_async
# always passes an explicit per-node _timeout.
UNBOUNDED_METHODS = frozenset({"fetch_object", "c_get", "c_wait",
                               "om_pull"})

# Methods whose handlers have at-most-once side effects: NEVER retried
# transparently — a retried-but-executed frame double-runs user code,
# double-frees accounting, or double-fires a state machine. This set
# exists so the choice is EXPLICIT: rtpuproto's RTPU103 gate fails the
# build when an RPC method is in none of the three classes, which is
# how the PR-10 `actor_died` double-restart class of bug gets decided
# at review time instead of in production. Grouped by server.
NON_IDEMPOTENT_METHODS = frozenset({
    # controller: state machines and fan-out (a duplicate actor_died
    # report double-restarts; a duplicate publish double-delivers)
    "actor_died", "kill_actor", "drain_node",
    "create_placement_group", "remove_placement_group",
    "publish", "add_task_events", "add_trace_spans", "fault_inject",
    # nodelet: task/actor lifecycle and resource accounting
    "submit_task", "submit_task_batch", "lease_worker_for_actor",
    "worker_register", "task_finished", "task_done", "actor_exited",
    "reserve_bundle", "return_bundle", "cancel_task",
    "object_sealed", "object_deleted", "fault_forward",
    # worker executor: user code runs here (dispatch dedupe windows
    # guard double-DELIVERY, not transport-level double-send)
    "execute_task", "create_actor", "actor_call", "kill_self",
    "drain_exit", "shutdown",
    # owner-side pushes: results/streams are seq-stamped, not retried
    "task_result", "task_spilled", "task_stream_item", "replica_ready",
    "borrow_inc", "borrow_dec", "pubsub",
    # compiled-graph channel writes: seq-replayed by the WRITER's
    # exactly-once protocol, never by the transport
    "chan_push",
    # controller persistence writes (append/compact ordering matters)
    "st_save_meta", "st_append_kv", "st_compact_kv",
    # warm standby: the streamed journal is seq-guarded by the follower
    # (a duplicate record is skipped, a gap forces resync — never a
    # transport retry); promotion binds an address at most once
    "journal_record", "standby_promote",
    # client proxy: submissions and refcounts mirror the owner API
    "c_export", "c_submit", "c_create_actor", "c_actor_call",
    "c_release_actor", "c_put", "c_cancel", "c_free", "c_kill_actor",
    "c_decref", "c_controller", "c_disconnect",
})

# the three classes partition the RPC surface: a method in two would
# make retry semantics ambiguous, and rtpuproto (RTPU103) additionally
# requires every registered method to appear in exactly one
assert not (IDEMPOTENT_METHODS & NON_IDEMPOTENT_METHODS)
assert not (IDEMPOTENT_METHODS & UNBOUNDED_METHODS)
assert not (UNBOUNDED_METHODS & NON_IDEMPOTENT_METHODS)


def _call_deadline(method: str, timeout: Optional[float]) -> Optional[float]:
    if timeout is not None:
        return timeout
    if method in UNBOUNDED_METHODS:
        return None
    from .config import get_config

    cfg_timeout = get_config().rpc_call_timeout_s
    return cfg_timeout if cfg_timeout > 0 else None


def _retry_budget(method: str) -> int:
    if method not in IDEMPOTENT_METHODS:
        return 0
    from .config import get_config

    return max(0, get_config().rpc_retry_max)


def _backoff_delay(attempt: int) -> float:
    """Exponential backoff with jitter (ref: the reference's
    exponential_backoff.h), bounded by rpc_retry_max_s."""
    from .config import get_config
    from .procutil import jitter

    cfg = get_config()
    return jitter(min(cfg.rpc_retry_max_s,
                      cfg.rpc_retry_base_s * (2 ** attempt)))


# --------------------------------------------------------------------------
# Fault injection — the deterministic fault plane (faults.py) subsumes
# the legacy probabilistic chaos hook; `_chaos = None` still forces a
# re-parse of config-sourced rules (test surface).
# --------------------------------------------------------------------------
_chaos: Optional[faults.FaultPlane] = None


def _get_chaos() -> faults.FaultPlane:
    global _chaos
    if _chaos is None:
        _chaos = faults.reload_from_config()
    return _chaos


def chaos_should_drop(method: str) -> bool:
    """Consult the fault rules for `method` outside the dispatch layer.
    Batched endpoints (submit_task_batch) use this to apply the
    PER-LOGICAL-REQUEST rules of the method they aggregate, so
    fault-tolerance tests keyed on e.g. "submit_task" keep exercising
    real drops on the coalesced fast path."""
    return _get_chaos().should_drop_request(method)


async def _apply_dispatch_fault(method: str,
                                one_way: bool = False) -> bool:
    """Run the fault plane's dispatch-side verdict for one inbound
    request. Returns True when the frame must be DROPPED (simulated
    network loss — the caller sees a hang into its deadline); a delay
    rule sleeps here; an error rule raises FaultInjectedError into the
    normal handler-error path so the caller gets a typed failure."""
    action = _get_chaos().on_dispatch(method)
    if action is None:
        return False
    kind, arg = action
    if kind == "drop":
        return True
    if kind == "delay":
        await asyncio.sleep(arg)
        return False
    if one_way:
        return True  # error on a one-way frame: nothing to answer
    raise faults.FaultInjectedError(arg)


# --------------------------------------------------------------------------
# Per-method count of RPC frames this process ISSUES (requests + notifies,
# socket and in-process alike). Cheap enough to keep always-on; the
# compiled-graph plane asserts against it that steady-state execute()
# moves zero control-plane frames — only channel frames.
# --------------------------------------------------------------------------
_send_counts: Dict[str, int] = collections.defaultdict(int)


def transport_sends() -> Dict[str, int]:
    """Snapshot of {method: frames issued} by this process since start."""
    return dict(_send_counts)


# --------------------------------------------------------------------------
# In-process server registry: when a client and server share a process (the
# single-host session runs controller + nodelet on the driver's loop), calls
# dispatch directly on the loop with zero serialization and zero socket hops
# — the moral equivalent of the reference embedding the plasma store inside
# the raylet process (object_manager.h:80) applied to the control plane.
# --------------------------------------------------------------------------
_local_servers: Dict[str, "RpcServer"] = {}


async def _hang_forever():
    await asyncio.Event().wait()


# --------------------------------------------------------------------------
# Event loop thread
# --------------------------------------------------------------------------
_stall_metric = None
_stall_handler_installed = False


def _arm_loop_watchdog(loop: asyncio.AbstractEventLoop, watchdog_ms: int):
    """Arm asyncio's slow-callback detector on `loop`: debug mode logs
    every callback that holds the loop past slow_callback_duration, and a
    handler on the asyncio logger counts those records into the
    rtpu_loop_stall_total metric (so benches/tests can assert on stalls
    without scraping stderr)."""
    global _stall_handler_installed
    loop.slow_callback_duration = watchdog_ms / 1000.0
    loop.set_debug(True)
    if _stall_handler_installed:
        return
    _stall_handler_installed = True

    import logging

    class _StallCounter(logging.Handler):
        def emit(self, record):
            # asyncio's slow-callback records read "Executing <...> took
            # 0.123 seconds"; everything else on the logger passes through
            try:
                if str(record.msg).startswith("Executing"):
                    global _stall_metric
                    if _stall_metric is None:
                        from ..util.metrics import Counter

                        _stall_metric = Counter(
                            "rtpu_loop_stall_total",
                            "event-loop callbacks that exceeded "
                            "loop_watchdog_ms")
                    _stall_metric.inc()
            except Exception:  # rtpulint: ignore[RTPU006] — a metrics failure must never break asyncio's logging path
                pass

    logging.getLogger("asyncio").addHandler(_StallCounter())


class EventLoopThread:
    """One asyncio loop on a daemon thread, shared per process."""

    _instance: Optional["EventLoopThread"] = None
    _lock = threading.Lock()

    def __init__(self):
        self.loop = asyncio.new_event_loop()
        from .config import get_config

        watchdog_ms = get_config().loop_watchdog_ms
        if watchdog_ms > 0:
            _arm_loop_watchdog(self.loop, watchdog_ms)
        self.thread = threading.Thread(
            target=self._run, name="rtpu-io", daemon=True
        )
        self.thread.start()

    def _run(self):
        asyncio.set_event_loop(self.loop)
        self.loop.run_forever()

    @classmethod
    def get(cls) -> "EventLoopThread":
        with cls._lock:
            if cls._instance is None or not cls._instance.thread.is_alive():
                cls._instance = cls()
            return cls._instance

    @classmethod
    def reset(cls):
        with cls._lock:
            inst, cls._instance = cls._instance, None
        if inst is not None and inst.thread.is_alive():
            inst.loop.call_soon_threadsafe(inst.loop.stop)

    def run(self, coro: Awaitable, timeout: Optional[float] = None):
        """Run coroutine on the loop from a sync thread, return its result."""
        if threading.current_thread() is self.thread:
            raise RuntimeError(
                "sync RPC bridge used from the io loop thread (deadlock); "
                "use the *_async coroutine form inside handlers")
        fut = asyncio.run_coroutine_threadsafe(coro, self.loop)
        return fut.result(timeout)

    def spawn(self, coro: Awaitable):
        """Fire-and-forget by default; the returned concurrent future
        lets callers that need completion (event-batch flush) wait."""
        return asyncio.run_coroutine_threadsafe(coro, self.loop)


# --------------------------------------------------------------------------
# Framing
# --------------------------------------------------------------------------
async def _read_frame(reader: asyncio.StreamReader) -> bytes:
    header = await reader.readexactly(_LEN.size)
    (length,) = _LEN.unpack(header)
    return await reader.readexactly(length)


def _frame(payload: bytes) -> bytes:
    return _LEN.pack(len(payload)) + payload


def parse_address(address: str):
    """'unix:/path' or 'tcp:host:port'."""
    if address.startswith("unix:"):
        return ("unix", address[5:])
    if address.startswith("tcp:"):
        host, port = address[4:].rsplit(":", 1)
        return ("tcp", host, int(port))
    raise ValueError(f"bad address {address!r}")


def advertise_ip(peer_host: Optional[str] = None) -> str:
    """This host's externally-reachable IP (RTPU_ADVERTISE_HOST overrides;
    otherwise a UDP-connect probe towards the peer/default route)."""
    import socket as socket_mod

    override = os.environ.get("RTPU_ADVERTISE_HOST")
    if override:
        return override
    probe_target = peer_host if peer_host and peer_host not in (
        "0.0.0.0", "127.0.0.1", "localhost") else "8.8.8.8"
    try:
        s = socket_mod.socket(socket_mod.AF_INET, socket_mod.SOCK_DGRAM)
        try:
            s.connect((probe_target, 9))
            return s.getsockname()[0]
        finally:
            s.close()
    except OSError:
        return "127.0.0.1"


async def _open_connection(address: str):
    parsed = parse_address(address)
    if parsed[0] == "unix":
        return await asyncio.open_unix_connection(parsed[1])
    return await asyncio.open_connection(parsed[1], parsed[2])


# --------------------------------------------------------------------------
# Server
# --------------------------------------------------------------------------
class ServerConn:
    """One inbound connection; lets handlers push notifications back."""

    def __init__(self, server: "RpcServer", writer: asyncio.StreamWriter):
        self.server = server
        self.writer = writer
        self.wlock = asyncio.Lock()
        self.closed = False
        self.meta: Dict[str, Any] = {}  # handlers can stash identity here

    async def send(self, msg_tuple) -> None:
        payload = serialization.dumps_frame(msg_tuple)
        async with self.wlock:
            if self.closed:
                raise ConnectionLost("connection closed")
            self.writer.write(_frame(payload))
            await self.writer.drain()

    async def notify(self, method: str, **kwargs) -> None:
        try:
            await self.send((NTF, method, kwargs))
        except (ConnectionLost, ConnectionError, RuntimeError):
            self.closed = True


class RpcServer:
    """Dispatches named handlers. Handlers may be sync or async; they receive
    their kwargs plus `_conn` (the ServerConn) if they declare it."""

    def __init__(self, address: str,
                 handlers: Dict[str, Callable],
                 on_disconnect: Optional[Callable[[ServerConn], None]] = None):
        self.address = address
        self.handlers = dict(handlers)
        self.on_disconnect = on_disconnect
        self._server: Optional[asyncio.base_events.Server] = None
        self.conns: set[ServerConn] = set()

    async def start(self):
        parsed = parse_address(self.address)
        if parsed[0] == "unix":
            os.makedirs(os.path.dirname(parsed[1]), exist_ok=True)
            if os.path.exists(parsed[1]):
                os.unlink(parsed[1])
            # big backlog: during creation bursts hundreds of workers
            # dial the hub faster than a loaded loop accepts; the
            # asyncio default (100) overflows and every refused client
            # backs off 50ms — a silent throughput cliff (r5)
            self._server = await asyncio.start_unix_server(
                self._on_conn, parsed[1], backlog=2048)
        else:
            host, port = parsed[1], parsed[2]
            self._server = await asyncio.start_server(
                self._on_conn, host or None, port, backlog=2048)
            # ephemeral port / wildcard bind: advertise the real endpoint
            real_port = self._server.sockets[0].getsockname()[1]
            adv_host = advertise_ip() if host in ("0.0.0.0", "") else host
            if port == 0 or host in ("0.0.0.0", ""):
                self.address = f"tcp:{adv_host}:{real_port}"
        _local_servers[self.address] = self

    async def stop(self):
        if _local_servers.get(self.address) is self:
            del _local_servers[self.address]
        if self._server is not None:
            self._server.close()
        # connections first: since Python 3.12.1 Server.wait_closed()
        # waits for every accepted connection to close, so awaiting it
        # with a client still attached never returns
        for conn in list(self.conns):
            try:
                conn.writer.close()
            except Exception:  # rtpulint: ignore[RTPU006] — peer may already be gone at stop; nothing to report
                pass
        if self._server is not None:
            try:
                await self._server.wait_closed()
            except Exception:  # rtpulint: ignore[RTPU006] — server teardown is best-effort; the listener fd is closed either way
                pass

    async def _on_conn(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter):
        conn = ServerConn(self, writer)
        self.conns.add(conn)
        try:
            while True:
                data = await _read_frame(reader)
                msg = serialization.loads_inline(data)
                kind = msg[0]
                if kind == REQ:
                    _, msg_id, method, kwargs = msg
                    spawn_logged(
                        self._dispatch(conn, msg_id, method, kwargs),
                        name="rpc.dispatch")
                elif kind == NTF:
                    _, method, kwargs = msg
                    spawn_logged(
                        self._dispatch(conn, None, method, kwargs),
                        name="rpc.dispatch")
        except (asyncio.IncompleteReadError, ConnectionResetError, BrokenPipeError):
            pass
        finally:
            conn.closed = True
            self.conns.discard(conn)
            if self.on_disconnect is not None:
                try:
                    res = self.on_disconnect(conn)
                    if asyncio.iscoroutine(res):
                        await res
                except Exception:
                    traceback.print_exc()
            try:
                writer.close()
            except Exception:  # rtpulint: ignore[RTPU006] — transport already torn down by the disconnect we are handling
                pass

    async def _dispatch(self, conn: ServerConn, msg_id, method: str, kwargs):
        handler = self.handlers.get(method)
        try:
            if await _apply_dispatch_fault(method,
                                           one_way=msg_id is None):
                return  # simulated network drop; caller hangs → deadline
            if handler is None:
                raise RpcError(f"no handler for {method!r}")
            if _wants_conn(handler):
                kwargs = dict(kwargs, _conn=conn)
            result = handler(**kwargs)
            if asyncio.iscoroutine(result):
                result = await result
            if msg_id is not None:
                await conn.send((RES, msg_id, True, result))
        except (ConnectionLost, ConnectionError):
            pass
        except Exception as e:
            if msg_id is not None:
                try:
                    await conn.send(
                        (RES, msg_id, False, (type(e).__name__, repr(e), traceback.format_exc()))
                    )
                except (ConnectionLost, ConnectionError):
                    pass
            else:
                traceback.print_exc()


def _wants_conn(handler) -> bool:
    # cache on the underlying function: bound methods are re-created per
    # access and reject attribute writes, so cache there via __func__
    target = getattr(handler, "__func__", handler)
    cached = getattr(target, "_rtpu_wants_conn", None)
    if cached is None:
        import inspect

        try:
            cached = "_conn" in inspect.signature(handler).parameters
        except (TypeError, ValueError):
            cached = False
        try:
            target._rtpu_wants_conn = cached
        except AttributeError:
            pass
    return cached


# --------------------------------------------------------------------------
# Client
# --------------------------------------------------------------------------
class _LocalConn:
    """Stands in for ServerConn when client and server share a process:
    server-pushed notifications route straight into the client's
    notify_handlers (pubsub etc.) without a socket."""

    __slots__ = ("client", "closed", "meta", "server")

    def __init__(self, client: "RpcClient", server: "RpcServer"):
        self.client = client
        self.server = server
        self.closed = False
        self.meta: Dict[str, Any] = {}

    async def send(self, msg_tuple) -> None:
        raise RpcError("local connections carry no raw frames")

    async def notify(self, method: str, **kwargs) -> None:
        if self.closed:
            return
        handler = self.client.notify_handlers.get(method)
        if handler is not None:
            try:
                res = handler(**kwargs)
                if asyncio.iscoroutine(res):
                    spawn_logged(res, name="rpc.local_notify")
            except Exception:
                traceback.print_exc()


class RpcClient:
    """Persistent client to one server address.

    `call` blocks the calling (sync) thread; `call_async` is the coroutine
    form for use on the io loop. Notifications pushed by the server are routed
    to `notify_handlers`.
    """

    def __init__(self, address: str,
                 notify_handlers: Optional[Dict[str, Callable]] = None):
        self.address = address
        self.notify_handlers = notify_handlers or {}
        self._reader: Optional[asyncio.StreamReader] = None
        self._writer: Optional[asyncio.StreamWriter] = None
        self._wlock: Optional[asyncio.Lock] = None
        self._pending: Dict[int, asyncio.Future] = {}
        self._ids = itertools.count(1)
        self._connect_lock: Optional[asyncio.Lock] = None
        self._closed = False
        self._local_conn: Optional[_LocalConn] = None
        # queued-but-unsent notify_nowait coroutines (close_when_drained)
        self._inflight_notifies = 0
        # optional hook (method, kwargs, exc) invoked on the io loop when
        # a fire-and-forget notify fails — lets persistence-critical
        # callers (controller storage) detect and replay lost sends
        # instead of silently diverging
        self.on_notify_error = None
        # optional zero-arg hook spawned on the io loop after a RE-dial
        # (not the first connect): session-state owners re-seed what the
        # dead connection carried (pubsub subscriptions survive a
        # controller restart this way)
        self.on_reconnect = None
        self._ever_connected = False
        self._idle_event: Optional[asyncio.Event] = None
        # one-way frames awaiting the coalesced flush (notify_async)
        self._wbuf: List[bytes] = []
        self._wbuf_fut: Optional[asyncio.Future] = None
        # MPSC staging for fire-and-forget sends from non-loop threads:
        # a burst rides ONE call_soon_threadsafe wakeup (see notify_nowait)
        self._nowait_buf: "collections.deque" = collections.deque()
        self._nowait_armed = False
        self._nowait_lock = threading.Lock()

    def _local_server(self) -> Optional["RpcServer"]:
        return _local_servers.get(self.address)

    async def _call_local(self, server: "RpcServer", method: str,
                          kwargs: dict, _timeout: Optional[float],
                          one_way: bool = False):
        """Direct in-process dispatch (no socket, no pickling). Fault
        injection still applies so FT tests behave identically."""
        try:
            dropped = await _apply_dispatch_fault(method, one_way=one_way)
        except faults.FaultInjectedError as e:
            raise RemoteHandlerError("FaultInjectedError", repr(e),
                                     "") from None
        if dropped:
            if one_way:
                return None
            if _timeout is not None:
                await asyncio.wait_for(_hang_forever(), _timeout)
            await _hang_forever()
        if self._local_conn is None or self._local_conn.server is not server:
            self._local_conn = _LocalConn(self, server)
        handler = server.handlers.get(method)
        try:
            if handler is None:
                raise RpcError(f"no handler for {method!r}")
            if _wants_conn(handler):
                kwargs = dict(kwargs, _conn=self._local_conn)
            result = handler(**kwargs)
            if asyncio.iscoroutine(result):
                if _timeout is not None:
                    result = await asyncio.wait_for(result, _timeout)
                else:
                    result = await result
            return result
        except asyncio.TimeoutError:
            raise
        except (ConnectionLost, ConnectionError):
            raise
        except RemoteHandlerError:
            raise
        except Exception as e:
            # raise even for one-way sends: in-process callers CAN see
            # handler failures, and e.g. the task-submit failback needs to
            raise RemoteHandlerError(
                type(e).__name__, repr(e), traceback.format_exc())

    # -- async interface (must run on the io loop) --
    async def _ensure_connected(self):
        if self._writer is not None and not self._writer.is_closing():
            return
        if self._connect_lock is None:
            self._connect_lock = asyncio.Lock()
        async with self._connect_lock:
            if self._writer is not None and not self._writer.is_closing():
                return
            from .config import get_config

            deadline = asyncio.get_event_loop().time() + get_config().rpc_connect_timeout_s
            last_err = None
            while asyncio.get_event_loop().time() < deadline:
                try:
                    self._reader, self._writer = await _open_connection(self.address)
                    break
                except (ConnectionRefusedError, FileNotFoundError, OSError) as e:
                    last_err = e
                    await asyncio.sleep(0.05)
            else:
                raise ConnectionLost(
                    f"could not connect to {self.address}: {last_err}"
                )
            self._wlock = asyncio.Lock()
            spawn_logged(self._read_loop(self._reader),
                         name="rpc.read_loop")
            reconnected = self._ever_connected
            self._ever_connected = True
            if reconnected and self.on_reconnect is not None:
                try:
                    res = self.on_reconnect()
                    if asyncio.iscoroutine(res):
                        spawn_logged(res, name="rpc.on_reconnect")
                except Exception:
                    traceback.print_exc()

    async def _read_loop(self, reader):
        try:
            while True:
                data = await _read_frame(reader)
                msg = serialization.loads_inline(data)
                if msg[0] == RES:
                    _, msg_id, ok, payload = msg
                    fut = self._pending.pop(msg_id, None)
                    if fut is not None and not fut.done():
                        if ok:
                            fut.set_result(payload)
                        else:
                            name, erepr, tb = payload
                            fut.set_exception(RemoteHandlerError(name, erepr, tb))
                elif msg[0] == NTF:
                    _, method, kwargs = msg
                    handler = self.notify_handlers.get(method)
                    if handler is not None:
                        try:
                            res = handler(**kwargs)
                            if asyncio.iscoroutine(res):
                                spawn_logged(res, name="rpc.notify_handler")
                        except Exception:
                            traceback.print_exc()
        except (asyncio.IncompleteReadError, ConnectionResetError, BrokenPipeError, OSError):
            pass
        finally:
            self._writer = None
            err = ConnectionLost(f"connection to {self.address} lost")
            for fut in self._pending.values():
                if not fut.done():
                    fut.set_exception(err)
            self._pending.clear()

    async def call_async(self, method: str, _timeout: Optional[float] = None,
                         _retry: Optional[int] = None, **kwargs):
        """One request/response. The failure-bounding policy lives here:
        every call gets a deadline (the caller's _timeout, else the
        rpc_call_timeout_s default — long-poll methods exempt), and
        idempotent control-plane methods retry under exponential backoff
        with jitter inside a bounded budget (`_retry` overrides it —
        periodic callers whose NEXT tick is the retry pass 0 so one
        blackholed call costs one tick, not budget × deadline).
        Exhaustion surfaces as the TYPED RpcTimeoutError /
        NodeUnreachableError instead of an unbounded hang or a bare
        transport error."""
        _send_counts[method] += 1
        timeout = _call_deadline(method, _timeout)
        retries = _retry_budget(method) if _retry is None else max(0, _retry)
        attempt = 0
        while True:
            try:
                return await self._call_attempt(method, timeout, kwargs)
            except RpcTimeoutError:
                raise
            except asyncio.TimeoutError as e:
                if attempt >= retries or self._closed:
                    raise RpcTimeoutError(
                        f"rpc {method!r} to {self.address} timed out "
                        f"after {timeout}s "
                        f"({attempt + 1} attempt(s))") from e
            except NodeUnreachableError:
                raise
            except ConnectionLost as e:
                if attempt >= retries or self._closed:
                    raise NodeUnreachableError(
                        f"rpc {method!r}: {self.address} unreachable "
                        f"({attempt + 1} attempt(s)): {e}") from e
            attempt += 1
            await asyncio.sleep(_backoff_delay(attempt - 1))

    async def _call_attempt(self, method: str, timeout: Optional[float],
                            kwargs: dict):
        if faults.check_send(method, self.address):
            # one-way partition: the frame never leaves this process —
            # the caller waits into its deadline, exactly like a
            # blackholed link (drills verify the typed timeout here)
            if timeout is not None:
                await asyncio.wait_for(_hang_forever(), timeout)
            await _hang_forever()
        server = self._local_server()
        if server is not None:
            return await self._call_local(server, method, kwargs, timeout)
        await self._ensure_connected()
        msg_id = next(self._ids)
        fut = asyncio.get_event_loop().create_future()
        self._pending[msg_id] = fut
        payload = serialization.dumps_frame((REQ, msg_id, method, kwargs))
        if self._wbuf:
            # flush coalesced one-way frames enqueued earlier on this
            # connection BEFORE the request frame: a request overtaking a
            # buffered notify breaks per-connection FIFO (e.g. a
            # cancel_task arriving ahead of the submit_task it cancels)
            await self._flush_wbuf()
        async with self._wlock:
            if self._writer is None:
                # dropped during the flush above: surface the RETRYABLE
                # type (AttributeError would skip reconnect handling)
                raise ConnectionLost(f"connection to {self.address} lost")
            self._writer.write(_frame(payload))
            await self._writer.drain()
        if timeout is not None:
            try:
                return await asyncio.wait_for(fut, timeout)
            except asyncio.TimeoutError:
                # the reply may still arrive later: drop the slot now or
                # every timed-out call leaks one pending future forever
                self._pending.pop(msg_id, None)
                raise
        return await fut

    async def notify_async(self, method: str, **kwargs):
        _send_counts[method] += 1
        if faults.check_send(method, self.address):
            return  # one-way partition: a fire-and-forget frame is lost
        server = self._local_server()
        if server is not None:
            await self._call_local(server, method, kwargs, None, one_way=True)
            return
        await self._ensure_connected()
        # write-coalescing: frames enqueued in the same event-loop pass
        # ride ONE socket write (a 100-call submit burst or a batch of
        # task_result pushes was 100 separate send() syscalls). Order is
        # the buffer order, so per-connection FIFO (streaming items +
        # terminator, actor-call order) is preserved; the shared flush
        # future propagates write failures to every caller in the batch,
        # keeping retry-on-stale-address semantics intact.
        payload = _frame(serialization.dumps_frame((NTF, method, kwargs)))
        self._wbuf.append(payload)
        if self._wbuf_fut is None:
            loop = asyncio.get_event_loop()
            self._wbuf_fut = loop.create_future()
            loop.call_soon(self._schedule_flush)
        await asyncio.shield(self._wbuf_fut)

    def _schedule_flush(self):
        # runs on the loop (scheduled via loop.call_soon in notify_async)
        spawn_logged(self._flush_wbuf(), name="rpc.flush_wbuf")

    async def _flush_wbuf(self):
        buf, fut = self._wbuf, self._wbuf_fut
        self._wbuf, self._wbuf_fut = [], None
        if not buf or fut is None:
            if fut is not None and not fut.done():
                fut.set_result(None)
            return
        try:
            async with self._wlock:
                if self._writer is None:
                    # connection dropped between enqueue and this flush:
                    # surface the RETRYABLE error type — an
                    # AttributeError here would skip every caller's
                    # reconnect/re-resolve handling and hang their gets
                    raise ConnectionLost(
                        f"connection to {self.address} lost")
                self._writer.write(b"".join(buf))
                await self._writer.drain()
            if not fut.done():
                fut.set_result(None)
        except BaseException as e:  # noqa: BLE001 — deliver to callers
            if not fut.done():
                fut.set_exception(e)

    # -- sync interface (from any non-io thread) --
    def call(self, method: str, _timeout: Optional[float] = None, **kwargs):
        return EventLoopThread.get().run(
            self.call_async(method, _timeout=_timeout, **kwargs)
        )

    def notify(self, method: str, **kwargs):
        EventLoopThread.get().run(self.notify_async(method, **kwargs))

    def notify_nowait(self, method: str, **kwargs):
        """Fire-and-forget from ANY thread: schedules the send on the io
        loop without waiting for it (the hot-path result/ack sends —
        blocking an executor thread ~200us per send just to learn the
        bytes left the socket buys nothing).

        Off-loop sends STAGE into an MPSC buffer drained once per loop
        wakeup: a burst of task_result/task_done pushes from an executor
        thread costs one call_soon_threadsafe instead of one per send,
        and the staged order is the send order, so per-connection FIFO
        (streaming items + terminator) is preserved."""
        elt = EventLoopThread.get()
        if threading.current_thread() is elt.thread:
            self._spawn_notify(method, kwargs)
            return
        self._nowait_buf.append((method, kwargs))
        with self._nowait_lock:
            if self._nowait_armed:
                return
            self._nowait_armed = True
        elt.loop.call_soon_threadsafe(self._drain_nowait)

    def _drain_nowait(self):
        # disarm BEFORE popping: a producer that appends after the pop
        # loop finished will observe the flag down and re-arm
        with self._nowait_lock:
            self._nowait_armed = False
        while True:
            try:
                method, kwargs = self._nowait_buf.popleft()
            except IndexError:
                return
            self._spawn_notify(method, kwargs)

    def _spawn_notify(self, method: str, kwargs: dict):
        # counted at ENQUEUE (synchronously on the loop): a drain that
        # only counted running coroutines would close underneath a
        # notify still sitting in the task queue
        self._inflight_notifies += 1
        try:
            spawn_logged(self._notify_swallow(method, kwargs),
                         name="rpc.notify_swallow")
        except BaseException:
            # loop closing at shutdown: keep the counter honest or every
            # later close_when_drained stalls out its full timeout
            self._inflight_notifies -= 1
            raise

    async def _notify_swallow(self, method: str, kwargs: dict):
        try:
            await self.notify_async(method, **kwargs)
        except (ConnectionLost, ConnectionError, OSError) as e:
            self._report_notify_error(method, kwargs, e)
        except Exception as e:  # noqa: BLE001 — hook decides, then log
            traceback.print_exc()
            self._report_notify_error(method, kwargs, e)
        finally:
            self._inflight_notifies -= 1
            if self._inflight_notifies == 0 and self._idle_event is not None:
                self._idle_event.set()

    def _report_notify_error(self, method: str, kwargs: dict, exc):
        cb = self.on_notify_error
        if cb is None:
            return
        try:
            cb(method, kwargs, exc)
        except Exception:
            traceback.print_exc()

    def queued_nowait(self) -> int:
        """Approximate count of fire-and-forget sends not yet on the
        socket (staged + in flight). Producers use it as a high-water
        check to fall back to blocking sends instead of growing the
        staging buffer without bound."""
        return len(self._nowait_buf) + self._inflight_notifies

    async def drain_async(self, timeout: float = 2.0):
        """Runs on the io loop: spawn any frames still staged in the
        nowait buffer, then wait (bounded) until every in-flight
        fire-and-forget send has been handed to the socket. The single
        shared implementation behind drain() and close_when_drained().
        Concurrent drainers share one idle event — replacing it would
        strand the earlier waiter for its full timeout."""
        if self._nowait_buf:
            self._drain_nowait()
        if self._inflight_notifies > 0:
            ev = self._idle_event
            if ev is None or ev.is_set():
                ev = self._idle_event = asyncio.Event()
            try:
                await asyncio.wait_for(ev.wait(), timeout)
            except asyncio.TimeoutError:
                pass

    def drain(self, timeout: float = 2.0):
        """Block the calling (non-loop) thread until every queued
        fire-and-forget send — staged or in flight — has been handed to
        the socket, or `timeout` elapses. Exit paths use this before
        close(): a result/terminator frame still staged at close would
        hang the owner's get() forever."""
        elt = EventLoopThread.get()
        if threading.current_thread() is elt.thread:
            return  # cannot block the loop; staged frames drain in-pass
        try:
            elt.run(self.drain_async(timeout), timeout=timeout + 1.0)
        except Exception:  # rtpulint: ignore[RTPU006] — drain is advisory at exit; close() proceeds regardless
            pass

    def close_when_drained(self, timeout: float = 10.0):
        """Close once every queued fire-and-forget notify has been sent
        (or after `timeout`). A plain close() between notify_nowait() and
        its scheduled coroutine running silently swallows the message —
        for a cache-evicted owner client that lost message is a task
        result, and the owner's get() hangs forever."""

        async def _drain_then_close():
            await self.drain_async(timeout)
            self.close()

        elt = EventLoopThread.get()
        if threading.current_thread() is elt.thread:
            spawn_logged(_drain_then_close(), name="rpc.drain_close")
        else:
            elt.loop.call_soon_threadsafe(
                lambda: spawn_logged(_drain_then_close(),
                                     name="rpc.drain_close"))

    def close(self):
        self._closed = True

        async def _close():
            if self._local_conn is not None and not self._local_conn.closed:
                self._local_conn.closed = True
                srv = self._local_conn.server
                if srv.on_disconnect is not None:
                    try:
                        res = srv.on_disconnect(self._local_conn)
                        if asyncio.iscoroutine(res):
                            await res
                    except Exception:  # rtpulint: ignore[RTPU006] — a disconnect callback must never block close; server-side state self-heals on reconnect
                        pass
            if self._writer is not None:
                try:
                    self._writer.close()
                except Exception:  # rtpulint: ignore[RTPU006] — socket may already be dead at close
                    pass

        elt = EventLoopThread.get()
        try:
            if threading.current_thread() is elt.thread:
                spawn_logged(_close(), name="rpc.close")
            else:
                elt.run(_close())
        except Exception:  # rtpulint: ignore[RTPU006] — close() runs on interpreter-exit paths where the loop may already be gone
            pass
