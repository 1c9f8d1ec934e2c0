"""Tracing: task spans across the cluster, and a flight recorder for the
loops that are too hot for them.

Task spans (parity with the reference's tracing layer, ref:
python/ray/util/tracing/tracing_helper.py — opt-in wrappers around
submit/execute that propagate an OpenTelemetry context through task
specs): plain dicts flushed through the task-event channel to the
controller, with trace/parent ids propagated in task specs. Opt-in via
`tracing.enable()` (no-op overhead when off).

Flight recorder (always on): the serving engine and the trainer write one
plain tuple per step, dispatch and request into a bounded ring per record
kind (`record`, read back with `records`); `region(name)` puts the same
interval into jax's profiler trace under a stable name whenever a profiler
session runs. No I/O, no uuid and no dict on that path. Every timestamp of
both tiers is Unix-epoch time (spans in seconds, ring records in
nanoseconds); jax's profiler reports the same clock counted from the start
of its session, so one constant per session places a record on a trace.
`chrome_trace()` renders both tiers for chrome://tracing or Perfetto.

Scopes (trace time only): `scope(name)` is a `jax.named_scope` at one of
the phases of a step listed in `SCOPES`; the name lands in the `op_name`
of every instruction traced under it, and `instruction_scopes` reads a
compiled program's text back as instruction name -> path, so that a
profiler trace's device time can be summed by the program's own names.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import re
import threading
import time
import uuid
from typing import Any, Deque, Dict, List, Optional, Tuple

_enabled = False
_lock = threading.Lock()
_finished: List[Dict[str, Any]] = []
_current_span: contextvars.ContextVar = contextvars.ContextVar(
    "rtpu_span", default=None)


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def is_enabled() -> bool:
    return _enabled


def current_context() -> Optional[Dict[str, str]]:
    """The (trace_id, span_id) pair to propagate to a child process."""
    span = _current_span.get()
    if span is None:
        return None
    return {"trace_id": span["trace_id"], "parent_id": span["span_id"]}


@contextlib.contextmanager
def span(name: str, kind: str = "internal",
         context: Optional[Dict[str, str]] = None,
         attributes: Optional[Dict[str, Any]] = None):
    """Record one span. `context` carries a remote parent (from
    current_context() shipped in a task spec); otherwise the parent is the
    ambient span in this task/thread."""
    if not (_enabled or context is not None
            or _current_span.get() is not None):
        # record when tracing is on, a remote parent context arrived with
        # the work, or an ambient traced span is open — so user spans
        # inside a traced task record without latching the process flag
        yield None
        return
    parent = _current_span.get()
    trace_id = (context or {}).get("trace_id") or (
        parent["trace_id"] if parent else uuid.uuid4().hex)
    parent_id = (context or {}).get("parent_id") or (
        parent["span_id"] if parent else None)
    record = {
        "name": name,
        "kind": kind,
        "trace_id": trace_id,
        "span_id": uuid.uuid4().hex[:16],
        "parent_id": parent_id,
        "start": time.time(),
        "attributes": dict(attributes or {}),
    }
    token = _current_span.set(record)
    try:
        yield record
    except Exception as e:
        record["attributes"]["error"] = repr(e)
        record["status"] = "ERROR"
        raise
    finally:
        record["end"] = time.time()
        record.setdefault("status", "OK")
        _current_span.reset(token)
        with _lock:
            _finished.append(record)


def drain() -> List[Dict[str, Any]]:
    """Return + clear this process's finished spans."""
    with _lock:
        out, _finished[:] = list(_finished), []
    return out


def collect() -> List[Dict[str, Any]]:
    """All spans: this process's (drained) + the cluster's (workers flush
    theirs to the controller after each traced task). The controller side
    is a RETAINED ring (up to 100k spans, like the task-event sink), so
    repeated collect() calls re-return cluster spans; local spans are
    consumed."""
    spans = drain()
    try:
        from ..runtime.core import get_core

        core = get_core(required=False)
        if core is not None:
            spans.extend(core.controller.call("list_trace_spans",
                                              _timeout=10))
    except Exception:  # rtpulint: ignore[RTPU006] — cluster spans are an additive tier; local spans still return when the controller is gone
        pass
    return spans


# ---------------------------------------------------------------------------
# Flight recorder: bounded rings of plain tuples, one per record kind.
# ---------------------------------------------------------------------------

# what each position of a record holds; `*_ns` are Unix-epoch nanoseconds
# (None where the event never happened), other `*_ns` of engine.step are
# durations inside the step
FIELDS: Dict[str, Tuple[str, ...]] = {
    # one per request, written when it finishes, aborts or expires. The
    # last four split `first_token_ns - dispatched_ns` by the device's
    # timeline as the engine stamps it (engine.dispatch, below) and sum to
    # it to the nanosecond: `prefill_device_ns` the device time of the
    # programs that carried the prompt's passes, `device_wait_ns` the rest
    # of the time from the first dispatch to the last pass's stamped end
    # (other programs ahead of it on the device, the host between its
    # passes, and the fetch's own lag behind that end), `harvest_host_ns`
    # the host's code from that stamp to the token's. `parts_exact` is
    # False where the host came to one of those programs after it had
    # finished: the host loop was behind the device, the two device parts
    # are then upper bounds and hold the lag, which `harvest_host_ns`
    # never shows. None for a request that got no first token, and for
    # every request of an engine whose handles cannot say (pp)
    "engine.request": (
        "request_id", "arrival_ns", "admitted_ns", "dispatched_ns",
        "first_token_ns", "finish_ns", "prompt_tokens", "cached_tokens",
        "output_tokens", "preemptions", "finish_reason",
        "device_wait_ns", "prefill_device_ns", "harvest_host_ns",
        "parts_exact"),
    # one per program enqueued, written when its tokens are harvested,
    # built BY NAME: a field nobody wrote is None. Through `k` the fields
    # are the engine's own; `rows` is a tuple of (request_id, q_tokens,
    # ctx_tokens) per real row. From `moe_assignments` through
    # `moe_assignments_routed` they are a model family's, documented where
    # they are produced (its dispatch facts: serve/llm/stage.py:
    # model_family). The ORDER stands: hand-made records of the benchmark's
    # own tests are laid out by position.
    # The last four are the program on the device's timeline, stamped by
    # the host with no profiler (serve/llm/engine.py: _device_stamps says
    # how, and when an end is exact): `enqueued_ns` when the compute seam
    # returned (`dispatch_ns` is taken before the host builds the program's
    # arrays); None in all four where the handle cannot say whether it is
    # ready (pp)
    "engine.dispatch": (
        "seq", "kind", "step_dispatched", "step_harvested", "dispatch_ns",
        "fetch_start_ns", "fetch_end_ns", "rows_padded", "tokens_padded",
        "rows", "k", "moe_assignments", "moe_experts_touched",
        "moe_expert_tokens_max", "ssm_layers", "ssm_state_bytes_row",
        "lin_layers", "lin_state_bytes_row", "sparse_layers",
        "sparse_tokens_read", "sparse_kernels_scored", "pass_index",
        "final", "block_passes", "block_tokens_fixed", "block_len",
        "window_layers", "full_layers", "window_tokens_read",
        "full_tokens_read", "window_tokens_held", "full_tokens_held",
        "mla_layers", "latent_bytes_token", "mla_ctx_chunks",
        "moe_assignments_routed",
        "enqueued_ns", "device_start_ns", "device_end_ns", "end_exact",
        # behind the stamps, as the next family's: the query heads a layer
        # of each kind of a two-kind stack has (models/mellum.py:
        # WindowFacts; they differ in models/laguna.py)
        "window_heads", "full_heads",
        # a family that came after the stamps (models/gigachat.py): every
        # field before these is held to its place by hand-made records
        "gdn_layers", "gdn_state_bytes_row",
        # the next family's (models/zaya.py: CcaFacts), behind them
        "cca_layers", "cca_tail_bytes_row",
        # the engine's own, LAST for the same reason: whether the program's
        # `temp` operand had a row above 0, which is the branch its sampler
        # took (serve/llm/stage.py: _device_sample); None: no sampler
        "drawn",
        # LAST again: the shape key the call site gave `compute.run` with
        # the record's kind ((sb, rb, cp) a prefill, (sb, rb) a verify,
        # (K, mp) a decode, the block key), which is the program the device
        # ran: `LLMEngine.program_scopes(kind, program_key)` is its table
        "program_key"),
    # one per LLMEngine.step(); `fetch_blocked`: how many of the step's
    # harvests found their program unfinished (the step waited for the
    # device, not the device for the step), `device_idle_ns`: time the
    # device had nothing enqueued, counted in the step that harvests the
    # program which ended the gap
    "engine.step": (
        "seq", "start_ns", "end_ns", "intake_ns", "admit_ns",
        "dispatch_prefill_ns", "dispatch_decode_ns", "fetch_ns",
        "harvest_ns", "running", "waiting", "fetch_blocked",
        "device_idle_ns"),
    # one per program the engine builds (a miss of its jit cache)
    "engine.program_built": ("kind", "shape_key", "ns", "step_seq"),
    # one per ShardedTrainer.step(): the host's time to dispatch the step
    "train.step": ("seq", "start_ns", "end_ns"),
}
# 65 536 records in all: a 50 s window plus 90 s of grace at five times a
# 62 ms decode step is 11k steps and as many dispatches
CAPACITY: Dict[str, int] = {
    "engine.request": 8192, "engine.dispatch": 16384, "engine.step": 32768,
    "engine.program_built": 1024, "train.step": 7168,
}

now_ns = time.time_ns   # the recorder's one clock


class _Ring:
    __slots__ = ("buf", "appended")

    def __init__(self, capacity: int):
        self.buf: Deque[tuple] = collections.deque(maxlen=capacity)
        # one writer thread per kind (the engine's driver, the training
        # loop): a second writer could lose a count here, never a record
        self.appended = 0


_rings: Dict[str, _Ring] = {k: _Ring(n) for k, n in CAPACITY.items()}


def record(kind: str, rec: tuple) -> None:
    """Append one record (a tuple laid out as FIELDS[kind]) to its ring;
    the oldest record falls out once the ring is full."""
    ring = _rings[kind]
    ring.appended += 1
    ring.buf.append(rec)


def appended(kind: str) -> int:
    """Records of this kind ever written (a cursor for `records`)."""
    return _rings[kind].appended


def dropped(kind: str) -> int:
    """Records of this kind that have fallen out of the ring."""
    ring = _rings[kind]
    return max(0, ring.appended - ring.buf.maxlen)


def records(kind: str, since: int = 0) -> List[tuple]:
    """The ring's records, oldest first; with `since` (an earlier
    `appended(kind)`) only those written after it that are still held."""
    ring = _rings[kind]
    held = len(ring.buf)
    fresh = min(held, ring.appended - since)
    if fresh <= 0:
        return []
    return list(itertools.islice(ring.buf, held - fresh, held))


def reset_ring() -> None:
    """Empty every ring and its counts (tests)."""
    for ring in _rings.values():
        ring.buf.clear()
        ring.appended = 0


_TraceAnnotation = None


class region:
    """`with region("rtpu.engine.fetch") as r:` — the interval as a
    TraceAnnotation in jax's profiler trace (an atomic load when no
    profiler session runs) and as `r.start_ns`/`r.end_ns`/`r.ns` for the
    caller, who folds it into the one record of its step: a region
    appends nothing by itself."""

    __slots__ = ("_annotation", "start_ns", "end_ns")

    def __init__(self, name: str):
        global _TraceAnnotation
        if _TraceAnnotation is None:
            # on first use, not at import: cluster workers import this
            # module and never jax
            from jax.profiler import TraceAnnotation

            _TraceAnnotation = TraceAnnotation
        self._annotation = _TraceAnnotation(name)

    def __enter__(self) -> "region":
        self._annotation.__enter__()
        self.start_ns = now_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = now_ns()
        self._annotation.__exit__(*exc)
        return False

    @property
    def ns(self) -> int:
        return self.end_ns - self.start_ns


# ---------------------------------------------------------------------------
# Scopes: the device's time by the program's own names.
# ---------------------------------------------------------------------------

# the phases of a step that are neither a flax module nor a jit of their
# own, each a `jax.named_scope` at trace time (nothing at run time): the
# name lands in the `op_name` of every instruction traced under it, beside
# the module names, the inner jits' (`jit(_sparse_prefill)`), the backward
# pass's `transpose(jvp(...))` and a rematerialised forward's
# `rematted_computation`, which jax writes itself. `program_scopes` (serve/
# llm/stage.py, parallel/train_lib.py) reads them back a built program. A
# scope is never the innermost name around a Pallas call: a kernel's
# instruction is named after that (`_moe_gmm.<n>`, `attn.<n>`).
SCOPES: Tuple[str, ...] = (
    # models/llama.py: MoEMLP, every expert family's layer
    "rtpu.moe.route",      # router logits, top-k, the gate's weights
    "rtpu.moe.layout",     # the sort by expert and every integer array
    "rtpu.moe.gather",     # tokens into expert order
    "rtpu.moe.products",   # the two grouped matmuls, silu * up, the stack
    "rtpu.moe.unsort",     # back to token order, weighted sum, the mask
    # the final norm and the vocabulary matmul, where a family computes it
    "rtpu.head",
    # serve/llm/stage.py: _device_sample, models/sdar.py: denoise
    "rtpu.sample",
    # what an attention layer moves that is no kernel: page, ring, latent
    # and per-slot state writes, the gathers that feed a kernel
    "rtpu.attn.cache_write",
    # models/mellum.py: a per-head output gate's projection and its
    # product with the heads (models/laguna.py has one)
    "rtpu.attn.gate",
    # models/zaya.py: everything between a CCA layer's projection and its
    # attention kernel that is no cache write: the value shift, the two
    # convolutions, the q-k mean, the norm, the temperature, the rotation
    "rtpu.attn.cca",
    # parallel/train_lib.py: _step
    "rtpu.loss",           # the forward under value_and_grad
    "rtpu.optimizer",      # tx.update, apply_updates, global_norm
)


def scope(name: str):
    """`with tracing.scope("rtpu.head"):` around the phase's code, inside
    a traced function; `name` is one of SCOPES."""
    if name not in SCOPES:
        raise KeyError(f"{name!r} is no scope of tracing.SCOPES")
    import jax

    return jax.named_scope(name)


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?(\S+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instruction_scopes(compiled_text: str) -> Dict[str, str]:
    """A compiled program's text (`jit(f).lower(...).compile().as_text()`)
    as instruction name -> the `op_name` path of its metadata ("" where
    the compiler made the instruction and gave it none): `fusion.694` ->
    `jit(run_prefill)/.../rtpu.moe.unsort/gather`. A fusion carries ONE
    path, its root's. The names are the ones a profiler trace's op events
    carry."""
    out: Dict[str, str] = {}
    for line in compiled_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is not None:
            path = _OP_NAME.search(line)
            out[m.group(1)] = path.group(1) if path else ""
    return out


def _dispatch_name(r: Dict[str, Any]) -> str:
    return f"{r['kind']} #{r['seq']}"


# how the records are drawn: (lane, record kind, event name, field that
# starts the event, field that ends it). A dispatch is drawn twice: as the
# host saw it, and on the lane `rtpu.device` as the device ran it by the
# engine's stamps, which is the device's timeline with no profiler session
_RING_EVENT = (
    ("engine.step", "engine.step", lambda r: "rtpu.engine.step", "start_ns",
     "end_ns"),
    ("engine.dispatch", "engine.dispatch", _dispatch_name, "dispatch_ns",
     "fetch_end_ns"),
    ("rtpu.device", "engine.dispatch", _dispatch_name, "device_start_ns",
     "device_end_ns"),
    ("engine.request", "engine.request", lambda r: str(r["request_id"]),
     "arrival_ns", "finish_ns"),
    ("engine.program_built", "engine.program_built",
     lambda r: f"built {r['kind']}", "ns", "ns"),
    ("train.step", "train.step", lambda r: "rtpu.train.step", "start_ns",
     "end_ns"),
)


def _ring_events() -> List[Dict[str, Any]]:
    """Ring records as chrome://tracing events, one thread per lane; a
    record without a lane's stamps (None, or a record older than the
    field) is not drawn there."""
    out = []
    for lane, kind, name, start, end in _RING_EVENT:
        for rec in records(kind):
            args = dict(zip(FIELDS[kind], rec))
            if args.get(start) is None or args.get(end) is None:
                continue
            out.append({"ph": "X", "name": name(args), "cat": lane,
                        "pid": "rtpu.ring", "tid": lane,
                        "ts": args[start] / 1e3,
                        "dur": max(args[end] - args[start], 0) / 1e3,
                        "args": args})
    return out


def chrome_trace(spans: Optional[List[Dict[str, Any]]] = None
                 ) -> List[Dict[str, Any]]:
    """Task spans (grouped per trace) and the flight recorder's rings as
    chrome://tracing events on one clock (microseconds since the epoch):
    `json.dump(tracing.chrome_trace(), f)` is a file Perfetto loads.
    Without `spans` this process's finished spans are drained."""
    out = []
    for record in (spans if spans is not None else drain()):
        out.append({
            "ph": "X",
            "name": record["name"],
            "cat": record["kind"],
            "pid": record["trace_id"][:8],
            "tid": (record["parent_id"] or record["span_id"])[:8],
            "ts": record["start"] * 1e6,
            "dur": max(record["end"] - record["start"], 0.0) * 1e6,
            "args": {**record["attributes"], "span_id": record["span_id"],
                     "status": record["status"]},
        })
    return out + _ring_events()
