"""Reduction from a profiler trace to numbers: device busy time, idle
share, time by operation name, and idle gaps attributed to what the host
was doing.

The profiler writes `<dir>/plugins/profile/<time>/*.xplane.pb`;
`load_xplane` reads it with `jax.profiler.ProfileData` into a `Trace`, a
plain structure that also round-trips through JSON, so the reduction is
tested on a small trace recorded from the chip (tests/data/).

What the planes and lines are called on a TPU v5e with jax 0.9.0 (read by
hand, PR 26): the device plane is `/device:TPU:<n>` with the lines `XLA
Modules` (one event per executed program, `jit_<function>(<fingerprint>)`),
`XLA Ops` (one event per executed HLO op, nested under `while`, named by the
op's whole HLO text), `Async XLA Ops`, `Steps`; the host plane `/host:CPU`
has one line per thread plus the line `python` with the python tracer's
events and the benchmark's TraceAnnotations. The patterns under which
programs and kernels appear are in the per-layer metrics' own files.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

# plane and line names as they appear today
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
LINES = {
    "ops": "XLA Ops",          # one event per executed HLO op, nested
    "modules": "XLA Modules",  # one event per executed program
}
WINDOW_SPAN = "chipbench.window"   # host span that delimits the window
HOST_SPAN_PREFIX = "chipbench."    # the benchmark's own TraceAnnotations

Event = Tuple[str, int, int]  # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Trace:
    """chip -> events, plus the benchmark's host spans, all on one clock."""
    ops: Dict[int, List[Event]]
    modules: Dict[int, List[Event]]
    host: List[Event]
    window: Tuple[int, int]            # (start_ns, end_ns)

    def to_json(self) -> Dict[str, Any]:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "modules": {str(k): v for k, v in self.modules.items()},
                "host": self.host, "window": list(self.window)}

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> "Trace":
        def ev(xs):
            return [(str(n), int(s), int(t)) for n, s, t in xs]
        return cls(ops={int(k): ev(v) for k, v in d["ops"].items()},
                   modules={int(k): ev(v) for k, v in d["modules"].items()},
                   host=ev(d["host"]), window=tuple(d["window"]))

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^%(?P<lhs>\S+) = (?P<shape>\(.*?\)|\S+) "
                  r"(?P<op>[\w\-]+)\(")
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'
# python-tracer events of the program's own host code that are kept as
# host spans (file basenames), and the shortest that is kept
PROGRAM_FILES = re.compile(r"^\$(engine|cache|train_lib|batch)\.py:\d+ "
                           r"(\w+)$")
MIN_PROGRAM_SPAN_NS = 50_000


def op_display_name(raw: str) -> str:
    """An `XLA Ops` event is named by its whole HLO text. Keep the
    instruction's name, its opcode and its result shape (layouts dropped):
    `fusion.120 fusion bf16[16,2048,28672]`. A Pallas kernel is a
    custom-call to `tpu_custom_call` and gets the opcode `pallas`; its
    instruction name comes from the jit or module scope it was called in
    (`_decode_call.12 pallas ...`, `attn.9 pallas ...`)."""
    head = _LAYOUT.sub("", raw[:600])
    m = _HLO.match(head)
    if not m:
        return raw[:120]
    op = "pallas" if PALLAS_MARK in raw else m.group("op")
    return f"{m.group('lhs')} {op} {m.group('shape')[:120]}"


def host_span_name(raw: str, duration_ns: int) -> Optional[str]:
    """The benchmark's own TraceAnnotations, and the program's host
    functions as the profiler's python tracer names them
    (`$engine.py:1285 _harvest` -> `engine.py:_harvest`)."""
    if raw.startswith(HOST_SPAN_PREFIX):
        return raw
    m = PROGRAM_FILES.match(raw)
    if m and duration_ns >= MIN_PROGRAM_SPAN_NS:
        return f"{m.group(1)}.py:{m.group(2)}"
    return None


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == LINES["ops"]:
                    ops[chip] = [(op_display_name(e.name), int(e.start_ns),
                                  int(e.duration_ns)) for e in line.events]
                elif line.name == LINES["modules"]:
                    modules[chip] = [(e.name, int(e.start_ns),
                                      int(e.duration_ns))
                                     for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = host_span_name(e.name, int(e.duration_ns))
                    if name is not None:
                        host.append((name, int(e.start_ns),
                                     int(e.duration_ns)))
    host.sort(key=lambda e: e[1])
    win = [e for e in host if e[0] == WINDOW_SPAN]
    if win:
        window = (win[0][1], win[0][1] + win[0][2])
    else:
        every = [e for evs in ops.values() for e in evs] + host
        window = (min(e[1] for e in every),
                  max(e[1] + e[2] for e in every)) if every else (0, 0)
    return Trace(ops=ops, modules=modules, host=host, window=window)


def describe_xplane(path: str, top: int = 40) -> str:
    """A by-hand look at a trace: planes, lines, the most frequent event
    names of each line and one event's stats. For the builder, not for
    metrics."""
    from collections import Counter

    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            dur = Counter()
            cnt = Counter()
            for e in events:
                dur[e.name] += e.duration_ns
                cnt[e.name] += 1
            for name, ns in dur.most_common(top):
                out.append(f"    {ns / 1e6:10.3f} ms {cnt[name]:6d} x "
                           f"{name[:150]!r}")
            shown = set()
            for e in events:
                if e.name in shown or len(shown) >= 6:
                    continue
                shown.add(e.name)
                try:
                    stats = {str(k): str(v)[:300] for k, v in e.stats}
                except Exception as exc:  # noqa: BLE001 — a look, not a metric
                    stats = {"stats_error": repr(exc)}
                out.append(f"    STATS {e.name[:60]!r}: {stats}")
    return "\n".join(out)


# ------------------------------------------------------------- arithmetic
def clip(events: Iterable[Event], window: Tuple[int, int]) -> List[Event]:
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def union_intervals(events: Iterable[Event]) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals covered by any event."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: List[List[int]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(events: Iterable[Event]) -> int:
    return sum(e - s for s, e in union_intervals(events))


def self_times(events: Sequence[Event]) -> List[Event]:
    """Each event's own time: its duration minus what its children (events
    nested inside it on the same line) cover. A `while` that spans a layer
    scan then keeps only the time none of its body's ops ran."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [e[2] for e in events]
    stack: List[Tuple[int, int, int]] = []   # (index, end, covered_until)
    for i in order:
        _, start, dur = events[i]
        end = start + dur
        while stack and start >= stack[-1][1]:
            stack.pop()
        if stack:
            parent, pend, covered = stack[-1]
            lo = max(start, covered)
            hi = min(end, pend)
            if hi > lo:
                self_ns[parent] -= hi - lo
                stack[-1] = (parent, pend, hi)
        stack.append((i, end, start))
    return [(events[i][0], events[i][1], max(0, self_ns[i]))
            for i in range(len(events))]


def time_by_name(events: Sequence[Event]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for name, _, own in self_times(events):
        out[name] = out.get(name, 0) + own
    return out


def durations_matching(events: Sequence[Event], pattern: str) -> List[int]:
    rx = re.compile(pattern)
    return [d for name, _, d in events if rx.search(name)]


def idle_gaps(events: Iterable[Event], window: Tuple[int, int]
              ) -> List[Tuple[int, int]]:
    """[start, end) intervals of the window in which no event ran."""
    gaps, at = [], window[0]
    for s, e in union_intervals(clip(events, window)):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if window[1] > at:
        gaps.append((at, window[1]))
    return gaps


def innermost_segments(spans: Sequence[Event]) -> List[Tuple[int, int, str]]:
    """Flatten properly nested spans into non-overlapping [start, end, name)
    segments, each named after the innermost span that covers it."""
    order = sorted(spans, key=lambda e: (e[1], -e[2]))
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []     # (end, name)
    at = None

    def emit(upto: int) -> None:
        nonlocal at
        if stack and at is not None and upto > at:
            out.append((at, upto, stack[-1][1]))
        at = upto

    for name, start, dur in order:
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        emit(start)
        at = start
        stack.append((start + dur, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def attribute_gaps(gaps: Sequence[Tuple[int, int]], host: Sequence[Event],
                   modules: Sequence[Event] = ()) -> Dict[str, int]:
    """Idle nanoseconds by cause. A part of a gap that lies inside a
    running program is the device's own (`(in program) <name>`); the rest
    goes to the innermost benchmark span on the host that covers it, or to
    `(between spans)`."""
    segs = innermost_segments(
        [(n, s, d) for n, s, d in host if n != WINDOW_SPAN])
    prog = [(s, s + d, "(in program) " + n) for n, s, d in
            sorted(modules, key=lambda e: e[1])]
    out: Dict[str, int] = {}

    def sweep(parts, segments, fallback):
        """Split parts by segments; returns the uncovered remainder."""
        rest, j = [], 0
        for a, b in parts:
            while j < len(segments) and segments[j][1] <= a:
                j += 1
            k, at = j, a
            while k < len(segments) and segments[k][0] < b:
                s, e, name = segments[k]
                if s > at:
                    rest.append((at, s))
                lo, hi = max(at, s), min(b, e)
                if hi > lo:
                    out[name] = out.get(name, 0) + hi - lo
                at = max(at, hi)
                k += 1
            if b > at:
                rest.append((at, b))
        if fallback is not None:
            for a, b in rest:
                out[fallback] = out.get(fallback, 0) + b - a
        return rest

    rest = sweep(sorted(gaps), prog, None)
    sweep(rest, segs, "(between spans)")
    return out


@dataclasses.dataclass
class Reduced:
    """What the per-layer readers and the result line take from a trace."""
    window_s: float
    busy_s: float                      # mean over chips
    idle_pct: float
    ops_by_name_s: Dict[str, float]    # self time, summed over chips
    gaps_by_span_s: Dict[str, float]
    trace: Trace

    def breakdown(self, top: int = 10) -> Dict[str, List[List[Any]]]:
        def top_of(d):
            return [[k, v] for k, v in sorted(
                d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": top_of(self.ops_by_name_s),
                "idle_gaps": top_of(self.gaps_by_span_s)}


def reduce_trace(trace: Trace) -> Reduced:
    lo, hi = trace.window
    window_ns = max(1, hi - lo)
    chips = sorted(trace.ops) or [0]
    busy, by_name, gaps_by = [], {}, {}
    for chip in chips:
        events = clip(trace.ops.get(chip, []), trace.window)
        busy.append(busy_ns(events))
        for name, ns in time_by_name(events).items():
            by_name[name] = by_name.get(name, 0.0) + ns / 1e9
        for name, ns in attribute_gaps(
                idle_gaps(events, trace.window), trace.host,
                clip(trace.modules.get(chip, []), trace.window)).items():
            gaps_by[name] = gaps_by.get(name, 0.0) + ns / 1e9 / len(chips)
    mean_busy = sum(busy) / len(busy)
    return Reduced(window_s=window_ns / 1e9, busy_s=mean_busy / 1e9,
                   idle_pct=100.0 * (1.0 - mean_busy / window_ns),
                   ops_by_name_s=by_name, gaps_by_span_s=gaps_by,
                   trace=trace)
