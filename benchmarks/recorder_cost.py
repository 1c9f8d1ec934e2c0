"""What the flight recorder (ray_tpu/util/tracing.py) costs the host.

A loop over `LLMEngine.step()` with the device stubbed out (the compute
seams return numpy arrays at once, so a step is the scheduler's host work
and nothing else), with the recorder as it ships and with its three entry
points (`region`, `record`, `now_ns`) replaced by no-ops, every other step;
the difference of the medians (and the median difference of neighbouring
steps) is the recorder's own microseconds per step; the device stamps a
harvest computes from them are timed alone. For
`ShardedTrainer.step` the recorder's part is one region and one record,
timed alone. Host numbers, whatever machine runs them: no device time.

Run:  python benchmarks/recorder_cost.py [--rows 20] [--steps 1400]
Prints ONE JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.serve.llm.engine import (EngineConfig, LLMEngine,  # noqa: E402
                                      SamplingParams)
from ray_tpu.serve.llm.stage import serve_model_config  # noqa: E402
from ray_tpu.util import tracing  # noqa: E402


class Handle:
    """What a compute seam returns: the tokens, and whether the program
    behind them had finished when the engine asks (`is_ready`, as a
    device array answers it)."""

    __slots__ = ("tokens", "ready")

    def __init__(self, tokens: np.ndarray, ready: bool):
        self.tokens, self.ready = tokens, ready

    def is_ready(self) -> bool:
        return self.ready


class StubEngine(LLMEngine):
    """The scheduler with no model behind it."""

    def _build_compute(self, params, mesh) -> None:
        self.model_cfg = serve_model_config(self.config)
        self.sharding = None
        self._attention = {"decode": "stub", "prefill": "stub"}
        self._device = {"platform": "none"}

    # whether the next handle says its program had finished before the
    # fetch: False is the rule while the host runs ahead of the device
    ready = False

    def _compute_prefill(self, sb, rb, *_):
        return Handle(np.ones((rb,), np.int32), self.ready)

    def _compute_decode(self, k_steps, *_):
        return Handle(np.ones((k_steps, self.config.max_batch), np.int32),
                      self.ready)

    def _fetch_tokens(self, handle):
        return handle.tokens


class _NullRegion:
    start_ns = end_ns = ns = 0

    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _engine(rows: int) -> StubEngine:
    eng = StubEngine(EngineConfig(
        page_size=16, num_pages=4096, max_model_len=4096, max_batch=32,
        prefill_buckets=(128, 256)))
    for i in range(rows):
        eng.add_request(f"r{i}", [1] * 100,
                        SamplingParams(max_tokens=3000))
    while len(eng.running) < rows or eng.waiting:
        eng.step()
    return eng


def _set_recorder(on: bool, real=(tracing.region, tracing.record,
                                   tracing.now_ns)) -> None:
    if on:
        tracing.region, tracing.record, tracing.now_ns = real
    else:
        tracing.region, tracing.record = _NullRegion, lambda k, r: None
        tracing.now_ns = lambda: 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=20,
                    help="running requests (a chat run holds 18-19)")
    ap.add_argument("--steps", type=int, default=1400,
                    help="steps with the recorder on, and as many without")
    args = ap.parse_args()
    eng = _engine(args.rows)
    on, off = [], []
    clock = time.perf_counter
    try:
        # every other step with the recorder: both sides see the same
        # contexts grow and the same noise of the machine
        for _ in range(args.steps):
            for flag, took in ((True, on), (False, off)):
                _set_recorder(flag)
                t0 = clock()
                eng.step()
                took.append(clock() - t0)
    finally:
        _set_recorder(True)
    assert len(eng.running) == args.rows, "the requests ran out of tokens"
    n = 200_000
    t0 = time.perf_counter()
    for i in range(n):
        with tracing.region("rtpu.train.step") as r:
            pass
        tracing.record("train.step", (i, r.start_ns, r.end_ns))
    train_us = (time.perf_counter() - t0) / n * 1e6
    # the device stamps of one harvest, alone (they are plain arithmetic on
    # the engine, so the switch above leaves them on both sides): is the
    # handle ready, then the record's four fields and the engine's totals
    handle = eng._compute_decode(1)
    rec = {"enqueued_ns": tracing.now_ns()}
    t0 = time.perf_counter()
    for _ in range(n):
        eng._device_stamps(rec, eng._handle_ready(handle), 1, 2)
    stamps_us = (time.perf_counter() - t0) / n * 1e6
    print(json.dumps({
        "rows": args.rows, "steps_each": args.steps,
        "engine_step_us_recorder_on": round(
            statistics.median(on) * 1e6, 2),
        "engine_step_us_recorder_off": round(
            statistics.median(off) * 1e6, 2),
        "recorder_us_per_engine_step": round(
            (statistics.median(on) - statistics.median(off)) * 1e6, 2),
        # the same from neighbouring steps: steadier on a shared host
        "recorder_us_per_engine_step_paired": round(statistics.median(
            a - b for a, b in zip(on, off)) * 1e6, 2),
        "recorder_us_per_train_step": round(train_us, 3),
        "device_stamps_us_per_dispatch": round(stamps_us, 3),
    }))


if __name__ == "__main__":
    main()
