"""One cell, one process, one result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The workload is a cell of BENCHMARK.json; its configuration, traffic mix,
metrics, reference and runner are files found by name (chipbench/README.md).
With --trace 0 the last line of standard output carries the cell's
end-to-end metrics, with --trace 1 its per-layer metrics and a breakdown of
a few traced seconds. Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result line.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time

_T_IMPORT = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import cell as cell_mod  # noqa: E402
from chipbench.cell import BenchError  # noqa: E402


def process_start_time() -> float:
    """Wall-clock time this process was created (set-up counts from it)."""
    try:
        import psutil

        return min(psutil.Process().create_time(), _T_IMPORT)
    except Exception:  # noqa: BLE001 — psutil missing or /proc unreadable
        return _T_IMPORT


def log(msg: str) -> None:
    print(f"[chipbench {time.strftime('%H:%M:%S')}] {msg}", flush=True)


class CompileCounter:
    """JAX's compile and persistent-cache events, from here on."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = self.compiles = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, name: str, **_):
        if name.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif name.endswith("/compilation_cache/cache_misses"):
            self.misses += 1

    def _on_duration(self, name: str, secs: float, **_):
        # fires once per program built, whether XLA compiled it or the
        # persistent cache supplied it
        if name.endswith("/backend_compile_duration"):
            self.compiles += 1

    def snapshot(self):
        return (self.hits, self.misses, self.compiles)


class Tracer:
    """Takes a profiler trace of a few seconds of the steady window when
    --trace 1; does nothing otherwise."""

    def __init__(self, on: bool, spec: dict, seconds: float, out_dir: str):
        self.on = on
        span = float(spec.get("seconds", 4.0))
        self.start_s = min(float(spec.get("start_share", 0.5)) * seconds,
                           max(0.0, seconds - span))
        self.stop_s = min(self.start_s + span, seconds)
        self.dir = out_dir
        self.state = "idle" if on else "off"
        self._window = None

    def poll(self, now: float) -> None:
        if self.state == "idle" and now >= self.start_s:
            import jax

            jax.profiler.start_trace(self.dir)
            self._window = jax.profiler.TraceAnnotation("chipbench.window")
            self._window.__enter__()
            self.state = "tracing"
        elif self.state == "tracing" and now >= self.stop_s:
            self._stop()

    def _stop(self) -> None:
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def finish(self) -> None:
        if self.state == "tracing":
            self._stop()


class HostHeartbeat(threading.Thread):
    """A thread that sleeps 10 ms at a time through the window and keeps the
    longest gap between two wake-ups: a long gap means the machine stalled
    the whole process (a shared host), not that the program was slow."""

    def __init__(self):
        super().__init__(daemon=True)
        self.longest = 0.0
        self.over_100ms = 0
        self._stop_it = threading.Event()

    def run(self):
        last = time.monotonic()
        while not self._stop_it.wait(0.01):
            now = time.monotonic()
            self.longest = max(self.longest, now - last)
            self.over_100ms += now - last > 0.1
            last = now

    def stop(self) -> str:
        self._stop_it.set()
        self.join()
        return (f"host heartbeat (a thread sleeping 10 ms at a time): longest "
                f"gap {self.longest:.3f} s, {self.over_100ms} over 0.1 s")


def device_facts() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def main(argv=None) -> int:
    t_start = process_start_time()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    return run_cell(cell_mod.load_cell(args.workload), args.seed,
                    args.seconds, args.trace, t_start)


def run_cell(cell, seed: int, seconds: float, trace: int, t_start: float,
             keep_trace=None) -> int:
    """One run of one cell. `keep_trace` (tools/sweep.py only): a directory
    for the reduced trace and a description of the raw one."""
    log(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, chips {cell.chips}, seed {seed}, "
        f"{seconds:g} s, trace {trace}")

    import jax

    from ray_tpu.util.compile_cache import enable_compile_cache

    # every program this process builds goes to the persistent cache, the
    # small ones too, so that a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    device = device_facts()
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not cell.rehearsal:
        raise BenchError(f"no TPU found: JAX reports {device}")
    if on_tpu and device["count"] < cell.chips:
        raise BenchError(f"cell {cell.name} needs {cell.chips} chips: "
                         f"{device}")
    log(f"device {device}; compile cache {cache_dir}")
    peaks = cell_mod.load_peaks(device["kind"]) if on_tpu else {}

    runner = cell_mod.load_module("runners", cell.runner).Runner(
        cell, seed, seconds, log)
    check = runner.setup()
    log(f"compile cache after set-up: hits {counter.hits} misses "
        f"{counter.misses} programs built {counter.compiles}")

    trace_dir = os.path.join(ROOT, ".chipbench", f"trace-{cell.name}")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    tracer = Tracer(bool(trace), cell.traffic.get("trace", {}),
                    seconds, trace_dir)
    before = counter.snapshot()
    # process start on the monotonic clock the runners time with
    mono_start = time.monotonic() - (time.time() - t_start)
    heartbeat = HostHeartbeat()
    heartbeat.start()
    runner.run_window(tracer)
    log(heartbeat.stop())
    # set-up ends at the first timed instant: the window's start, after a
    # serving mix's ramp
    setup_s = runner.t0 - mono_start
    after = counter.snapshot()
    # a program built or fetched from the cache inside the window
    built = sum(a - b for a, b in zip(after, before))
    window_s = runner.window_seconds()
    if hasattr(runner, "final_check"):
        check = runner.final_check()
    check["numbers"].append({"name": "programs_built_in_window",
                             "value": built, "limit": 0, "ok": built == 0})
    correct = bool(check["correct"]) and built == 0
    for row in check["numbers"]:
        log(f"check {row['name']}: {row['value']!r} (limit <= "
            f"{row['limit']!r}) {'ok' if row['ok'] else 'NOT OK'}")
    log(f"check notes: {check.get('notes')}")

    reduced = None
    if trace:
        from chipbench import tracered

        xplane = tracered.find_xplane(trace_dir)
        reduced = tracered.reduce_trace(tracered.load_xplane(xplane))
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            reduced.trace.save(os.path.join(
                keep_trace, f"{cell.name}.trace.json.gz"))
            with open(os.path.join(keep_trace,
                                   f"{cell.name}.describe.txt"), "w") as f:
                f.write(tracered.describe_xplane(xplane))
        shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = {"cell": cell, "runner": runner, "records": runner.records,
           "samples": runner.samples, "counters": runner.counters,
           "seconds": seconds, "window_s": window_s,
           "setup_s": setup_s, "trace": reduced, "peaks": peaks,
           "work": runner.work_facts(), "log": log}
    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        reader = cell_mod.load_module("readers", m.spec["reader"])
        value = reader.read(ctx, **m.spec.get("params", {}))
        if value is None:
            log(f"metric {m.name}: nothing to read, left out")
            continue
        metrics[m.name] = {"value": float(value), "unit": m.unit}
    for line in _summary_lines(runner):
        log(line)
    counts = runner.counts()
    device_out = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"],
                  "memory_peak_bytes": memory_peak(cell.chips)}
    result = {"correct": correct, "attempted": counts["attempted"],
              "failed": counts["failed"], "metrics": metrics,
              "device": device_out}
    if reduced is not None:
        device_out["busy_s"] = reduced.busy_s
        device_out["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    log(f"set-up {setup_s:.1f} s, window {window_s:.3f} s, cache hits "
        f"{counter.hits} misses {counter.misses}")
    if not on_tpu:
        log("rehearsal off the TPU: control flow finished; no device metric "
            "is printed. What the result line WOULD hold, for the builder: "
            + json.dumps({k: result[k] for k in
                          ("correct", "attempted", "failed")})
            + " metric names " + json.dumps(sorted(metrics)))
        return 3
    print(json.dumps(result), flush=True)
    return 0


def _summary_lines(runner):
    """Medians, sample counts and failure counts: earlier lines, not the
    last one."""
    from chipbench import stats

    out = []
    recs = runner.records
    if recs:
        for field in ("ttft_ms", "tpot_ms", "late_ms"):
            out.append(f"{field} over counted requests: "
                       f"{stats.summarize(stats.field_values(recs, field))}")
        out.append(f"requests: {len(recs)} scheduled, "
                   f"{sum(1 for r in recs if r.sent_s is not None)} sent, "
                   f"{sum(1 for r in recs if r.ok)} finished")
    if runner.counters:
        out.append(f"counters: {runner.counters}")
    for key, vals in runner.samples.items():
        if vals and key != "t":
            out.append(f"samples {key}: n={len(vals)} mean="
                       f"{sum(vals)/len(vals):.3f} min={min(vals)} "
                       f"max={max(vals)}")
    ends = getattr(runner, "step_end_s", None)
    if ends:
        # a slow run shows here as one stalled step or as every step slower
        took = sorted(b - a for a, b in zip([0.0] + ends, ends))
        mid = took[len(took) // 2]
        out.append(f"step seconds: median {mid:.4f} max {took[-1]:.4f}, "
                   f"{sum(t > 1.2 * mid for t in took)} of {len(took)} over "
                   f"1.2 x the median")
    losses = getattr(runner, "losses", None)
    if losses:
        out.append(f"losses: first {losses[:3]} last {losses[-3:]} "
                   f"steps {len(losses)}")
    return out


if __name__ == "__main__":
    try:
        code = main()
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        code = 1
    sys.stdout.flush()
    sys.exit(code)
