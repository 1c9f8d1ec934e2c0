"""One untraced run of a benchmark cell (needs a chip) that keeps what a
result line throws away: every flight record of the run (`engine.request`,
`engine.dispatch`, `engine.step`: ray_tpu/util/tracing.py, no profiler), the
benchmark's own request records, the garbage collector's pauses and the gaps
of a thread that only sleeps, as JSON; then what two such runs of one seed
are compared by. How PR 51 found what moves `mistral7b-chat`'s
`ttft_ms_p95` between two runs of one program (PERF.md section 6). Its
result line is not a benchmark result.

    python3 benchmarks/flightrecords.py run <tree root> <cell> <seed> <out.json>
    python3 benchmarks/flightrecords.py read <out.json> ...

`tree root` is a checkout of this repo (`.` or a parent unpacked under
`.scratch/`): the run is that tree's own `chipbench/` and engine. `read`
needs no chip: a run's counted requests by `ttft_ms` with its parts, its
programs' stamped device times by shape, and the steps that took five times
the median step with what they harvested.
"""
import collections
import gc
import json
import os
import statistics
import sys
import threading
import time

KINDS = ("engine.request", "engine.dispatch", "engine.step")


def run(root: str, cell_name: str, seed: str, out_path: str) -> int:
    root = os.path.abspath(root)
    out_path = os.path.abspath(out_path)
    os.chdir(root)
    sys.path.insert(0, root)
    from chipbench import cell as cell_mod
    from chipbench import run as bench

    pauses, gaps, start = [], [], [0]

    def on_gc(phase, info):
        if phase == "start":
            start[0] = time.time_ns()
        else:
            pauses.append((info["generation"], start[0],
                           time.time_ns() - start[0]))

    def beat():
        last = time.time_ns()
        while True:
            time.sleep(0.005)
            now = time.time_ns()
            if now - last > 30_000_000:
                gaps.append((last, now - last))
            last = now

    gc.callbacks.append(on_gc)
    threading.Thread(target=beat, daemon=True).start()
    held = {}
    lines = bench._summary_lines
    bench._summary_lines = lambda runner: (held.update(runner=runner),
                                           lines(runner))[1]
    seconds = float(os.environ.get("FLIGHT_SECONDS", "50"))
    code = bench.run_cell(cell_mod.load_cell(cell_name), int(seed), seconds,
                          0, bench.process_start_time())
    runner = held["runner"]
    # the window's start on the records' clock
    t0 = time.time_ns() - int((time.monotonic() - runner.t0) * 1e9)
    out = dump([{f: getattr(r, f) for f in r.__dataclass_fields__
                 if f != "token_ids"} for r in runner.records],
               t0, seconds, pauses, gaps)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f, default=str)
    return code


def dump(requests, t0_epoch_ns: int, seconds: float, pauses=(), gaps=()):
    """What `read` reads: the rings as they stand, beside the benchmark's
    request records (dicts; seconds from the window's start)."""
    from ray_tpu.util import tracing

    out = {"t0_epoch_ns": t0_epoch_ns, "seconds": seconds,
           "gc": list(pauses), "thread_gaps": list(gaps),
           "fields": {k: list(tracing.FIELDS[k]) for k in KINDS},
           "requests": list(requests)}
    for kind in KINDS:
        out[kind] = [list(r) for r in tracing.records(kind)]
    return out


def _p95(values):
    v = sorted(values)
    i, f = divmod(0.95 * (len(v) - 1), 1)
    return v[int(i)] + (v[min(int(i) + 1, len(v) - 1)] - v[int(i)]) * f


def read(path: str) -> None:
    with open(path) as f:
        d = json.load(f)
    t0, seconds, fields = d["t0_epoch_ns"], d["seconds"], d["fields"]
    engine = {r[0]: dict(zip(fields["engine.request"], r))
              for r in d["engine.request"]}
    rows = []
    for r in d["requests"]:
        if not r["counted"] or r["first_s"] is None:
            continue
        e = engine.get(r["rid"], {})
        rows.append((1e3 * (r["first_s"] - r["due_s"]), r["rid"],
                     r["prompt_tokens"], 1e3 * (r["sent_s"] - r["due_s"]),
                     [(e.get(k) or 0) / 1e6 for k in (
                         "device_wait_ns", "prefill_device_ns",
                         "harvest_host_ns")], e.get("parts_exact"),
                     r["due_s"]))
    ttft = [r[0] for r in rows]
    print(f"== {path}: {len(rows)} counted, ttft_ms p50 "
          f"{statistics.median(ttft):.2f} p95 {_p95(ttft):.2f} max "
          f"{max(ttft):.2f}")
    for t, rid, prompt, late, parts, exact, due in sorted(rows,
                                                           reverse=True)[:7]:
        print(f"   {rid:6s} prompt {prompt:5d} ttft {t:7.1f} = late "
              f"{late:5.1f} + device wait {parts[0]:5.1f} + prefill "
              f"{parts[1]:5.1f} + harvest {parts[2]:4.1f} (+ host) exact "
              f"{exact} due {due:.2f} s")
    inside = lambda ns: 0 <= (ns - t0) / 1e9 < seconds  # noqa: E731
    dispatches = [dict(zip(fields["engine.dispatch"], r))
                  for r in d["engine.dispatch"]]
    harvested = collections.defaultdict(list)
    by_shape = collections.defaultdict(list)
    for e in dispatches:
        if e["device_start_ns"] is None or e["device_end_ns"] is None:
            continue
        e["device_ms"] = (e["device_end_ns"] - e["device_start_ns"]) / 1e6
        harvested[e["step_harvested"]].append(e)
        if inside(e["dispatch_ns"]):
            by_shape[e["kind"], e["rows_padded"],
                     e["tokens_padded"]].append(e["device_ms"])
    for shape, ms in sorted(by_shape.items()):
        print(f"   program {shape}: {len(ms)} in the window, stamped device "
              f"ms median {statistics.median(ms):.3f} min {min(ms):.3f} "
              f"max {max(ms):.3f}")
    steps = [s for s in (dict(zip(fields["engine.step"], r))
                         for r in d["engine.step"]) if inside(s["start_ns"])]
    took = [(s["end_ns"] - s["start_ns"]) / 1e6 for s in steps]
    mid = statistics.median(took)
    print(f"   steps {len(steps)}, median {mid:.2f} ms; device idle "
          f"{sum(s['device_idle_ns'] for s in steps) / 1e6:.0f} ms; those "
          f"over 5 x the median that harvested no prefill of 1024 or more:")
    for s, ms in zip(steps, took):
        what = [(e["kind"], e["tokens_padded"], round(e["device_ms"], 1))
                for e in harvested.get(s["seq"], [])]
        if ms > 5 * mid and not any(k == "prefill" and n >= 1024
                                    for k, n, _ in what):
            print(f"     at {(s['start_ns'] - t0) / 1e9:7.3f} s: {ms:.1f} ms"
                  f" (fetch {s['fetch_ns'] / 1e6:.1f}), harvested {what}")
    for name, events, at, ns in (
            ("thread gaps", d.get("thread_gaps", []), 0, 1),
            ("gc pauses over 5 ms", d["gc"], 1, 2)):
        hits = [(round((g[at] - t0) / 1e9, 3), round(g[ns] / 1e6, 1))
                for g in events if inside(g[at]) and g[ns] > 5e6]
        print(f"   {name} in the window (s, ms): {hits}")


if __name__ == "__main__":
    if sys.argv[1] == "run":
        sys.exit(run(*sys.argv[2:6]))
    for p in sys.argv[2:]:
        read(p)
