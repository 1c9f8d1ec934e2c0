"""A traced span's device time by the program's own names.

A built program can say which scope each of its instructions belongs to
(`LLMEngine.program_scopes(kind, program_key)`, `ShardedTrainer.
program_scopes()`: instruction name -> the `op_name` path jax wrote for
it, with the program's `jax.named_scope`s, its flax modules, its inner
jits, `transpose(jvp(...))` for the backward pass and
`rematted_computation` for a rematerialised forward). The trace's op
events carry the same instruction names (`tracered.op_display_name` keeps
them: `fusion.694 fusion bf16[...]`). `table` sums the self time of every
op that started inside a whole program of the span by the path of its
instruction: the serving programs are the ones `paired.whole_programs`
returns, each with the `engine.dispatch` record whose `program_key` names
the bucket that ran; the trainer's are the `jit__step(` module events.

COVERAGE is the share of that self time whose instruction the program's
table knew. The table is made from a lowering after the run; it is the
executed program's because the same lowering under the same options is the
compile cache's entry the run wrote, and the coverage is the check: under
98% nothing is read.

Nothing raises and a parent commit is not failed: a program without
`program_scopes`, a record without `program_key`, an engine whose programs
are another process's, no trace, a pairing refused, each give None and a
line.
"""

from __future__ import annotations

import dataclasses
import functools
import re
import statistics
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from chipbench import paired, tracered

TRAIN = "train"                       # the kind of the trainer's program
TRAIN_STEP = re.compile(r"^jit__step\(")
MIN_COVERAGE = 0.98
ROLLUP_TOP = 20

# components of an op_name path that say how the code is nested, not whose
# it is. `transpose(jvp(...))` and `rematted_computation` are kept: they are
# the backward pass and the recomputed forward; so is a transform around a
# scope (`jvp(rtpu.loss)`)
_STRUCTURAL = re.compile(
    r"^(while|body|cond|closed_call|branch_\d+_fun|checkpoint|pjit|"
    r"(vmap|jvp)\(((?!rtpu\.).)*\)|custom_[jv][vj]p_call\w*|core_call|"
    r".*<(locals|lambda)>.*)$")
_JIT = re.compile(r"^jit\((\w+)\)$")


def _library_jit(component: str) -> bool:
    """`jit(_where)`, `jit(floor_divide)`, `jit(silu)`: a function of
    jax's own library that is jitted where it is defined, not a wrapper of
    the program's (`jit(_moe_gmm)`, `jit(_sparse_prefill)`)."""
    m = _JIT.match(component)
    if m is None:
        return False
    import jax
    import jax.numpy as jnp

    name = m.group(1).lstrip("_")
    return (hasattr(jnp, name.split("_")[0]) or len(name) > 1 and any(
        hasattr(mod, name) for mod in (jax.nn, jax.lax, jax.random)))


Span = Tuple[int, int, Dict[str, str]]   # (start_ns, end_ns, its table)


@dataclasses.dataclass
class Table:
    """Self time of the ops inside `programs` whole programs of `device_ns`
    device time, by ((op_name path, opcode)); `unknown_ns` of it belongs to
    instructions the programs' tables did not hold."""
    programs: int
    device_ns: int
    by_scope: Dict[Tuple[str, str], int]
    unknown_ns: int

    @property
    def known_ns(self) -> int:
        return sum(self.by_scope.values())

    @property
    def coverage(self) -> float:
        total = self.known_ns + self.unknown_ns
        return self.known_ns / total if total else 0.0


def instruction(display_name: str) -> Tuple[str, str]:
    """(`fusion.694`, `fusion`) of `fusion.694 fusion bf16[...]`."""
    parts = display_name.split(" ", 2)
    return parts[0].lstrip("%"), parts[1] if len(parts) > 1 else ""


def _said_once(owners: List[str]) -> List[str]:
    """`A B A B C D C D` -> `A B C D`: XLA's inliner writes the call
    site's path in front of the callee's, which begins with it again."""
    n = len(owners) // 2
    while n:
        for i in range(len(owners) - 2 * n + 1):
            if owners[i:i + n] == owners[i + n:i + 2 * n]:
                del owners[i:i + n]
                n = len(owners) // 2 + 1
                break
        n -= 1
    return owners


@functools.lru_cache(maxsize=None)
def cut(path: str) -> str:
    """An op_name path without its primitive and its nesting, up to its
    last scope, inner jit or module: `jit(run_block)/while/body/closed_call/
    LlamaModel/layers/mlp/rtpu.moe.unsort/gather` -> `jit(run_block)/
    LlamaModel/layers/mlp/rtpu.moe.unsort`. "" stays "" (no scope: an
    instruction the compiler made)."""
    if not path:
        return ""
    owners = _said_once([c for c in path.split("/")[:-1]
                         if not _STRUCTURAL.match(c) and not _library_jit(c)])
    return "/".join(owners) or path


def _self_times(ctx) -> List[tracered.Event]:
    """Every op of the first chip with its self time, by start; once a run."""
    memo = ctx.setdefault("_scoped", {})
    if "ops" not in memo:
        trace = ctx["trace"].trace
        memo["ops"] = sorted(
            tracered.self_times(trace.ops.get(min(trace.modules), [])),
            key=lambda e: e[1])
    return memo["ops"]


def ops_inside(ctx, spans: Sequence[Span]):
    """(display name, start_ns, self_ns, path or None) of every op that
    started inside one of `spans` (sorted, disjoint); None: the span's
    table does not hold the op's instruction."""
    at = 0
    for name, start, own in _self_times(ctx):
        while at < len(spans) and spans[at][1] <= start:
            at += 1
        if at == len(spans):
            return
        if spans[at][0] <= start:
            yield name, start, own, spans[at][2].get(instruction(name)[0])


def _scopes_of(ctx, what: str) -> Optional[Callable[[str, Any], Any]]:
    """(kind, program_key) -> the program's table, through the runner's
    engine or trainer; None, with a line, where the program cannot say."""
    runner, log = ctx["runner"], ctx["log"]
    engine = getattr(runner, "engine", None)
    trainer = getattr(runner, "trainer", None)
    if getattr(engine, "program_scopes", None) is not None:
        return engine.program_scopes
    if getattr(trainer, "program_scopes", None) is not None:
        return lambda kind, key: trainer.program_scopes()
    log(f"scoped: this program has no program_scopes: {what} left out")
    return None


def spans_of(ctx, kind: str, what: str) -> Optional[List[Span]]:
    """The whole programs of `kind` in the traced span, each with its
    table; [] where the kind has none to give (skipped), None where it
    has programs and they cannot be read (a line says why). Once a run and
    kind: the metrics' sets of kinds overlap."""
    memo = ctx.setdefault("_scoped", {})
    if ("spans", kind) not in memo:
        memo["spans", kind] = _spans_of(ctx, kind, what)
    return memo["spans", kind]


def _spans_of(ctx, kind: str, what: str) -> Optional[List[Span]]:
    red, log = ctx["trace"], ctx["log"]
    if red is None:
        return None
    scopes_of = _scopes_of(ctx, what)
    if scopes_of is None:
        return None
    trace = red.trace
    lo, hi = trace.window
    if not trace.modules:
        log(f"scoped: the trace has no device plane: {what} left out")
        return None
    modules = trace.modules[min(trace.modules)]
    if kind == TRAIN:
        events = [(s, s + d, None) for name, s, d in modules
                  if TRAIN_STEP.search(name) and s >= lo and s + d <= hi]
        if events:
            # the profiler's session cuts the step it starts in and the
            # one it ends in, and gives what it saw of them: a whole step
            # is one program of one shape, as long as the median
            mid = statistics.median(end - s for s, end, _ in events)
            events = [e for e in events if e[1] - e[0] >= 0.9 * mid]
    elif kind not in paired.PROGRAMS or not any(
            paired.PROGRAMS[kind].search(name) for name, _, _ in modules):
        # a kind this process pairs no programs of, or ran none of
        return []
    else:
        whole = paired.whole_programs(ctx, kind, what)
        if whole is None:
            return []
        if any(r.get("program_key") is None for _, r in whole):
            log(f"scoped: engine.dispatch records carry no program_key: "
                f"{what} left out")
            return None
        events = [(e[1], e[1] + e[2], tuple(r["program_key"]))
                  for e, r in whole]
    spans, took = [], {}
    for start, end, key in sorted(events, key=lambda e: e[0]):
        t0 = time.perf_counter()
        try:
            table = scopes_of(kind, key)
        except Exception as e:  # noqa: BLE001 — a reader fails no run
            log(f"scoped: program_scopes({kind!r}, {key}) raised {e!r}: "
                f"{what} left out")
            return None
        if table is None:
            log(f"scoped: the program answers no table for {kind} {key} "
                f"(its programs are another process's): {what} left out")
            return None
        took.setdefault(key, time.perf_counter() - t0)
        spans.append((start, end, table))
    if took and max(took.values()) > 0.01:
        # a key's first call lowers, fetches and parses; after the window
        log(f"scoped: program_scopes of {len(took)} {kind} programs took "
            f"{sum(took.values()):.2f} s, the slowest "
            f"{max(took.values()):.2f} s")
    return spans


def table(ctx, kinds: Sequence[str]) -> Optional[Table]:
    """The self time by scope inside the whole programs of `kinds` in the
    traced span (once a run and set of kinds), or None with a line."""
    memo = ctx.setdefault("_scoped", {})
    kinds = tuple(kinds)
    if kinds not in memo:
        memo[kinds] = _table(ctx, kinds)
        if memo[kinds] is not None:
            for line in rollup_lines(memo[kinds], "+".join(kinds)):
                ctx["log"](line)
    return memo[kinds]


def _table(ctx, kinds: Tuple[str, ...]) -> Optional[Table]:
    what = f"time by scope in {'+'.join(kinds)} programs"
    if ctx["trace"] is None:
        return None
    spans: List[Span] = []
    for kind in kinds:
        got = spans_of(ctx, kind, what)
        if got is None:
            return None
        spans += got
    if not spans:
        ctx["log"](f"scoped: no whole program of {kinds} in the span: "
                   f"{what} left out")
        return None
    spans.sort(key=lambda s: s[0])
    out = Table(programs=len(spans),
                device_ns=sum(end - start for start, end, _ in spans),
                by_scope={}, unknown_ns=0)
    for name, _, own, path in ops_inside(ctx, spans):
        if path is None:
            out.unknown_ns += own
        else:
            key = (path, instruction(name)[1])
            out.by_scope[key] = out.by_scope.get(key, 0) + own
    if out.coverage < MIN_COVERAGE:
        ctx["log"](f"scoped: the programs' tables know the instructions of "
                   f"{100 * out.coverage:.1f}% of the ops' self time in "
                   f"{out.programs} programs (under "
                   f"{100 * MIN_COVERAGE:.0f}%): {what} left out")
        return None
    return out


def matching_ns(t: Table, scope: str, not_op: Optional[str] = None) -> int:
    """Self time of the ops whose path matches the regex `scope`, less
    those whose opcode is `not_op`."""
    rx = re.compile(scope)
    return sum(ns for (path, op), ns in t.by_scope.items()
               if rx.search(path) and op != not_op)


def rollup(by_scope: Dict[Tuple[str, str], int]) -> List[Tuple[str, int]]:
    """[(cut path, self ns)], the largest first; a Pallas kernel's time
    apart from the rest of its path's (`... [pallas]`), instructions the
    compiler made under `(no scope)`."""
    out: Dict[str, int] = {}
    for (path, op), ns in by_scope.items():
        key = cut(path) or "(no scope)"
        if op == "pallas":
            key += " [pallas]"
        out[key] = out.get(key, 0) + ns
    return sorted(out.items(), key=lambda kv: -kv[1])


def rollup_lines(t: Table, what: str) -> List[str]:
    """The table a builder made by hand: ms a program and share of the
    programs' device time, the top paths."""
    n, dev = max(t.programs, 1), max(t.device_ns, 1)
    ops_ns = t.known_ns + t.unknown_ns
    lines = [f"scoped {what}: {t.programs} whole programs, "
             f"{t.device_ns / n / 1e6:.3f} ms a program on the device; ops' "
             f"self time {100 * ops_ns / dev:.1f}% of it (the rest: no op "
             f"ran), coverage {100 * t.coverage:.2f}%"]
    rows = rollup(t.by_scope)
    for key, ns in rows[:ROLLUP_TOP]:
        lines.append(f"scoped {what}: {ns / n / 1e6:9.4f} ms "
                     f"{100 * ns / dev:6.2f}%  {key}")
    rest = sum(ns for _, ns in rows[ROLLUP_TOP:]) + t.unknown_ns
    lines.append(f"scoped {what}: {rest / n / 1e6:9.4f} ms "
                 f"{100 * rest / dev:6.2f}%  ({len(rows[ROLLUP_TOP:])} more "
                 f"paths, and {t.unknown_ns / n / 1e6:.4f} ms of unknown "
                 f"instructions)")
    return lines
