"""chipbench/scoped.py and readers/scope_time_pct.py on a hand-made trace
and a fake table: the share across programs of different keys, what lies
outside a whole program, the coverage gate, every way a program cannot
answer (none raises), the roll-up's arithmetic, and the nine entries'
files."""

import json
import os
import types

import pytest

from chipbench import cell as cell_mod
from chipbench import paired, scoped
from chipbench import tracered as t

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000
NEW = {
    "head_sample_time_pct.tpot": "tpot_ms_p95",
    "moe_rest_time_pct.tpot": "tpot_ms_p95",
    "moe_rest_time_pct.ttft": "ttft_ms_p95",
    "sparse_prefill_time_pct.serve_tok_s": "serve_tok_s",
    "sparse_select_time_pct.serve_tok_s": "serve_tok_s",
    "lightning_time_pct.serve_tok_s": "serve_tok_s",
    "train_backward_time_pct.train_tok_s": "train_tok_s",
    "train_remat_time_pct.train_tok_s": "train_tok_s",
    "train_optimizer_time_pct.train_tok_s": "train_tok_s",
}
LOCKED = ("kimi-k2.5-longdoc", "mellum2-mixedctx", "gigachat3.5-reasoning")

# two buckets of one kind: the same instruction name under another scope
TABLES = {
    ("decode", (1, 8)): {
        "fusion.1": "jit(run_decode)/while/body/M/mlp/rtpu.moe.unsort/gather",
        "_moe_gmm.2": "jit(run_decode)/M/mlp/rtpu.moe.products/"
                      "jit(_moe_gmm)/pallas_call",
        "fusion.3": "jit(run_decode)/M/rtpu.head/lm_head/dot_general",
        "copy.4": ""},
    ("decode", (1, 16)): {
        "fusion.1": "jit(run_decode)/M/rtpu.head/lm_head/dot_general",
        "_moe_gmm.2": "jit(run_decode)/M/mlp/rtpu.moe.products/"
                      "jit(_moe_gmm)/pallas_call",
        "fusion.3": "jit(run_decode)/rtpu.sample/argmax",
        "copy.4": ""},
}


def _op(name, opcode, start_ms, ms):
    return (f"{name} {opcode} bf16[8,128]", int(start_ms * MS), int(ms * MS))


def _trace(ops, modules, window=(0, 100 * MS)):
    return t.reduce_trace(t.Trace(ops={0: ops}, modules={0: modules},
                                  host=[], window=window))


class _Engine:
    def __init__(self, tables=TABLES):
        self.tables, self.asked = tables, []

    def program_scopes(self, kind, key):
        self.asked.append((kind, tuple(key)))
        return self.tables[(kind, tuple(key))]


def _ctx(ops, programs, engine, monkeypatch, keys=True):
    """`paired.whole_programs` handed in: `programs` [(kind, start_ms, ms,
    key)] as whole programs with the records that ran them."""
    lines = []
    modules = [(f"jit_run_{k}(1)", int(s * MS), int(d * MS))
               for k, s, d, _ in programs]

    def whole(ctx, kind, what):
        got = [((k, int(s * MS), int(d * MS)),
                {"kind": k, **({"program_key": key} if keys else {})})
               for k, s, d, key in programs if k == kind]
        return got or None

    monkeypatch.setattr(paired, "whole_programs", whole)
    return {"trace": _trace(ops, modules), "log": lines.append,
            "lines": lines,
            "runner": types.SimpleNamespace(engine=engine)}


def _two_programs(monkeypatch, engine=None, **more):
    """A 10 ms program of key (1, 8) and a 30 ms one of key (1, 16), an op
    between them and one behind."""
    ops = [_op("fusion.1", "fusion", 10, 2),      # unsort, 2 ms
           _op("_moe_gmm.2", "pallas", 12, 4),    # the kernel, 4 ms
           _op("fusion.3", "fusion", 16, 1),      # head, 1 ms
           _op("copy.4", "copy", 17, 1),          # no scope
           _op("fusion.1", "fusion", 25, 5),      # between the programs
           _op("fusion.1", "fusion", 40, 6),      # head by ITS table, 6 ms
           _op("_moe_gmm.2", "pallas", 46, 9),
           _op("fusion.3", "fusion", 55, 3),      # sample, 3 ms
           _op("fusion.3", "fusion", 90, 3)]      # behind the last program
    programs = [("decode", 10, 10, (1, 8)), ("decode", 40, 30, (1, 16))]
    return _ctx(ops, programs, engine or _Engine(), monkeypatch, **more)


def _read(ctx, **params):
    return cell_mod.load_module("readers", "scope_time_pct").read(
        ctx, **params)


def test_a_share_is_summed_across_programs_of_different_keys(monkeypatch):
    ctx = _two_programs(monkeypatch)
    table = scoped.table(ctx, ["decode", "block"])   # block: not paired here
    assert table.programs == 2 and table.device_ns == 40 * MS
    assert table.unknown_ns == 0 and table.coverage == 1.0
    # head 1 + 6, sample 3, of 40 ms
    assert _read(ctx, kinds=["decode", "block"],
                 scope=r"rtpu\.head|rtpu\.sample") == pytest.approx(25.0)
    # the expert layer less its kernels: the un-sort's 2 ms
    assert _read(ctx, kinds=["decode", "block"], scope=r"rtpu\.moe\.",
                 not_op="pallas") == pytest.approx(5.0)
    assert _read(ctx, kinds=["decode", "block"],
                 scope=r"rtpu\.moe\.") == pytest.approx(100 * 15 / 40)
    # each key asked of the program, the table made once a run
    assert set(ctx["runner"].engine.asked) == set(TABLES)
    assert len([x for x in ctx["lines"] if "whole programs" in x]) == 1


def test_an_op_outside_any_whole_program_is_not_counted(monkeypatch):
    ctx = _two_programs(monkeypatch)
    table = scoped.table(ctx, ["decode"])
    # 8 ms in the first program, 18 in the second; the 5 ms op between
    # them and the 3 ms one behind belong to neither
    assert table.known_ns == 26 * MS
    assert sum(ns for (path, _), ns in table.by_scope.items()
               if path.endswith("rtpu.moe.unsort/gather")) == 2 * MS


def test_the_rollup_sums_to_the_programs_ops(monkeypatch):
    ctx = _two_programs(monkeypatch)
    table = scoped.table(ctx, ["decode"])
    rows = dict(scoped.rollup(table.by_scope))
    assert sum(rows.values()) == table.known_ns
    assert rows["jit(run_decode)/M/mlp/rtpu.moe.products/jit(_moe_gmm) "
                "[pallas]"] == 13 * MS
    assert rows["(no scope)"] == 1 * MS
    assert rows["jit(run_decode)/M/rtpu.head/lm_head"] == 7 * MS
    (head,) = [x for x in ctx["lines"] if "whole programs" in x]
    assert "2 whole programs, 20.000 ms a program" in head
    assert "65.0% of it" in head and "coverage 100.00%" in head
    assert any("6.5000 ms  32.50%" in x and "[pallas]" in x
               for x in ctx["lines"])


def test_coverage_under_98_percent_reads_nothing(monkeypatch):
    # the second program's table lacks the instruction of 3 ms of 100
    tables = {**TABLES, ("decode", (1, 16)): {
        k: v for k, v in TABLES[("decode", (1, 16))].items()
        if k != "fusion.3"}}
    ops = [_op("fusion.1", "fusion", 10, 47), _op("fusion.1", "fusion", 60,
                                                  50),
           _op("fusion.3", "fusion", 110, 3)]
    programs = [("decode", 10, 48, (1, 8)), ("decode", 60, 60, (1, 16))]
    ctx = _ctx(ops, programs, _Engine(tables), monkeypatch)
    assert scoped.table(ctx, ["decode"]) is None
    assert any("97.0%" in x and "left out" in x for x in ctx["lines"])
    assert _read(ctx, kinds=["decode"], scope="rtpu") is None
    # 1 ms of 100 unknown: read
    ops[-1] = _op("fusion.3", "fusion", 110, 1)
    ops[0] = _op("fusion.1", "fusion", 10, 49)
    ctx = _ctx(ops, programs, _Engine(tables), monkeypatch)
    assert scoped.table(ctx, ["decode"]).coverage == pytest.approx(0.99)


class _Raises:
    def program_scopes(self, kind, key):
        raise RuntimeError("no such program")


@pytest.mark.parametrize("case", [
    "no program_scopes", "no program_key", "no trace", "pp", "raises",
    "no whole program", "kind not paired"])
def test_a_program_that_cannot_answer_reads_nothing_and_does_not_raise(
        monkeypatch, case):
    engine = {"no program_scopes": types.SimpleNamespace(),
              "pp": types.SimpleNamespace(program_scopes=lambda k, key: None),
              "raises": _Raises()}.get(case, _Engine())
    ctx = _two_programs(monkeypatch, engine, keys=case != "no program_key")
    kinds = ["decode"]
    if case == "no trace":
        ctx["trace"] = None
    if case == "no whole program":
        kinds = ["prefill"]
    if case == "kind not paired":
        kinds = ["block"]      # runners/engine_diffusion.py adds it
        monkeypatch.delitem(paired.PROGRAMS, "block", raising=False)
    assert _read(ctx, kinds=kinds, scope="rtpu") is None
    if case != "no trace":
        assert len([x for x in ctx["lines"] if "left out" in x]) == 1


def test_the_trainers_programs_are_its_step_events_wholly_in_the_window():
    table = {"fusion.1": "jit(_step)/transpose(jvp(rtpu.loss))/M/"
                         "rematted_computation/layers/mlp/dot_general",
             "fusion.2": "jit(_step)/transpose(jvp(rtpu.loss))/M/layers/"
                         "mlp/dot_general",
             "fusion.3": "jit(_step)/rtpu.optimizer/mul",
             "fusion.4": "jit(_step)/jvp(rtpu.loss)/M/layers/mlp/dot_general"}
    ops, modules = [], []
    for i, start in enumerate((-5, 10, 30, 95)):   # the first and last: cut
        modules.append(("jit__step(7)", start * MS, 10 * MS))
        ops += [_op("fusion.4", "fusion", start, 3),
                _op("fusion.1", "fusion", start + 3, 2),
                _op("fusion.2", "fusion", start + 5, 4),
                _op("fusion.3", "fusion", start + 9, 1)]
    modules.append(("jit__init(1)", 50 * MS, 5 * MS))
    # what the profiler's session saw of the step it started in: inside the
    # window, and no whole program (its forward is missing)
    modules.append(("jit__step(7)", 0, 4 * MS))
    ops += [_op("fusion.2", "fusion", 0, 3), _op("fusion.3", "fusion", 3, 1)]
    lines = []
    trainer = types.SimpleNamespace(program_scopes=lambda: table)
    ctx = {"trace": _trace(ops, modules), "log": lines.append,
           "runner": types.SimpleNamespace(trainer=trainer)}
    got = scoped.table(ctx, ["train"])
    assert got.programs == 2 and got.device_ns == 20 * MS
    assert _read(ctx, kinds=["train"],
                 scope=r"transpose\(jvp") == pytest.approx(60.0)
    assert _read(ctx, kinds=["train"],
                 scope="rematted_computation") == pytest.approx(20.0)
    assert _read(ctx, kinds=["train"],
                 scope=r"rtpu\.optimizer") == pytest.approx(10.0)
    # a trainer that took no step yet answers None
    ctx = {**ctx, "_scoped": {}, "runner": types.SimpleNamespace(
        trainer=types.SimpleNamespace(program_scopes=lambda: None))}
    assert _read(ctx, kinds=["train"], scope="rtpu") is None


@pytest.mark.parametrize("path, cut", [
    ("jit(run_block)/while/body/closed_call/LlamaModel/layers/mlp/"
     "rtpu.moe.unsort/gather",
     "jit(run_block)/LlamaModel/layers/mlp/rtpu.moe.unsort"),
    ("jit(_step)/jvp(rtpu.loss)/M/rtpu.head/lm_head/dot_general",
     "jit(_step)/jvp(rtpu.loss)/M/rtpu.head/lm_head"),
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "moe_unsort/gather",
     "jit(f)/transpose(jvp(jvp()))/rematted_computation/moe_unsort"),
    ("jit(run_decode)/M/attn/rtpu.attn.cache_write/jit(_where)/select_n",
     "jit(run_decode)/M/attn/rtpu.attn.cache_write"),
    ("jit(run_prefill)/M/SparseLayer/jit(_sparse_prefill)/jit(clip)/max",
     "jit(run_prefill)/M/SparseLayer/jit(_sparse_prefill)"),
    # an inlined call: the call site's path in front of the callee's
    ("jit(run_block)/LlamaModel/while/body/closed_call/jit(run_block)/"
     "LlamaModel/while/body/closed_call/layers/layer/moe/moe._dropless/"
     "rtpu.moe.products/jit(_moe_gmm)/layers/layer/moe/moe._dropless/"
     "rtpu.moe.products/jit(_moe_gmm)/jit(searchsorted)/jit(searchsorted)/"
     "vmap()/while/body/closed_call/gather",
     "jit(run_block)/LlamaModel/layers/layer/moe/moe._dropless/"
     "rtpu.moe.products/jit(_moe_gmm)"),
    ("gather", "gather"), ("", "")])
def test_a_path_is_cut_at_its_last_scope_jit_or_module(path, cut):
    assert scoped.cut(path) == cut


def test_an_instruction_is_the_display_names_first_word():
    assert scoped.instruction("fusion.694 fusion bf16[16,2048]") == (
        "fusion.694", "fusion")
    assert scoped.instruction("_moe_gmm.26 pallas bf16[4096,1536]") == (
        "_moe_gmm.26", "pallas")
    assert scoped.instruction("odd") == ("odd", "")


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_entry_lists_its_cells_and_finds_its_reader(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["moves"] == NEW[name] and entry["better"] == "lower"
    assert entry["unit"] == "%" and entry["source"] == "device_trace"
    assert entry["workloads"] and not set(entry["workloads"]) & set(LOCKED)
    # the last nine entries: nothing put first or in the middle
    assert name in [m["name"] for m in bench["per_layer"][-9:]]
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
    # every cell reports the end-to-end metric the entry moves
    moved = next(m for m in bench["end_to_end"] if m["name"] == NEW[name])
    for cell in entry["workloads"]:
        assert cell in moved.get("workloads", cells)
        loaded = cell_mod.load_cell(cell)
        (metric,) = [m for m in loaded.per_layer if m.name == name]
        assert metric.spec["reader"] == "scope_time_pct"
        assert metric.spec["layer"] == entry["layer"]
        assert "ONE op_name" in metric.spec["fragile"]
        assert callable(cell_mod.load_module(
            "readers", metric.spec["reader"]).read)
