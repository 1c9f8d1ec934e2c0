"""The needed work of a MiniCPM-SALA-family model's mixers, decode step and
prefill pass, by hand at the configuration's widths; the readers built on
it, on a hand-made trace with records made to fit it; the new files."""

import os
import time
import types

import numpy as np
import pytest

from chipbench import cell as cell_mod
from chipbench import sala_work as w
from chipbench import tracered as t
from ray_tpu.util import tracing

MS = 1_000_000
PUB = cell_mod.load_json(os.path.join(
    cell_mod.HERE, "configs", "minicpm-sala-serve.json"))
SC = PUB["sparse_config"]
PEAKS = cell_mod.load_peaks("TPU v5 lite")
STATE_ROW = 12 * 32 * 128 * 128 * 4
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# ------------------------------------------------------------- the counts
def test_the_cut_is_twelve_lightning_and_four_sparse_layers():
    assert w.layer_counts(PUB) == {w.LIGHTNING: 12, w.SPARSE: 4}
    per = w.layer_params(PUB)
    # the issue's arithmetic: 285.2 M and 253.8 M a layer
    assert round(per[w.LIGHTNING] / 1e6, 1) == 285.2
    assert round(per[w.SPARSE] / 1e6, 1) == 253.8
    # a step reads the layers and the head once: 10.08 GB less the
    # embedding table's 0.60 GB
    assert round(w.decode_weight_bytes(PUB) / 1e9, 2) == 9.48
    assert w.lightning_state_bytes_row(PUB) == STATE_ROW == 25_165_824
    assert w.kv_bytes_token(PUB) == 512


@pytest.mark.parametrize("t_pos, keys, kernels", [
    (0, 1, 0), (8191, 8192, 0),           # dense under dense_len
    # first block + 33 window blocks (6144..8192) + 64 of the 95 others,
    # the own block holding one key
    (8192, 97 * 64 + 1, 511),
    # 20,000 = 312 * 64 + 32: window from block 280, own block 33 keys
    (20000, 97 * 64 + 33, 1249),
    (40959, 96 * 64 + 64, 2559),     # the window on a block edge: 32
])
def test_keys_attended_and_kernels_scored_follow_the_rule(t_pos, keys,
                                                          kernels):
    assert int(w.keys_attended(t_pos, SC)) == keys
    assert int(w.kernels_scored(t_pos, SC)) == kernels


def test_a_window_that_starts_on_a_block_edge_has_one_block_fewer():
    # t = 8255: positions 6208..8255 are blocks 97..128: 32, not 33
    assert int(w.keys_attended(8255, SC)) == (1 + 32 + 64) * 64
    few = dict(SC, dense_len=0)
    # under 64 other blocks all of them join: everything is attended
    assert int(w.keys_attended(3000, few)) == 3001


def test_a_decode_step_moves_weights_live_state_and_selected_keys():
    none = w.decode_step_bytes(PUB, [])
    assert none == w.decode_weight_bytes(PUB)
    rows = [20000] * 16
    step = w.decode_step_bytes(PUB, rows)
    selected = 16 * 8 * (97 * 64 + 33) * 512      # 4 layers x 2 kv heads
    scored = 16 * 8 * 1249 * 256
    assert step == none + 2 * 16 * STATE_ROW + selected + scored
    assert w.sparse_decode_bytes(PUB, rows) == selected
    # 9.48 + 0.81 + 0.41 + 0.04 GB: 13.1 ms at 819 GB/s
    assert step / PEAKS["hbm_bytes_per_s"] == pytest.approx(13.1e-3,
                                                            rel=0.01)
    # dense layers would read 16 x 8 x 20001 x 512 B = 1.31 GB instead
    assert selected / (16 * 8 * 20001 * 512) == pytest.approx(0.312,
                                                              abs=0.001)


def test_a_pass_needs_its_tokens_matmuls_pairs_and_one_head():
    t_pos = np.arange(8192, 12288)
    ops = w.pass_ops(t_pos, False, PUB)
    per = w.layer_params(PUB)
    matmul = 2 * 4096 * (12 * per[w.LIGHTNING] + 4 * per[w.SPARSE])
    state = 4 * 128 * 128 * 32 * 12 * 4096
    pairs = int(w.keys_attended(t_pos, SC).sum())
    attn = 4 * pairs * 4 * 128 * 32
    assert ops == matmul + state + attn
    assert w.sparse_prefill_ops(t_pos, PUB) == attn
    assert w.pass_ops(t_pos, True, PUB) - ops == 2 * 4096 * 73448
    # 36.3 TFLOP of matmuls, 0.1 of state, 1.7 of pairs: 0.19 s at peak
    assert ops / PEAKS["bf16_flops_per_s"] == pytest.approx(0.194, rel=0.02)
    assert w.lightning_prefill_bytes(4096 * 12, PUB) == 4096 * 12 * 40960


# ---------------------------------------------------------------- readers
@pytest.fixture
def empty_ring():
    tracing.reset_ring()
    yield
    tracing.reset_ring()


def _ctx(trace):
    lines = []
    cell = types.SimpleNamespace(config=PUB)
    return {"runner": types.SimpleNamespace(t0=time.monotonic() - 60.0),
            "seconds": 50.0, "log": lines.append, "lines": lines,
            "trace": t.reduce_trace(trace), "peaks": PEAKS, "cell": cell}


KINDS = ["decode", "prefill", "decode", "prefill", "decode", "decode"]
DECODE_ROWS = (("c", 1, 9001), ("d", 1, 20001), ("e", 1, 30001))


def _made_trace():
    """Six steps of 400 ms: a resumed prefill pass in steps 1 and 3 (300
    ms), a decode program (16 ms, its paged-decode kernel 1 ms in three
    calls) in every other."""
    step, ops, modules, host = 400 * MS, [], [], []
    for i, kind in enumerate(KINDS):
        s = (i + 1) * step
        host.append(("chipbench.engine.step", s - 2 * MS, 1 * MS))
        if kind == "prefill":
            modules.append(("jit_run_prefill(123)", s, 300 * MS))
            ops.append(("while.3 while (s32[])", s, 290 * MS))
        else:
            modules.append(("jit_run_decode(456)", s, 16 * MS))
            ops.append(("fusion.9 fusion bf16[16,32768]", s, 12 * MS))
            ops.append(("_decode_call.14 pallas bf16[32,1,16,128]",
                        s + 12 * MS, 400_000))
            ops.append(("_decode_call.15 pallas bf16[32,1,16,128]",
                        s + 13 * MS, 400_000))
            ops.append(("_decode_call pallas bf16[32,1,16,128]",
                        s + 14 * MS, 200_000))
    return t.Trace(ops={0: ops}, modules={0: modules}, host=host,
                   window=(200 * MS, 8 * step))


def _records(trace, sala=True):
    offset = time.time_ns() - 30 * 10**9
    for i, (_, s, d) in enumerate(trace.host):
        tracing.record("engine.step", (
            i, s + offset, s + d + offset, 0, 0, 0, 0, 0, 0, 3, 0))
    for i, ((_, s, d), kind) in enumerate(zip(trace.modules[0], KINDS)):
        if kind == "prefill":
            rows = (("a", 4096, 12288),)
            tail = (0, 0, (2,), (False,))
        else:
            rows = DECODE_ROWS
            read = 8 * int(w.keys_attended([c - 1 for _, _, c in rows],
                                           SC).sum())
            tail = (read, 0, None, None)
        fields = ((None,) * 5 + (12, STATE_ROW, 4) + tail) if sala else ()
        tracing.record("engine.dispatch", (
            i, kind, i, i + 1, s - 3 * MS + offset, s + offset,
            s + d + offset + 200_000, 16, 16, rows, 1) + fields)


def _reader(name):
    return cell_mod.load_module("readers", name).read


def test_decode_readers_count_live_rows_and_selected_keys(empty_ring):
    trace = _made_trace()
    ctx = _ctx(trace)
    pattern = cell_mod.load_json(os.path.join(
        cell_mod.HERE, "layer_metrics",
        "sparse_decode_roofline.serve_tok_s.json"))["params"]["op_pattern"]
    assert _reader("sparse_decode_roofline")(ctx, pattern) is None
    _records(trace)
    positions = [c - 1 for _, _, c in DECODE_ROWS]
    got = _reader("sala_decode_bytes_roofline")(ctx)
    step = w.decode_step_bytes(PUB, positions)
    assert got == pytest.approx(100 * (step / 819e9) / 16e-3)
    assert 70 < got < 80
    assert any("4 decode programs paired" in x and "3.0 live rows" in x
               for x in ctx["lines"])
    got = _reader("sparse_decode_roofline")(ctx, pattern)
    least = w.sparse_decode_bytes(PUB, positions) / 819e9
    assert got == pytest.approx(100 * least / 1e-3)
    assert 0 < got < 100


def test_prefill_pass_roofline_is_real_work_over_the_programs_time(
        empty_ring):
    trace = _made_trace()
    ctx = _ctx(trace)
    _records(trace)
    got = _reader("sala_prefill_pass_roofline")(ctx)
    least = w.pass_ops(np.arange(8192, 12288), False, PUB) / 197e12
    assert got == pytest.approx(100 * least / 0.3)
    assert 60 < got < 70
    assert any("2 prefill programs paired" in x and "2 resumed rows" in x
               for x in ctx["lines"])


def test_sparse_kept_pct_is_keys_read_over_the_rows_contexts(empty_ring):
    trace = _made_trace()
    ctx = _ctx(trace)
    read = _reader("sparse_kept_pct")
    assert read(ctx) is None
    _records(trace)
    # the records' dispatch_ns lie 30 s back: inside a window that began
    # 60 s back and lasts 50 s
    keys = int(w.keys_attended([9000, 20000, 30000], SC).sum())
    assert read(ctx) == pytest.approx(100 * keys / (9001 + 20001 + 30001))
    assert 25 < read(ctx) < 40


def test_records_of_another_model_read_nothing(empty_ring):
    trace = _made_trace()
    ctx = _ctx(trace)
    _records(trace, sala=False)
    assert _reader("sala_decode_bytes_roofline")(ctx) is None
    assert _reader("sparse_decode_roofline")(ctx, "^_decode_call") is None
    assert _reader("sala_prefill_pass_roofline")(ctx) is None
    assert _reader("sparse_kept_pct")(ctx) is None
    assert any("no decode record carries" in x for x in ctx["lines"])


# -------------------------------------------------------------- new files
def test_the_configuration_holds_the_catalogs_keys_and_cuts_depth_alone():
    import json

    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(CATALOG)
               if '"MiniCPM-SALA"' in line)
    assert PUB["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if PUB.get(k) != v}
    assert differ == {"num_hidden_layers"} == set(PUB["reduced"])
    assert (PUB["hidden_size"], PUB["intermediate_size"], PUB["vocab_size"],
            PUB["num_attention_heads"], PUB["num_key_value_heads"],
            PUB["head_dim"], PUB["lightning_nh"],
            PUB["lightning_head_dim"]) == (4096, 16384, 73448, 32, 2, 128,
                                           32, 128)
    assert PUB["kept_layers"] == list(range(9, 25))
    assert PUB["num_hidden_layers"] == len(PUB["kept_layers"]) == 16
    assert [i for i in PUB["kept_layers"]
            if PUB["mixer_types"][i] == "minicpm4"] == [9, 16, 17, 22]
    for key in ("mup", "lightning_decay", "lightning_layer", "sparse_config",
                "sparse_rule", "departures"):
        assert key in PUB["assumed"], key
    e = PUB["engine"]
    assert e["page_size"] == SC["block_size"] == 64
    assert e["num_pages"] * e["page_size"] == e["max_batch"] \
        * e["max_model_len"]
    assert max(e["prefill_buckets"]) == 4096


def test_the_mix_is_the_issues_to_the_letter():
    mix = cell_mod.load_json(os.path.join(
        cell_mod.HERE, "traffic", "longdoc-sala.json"))
    assert mix["arrivals"] == {"process": "backlog", "max_rate_per_s": 2.0}
    assert (mix["block"], mix["ramp_s"], mix["grace_s"]) == (16, 30, 0)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 16384,
                                 "sigma": 0.6, "min": 8192, "max": 40960}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    lens = mix["check"]["prompt_lens"]
    assert sum(n < SC["dense_len"] for n in lens) == 1 and len(lens) == 3
    ep = mix["check"]["engine_prompts"]
    assert (ep["count"], ep["decode_tokens"]) == (8, 16)
    assert 8192 < ep["min_len"] <= ep["max_len"] <= 12000
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] + 1 \
        <= PUB["engine"]["max_model_len"]
    cell = cell_mod.load_cell("minicpm-sala-longdoc")
    assert {m.name for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    assert {m.name for m in cell.per_layer} >= {
        "sparse_kept_pct.serve_tok_s", "sparse_decode_roofline.serve_tok_s",
        "decode_bytes_roofline.serve_tok_s",
        "prefill_pass_roofline.serve_tok_s", "kv_pages_peak_pct.serve_tok_s",
        "prefill_dispatch_ms.serve_tok_s", "device_idle_pct.serve_tok_s",
        "prefill_pad_pct.serve_tok_s"}


def test_the_runner_names_the_programs_keys_and_refuses_an_older_program(
        monkeypatch):
    import importlib.util

    from chipbench.runners import engine_sala

    pub = {k: PUB[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "rope_theta", "rms_norm_eps") + engine_sala.FAMILY_KEYS}
    over = engine_sala.model_overrides(pub)
    from ray_tpu.models import minicpm_sala

    cfg = minicpm_sala.get_config("minicpm-sala", **over)
    assert cfg.layers == tuple(range(9, 25))
    assert (cfg.n_lightning_layers, cfg.n_sparse_layers) == (12, 4)
    assert cfg.sparse == minicpm_sala.SparseParams(32, 16, 64, 1, 2048, 64,
                                                   8192)
    assert cfg.num_params() * 2 == w.decode_weight_bytes(PUB) \
        + 2 * 73448 * 4096
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(cell_mod.BenchError, match="minicpm_sala.py"):
        engine_sala._require_sala_program()


def test_a_runs_leaves_are_made_a_layer_at_a_time_from_the_seed():
    import jax
    import jax.numpy as jnp

    from chipbench import weights_sala

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    tree = {"embed": sds(64, 32), "lm_head": sds(32, 64),
            "final_norm": {"scale": sds(32)},
            "run_0": {"qkvg_proj": {"kernel": sds(3, 32, 128)},
                      "q_norm": {"scale": sds(3, 8)}}}
    a, b = (weights_sala.make_params(tree, s) for s in (7, 2**31 + 5))
    assert abs(float(a["run_0"]["qkvg_proj"]["kernel"].std())
               - 32 ** -0.5) < 0.02
    assert abs(float(a["lm_head"].std()) - 32 ** -0.5) < 0.02
    assert abs(float(a["embed"].std()) - 0.02) < 0.005
    scale = np.asarray(a["run_0"]["q_norm"]["scale"])
    assert abs(scale.mean() - 1) < 0.2 and scale.std() > 0.02
    layers = np.asarray(a["run_0"]["qkvg_proj"]["kernel"])
    assert not np.array_equal(layers[0], layers[1])
    assert not np.array_equal(layers, b["run_0"]["qkvg_proj"]["kernel"])
