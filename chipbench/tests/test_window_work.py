"""The cell `mellum2-mixedctx`: its work counts against brute force and a
hand count, its files against the contract and the catalog, its readers on
hand-made records (positions by name), and its two controls refused at a
tiny size."""

import json
import os

import pytest

from chipbench import cell as cell_mod
from chipbench import generator, paired, window_work
from chipbench.cell import HERE, load_json

CELL = "mellum2-mixedctx"
BENCH = cell_mod.load_benchmark()
NEW = ("window_decode_roofline.serve_tok_s",
       "window_flash_roofline.serve_tok_s",
       "window_attn_time_pct.serve_tok_s", "kv_window_kept_pct.serve_tok_s",
       "window_decode_bytes_roofline.serve_tok_s",
       "window_prefill_pass_roofline.serve_tok_s",
       "window_moe_gmm_roofline.serve_tok_s",
       "full_decode_roofline.serve_tok_s", "full_flash_roofline.serve_tok_s")
JOINED = ("kv_pages_peak_pct.serve_tok_s", "prefill_dispatch_ms.serve_tok_s",
          "device_idle_pct.serve_tok_s", "prefill_pad_pct.serve_tok_s")


@pytest.fixture(scope="module")
def pub():
    return cell_mod.load_cell(CELL).config


def test_the_cut_is_the_issues_arithmetic(pub):
    attn = 2304 * 128 * (32 + 2 * 4) + 32 * 128 * 2304
    assert window_work.attn_params(pub) == attn == 21_233_664
    assert window_work.layer_counts(pub) == {"sliding_attention": 6,
                                             "full_attention": 2}
    per_token = 8 * (attn + 2304 * 64)
    assert window_work.token_params(pub) == per_token
    held = per_token + 8 * 64 * 3 * 2304 * 896 + 2 * 98304 * 2304
    assert round(held / 1e9, 3) == 3.795
    assert window_work.kv_bytes_token(pub) == 2048      # 2 KB a layer
    assert window_work.decode_weight_bytes(pub) == 2 * (per_token
                                                        + 2304 * 98304)


@pytest.mark.parametrize("q, end, window", [
    (1, 1, 1024), (383, 383, 1024), (4096, 4096, 1024), (4096, 12288, 1024),
    (700, 1100, 1024), (256, 1024, 1024), (256, 1025, 1024), (37, 90, 32),
    (5, 5, 1), (300, 2000, 200)])
def test_pairs_inside_the_band_against_brute_force(q, end, window):
    want_w = want_f = 0
    for p in range(end - q, end):
        want_f += p + 1
        want_w += sum(1 for j in range(p + 1) if j > p - window)
    assert window_work.window_pairs(q, end, window) == want_w
    assert window_work.full_pairs(q, end) == want_f
    assert window_work.window_pairs(q, end, 10 ** 9) == want_f


@pytest.mark.parametrize("ctx, k, window", [
    (1, 1, 1024), (700, 1, 1024), (1020, 8, 1024), (9000, 4, 1024),
    (30, 5, 32)])
def test_keys_a_decode_reads_against_brute_force(ctx, k, window):
    assert window_work.window_keys(ctx, k, window) == sum(
        min(ctx + j, window) for j in range(k))


def test_the_windowed_decode_kernel_reads_a_window_whatever_the_context(pub):
    short, long_ = (window_work.window_decode_kernel(c, 1, pub)
                    for c in (500, 30000))
    assert short["bytes"] == 6 * (2048 * 500 + 2 * 32 * 128 * 2)
    assert long_["bytes"] == 6 * (2048 * 1024 + 2 * 32 * 128 * 2)
    assert long_["ops"] == 6 * 32 * 4 * 128 * 1024
    # 8 operations a byte (8 query heads a kv head): far under the
    # chip's 240, memory-bound
    assert 7 < long_["ops"] / long_["bytes"] < 9
    assert window_work.window_decode_kernel(30000, 3, pub)["ops"] == \
        3 * long_["ops"]


def test_a_pass_counts_its_tokens_its_assignments_and_each_kinds_pairs(pub):
    got = window_work.pass_ops(4096, 12288, 4096 * 8 * 8, pub)
    attn = 32 * 4 * 128 * (
        2 * window_work.full_pairs(4096, 12288)
        + 6 * window_work.window_pairs(4096, 12288, 1024))
    assert got == (2.0 * window_work.token_params(pub) * 4096
                   + 2.0 * 3 * 2304 * 896 * 4096 * 64 + attn)
    # behind 8192 tokens a sliding layer makes a tenth of a full one's
    assert (window_work.window_pairs(4096, 12288, 1024) * 9
            < window_work.full_pairs(4096, 12288))
    # the issue's arithmetic: 1.13 GFLOP of weights a token, and at the
    # mix's lengths attention is a fifth to a quarter of a token's work
    weights = (2.0 * window_work.token_params(pub)
               + 2.0 * 3 * 2304 * 896 * 64)
    assert round(weights / 1e9, 2) == 1.13
    assert window_work.window_flash_ops([("a", 4096, 12288)], pub) == \
        32 * 4 * 128 * 6 * window_work.window_pairs(4096, 12288, 1024)


def test_a_decode_step_reads_weights_touched_experts_and_both_kinds(pub):
    got = window_work.decode_step_bytes(pub, [500, 30000], 300)
    assert got == (window_work.decode_weight_bytes(pub)
                   + 2 * 3.0 * 2304 * 896 * 300
                   + 2048 * (2 * 30500 + 6 * (500 + 1024)))
    # 64 rows of the mix's mean context, all 512 experts touched: 7.6 GB
    # of weights, 1.9 GB of full-layer keys, 0.8 of windows (the issue's)
    step = window_work.decode_step_bytes(pub, [7200] * 64, 512)
    assert 9.5e9 < step < 11e9


def test_configuration_keeps_the_catalogs_keys_and_cuts_what_it_says(pub):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if '"name": "Mellum2-12B-A2.5B-Instruct"' in line)
    differ = {k for k, v in row["config"].items() if pub.get(k) != v}
    assert differ == {"num_hidden_layers"} == set(pub["reduced"])
    assert pub["published"] == {"num_hidden_layers": 28}
    assert pub["num_hidden_layers"] == 8 and pub["held"] == {"layers": [0, 8]}
    assert pub["source"] == row["source_url"]
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "mellum2-12b-a2.5b-serve")
    assert entry["reduced"] == pub["reduced"]
    assert entry["source"] == pub["source"]
    assert entry["file"] == "chipbench/configs/mellum2-12b-a2.5b-serve.json"
    for key in ("equations", "attention", "no_qk_norm", "experts",
                "no_mtp_head", "text", "yarn", "cache", "weights"):
        assert pub["assumed"][key], key
    assert "stages of 8, 8, 8 and 4" in pub["stands_for"]
    eng = pub["engine"]
    assert eng["max_batch"] == 64 and eng["max_model_len"] == 33792
    assert eng["max_model_len"] % eng["page_size"] == 0
    assert pub["sliding_window"] % eng["page_size"] == 0
    assert eng["num_pages"] * eng["page_size"] >= 600_000
    assert eng["prefill_buckets"] == [256, 512, 1024, 2048, 4096]
    for name in ("runner", "reference"):
        assert os.path.isfile(os.path.join(HERE, name + "s",
                                           pub[name] + ".py"))
    assert pub["limits_why"] and len(pub["limits"]) == 4


def test_the_configuration_is_red_in_the_one_case_every_other_vocabulary_is():
    """`tests/test_files.py`'s rules hold for the new configuration but
    the one PERF.md section 7 records: that test asserts Mistral's widths
    of every configuration. Still exactly one red case, the same one."""
    from chipbench.tests import test_files

    reds = []
    for name in dir(test_files):
        if not name.startswith("test_"):
            continue
        fn = getattr(test_files, name)
        if getattr(fn, "pytestmark", None) or fn.__code__.co_argcount:
            continue                # parametrised or with fixtures
        try:
            fn()
        except AssertionError:
            reds.append(name)
    assert reds == [
        "test_configs_are_files_with_every_reduced_key_and_no_width_cut"]


def test_the_mix_and_the_cell_are_the_issues():
    cell = cell_mod.load_cell(CELL)
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry == BENCH["workloads"][-1]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "mellum2-12b-a2.5b-serve", "mixedctx-mellum", 1)
    assert len(entry["why"]) <= 200
    mix = cell.traffic
    assert mix["arrivals"]["process"] == "backlog" and mix["block"] == 32
    assert (mix["ramp_s"], mix["grace_s"]) == (30, 0)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                 "sigma": 1.1, "min": 256, "max": 32768}
    assert mix["output_len"] == {"dist": "uniform", "min": 128, "max": 512}
    assert mix["check"]["prompt_lens"] == [700, 3000, 9000, 20000]
    assert mix["trace"] == {"start_share": 0.5, "seconds": 6.0}
    # the cycle: 383 ... 32768, mean 6836; 3 inside one window, 8 past
    # YaRN's original 8192 holding 63% of the tokens, 69 passes of 4096
    prompt, output, _ = generator._base_cycle(
        dict(mix, arrivals={"process": "uniform"}), 32, 1.0)
    lens = sorted(int(n) for n in prompt)
    assert lens[:4] == [383, 648, 861, 1059]
    assert lens[-3:] == [19484, 25882, 32768]
    assert sum(lens) == 218737
    assert sum(n <= 1024 for n in lens) == 3
    assert sum(n > 8192 for n in lens) == 8
    assert sum(-(-n // 4096) for n in lens) == 69
    assert 128 <= min(output) and max(output) <= 512
    assert max(lens) + max(output) <= cell.config["engine"]["max_model_len"]
    # deep enough never to run dry: 1.25 x the 264 requests the change
    # admits over the ramp and the window (my chip runs, PR 47)
    assert mix["arrivals"]["max_rate_per_s"] * (30 + 50) >= 1.25 * 264
    assert "serve_tok_s" in {m.name for m in cell.end_to_end}
    assert {m.name for m in cell.per_layer} == set(NEW) | set(JOINED)


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_lists_this_cell_only_and_finds_its_reader(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "serve_tok_s"
    spec = load_json(os.path.join(HERE, "layer_metrics", name + ".json"))
    assert os.path.isfile(os.path.join(HERE, "readers",
                                       spec["reader"] + ".py"))
    for key in ("layer", "moves", "unit", "source"):
        assert spec[key] == entry[key], key
    assert entry["unit"] == "%"


def test_the_other_families_kernel_metrics_do_not_list_the_cell():
    for m in BENCH["per_layer"]:
        if m["name"].startswith(("moe_gmm", "moe_experts", "paged_decode",
                                 "expert_", "flash_fwd", "sparse_", "mla_",
                                 "prefill_pass_roofline",
                                 "decode_bytes_roofline")):
            assert CELL not in m["workloads"], m["name"]


# ----------------------------------------------------- readers on records
def _ctx(records, cell):
    import time

    from ray_tpu.util import tracing

    class R:
        t0 = time.monotonic() - 1.0

    fields = tracing.FIELDS["engine.dispatch"]
    now = time.time_ns()
    for i, over in enumerate(records):
        rec = dict.fromkeys(fields)
        rec.update(seq=i, kind="decode", dispatch_ns=now - 10 ** 8, k=1,
                   rows=(("a", 1, 500), ("b", 1, 30000)))
        rec.update(over)
        tracing.record("engine.dispatch", tuple(rec[f] for f in fields))
    return {"cell": cell, "runner": R(), "seconds": 2.0, "log": print,
            "trace": None, "peaks": cell_mod.load_peaks("TPU v5 lite")}


def test_what_the_windows_keep_is_read_off_the_windows_decode_records():
    from ray_tpu.util import tracing

    tracing.reset_ring()
    cell = cell_mod.load_cell(CELL)
    reader = cell_mod.load_module("readers", "window_kept_pct")
    ctx = _ctx([dict(window_layers=6, window_tokens_held=500 + 1024,
                     full_tokens_held=30500),
                dict(window_layers=6, window_tokens_held=1024,
                     full_tokens_held=9000),
                dict(kind="prefill", rows=(("c", 4096, 8192),),
                     window_layers=6, window_tokens_held=1024,
                     full_tokens_held=8192)], cell)
    assert reader.read(ctx) == pytest.approx(100 * 2548 / 39500)
    # a program without the fields (the parent, another family) gives
    # nothing, and no error
    tracing.reset_ring()
    assert reader.read(_ctx([dict(moe_assignments=5)], cell)) is None
    tracing.reset_ring()


@pytest.mark.parametrize("what", ["decode_kernel", "flash", "pass",
                                  "decode_bytes", "gmm",
                                  "full_decode_kernel", "full_flash"])
def test_a_roofline_without_a_trace_gives_nothing_and_does_not_raise(what):
    from ray_tpu.util import tracing

    tracing.reset_ring()
    cell = cell_mod.load_cell(CELL)
    reader = cell_mod.load_module("readers", "window_roofline")
    assert reader.read(_ctx([dict(window_layers=6)], cell), what=what,
                       op_pattern="^x") is None
    tracing.reset_ring()


def test_the_rooflines_count_the_paired_records(monkeypatch):
    """`paired.whole_programs` handed in: two decode programs of 10 ms and
    one prefill of 300 ms with their records."""
    cell = cell_mod.load_cell(CELL)
    pub, peaks = cell.config, cell_mod.load_peaks("TPU v5 lite")
    reader = cell_mod.load_module("readers", "window_roofline")
    dec = dict(kind="decode", k=1, rows=(("a", 1, 500), ("b", 1, 30000)),
               window_layers=6, moe_assignments=128, moe_experts_touched=100)
    pre = dict(kind="prefill", k=1, rows=(("c", 4096, 12288),),
               window_layers=6, moe_assignments=4096 * 64,
               moe_experts_touched=512)
    whole = {"decode": [(("decode", 0, 10_000_000), dec)] * 2,
             "prefill": [(("prefill", 0, 300_000_000), pre)]}
    monkeypatch.setattr(paired, "whole_programs",
                        lambda ctx, kind, what: whole[kind])
    monkeypatch.setattr(paired, "op_self_ns",
                        lambda ctx, whole, pattern: 4_000_000 * len(whole))
    ctx = {"cell": cell, "log": print, "peaks": peaks, "trace": object()}
    hbm, mxu = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    k = [window_work.window_decode_kernel(c, 1, pub) for c in (500, 30000)]
    least = 2 * max(sum(w["bytes"] for w in k) / hbm,
                    sum(w["ops"] for w in k) / mxu)
    assert reader.read(ctx, what="decode_kernel", op_pattern="x") == \
        pytest.approx(100 * least / 0.008)
    assert reader.read(ctx, what="decode_bytes") == pytest.approx(
        100 * 2 * window_work.decode_step_bytes(pub, [500, 30000], 100)
        / hbm / 0.020)
    assert reader.read(ctx, what="flash", op_pattern="x") == pytest.approx(
        100 * window_work.window_flash_ops(pre["rows"], pub) / mxu / 0.004)
    k = [window_work.full_decode_kernel(c, 1, pub) for c in (500, 30000)]
    assert reader.read(ctx, what="full_decode_kernel", op_pattern="x") == \
        pytest.approx(100 * 2 * sum(w["bytes"] for w in k) / hbm / 0.008)
    assert reader.read(ctx, what="full_flash", op_pattern="x") == \
        pytest.approx(100 * window_work.attention_ops(
            window_work.full_pairs(4096, 12288), 2, pub) / mxu / 0.004)
    assert reader.read(ctx, what="pass") == pytest.approx(
        100 * window_work.pass_ops(4096, 12288, 4096 * 64, pub) / mxu
        / 0.300)
    g = window_work.gmm_work(pub, 4096 * 64 + 256, 512 + 200)
    assert reader.read(ctx, what="gmm", op_pattern="x") == pytest.approx(
        100 * max(g["ops"] / mxu, g["bytes"] / hbm) / 0.012)
    # records of another family: nothing, no error
    whole["decode"] = [(("decode", 0, 1), dict(dec, window_layers=None))]
    assert reader.read(ctx, what="decode_kernel", op_pattern="x") is None


# a device op's name in a trace: `<innermost jit> pallas <shape>` at a
# program's first call of the kernel, `<jit>.<n> pallas <shape>` at its later
# ones (PERF.md section 3); a pattern that asks for the suffix leaves the
# first call's time out of a share whose work counts every call
_OPS = {
    "window_decode_roofline.serve_tok_s": ("_window_decode",),
    "window_flash_roofline.serve_tok_s": ("_window_flash",),
    "window_moe_gmm_roofline.serve_tok_s": ("_moe_gmm",),
    "full_decode_roofline.serve_tok_s": ("_decode_call",),
    "full_flash_roofline.serve_tok_s": ("attn", "_ctx_flash"),
    "window_attn_time_pct.serve_tok_s": (
        "_window_decode", "_window_flash", "_decode_call", "_ctx_flash",
        "attn"),
}


@pytest.mark.parametrize("name", sorted(_OPS))
def test_a_kernels_pattern_takes_a_programs_first_call_and_its_later_ones(
        name):
    import re

    params = load_json(os.path.join(HERE, "layer_metrics",
                                    name + ".json"))["params"]
    rx = re.compile(params.get("op_pattern") or params["pattern"])
    every = {k for ops in _OPS.values() for k in ops} | {"_moe_gmm"}
    for kernel in every:
        for op in (f"{kernel} pallas bf16[4096,1792]",
                   f"{kernel}.81 pallas bf16[512,1792]"):
            assert bool(rx.search(op)) == (kernel in _OPS[name]), (name, op)
        # another kernel whose name starts the same, and plain XLA under it
        assert not rx.search(f"{kernel}_bwd.3 pallas f32[8,128]")
        assert not rx.search(f"{kernel}.3 fusion bf16[8,128]")


def test_a_kernels_time_counts_its_unsuffixed_first_call():
    """`paired.op_self_ns` on a hand-made trace: the grouped matmul's first
    call of a program carries no suffix and is counted with the second."""
    from types import SimpleNamespace

    pattern = load_json(os.path.join(
        HERE, "layer_metrics", "window_moe_gmm_roofline.serve_tok_s.json"))[
        "params"]["op_pattern"]
    ops = [("_moe_gmm pallas bf16[4096,1792]", 1_000, 279),
           ("fusion.868 bf16[32768,2304]", 1_300, 50),
           ("_moe_gmm.84 pallas bf16[512,1792]", 1_400, 64),
           ("_moe_gmm.85 pallas bf16[512,1792]", 9_000, 107)]   # outside
    ctx = {"trace": SimpleNamespace(trace=SimpleNamespace(
        modules={0: []}, ops={0: ops}))}
    whole = [(("prefill", 900, 1_000), {})]
    assert paired.op_self_ns(ctx, whole, pattern) == 279 + 64


# ------------------------------------------------------------ the controls
def _tiny_cell():
    cell = cell_mod.load_cell(CELL)
    c = cell.config
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
             num_experts=8, num_experts_per_tok=2, vocab_size=256,
             num_hidden_layers=8, sliding_window=32, dtype="float32",
             program_preset="tiny-mellum")
    c["rope_parameters"] = dict(
        c["rope_parameters"], full_attention=dict(
            c["rope_parameters"]["full_attention"],
            original_max_position_embeddings=64))
    c["engine"] = dict(page_size=16, num_pages=128, max_model_len=512,
                       max_batch=4, prefill_buckets=[32, 64])
    c["limits"] = dict.fromkeys(c["limits"], 1e-3)
    c["limits"]["logit_max_err_over_rms"] = 1e-2
    cell.traffic["check"] = {
        "prompt_lens": [30, 100, 200], "decode_tokens": 4,
        "engine_prompts": {"count": 3, "min_len": 70, "max_len": 130,
                           "decode_tokens": 5}}
    cell.rehearsal = True
    return cell


def test_sound_program_passes_and_both_controls_are_refused_at_tiny_size():
    from chipbench.runners import engine_window

    cell = _tiny_cell()
    runner = engine_window.Runner(cell, 3_000_000_019, 2, lambda msg: None)
    check = runner.setup(warm=False)
    assert check["correct"], check["numbers"]
    assert {r["name"] for r in check["numbers"]} == set(cell.config["limits"])
    assert engine_window.AGREE in cell.config["limits"]
    notes = check["notes"]
    assert notes["selection_sets"] == 8 * (33 + 103 + 203)
    assert notes["selection_differs_share"] == 0.0
    ref, cfg = runner.reference, dict(runner.published)
    weights = ref.weights_from_program_tree(runner.engine.params)
    limits, sample = cell.config["limits"], runner.check_sample
    low = engine_window.control_numbers(ref, weights, cfg, "bfloat16",
                                        sample, limits)
    refused = [r["name"] for r in low["numbers"] if not r["ok"]]
    assert "logit_rel_rms_err" in refused, low["numbers"]
    whole = engine_window.control_numbers(
        ref, weights, cfg, "float32", sample, limits,
        {**cfg, "sliding_window": None})
    refused = [r["name"] for r in whole["numbers"] if not r["ok"]]
    assert "logit_rel_rms_err" in refused, whole["numbers"]
    same = engine_window.control_numbers(ref, weights, cfg, "float32",
                                         sample, limits)
    assert all(r["value"] == 0.0 for r in same["numbers"]), same["numbers"]
    # both kinds of the cache in `kv_pages_peak_pct`'s inputs
    runner.samples.update(free_pages=[127, 100], running=[0, 4], t=[0, 1])
    runner.counters = {"num_pages": 128, "preempted": 0}
    sala_window = engine_window.sala.Runner.run_window
    engine_window.sala.Runner.run_window = lambda self, tracer: None
    try:
        runner.run_window(None)
    finally:
        engine_window.sala.Runner.run_window = sala_window
    assert runner.counters["num_pages"] == 2 * 128 + 6 * 2 * 4
    assert runner.samples["free_pages"] == [2 * 127 + 48, 2 * 100]
    runner.engine.close()


def test_a_program_without_the_family_is_refused_at_once(monkeypatch):
    import importlib.util

    from chipbench.cell import BenchError
    from chipbench.runners import engine_window

    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        None if name == "ray_tpu.models.mellum" else real(name, *a)))
    with pytest.raises(BenchError, match="sliding-window and full"):
        engine_window.Runner(cell_mod.load_cell(CELL), 1, 1, print)
