"""The cell `laguna-xs2-agentturns`: its work counts against hand counts at
each kind's OWN heads, its files against the contract and the catalog, its
readers on hand-made records and programs, and its two controls refused at
a tiny size."""

import json
import os
import re

import pytest

from chipbench import cell as cell_mod
from chipbench import generator, laguna_work, paired, window_work
from chipbench.cell import HERE, load_json

CELL = "laguna-xs2-agentturns"
CONFIG = "laguna-xs.2-serve"
BENCH = cell_mod.load_benchmark()
NEW = ("laguna_decode_bytes_roofline.tpot",
       "laguna_prefill_pass_roofline.ttft",
       "laguna_expert_gmm_roofline.tpot", "laguna_expert_gmm_roofline.ttft",
       "laguna_full_decode_roofline.tpot",
       "laguna_window_decode_roofline.tpot",
       "laguna_full_flash_roofline.ttft", "laguna_window_flash_roofline.ttft",
       "laguna_expert_gmm_time_pct.tpot", "laguna_experts_touched_pct.tpot",
       "laguna_attn_time_pct.ttft", "attn_gate_time_pct.tpot")
JOINED = ("gen_late_ms_p95.ttft", "ttft_ms_p50.ttft", "tpot_ms_p50.tpot",
          "queue_wait_ms_p95.ttft", "prefill_ms_p95.ttft",
          "prefill_pad_pct.ttft", "decode_batch_mean.tpot",
          "decode_dispatch_ms.tpot", "device_idle_pct.tpot",
          "step_host_ms_p50.tpot", "step_ms_mean.tpot",
          "head_sample_time_pct.tpot", "moe_rest_time_pct.tpot",
          "moe_rest_time_pct.ttft")
# NOT joined, though the issue lists them: the two readings of the engine's
# stamps (`chipbench/stamped.py`) read nothing in this cell: the host comes
# to under 80% of the programs before they end (68.7% in the first traced
# run: a step that dispatches a prompt's passes is long), and a traced
# run's line that lacks a metric which lists the cell is refused
UNREAD = ("ttft_device_wait_ms_p50.ttft", "prefill_device_us_per_token.ttft")
FULL, SLIDING = "full_attention", "sliding_attention"
H, D, E, F = 2048, 128, 256, 512


@pytest.fixture(scope="module")
def pub():
    return cell_mod.load_cell(CELL).config


def test_the_cut_is_the_issues_arithmetic(pub):
    full = H * D * (48 + 16) + 48 * D * H + H * 48
    sliding = H * D * (64 + 16) + 64 * D * H + H * 64
    assert laguna_work.attn_params(pub, 48) == full == 29_458_432
    assert laguna_work.attn_params(pub, 64) == sliding == 37_879_808
    assert laguna_work.kind_heads(pub, FULL) == (2, 48)
    assert laguna_work.kind_heads(pub, SLIDING) == (3, 64)
    assert laguna_work.sparse_layers(pub) == 4
    per_token = (2 * full + 3 * sliding + 3 * H * 8192
                 + 4 * (H * E + 3 * H * F))
    assert laguna_work.token_params(pub) == per_token
    held = (per_token + 4 * (E * 3 * H * F + E) + 2 * 100352 * H
            + 5 * 2 * H + H)
    # the program tree's count (tests/test_laguna.py holds the tree's)
    assert held == 3_869_858_816
    assert window_work.kv_bytes_token(pub) == 4096      # 4 KB a layer


def test_a_kinds_kernel_reads_a_page_once_for_its_whole_group(pub):
    """A live row at 9000 tokens: a full layer's decode reads 9000 keys and
    values ONCE for all 48 heads (6 a kv head), a sliding layer's 512 for
    all 64; the operations are each kind's heads'."""
    full = laguna_work.decode_kernel(FULL, 9000, 1, pub)
    assert full["bytes"] == 2 * (4096 * 9000 + 2 * 48 * D * 2)
    assert full["ops"] == 2 * 48 * 4 * D * 9000
    win = laguna_work.decode_kernel(SLIDING, 9000, 1, pub)
    assert win["bytes"] == 3 * (4096 * 512 + 2 * 64 * D * 2)
    assert win["ops"] == 3 * 64 * 4 * D * 512
    # under the window both kinds read the context
    assert laguna_work.decode_kernel(SLIDING, 300, 1, pub)["bytes"] == 3 * (
        4096 * 300 + 2 * 64 * D * 2)
    # a pass of 4096 behind 8192: the band's pairs at 64 heads, the causal
    # mask's at 48
    rows = (("a", 4096, 12288),)
    assert laguna_work.flash_ops(SLIDING, rows, pub) == (
        3 * 64 * 4 * D * 4096 * 512)
    assert laguna_work.flash_ops(FULL, rows, pub) == 2 * 48 * 4 * D * (
        4096 * 4097 // 2 + 4096 * 8192)


def test_a_pass_and_a_step_count_tokens_assignments_and_both_kinds(pub):
    got = laguna_work.pass_ops(4096, 12288, 4096 * 8 * 4, pub)
    assert got == (2.0 * laguna_work.token_params(pub) * 4096
                   + 2.0 * 3 * H * F * 4096 * 32
                   + laguna_work.flash_ops(FULL, (("a", 4096, 12288),), pub)
                   + laguna_work.flash_ops(SLIDING, (("a", 4096, 12288),),
                                           pub))
    # the issue's 2.77 TFLOP for a fresh 4096 pass is weights and experts
    # alone; the pairs add a fifth
    fresh = laguna_work.pass_ops(4096, 4096, 4096 * 32, pub)
    assert 2.7e12 < 2.0 * laguna_work.token_params(pub) * 4096 + (
        2.0 * 3 * H * F * 4096 * 32) < 2.85e12 < fresh < 3.6e12
    assert laguna_work.pass_kv_bytes(4096, 12288, pub) == 4096 * (
        5 * 4096 + 2 * 8192 + 3 * 512)
    assert laguna_work.program_weight_bytes(pub, 1024, False) == 2 * (
        laguna_work.token_params(pub) + 3 * H * F * 1024)
    step = laguna_work.decode_step_bytes(pub, [500, 9000], 700)
    assert step == (2 * (laguna_work.token_params(pub) + H * 100352)
                    + 2 * 3.0 * H * F * 700
                    + 4096 * (2 * 9500 + 3 * (500 + 512)))
    # 64 rows at 5000 tokens, 86.5% of the 1024 experts: the issue's 6.4 GB
    # of weights + experts, 2.6 GB of full-layer keys, 0.4 of windows
    full_step = laguna_work.decode_step_bytes(pub, [5000] * 64, 886)
    assert 9.0e9 < full_step < 10.5e9


def test_configuration_keeps_the_catalogs_keys_and_cuts_what_it_says(pub):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if '"name": "Laguna-XS.2"' in line)
    differ = {k for k, v in row["config"].items() if pub.get(k) != v}
    assert differ == {"num_hidden_layers"} == set(pub["reduced"])
    assert pub["published"] == {"num_hidden_layers": 40}
    assert pub["num_hidden_layers"] == 5 and pub["held"] == {"layers": [0, 5]}
    assert len(pub["layer_types"]) == len(pub["mlp_layer_types"]) == len(
        pub["num_attention_heads_per_layer"]) == 40
    assert pub["source"] == row["source_url"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == pub["reduced"]
    assert entry["source"] == pub["source"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    for key in ("equations", "attention", "R1_gate", "R2_router",
                "R3_no_qk_norm", "ffn", "yarn", "cache", "weights", "engine",
                "published_keys"):
        assert pub["assumed"][key], key
    for key in ("R1_gate", "R2_router", "R3_no_qk_norm"):
        assert "modeling_laguna.py" in pub["assumed"][key], key
    assert "stage 0 of 8" in pub["stands_for"]
    assert "3,869,858,816" in pub["reduced_why"]
    eng = pub["engine"]
    assert eng["max_batch"] == 64 and eng["max_model_len"] == 17408
    assert eng["max_model_len"] % eng["page_size"] == 0
    assert pub["sliding_window"] % eng["page_size"] == 0
    assert eng["prefill_buckets"][0] == 512
    assert (pub["runner"], pub["reference"], pub["program_preset"]) == (
        "engine_laguna", "laguna_decoder", "laguna-xs.2")
    for name in ("runner", "reference"):
        assert os.path.isfile(os.path.join(HERE, name + "s",
                                           pub[name] + ".py"))
    assert pub["limits_why"] and len(pub["limits"]) == 4


def test_the_mix_and_the_cell_are_the_issues():
    cell = cell_mod.load_cell(CELL)
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "agentturns-laguna", 1)
    assert len(entry["why"]) <= 200
    mix = cell.traffic
    assert mix["arrivals"]["process"] == "quantile_exponential"
    rate = mix["arrivals"]["rate_per_s"]
    assert rate * 4 == int(rate * 4) and mix["rate_why"]
    assert (mix["ramp_s"], mix["grace_s"]) == (16, 90)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                 "sigma": 0.9, "min": 512, "max": 16384}
    assert mix["output_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["check"] == {
        "prompt_lens": [300, 1500, 5000, 12000], "decode_tokens": 6,
        "engine_prompts": {"count": 8, "min_len": 400, "max_len": 9000,
                           "decode_tokens": 16}}
    assert mix["trace"] == {"start_share": 0.5, "seconds": 5.0}
    assert not set(mix.get("engine", {}))
    # the window's one cycle: rate x 50 requests, every length inside the
    # engine's, most prompts one or two passes of at most 4096
    n = round(rate * BENCH["run_seconds"])
    prompt, output, gaps = generator._base_cycle(mix, n,
                                                 float(BENCH["run_seconds"]))
    assert 512 <= min(prompt) and max(prompt) <= 16384
    assert 32 <= min(output) and max(output) <= 1024
    assert int(max(prompt)) + int(max(output)) <= cell.config["engine"][
        "max_model_len"]
    assert sum(-(-int(p) // 4096) <= 2 for p in prompt) > 0.7 * n
    assert {m.name for m in cell.end_to_end} == {"ttft_ms_p95", "tpot_ms_p95",
                                                 "setup_s"}
    assert {m.name for m in cell.per_layer} == set(NEW) | set(JOINED)
    assert not {m.name for m in cell.per_layer} & set(UNREAD)


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_lists_this_cell_only_and_finds_its_reader(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == {"tpot": "tpot_ms_p95",
                              "ttft": "ttft_ms_p95"}[name.rsplit(".", 1)[1]]
    spec = load_json(os.path.join(HERE, "layer_metrics", name + ".json"))
    assert os.path.isfile(os.path.join(HERE, "readers",
                                       spec["reader"] + ".py"))
    for key in ("layer", "moves", "unit", "source"):
        assert spec[key] == entry[key], key
    assert entry["unit"] == "%" and entry["better"] == "higher"
    if name.endswith(("_roofline.tpot", "_roofline.ttft")):
        assert name.startswith("laguna_")


def test_the_other_families_kernel_metrics_do_not_list_the_cell():
    for m in BENCH["per_layer"]:
        if m["name"].startswith(("moe_gmm", "moe_experts", "paged_decode",
                                 "expert_", "flash_fwd", "sparse_", "mla_",
                                 "window_", "full_", "gdn_", "block_",
                                 "prefill_pass_roofline",
                                 "decode_bytes_roofline")):
            assert CELL not in m["workloads"], m["name"]


# ----------------------------------------------------- readers on records
DEC = dict(kind="decode", k=1, rows=(("a", 1, 500), ("b", 1, 9000)),
           window_layers=3, full_layers=2, window_heads=64, full_heads=48,
           moe_assignments=2 * 8 * 4, moe_experts_touched=60)
PRE = dict(kind="prefill", k=1, rows=(("c", 4096, 12288),), window_layers=3,
           full_layers=2, window_heads=64, full_heads=48,
           moe_assignments=4096 * 32, moe_experts_touched=1024)


@pytest.mark.parametrize("what", ["window_decode", "full_decode",
                                  "window_flash", "full_flash", "pass",
                                  "decode_bytes", "gmm", "attn_time",
                                  "experts_touched"])
def test_a_reading_without_a_trace_gives_nothing_and_does_not_raise(what):
    cell = cell_mod.load_cell(CELL)
    reader = cell_mod.load_module("readers", "laguna_roofline")
    ctx = {"cell": cell, "log": print, "trace": None,
           "peaks": cell_mod.load_peaks("TPU v5 lite")}
    assert reader.read(ctx, what=what, op_pattern="^x",
                       kind="decode") is None


def test_the_readings_count_the_paired_records(monkeypatch):
    """`paired.whole_programs` handed in: two decode programs of 10 ms and
    one prefill of 300 ms with their records."""
    cell = cell_mod.load_cell(CELL)
    pub, peaks = cell.config, cell_mod.load_peaks("TPU v5 lite")
    reader = cell_mod.load_module("readers", "laguna_roofline")
    whole = {"decode": [(("decode", 0, 10_000_000), DEC)] * 2,
             "prefill": [(("prefill", 0, 300_000_000), PRE)]}
    monkeypatch.setattr(paired, "whole_programs",
                        lambda ctx, kind, what: whole[kind])
    monkeypatch.setattr(paired, "op_self_ns",
                        lambda ctx, whole, pattern: 4_000_000 * len(whole))
    ctx = {"cell": cell, "log": print, "peaks": peaks, "trace": object()}
    hbm, mxu = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    for what, kind in (("window_decode", SLIDING), ("full_decode", FULL)):
        k = [laguna_work.decode_kernel(kind, c, 1, pub) for c in (500, 9000)]
        least = 2 * max(sum(w["bytes"] for w in k) / hbm,
                        sum(w["ops"] for w in k) / mxu)
        assert reader.read(ctx, what=what, op_pattern="x") == pytest.approx(
            100 * least / 0.008)
    assert reader.read(ctx, what="decode_bytes") == pytest.approx(
        100 * 2 * laguna_work.decode_step_bytes(pub, [500, 9000], 60)
        / hbm / 0.020)
    for what, kind in (("window_flash", SLIDING), ("full_flash", FULL)):
        assert reader.read(ctx, what=what, op_pattern="x") == pytest.approx(
            100 * laguna_work.flash_ops(kind, PRE["rows"], pub) / mxu
            / 0.004)
    ops = laguna_work.pass_ops(4096, 12288, 4096 * 32, pub)
    nbytes = (laguna_work.program_weight_bytes(pub, 1024, False)
              + laguna_work.pass_kv_bytes(4096, 12288, pub))
    assert ops / mxu > nbytes / hbm       # a 4096 pass is compute-bound
    assert reader.read(ctx, what="pass") == pytest.approx(
        100 * ops / mxu / 0.300)
    g = laguna_work.gmm_work(pub, 2 * 64, 2 * 60)
    assert reader.read(ctx, what="gmm", kind="decode", op_pattern="x") == \
        pytest.approx(100 * max(g["ops"] / mxu, g["bytes"] / hbm) / 0.008)
    g = laguna_work.gmm_work(pub, 4096 * 32, 1024)
    assert reader.read(ctx, what="gmm", kind="prefill", op_pattern="x") == \
        pytest.approx(100 * max(g["ops"] / mxu, g["bytes"] / hbm) / 0.004)
    assert reader.read(ctx, what="attn_time", kind="prefill",
                       op_pattern="x") == pytest.approx(100 * 4 / 300)
    assert reader.read(ctx, what="attn_time", kind="decode",
                       op_pattern="x") == pytest.approx(100 * 8 / 20)
    assert reader.read(ctx, what="experts_touched") == pytest.approx(
        100 * 120 / (2 * 4 * 256))
    # records of a family without heads a kind: nothing, no error
    whole["decode"] = [(("decode", 0, 1), dict(DEC, window_heads=None))]
    assert reader.read(ctx, what="full_decode", op_pattern="x") is None


def test_a_small_pass_is_bounded_by_its_bytes(monkeypatch):
    """A 512-token pass reads 3.5 GB of weights and all it touches of the
    experts for 0.4 TFLOP: the longer bound is the bytes'."""
    cell = cell_mod.load_cell(CELL)
    pub, peaks = cell.config, cell_mod.load_peaks("TPU v5 lite")
    reader = cell_mod.load_module("readers", "laguna_roofline")
    rec = dict(PRE, rows=(("d", 512, 512),), moe_assignments=512 * 32,
               moe_experts_touched=1024)
    monkeypatch.setattr(paired, "whole_programs", lambda ctx, kind, what: [
        (("prefill", 0, 20_000_000), rec)])
    ctx = {"cell": cell, "log": print, "peaks": peaks, "trace": object()}
    nbytes = (laguna_work.program_weight_bytes(pub, 1024, False)
              + laguna_work.pass_kv_bytes(512, 512, pub))
    assert reader.read(ctx, what="pass") == pytest.approx(
        100 * nbytes / peaks["hbm_bytes_per_s"] / 0.020)


_OPS = {
    "laguna_window_decode_roofline.tpot": ("_window_decode",),
    "laguna_full_decode_roofline.tpot": ("_decode_call",),
    "laguna_window_flash_roofline.ttft": ("_window_flash",),
    "laguna_full_flash_roofline.ttft": ("attn", "_ctx_flash"),
    "laguna_expert_gmm_roofline.tpot": ("_moe_gmm",),
    "laguna_expert_gmm_roofline.ttft": ("_moe_gmm",),
    "laguna_expert_gmm_time_pct.tpot": ("_moe_gmm",),
    "laguna_attn_time_pct.ttft": ("_window_flash", "_ctx_flash", "attn"),
}


@pytest.mark.parametrize("name", sorted(_OPS))
def test_a_kernels_pattern_takes_a_programs_first_call_and_its_later_ones(
        name):
    params = load_json(os.path.join(HERE, "layer_metrics",
                                    name + ".json"))["params"]
    rx = re.compile(params["op_pattern"])
    every = {k for ops in _OPS.values() for k in ops} | {"_mla_decode"}
    for kernel in every:
        for op in (f"{kernel} pallas bf16[4096,1792]",
                   f"{kernel}.81 pallas bf16[512,1792]"):
            assert bool(rx.search(op)) == (kernel in _OPS[name]), (name, op)
        assert not rx.search(f"{kernel}_bwd.3 pallas f32[8,128]")
        assert not rx.search(f"{kernel}.3 fusion bf16[8,128]")


def test_the_gates_share_is_read_off_its_scope():
    spec = load_json(os.path.join(HERE, "layer_metrics",
                                  "attn_gate_time_pct.tpot.json"))
    assert spec["reader"] == "scope_time_pct"
    assert spec["params"]["kinds"] == ["decode"]
    rx = re.compile(spec["params"]["scope"])
    assert rx.search("jit(run_decode)/run_01/attn/rtpu.attn.gate/mul")
    assert not rx.search("jit(run_decode)/run_01/attn/rtpu.attn.cache_write")


# ------------------------------------------------------------ the controls
def _tiny_cell():
    cell = cell_mod.load_cell(CELL)
    c = cell.config
    c.update(hidden_size=64, intermediate_size=128, num_key_value_heads=2,
             head_dim=16, moe_intermediate_size=32,
             shared_expert_intermediate_size=32, num_experts=16,
             num_experts_per_tok=4, vocab_size=256, num_hidden_layers=5,
             sliding_window=32, dtype="float32",
             num_attention_heads_per_layer=[6, 8, 8, 8] * 10,
             program_preset="tiny-laguna")
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
    from chipbench.runners import engine_laguna, engine_window

    cell = _tiny_cell()
    runner = engine_laguna.Runner(cell, 3_000_000_019, 2, lambda msg: None)
    check = runner.setup(warm=False)
    assert check["correct"], check["numbers"]
    assert {r["name"] for r in check["numbers"]} == set(cell.config["limits"])
    assert engine_window.AGREE in cell.config["limits"]
    # the four sparse layers' choices are compared; the set-up put the
    # window runner's overrides and weights back
    assert check["notes"]["selection_sets"] == 4 * (33 + 103 + 203)
    assert engine_window.model_overrides is not engine_laguna.model_overrides
    got = runner.engine.model_cfg
    assert (got.heads(FULL), got.heads(SLIDING), got.n_expert_layers) == (
        6, 8, 4)
    bias = runner.engine.params["run_01"]["moe"]["router_bias"]
    assert float(abs(bias).max()) > 0
    ref, cfg = runner.reference, dict(runner.published)
    weights = ref.weights_from_program_tree(runner.engine.params)
    limits, sample = cell.config["limits"], runner.check_sample
    low = engine_laguna.control_numbers(ref, weights, cfg, "bfloat16",
                                        sample, limits)
    refused = [r["name"] for r in low["numbers"] if not r["ok"]]
    assert "logit_rel_rms_err" in refused, low["numbers"]
    whole = engine_laguna.control_numbers(
        ref, weights, cfg, "float32", sample, limits,
        {**cfg, "sliding_window": None})
    refused = [r["name"] for r in whole["numbers"] if not r["ok"]]
    assert "logit_rel_rms_err" in refused, whole["numbers"]
    same = engine_laguna.control_numbers(ref, weights, cfg, "float32",
                                         sample, limits)
    assert all(r["value"] == 0.0 for r in same["numbers"]), same["numbers"]
    runner.engine.close()


def test_a_program_without_the_family_is_refused_at_once(monkeypatch):
    import importlib.util

    from chipbench.cell import BenchError
    from chipbench.runners import engine_laguna

    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        None if name == "ray_tpu.models.laguna" else real(name, *a)))
    with pytest.raises(BenchError, match="their own shapes"):
        engine_laguna.Runner(cell_mod.load_cell(CELL), 1, 1, print)
