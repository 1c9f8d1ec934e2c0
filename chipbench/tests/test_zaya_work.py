"""The cell `zaya1-8b-reasoning`: its work counts against a hand count, its
files against the contract and the catalog, its readers on hand-made
records, and its controls (a lower precision, a tail dropped at a pass
boundary) refused at a tiny size. Nothing here asserts that an entry is the
LAST of `BENCHMARK.json`: the next cell would break that."""

import json
import os

import pytest

from chipbench import cell as cell_mod
from chipbench import paired, zaya_work
from chipbench.cell import HERE, load_json

CELL = "zaya1-8b-reasoning"
BENCH = cell_mod.load_benchmark()
NEW = ("zaya_decode_bytes_roofline.serve_tok_s",
       "zaya_prefill_pass_roofline.serve_tok_s",
       "zaya_expert_gmm_roofline.serve_tok_s",
       "zaya_expert_gmm_time_pct.serve_tok_s",
       "zaya_cca_decode_roofline.serve_tok_s",
       "zaya_cca_flash_roofline.serve_tok_s", "cca_time_pct.serve_tok_s",
       "router_time_pct.serve_tok_s", "zaya_experts_touched_pct.serve_tok_s")
JOINED = ("kv_pages_peak_pct.serve_tok_s", "prefill_dispatch_ms.serve_tok_s",
          "device_idle_pct.serve_tok_s", "prefill_pad_pct.serve_tok_s")


@pytest.fixture(scope="module")
def pub():
    return cell_mod.load_cell(CELL).config


def test_the_cut_is_the_issues_arithmetic(pub):
    # [W_q | W_k | W_v1 | W_v2], the head-mixing taps, W_o
    attn = 2048 * (1024 + 256 + 256) + 2 * 10 * 128 * 128 + 1024 * 2048
    assert zaya_work.attn_params(pub) == attn == 5_570_560
    router = 2048 * 256 + 2 * 256 * 256 + 256 * 16
    assert zaya_work.router_params(pub) == router == 659_456
    assert zaya_work.token_params(pub) == 20 * (attn + router)
    # the held matrices: + 20 layers x 16 experts and the tied embedding
    held = (zaya_work.token_params(pub) + 20 * 16 * 3 * 2048 * 2048
            + 262272 * 2048)
    assert round(held / 1e9, 3) == 4.688
    # a token multiplies 18.8 M a layer; the issue's floor of 2650 tokens
    active = zaya_work.token_params(pub) + 20 * 3 * 2048 * 2048
    assert round(active / 20 / 1e6, 1) == 18.8
    assert round(240 * (held - 262272 * 2048) / active, -1) == 2650
    # a token's keys and values: 1 KB a layer; a slot's tails: 108 KB
    assert zaya_work.kv_bytes_token(pub) == 1024
    assert zaya_work.tail_values(pub) == 2 * 1280 + 128 == 2688
    assert zaya_work.tail_bytes_row(pub) == 20 * 2688 * 2 == 107_520


def test_a_decode_step_reads_experts_pages_tails_and_the_head(pub):
    step = zaya_work.decode_step_bytes(pub, [3000] * 64, 20 * 15.7)
    experts = 20 * 15.7 * 3 * 2048 * 2048 * 2
    pages = 64 * 3000 * 1024 * 20
    head = 262272 * 2048 * 2
    assert step == pytest.approx(
        2 * zaya_work.token_params(pub) + head + experts + pages
        + 2 * 64 * 107_520)
    # the issue's estimate: a layer 601 MB (396 of experts, 192 of pages,
    # 12.5 of weights, 0.7 of tails), a step 13.1 GB, 16.0 ms at the peak:
    # 13.17 GB and 16.1 ms with the pages at 64 x 3000 x 1024 to the byte
    assert round(experts / 20 / 1e6) == 395 and pages / 20 == 196_608_000
    assert round(2 * zaya_work.token_params(pub) / 20 / 1e6, 1) == 12.5
    assert round(step / 1e9, 2) == 13.17
    assert round(step / 819e9 * 1e3, 1) == 16.1
    assert round(100 * head / step) == 8


def test_a_pass_counts_tokens_pairs_and_assignments(pub):
    base = zaya_work.pass_ops(4096, 4096, 20 * 4096, pub)
    pairs = 20 * 8 * 4 * 128 * (4096 * 4097 // 2)
    assert base == (2 * zaya_work.token_params(pub) * 4096
                    + 2 * 3 * 2048 * 2048 * 20 * 4096 + pairs)
    # the issue: 4096 x 0.376 B x 2 = 3.1 TFLOP of matmuls a 4096 pass
    assert round((base - pairs) / 1e12, 1) == 3.1
    # a resumed pass behind 4096 tokens sees them with every query
    more = zaya_work.pass_ops(904, 5000, 0, pub) - zaya_work.pass_ops(
        904, 904, 0, pub)
    assert more == 20 * 8 * 4 * 128 * 904 * 4096
    assert zaya_work.flash_ops([("a", 904, 5000)], pub) == (
        20 * 8 * 4 * 128 * (904 * 905 // 2 + 904 * 4096))
    # a program reads its weights once, and writes and reads 1 KB a token
    assert zaya_work.pass_kv_bytes(904, 5000, pub) == 20 * 5000 * 1024
    assert zaya_work.program_weight_bytes(pub, 320, False) == (
        2 * zaya_work.token_params(pub) + 320 * 3 * 2048 * 2048 * 2)


def test_the_decode_kernel_reads_a_kilobyte_a_token_and_layer(pub):
    w = zaya_work.decode_kernel(3000, 1, pub)
    assert w["bytes"] == 20 * (3000 * 1024 + 2 * 8 * 128 * 2)
    assert w["ops"] == 20 * 8 * 4 * 128 * 3000
    # 4 query heads a kv head: 2 operations a byte, bound by the bytes
    assert w["ops"] / w["bytes"] < 4.1
    two = zaya_work.decode_kernel(3000, 2, pub)
    assert two["ops"] == 20 * 8 * 4 * 128 * (3000 + 3001)


def test_configuration_keeps_the_catalogs_keys_and_cuts_what_it_says(pub):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if '"name": "ZAYA1-8B"' in line)
    differ = {k for k, v in row["config"].items() if pub.get(k) != v}
    assert differ == {"num_hidden_layers"} == set(pub["reduced"])
    assert pub["published"] == {"num_hidden_layers": 40}
    assert pub["num_hidden_layers"] == 20 and pub["held"] == {
        "layers": [0, 20]}
    assert pub["source"] == row["source_url"]
    entry = next(c for c in BENCH["configs"] if c["name"] == "zaya1-8b-serve")
    assert entry["reduced"] == pub["reduced"]
    assert entry["source"] == pub["source"] and len(entry["why"]) <= 200
    for key in ("equations", "R1_value_shift", "R2_convolutions",
                "R3_qk_mean", "R4_norm_temperature", "R5_router", "rotation",
                "weights", "engine", "tail_layout"):
        assert pub["assumed"][key], key
        if key.startswith("R"):
            assert "modeling_zaya.py" in pub["assumed"][
                "equations"] and "One " in pub["assumed"][key]
    assert "two v5e chips" in pub["stands_for"]
    assert "FIRST stage" in pub["stands_for"]
    engine = pub["engine"]
    assert (engine["page_size"], engine["max_model_len"],
            engine["max_batch"], engine["prefill_buckets"]) == (
        64, 14400, 64, [512, 1024, 2048, 4096])
    # weights + pool hold over 12 GB of the chip's 16
    pool = engine["num_pages"] * 20 * 2 * 64 * 256 * 2
    assert 2 * 4_688_410_984 + pool > 12e9
    for name in ("runner", "reference"):
        assert os.path.isfile(os.path.join(HERE, name + "s",
                                           pub[name] + ".py"))
    assert pub["limits_why"] and len(pub["limits"]) >= 3


def test_the_mix_and_the_cell_are_the_issues():
    from chipbench import generator

    cell = cell_mod.load_cell(CELL)
    mix = cell.traffic
    assert cell.chips == 1 and cell.traffic_name == "reasoning-zaya"
    assert mix["arrivals"]["process"] == "backlog" and mix["block"] == 32
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 1.0, "min": 64, "max": 6144}
    assert mix["output_len"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.6, "min": 512, "max": 8192}
    assert (mix["ramp_s"], mix["grace_s"]) == (40, 0)
    assert mix["check"]["prompt_lens"] == [200, 1500, 5000]
    assert mix["check"]["engine_prompts"] == {
        "count": 16, "min_len": 300, "max_len": 3000, "decode_tokens": 32}
    sched = generator.make_schedule(mix, 2147483659, 50.0, 262272)
    assert all(r.due_s == -40.0 for r in sched)
    assert len(sched) == -(-mix["arrivals"]["max_rate_per_s"] * 90 // 1)
    lens = [len(r.prompt_ids) for r in sched[:32]]
    outs = [r.max_tokens for r in sched[:32]]
    assert min(lens) >= 64 and max(lens) <= 6144
    assert min(outs) >= 512 and max(outs) <= 8192
    assert max(max(r.prompt_ids) for r in sched[:4]) < 262272
    # the longest prompt and answer and one page fit a sequence, in pages
    assert 6144 + 8192 + 64 == cell.config["engine"]["max_model_len"]
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "20/40" in entry["why"]
    assert {m.name for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    assert {m.name for m in cell.per_layer} == set(NEW) | set(JOINED)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 0
    assert len(BENCH["workloads"]) >= 12


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_lists_this_cell_and_finds_its_reader(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert CELL in entry["workloads"] and entry["moves"] == "serve_tok_s"
    spec = load_json(os.path.join(HERE, "layer_metrics", name + ".json"))
    assert os.path.isfile(os.path.join(HERE, "readers",
                                       spec["reader"] + ".py"))
    for key in ("layer", "moves", "unit", "source"):
        assert spec[key] == entry[key], key
    assert entry["unit"] == "%"
    if "scope" in spec.get("params", {}):
        import re

        from ray_tpu.util import tracing
        assert any(re.search(spec["params"]["scope"], s)
                   for s in tracing.SCOPES)


# ----------------------------------------------------- readers on records
@pytest.mark.parametrize("what", ["cca_decode", "cca_flash", "decode_bytes",
                                  "pass", "gmm", "experts_touched"])
def test_a_reading_without_a_trace_gives_nothing_and_does_not_raise(what):
    cell = cell_mod.load_cell(CELL)
    reader = cell_mod.load_module("readers", "zaya_roofline")
    ctx = {"cell": cell, "log": print, "trace": None,
           "peaks": cell_mod.load_peaks("TPU v5 lite")}
    assert reader.read(ctx, what=what, op_pattern="^x", kind="decode") is None
    assert reader.read(dict(ctx, peaks={}), what=what) is None


def test_the_rooflines_count_the_paired_records(monkeypatch):
    """`paired.whole_programs` handed in: two decode programs of 20 ms and
    one prefill of 100 ms with their records."""
    cell = cell_mod.load_cell(CELL)
    pub, peaks = cell.config, cell_mod.load_peaks("TPU v5 lite")
    reader = cell_mod.load_module("readers", "zaya_roofline")
    dec = dict(kind="decode", k=1, rows=(("a", 1, 900), ("b", 1, 1800)),
               cca_layers=20, moe_assignments=40, moe_experts_touched=38)
    pre = dict(kind="prefill", k=1, rows=(("c", 904, 5000),), cca_layers=20,
               moe_assignments=20 * 904, moe_experts_touched=320)
    whole = {"decode": [(("decode", 0, 20_000_000), dec)] * 2,
             "prefill": [(("prefill", 0, 100_000_000), pre)]}
    monkeypatch.setattr(paired, "whole_programs",
                        lambda ctx, kind, what: whole[kind])
    monkeypatch.setattr(paired, "op_self_ns",
                        lambda ctx, whole, pattern: 4_000_000 * len(whole))
    ctx = {"cell": cell, "log": print, "peaks": peaks, "trace": object()}
    hbm, mxu = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    kernel = [zaya_work.decode_kernel(c, 1, pub) for c in (900, 1800)]
    assert reader.read(ctx, what="cca_decode", op_pattern="x") == \
        pytest.approx(100 * 2 * sum(w["bytes"] for w in kernel) / hbm / 0.008)
    assert reader.read(ctx, what="decode_bytes") == pytest.approx(
        100 * 2 * zaya_work.decode_step_bytes(pub, [900, 1800], 38) / hbm
        / 0.040)
    assert reader.read(ctx, what="cca_flash", op_pattern="x") == \
        pytest.approx(100 * zaya_work.flash_ops(pre["rows"], pub) / mxu
                      / 0.004)
    need_bytes = (zaya_work.program_weight_bytes(pub, 320, False)
                  + zaya_work.pass_kv_bytes(904, 5000, pub))
    need_ops = zaya_work.pass_ops(904, 5000, 20 * 904, pub)
    assert reader.read(ctx, what="pass") == pytest.approx(
        100 * max(need_ops / mxu, need_bytes / hbm) / 0.100)
    g = zaya_work.gmm_work(pub, 80, 76)
    assert reader.read(ctx, what="gmm", kind="decode", op_pattern="x") == \
        pytest.approx(100 * max(g["ops"] / mxu, g["bytes"] / hbm) / 0.008)
    assert reader.read(ctx, what="time", kind="decode", op_pattern="x") == \
        pytest.approx(100 * 8 / 40)
    assert reader.read(ctx, what="experts_touched") == pytest.approx(
        100 * 76 / (2 * 20 * 16))
    # records of another family (the parent's program): nothing, no error
    whole["decode"] = [(("decode", 0, 1), dict(dec, cca_layers=None))]
    assert reader.read(ctx, what="cca_decode", op_pattern="x") is None


# ------------------------------------------------------------ the controls
def _tiny_cell():
    """The cell with its configuration shrunk, in memory, to the
    `tiny-zaya` preset's sizes: the recipe for a CPU rehearsal
    (`cell.rehearsal = True`, then `run.run_cell`)."""
    cell = cell_mod.load_cell(CELL)
    c = cell.config
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, moe_intermediate_size=32, num_experts=4,
             router_hidden_size=16, vocab_size=512, num_hidden_layers=3,
             dtype="float32", program_preset="tiny-zaya")
    c["engine"] = dict(page_size=16, num_pages=128, max_model_len=512,
                       max_batch=4, prefill_buckets=[32, 64])
    c["limits"] = dict.fromkeys(c["limits"], 1e-3)
    c["limits"]["logit_max_err_over_rms"] = 1e-2
    cell.traffic.update(
        prompt_len={"dist": "lognormal", "median": 40, "sigma": 1.0,
                    "min": 8, "max": 200},
        output_len={"dist": "lognormal", "median": 24, "sigma": 0.6,
                    "min": 4, "max": 96},
        ramp_s=1, arrivals={"process": "backlog", "max_rate_per_s": 400})
    cell.traffic["check"] = {
        "prompt_lens": [30, 100, 200], "decode_tokens": 4,
        "engine_prompts": {"count": 3, "min_len": 70, "max_len": 130,
                           "decode_tokens": 5}}
    cell.rehearsal = True
    return cell


def test_sound_program_passes_and_both_controls_are_refused_at_tiny_size():
    from chipbench.runners import engine_cca, engine_mla

    cell = _tiny_cell()
    runner = engine_cca.Runner(cell, 3_000_000_019, 2, lambda msg: None)
    check = runner.setup(warm=False)
    assert check["correct"], check["numbers"]
    assert {r["name"] for r in check["numbers"]} == set(cell.config["limits"])
    assert engine_mla.AGREE in cell.config["limits"]
    notes = check["notes"]
    # the chosen expert of every layer at every position of the three
    # sequences (30, 100 and 200 tokens + 3 fed back)
    assert notes["selection_sets"] == 3 * (33 + 103 + 203)
    assert notes["selection_differs_share"] == 0.0
    limits = cell.config["limits"]
    # a tail dropped at a pass boundary (100 = 64 + 36, 200 = 3 x 64 + 8)
    dropped = engine_cca.zero_tail_numbers(runner, limits)
    refused = [r["name"] for r in dropped["numbers"] if not r["ok"]]
    assert engine_cca.BEHIND in refused, dropped["numbers"]
    # 1 resumed pass + 3 decode steps, 3 + 3 and 3 + 3: 13 positions
    assert dropped["notes"]["positions_behind_a_boundary"] == 13
    assert check["notes"]["positions_behind_a_boundary"] == 13
    ref, cfg = runner.reference, dict(runner.published)
    weights = ref.weights_from_program_tree(runner.engine.params)
    res = engine_cca.control_numbers(ref, weights, cfg, "bfloat16",
                                     runner.check_sample, limits)
    refused = [r["name"] for r in res["numbers"] if not r["ok"]]
    assert "logit_rel_rms_err" in refused, res["numbers"]
    same = engine_cca.control_numbers(ref, weights, cfg, "float32",
                                      runner.check_sample, limits)
    assert all(r["value"] == 0.0 for r in same["numbers"]), same["numbers"]
    runner.engine.close()


def test_a_program_without_the_family_is_refused_at_once(monkeypatch):
    import importlib.util

    from chipbench.cell import BenchError
    from chipbench.runners import engine_cca

    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        None if name == "ray_tpu.models.zaya" else real(name, *a)))
    with pytest.raises(BenchError, match="compressed latent"):
        engine_cca.Runner(cell_mod.load_cell(CELL), 1, 1, print)


def test_a_switch_the_program_has_one_setting_of_is_refused():
    from chipbench.cell import BenchError
    from chipbench.runners import engine_cca

    cell = cell_mod.load_cell(CELL)
    cell.config["tie_word_embeddings"] = False
    with pytest.raises(BenchError, match="tie_word_embeddings"):
        engine_cca.Runner(cell, 1, 1, print)
    cell = cell_mod.load_cell(CELL)
    cell.config["layer_types"] = ["hybrid_sliding"] * 40
    runner = engine_cca.Runner(cell, 1, 1, print)
    with pytest.raises(BenchError, match="not `hybrid`"):
        engine_cca.model_overrides(runner.published)
