"""The cell `gigachat3.5-reasoning`: its work counts against a hand count,
its files against the contract and the catalog, its readers on hand-made
records, and its control refused at a tiny size."""

import json
import os

import pytest

from chipbench import cell as cell_mod
from chipbench import gdn_work, mla_work, paired
from chipbench.cell import HERE, load_json

CELL = "gigachat3.5-reasoning"
BENCH = cell_mod.load_benchmark()
NEW = ("gdn_update_roofline.serve_tok_s", "gdn_update_time_pct.serve_tok_s",
       "gdn_decode_bytes_roofline.serve_tok_s",
       "gdn_prefill_pass_roofline.serve_tok_s",
       "gdn_mla_decode_time_pct.serve_tok_s",
       "gdn_expert_gmm_roofline.serve_tok_s",
       "gdn_expert_held_pct.serve_tok_s")
JOINED = ("kv_pages_peak_pct.serve_tok_s", "prefill_dispatch_ms.serve_tok_s",
          "device_idle_pct.serve_tok_s", "prefill_pad_pct.serve_tok_s")


@pytest.fixture(scope="module")
def pub():
    return cell_mod.load_cell(CELL).config


def test_the_cut_is_the_issues_arithmetic(pub):
    assert gdn_work.layer_counts(pub) == {"mla": 1, "gdn": 4, "dense": 1,
                                          "moe": 4}
    assert gdn_work.gdn_channels(pub) == 16384
    # a GDN mixer's matrices: [q|k|v|z], [b|a], out
    gdn = 7168 * (2 * 4096 + 2 * 8192) + 7168 * 128 + 8192 * 7168
    assert gdn_work.gdn_params(pub) == gdn == 235_798_528
    # the MLA mixer: Kimi's 101.12 M and the output gate
    assert gdn_work.mla_params(pub) == 101_122_048 + 7168 * 8192
    per_token = (gdn_work.mla_params(pub) + 4 * gdn + 3 * 7168 * 18432
                 + 4 * (7168 * 256 + 3 * 7168 * 2048))
    assert gdn_work.token_params(pub) == per_token
    # the held matrices: + 4 layers x 16 experts, the embedding and head
    held = per_token + 4 * 16 * 3 * 7168 * 2048 + 2 * 16032 * 7168
    assert round(held / 1e9, 2) == 4.73
    # a decode slot: 4 layers x (4.19 MB of state + 0.10 MB of tail)
    assert gdn_work.state_bytes(pub) == 64 * 128 * 128 * 4 == 4_194_304
    assert gdn_work.tail_bytes(pub) == 3 * 16384 * 2
    assert round(4 * (gdn_work.state_bytes(pub)
                      + gdn_work.tail_bytes(pub)) / 1e6, 1) == 17.2


def test_the_update_kernel_moves_a_live_rows_state_twice(pub):
    w = gdn_work.update_kernel(96, 1, pub)
    per = 2 * 4_194_304 + (4 * 64 * 128 + 2 * 64) * 4
    assert w["bytes"] == 96 * 4 * per
    assert w["ops"] == 96 * 4 * 64 * 7 * 128 * 128
    # 0.9 operations a byte: bound by the bytes on any chip
    assert w["ops"] / w["bytes"] < 1
    assert gdn_work.update_kernel(96, 2, pub)["bytes"] == 2 * w["bytes"]
    assert round(w["bytes"] / 1e9, 2) == 3.27


def test_a_pass_counts_tokens_chunk_products_pairs_and_assignments(pub):
    chunk = 64 * (5 * 128 * 64 + 6 * 128 * 128)
    assert gdn_work.gdn_chunk_ops(1, pub) == chunk
    base = gdn_work.pass_ops(4096, 4096, 0, 0, pub)
    pairs = 64 * 2 * (128 + 64 + 128) * (4096 * 4097 // 2)
    assert base == (2 * gdn_work.token_params(pub) * 4096
                    + 4 * 4096 * chunk + pairs)
    # the delta rule's own products are a hundredth of a pass
    assert 0.005 < 4 * 4096 * chunk / base < 0.02
    # a resumed pass behind 4096 tokens materialised one chunk in ONE layer
    more = gdn_work.pass_ops(904, 5000, 1, 512, pub) - gdn_work.pass_ops(
        904, 904, 0, 0, pub)
    assert more == (2 * 512 * 64 * 256 * 4096 + 2 * 3 * 7168 * 2048 * 512
                    + 64 * 640 * 904 * 4096)
    # a fresh pass with 2048 held assignments a layer (the issue: 14.7)
    assert round(gdn_work.pass_ops(4096, 4096, 0, 4 * 2048, pub) / 1e12,
                 1) == 15.0


def test_a_decode_step_reads_weights_experts_states_and_latents(pub):
    step = gdn_work.decode_step_bytes(pub, [3000] * 96, 60)
    assert step == (gdn_work.decode_weight_bytes(pub)
                    + 60 * 2 * 3 * 7168 * 2048
                    + 2 * 4 * 96 * (4_194_304 + 98_304)
                    + 2 * 576 * 1 * 96 * 3000)
    # the issue's estimate: 8.9 GB of weights with 60 experts touched, 3.2
    # GB of state, 0.3-0.5 GB of latents
    assert round((gdn_work.decode_weight_bytes(pub)
                  + 60 * 2 * 3 * 7168 * 2048) / 1e9, 1) == 8.9
    assert round(2 * 4 * 96 * 4_194_304 / 1e9, 1) == 3.2
    assert 15 < step / 819e9 * 1e3 < 16           # ms at the HBM peak


def test_configuration_keeps_the_catalogs_keys_and_cuts_what_it_says(pub):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if '"name": "GigaChat3.5-432B-A28B"' in line)
    differ = {k for k, v in row["config"].items() if pub.get(k) != v}
    assert differ == {"num_hidden_layers", "n_routed_experts", "vocab_size",
                      "num_nextn_predict_layers"} == set(pub["reduced"])
    assert pub["published"] == {k: row["config"][k] for k in differ}
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"], pub["num_nextn_predict_layers"]) == (
                5, 16, 16032, 0)
    assert pub["held"] == {"layers": [2, 7], "experts": [0, 16],
                           "vocab_rows": [0, 16032]}
    assert pub["source"] == row["source_url"] and len(pub["source"]) == 74
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "gigachat3.5-432b-a28b-serve")
    assert entry == BENCH["configs"][-1]
    assert entry["reduced"] == pub["reduced"]
    assert entry["source"] == pub["source"]
    for key in ("equations", "gated_delta_net", "qkvz_layout", "attention",
                "router", "readings", "mtp", "share", "vocabulary",
                "weights", "engine"):
        assert pub["assumed"][key], key
    assert set(pub["assumed"]["readings"]) == {
        "i_norm", "ii_gated_attention", "iii_gdn_output_gate",
        "iv_swiglu_limit"}
    assert "16 chips" in pub["stands_for"] and "stages of 5" in pub[
        "stands_for"]
    assert pub["engine"] == {
        "page_size": 64, "num_pages": 12288, "max_model_len": 14400,
        "max_batch": 96, "prefill_buckets": [512, 1024, 2048, 4096]}
    for name in ("runner", "reference"):
        assert os.path.isfile(os.path.join(HERE, name + "s",
                                           pub[name] + ".py"))
    assert pub["limits_why"] and len(pub["limits"]) >= 3


def test_the_mix_and_the_cell_are_the_issues():
    from chipbench import generator

    cell = cell_mod.load_cell(CELL)
    mix = cell.traffic
    assert cell.chips == 1 and cell.traffic_name == "reasoning-gigachat"
    assert mix["arrivals"]["process"] == "backlog" and mix["block"] == 32
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 1.0, "min": 128, "max": 8192}
    assert mix["output_len"] == {"dist": "lognormal", "median": 1536,
                                 "sigma": 0.7, "min": 256, "max": 6144}
    assert (mix["ramp_s"], mix["grace_s"]) == (40, 0)
    assert mix["check"]["prompt_lens"] == [300, 1500, 5000]
    assert mix["check"]["engine_prompts"] == {
        "count": 16, "min_len": 600, "max_len": 3000, "decode_tokens": 32}
    sched = generator.make_schedule(mix, 2147483659, 50.0, 16032)
    assert all(r.due_s == -40.0 for r in sched)
    assert len(sched) == -(-mix["arrivals"]["max_rate_per_s"] * 90 // 1)
    lens = [len(r.prompt_ids) for r in sched[:32]]
    outs = [r.max_tokens for r in sched[:32]]
    assert min(lens) >= 128 and max(lens) <= 8192
    assert min(outs) >= 256 and max(outs) <= 6144
    assert max(max(r.prompt_ids) for r in sched[:4]) < 16032
    # the longest prompt and answer and one page fit a sequence, in pages
    assert 8192 + 6144 + 64 == cell.config["engine"]["max_model_len"]
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry == BENCH["workloads"][-1]
    assert len(entry["why"]) <= 200 and "1/16" in entry["why"]
    assert {m.name for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
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


def test_the_latent_kernels_rooflines_do_not_list_the_cell():
    """`mla_roofline`'s kernel counts multiply by `num_hidden_layers` (5
    here, of which ONE is a latent layer): they would read five times the
    work, and are left to their own cell."""
    for m in BENCH["per_layer"]:
        if m["name"].startswith(("mla_decode_roofline", "mla_flash_roofline",
                                 "mla_prefill_pass", "mla_decode_bytes")):
            assert CELL not in m["workloads"], m["name"]


# ----------------------------------------------------- readers on records
@pytest.mark.parametrize("what", ["update_kernel", "decode_bytes", "pass"])
def test_a_roofline_without_a_trace_gives_nothing_and_does_not_raise(what):
    cell = cell_mod.load_cell(CELL)
    reader = cell_mod.load_module("readers", "gdn_roofline")
    ctx = {"cell": cell, "log": print, "trace": None,
           "peaks": cell_mod.load_peaks("TPU v5 lite")}
    assert reader.read(ctx, what=what, op_pattern="^x") is None
    assert reader.read(dict(ctx, peaks={}), what=what) is None


def test_the_rooflines_count_the_paired_records(monkeypatch):
    """`paired.whole_programs` handed in: two decode programs of 25 ms and
    one prefill of 250 ms with their records."""
    cell = cell_mod.load_cell(CELL)
    pub, peaks = cell.config, cell_mod.load_peaks("TPU v5 lite")
    reader = cell_mod.load_module("readers", "gdn_roofline")
    dec = dict(kind="decode", k=1, rows=(("a", 1, 900), ("b", 1, 1800)),
               gdn_layers=4, mla_layers=1, moe_assignments=3,
               moe_experts_touched=3)
    pre = dict(kind="prefill", k=1, rows=(("c", 904, 5000),), gdn_layers=4,
               mla_layers=1, mla_ctx_chunks=(1,), moe_assignments=512,
               moe_experts_touched=60)
    whole = {"decode": [(("decode", 0, 25_000_000), dec)] * 2,
             "prefill": [(("prefill", 0, 250_000_000), pre)]}
    monkeypatch.setattr(paired, "whole_programs",
                        lambda ctx, kind, what: whole[kind])
    monkeypatch.setattr(paired, "op_self_ns",
                        lambda ctx, whole, pattern: 4_000_000 * len(whole))
    ctx = {"cell": cell, "log": print, "peaks": peaks, "trace": object()}
    hbm, mxu = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    assert reader.read(ctx, what="update_kernel", op_pattern="x") == \
        pytest.approx(100 * 2 * gdn_work.update_kernel(2, 1, pub)["bytes"]
                      / hbm / 0.008)
    assert reader.read(ctx, what="decode_bytes") == pytest.approx(
        100 * 2 * gdn_work.decode_step_bytes(pub, [900, 1800], 3) / hbm
        / 0.050)
    assert reader.read(ctx, what="pass") == pytest.approx(
        100 * gdn_work.pass_ops(904, 5000, 1, 512, pub) / mxu / 0.250)
    # the expert metric under its new name is the latent family's reader
    gmm = cell_mod.load_module("readers", "mla_roofline")
    g = mla_work.gmm_work(pub, 512 + 6, 60 + 6)
    assert gmm.read(ctx, what="gmm", op_pattern="x") == pytest.approx(
        100 * max(g["ops"] / mxu, g["bytes"] / hbm) / 0.012)
    # records of another family (the parent's program): nothing, no error
    whole["decode"] = [(("decode", 0, 1), dict(dec, gdn_layers=None))]
    assert reader.read(ctx, what="update_kernel", op_pattern="x") is None


# ------------------------------------------------------------- the control
def _tiny_cell():
    """The cell with its configuration shrunk, in memory, to the
    `tiny-gigachat` preset's sizes: the recipe for a CPU rehearsal
    (`cell.rehearsal = True`, then `run.run_cell`)."""
    cell = cell_mod.load_cell(CELL)
    c = cell.config
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
             moe_intermediate_size=32, num_experts_per_tok=4, vocab_size=256,
             num_hidden_layers=6, n_routed_experts=4,
             full_attention_layers=[3, 7], first_k_dense_replace=2,
             linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=16, linear_value_head_dim=16,
             dtype="float32", program_preset="tiny-gigachat")
    c["published"] = dict(c["published"], n_routed_experts=16)
    c["held"] = {"layers": [1, 7], "experts": [4, 8], "vocab_rows": [0, 256]}
    c["rope_scaling"] = dict(c["rope_scaling"],
                             original_max_position_embeddings=64)
    c["engine"] = dict(page_size=16, num_pages=128, max_model_len=512,
                       max_batch=4, prefill_buckets=[32, 64])
    c["engine_facts"] = dict(c["engine_facts"], ctx_chunk_tokens=32)
    c["limits"] = dict.fromkeys(c["limits"], 1e-3)
    c["limits"]["logit_max_err_over_rms"] = 1e-2
    cell.traffic.update(
        prompt_len={"dist": "lognormal", "median": 40, "sigma": 1.0,
                    "min": 8, "max": 200},
        output_len={"dist": "lognormal", "median": 24, "sigma": 0.7,
                    "min": 4, "max": 96},
        ramp_s=1, arrivals={"process": "backlog", "max_rate_per_s": 400})
    cell.traffic["check"] = {
        "prompt_lens": [30, 100, 200], "decode_tokens": 4,
        "engine_prompts": {"count": 3, "min_len": 70, "max_len": 130,
                           "decode_tokens": 5}}
    cell.rehearsal = True
    return cell


def test_sound_program_passes_and_the_control_is_refused_at_tiny_size():
    from chipbench.runners import engine_gdn, engine_mla

    cell = _tiny_cell()
    runner = engine_gdn.Runner(cell, 3_000_000_019, 2, lambda msg: None)
    check = runner.setup(warm=False)
    assert check["correct"], check["numbers"]
    assert {r["name"] for r in check["numbers"]} == set(cell.config["limits"])
    assert engine_mla.AGREE in cell.config["limits"]
    notes = check["notes"]
    # five expert layers in three runs, in the model's order
    assert notes["selection_sets"] == 5 * (33 + 103 + 203)
    assert notes["selection_differs_share"] == 0.0
    ref, cfg = runner.reference, dict(runner.published)
    weights = ref.weights_from_program_tree(runner.engine.params)
    res = engine_gdn.control_numbers(ref, weights, cfg, "bfloat16",
                                     runner.check_sample,
                                     cell.config["limits"])
    refused = [r["name"] for r in res["numbers"] if not r["ok"]]
    assert "logit_rel_rms_err" in refused, res["numbers"]
    same = engine_gdn.control_numbers(ref, weights, cfg, "float32",
                                      runner.check_sample,
                                      cell.config["limits"])
    assert all(r["value"] == 0.0 for r in same["numbers"]), same["numbers"]
    runner.engine.close()


def test_a_program_without_the_family_is_refused_at_once(monkeypatch):
    import importlib.util

    from chipbench.cell import BenchError
    from chipbench.runners import engine_gdn

    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        None if name == "ray_tpu.models.gigachat" else real(name, *a)))
    with pytest.raises(BenchError, match="gated-delta-net"):
        engine_gdn.Runner(cell_mod.load_cell(CELL), 1, 1, print)


def test_a_switch_the_program_has_one_setting_of_is_refused():
    from chipbench.cell import BenchError
    from chipbench.runners import engine_gdn

    cell = cell_mod.load_cell(CELL)
    cell.config["num_nextn_predict_layers"] = 2
    with pytest.raises(BenchError, match="num_nextn_predict_layers"):
        engine_gdn.Runner(cell, 1, 1, print)
