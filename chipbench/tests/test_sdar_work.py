"""The cell `sdar-30b-a3b-chat`: its work counts against a hand count, its
files against the contract and the catalog, its readers on hand-made
records, and its control refused at a tiny size."""

import json
import os

import pytest

from chipbench import cell as cell_mod
from chipbench import sdar_work
from chipbench.cell import HERE, ROOT, load_json

CELL = "sdar-30b-a3b-chat"
BENCH = cell_mod.load_benchmark()
NEW = ("block_passes_per_token.tpot", "block_dispatch_ms.tpot",
       "block_bytes_roofline.tpot", "expert_gmm_roofline.tpot",
       "expert_gmm_roofline.ttft", "expert_gmm_time_pct.tpot",
       "block_attn_roofline.tpot", "experts_touched_pct.tpot")


@pytest.fixture(scope="module")
def pub():
    return cell_mod.load_cell(CELL).config


def test_byte_counts_are_the_hand_count(pub):
    # a layer outside its experts: q 2048 x 4096, k and v 2048 x 512 each,
    # o 4096 x 2048 in bf16, two head-dim norms, two layer norms, and the
    # router 2048 x 128 in float32
    attn = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert sdar_work.layer_dense_bytes(pub) == (
        2 * (attn + 2 * 128 + 2 * 2048) + 4 * 2048 * 128) == 38_806_016
    assert sdar_work.expert_bytes(pub) == 2 * 3 * 2048 * 768 == 9_437_184
    assert sdar_work.head_bytes(pub) == 2 * (2048 * 151936 + 2048)
    assert sdar_work.kv_token_bytes(pub) == 2 * 2 * 4 * 128 == 2048
    # one pass of 64 rows x 4 tokens that touches every expert, 64 rows of
    # 500 tokens of context: the issue's 7.48 GB of layers + 0.62 GB of head
    one = sdar_work.forward_bytes(pub, 1, 6 * 128, 64 * 500, 256)
    layers = 6 * (38_806_016 + 128 * 9_437_184)
    kv = 6 * 2048 * 64 * 500
    assert one == layers + 2 * (2048 * 151936 + 2048) + 256 * 4096 + kv
    assert round(layers / 1e9, 2) == 7.48 and round(one / 1e9, 2) == 8.5
    # five passes read everything five times; an untouched expert is not read
    assert sdar_work.forward_bytes(pub, 5, 5 * 6 * 128, 64 * 500, 256) \
        == 5 * one
    assert one - sdar_work.forward_bytes(pub, 1, 6 * 128 - 1, 64 * 500,
                                         256) == 9_437_184


def test_gmm_work_is_counted_at_the_experts_width(pub):
    # 2048 assignments (64 rows x 4 tokens x 8) on 128 experts
    w = sdar_work.gmm_work(pub, 2048, 128)
    assert w["ops"] == 2 * 3 * 2048 * 768 * 2048
    assert w["bytes"] == 2 * (3 * 2048 * 768 * 128
                              + (2048 + 2 * 768 + 768 + 2048) * 2048)
    # the dense width (6144, used by no layer) would count 8 times as much
    assert pub["intermediate_size"] == 8 * pub["moe_intermediate_size"]


def test_block_attention_reads_each_rows_context_once_a_pass(pub):
    got = sdar_work.block_attn_bytes(pub, passes=5, ctx_tokens=64 * 500,
                                     rows=64, block=4)
    q = 2 * 64 * 4 * 32 * 128 * 2
    assert got == 5 * 6 * (2048 * 64 * 500 + q)


def test_configuration_keeps_the_catalogs_keys_and_cuts_depth_only(pub):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if '"SDAR-30B-A3B-Chat"' in line)
    differ = {k for k, v in row["config"].items() if pub.get(k) != v}
    assert differ == {"num_hidden_layers"} == set(pub["reduced"])
    assert pub["num_hidden_layers"] == 6 and pub["source"] == row["source_url"]
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "sdar-30b-a3b-serve")
    assert entry["reduced"] == pub["reduced"]
    assert entry["source"] == pub["source"]
    gen = pub["generation"]
    assert (gen["block_length"], gen["denoising_steps"], gen["remasking"]) \
        == (4, 4, "low_confidence_static")
    eng = pub["engine"]
    assert eng["page_size"] % gen["block_length"] == 0
    assert eng["max_model_len"] % gen["block_length"] == 0
    assert eng["num_pages"] * eng["page_size"] >= (
        eng["max_batch"] * eng["max_model_len"])
    for name in ("runner", "reference"):
        kind = name + "s"
        assert os.path.isfile(os.path.join(HERE, kind, pub[name] + ".py"))
    assert len(pub["limits"]) == 4 and pub["limits_why"]


def test_the_mix_and_the_cell_are_the_issues():
    cell = cell_mod.load_cell(CELL)
    mix = cell.traffic
    assert cell.chips == 1 and cell.traffic_name == "chat-sdar"
    assert mix["arrivals"]["process"] == "quantile_exponential"
    rate = mix["arrivals"]["rate_per_s"]
    assert rate * 4 == int(rate * 4)                  # a quarter
    chat = load_json(os.path.join(HERE, "traffic", "chat.json"))
    assert mix["prompt_len"] == chat["prompt_len"]
    assert mix["output_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.6, "min": 128, "max": 1024}
    assert (mix["ramp_s"], mix["grace_s"]) == (16, 90)
    assert mix["trace"] == {"start_share": 0.5, "seconds": 4.0}
    assert [n % 4 for n in mix["check"]["prompt_lens"]] == [1, 2, 3, 0]
    assert set(mix.get("engine", {})) <= {"max_model_len", "prefill_buckets"}
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert f"{rate:g}/s" in entry["why"] and len(entry["why"]) <= 200
    assert {m.name for m in cell.end_to_end} == {
        "ttft_ms_p95", "tpot_ms_p95", "setup_s"}


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_lists_this_cell_only_and_finds_its_reader(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    spec = load_json(os.path.join(HERE, "layer_metrics", name + ".json"))
    assert os.path.isfile(os.path.join(HERE, "readers",
                                       spec["reader"] + ".py"))
    for key in ("layer", "moves", "unit", "source"):
        assert spec[key] == entry[key], key
    if name.endswith("roofline.tpot") or name.endswith("roofline.ttft"):
        assert entry["unit"] == "%"


def test_the_existing_expert_metrics_do_not_list_the_cell():
    # readers/moe_gmm_roofline.py counts at `intermediate_size` (6144 here)
    for m in BENCH["per_layer"]:
        if m["name"].startswith(("moe_gmm", "moe_experts", "paged_decode",
                                 "decode_dispatch")):
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
        rec.update(seq=i, kind="block", dispatch_ns=now - 10 ** 8, k=5,
                   rows=(("a", 4, 40), ("b", 4, 80)))
        rec.update(over)
        tracing.record("engine.dispatch", tuple(rec[f] for f in fields))
    return {"cell": cell, "runner": R(), "seconds": 2.0, "log": print}


def test_ring_readers_sum_over_the_windows_block_records():
    from ray_tpu.util import tracing

    tracing.reset_ring()
    cell = cell_mod.load_cell(CELL)
    reader = cell_mod.load_module("readers", "ring_block_ratio")
    ctx = _ctx([dict(block_passes=5, block_tokens_fixed=8,
                     moe_experts_touched=5 * 6 * 100),
                dict(block_passes=3, block_tokens_fixed=8,
                     moe_experts_touched=3 * 6 * 128)], cell)
    assert reader.read(ctx, num="block_passes",
                       den="block_tokens_fixed") == 0.5
    # a pass is computed for every live row: (5 + 3) x 2 rows for 16 tokens
    assert reader.read(ctx, num="block_passes", den="block_tokens_fixed",
                       num_x_rows=True) == 1.0
    got = reader.read(ctx, num="moe_experts_touched",
                      den_passes_x_experts=True, scale=100.0)
    assert got == pytest.approx(100 * (3000 + 2304) / (8 * 6 * 128))
    # a program without the fields (the parent) gives nothing, and no error
    tracing.reset_ring()
    assert reader.read(_ctx([dict(kind="decode")], cell),
                       num="block_passes", den="block_tokens_fixed") is None
    tracing.reset_ring()


# ------------------------------------------------------------- the control
def _tiny_cell():
    cell = cell_mod.load_cell(CELL)
    c = cell.config
    c.update(vocab_size=256, hidden_size=64, intermediate_size=128,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_experts=16,
             num_experts_per_tok=4, moe_intermediate_size=48,
             program_preset="tiny-sdar", dtype="float32")
    c["generation"]["mask_token_id"] = 255
    c["engine"] = dict(page_size=16, num_pages=200, max_model_len=512,
                       max_batch=8, prefill_buckets=[128, 256, 512])
    c["limits"] = dict.fromkeys(c["limits"], 1e-3)
    c["limits"]["logit_max_err_over_rms"] = 1e-2
    cell.traffic["check"].update(prompt_lens=[41, 130, 251, 300], blocks=3,
                                 blocks_after_longest=2)
    cell.traffic["check"]["engine_prompts"].update(count=4)
    cell.rehearsal = True
    return cell


@pytest.mark.parametrize("seed", [5, 3_000_000_019])
def test_sound_program_passes_and_the_control_is_refused_at_tiny_size(seed):
    from chipbench.runners import engine_diffusion as ed
    from chipbench.tools import read_limits_sdar

    cell = _tiny_cell()
    runner = ed.Runner(cell, seed, 2, lambda msg: None)
    check = runner.setup(warm=False)
    assert check["correct"], check["numbers"]
    assert {r["name"] for r in check["numbers"]} == set(cell.config["limits"])
    assert check["notes"]["tokens_checked"] >= 4 * 15
    res = read_limits_sdar.control_numbers(runner, "bfloat16",
                                           cell.config["limits"])
    refused = [r["name"] for r in res["numbers"] if not r["ok"]]
    assert len(refused) >= 3, res["numbers"]
    same = read_limits_sdar.control_numbers(runner, "float32",
                                            cell.config["limits"])
    assert all(r["value"] == 0.0 for r in same["numbers"]), same["numbers"]
    runner.engine.close()


def test_a_program_without_the_block_step_is_refused_at_once(monkeypatch):
    import importlib.util

    from chipbench.cell import BenchError
    from chipbench.runners import engine_diffusion as ed

    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        None if name == "ray_tpu.models.sdar" else real(name, *a)))
    with pytest.raises(BenchError, match="diffusion over blocks"):
        ed.Runner(cell_mod.load_cell(CELL), 1, 1, print)
