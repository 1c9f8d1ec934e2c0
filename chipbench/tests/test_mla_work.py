"""The cell `kimi-k2.5-longdoc`: its work counts against a hand count, its
files against the contract and the catalog, its readers on hand-made
records (positions by `FIELDS.index`), and its control refused at a tiny
size."""

import json
import os

import pytest

from chipbench import cell as cell_mod
from chipbench import mla_work, paired
from chipbench.cell import HERE, load_json

CELL = "kimi-k2.5-longdoc"
BENCH = cell_mod.load_benchmark()
NEW = ("mla_decode_roofline.serve_tok_s", "mla_decode_time_pct.serve_tok_s",
       "mla_flash_roofline.serve_tok_s",
       "mla_prefill_pass_roofline.serve_tok_s",
       "mla_decode_bytes_roofline.serve_tok_s",
       "expert_share_gmm_roofline.serve_tok_s",
       "expert_share_held_pct.serve_tok_s")
JOINED = ("kv_pages_peak_pct.serve_tok_s", "prefill_dispatch_ms.serve_tok_s",
          "device_idle_pct.serve_tok_s", "prefill_pad_pct.serve_tok_s")


@pytest.fixture(scope="module")
def pub():
    return cell_mod.load_cell(CELL).config


def test_the_cut_is_the_issues_arithmetic(pub):
    # a layer's attention: q_a, q_b, kv_a, kv_b, o
    attn = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256
            + 64 * 128 * 7168)
    assert mla_work.attn_params(pub) == attn == 101_122_048
    assert mla_work.layer_counts(pub) == {"dense": 1, "moe": 5}
    # what every token multiplies outside the routed experts and the head:
    # attention x 6, the dense FFN, 5 x (router over 384 + shared expert)
    per_token = 6 * attn + 3 * 7168 * 18432 + 5 * (7168 * 384
                                                   + 3 * 7168 * 2048)
    assert mla_work.token_params(pub) == per_token == 1_237_057_536
    # the held matrices: + 5 layers x 12 experts, the embedding and head
    held = per_token + 5 * 12 * 3 * 7168 * 2048 + 2 * 20480 * 7168
    assert round(held / 1e9, 3) == 4.173
    assert mla_work.latent_width(pub) == 576
    assert mla_work.decode_weight_bytes(pub) == 2 * (per_token
                                                     + 7168 * 20480)


def test_the_decode_kernel_reads_a_latent_once_for_all_heads(pub):
    # one row of 18,000 tokens, one step, six layers
    w = mla_work.decode_kernel(18000, 1, pub)
    assert w["bytes"] == 6 * 2 * (576 * 18000 + 64 * (576 + 512))
    assert w["ops"] == 6 * 64 * 2 * (576 + 512) * 18000
    # 121 operations a latent byte: under the chip's 240, memory-bound
    assert round(w["ops"] / w["bytes"]) == 120
    # fused steps see one token more each
    two = mla_work.decode_kernel(18000, 2, pub)
    assert two["ops"] == 6 * 64 * 2 * 1088 * (18000 + 18001)
    # per-head keys and values would be 64 x 320 values a token: 35.6 x
    assert round(64 * 320 / 576, 1) == 35.6


def test_the_flash_forward_counts_real_pairs_at_192_and_128(pub):
    assert mla_work.real_pairs(4096, 4096) == 4096 * 4097 // 2
    assert mla_work.real_pairs(100, 8292) == 100 * 101 // 2 + 100 * 8192
    assert mla_work.flash_ops(1000, pub) == 6 * 64 * 2 * 320 * 1000


def test_a_pass_counts_its_tokens_its_chunks_and_its_held_assignments(pub):
    # the table's 529 pages of 64 tokens in chunks of 4096: eight whole
    # and one of 17 pages
    assert mla_work.chunk_tokens(1, pub) == 4096
    assert mla_work.chunk_tokens(8, pub) == 32768
    assert mla_work.chunk_tokens(9, pub) == 529 * 64 == 33856
    base = mla_work.pass_ops(4096, 4096, 0, 0, pub)
    assert base == 2 * 1_237_057_536 * 4096 + mla_work.flash_ops(
        4096 * 4097 // 2, pub)
    # a resumed pass behind 8192 tokens materialised two chunks: W_kvb over
    # them in six layers, and the pairs with the context
    more = mla_work.pass_ops(4096, 12288, 2, 1024, pub) - base
    assert more == (2 * 512 * 64 * 256 * 6 * 8192
                    + 2 * 3 * 7168 * 2048 * 1024
                    + mla_work.flash_ops(4096 * 8192, pub))


def test_a_decode_step_reads_weights_touched_experts_and_latents(pub):
    step = mla_work.decode_step_bytes(pub, [18000] * 24, 24)
    assert step == (mla_work.decode_weight_bytes(pub)
                    + 24 * 2 * 3 * 7168 * 2048
                    + 2 * 576 * 6 * 24 * 18000)
    # an expert is 88.1 MB; the issue's 2.8 GB + 2.1 GB + 3.0 GB
    assert round(2 * 3 * 7168 * 2048 / 1e6, 1) == 88.1
    assert round(mla_work.decode_weight_bytes(pub) / 1e9, 1) == 2.8
    assert round(2 * 576 * 6 * 24 * 18000 / 1e9, 1) == 3.0
    w = mla_work.gmm_work(pub, 1024, 60)
    assert w["ops"] == 2 * 3 * 7168 * 2048 * 1024
    assert w["bytes"] == 2 * (3 * 7168 * 2048 * 60
                              + (7168 + 2 * 2048 + 2048 + 7168) * 1024)


def test_configuration_keeps_the_catalogs_keys_and_cuts_what_it_says(pub):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if '"name": "Kimi-K2.5"' in line)
    differ = {k for k, v in row["config"].items() if pub.get(k) != v}
    assert differ == {"num_hidden_layers", "n_routed_experts",
                      "vocab_size"} == set(pub["reduced"])
    assert pub["published"] == {k: row["config"][k] for k in differ}
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (6, 12, 20480)
    assert pub["held"] == {"layers": [0, 6], "experts": [0, 12],
                           "vocab_rows": [0, 20480]}
    assert pub["source"] == row["source_url"]
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "kimi-k2.5-serve")
    assert entry["reduced"] == pub["reduced"]
    assert entry["source"] == pub["source"]
    for key in ("tower", "rotated_pairing", "share", "vocabulary", "weights",
                "router", "absorbed_form"):
        assert pub["assumed"][key], key
    assert "32 chips" in pub["stands_for"] and "stages of 6" in pub[
        "stands_for"]
    eng = pub["engine"]
    assert eng == {"page_size": 64, "num_pages": 8192, "max_model_len": 33856,
                   "max_batch": 24,
                   "prefill_buckets": [512, 1024, 2048, 4096]}
    for name in ("runner", "reference"):
        assert os.path.isfile(os.path.join(HERE, name + "s",
                                           pub[name] + ".py"))
    assert pub["limits_why"] and len(pub["limits"]) >= 3


def test_the_mix_and_the_cell_are_the_issues():
    from chipbench import generator

    cell = cell_mod.load_cell(CELL)
    mix = cell.traffic
    assert cell.chips == 1 and cell.traffic_name == "longdoc-kimi"
    assert mix["arrivals"]["process"] == "backlog" and mix["block"] == 16
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 16384,
                                 "sigma": 0.5, "min": 8192, "max": 32768}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert (mix["ramp_s"], mix["grace_s"]) == (40, 0)
    assert mix["trace"] == {"start_share": 0.5, "seconds": 12.0}
    assert mix["check"]["prompt_lens"] == [3000, 9000, 20000]
    sched = generator.make_schedule(mix, 2147483659, 50.0, 20480)
    assert len(sched) == 160 and all(r.due_s == -40.0 for r in sched)
    lens = [len(r.prompt_ids) for r in sched[:16]]
    assert min(lens) >= 8192 and max(lens) <= 32768
    assert max(max(r.prompt_ids) for r in sched[:4]) < 20480
    # the longest prompt and answer fit a sequence
    assert 32768 + 1024 <= cell.config["engine"]["max_model_len"]
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "1/32" in entry["why"]
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


def test_the_other_families_expert_and_decode_metrics_do_not_list_the_cell():
    # they match `_decode_call`, count at `intermediate_size` or read the
    # other families' fields
    for m in BENCH["per_layer"]:
        if m["name"].startswith(("moe_gmm", "moe_experts", "paged_decode",
                                 "expert_gmm", "flash_fwd", "sparse_",
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
                   rows=(("a", 1, 9000), ("b", 1, 18000)))
        rec.update(over)
        tracing.record("engine.dispatch", tuple(rec[f] for f in fields))
    return {"cell": cell, "runner": R(), "seconds": 2.0, "log": print,
            "trace": None, "peaks": cell_mod.load_peaks("TPU v5 lite")}


def test_the_held_share_is_read_off_the_windows_records():
    from ray_tpu.util import tracing

    tracing.reset_ring()
    cell = cell_mod.load_cell(CELL)
    reader = cell_mod.load_module("readers", "expert_share_held_pct")
    fields = tracing.FIELDS["engine.dispatch"]
    # the family's four sit behind every other family's, before the stamps
    assert fields.index("moe_assignments_routed") + 1 == fields.index(
        "enqueued_ns")
    ctx = _ctx([dict(moe_assignments=3, moe_experts_touched=3,
                     moe_assignments_routed=2 * 8 * 5, mla_layers=6),
                dict(kind="prefill", rows=(("c", 4096, 8192),),
                     moe_assignments=1000, moe_experts_touched=60,
                     moe_assignments_routed=4096 * 8 * 5, mla_layers=6,
                     mla_ctx_chunks=(1,))], cell)
    assert reader.read(ctx) == pytest.approx(
        100 * 1003 / (80 + 4096 * 40))
    # a program without the fields (the parent, another family) gives
    # nothing, and no error
    tracing.reset_ring()
    assert reader.read(_ctx([dict(moe_assignments=5)], cell)) is None
    tracing.reset_ring()


@pytest.mark.parametrize("what", ["decode_kernel", "flash", "pass",
                                  "decode_bytes", "gmm"])
def test_a_roofline_without_a_trace_gives_nothing_and_does_not_raise(what):
    from ray_tpu.util import tracing

    tracing.reset_ring()
    cell = cell_mod.load_cell(CELL)
    reader = cell_mod.load_module("readers", "mla_roofline")
    assert reader.read(_ctx([dict(mla_layers=6)], cell), what=what,
                       op_pattern="^x") is None
    tracing.reset_ring()


def test_the_rooflines_count_the_paired_records(monkeypatch):
    """`paired.whole_programs` handed in: two decode programs of 10 ms and
    one prefill of 300 ms with their records."""
    cell = cell_mod.load_cell(CELL)
    pub, peaks = cell.config, cell_mod.load_peaks("TPU v5 lite")
    reader = cell_mod.load_module("readers", "mla_roofline")
    dec = dict(kind="decode", k=1, rows=(("a", 1, 9000), ("b", 1, 18000)),
               mla_layers=6, moe_assignments=3, moe_experts_touched=3)
    pre = dict(kind="prefill", k=1, rows=(("c", 4096, 12288),), mla_layers=6,
               mla_ctx_chunks=(2,), moe_assignments=1024,
               moe_experts_touched=60)
    whole = {"decode": [(("decode", 0, 10_000_000), dec)] * 2,
             "prefill": [(("prefill", 0, 300_000_000), pre)]}
    monkeypatch.setattr(paired, "whole_programs",
                        lambda ctx, kind, what: whole[kind])
    monkeypatch.setattr(paired, "op_self_ns",
                        lambda ctx, whole, pattern: 4_000_000 * len(whole))
    ctx = {"cell": cell, "log": print, "peaks": peaks, "trace": object()}
    hbm, mxu = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    k = [mla_work.decode_kernel(c, 1, pub) for c in (9000, 18000)]
    least = 2 * max(sum(w["bytes"] for w in k) / hbm,
                    sum(w["ops"] for w in k) / mxu)
    assert reader.read(ctx, what="decode_kernel", op_pattern="x") == \
        pytest.approx(100 * least / 0.008)
    assert reader.read(ctx, what="decode_bytes") == pytest.approx(
        100 * 2 * mla_work.decode_step_bytes(pub, [9000, 18000], 3) / hbm
        / 0.020)
    assert reader.read(ctx, what="flash", op_pattern="x") == pytest.approx(
        100 * mla_work.flash_ops(mla_work.real_pairs(4096, 12288), pub)
        / mxu / 0.004)
    assert reader.read(ctx, what="pass") == pytest.approx(
        100 * mla_work.pass_ops(4096, 12288, 2, 1024, pub) / mxu / 0.300)
    g = mla_work.gmm_work(pub, 1024 + 6, 60 + 6)
    assert reader.read(ctx, what="gmm", op_pattern="x") == pytest.approx(
        100 * max(g["ops"] / mxu, g["bytes"] / hbm) / 0.012)
    # records of another family: nothing, no error
    whole["decode"] = [(("decode", 0, 1), dict(dec, mla_layers=None))]
    assert reader.read(ctx, what="decode_kernel", op_pattern="x") is None


# ------------------------------------------------------------- the control
def _tiny_cell():
    cell = cell_mod.load_cell(CELL)
    c = cell.config
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
             moe_intermediate_size=32, num_experts_per_tok=4, vocab_size=256,
             num_hidden_layers=3, n_routed_experts=4, dtype="float32",
             program_preset="tiny-kimi")
    c["published"] = dict(c["published"], n_routed_experts=16)
    c["held"] = {"layers": [0, 3], "experts": [4, 8], "vocab_rows": [0, 256]}
    c["rope_scaling"] = dict(c["rope_scaling"],
                             original_max_position_embeddings=64)
    c["engine"] = dict(page_size=16, num_pages=128, max_model_len=512,
                       max_batch=4, prefill_buckets=[32, 64])
    c["engine_facts"] = dict(c["engine_facts"], ctx_chunk_tokens=32)
    c["limits"] = dict.fromkeys(c["limits"], 1e-3)
    c["limits"]["logit_max_err_over_rms"] = 1e-2
    cell.traffic["check"] = {
        "prompt_lens": [30, 100, 200], "decode_tokens": 4,
        "engine_prompts": {"count": 3, "min_len": 70, "max_len": 130,
                           "decode_tokens": 5}}
    cell.rehearsal = True
    return cell


def test_sound_program_passes_and_the_control_is_refused_at_tiny_size():
    from chipbench.runners import engine_mla

    cell = _tiny_cell()
    runner = engine_mla.Runner(cell, 3_000_000_019, 2, lambda msg: None)
    check = runner.setup(warm=False)
    assert check["correct"], check["numbers"]
    assert {r["name"] for r in check["numbers"]} == set(cell.config["limits"])
    assert engine_mla.AGREE in cell.config["limits"]
    notes = check["notes"]
    assert notes["selection_sets"] == 2 * (33 + 103 + 203)
    assert notes["selection_differs_share"] == 0.0
    ref, cfg = runner.reference, dict(runner.published)
    weights = ref.weights_from_program_tree(runner.engine.params)
    res = engine_mla.control_numbers(ref, weights, cfg, "bfloat16",
                                     runner.check_sample,
                                     cell.config["limits"])
    refused = [r["name"] for r in res["numbers"] if not r["ok"]]
    assert "logit_rel_rms_err" in refused, res["numbers"]
    same = engine_mla.control_numbers(ref, weights, cfg, "float32",
                                      runner.check_sample,
                                      cell.config["limits"])
    assert all(r["value"] == 0.0 for r in same["numbers"]), same["numbers"]
    runner.engine.close()


def test_a_program_without_the_family_is_refused_at_once(monkeypatch):
    import importlib.util

    from chipbench.cell import BenchError
    from chipbench.runners import engine_mla

    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        None if name == "ray_tpu.models.kimi" else real(name, *a)))
    with pytest.raises(BenchError, match="latent attention"):
        engine_mla.Runner(cell_mod.load_cell(CELL), 1, 1, print)
