"""Every data file loads, is found by the name BENCHMARK.json gives, and
BENCHMARK.json keeps to the contract's shapes."""

import glob
import json
import os
import re

import pytest

from chipbench import cell as cell_mod
from chipbench.cell import HERE, ROOT, load_json

BENCH = cell_mod.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _cells():
    return [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(HERE, "**", "*.json"), recursive=True)
    + [os.path.join(ROOT, "BENCHMARK.json")]))
def test_json_loads(path):
    load_json(path)
    rel = os.path.relpath(path, ROOT)
    assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    cells = len(BENCH["workloads"])
    # the contract's budget at the full 24 cells
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert 1 <= cells <= 24
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for word in BENCH["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/")


def test_names_units_and_one_line_texts():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
            # one-line texts: every `why` and `layer`, a configuration's
            # `source` (a metric's `source` is an enum, checked below)
            for key in ("why", "layer") + (("source",) if group == "configs"
                                           else ()):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                        and "\t" not in e[key], (e["name"], key, len(e[key]))
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(pairs) // 4)


def test_configs_are_files_with_every_reduced_key_and_no_width_cut():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"].startswith("chipbench/") and c["file"] not in files
        files.add(c["file"])
        body = load_json(os.path.join(ROOT, c["file"]))
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in body
            assert not re.search(r"(_dim|_rank|_size)$|^head_dim$", key), key
        # the published widths of Mistral-7B-v0.3, none cut
        assert (body["hidden_size"], body["intermediate_size"],
                body["num_attention_heads"], body["num_key_value_heads"],
                body["head_dim"], body["vocab_size"]) == (
                    4096, 14336, 32, 8, 128, 32768)
        assert body["rope_theta"] == 1e6 and body["rms_norm_eps"] == 1e-5
        assert "assumed" in body and "stands_for" in body


def test_a_mix_forces_only_lengths_and_limits_are_all_set():
    from chipbench.runners.engine import FORCED_BY_MIX

    for path in glob.glob(os.path.join(HERE, "traffic", "*.json")):
        assert set(load_json(path).get("engine", {})) <= set(FORCED_BY_MIX)
    for path in glob.glob(os.path.join(HERE, "configs", "*.json")):
        limits = load_json(path)["limits"]
        assert limits and all(isinstance(v, (int, float))
                              for v in limits.values()), path


def test_scheduling_knobs_are_never_set_by_a_configuration_or_a_mix():
    knobs = {"decode_steps_per_dispatch", "pipeline_depth",
             "prefill_wave_size", "prefill_chunk_tokens", "spec_lookahead"}
    for path in glob.glob(os.path.join(HERE, "configs", "*.json")) \
            + glob.glob(os.path.join(HERE, "traffic", "*.json")):
        engine = load_json(path).get("engine", {})
        assert not knobs & set(engine), path


@pytest.mark.parametrize("name", _cells() + ["tiny-chat", "tiny-docbatch",
                                             "tiny-pretrain"])
def test_every_cell_finds_its_files_and_its_metrics(name):
    cell = cell_mod.load_cell(name)
    assert os.path.isfile(os.path.join(HERE, "runners", cell.runner + ".py"))
    assert os.path.isfile(os.path.join(
        HERE, "references", cell.config["reference"] + ".py"))
    e2e = {m.name for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert os.path.isfile(os.path.join(
            HERE, "readers", m.spec["reader"] + ".py")), m.name
    for m in cell.per_layer:
        assert m.moves in e2e, (m.name, m.moves)
        assert m.spec["layer"] == m.layer and m.spec["moves"] == m.moves


def test_every_moves_names_a_metric_all_of_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        target = e2e[m["moves"]].get("workloads") or _cells()
        for cell in m.get("workloads") or _cells():
            assert cell in target, (m["name"], cell)
    for m in BENCH["end_to_end"]:
        for cell in m.get("workloads", []):
            assert cell in _cells()


def test_metrics_of_one_layer_spell_it_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert len({l.lower() for l in layers}) == len(layers)


def test_unknown_device_kind_is_an_error():
    assert cell_mod.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(cell_mod.BenchError):
        cell_mod.load_peaks("TPU v9 imaginary")
