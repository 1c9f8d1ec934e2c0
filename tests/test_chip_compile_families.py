"""Compile the serving engine's programs of the families with two or three
kinds of pool (Mellum2, Laguna, GigaChat3.5, ZAYA1) for a described (not
attached) TPU v5e, at real widths, as their cells run them. The rest of the
engines' programs, what a compile says and the two helpers used here:
tests/test_chip_compile_engines.py. A file of its own so that a worker
takes one and another the other: together they are 1,100 s of the TPU
compiler's time.
"""

import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from test_chip_compile_engines import HBM_GIB, _array_types


# ------------- sliding-window and full attention layers, rings beside pages
MELLUM_PAGE, MELLUM_PAGES, MELLUM_LEN = 64, 16384, 33792


def _compile_two_kind_program(topo, shape_engine, engine_cfg: dict,
                              layers: int, pages: int, kind: str, key):
    """A shape-only engine of a family with pages and rings (models/
    mellum.py, models/laguna.py), its `kind` program compiled for the
    described chip over a pool of `pages` pages -> (compiled, the pool's
    spec, the program's key)."""
    from ray_tpu.serve.llm import EngineConfig

    cfg = EngineConfig(**engine_cfg)
    engine = shape_engine(cfg)
    stage = engine.compute
    spec = stage.family.pool_spec(stage.model_cfg, layers, pages,
                                  cfg.page_size, cfg.max_batch)
    stage.kv_pages = {k: jax.ShapeDtypeStruct(*sd) for k, sd in spec.items()}
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    key = (engine._decode_shape_key() if kind == "decode"
           else (key[0], engine._wave_rb, key[1]))
    with pytest.MonkeyPatch.context() as mp_ctx:
        mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
        compiled = stage.program(kind, key).lower(*jax.tree.map(
            sds, (*stage._state(kind), *stage.dummy_args(kind, key)),
            is_leaf=lambda a: isinstance(a, jax.ShapeDtypeStruct))
        ).compile()
    return compiled, spec, key


@pytest.mark.parametrize("kind, key", [
    ("decode", None), ("prefill", (4096, MELLUM_LEN // MELLUM_PAGE))])
def test_mellum_program_fits_and_carries_both_pools_in_place(
        topo, no_persistent_cache, shape_engine, kind, key):
    """Mellum2-12B-A2.5B-Instruct at its published widths as the cell
    `mellum2-mixedctx` runs it: layers 0-7 (two periods of three sliding
    layers and a full one), all 64 experts, the whole vocabulary, 64 slots:
    the full layers' pages `[2, 16384, 4, 64, 256]` (1.05 M tokens, 4.3 GB)
    beside the sliding layers' rings `[6, 64 x 16, 4, 64, 256]` (a window a
    slot, 0.8 GB whatever the contexts). The decode program runs the
    paged kernel under two names, `_decode_call` over the block table and
    `_window_decode` over the rings; a resumed `[1 x 4096]` pass behind a
    33,792-token table fits the chip beside 7.6 GB of weights, its sliding
    layers' flash calls (`_window_flash`: own tokens, then the ring) apart
    from its full layers'; both parts of the pool are aliased from argument
    to result."""
    cfg = dict(
        model="mellum2-12b-a2.5b", dtype="bfloat16", page_size=MELLUM_PAGE,
        num_pages=64, max_model_len=MELLUM_LEN, max_batch=64,
        prefill_buckets=(256, 512, 1024, 2048, 4096),
        model_overrides=dict(num_layers=8))
    compiled, spec, key = _compile_two_kind_program(
        topo, shape_engine, cfg, 8, MELLUM_PAGES, kind, key)
    assert spec["kv_pages"][0] == (2, MELLUM_PAGES, 4, MELLUM_PAGE, 256)
    assert spec["win_pages"][0] == (6, 64 * 1024 // MELLUM_PAGE, 4,
                                    MELLUM_PAGE, 256)
    mem, text = compiled.memory_analysis(), compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(kind, key, "GiB", total / 2 ** 30, "temp",
          mem.temp_size_in_bytes / 2 ** 30)
    # 7.59 GB of weights + 4.29 GB of pages + 0.81 GB of rings
    assert 11.8 * 2 ** 30 <= total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    names = sorted({k.split(".")[0] for k in kernels})
    if kind == "decode":
        assert names == ["_decode_call", "_moe_gmm", "_window_decode"], kernels
    else:
        assert "_window_flash" in names and "_moe_gmm" in names, kernels
        # a sliding run's scan body: the own-tokens call, and the ring's
        # where the pass resumes; two sliding runs
        assert sum(k.startswith("_window_flash") for k in kernels) == 2 * (
            2 if key[2] else 1), kernels
    # neither part of the pool is copied whole: the rings are gathered a
    # slot's rows and scattered back a slot's pages, as the pages are
    whole = {sd[0] for sd in spec.values()}
    copied = [line.strip()[:160] for line in text.splitlines()
              if (m := re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) copy\(",
                                line))
              and any(dims in whole for dims, _ in _array_types(m.group(1)))]
    assert not copied, "\n".join(copied)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (3 if kind == "decode" else 2), header[:400]


# ------ the same stack with the shapes a kind: 48 and 64 heads, a dense layer
LAGUNA_PAGE, LAGUNA_PAGES, LAGUNA_LEN = 64, 8192, 17408


@pytest.mark.parametrize("kind, key", [
    ("decode", None), ("prefill", (4096, LAGUNA_LEN // LAGUNA_PAGE)),
    ("prefill", (512, 0))])
def test_laguna_program_fits_and_carries_both_pools_in_place(
        topo, no_persistent_cache, shape_engine, kind, key):
    """Laguna-XS.2 at its published widths as the cell
    `laguna-xs2-agentturns` runs it: layers 0-4 (full attention + the dense
    FFN, three sliding layers, a full one; 48 and 64 query heads on 8 kv
    heads), all 256 experts beside the shared one, the whole vocabulary,
    64 slots: the two full layers' pages `[2, 8192, 8, 64, 256]` (524k
    tokens, 4.29 GB) beside the three sliding layers' rings `[3, 64 x 8, 8,
    64, 256]` (0.40 GB whatever the contexts). The decode program runs the
    paged kernel at a group of 6 (`_decode_call`) and of 8
    (`_window_decode`); a resumed `[1 x 4096]` pass behind a 17,408-token
    table fits the chip beside 7.74 GB of weights; both parts of the pool
    are aliased from argument to result and neither is copied whole."""
    cfg = dict(
        model="laguna-xs.2", dtype="bfloat16", page_size=LAGUNA_PAGE,
        num_pages=64, max_model_len=LAGUNA_LEN, max_batch=64,
        prefill_buckets=(512, 1024, 2048, 4096),
        model_overrides=dict(num_layers=5))
    compiled, spec, key = _compile_two_kind_program(
        topo, shape_engine, cfg, 5, LAGUNA_PAGES, kind, key)
    assert spec["kv_pages"][0] == (2, LAGUNA_PAGES, 8, LAGUNA_PAGE, 256)
    assert spec["win_pages"][0] == (3, 64 * 512 // LAGUNA_PAGE, 8,
                                    LAGUNA_PAGE, 256)
    mem, text = compiled.memory_analysis(), compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(kind, key, "GiB", total / 2 ** 30, "temp",
          mem.temp_size_in_bytes / 2 ** 30)
    # 7.74 GB of weights + 4.29 GB of pages + 0.40 GB of rings
    assert 11.5 * 2 ** 30 <= total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    names = sorted({k.split(".")[0] for k in kernels})
    if kind == "decode":
        assert names == ["_decode_call", "_moe_gmm", "_window_decode"], kernels
    else:
        assert "_window_flash" in names and "_moe_gmm" in names, kernels
        # ONE sliding run's scan body: the own-tokens call, and the ring's
        # where the pass resumes
        assert sum(k.startswith("_window_flash") for k in kernels) == (
            2 if key[2] else 1), kernels
    # the gate's product is the program's own name for it
    scopes = set(re.findall(r'op_name="[^"]*?(rtpu\.[\w.]+)', text))
    assert "rtpu.attn.gate" in scopes, sorted(scopes)
    whole = {sd[0] for sd in spec.values()}
    copied = [line.strip()[:160] for line in text.splitlines()
              if (m := re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) copy\(",
                                line))
              and any(dims in whole for dims, _ in _array_types(m.group(1)))]
    assert not copied, "\n".join(copied)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (3 if kind == "decode" else 2), header[:400]


# -------- gated-delta-net layers beside latent attention, three kinds of pool
GIGA_PAGES, GIGA_LEN, GIGA_SLOTS = 12288, 14400, 96


@pytest.mark.parametrize("kind, key", [
    ("decode", None), ("prefill", (4096, GIGA_LEN // 64))])
def test_gigachat_program_fits_and_carries_its_three_kind_pool_in_place(
        topo, no_persistent_cache, shape_engine, kind, key):
    """GigaChat3.5-432B-A28B at its published widths as the cell
    `gigachat3.5-reasoning` runs it: published layers 2-6 (a GDN layer with
    the dense FFN, an MLA layer and three GDN layers with 16 of the 256
    routed experts beside the shared one), 16,032 rows of the vocabulary, 96
    slots: ONE layer's latent pages `[1, 12288, 1, 64, 640]` (1.01 GB)
    beside the four GDN layers' matrices `[4, 96, 64, 128, 128]` float32
    (1.61 GB) and conv tails `[4, 3, 96, 16384]`. The decode program runs
    the delta-rule update as a kernel under its own name (`_gdn_update`)
    beside the latent kernel and the grouped matmul; a resumed `[1 x 4096]`
    pass behind a 14,400-token table (the fresh pass's program and its
    context chunks' calls) fits the chip beside 9.46 GB of weights; no part of
    the pool is copied whole and all three are aliased from argument to
    result."""
    from ray_tpu.serve.llm import EngineConfig

    cfg = EngineConfig(
        model="gigachat3.5-432b-a28b", dtype="bfloat16", page_size=64,
        num_pages=64, max_model_len=GIGA_LEN, max_batch=GIGA_SLOTS,
        prefill_buckets=(512, 1024, 2048, 4096),
        model_overrides=dict(num_layers=5, kept_layers=(2, 3, 4, 5, 6),
                             num_experts=16, n_routed_experts=256,
                             expert_first=0, vocab_size=16032))
    engine = shape_engine(cfg)
    stage = engine.compute
    spec = stage.family.pool_spec(stage.model_cfg, 5, GIGA_PAGES, 64,
                                  GIGA_SLOTS)
    assert spec["latent_pages"][0] == (1, GIGA_PAGES, 1, 64, 640)
    assert spec["gdn_state"][0] == (4, GIGA_SLOTS, 64, 128, 128)
    assert spec["gdn_conv"][0] == (4, 3, GIGA_SLOTS, 16384)
    stage.kv_pages = {k: jax.ShapeDtypeStruct(*sd) for k, sd in spec.items()}
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    key = (engine._decode_shape_key() if kind == "decode"
           else (key[0], engine._wave_rb, key[1]))
    with pytest.MonkeyPatch.context() as mp_ctx:
        mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
        compiled = stage.program(kind, key).lower(*jax.tree.map(
            sds, (*stage._state(kind), *stage.dummy_args(kind, key)),
            is_leaf=lambda a: isinstance(a, jax.ShapeDtypeStruct))
        ).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(kind, key, "GiB", total / 2 ** 30, "temp",
          mem.temp_size_in_bytes / 2 ** 30)
    # 9.46 GB of weights + 1.01 GB of latents + 1.65 GB of state and tails
    assert 11.2 * 2 ** 30 <= total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    names = sorted({k.split(".")[0] for k in kernels})
    if kind == "decode":
        assert names == ["_gdn_update", "_mla_decode", "_moe_gmm"], kernels
    else:
        assert names == ["_mla_flash", "_moe_gmm"], kernels
    whole = {sd[0] for sd in spec.values()}
    copied = [line.strip()[:160] for line in text.splitlines()
              if (m := re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) copy\(",
                                line))
              and any(dims in whole for dims, _ in _array_types(m.group(1)))]
    assert not copied, "\n".join(copied)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (4 if kind == "decode" else 3), header[:400]


# ------- attention inside a compressed latent, a tail a slot beside its pages
ZAYA_PAGES, ZAYA_LEN, ZAYA_SLOTS, ZAYA_LAYERS = 3456, 14400, 64, 20


@pytest.mark.parametrize("kind, key", [
    ("decode", None), ("prefill", (4096, 0)),
    ("prefill", (4096, ZAYA_LEN // 64))])
def test_zaya_program_fits_and_carries_both_pools_in_place(
        topo, no_persistent_cache, shape_engine, kind, key):
    """ZAYA1-8B at its published widths as the cell `zaya1-8b-reasoning`
    runs it: published layers 0-19 with all 16 experts of 2048 and the
    whole 262,272-row tied vocabulary, 64 slots: pages of the latent's 2 kv
    heads `[20, P, 2, 64, 256]` beside a tail a slot `[20, 64, 2688]`. The
    decode program's attention is the paged-decode kernel at 8 query heads
    on 2 kv heads (a group of 4, Mistral's), once a scan body, beside the
    grouped matmul at E = 16; a `[1 x 4096]` pass, fresh and resumed behind
    a 14,400-token table, is the flash forward and fits the chip beside
    9.38 GB of weights; no part of the pool is copied whole and both are
    aliased from argument to result."""
    from ray_tpu.serve.llm import EngineConfig

    cfg = EngineConfig(
        model="zaya1-8b", dtype="bfloat16", page_size=64, num_pages=64,
        max_model_len=ZAYA_LEN, max_batch=ZAYA_SLOTS,
        prefill_buckets=(512, 1024, 2048, 4096),
        model_overrides=dict(num_layers=ZAYA_LAYERS))
    engine = shape_engine(cfg)
    stage = engine.compute
    spec = stage.family.pool_spec(stage.model_cfg, ZAYA_LAYERS, ZAYA_PAGES,
                                  64, ZAYA_SLOTS)
    assert spec["kv_pages"][0] == (ZAYA_LAYERS, ZAYA_PAGES, 2, 64, 256)
    assert spec["cca_tail"][0] == (ZAYA_LAYERS, ZAYA_SLOTS, 2688)
    stage.kv_pages = {k: jax.ShapeDtypeStruct(*sd) for k, sd in spec.items()}
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    key = (engine._decode_shape_key() if kind == "decode"
           else (key[0], engine._wave_rb, key[1]))
    assert stage.operands("prefill")[-1] == "slots"
    with pytest.MonkeyPatch.context() as mp_ctx:
        mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
        compiled = stage.program(kind, key).lower(*jax.tree.map(
            sds, (*stage._state(kind), *stage.dummy_args(kind, key)),
            is_leaf=lambda a: isinstance(a, jax.ShapeDtypeStruct))
        ).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(kind, key, "GiB", total / 2 ** 30, "temp",
          mem.temp_size_in_bytes / 2 ** 30)
    # 9.38 GB of weights + 4.53 GB of pages
    assert 12.9 * 2 ** 30 <= total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    names = sorted({k.split(".")[0] for k in kernels})
    if kind == "decode":
        assert names == ["_decode_call", "_moe_gmm"], kernels
    else:
        assert names == (["_ctx_flash", "_moe_gmm", "attn"] if key[2]
                         else ["_moe_gmm", "attn"]), kernels
    whole = {sd[0] for sd in spec.values()}
    copied = [line.strip()[:160] for line in text.splitlines()
              if (m := re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) copy\(",
                                line))
              and any(dims in whole for dims, _ in _array_types(m.group(1)))]
    assert not copied, "\n".join(copied)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (3 if kind == "decode" else 2), header[:400]
