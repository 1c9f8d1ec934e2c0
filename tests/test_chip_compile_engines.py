"""Compile the serving engine's own programs for a described (not
attached) TPU v5e, at real widths: `run_decode` / `run_prefill` /
`run_verify` at Mistral's and Mixtral's, and Jamba's, MiniCPM-SALA's,
SDAR's and Kimi's decode and prefill programs as their cells run them. (The kernels alone and the
trainer's step: tests/test_chip_compile.py.)

Nothing runs: a compile that passes says nothing about results or speed.
What a compiled module does say is whether it fits the chip, which kernels
it holds, and where the KV pool goes: the programs are read for whole-pool
copies (`test_engine_program_keeps_the_pool_in_place`).

An engine here is shapes only (`params={}`, then `jax.eval_shape` of its
parameters), built once a configuration for the cases that compile its
programs (`shape_engine`); that, `topo` and `no_persistent_cache` are
conftest.py's. The families with two or three kinds of pool are a file of
their own, tests/test_chip_compile_families.py, on this one's helpers.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

BF16 = jnp.bfloat16


# ------------------------------------------- the engine's own programs
# Mistral-7B widths. "short": 2 layers of the chat cell's sizes; 2200
# pages, so that one layer of the pool (144 MB) is more than the chip's
# 128 MiB of VMEM, as in a deployment: a pool that fits there is
# prefetched whole, which reads as a copy. "long": the docbatch cell's
# configuration at its full 16 layers, where the flash kernel's resident
# K, V and segment ids are largest (kv 8320).
# "mixtral": the `mixtral-chat` cell's configuration, Mixtral-8x7B widths
# (8 experts of width 14336, top-2) at its 3 layers, 2800 pages and 32
# slots: the `[32 x 1]` decode program and the `[16 x 2048]` wave.
HKV, PAGE, HEAD_DIM = 8, 16, 128
MISTRAL = dict(num_heads=32, num_kv_heads=HKV, head_dim=HEAD_DIM,
               hidden_size=4096, intermediate_size=14336, vocab_size=32768,
               rope_theta=1e6)
ENGINES = {
    "short": dict(layers=2, pages=2200, max_model_len=2688, buckets=(8, 128)),
    "long": dict(layers=16, pages=1900, max_model_len=8320, buckets=(4096,)),
    "mixtral": dict(layers=3, pages=2800, max_model_len=2688,
                    buckets=(128, 2048), max_batch=32, model="mixtral-8x7b",
                    widths={}),
}
# a pipeline's stages run the same programs over a slice of the layers
# (serve/llm/stage.py): name -> (engine, first layer, layers)
STAGES = {"short-first": ("short", 0, 1), "short-last": ("short", 1, 1),
          "mixtral-first": ("mixtral", 0, 2)}
HBM_GIB = 15.75     # what a program may use of a v5e's 16 GB
MOVES = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice",
         "slice", "transpose", "concatenate")
# program -> (engine, kind, shape key given (wave rows, pages per sequence))
ENGINE_PROGRAMS = {
    "decode-S1": ("short", "decode", lambda rb, mp: (1, mp)),
    "prefill-bucket128": ("short", "prefill", lambda rb, mp: (128, rb, 0)),
    "prefill-bucket128-prefix-hit":
        ("short", "prefill", lambda rb, mp: (128, rb, mp)),
    "verify-unaligned-span8": ("short", "verify", lambda rb, mp: (8, rb)),
    "prefill-bucket4096-prefix-hit-kv8320":
        ("long", "prefill", lambda rb, mp: (4096, rb, mp)),
    "mixtral-decode-32x1": ("mixtral", "decode", lambda rb, mp: (1, mp)),
    "mixtral-prefill-16x2048":
        ("mixtral", "prefill", lambda rb, mp: (2048, rb, 0)),
    # the programs most of the cells' waves take (a chat prompt's median
    # is 256 tokens, a document prefills with no prefix): one row a pass
    "prefill-bucket4096-kv8320":
        ("long", "prefill", lambda rb, mp: (4096, rb, 0)),
    "mixtral-prefill-16x128-prefix-hit":
        ("mixtral", "prefill", lambda rb, mp: (128, rb, mp)),
    # stages: hidden states in place of ids going in, or of tokens coming out
    "decode-S1-first-stage": ("short-first", "decode", lambda rb, mp: (1, mp)),
    "decode-S1-last-stage": ("short-last", "decode", lambda rb, mp: (1, mp)),
    "prefill-bucket128-first-stage":
        ("short-first", "prefill", lambda rb, mp: (128, rb, 0)),
    "prefill-bucket128-prefix-hit-last-stage":
        ("short-last", "prefill", lambda rb, mp: (128, rb, mp)),
    "mixtral-prefill-16x128-first-stage":
        ("mixtral-first", "prefill", lambda rb, mp: (128, rb, 0)),
}


@pytest.fixture(scope="module")
def engines():
    """name -> an `LLMEngine` whose params are shapes only: its stage's
    `program` builds the real `run_decode` / `run_prefill` / `run_verify`,
    nothing runs. Its own pool is two pages; a program takes the pool's size from
    its argument, which the test gives at `ENGINES[name]["pages"]`."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.serve.llm.stage import StageCompute, init_params

    def build(layers, pages, max_model_len, buckets, max_batch=8,
              model="llama3-8b", widths=MISTRAL, stage=None):
        cfg = EngineConfig(
            model=model, dtype="bfloat16", page_size=PAGE,
            num_pages=2, max_model_len=max_model_len, max_batch=max_batch,
            prefill_buckets=buckets,
            model_overrides=dict(num_layers=layers, **widths))
        eng = LLMEngine(cfg, params={})
        if stage:
            eng.compute = StageCompute(cfg, *stage, params={})
        c = eng.compute
        x = (jnp.zeros((1, 8), jnp.int32) if c.first
             else jnp.zeros((1, 8, c.model_cfg.hidden_size), BF16))
        c.params = jax.eval_shape(
            lambda: init_params(c.model, x, jax.random.PRNGKey(0)))
        return eng

    out = {name: build(**sizes) for name, sizes in ENGINES.items()}
    out.update({name: build(**ENGINES[which], stage=(lo, n))
                for name, (which, lo, n) in STAGES.items()})
    return out


def _program_args(kind, shape_key, rows, mp, sds, hidden=None):
    """Shapes of a program's arguments after (params, kv_pages); `hidden`
    is the width of the hidden states a stage that is not first takes in
    place of ids."""
    i32, f32 = jnp.int32, jnp.float32

    def x(span):
        return (sds((rows, span), i32) if hidden is None
                else sds((rows, span, hidden), BF16))

    if kind == "decode":
        return (sds((rows, 1), i32), sds((rows, mp), i32), sds((rows,), i32),
                sds((rows,), i32), sds((rows, 1), i32),
                sds((rows,), jnp.bool_), x(1),
                sds((rows,), f32), sds((rows,), i32),
                sds((shape_key[0], rows, 2), jnp.uint32))
    span = shape_key[0]
    # a prefill or a verify takes the number of real rows first: it loops
    # over them
    args = (sds((), i32), sds((rows, mp), i32), sds((rows,), i32), x(span),
            sds((rows, span), i32))
    if kind == "verify":
        return args
    return args + (sds((rows,), i32), sds((rows,), f32), sds((rows,), i32),
                   sds((rows, 2), jnp.uint32))


def _array_types(type_text):
    """[(dims, minor-to-major)] of every array in an HLO type."""
    return [(tuple(map(int, dims.split(","))) if dims else (),
             tuple(map(int, m2m.split(","))) if m2m else ())
            for dims, m2m in re.findall(r"\w+\[([\d,]*)\]\{([\d,]*)",
                                        type_text)]


def _check_expert_program(cfg, tokens, compiled, text):
    """An expert model's program fits the chip, runs the grouped matmul
    kernel, holds no capacity dispatch tensor and copies no layer's stack
    of expert weights (a slice handed to a kernel would be one)."""
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    assert re.search(r"%_moe_gmm\.\d+ = [^\n]*tpu_custom_call", text)
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    group = min(cfg.moe_group_size, tokens)
    capacity = int(cfg.capacity_factor * k * group / E)
    h, f = cfg.hidden_size, cfg.intermediate_size
    for dims, _ in _array_types(text):
        assert dims[-2:] != (E, capacity), dims      # [G, g, E, C] one-hot
        assert dims not in ((E, h, 2 * f), (E, f, h)), dims


@pytest.fixture(scope="module")
def compiled_programs(topo, no_persistent_cache, engines):
    """name -> (engine, kind, shape key, rows, pool dims, compiled, its
    text), each program compiled once for the cases that read it."""
    done = {}

    def get(name):
        if name in done:
            return done[name]
        one_chip = SingleDeviceSharding(topo.devices[0])

        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        which, kind, key = ENGINE_PROGRAMS[name]
        engine = engines[which]
        mp = engine.max_pages_per_seq
        rows = (engine.config.max_batch if kind == "decode"
                else engine._wave_rb)
        shape_key = key(rows, mp)
        stage = engine.compute
        pool_dims = (stage.n_layers, ENGINES[STAGES.get(
            which, (which,))[0]]["pages"], HKV, PAGE, 2 * HEAD_DIM)
        # the ops choose kernel or reference by the backend, at trace time
        with pytest.MonkeyPatch.context() as mp_ctx:
            mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
            compiled = stage.program(kind, shape_key).lower(
                jax.tree.map(lambda a: sds(a.shape, a.dtype), stage.params),
                sds(pool_dims, BF16),
                *_program_args(
                    kind, shape_key, rows, mp, sds,
                    None if stage.first else stage.model_cfg.hidden_size),
            ).compile()
        done[name] = (engine, kind, shape_key, rows, pool_dims, compiled,
                      compiled.as_text())
        return done[name]

    return get


@pytest.mark.parametrize("name", list(ENGINE_PROGRAMS))
def test_engine_program_keeps_the_pool_in_place(compiled_programs, name):
    """The donated pool is ONE buffer from argument to result: no
    instruction that moves data has an output of its size or a layer's,
    it is row-major wherever it appears (the layout the decode kernel's
    custom call demands, so nothing re-lays it out), and the argument is
    aliased to the result."""
    engine, kind, shape_key, rows, pool_dims, compiled, text = \
        compiled_programs(name)
    layer_bytes = 2 * int(np.prod(pool_dims[1:]))
    pool_bytes = (layer_bytes, pool_dims[0] * layer_bytes)  # in any shape
    if engine.model_cfg.num_experts:
        _check_expert_program(engine.model_cfg, rows * shape_key[0]
                              if kind == "prefill" else rows, compiled, text)

    assert "tpu_custom_call" in text
    moved, layouts, pool_param = [], set(), None
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if not m:
            continue
        arrays = _array_types(m.group(1))
        if m.group(2) in MOVES and any(
                2 * int(np.prod(dims)) in pool_bytes for dims, _ in arrays):
            moved.append(line.strip()[:160])
        layouts.update(m2m for dims, m2m in arrays if dims == pool_dims)
        # the entry computation's parameters are the ones with a sharding
        entry = re.search(r" parameter\((\d+)\), sharding=", line)
        if entry and arrays[0][0] == pool_dims:
            pool_param = int(entry.group(1))
    assert not moved, "\n".join(moved)
    assert layouts == {(4, 3, 2, 1, 0)}, layouts
    header = text.split("\n", 1)[0]
    assert pool_param is not None
    assert re.search(r"input_output_alias=\{[^\n]*\(%d, \{\}, (may|must)-alias\)"
                     % pool_param, header), header[:300]


@pytest.mark.parametrize("name", [n for n, (_, kind, _) in
                                  ENGINE_PROGRAMS.items()
                                  if kind in ("prefill", "verify")])
def test_prefill_program_computes_one_row_a_pass(compiled_programs, name):
    """A wave computes its real rows in a loop, one row a pass: the
    program holds a while loop beside the layer scan's, and no
    activation at the wave's width (the padded `[16, 2048, 32000]` head
    and `[16, 2048, 28672]` MLP of the program that padded every wave
    to its size). Its inputs, `[rows, span]` ids and positions, stay; so
    do the `[rows, span, hidden]` states between a pipeline's stages,
    which the loop reads and writes a row at a time. A speculative verify
    is the same loop."""
    engine, _, shape_key, rows, _, _, text = compiled_programs(name)
    span = shape_key[0]
    assert rows > 1
    stage = engine.compute
    between = set() if stage.first and stage.last else {
        (rows, span, stage.model_cfg.hidden_size)}
    wide = set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if m:
            wide.update(dims for dims, _ in _array_types(m.group(1))
                        if len(dims) > 2 and dims[:2] == (rows, span))
    assert not wide - between, wide
    # the row loop and the layer scan inside it (a scan over one layer
    # is no loop)
    assert len(re.findall(r" while\(", text)) >= 1 + (stage.n_layers > 1)


# ------------------------------- a model with state-space layers, whole
@pytest.mark.parametrize("kind, span", [("decode", 1), ("prefill", 2048)])
def test_jamba_program_fits_and_carries_both_pools_in_place(
        topo, no_persistent_cache, shape_engine, kind, span):
    """AI21-Jamba2-3B at its published size, all 28 layers, as the cell
    `jamba2-3b-chat` runs it (64 slots, 10,753 pages): the program fits
    the chip, holds the selective-scan kernel (prefill) and the paged
    decode kernel at 20 q heads on 1 kv head (decode), aliases the pages
    and both state arrays from argument to result, and moves no array as
    large as a state array or as a run of Mamba layers' weights (the
    period's slice of a [periods, run, ...] stack was such a copy: 2.7 GB
    a decode step)."""
    from ray_tpu.serve.llm import EngineConfig

    cfg = EngineConfig(
        model="jamba2-3b", dtype="bfloat16", page_size=PAGE, num_pages=10753,
        max_model_len=2688, max_batch=64, prefill_buckets=(128, 2048))
    engine = shape_engine(cfg)
    stage = engine.compute
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    key = ((1, engine.max_pages_per_seq) if kind == "decode"
           else (span, engine._wave_rb, 0))
    assert stage.operands("prefill")[-1] == "slots"
    with pytest.MonkeyPatch.context() as mp_ctx:
        mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
        compiled = stage.program(kind, key).lower(*jax.tree.map(
            sds, (*stage._state(kind), *stage.dummy_args(kind, key)))
        ).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    if kind == "decode":
        assert any(k.startswith("_decode_call") for k in kernels), kernels
    else:
        assert {"_ssm_scan", "_ssm_scan.3"} <= kernels, kernels
        assert any(k.startswith("attn.") for k in kernels), kernels
    pools = {name: tuple(a.shape) for name, a in stage.kv_pages.items()}
    assert (pools["ssm_h"], pools["ssm_conv"]) == ((26, 64, 16, 8, 640),
                                                   (26, 3, 64, 5120))
    run_bytes = 2 * 2560 * 10240 * 2          # two layers' W_in
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if not m or m.group(2) not in MOVES:
            continue
        for dims, _ in _array_types(m.group(1)):
            # an update in place has the pool's shape: its operand is the
            # donated buffer, and that it aliases is asserted below
            if dims in pools.values():
                if m.group(2) != "dynamic-update-slice":
                    moved.append(line.strip()[:160])
            elif int(np.prod(dims)) * 2 >= run_bytes:
                moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (4 if kind == "decode" else 3), header[:400]


# ------------- a model with lightning and block-sparse layers, at its cut
@pytest.mark.parametrize("kind, ctx", [("decode", 0), ("prefill", 660),
                                       ("prefill", 0)])
def test_sala_program_fits_and_carries_its_pool_in_place(
        topo, no_persistent_cache, shape_engine, kind, ctx):
    """MiniCPM-SALA at its published widths, layers 9 to 24, as the cell
    `minicpm-sala-longdoc` runs it (16 slots, 10,560 pages of 64): the
    program fits the chip beside its 3.26 GB pool, the decode step reads
    its selected pages through the paged-decode kernel (one call a run of
    sparse layers), a pass with nothing cached goes through the flash
    kernel and a resumed one through plain XLA, all three parts of the
    pool are aliased from argument to result, and nothing as large as a
    part of the pool or a layer's FFN weights is copied (the whole-pool
    view a head a page, `[L, P*G, 1, page, 2D]`, is a bitcast)."""
    from ray_tpu.serve.llm import EngineConfig

    cfg = EngineConfig(
        model="minicpm-sala", dtype="bfloat16", page_size=64, num_pages=64,
        max_model_len=42240, max_batch=16, prefill_buckets=(512, 4096),
        model_overrides=dict(num_layers=16, kept_layers=tuple(range(9, 25))))
    engine = shape_engine(cfg)
    stage = engine.compute
    # the cell's pool, as shapes (the engine above holds a small one)
    stage.kv_pages = {
        name: jax.ShapeDtypeStruct(shape, dtype) for name, (shape, dtype)
        in stage.family.pool_spec(stage.model_cfg, 16, 10560, 64, 16).items()}
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    key = ((1, engine.max_pages_per_seq) if kind == "decode"
           else (4096, engine._wave_rb, ctx))
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
    assert 12.3 * 2 ** 30 <= total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    if kind == "decode":
        assert len(kernels) == 3 and all(
            k.startswith("_decode_call") for k in kernels), kernels
    elif ctx:
        assert not kernels, kernels
    else:
        assert len(kernels) == 3, kernels
    pools = {name: tuple(a.shape) for name, a in stage.kv_pages.items()}
    assert pools == {"kv_pages": (4, 10560, 2, 64, 256),
                     "kc": (4, 10560, 2, 4, 128),
                     "lin_state": (12, 16, 32, 128, 128)}
    # a layer's weights sliced from its run's stack are read in place (the
    # slice sits inside the matmul's fusion); what may not appear is a
    # COPY of a layer's FFN weights, or any move of a part of the pool but
    # the update in place. (The one copy there is: the 0.6 GB head, into
    # the layout its one-row product wants, once a prefill program.)
    ffn, head = (1, 4096, 32768), (4096, 73448)
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if not m or m.group(2) not in MOVES:
            continue
        for dims, _ in _array_types(m.group(1)):
            if dims in pools.values():
                if m.group(2) != "dynamic-update-slice":
                    moved.append(line.strip()[:160])
            elif (m.group(2) in ("copy", "transpose", "concatenate")
                  and int(np.prod(dims)) >= int(np.prod(ffn))
                  and dims != head):
                moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (4 if kind == "decode" else 3), header[:400]


# --------- a model that generates by diffusion over blocks, at its cut
@pytest.mark.parametrize("kind, key", [
    ("block", None), ("prefill", (2048, 0)), ("prefill", (2048, 200)),
    ("prefill", (128, 0))])
def test_sdar_program_fits_and_carries_its_pool_in_place(
        topo, no_persistent_cache, shape_engine, kind, key):
    """SDAR-30B-A3B at its published widths, 6 layers with all 128
    experts, as the cell `sdar-30b-a3b-chat` runs it (64 slots, 12,800
    pages of 16): the program fits the chip beside its 2.5 GB pool; the
    block program's attention is the paged-decode kernel given 4 tokens x
    8 heads a kv head (two calls in the pass that opens a block, which is
    [64, 8] wide: the pending block's queries and the new block's, each
    with its own length; one in the loop's pass) and its experts the
    grouped matmul at E = 128 (4096 sorted rows in the opening pass, 2048
    in the loop's); a prefill's is the flash kernel under the block mask;
    the pool (and the block program's carry) is aliased from argument to
    result. The block program is 11.06 GiB (PR 42: what it was with the
    settling pass it had until then; 0.59 GiB of it temporaries). Since PR
    54 a greedy batch's pass decides in `_head_argmax` and writes no
    logits; the program reads 11.058 GiB with 0.588 of temporaries all the
    same: the branch that draws keeps its `f32[64,4,151936]` and XLA sizes
    a `cond` for the larger branch."""
    from ray_tpu.serve.llm import EngineConfig

    cfg = EngineConfig(
        model="sdar-30b-a3b", dtype="bfloat16", page_size=16, num_pages=64,
        max_model_len=3200, max_batch=64,
        prefill_buckets=(128, 256, 512, 1024, 2048),
        model_overrides=dict(num_layers=6,
                             remasking="low_confidence_static"))
    engine = shape_engine(cfg)
    stage = engine.compute
    shape, dtype = stage.family.pool_spec(stage.model_cfg, 6, 12800, 16, 64)
    stage.kv_pages = jax.ShapeDtypeStruct(shape, dtype)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    key = (engine._block_shape_key() if kind == "block"
           else (key[0], engine._wave_rb, key[1]))
    assert key == ((4, 4, 200) if kind == "block" else key)
    with pytest.MonkeyPatch.context() as mp_ctx:
        mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
        compiled = stage.program(kind, key).lower(*jax.tree.map(
            sds, (*stage._state(kind), *stage.dummy_args(kind, key)),
            is_leaf=lambda a: isinstance(a, jax.ShapeDtypeStruct))
        ).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # 8.72 GB of weights + 2.52 GB of pages, under the chip's 15.75 GiB
    assert 10.4 * 2 ** 30 <= total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    names = sorted({k.split(".")[0] for k in kernels})
    if kind == "block":
        assert names == ["_decode_call", "_head_argmax", "_moe_gmm"], kernels
        assert sum(k.startswith("_decode_call") for k in kernels) == 3
        assert sum(k.startswith("_moe_gmm") for k in kernels) == 4
        # the head of a greedy batch's pass, the opening one's and the
        # loop's; the float32 logits are the drawing branch's alone
        assert sum(k.startswith("_head_argmax") for k in kernels) == 2
        assert text.count("f32[64,4,151936]") > 0
        # the opening pass's ids, and no head over its left half
        assert "s32[64,8]" in text and "[64,8,151936]" not in text
        assert total <= 11.3 * 2 ** 30, total / 2 ** 30
    else:
        # (since PR 60 q's and k's rotation is a kernel over the rows the
        # flash kernel reads: models/llama.py: rope, ops/rotary.py)
        assert names == ["_moe_gmm", "_rotate_rows", "attn"], kernels
        assert sum(k.startswith("_rotate_rows") for k in kernels) == 2
        # a resumed pass: the own-tokens part and the part over its pages
        assert sum(k.startswith("attn") for k in kernels) == (
            2 if key[2] else 1)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (2 if kind == "block" else 1), header[:400]
    assert tuple(stage.kv_pages.shape) == (6, 12800, 4, 16, 256)


# ---------------- latent attention and one chip's share of the experts
@pytest.mark.parametrize("kind, key", [
    ("decode", None), ("prefill", (4096, 529)), ("prefill", (4096, 0)),
    ("prefill", (512, 529))])
def test_kimi_program_fits_and_carries_its_pool_in_place(
        topo, no_persistent_cache, shape_engine, kind, key):
    """Kimi-K2.5 at its published widths as the cell `kimi-k2.5-longdoc`
    runs it: one dense layer and five expert layers, each with 12 of the
    384 routed experts beside the shared one, 20,480 rows of the
    vocabulary, 24 slots, 8192 latent pages of 64 tokens (`[6, 8192, 1,
    64, 640]`, 4.03 GB). The decode program's attention is the latent
    kernel under its own name (`_mla_decode`), once a scan body; a resumed
    `[1 x 4096]` pass behind a 33,856-token table (nine context chunks of
    4096 tokens, each a flash call under a `cond`, beside the own-tokens
    call) fits
    the chip; the experts are the grouped matmul at E = 12; the pool is
    aliased from argument to result."""
    from ray_tpu.serve.llm import EngineConfig

    cfg = EngineConfig(
        model="kimi-k2.5", dtype="bfloat16", page_size=64, num_pages=64,
        max_model_len=33856, max_batch=24,
        prefill_buckets=(512, 1024, 2048, 4096),
        model_overrides=dict(num_layers=6, num_experts=12,
                             n_routed_experts=384, expert_first=0,
                             vocab_size=20480))
    engine = shape_engine(cfg)
    stage = engine.compute
    shape, dtype = stage.family.pool_spec(stage.model_cfg, 6, 8192, 64, 24)
    assert shape == (6, 8192, 1, 64, 640)
    stage.kv_pages = jax.ShapeDtypeStruct(shape, dtype)
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
    # 8.35 GB of weights + 4.03 GB of latents, under the chip's 15.75 GiB
    assert 11.4 * 2 ** 30 <= total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    names = sorted({k.split(".")[0] for k in kernels})
    if kind == "decode":
        assert names == ["_mla_decode", "_moe_gmm"], kernels
    else:
        assert names == ["_mla_flash", "_moe_gmm"], kernels
        # the own-tokens call and one a context chunk of a resumed pass,
        # in the dense run's scan body and in the expert run's
        assert sum(k.startswith("_mla_flash") for k in kernels) == 2 * (
            1 + (9 if key[2] else 0)), kernels
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (2 if kind == "decode" else 1), header[:400]
