"""The device's time by the program's own names (ray_tpu/util/tracing.py:
SCOPES, `StageCompute.program_scopes`, `ShardedTrainer.program_scopes`,
the `program_key` of an `engine.dispatch` record).

CPU, tiny presets, no TPU library loaded at import. A kernel's instruction
name comes from the innermost name around its Pallas call: on the CPU the
wrappers lower to their jnp forms under the same jit, so what is shown
here is that no scope sits inside a wrapper's jit; that the custom calls
keep `_moe_gmm.<n>`, `_decode_call.<n>`, `attn.<n>` in the programs
compiled for a described v5e is tests/test_chip_compile.py's, which
compiles this tree.
"""

import contextlib
import re

import numpy as np
import pytest

from ray_tpu.serve.llm import LLMEngine, SamplingParams
from ray_tpu.util import tracing

from _engines import new_engine, tiny_engine

MOE = {s for s in tracing.SCOPES if s.startswith("rtpu.moe.")}
SERVING = {"rtpu.head", "rtpu.sample", "rtpu.attn.cache_write"}
TRAINING = {"rtpu.loss", "rtpu.optimizer", "rtpu.head"}
# family -> (preset, page size, the kind of its generation step, the scopes
# its programs hold)
FAMILIES = {
    "llama": ("tiny", 8, "decode", SERVING),
    "experts": ("tiny-moe", 16, "decode", SERVING | MOE),
    "sdar": ("tiny-sdar", 16, "block", SERVING | MOE),
    "minicpm-sala": ("tiny-sala", 16, "decode", SERVING),
    "jamba": ("tiny-jamba", 8, "decode", SERVING),
    # the one family with a per-head output gate (models/laguna.py)
    "laguna": ("tiny-laguna", 16, "decode",
               SERVING | MOE | {"rtpu.attn.gate"}),
    # the one family whose q, k and v come out of convolutions in time
    # (models/zaya.py); its MLP router is under `rtpu.moe.route`
    "zaya": ("tiny-zaya", 16, "decode", SERVING | MOE | {"rtpu.attn.cca"}),
}
# the jitted wrappers a kernel's instruction is named after, by family:
# each must be in some path, and no scope may follow it there
WRAPPERS = {
    "experts": ("_moe_gmm",), "sdar": ("_moe_gmm",),
    "laguna": ("_moe_gmm",), "zaya": ("_moe_gmm",),
    "minicpm-sala": ("_sparse_prefill", "_sparse_select",
                     "_sparse_compress", "_lightning_prefill",
                     "_lightning_update"),
    "jamba": ("_ssm_scan", "_ssm_update"),
}


def _dicts(kind):
    return [dict(zip(tracing.FIELDS[kind], rec))
            for rec in tracing.records(kind)]


def _engine(family, build=new_engine, **more):
    """An engine of its own (most cases here read what an engine built, or
    trace it under a patch); `build=tiny_engine`: the module's."""
    preset, page, _, _ = FAMILIES[family]
    return build(
        preset, dtype="float32", page_size=page, num_pages=96,
        max_model_len=256, max_batch=2, prefill_buckets=(32, 64), seed=3,
        **more)


def _generate(engine, lens=(41,), max_tokens=5, seed=4):
    rng = np.random.default_rng(seed)
    for i, n in enumerate(lens):
        engine.add_request(f"s{i}", rng.integers(1, 200, n).tolist(),
                           SamplingParams(max_tokens=max_tokens))
    out = {}
    for _ in range(600):
        if not engine.has_work():
            return out
        for d in engine.step():
            out.setdefault(d.request_id, []).extend(d.new_token_ids)
    raise AssertionError("the engine did not run dry")


@pytest.fixture(scope="module")
def tables():
    """family -> kind -> (the key a dispatch record names, its table); an
    engine a family, a short run, the tables of the programs it ran."""
    done = {}

    def get(family):
        if family not in done:
            tracing.reset_ring()
            engine = _engine(family, tiny_engine)
            # a prompt over the largest bucket: a resumed pass beside the
            # fresh one, where the family resumes
            _generate(engine, lens=(41, 64 if family == "jamba" else 100))
            # of a kind's keys the last: the resumed pass's, with its
            # context's gathers
            keys = {rec["kind"]: rec["program_key"]
                    for rec in _dicts("engine.dispatch")}
            done[family] = {kind: (key, engine.program_scopes(kind, key))
                            for kind, key in keys.items()}
        return done[family]

    return get


@pytest.fixture(scope="module")
def trainer_table():
    import jax

    from ray_tpu.models.llama import LlamaModel, get_config
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.train_lib import ShardedTrainer

    cfg = get_config("tiny", scan_layers=True, remat=True)
    mesh = create_mesh(MeshConfig(dp=1, fsdp=1, sp=1, tp=1),
                       devices=jax.devices()[:1])
    trainer = ShardedTrainer(LlamaModel(cfg), mesh)
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 17), dtype=np.int32)}
    state = trainer.init(jax.random.PRNGKey(0), batch)
    assert trainer.program_scopes() is None       # before a step
    for _ in range(2):
        state, metrics = trainer.step(state, batch)   # the state is given away
    assert np.isfinite(float(metrics["loss"]))
    return trainer, trainer.program_scopes()


def _has(table, scope):
    rx = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
    return any(rx.search(path) for path in table.values())


CASES = [(family, kind, scope)
         for family, (_, _, step, _) in FAMILIES.items()
         for kind in ("prefill", step) for scope in tracing.SCOPES]


@pytest.mark.parametrize("family, kind, scope", CASES)
def test_a_familys_program_holds_its_scopes_and_no_other(
        tables, family, kind, scope):
    key, table = tables(family)[kind]
    assert len(table) > 100 and len(key) in (2, 3)
    assert all(isinstance(p, str) for p in table.values())
    # every instruction the compiled text lists, named as a trace names it
    assert not any(name.startswith("%") or " " in name for name in table)
    assert _has(table, scope) == (scope in FAMILIES[family][3]), (
        sorted({p for p in table.values() if scope in p})[:5])


@pytest.mark.parametrize("scope", tracing.SCOPES)
def test_the_train_step_holds_its_scopes_and_no_serving_scope(
        trainer_table, scope):
    _, table = trainer_table
    assert _has(table, scope) == (scope in TRAINING)
    paths = set(table.values())
    # the forward under value_and_grad and its transpose carry the scope;
    # the recomputed forward is under the transpose (jax writes both)
    assert any("jvp(rtpu.loss)" in p and "transpose" not in p for p in paths)
    assert any("transpose(jvp(rtpu.loss))" in p for p in paths)
    assert any("rematted_computation" in p and "transpose(jvp" in p
               for p in paths)
    assert not any("rematted_computation" in p and "transpose" not in p
                   for p in paths if p.startswith("jit("))
    assert not any("rtpu.optimizer" in p and "jvp" in p for p in paths)


def test_the_trainers_table_is_parsed_once_from_shapes_not_arrays(
        trainer_table, monkeypatch):
    import jax

    trainer, table = trainer_table
    assert all(isinstance(a, jax.ShapeDtypeStruct)
               for a in jax.tree.leaves(trainer._step_avals))
    monkeypatch.setattr(tracing, "instruction_scopes", lambda text: 1 / 0)
    assert trainer.program_scopes() is table


@pytest.mark.parametrize("family", sorted(WRAPPERS))
def test_no_scope_sits_inside_a_kernels_wrapper(tables, family):
    """`_moe_gmm.<n>` and its like are named after the innermost name
    around the Pallas call: the wrapper's jit, with no scope after it."""
    paths = {p for _, table in tables(family).values()
             for p in table.values()}
    for wrapper in WRAPPERS[family]:
        inside = [p for p in paths if f"jit({wrapper})" in p]
        assert inside, wrapper
        for p in inside:
            assert "rtpu." not in p.rsplit(f"jit({wrapper})", 1)[1], p
    if "_moe_gmm" in WRAPPERS[family]:
        # the products' scope is AROUND the wrapper
        assert all("rtpu.moe.products" in p.split("jit(_moe_gmm)")[0]
                   for p in paths if "jit(_moe_gmm)" in p)


def test_an_instructions_path_is_read_from_the_compiled_text():
    text = '''HloModule jit_f, entry_computation_layout={()->f32[]}

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(f)/add"}
}

ENTRY %main.3 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %copy.2 = f32[8]{0} copy(%a)
  %_moe_gmm.26 = f32[8]{0} custom-call(%copy.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/rtpu.moe.products/jit(_moe_gmm)/pallas_call" source_file="x.py" source_line=3}
  ROOT fusion.694 = f32[8]{0} fusion(%_moe_gmm.26), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/rtpu.moe.unsort/gather"}
}
'''
    assert tracing.instruction_scopes(text) == {
        "p": "", "add.1": "jit(f)/add", "a": "a", "copy.2": "",
        "_moe_gmm.26": "jit(f)/rtpu.moe.products/jit(_moe_gmm)/pallas_call",
        "fusion.694": "jit(f)/rtpu.moe.unsort/gather"}


def test_a_scope_is_one_of_the_vocabulary():
    assert len(set(tracing.SCOPES)) == len(tracing.SCOPES) == 12
    assert all(s.startswith("rtpu.") for s in tracing.SCOPES)
    with pytest.raises(KeyError, match="rtpu.moe.sort"):
        tracing.scope("rtpu.moe.sort")


def test_program_scopes_builds_counts_and_caches_nothing_of_the_engines(
        monkeypatch):
    tracing.reset_ring()
    engine = _engine("experts")
    _generate(engine)
    compute = engine.compute
    built, programs = compute.programs_built, dict(compute.programs)
    records = tracing.appended("engine.program_built")
    stats = engine.stats()["programs_built_total"]
    parsed = []
    parse = tracing.instruction_scopes
    monkeypatch.setattr(tracing, "instruction_scopes",
                        lambda text: parsed.append(1) or parse(text))
    ran = engine.program_scopes("decode", engine._decode_shape_key())
    # a bucket traffic never hit: lowered for its table, not kept
    cold = engine.program_scopes("prefill", (32, engine._wave_rb, 0))
    assert ("prefill", 32, engine._wave_rb, 0) not in programs
    assert len(parsed) == 2 and ran and cold and ran is not cold
    assert engine.program_scopes("decode",
                                 engine._decode_shape_key()) is ran
    assert engine.program_scopes("prefill",
                                 (32, engine._wave_rb, 0)) is cold
    assert len(parsed) == 2                   # a second call parses nothing
    assert compute.programs_built == built == stats
    assert engine.stats()["programs_built_total"] == stats
    assert compute.programs == programs
    assert tracing.appended("engine.program_built") == records
    # and the engine still serves from the programs it had
    assert len(_generate(engine, seed=9)["s0"]) == 5
    assert compute.programs_built == built
    engine.close()


@pytest.mark.parametrize("family, more, kinds", [
    ("llama", {}, {"prefill", "decode"}),
    ("llama", {"spec_lookahead": 3}, {"prefill", "spec"}),
    ("sdar", {}, {"prefill", "block"})])
def test_a_dispatch_records_program_key_is_the_key_the_program_ran_under(
        family, more, kinds):
    tracing.reset_ring()
    engine = _engine(family, **more)
    if more:
        # a draft for every slot, of tokens the model will not choose
        engine._prompt_lookup_draft = lambda req, n: [250, 251][:n]
    ran = []
    run = engine.compute.run
    engine.compute.run = lambda kind, key, *ops: (
        ran.append((kind, tuple(key))), run(kind, key, *ops))[1]
    _generate(engine, lens=(41, 100))
    fields = tracing.FIELDS["engine.dispatch"]
    assert fields[-1] == "program_key" and fields[-2] == "drawn"
    recs = sorted(_dicts("engine.dispatch"), key=lambda r: r["seq"])
    assert {r["kind"] for r in recs} == kinds
    assert [("verify" if r["kind"] == "spec" else r["kind"],
             tuple(r["program_key"])) for r in recs] == ran
    for r in recs:
        # the record's own kind asks for its table, a verify's too
        assert ("verify" if r["kind"] == "spec" else r["kind"],
                *r["program_key"]) in engine.compute.programs
    spec = [r for r in recs if r["kind"] == "spec"]
    if spec:
        # the verify program's argmax is its sampler
        table = engine.program_scopes("spec", spec[0]["program_key"])
        assert _has(table, "rtpu.sample") and _has(table, "rtpu.head")
    # chrome_trace shows it with no further code
    shown = [e["args"]["program_key"] for e in tracing.chrome_trace([])
             if e["cat"] == "engine.dispatch"]
    assert shown == [r["program_key"] for r in _dicts("engine.dispatch")]
    engine.close()


def test_an_engine_whose_programs_are_another_processs_answers_none():
    engine = LLMEngine.__new__(LLMEngine)       # pp: compute stays None
    assert engine.compute is None
    assert engine.program_scopes("decode", (1, 16)) is None


@pytest.mark.parametrize("family", ["llama", "experts", "sdar"])
def test_greedy_tokens_are_bit_equal_with_and_without_the_scopes(
        family, monkeypatch):
    """A scope is a name at trace time: the programs compute what they
    computed without one."""
    with_scopes = _generate(_engine(family, tiny_engine), lens=(41, 23),
                            max_tokens=8)
    monkeypatch.setattr(tracing, "scope",
                        lambda name: contextlib.nullcontext())
    bare_engine = _engine(family)
    bare = _generate(bare_engine, lens=(41, 23), max_tokens=8)
    table = bare_engine.program_scopes("prefill",
                                       (64, bare_engine._wave_rb, 0))
    assert not any("rtpu." in p for p in table.values())
    assert with_scopes == bare and len(bare["s0"]) == 8
