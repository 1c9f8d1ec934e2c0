"""A hybrid decoder of lightning linear-attention layers beside block-sparse
attention layers (models/minicpm_sala.py) through the paged engine,
against the plain reference (chipbench/references/sala_decoder.py): seeded
random weights, float32, `tiny-sala` (sparse, lightning x 2, sparse x 2,
lightning; blocks of 16, the selection live from position 64), on the
CPU. And what it brought every family: a prompt past the largest bucket
prefilled in passes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import sala_work
from chipbench.references import sala_decoder as reference
from ray_tpu.models import minicpm_sala as sala
from ray_tpu.ops import lightning_attention as la
from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops.paged_attention import (paged_attention_decode,
                                         paged_write)
from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.serve.llm.engine import PassCost
from ray_tpu.serve.llm.stage import model_family
from ray_tpu.util import tracing

from _engines import applied, fresh_params, jitted, scarce, tiny_engine

F32 = jnp.float32
CFG = sala.get_config("tiny-sala", dtype=F32, param_dtype=F32)
SP = CFG.sparse
SC = dict(kernel_size=8, kernel_stride=4, block_size=16, init_blocks=1,
          window_size=32, topk=2, dense_len=64)
# the published keys of `tiny-sala`, as a configuration file would hold them
PUB = dict(mixer_types=list(CFG.mixer_types), kept_layers=None,
           num_hidden_layers=6, rms_norm_eps=1e-6, intermediate_size=128,
           hidden_size=64, scale_depth=1.4, scale_emb=12, dim_model_base=32,
           lightning_nh=4, lightning_head_dim=16, rope_theta=10000.0,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           vocab_size=256, sparse_config=SC)
VOCAB, PAGE, MP = CFG.vocab_size, 16, 16


BASE = dict(model="tiny-sala", dtype="float32", num_pages=64,
            page_size=PAGE, max_model_len=256, max_batch=4,
            prefill_buckets=(32, 64), seed=3)


def _engine_config(**over):
    return EngineConfig(**{**BASE, **over})


def _engine(**over):
    """The module's engine of this configuration, renewed."""
    return tiny_engine(**{**BASE, **over})


@pytest.fixture(scope="module")
def model():
    return sala.SalaModel(CFG)


@pytest.fixture(scope="module")
def params(model):
    return fresh_params(model, 11)


@pytest.fixture(scope="module")
def ref_weights(params):
    return reference.weights_from_program_tree(params)


def _ref_logits(ref_weights, seq):
    return np.asarray(jitted(reference.forward)(
        ref_weights, jnp.asarray([seq], jnp.int32), PUB)[0])


def _prompt(seed, n, vocab=VOCAB):
    return np.random.default_rng(seed).integers(0, vocab, n).tolist()


def _generate(engine, prompts, max_tokens):
    out = {}
    for i, p in enumerate(prompts):
        engine.add_request(f"r{i}", p, SamplingParams(max_tokens=max_tokens))
        out[f"r{i}"] = []
    while engine.has_work():
        for d in engine.step():
            out[d.request_id].extend(d.new_token_ids)
    return out


def _fresh_pool(slots=2, pages=1 + MP):
    return {k: jnp.zeros(*sd) for k, sd in sala.pool_spec(
        CFG, CFG.num_layers, pages, PAGE, slots).items()}


BT = jnp.arange(1, 1 + MP, dtype=jnp.int32)[None]


def _apply(model, params, pool, bt, total, ids, positions, slots, ctx):
    cache = sala.serving_cache(CFG, pool, bt, total, slots, ctx_pages=ctx)
    logits, cache = applied(model, params, ids, positions=positions,
                            kv_caches=cache)
    return logits, cache.pool


_APPLY = jax.jit(_apply, static_argnums=(0, 8))


def _prefill(model, params, pool, ids, pass_len, slot=1):
    """ids [n] through the paged path in passes of `pass_len`; -> (logits
    [n, V], pool)."""
    n, out = len(ids), []
    for p0 in range(0, n, pass_len):
        m = min(pass_len, n - p0)
        row = np.zeros((1, pass_len), np.int32)
        row[0, :m] = ids[p0:p0 + m]
        logits, pool = _APPLY(
            model, params, pool, BT, jnp.asarray([p0 + m]), jnp.asarray(row),
            (p0 + jnp.arange(pass_len))[None], jnp.asarray([slot]),
            MP if p0 else 0)
        out.append(logits[0, :m])
    return jnp.concatenate(out), pool


def _decode(model, params, pool, tokens, first_pos, slot=1, slots=2):
    """One token at a time in slot `slot` of `slots`; the others idle."""
    bt = jnp.zeros((slots, MP), jnp.int32).at[slot].set(BT[0])
    out = []
    for j, tok in enumerate(tokens):
        t = first_pos + j
        total = jnp.zeros((slots,), jnp.int32).at[slot].set(t + 1)
        logits, pool = _APPLY(
            model, params, pool, bt, total,
            jnp.zeros((slots, 1), jnp.int32).at[slot, 0].set(tok),
            jnp.zeros((slots, 1), jnp.int32).at[slot, 0].set(t), None, 0)
        out.append(logits[slot, 0])
    return jnp.stack(out), pool


# ------------------------------------------------------------------ model
def test_presets_count_their_parameters_and_their_state():
    full = sala.get_config("minicpm-sala")
    assert (full.n_sparse_layers, full.n_lightning_layers) == (8, 24)
    assert [i for i, k in enumerate(full.mixer_types)
            if k == sala.SPARSE] == [0, 9, 16, 17, 22, 29, 30, 31]
    cut = sala.get_config("minicpm-sala", num_layers=16,
                          kept_layers=tuple(range(9, 25)))
    assert (cut.n_sparse_layers, cut.n_lightning_layers) == (4, 12)
    assert [k for k, _ in cut.runs] == [
        sala.SPARSE, sala.LIGHTNING, sala.SPARSE, sala.LIGHTNING,
        sala.SPARSE, sala.LIGHTNING]
    assert [len(v) for _, v in cut.runs] == [1, 6, 2, 4, 1, 2]
    # 4 x 253.8 M + 12 x 285.2 M + 601.7 M: 10.08 GB in bf16
    assert round(cut.num_params() / 1e9, 2) == 5.04
    assert cut.slot_state_bytes_row() == 12 * 32 * 128 * 128 * 4
    assert cut.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert CFG.n_slot_state_layers == 3 and CFG.n_sparse_layers == 3


@pytest.mark.parametrize("over, why", [
    (dict(num_layers=7), "num_layers 7 but 6 layers kept"),
    (dict(kept_layers=(3, 1), num_layers=2), "in order"),
    (dict(mixer_types=("mamba",) * 6), "names"),
    (dict(attn_use_rope=True), "published MiniCPM-SALA switches"),
    (dict(sparse_kernel_size=6), "whole numbers of strides"),
])
def test_a_config_the_model_cannot_run_is_refused(over, why):
    with pytest.raises((ValueError, NotImplementedError), match=why):
        sala.get_config("tiny-sala", **over)


def test_model_family_knows_three_families():
    from ray_tpu.models import jamba, llama

    assert model_family("tiny-sala") is sala
    assert model_family("minicpm-sala") is sala
    assert model_family("tiny-jamba") is jamba
    assert model_family("tiny") is llama
    assert (llama.RESUMES_PREFILL, jamba.RESUMES_PREFILL,
            sala.RESUMES_PREFILL) == (True, False, True)
    with pytest.raises(KeyError, match="no model preset"):
        model_family("tiny-nothing")
    with pytest.raises(ValueError, match="must be one page"):
        sala.pool_spec(CFG, 6, 8, 8, 2)


@pytest.mark.parametrize("n, pass_len, g", [(150, 160, 8), (150, 64, 8),
                                            (40, 64, 30), (200, 32, 4)])
def test_prefill_then_decode_through_the_pool_is_the_references_forward(
        model, params, ref_weights, n, pass_len, g):
    """Logits at every position: a prompt in one pass or several (each
    resuming from the slot's state, the pages and the compressed keys),
    then token by token beside an idle slot, against the reference's full
    forward (no cache, token-by-token recurrence, dense scores). 40 + 30
    crosses dense_len in decode; the others in prefill."""
    seq = _prompt(n, n + g)
    want = _ref_logits(ref_weights, seq)
    got, pool = _prefill(model, params, _fresh_pool(), seq[:n], pass_len)
    np.testing.assert_allclose(got, want[:n], atol=3e-4)
    got, _ = _decode(model, params, pool, seq[n:], n)
    np.testing.assert_allclose(got, want[n:], atol=3e-4)


def test_the_models_own_forward_is_the_paged_path(model, params, ref_weights):
    seq = _prompt(5, 100)
    got = applied(model, params, jnp.asarray([seq, seq[::-1]]))
    np.testing.assert_allclose(got[0], _ref_logits(ref_weights, seq),
                               atol=3e-4)
    np.testing.assert_allclose(got[1], _ref_logits(ref_weights, seq[::-1]),
                               atol=3e-4)


@pytest.fixture(scope="module")
def one_and_three(model, params):
    seq = _prompt(7, 170)
    return (_prefill(model, params, _fresh_pool(), seq, 176),
            _prefill(model, params, _fresh_pool(), seq, 64))


@pytest.mark.parametrize("part", ["kv_pages", "kc", "lin_state", "logits"])
def test_a_prompt_in_three_passes_leaves_what_one_pass_leaves(
        one_and_three, part):
    (one, pool1), (three, pool3) = one_and_three
    if part == "logits":
        np.testing.assert_allclose(three, one, atol=2e-5)
    else:
        assert float(jnp.abs(pool1[part]).max()) > 0
        np.testing.assert_allclose(pool3[part], pool1[part], rtol=1e-4,
                                   atol=1e-4)


def test_a_decode_step_leaves_dead_slots_bit_for_bit(model, params):
    """Slot 0 is idle and holds NaN state; page 0 (every idle row's table
    points there) holds a mark and two pages that belong to nobody hold
    NaN: ten steps of slot 1 change none of them, and slot 1's own result
    has no NaN in it."""
    seq = _prompt(9, 100)
    _, pool = _prefill(model, params, _fresh_pool(pages=MP + 3), seq[:90],
                       96)
    pool = dict(pool, lin_state=pool["lin_state"].at[:, 0].set(jnp.nan),
                kv_pages=pool["kv_pages"].at[:, 0].set(7.0).at[
                    :, MP + 1:].set(jnp.nan),
                kc=pool["kc"].at[:, 0].set(7.0).at[:, MP + 1:].set(jnp.nan))
    logits, new = _decode(model, params, pool, seq[90:], 90)
    assert bool(jnp.isfinite(logits).all())
    bits = lambda a: np.asarray(a).view(np.uint32)   # noqa: E731
    np.testing.assert_array_equal(bits(new["lin_state"][:, 0]),
                                  bits(pool["lin_state"][:, 0]))
    for part in ("kv_pages", "kc"):
        for pages in (slice(0, 1), slice(MP + 1, None)):
            np.testing.assert_array_equal(bits(new[part][:, pages]),
                                          bits(pool[part][:, pages]))
    assert not np.array_equal(bits(new["lin_state"][:, 1]),
                              bits(pool["lin_state"][:, 1]))


# ----------------------------------------------------------------- engine
@pytest.mark.parametrize("preset, over", [
    ("tiny-sala", {}),
    ("tiny-sala", dict(prefill_chunk_tokens=32)),
    ("tiny", dict(page_size=8, prefill_buckets=(16, 32))),
])
def test_a_prompt_past_the_largest_bucket_is_served_in_passes(preset, over):
    """The same prompts through an engine whose largest bucket holds them
    whole and through one that needs 2 to 5 passes: the same tokens; and
    where every request ends with its prefill (one token), the same
    pages (and state, and compressed keys)."""
    vocab = 256
    prompts = [_prompt(21, 150, vocab), _prompt(22, 40, vocab),
               _prompt(23, 100, vocab)]
    def engines():
        whole = _engine(model=preset, **{
            **over, "prefill_buckets": (64, 160), "prefill_chunk_tokens": 0})
        # whole whatever the plan: a tiny model's attention is dear beside
        # its products, and 100 tokens would go as 64 + 64
        whole._pass_cost = PassCost(float("inf"), 0.0)
        # (the same seed: the same weights)
        return whole, _engine(model=preset, **over)

    whole, passes = engines()
    assert _generate(passes, prompts, 12) == _generate(whole, prompts, 12)
    st = passes.stats()
    assert st["prefill_resumed_passes_total"] >= 3
    assert st["prefill_passes_total"] >= 3 + st["prefill_resumed_passes_total"]
    assert whole.stats()["prefill_resumed_passes_total"] == 0
    whole, passes = engines()
    assert _generate(passes, prompts, 1) == _generate(whole, prompts, 1)
    pools = [e.kv_pages if isinstance(e.kv_pages, dict)
             else {"kv_pages": e.kv_pages} for e in (passes, whole)]
    for part in pools[0]:
        np.testing.assert_allclose(pools[0][part], pools[1][part],
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("preset, page", [("tiny-sala", 16), ("tiny", 8)])
def test_a_preempted_request_whose_folded_prompt_outgrows_the_buckets_finishes(
        preset, page):
    """Two requests that cannot both keep their pages: one is preempted,
    its generated tokens are folded into its prompt, and the folded
    prompt (over the largest bucket of 32) is prefilled in passes; both
    end with the tokens an engine with room gives."""
    prompts = [_prompt(31, 30), _prompt(32, 30)]
    over = dict(model=preset, page_size=page, prefill_buckets=(32,),
                max_model_len=128, max_batch=2)
    # (its own sizes: one bucket of 32 that the folded prompt outgrows;
    # then the same engine, renewed, with the pages that two rows of 70
    # tokens cannot both keep)
    want = _generate(_engine(**over), prompts, 40)
    with scarce(_engine(**over), 112 // page - 1) as tight:
        got = _generate(tight, prompts, 40)
        st = tight.stats()
    assert st["preempted_total"] >= 1
    assert st["prefill_resumed_passes_total"] >= 1
    assert got == want


REFUSED = {
    "spec_lookahead": (dict(spec_lookahead=4), "rolled back"),
    "tp": (dict(tp=2), "per-slot matrices"),
    "pp": (dict(pp=2), "list of two kinds"),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_engine_options_that_need_the_state_moved_are_refused(option):
    over, why = REFUSED[option]
    with pytest.raises(NotImplementedError, match=why) as e:
        LLMEngine(_engine_config(**over))
    assert "per-slot linear-attention state" in str(e.value)


def test_prefix_reuse_and_the_hand_off_stay_off(params):
    engine = _engine(params=params, twin="params")
    with pytest.raises(NotImplementedError, match="hand-off"):
        engine.add_request("p", [1, 2, 3], SamplingParams(
            max_tokens=4, prefill_only=True))
    twin = _prompt(41, 70)
    _generate(engine, [twin, twin], 3)
    st = engine.stats()
    assert st["prefix_reuse_refused_total"] == 2
    assert st["prefix_token_hits"] == 0
    # both variants of every bucket: passes after a prompt's first run the
    # one with a context part
    programs = engine._warmup_programs(None, False)
    assert sorted(key[2] for _, key in programs) == [0, 0, 16, 16]


# ------------------------------------------------------ spans and counters
def test_records_and_stats_on_a_known_schedule(params):
    """Three prompts (150 tokens: passes of 64, 64, 22; 100: 64, 36; 40:
    one), then 6 decode steps: the counters against the selection rule
    counted independently (chipbench/sala_work.py)."""
    tracing.reset_ring()
    engine = _engine(params=params, twin="params")
    st = engine.stats()
    assert st["lin_state_pool_bytes"] == 4 * CFG.slot_state_bytes_row()
    assert st["sparse_index_pool_bytes"] == 3 * 64 * 2 * 4 * 16 * 4
    lens = (150, 40, 100)
    _generate(engine, [_prompt(50 + i, n) for i, n in enumerate(lens)], 6)
    st = engine.stats()
    assert st["prefill_passes_total"] == 6
    assert st["prefill_resumed_passes_total"] == 3
    assert st["lightning_prefill_tokens_total"] == 3 * sum(lens)
    fields = tracing.FIELDS["engine.dispatch"]
    recs = [dict(zip(fields, r)) for r in tracing.records("engine.dispatch")]
    pre = [r for r in recs if r["kind"] == "prefill"]
    dec = [r for r in recs if r["kind"] == "decode"]
    assert sorted(p for r in pre for p in r["pass_index"]) == [0, 0, 0, 1, 1,
                                                               2]
    assert sum(f for r in pre for f in r["final"]) == 3
    per = 3 * 2                        # sparse layers x kv-head groups
    for r in recs:
        assert (r["lin_layers"], r["sparse_layers"]) == (3, 3)
        assert r["lin_state_bytes_row"] == CFG.slot_state_bytes_row()
        assert r["ssm_layers"] is None and r["moe_assignments"] is None
        if r["kind"] == "prefill":
            t = np.concatenate([np.arange(e - q, e) for _, q, e in r["rows"]])
        else:
            assert r["pass_index"] is None and r["final"] is None
            t = np.asarray([c - 1 + j for _, _, c in r["rows"]
                            for j in range(r["k"])])
        assert r["sparse_tokens_read"] == per * int(
            sala_work.keys_attended(t, SC).sum())
        assert r["sparse_kernels_scored"] == per * int(
            sala_work.kernels_scored(t, SC).sum())
    assert st["lightning_state_updates_total"] == 3 * sum(
        r["k"] * len(r["rows"]) for r in dec)
    every = np.concatenate(
        [np.arange(n) for n in lens]
        + [np.asarray([c - 1]) for r in dec for _, _, c in r["rows"]])
    assert st["sparse_ctx_tokens_total"] == per * int((every + 1).sum())
    assert st["sparse_dense_rows_total"] == sum(
        c - 1 < 64 for r in dec for _, _, c in r["rows"])
    assert 0 < st["sparse_blocks_selected_total"] < st[
        "sparse_ctx_tokens_total"] / 16 + per * len(every)


def test_other_families_carry_none_of_it():
    fields = tracing.FIELDS["engine.dispatch"]
    for preset, n in (("tiny", 11), ("tiny-jamba", 16)):
        tracing.reset_ring()
        engine = _engine(model=preset, page_size=8,
                         prefill_buckets=(16, 32))
        _generate(engine, [[1, 2, 3, 4, 5]], 3)
        assert not [k for k in engine.stats()
                    if k.startswith(("lightning_", "sparse_", "lin_"))]
        assert engine.stats()["prefill_passes_total"] == 1
        # None from the family's first position to the device stamps,
        # which come last
        recs = tracing.records("engine.dispatch")
        assert recs and all(set(r[n:fields.index("enqueued_ns")]) == {None}
                            for r in recs)


# -------------------------------------------------------------- lightning
def _token_by_token(q, k, v, log_decay, s0, scale):
    def step(state, qkv):
        qt, kt, vt = qkv
        state = (jnp.exp(log_decay)[:, None, None] * state
                 + kt[:, :, None] * vt[:, None, :])
        return state, (qt[:, :, None] * state).sum(1) * scale

    state, o = jax.lax.scan(step, s0, (q, k, v))
    return o, state


@pytest.mark.parametrize("s, real, chunk", [(40, 40, 8), (100, 91, 32),
                                            (64, 0, 16), (300, 263, 128)])
def test_lightning_prefill_is_the_recurrence_from_a_nonzero_state(
        s, real, chunk):
    """Lengths that do not divide the chunk, a padded tail (no decay, no
    update past `real`), a head that hardly decays and one that forgets
    in two tokens."""
    h, d = 4, 16
    ks = jax.random.split(jax.random.PRNGKey(s), 4)
    q, k, v = (jax.random.normal(kk, (s, h, d)) for kk in ks[:3])
    s0 = jax.random.normal(ks[3], (h, d, d))
    log_decay = -jnp.asarray([1e-5, 0.02, 0.3, 0.9])
    want_o, want_s = _token_by_token(q[:real], k[:real], v[:real],
                                     log_decay, s0, 0.25)
    o, state = la.lightning_prefill(q, k, v, log_decay, s0, real,
                                    scale=0.25, chunk=chunk)
    np.testing.assert_allclose(o[:real], want_o, atol=3e-4, rtol=1e-4)
    np.testing.assert_allclose(state, want_s, atol=3e-4, rtol=1e-4)


def test_lightning_update_is_one_token_in_place_for_live_slots():
    h, d, slots = 4, 16, 6
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(kk, (slots, h, d)) for kk in ks[:3])
    pool = jax.random.normal(ks[3], (2, slots, h, d, d)).at[:, 4].set(
        jnp.nan)
    log_decay = -jnp.asarray([1e-5, 0.02, 0.3, 0.9])
    live = jnp.asarray([True, False, True, True, False, True])
    o, new = la.lightning_update(q, k, v, log_decay, pool, 1, live,
                                 scale=0.25)
    for i in range(slots):
        if not live[i]:
            assert (o[i] == 0).all()
            continue
        want_o, want_s = _token_by_token(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                         log_decay, pool[1, i], 0.25)
        np.testing.assert_allclose(o[i], want_o[0], atol=1e-5)
        np.testing.assert_allclose(new[1, i], want_s, atol=1e-5)
    bits = lambda a: np.asarray(a).view(np.uint32)   # noqa: E731
    np.testing.assert_array_equal(bits(new[0]), bits(pool[0]))
    np.testing.assert_array_equal(bits(new[1, [1, 4]]), bits(pool[1, [1, 4]]))
    o, same = la.lightning_update(q, k, v, log_decay, pool, 1,
                                  jnp.zeros((slots,), bool), scale=0.25)
    assert (o == 0).all()
    np.testing.assert_array_equal(bits(same), bits(pool))


# ----------------------------------------------------------------- sparse
G, REP, D = 2, 2, 16


def _paged(s, seed=0, pages=40):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    k, v = (jax.random.normal(kk, (1, s, G, D)) for kk in ks[:2])
    q = jax.random.normal(ks[2], (s, G * REP, D))
    kv = paged_write(jnp.zeros((1, pages, G, PAGE, 2 * D)), k, v, BT,
                     jnp.arange(s)[None], jnp.asarray([s]), 0)
    kc = sa.compress_keys(kv, jnp.zeros((1, pages, G, SP.kpb, D)), BT,
                          jnp.asarray([0]), jnp.asarray([s]), 0,
                          new_tokens=s, sp=SP)
    return q, k[0], v[0], kv, kc


def _plain_sparse(q, k, v):
    """The rule with dense scores, a query at a time (numpy)."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    s = q.shape[0]
    nk = (s - SP.kernel) // SP.stride + 1
    kc = np.stack([k[j * SP.stride:j * SP.stride + SP.kernel].mean(0)
                   for j in range(nk)])                      # [NK, G, D]
    out = np.zeros_like(q)
    sets = []
    for t in range(s):
        row = []
        for g in range(G):
            qs = q[t, g * REP:(g + 1) * REP]
            own = t // SP.block
            if t < SP.dense_len:
                blocks = set(range(own + 1))
            else:
                ok = [j for j in range(nk)
                      if j * SP.stride + SP.kernel <= t + 1]
                lg = qs @ kc[ok, g].T * 0.25
                p = np.exp(lg - lg.max(-1, keepdims=True))
                rel = (p / p.sum(-1, keepdims=True)).sum(0)
                w0 = max(t - (SP.window - 1), 0) // SP.block
                blocks = set(range(SP.init_blocks)) | set(range(w0, own + 1))
                score = {}
                for b in range(SP.init_blocks, w0):
                    over = [rel[i] for i, j in enumerate(ok)
                            if j * SP.stride + SP.kernel - 1 >= b * SP.block
                            and j * SP.stride <= b * SP.block + SP.block - 1]
                    score[b] = max(over)
                best = sorted(score, key=lambda b: (-score[b], b))
                blocks |= set(best[:SP.topk])
            keys = [i for i in range(t + 1) if i // SP.block in blocks]
            lg = qs @ k[keys, g].T * 0.25
            p = np.exp(lg - lg.max(-1, keepdims=True))
            out[t, g * REP:(g + 1) * REP] = (p / p.sum(-1, keepdims=True)
                                             ) @ v[keys, g]
            row.append((len(keys), len(blocks)))
        sets.append(row)
    return out, sets


def test_sparse_prefill_and_decode_are_the_rule_with_dense_scores():
    s = 150
    q, k, v, kv, kc = _paged(s)
    want, sets = _plain_sparse(q, k, v)
    got, picked = sa.sparse_prefill(q, kv, kc, BT[0], 0, s, 0, sp=SP,
                                    scale=0.25, q_tile=32, kv_pages_chunk=4)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert picked.sum(-1).tolist() == [[n for _, n in r] for r in sets]
    for t in (3, 63, 64, 70, 100, 128, 131, 149):
        one, sel = sa.sparse_decode(q[t][None], kv, kc, BT,
                                    jnp.asarray([t + 1]), 0, sp=SP,
                                    scale=0.25)
        np.testing.assert_allclose(one[0], want[t], atol=2e-5)
        assert (sel[0] == picked[t]).all()
    # the host's count of what a query attends is the rule's
    t = np.arange(s)
    assert sa.keys_attended(t, SP).tolist() == [r[0][0] for r in sets]
    assert (sa.keys_attended(t, SP) == sala_work.keys_attended(t, SC)).all()
    assert (sa.kernels_scored(t, SP)
            == sala_work.kernels_scored(t, SC)).all()
    assert sa.kernels_scored(np.asarray([63, 64, 70, 71]), SP).tolist() == [
        0, 15, 16, 17]


def test_a_tie_between_blocks_goes_to_the_lower_index():
    """A kernel that straddles two blocks gives both its relevance: where
    it is the largest, both blocks tie, and exactly topk join."""
    rel = jnp.zeros((1, 1, 32)).at[0, 0, 7].set(0.5).at[0, 0, 13].set(0.2)
    sel = sa._select(rel, jnp.asarray([127]), SP)[0, 0]
    # kernel 7 (keys 28..35) overlaps blocks 1 and 2; window blocks 6, 7
    assert np.nonzero(np.asarray(sel))[0].tolist() == [0, 1, 2, 6, 7]
    assert int(sel.sum()) == SP.init_blocks + 2 + SP.topk


@pytest.mark.parametrize("cuts", [(176,), (64, 128, 170), (16, 17, 90, 170)])
def test_compressed_keys_in_passes_are_bit_for_bit_those_of_one_pass(cuts):
    s = 170
    _, k, v, kv, want = _paged(s, seed=3)
    kc = jnp.zeros_like(want)
    start = 0
    for end in cuts:
        end = min(end, s)
        kc = sa.compress_keys(kv, kc, BT, jnp.asarray([start]),
                              jnp.asarray([end]), 0,
                              new_tokens=max(end - start, 1), sp=SP)
        start = end
    np.testing.assert_array_equal(np.asarray(kc).view(np.uint32),
                                  np.asarray(want).view(np.uint32))
    # kernel j in page j // 4, slot j % 4: the mean of its 8 keys
    j = 9
    np.testing.assert_allclose(want[0, 1 + j // 4, :, j % 4],
                               k[4 * j:4 * j + 8].mean(0), atol=1e-6)
    assert float(jnp.abs(want[0, 1 + 41 // 4, :, 41 % 4:]).max()) == 0.0


def test_the_decode_kernel_in_interpret_mode_reads_a_selected_table():
    """The paged-decode Pallas kernel (interpret mode) over the pool viewed
    a head a page, through a table of selected pages with the own block's
    part last, against its jax.numpy form."""
    s = 150
    q, _, _, kv, kc = _paged(s, seed=5)
    lengths = jnp.asarray([s, 100])
    bt = jnp.concatenate([BT, BT])
    table, sel_len, _ = sa._sparse_select(
        q[jnp.asarray([s - 1, 99])], kc, bt, lengths, jnp.int32(0), sp=SP,
        scale=0.25, groups=G)
    assert table.shape == (4, SP.table_width(MP))
    assert sel_len.tolist() == [s - 1 - 80 + 1 - 0] * 2 + [36 + 16] * 2 \
        or sel_len.shape == (4,)
    pool = kv.reshape(1, -1, 1, PAGE, 2 * D)
    rows = q[jnp.asarray([s - 1, 99])].reshape(4, REP, D)
    want = paged_attention_decode(rows, pool, table, sel_len, layer=0,
                                  scale=0.25, force_reference=True)
    got = paged_attention_decode(rows, pool, table, sel_len, layer=0,
                                 scale=0.25, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)
