"""One stage-compute body (serve/llm/stage.py): a pipeline's stage
workers, chained in ONE process with no actor and no channel, emit what a
single StageCompute over all layers emits — the fast twin of the `slow`
pipelined-engine parity tests in tests/test_llm_pp.py.
"""

import numpy as np
import pytest

from ray_tpu.serve.llm import EngineConfig
from ray_tpu.serve.llm.pp import PipelinedEngine, _StageWorker
from ray_tpu.serve.llm.stage import StageCompute

CFG = dict(page_size=8, num_pages=64, max_model_len=128, max_batch=8,
           prefill_buckets=(32,), dtype="float32",
           model_overrides={"vocab_size": 512})
SB, RB, MP, S = 32, 4, 128 // 8, 8


@pytest.fixture(scope="module", params=["tiny", "tiny-moe"])
def stages(request):
    """(the whole model on one StageCompute, its layers [0,1) and [1,2)
    on two stage workers holding slices of the same params)."""
    whole = StageCompute(EngineConfig(model=request.param, **CFG))
    chain = [_StageWorker(EngineConfig(model=request.param, pp=2, **CFG), s)
             for s in range(2)]
    for worker in chain:
        worker.load_params(whole.params)
    assert [(w.compute.first_layer, w.compute.n_layers, w.compute.first,
             w.compute.last) for w in chain] == [(0, 1, True, False),
                                                 (1, 1, False, True)]
    return whole, chain


def _both(stages, kind, key, operands):
    """The program's host-bound result from the whole model and from the
    chain (tokens, and an expert model's [L, E] counts behind them)."""
    whole, chain = stages
    want = np.asarray(whole.run(kind, key, *operands))
    frame = PipelinedEngine._frame(kind, key, operands)
    for worker in chain:
        frame = worker.tick(frame)
    assert frame["kind"] == kind
    return want, frame["toks"]


@pytest.mark.parametrize("n", [1, 3, RB])
def test_two_chained_stages_emit_the_whole_models_tokens(stages, n):
    """A wave of 1, 3 and a full wave of rows through the row loop, then
    a K = 1 decode step of those rows' slots: greedy tokens (and routing
    counts) bit-equal, the padding rows never computed by either."""
    rng = np.random.default_rng(n)
    lens = [int(x) for x in rng.integers(9, SB + 1, n)]
    ids = np.zeros((RB, SB), np.int32)
    positions = np.zeros((RB, SB), np.int32)
    bt = np.zeros((RB, MP), np.int32)
    total = np.zeros((RB,), np.int32)
    gather = np.zeros((RB,), np.int32)
    for i, ln in enumerate(lens):
        ids[i, :ln] = rng.integers(0, 500, ln)
        positions[i] = np.arange(SB)
        bt[i, :5] = 1 + 5 * i + np.arange(5)   # 33 tokens: 5 pages
        total[i], gather[i] = ln, ln - 1
    want, got = _both(stages, "prefill", (SB, RB, 0), (
        np.int32(n), bt, total, ids, positions, gather,
        np.zeros((RB,), np.float32), np.zeros((RB,), np.int32),
        np.zeros((RB, 2), np.uint32)))
    np.testing.assert_array_equal(got, want)
    first = want[:RB]

    # each row's slot decodes one step from its prefill's token
    dbt = np.zeros((S, MP), np.int32)
    dtotal = np.zeros((S,), np.int32)
    dpos = np.zeros((S, 1), np.int32)
    mask = np.zeros((S,), bool)
    x = np.zeros((S, 1), np.int32)
    for i, ln in enumerate(lens):
        dbt[i], dtotal[i], dpos[i, 0] = bt[i], ln + 1, ln
        mask[i], x[i, 0] = True, first[i]
    want, got = _both(stages, "decode", (1, MP), (
        dbt, dtotal, np.full((S,), 128, np.int32), dpos, mask, x,
        np.zeros((S,), np.float32), np.zeros((S,), np.int32),
        np.zeros((1, S, 2), np.uint32)))
    np.testing.assert_array_equal(got, want)
    if stages[0].model_cfg.num_experts:
        # [2 layers, 4 experts]: every real row's token, top-2 a layer
        counts = want[S:].reshape(2, 4)
        assert counts.sum(axis=1).tolist() == [2 * n, 2 * n]
