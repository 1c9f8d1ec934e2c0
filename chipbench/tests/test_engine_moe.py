"""Runner `engine_moe`: the number it adds to the output check."""

import numpy as np

from chipbench.runners import engine_moe


def _pair(rng, layers=2, padded=64, vocab=32, k=2):
    logits = rng.normal(size=(padded, vocab))
    chosen = np.sort(rng.integers(0, 8, (layers, padded, k)), axis=-1)
    return logits, chosen


def test_agree_number_reads_only_positions_whose_every_layer_agrees():
    rng = np.random.default_rng(3)
    seqs = [([1, 2, 3], [4, 5, 6])]          # 5 positions, padded to 64
    ref_logits, ref_chosen = _pair(rng)
    logits, chosen = ref_logits.copy(), ref_chosen.copy()
    logits[:5] += 1e-3 * rng.normal(size=(5, 32))     # rounding
    chosen[1, 3, 0] = (chosen[1, 3, 0] + 1) % 8       # one flipped expert
    logits[3] += 0.5 * rng.normal(size=32)            # and what it costs
    logits[7] += 9.0                                  # padding: not read

    row, notes = engine_moe.expert_choice(
        lambda ids: (logits, chosen), lambda ids: (ref_logits, ref_chosen),
        seqs, {engine_moe.AGREE: 0.01})
    assert row["name"] == engine_moe.AGREE and row["ok"]
    assert 5e-4 < row["value"] < 2e-3
    assert notes["expert_sets_differ"] == 1
    assert notes["expert_sets_compared"] == 2 * 5
    assert notes["logit_rel_rms_err_where_they_differ"] > 0.3

    chosen[1, 3] = ref_chosen[1, 3]          # same logits, no flip: judged
    row, notes = engine_moe.expert_choice(
        lambda ids: (logits, chosen), lambda ids: (ref_logits, ref_chosen),
        seqs, {engine_moe.AGREE: 0.01})
    assert not row["ok"] and row["value"] > 0.1
    assert notes["logit_rel_rms_err_where_they_differ"] is None


def test_no_position_agrees_is_not_correct():
    rng = np.random.default_rng(4)
    ref_logits, ref_chosen = _pair(rng)
    row, _ = engine_moe.expert_choice(
        lambda ids: (ref_logits, (ref_chosen + 1) % 8),
        lambda ids: (ref_logits, ref_chosen), [([1], [2, 3])],
        {engine_moe.AGREE: 1.0})
    assert not row["ok"]
