"""The grouped matmul's needed work, by hand at Mixtral's widths."""

from chipbench import moe_work

H, F = 4096, 14336


def test_ops_are_three_matmuls_per_real_assignment():
    assert moe_work.gmm_ops(1, H, F) == 2 * 3 * H * F == 352_321_536
    # a [16 x 2048] wave with one real row of 256 tokens, top-2, 3 layers
    assert moe_work.gmm_ops(256 * 2 * 3, H, F) == 1536 * 352_321_536
    assert moe_work.gmm_ops(0, H, F) == 0


def test_bytes_are_touched_experts_plus_activations():
    one_expert = 3 * H * F * 2
    assert one_expert == 352_321_536      # bf16: by chance the ops' number
    row = 2 * (H + 2 * F + F + H)
    assert moe_work.gmm_bytes(10, 6, H, F) == 6 * one_expert + 10 * row
    # no real token: nothing has to move
    assert moe_work.gmm_bytes(0, 0, H, F) == 0
    # all 8 experts of one layer against 2 of them: weights dominate a
    # decode step, where an expert sees one or two rows
    assert (moe_work.gmm_bytes(12, 8, H, F)
            / moe_work.gmm_bytes(12, 2, H, F)) > 3.9


def test_decode_is_bytes_bound_and_a_full_wave_compute_bound():
    peak_ops, peak_bytes = 197e12, 819e9
    step = (moe_work.gmm_ops(12, H, F) / peak_ops,
            moe_work.gmm_bytes(12, 6, H, F) / peak_bytes)
    assert step[1] > 100 * step[0]
    wave = (moe_work.gmm_ops(2 * 16 * 2048, H, F) / peak_ops,
            moe_work.gmm_bytes(2 * 16 * 2048, 8, H, F) / peak_bytes)
    assert wave[0] > 10 * wave[1]
