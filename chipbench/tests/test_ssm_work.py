"""The selective scan's and a hybrid decode step's needed work, by hand at
Jamba2-3B's widths; the two readers built on it, on a hand-made trace with
records made to fit it; the mixer's weights as Mamba initialises them."""

import os
import time
import types

import pytest

from chipbench import cell as cell_mod
from chipbench import ssm_work
from chipbench import tracered as t
from ray_tpu.util import tracing

D, N = 5120, 16
MS = 1_000_000
PUB = cell_mod.load_json(os.path.join(
    cell_mod.HERE, "configs", "ai21-jamba2-3b-serve.json"))
PEAKS = cell_mod.load_peaks("TPU v5 lite")
STATE_ROW = 26 * (D * N * 4 + D * 3 * 2)


def test_scan_counts_are_the_issues_per_token_and_layer():
    assert ssm_work.scan_bytes(1, D, N) == 41_088
    assert ssm_work.scan_ops(1, D, N) == 737_280
    # a 2048-token row over the 26 layers: 2.7 ms of bytes at 819 GB/s
    row = ssm_work.scan_bytes(2048 * 26, D, N) / PEAKS["hbm_bytes_per_s"]
    assert row == pytest.approx(2.67e-3, rel=0.01)
    assert ssm_work.scan_bytes(0, D, N) == 0 == ssm_work.scan_ops(0, D, N)


def test_weights_are_the_whole_model_once():
    # 26 x 104.16 M + 2 x 76.68 M + 167.77 M (tied) + the final norm
    assert ssm_work.hybrid_weight_bytes(PUB) == 2 * 3_029_337_472
    untied = dict(PUB, tie_word_embeddings=False)
    assert ssm_work.hybrid_weight_bytes(untied) \
        == 2 * (3_029_337_472 + 65536 * 2560)


def test_a_decode_step_moves_weights_live_state_and_live_kv():
    w = ssm_work.hybrid_weight_bytes(PUB)
    assert STATE_ROW == 9_318_400                    # 9.32 MB a slot
    none = ssm_work.decode_step_bytes(w, 0, STATE_ROW, 0, 1024)
    assert none == w
    step = ssm_work.decode_step_bytes(w, 32, STATE_ROW, 32 * 400, 1024)
    assert step == w + 2 * 32 * STATE_ROW + 32 * 400 * 1024
    # at 32-64 live rows: 6.7-7.3 GB, 8.1-8.9 ms at 819 GB/s
    lo = step / PEAKS["hbm_bytes_per_s"]
    hi = ssm_work.decode_step_bytes(w, 64, STATE_ROW, 64 * 400, 1024) \
        / PEAKS["hbm_bytes_per_s"]
    assert 8.0e-3 < lo < 8.3e-3 and 8.8e-3 < hi < 9.0e-3


# ---------------------------------------------------------------- readers
@pytest.fixture
def empty_ring():
    tracing.reset_ring()
    yield
    tracing.reset_ring()


def _ctx(trace):
    lines = []
    cell = types.SimpleNamespace(config=PUB)
    return {"runner": types.SimpleNamespace(t0=time.monotonic() - 60.0),
            "seconds": 50.0, "log": lines.append, "lines": lines,
            "trace": t.reduce_trace(trace), "peaks": PEAKS, "cell": cell}


def _made_trace():
    """Six steps of 20 ms: a prefill program (its scans nested under a
    while) in steps 1 and 3, a decode program in every other."""
    step, ops, modules, host = 20 * MS, [], [], []
    kinds = ["decode", "prefill", "decode", "prefill", "decode", "decode"]
    for i, kind in enumerate(kinds):
        s = (i + 1) * step
        host.append(("chipbench.engine.step", s - 2 * MS, 1 * MS))
        if kind == "prefill":
            modules.append(("jit_run_prefill(123)", s, 12 * MS))
            # names as `tracered.op_display_name` cuts them
            ops.append(("while.3 while (s32[])", s, 11 * MS))
            ops.append(("_ssm_scan pallas (f32[2048,5,8,128], "
                        "f32[5,16,8,128])", s + MS, 2 * MS))
            ops.append(("_ssm_scan.1 pallas (f32[2048,5,8,128], "
                        "f32[5,16,8,128])", s + 4 * MS, 3 * MS))
            ops.append(("fusion.7 fusion bf16[2048,10240]", s + 8 * MS,
                        2 * MS))
        else:
            modules.append(("jit_run_decode(456)", s, 10 * MS))
            ops.append(("fusion.9 fusion bf16[64,16384]", s, 9 * MS))
    return t.Trace(ops={0: ops}, modules={0: modules}, host=host,
                   window=(10 * MS, 8 * step)), kinds


def _records(trace, kinds, ssm=True):
    offset = time.time_ns() - 30 * 10**9
    for i, (_, s, d) in enumerate(trace.host):
        tracing.record("engine.step", (
            i, s + offset, s + d + offset, 0, 0, 0, 0, 0, 0, 3, 0))
    for i, ((_, s, d), kind) in enumerate(zip(trace.modules[0], kinds)):
        rows = ((("a", 300, 300), ("b", 1000, 1000)) if kind == "prefill"
                else (("c", 1, 500), ("d", 1, 700), ("e", 1, 900)))
        tail = (None, None, None, 26, STATE_ROW) if ssm else ()
        tracing.record("engine.dispatch", (
            i, kind, i, i + 1, s - 3 * MS + offset, s + offset,
            s + d + offset + 200_000, 64, 64, rows, 1) + tail)


def test_scan_roofline_is_real_tokens_bytes_over_the_kernels_time(
        empty_ring):
    trace, kinds = _made_trace()
    ctx = _ctx(trace)
    read = cell_mod.load_module("readers", "ssm_scan_roofline").read
    pattern = cell_mod.load_json(os.path.join(
        cell_mod.HERE, "layer_metrics", "ssm_scan_roofline.ttft.json")
        )["params"]["op_pattern"]
    assert read(ctx, pattern) is None                 # no record yet
    _records(trace, kinds)
    got = read(ctx, pattern)
    least = ssm_work.scan_bytes(2 * 26 * 1300, D, N) / 819e9
    assert got == pytest.approx(100 * least / (2 * 5e-3))
    assert 0 < got < 100
    assert any("2 prefill programs paired" in x for x in ctx["lines"])
    # the share of busy time, by the same pattern
    pct = cell_mod.load_module("readers", "trace_op_time_pct").read(
        ctx, pattern)
    assert pct == pytest.approx(100 * 10 / (4 * 9 + 2 * 11))


def test_decode_bytes_roofline_counts_live_rows_only(empty_ring):
    trace, kinds = _made_trace()
    ctx = _ctx(trace)
    read = cell_mod.load_module("readers", "decode_bytes_roofline").read
    _records(trace, kinds)
    got = read(ctx)
    step = ssm_work.decode_step_bytes(
        ssm_work.hybrid_weight_bytes(PUB), 3, STATE_ROW, 2100, 1024)
    assert got == pytest.approx(100 * (step / 819e9) / 10e-3)
    assert 70 < got < 80
    assert any("4 decode programs paired" in x and "3.0 live rows" in x
               for x in ctx["lines"])


def test_records_of_another_model_read_nothing(empty_ring):
    trace, kinds = _made_trace()
    ctx = _ctx(trace)
    _records(trace, kinds, ssm=False)
    assert cell_mod.load_module("readers", "decode_bytes_roofline").read(
        ctx) is None
    assert cell_mod.load_module("readers", "ssm_scan_roofline").read(
        ctx, "^_ssm_scan") is None
    assert any("no decode record carries" in x for x in ctx["lines"])


# ---------------------------------------------------------------- weights
def test_the_mixers_leaves_are_made_as_mamba_makes_them():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import weights_ssm

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    tree = {"embed": sds(64, 32), "period_0": {"pre": {"mixer": {
        "A_log": sds(3, 16, 64), "D": sds(3, 64), "dt_bias": sds(3, 64),
        "conv_kernel": sds(3, 4, 64), "conv_bias": sds(3, 64),
        "in_proj": {"kernel": sds(3, 32, 128)},
        "dt_norm": {"scale": sds(3, 8)}}}}}
    a, b = (weights_ssm.make_params(tree, s) for s in (7, 2**31 + 5))
    m = a["period_0"]["pre"]["mixer"]
    np.testing.assert_allclose(m["A_log"][1, :, 9], np.log(np.arange(1, 17)),
                               rtol=1e-6)
    assert (m["D"] == 1).all()
    step = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert 0.99e-3 <= step.min() and step.max() <= 0.101
    assert step.std() > 0.01
    for name in ("conv_kernel", "conv_bias"):
        assert 0.3 < float(jnp.abs(m[name]).max()) <= 0.5
    # everything else is weights.py's: N(0, 1/fan_in), scales near 1
    assert abs(float(m["in_proj"]["kernel"].std()) - 32 ** -0.5) < 0.02
    assert abs(float(m["dt_norm"]["scale"].mean()) - 1) < 0.2
    assert not np.array_equal(m["dt_bias"],
                              b["period_0"]["pre"]["mixer"]["dt_bias"])
