"""The control of `correct`, at a size a test run can hold: the tiny
configuration states float32, so its control is the plain reference
computed in bfloat16. The sound program passes the tiny limits; the control
must not."""

import numpy as np
import pytest

from chipbench import cell as cell_mod
from chipbench import control


@pytest.fixture(scope="module")
def tiny_engine_runner():
    from chipbench.runners import engine as er

    cell = cell_mod.load_cell("tiny-chat")
    runner = er.Runner(cell, 1234, 2, lambda msg: None)
    check = runner.setup()
    return cell, runner, check


def test_sound_program_passes(tiny_engine_runner):
    cell, runner, check = tiny_engine_runner
    assert check["correct"], check["numbers"]


@pytest.fixture(scope="module", params=[1, 2, 3_000_000_019])
def sample_on_seed(request):
    """A sound run's check sample (its sequences and the engine's tokens)
    on three seeds: what the control is teacher-forced along."""
    from chipbench.runners import engine as er

    cell = cell_mod.load_cell("tiny-chat")
    runner = er.Runner(cell, request.param, 2, lambda msg: None)
    assert runner.setup(warm=False)["correct"]
    return cell, runner


def test_control_in_lower_precision_fails(sample_on_seed):
    cell, runner = sample_on_seed
    ref_w = runner.reference.weights_from_program_tree(runner.engine.params)
    assert control.BELOW["float32"] == ("bfloat16",)
    res = control.serve_numbers(runner.reference, ref_w,
                                dict(runner.published), "bfloat16",
                                runner.check_sample, cell.config["limits"])
    assert not res["correct"], res["numbers"]
    same = control.serve_numbers(runner.reference, ref_w,
                                 dict(runner.published), "float32",
                                 runner.check_sample, cell.config["limits"])
    assert same["correct"] and all(r["value"] == 0.0
                                   for r in same["numbers"])


def _tiny_weights():
    import jax
    import jax.numpy as jnp

    shapes = {
        "embed": (256, 64), "lm_head": (64, 256), "final_norm": (64,),
        "layers": {"qkv": (2, 64, 128), "o": (2, 64, 64),
                   "gate_up": (2, 64, 256), "down": (2, 128, 64),
                   "attn_norm": (2, 64), "mlp_norm": (2, 64)}}
    rng = np.random.default_rng(0)
    w = jax.tree.map(lambda s: jnp.asarray(
        rng.normal(0, s[-2] ** -0.5 if len(s) > 1 else 1.0, s), jnp.float32),
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    ids = jnp.asarray(rng.integers(0, 256, (2, 48)), jnp.int32)
    return w, ids


TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=256, rope_theta=10000.0, rms_norm_eps=1e-5)


@pytest.mark.parametrize("number", ["nll_rms_err_over_std", "grad_rel_err",
                                    "grad_worst_leaf_rel_err",
                                    "step_grad_norm_vs_backward"])
def test_training_control_fails_on_every_number(number):
    """Forward AND backward: the control's gradient matmuls are rounded
    too, and each gradient number alone catches it."""
    from chipbench.references import dense_decoder as ref

    w, ids = _tiny_weights()
    limits = {"nll_rms_err_over_std": 1e-3, "grad_rel_err": 1e-3,
              "grad_worst_leaf_rel_err": 1e-3,
              "step_grad_norm_vs_backward": 1e-6}
    res = control.train_numbers(ref, w, TINY, "bfloat16", ids,
                                {number: limits[number]})
    assert not res["correct"], res["numbers"]
    same = control.train_numbers(ref, w, TINY, "float32", ids, limits)
    assert same["correct"] and all(r["value"] == 0.0
                                   for r in same["numbers"])


def test_a_backward_only_fault_is_caught_by_the_gradient_numbers_alone():
    """The forward of the control rounded nothing here (float32), only the
    backward's matmuls did: the per-token loss agrees exactly and the
    gradient numbers still fail."""
    import jax

    from chipbench import compare
    from chipbench.references import dense_decoder as ref

    w, ids = _tiny_weights()
    (_, nll), g32 = jax.jit(lambda w, x: ref.loss_and_grads(w, x, TINY))(
        w, ids)

    def loss_backward_rounded(w):
        # float32 forward values, bfloat16-rounded gradient matmuls
        real_mm = ref._mm
        ref._mm = lambda a, b, p: _fwd32_bwd_rounded(a, b)
        try:
            return ref.next_token_nll(w, ids, TINY, "float32").mean()
        finally:
            ref._mm = real_mm

    @jax.custom_vjp
    def _fwd32_bwd_rounded(a, b):
        return a @ b

    def fwd(a, b):
        return a @ b, (a, b)

    def bwd(res, g):
        return ref._mm_control_bwd("bfloat16", res, g)

    _fwd32_bwd_rounded.defvjp(fwd, bwd)
    with jax.default_matmul_precision("highest"):
        g = jax.grad(loss_backward_rounded)(w)
    check = compare.LossCheck()
    check.set_grads(compare.grad_sums(g, g32))
    assert check.values["grad_rel_err"] > 1e-3
    assert check.values["grad_worst_leaf_rel_err"] > 1e-3
