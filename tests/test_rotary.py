"""The rotation as a kernel over rows of `[B, S, H x D]` (`ops/rotary.py:
rotate_rows`, interpret mode on the CPU) against `models/llama.py: rope`'s
jax.numpy form, values and gradients, and where each of the two is taken."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaModel, get_config, rope
from ray_tpu.ops.rotary import rotate_rows, rows_rotatable

THETA = 1e6


def _operands(b, s, h, d, dtype, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (b, s, h, d),
                          jnp.float32).astype(dtype)
    positions = jnp.arange(s)[None] + 1000 * jnp.arange(1, b + 1)[:, None]
    return x, positions


def _tables(positions, d):
    freqs = THETA ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(angles), jnp.sin(angles)


# (batch, sequence, heads, head width, dtype): a block of 128, 256 and 512
# rows; one head, a group that is one lane block of 1024, heads over
# several blocks; a head of two lane tiles
ROW_CASES = {
    "one-head-128-rows": (2, 128, 1, 128, jnp.float32),
    "8-heads-one-block": (1, 256, 8, 128, jnp.float32),
    "20-heads-two-blocks-512-rows": (1, 512, 20, 128, jnp.float32),
    "6-heads-384-rows": (2, 384, 6, 128, jnp.float32),
    "heads-of-256": (1, 128, 3, 256, jnp.float32),
    "bf16": (2, 256, 4, 128, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(ROW_CASES))
def test_the_rows_kernel_is_ropes_arithmetic_and_its_backward_turns_back(
        case):
    """Values: the kernel's float32 `x cos + partner sin` against `rope`'s
    `x1 cos - x2 sin | x2 cos + x1 sin` (one rounding of the last place
    apart in bf16: the same products, added in another order). Gradients:
    the kernel's backward is the same pass with the sine negated, against
    jax's own transpose of the jax.numpy form."""
    b, s, h, d, dtype = ROW_CASES[case]
    x, positions = _operands(b, s, h, d, dtype, seed=len(case))
    cos, sin = _tables(positions, d)
    tol = dict(atol=1e-5, rtol=1e-5) if dtype == jnp.float32 else dict(
        atol=2e-2, rtol=2e-2)
    assert rows_rotatable(x)
    got = rotate_rows(x, cos, sin, interpret=True)
    want = rope(x, positions, THETA)
    assert got.dtype == want.dtype == dtype and got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)

    def loss(fn):
        return lambda x: jnp.sum(jnp.sin(fn(x).astype(jnp.float32)))

    g_got = jax.grad(loss(lambda x: rotate_rows(x, cos, sin,
                                                interpret=True)))(x)
    g_want = jax.grad(loss(lambda x: rope(x, positions, THETA)))(x)
    np.testing.assert_allclose(np.asarray(g_got, np.float32),
                               np.asarray(g_want, np.float32), **tol)


@pytest.mark.parametrize("shape", [(2, 1, 4, 128), (2, 8, 4, 128),
                                   (2, 128, 4, 64), (2, 200, 4, 128)],
                         ids=lambda s: "x".join(map(str, s)))
def test_a_shape_the_rows_kernel_does_not_take_keeps_ropes_jaxpr(shape):
    """A decode step's one token, a span of 8, a head of 64 and a sequence
    that is no whole block of 128 rows: `rope(..., rows=True)` is the
    jax.numpy form it was, to the character (the decode programs and the
    tiny configurations' pinned jaxprs do not move)."""
    x, positions = _operands(*shape, jnp.float32)
    assert not rows_rotatable(x)
    plain = jax.make_jaxpr(lambda x: rope(x, positions, THETA))(x)
    rows = jax.make_jaxpr(lambda x: rope(x, positions, THETA, True))(x)
    assert str(plain) == str(rows)
    assert "pallas_call" not in str(rows)


def test_the_model_rotates_rows_where_its_attention_is_the_flash_kernel():
    """`Attention` asks for the rows kernel exactly where its attention is
    the one-device flash kernel (`ops/attention.py: flash_on_one_device`):
    at heads of 128 `attention_impl="flash"` traces three kernels a layer
    (two rotations and the flash forward) and `"reference"` none, and the
    logits and the gradients agree."""
    import flax.linen as nn

    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0, 256)
    out = {}
    for impl in ("flash", "reference"):
        cfg = get_config("tiny", attention_impl=impl, dtype=jnp.float32,
                         head_dim=128, num_heads=2, num_kv_heads=1,
                         num_layers=1, scan_layers=False)
        model = LlamaModel(cfg)
        params = nn.meta.unbox(model.init(jax.random.PRNGKey(0),
                                          ids)["params"])

        def loss(p):
            return jnp.mean(jnp.square(model.apply({"params": p}, ids)))

        text = str(jax.make_jaxpr(loss)(params))
        assert text.count("pallas_call") == (3 if impl == "flash" else 0)
        out[impl] = (model.apply({"params": params}, ids),
                     jax.grad(loss)(params))
    np.testing.assert_allclose(out["flash"][0], out["reference"][0],
                               atol=2e-4, rtol=2e-4)
    for a, b in zip(jax.tree.leaves(out["flash"][1]),
                    jax.tree.leaves(out["reference"][1])):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-3)
