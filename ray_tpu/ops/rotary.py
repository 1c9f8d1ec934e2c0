"""Rotary frequencies that are not `theta^(-2i/d)`: YaRN's.

`models/llama.py: rope` takes a `theta` and computes its frequencies in the
program. A model whose context was extended by YaRN (Peng et al., 2023,
arXiv:2309.00071; the DeepSeek-V3 family's `rope_scaling` of type "yarn")
rotates by a blend a dimension: the pairs that turn fast (many turns inside
the original context) keep their frequency, the slow ones are divided by
`factor`, the ones between are interpolated. The blend depends on the
config alone, so it is a STATIC table (numpy, float64, made once at trace
time) and the program holds it as a constant.

The pairing is `rope`'s: dims i and i + d/2 turn together (the published
code de-interleaves a head's dims before its `rotate_half`, which gives
that layout).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """[dim // 2] float32. `f_i = theta^(-2i/dim)`; `dim(r) = dim ln(orig /
    (2 pi r)) / (2 ln theta)` is the pair that makes r turns over the
    original context; pairs below `low = floor(dim(beta_fast))` keep f_i,
    pairs above `high = ceil(dim(beta_slow))` get f_i / factor, between
    them a linear ramp."""
    half = dim // 2
    f = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / dim)

    def pair_of(turns: float) -> float:
        return (dim * math.log(original_max_position / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (f / factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 mscale ln(factor) + 1."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def rotate(x: jax.Array, positions: jax.Array, inv_freq,
           factor: float = 1.0, rotary_dim: Optional[int] = None) -> jax.Array:
    """x [B, S, H, D] turned by `positions` [B, S] x `inv_freq`
    [rotary_dim // 2] (`models/llama.py: rope` with the frequencies given).
    `factor` multiplies cos and sin (YaRN's `attention_factor` where a
    model applies it to the rotation: a score of two rotated vectors
    carries its square). `rotary_dim` < D: only the head's FIRST
    `rotary_dim` dims turn (dims i and i + rotary_dim/2 together), the
    rest pass through untouched and unscaled (a `partial_rotary_factor`)."""
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(
        inv_freq, jnp.float32)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    rest = None
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        x, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = out.astype(x.dtype)
    return out if rest is None else jnp.concatenate([out, rest], axis=-1)


# ------------------------------------------------- the rotation, on rows
# `[B, S, H, D]` is `[B, S, H x D]` as a projection writes it and as the
# flash kernels' blocks index it (ops/flash_attention.py: `_heads_on_lanes`),
# but XLA's own layout of the four-dimensional shape keeps H on the
# sublanes: written in jax.numpy, the rotation costs a copy of the array on
# its way in and another on its way out, and the split of a head's lanes at
# d/2 two passes more (compiled for a described v5e: five passes over q
# where this is one). The kernel reads a row's block, turns each head's
# lanes against the tile's other half (`pltpu.roll`) and writes it back.
_ROW_LANES = 1024   # lanes of a block: whole heads, 512 rows x 1024 x 2 B


def _rows_kernel(x_ref, cos_ref, sin_ref, o_ref):
    cos, sin = cos_ref[0], sin_ref[0]      # [rows, d]: (cos | cos), (-sin | sin)
    d = cos.shape[1]
    for head in range(x_ref.shape[2] // d):
        lanes = slice(head * d, (head + 1) * d)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        o_ref[0, :, lanes] = (x * cos + pltpu.roll(x, d // 2, 1) * sin
                              ).astype(o_ref.dtype)


def _rows_call(x, cos, sin, interpret):
    b, s, h, d = x.shape
    rows = next(r for r in (512, 256, 128) if s % r == 0)
    heads = max(g for g in range(1, h + 1)
                if h % g == 0 and g * d <= max(_ROW_LANES, d))
    block = pl.BlockSpec((1, rows, heads * d), lambda b_, i, j: (b_, i, j))
    table = pl.BlockSpec((1, rows, d), lambda b_, i, j: (b_, i, 0))
    return pl.pallas_call(
        _rows_kernel, grid=(b, s // rows, h // heads),
        in_specs=[block, table, table], out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, s, h * d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=interpret,
    )(x.reshape(b, s, h * d), cos, sin).reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _turn_rows(x, cos, sin, interpret):
    return _rows_call(x, cos, sin, interpret)


def _turn_rows_fwd(x, cos, sin, interpret):
    return _rows_call(x, cos, sin, interpret), (cos, sin)


def _turn_rows_bwd(interpret, tables, g):
    # the transpose of a rotation is the rotation back
    cos, sin = tables
    return (_rows_call(g, cos, -sin, interpret), jnp.zeros_like(cos),
            jnp.zeros_like(sin))


_turn_rows.defvjp(_turn_rows_fwd, _turn_rows_bwd)


def rows_rotatable(x: jax.Array) -> bool:
    """Whether `rotate_rows` takes `x` [B, S, H, D]: a head is whole lane
    tiles and the sequence whole blocks of 128 rows (the flash kernels'
    granule; a decode step's one token is not)."""
    return x.shape[-1] % 128 == 0 and x.shape[1] % 128 == 0


def rotate_rows(x: jax.Array, cos: jax.Array, sin: jax.Array, *,
                interpret: Optional[bool] = None) -> jax.Array:
    """`x1 cos - x2 sin | x2 cos + x1 sin` of every head of x [B, S, H, D]
    (dims i and i + D/2 together, float32 arithmetic, x's dtype out) for
    cos, sin [B, S, D/2]: `models/llama.py: rope`'s arithmetic as ONE pass
    of a Pallas kernel over rows of `[B, S, H x D]`, with a backward that
    is the same pass turned back. A one-device program, as the flash
    kernels are (`interpret` as theirs: None is by the backend); under a
    name of its own in a trace (`_rotate_rows`)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _rotate_rows(x, cos, sin, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rotate_rows(x, cos, sin, *, interpret: bool):
    cos = jnp.concatenate([cos, cos], axis=-1).astype(jnp.float32)
    sin = jnp.concatenate([-sin, sin], axis=-1).astype(jnp.float32)
    return _turn_rows(x, cos, sin, interpret)
