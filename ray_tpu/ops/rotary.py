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

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


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
