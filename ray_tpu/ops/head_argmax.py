"""The vocabulary head of a greedy pass, decided where its product is
(serve/llm/stage.py: `_block_program`; models/sdar.py: `decide`).

`x` [R, h] (the final-normed hidden states) times `w` [h, V] (`lm_head`)
-> a row's argmax, its largest logit and its log-sum-exp: the three
numbers a greedy denoising pass keeps of a row's V logits. The [R, V]
logits exist a vocabulary tile at a time in VMEM and never reach HBM:
written out in float32 and read back by the reductions they are three
quarters of the bytes of the weights that make them (256 rows at
151,936).

The logits are the ones the plain head's program reduces on the chip: the
product accumulated in float32 and reduced in float32, NOT rounded to the
operands' type on the way. (`lm_head` at `dtype` bf16 followed by
`.astype(float32)` reads as a rounding, but XLA keeps the product's
float32: the convert joins the product's own fusion, `fusion.<n>
f32[64,4,151936]` under `rtpu.head/lm_head`. benchmarks/
head_argmax_probe.py: against that form this kernel's argmax agrees in
every row and the confidence to 3e-9, where a rounded product moves the
argmax of 1-2% of the rows.) Ties keep the lowest index (`jnp.argmax`'s
rule). The log-sum-exp is summed tile by tile under a running maximum,
which differs from the two-pass sum in the last float32 bits.

On a TPU backend a Pallas kernel over vocabulary tiles; on any other the
plain `jax.numpy` form. The choice is made in one place (`_impl`) and
never falls back on a TPU. Every call goes through ONE jitted wrapper,
`_head_argmax`, and the kernel's events in a device trace are named after
it (`_head_argmax.<n>`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# what a kernel gets unasked on v5e (ops/flash_attention.py:
# _VMEM_UNASKED), less room for the row statistics and Mosaic's own
_VMEM_BUDGET = 14 << 20
_TILE_MAX = 1024


def _impl() -> str:
    """"pallas" | "jnp"; a test passes "pallas_interpret" to run the
    kernel off the TPU."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def vocab_tile(rows: int, h: int, itemsize: int = 2) -> int:
    """Columns of `w` a grid step multiplies: the weight tile [h, tn]
    double-buffered, `x` (two buffers as well) and the tile's float32
    product inside `_VMEM_BUDGET`; 1024 at 256 rows of 2048 (8 MB of
    weights in flight, 5.1 us of DMA against 5.4 of products a step)."""
    tn = _TILE_MAX
    while tn > LANES and (2 * itemsize * h * (tn + rows) + 4 * rows * tn
                          > _VMEM_BUDGET):
        tn //= 2
    return tn


def head_argmax(x: jax.Array, w: jax.Array, impl: str = None):
    """x [R, h], w [h, V] -> (argmax int32 [R], the largest logit float32
    [R], the logits' log-sum-exp float32 [R]); see the module docstring."""
    return _head_argmax(x, w, impl=impl or _impl())


@functools.partial(jax.jit, static_argnames=("impl",))
def _head_argmax(x, w, *, impl: str):
    w = w.astype(x.dtype)
    if impl == "jnp":
        logits = jnp.dot(x, w).astype(jnp.float32)
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                jnp.max(logits, axis=-1),
                jax.nn.logsumexp(logits, axis=-1))
    rows = x.shape[0]
    pad = (-rows) % 16          # whole sublane tiles of a 2-byte type
    m, s, at = _by_lane(jnp.pad(x, ((0, pad), (0, 0))), w,
                        impl == "pallas_interpret")
    m, s, at = m[:rows], s[:rows], at[:rows]
    # a lane holds the statistics of the columns that fell on it: fold
    # the 128 of a row
    top = jnp.max(m, axis=-1)
    col = at * LANES + jnp.arange(LANES, dtype=jnp.int32)
    arg = jnp.min(jnp.where(m == top[:, None], col,
                            jnp.iinfo(jnp.int32).max), axis=-1)
    lse = top + jnp.log(jnp.sum(s * jnp.exp(m - top[:, None]), axis=-1))
    return arg, top, lse


def _kernel(x_ref, w_ref, m_ref, s_ref, at_ref, *, v: int, tn: int):
    """One vocabulary tile. m / s / at [R, 128], resident over the grid: a
    lane's running maximum, its sum of exp(logit - m), and the 128-column
    chunk of `w` where the maximum first stood."""
    j = pl.program_id(0)
    shape, chunks = m_ref.shape, tn // LANES

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(shape, -jnp.inf, jnp.float32)
        s_ref[...] = jnp.zeros(shape, jnp.float32)
        at_ref[...] = jnp.zeros(shape, jnp.int32)

    logits = jnp.dot(x_ref[...], w_ref[...],
                     preferred_element_type=jnp.float32)
    # columns left in the vocabulary from this tile's first on: the last
    # tile is partial (151,936 = 128 x 1187, and 1187 is prime)
    left = v - j * tn - jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    tile = [jnp.where(left > c * LANES,
                      logits[:, c * LANES:(c + 1) * LANES], -jnp.inf)
            for c in range(chunks)]
    best = functools.reduce(jnp.maximum, tile)
    first = jnp.zeros(shape, jnp.int32)
    for c in reversed(range(chunks)):          # the lowest chunk wins a tie
        first = jnp.where(tile[c] == best, c, first)
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, best)
    # (finite from the first tile on: every lane has a column, `_by_lane`)
    s_ref[...] = s_ref[...] * jnp.exp(m_old - m_new) + sum(
        jnp.exp(t - m_new) for t in tile)
    # strictly greater: an earlier tile keeps a tie
    at_ref[...] = jnp.where(best > m_old, j * chunks + first, at_ref[...])
    m_ref[...] = m_new


def _by_lane(x, w, interpret: bool):
    rows, h = x.shape
    v = w.shape[1]
    if v < LANES:
        raise ValueError(f"a vocabulary of {v} leaves lanes of the "
                         f"{LANES} without a column")
    tn = min(vocab_tile(rows, h, x.dtype.itemsize),
             -(-v // LANES) * LANES)
    stat = pl.BlockSpec((rows, LANES), lambda j: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, v=v, tn=tn),
        grid=(-(-v // tn),),
        in_specs=[pl.BlockSpec((rows, h), lambda j: (0, 0)),
                  pl.BlockSpec((h, tn), lambda j: (0, j))],
        out_specs=[stat, stat, stat],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, w)

