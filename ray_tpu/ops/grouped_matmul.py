"""Grouped matmul for the dropless expert layer (models/llama.py:MoEMLP).

`lhs` [M, K] holds the routed assignments ordered by expert: the first
`group_sizes[0]` rows belong to expert 0, the next `group_sizes[1]` to
expert 1, ... Row i of the result is `lhs[i] @ rhs[expert of i]`
([E, K, N] weights). `sum(group_sizes)` may be less than M: the rows past
it (padding the engine masked out) belong to no expert and cost no expert
work. What comes back in them is UNDEFINED (the kernel never visits their
tiles, so it is whatever the buffer held; `ragged_dot` writes zeros): the
caller masks them where it folds assignments back into tokens, once, at
[T, h] instead of on every [M, N] here. Their gradient is zero.

On a TPU backend this is the Pallas TPU grouped matmul that jax ships
(`jax.experimental.pallas.ops.tpu.megablox`: `gmm` forward and for the
activations' gradient, `tgmm` for the weights'); on any other backend the
plain `jax.lax.ragged_dot`. The choice is made in one place (`_impl`) and
never falls back on a TPU.

Every call goes through ONE jitted wrapper, `_moe_gmm`, and the kernel's
events in a device trace are named after it (`_moe_gmm.<n>`; the backward's
after `_moe_gmm_bwd`): an instruction takes the name of the innermost jit
around its `pallas_call`, which is why megablox's own jits are unwrapped
here. PERF.md section 3 lists the name among the fragile ones; chipbench's
`moe_gmm_*` metrics match it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# The tile rule's only constants. VMEM_BYTES: what a kernel gets unasked on
# v5e. TILE_M_MIN: the MXU's own rows. BALANCE_ROWS: the rows at which one
# (tile, expert) visit's products take as long as streaming the [K, N]
# weights it multiplies them by (a visit makes 2 * tm operations a 2-byte
# weight element; the chip does 197e12 / 819e9 = 240 a byte,
# chipbench/peaks.json), to the power of two.
VMEM_BYTES = 16 * 2 ** 20
TILE_M_MIN, BALANCE_ROWS = 128, 256


def row_tile(m: int, e: int) -> tuple:
    """(tm, aligned) for a call of `m` assignments on `e` experts: the
    kernel's m-tile, and whether the caller should start every expert's
    rows on a tile boundary (`aligned_rows`), from the shape alone.

    megablox visits every (m-tile, expert) pair that shares a row,
    multiplies the WHOLE [tm, K] x [K, N] tile at each visit whatever part
    of it is that expert's, and streams that expert's [K, N] once a visit.
    (K x N as the weights have them: `_tile` gives `tk` and `tn` that
    divide both, so a visit is counted, here and in `tile_visits`, on
    tiles that multiply no column twice and none the weights lack.)
    On the chip (benchmarks/moe_gmm_probe.py, PERF.md section 6, PR 34) a
    visit costs the longer of the two: up to BALANCE_ROWS what counts is
    the number of visits, past it the rows multiplied (two visits of 256
    rows cost what one of 512 does, and waste less of a tile that is not
    full). With groups packed end to end a call makes m / tm visits and
    one more for every group that starts inside a tile (up to e - 1: 7 of
    8-13 at Mixtral's prefill shapes); with every group on a tile boundary
    it makes ceil(rows / tm) an expert, ONE where the tile holds what the
    expert gets. So: groups on tile boundaries, on BALANCE_ROWS, or on
    TILE_M_MIN (a visit an eighth cheaper) where that holds an expert's
    share m / e of a full call with half again for uneven routing.
    Alignment costs `e` tiles of rows that the elementwise work between
    the two products passes too, so it is asked for only where the share
    is half the smallest tile or more; under that (a decode step, the
    shortest bucket, many small experts on a short call) rows stay packed
    on the smallest tile."""
    share = -(-m // e)
    if 2 * share < TILE_M_MIN:
        return TILE_M_MIN, False
    return (TILE_M_MIN if 3 * share <= 2 * TILE_M_MIN else BALANCE_ROWS), True


def aligned_rows(m: int, e: int, tm: int) -> int:
    """Rows that hold `m` assignments with each of `e` groups padded to
    whole tiles, however they are routed."""
    return -(-m // tm) * tm + e * tm


def tile_vmem_bytes(tile: tuple, itemsize: int = 2) -> int:
    """VMEM the kernel holds at a tile: lhs, rhs and out blocks double
    buffered, and the float32 accumulator."""
    tm, tk, tn = tile
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def tgmm_vmem_bytes(tile: tuple, itemsize: int = 2) -> int:
    """VMEM `tgmm` (the weights' gradient) holds at a tile: the two row
    blocks and the [tk, tn] out block double buffered, and a float32
    accumulator as large as the out block."""
    tm, tk, tn = tile
    return 2 * itemsize * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn


def tile_for(m: int, e: int, k: int, n: int) -> tuple:
    """The (tm, tk, tn) tile of the megablox kernel for `m` assignments on
    `e` experts of [k, n]: a shape in, a tile out. `tm` is `row_tile`'s;
    `tk` and `tn` are `_tile`'s."""
    return _tile(row_tile(m, e)[0], k, n)


def _tile(tm: int, k: int, n: int, vmem=tile_vmem_bytes) -> tuple:
    """`tk` and `tn` for an m-tile over [k, n] weights. The kernel makes
    ceil(k / tk) x ceil(n / tn) grid steps a visit and multiplies the WHOLE
    tile at each (the last k-tile under an iota mask over both blocks): a
    tile that does not divide multiplies columns the weights do not have.
    The rows are read again for every n-tile, which is tm / tn of the
    weights' own traffic: `tn` is 8 * tm beside a `tk` of 1024, halved
    until the double-buffered blocks and the float32 accumulator fit
    VMEM_BYTES (`vmem`: the kernel's count, `tile_vmem_bytes` unless the
    caller's is `tgmm`). That is the tile wherever it divides both widths
    (PR 34 probed it there). Where it does not, and the widths are whole
    MXU edges, the tile is the one that divides them, fits VMEM_BYTES and
    makes the fewest steps a visit; of those, the fewest k-steps (each is
    a pass over the accumulator, 0.8-1.0 us a visit at tm 128, where a
    second n-tile re-reads the rows only if the m-tile changed:
    benchmarks/moe_gmm_probe.py, PERF.md section 6, PR 48)."""
    tk, tn = min(1024, k), min(8 * tm, n)
    while vmem((tm, tk, tn)) > VMEM_BYTES and tn > 1024:
        tn //= 2
    edge = TILE_M_MIN
    if (k % tk == 0 and n % tn == 0) or k % edge or n % edge:
        return tm, tk, tn

    def divisors(width):
        return [t for t in range(edge, width + 1, edge) if width % t == 0]

    fits = [(a, b) for a in divisors(k) for b in divisors(n)
            if vmem((tm, a, b)) <= VMEM_BYTES]
    return (tm, *min(fits, key=lambda t: ((k // t[0]) * (n // t[1]), -t[0])))


def tile_fit(k: int, n: int, tile: tuple) -> float:
    """Real K x N over the K x N a visit multiplies at `tile`: 1.0 where
    `tk` and `tn` divide the weights."""
    _, tk, tn = tile
    return k * n / ((-(-k // tk) * tk) * (-(-n // tn) * tn))


def tile_visits(group_sizes, tm: int) -> int:
    """How many (m-tile, group) pairs the kernel multiplies for these group
    sizes (consecutive runs of rows from row 0) at m-tile `tm`: for each
    non-empty group, the tiles its rows span. Rows multiplied are this
    times `tm`. Plain numpy, on the host: the probe, the tests and the
    engine's `moe_tile_rows_total` share it. `group_sizes` may carry leading
    axes ([steps, L, E]): each row of groups is a call of its own."""
    sizes = np.asarray(group_sizes, np.int64)
    ends = np.cumsum(sizes, axis=-1)
    starts = ends - sizes
    spans = -(-ends // tm) - starts // tm
    return int(np.where(sizes > 0, spans, 0).sum())


def _impl() -> str:
    """"megablox" | "ragged_dot"; a test patches in "megablox_interpret",
    the kernel in interpret mode, to run it off the TPU."""
    return "megablox" if jax.default_backend() == "tpu" else "ragged_dot"


def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   group_sizes: jax.Array, layer: jax.Array = None,
                   tm: int = None) -> jax.Array:
    """lhs [M, K] x rhs [E, K, N] -> [M, N] in lhs's type; see the module
    docstring. With `layer` (a traced index), rhs is a whole [L, E, K, N]
    stack and the groups are layer `layer`'s experts: the stack is read in
    place, as L*E groups of which all but E are empty, instead of being
    sliced (a slice handed to a kernel is a copy). `tm`: the kernel's
    m-tile, for a caller that laid the groups out by `row_tile`; otherwise
    `row_tile`'s for M rows (any tile gives the same result)."""
    if tm is None:
        tm = row_tile(lhs.shape[0], group_sizes.shape[0])[0]
    rhs, group_sizes = stacked_groups(rhs, group_sizes, layer)
    return _moe_gmm(lhs, rhs, group_sizes, tm=tm, impl=_impl())


def stacked_groups(rhs, group_sizes, layer):
    """(rhs [G, K, N], group_sizes [G] int32) as the kernel takes them: a
    whole [L, E, K, N] stack becomes L * E groups, layer `layer`'s E sizes
    in their place among zeros."""
    group_sizes = group_sizes.astype(jnp.int32)
    if layer is None:
        return rhs, group_sizes
    n_layers, e = rhs.shape[:2]
    return (rhs.reshape((n_layers * e,) + rhs.shape[2:]),
            jax.lax.dynamic_update_slice(
                jnp.zeros((n_layers * e,), jnp.int32), group_sizes,
                (layer * e,)))


@functools.partial(jax.jit, static_argnames=("tm", "impl"))
def _moe_gmm(lhs, rhs, group_sizes, *, tm: int, impl: str):
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    m = lhs.shape[0]
    pad = (-m) % tm   # the kernel wants whole m-tiles
    return _megablox(jnp.pad(lhs, ((0, pad), (0, 0))), rhs, group_sizes,
                     _tile(tm, *rhs.shape[1:]),
                     impl == "megablox_interpret")[:m]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _megablox(lhs, rhs, group_sizes, tile, interpret):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    return gmm.__wrapped__(lhs, rhs, group_sizes, lhs.dtype, tile,
                           interpret=interpret)


def _megablox_fwd(lhs, rhs, group_sizes, tile, interpret):
    return (_megablox(lhs, rhs, group_sizes, tile, interpret),
            (lhs, rhs, group_sizes))


def _megablox_bwd(tile, interpret, res, grad):
    return (*_moe_gmm_bwd(*res, grad, tm=tile[0], interpret=interpret),
            None)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _moe_gmm_bwd(lhs, rhs, group_sizes, grad, *, tm, interpret):
    """d lhs = grad x rhs^T by group; d rhs[e] = lhs[group e]^T x grad.
    Each product at the tile of ITS widths: d lhs contracts over N, and
    `tgmm` holds a whole [tk, tn] float32 accumulator beside its out
    block, so the forward's tile is neither's."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    k, n = rhs.shape[1:]
    d_lhs = gmm.__wrapped__(grad, rhs, group_sizes, lhs.dtype,
                            _tile(tm, n, k), transpose_rhs=True,
                            interpret=interpret)
    # rows in no group: the kernel left their gradient unwritten too
    d_lhs = jnp.where(
        (jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes))[:, None], d_lhs, 0)
    d_rhs = tgmm.__wrapped__(lhs.swapaxes(0, 1), grad, group_sizes,
                             rhs.dtype, _tile(tm, k, n, tgmm_vmem_bytes),
                             num_actual_groups=rhs.shape[0],
                             interpret=interpret)
    return d_lhs, d_rhs


_megablox.defvjp(_megablox_fwd, _megablox_bwd)
