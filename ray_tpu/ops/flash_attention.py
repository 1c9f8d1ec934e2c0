"""Pallas TPU flash attention, forward + backward.

The reference framework ships no attention kernels at all — it delegates to
external engines (torch SDPA / vLLM; see SURVEY.md §2.4 "sequence parallel:
ABSENT").  Here the hot op is owned natively: a blocked online-softmax
(FlashAttention-2 style) kernel laid out for the TPU MXU/VMEM:

- blocked tiling on both query and key axes (512 default, 128 minimum),
- K/V for one (batch, kv-head) kept resident in VMEM; the inner k-loop is a
  `fori_loop` of MXU matmuls with f32 accumulation. That residency bounds
  the kv length the kernel takes. Compile limits on v5e (from a compile
  for a described v5e chip, jax 0.9.0 — not from a run; guarded by
  tests/test_chip_compile.py): forward and backward compile up to 16384
  kv rows, at head_dim 64 and 128 alike, and the forward is refused at
  32768 (`RESOURCE_EXHAUSTED ... vmem`). The backward holds Q, dO, dq,
  K, V, dk, dv of one query head and float32 accumulators of all three
  gradients, 10 MB at 2048 rows and 84 MB at 16384, and asks for that
  VMEM from its shapes (the chip has 128 MiB).
  Longer sequences need K/V tiled over the grid (ROADMAP A6),
- GQA handled in the BlockSpec index map (q-head h reads kv-head h // n_rep),
  so no materialised `repeat_kv`,
- operands where the projections left them: the caller's `[B, S, H, D]` is
  `[B, S, H x D]` by a reshape, and where a head is whole lane tiles (D and
  Dv multiples of 128: `_heads_on_lanes`) a head is a LANE BLOCK of that
  row: the forward's q and `o` blocks are `(1, block_q, g x D)` at lane
  block h, K and V `(1, Sk, D)` at lane block h // n_rep, the backward's q,
  do, dq `(1, Sq, D)` and k, v, dk, dv `(1, Sk, D)` likewise. Nothing is
  transposed around a call, `o` is kept once (`SAVED_OUTPUTS`) in the
  layout both its readers take (the o projection; `delta`, a product with
  the heads' lanes), and only `lse` and `delta`, one float32 a (query,
  head), are heads-first. K and V of a head are then strided rows that XLA
  cannot hold in VMEM whole, so the forward asks for its VMEM from 12,288
  rows on. Any other width (a latent family's keys of 192, a tiny
  configuration's heads of 16) keeps `[B, H, S, D]` operands, the
  transposes that make them and the text it lowered to,
- causal masking is relative to the *end* of the kv sequence (tril with
  offset sk - sq), which makes the same kernel correct for training
  (sq == sk), chunked prefill and multi-token decode (sq < sk),
- packed-sequence masking via (q_segment_ids, kv_segment_ids),
- both kernels hold a score tile TRANSPOSED, `[block_k, block_q]`: what
  there is one of a query (the running maximum, the sum, `lse`, `delta`) is
  a lane row that broadcasts along sublanes, the reductions over keys run
  down the sublanes, and `lse` leaves the forward in the layout the
  backward reads it in. A query block is the tile's lanes, so where it is
  under 512 rows (a short prompt's bucket) a grid step of the forward
  takes as many query heads of the kv head's group as fill them, side by
  side along the lanes (`_fold`), and reads the group's K and V once,
- the forward's key loop knows a tile's class before it computes it
  (`_fwd_trips`): INTERIOR tiles, where every real query row sees every
  key, run with no mask at all; only EDGE tiles build one: those the
  causal (or block-causal) diagonal crosses, the one that holds the end of
  the keys inside it, every tile under segment ids. The softmax scale
  rides in the exponent's argument (`exp2((s - m) * scale * log2(e))`, the
  maximum taken on raw scores), never in the bf16 operands,
- on the forward-only path (`return_lse=True`: paged prefill) each batch
  row's TRUE lengths as data (`q_lens`, `kv_lens`, scalar-prefetched): a
  query block past a row's queries runs no loop and the key loop ends with
  the row's keys, so a bucket's padding and a block table's unused width
  cost nothing; the shapes, and so the programs, stay the buckets',
- on the same path a static sliding `window` (a model whose layers mix
  windowed and full attention, models/mellum.py): a query sees the W keys up
  to its own place; a query block's key loop STARTS at the first tile the
  band touches (`_band_trips`: tiles wholly behind the band are not
  visited, not masked) and the tiles the band's lower edge crosses are edge
  tiles as the diagonal's are. The backward has no band and refuses one,
- backward pass as ONE Pallas kernel using the saved log-sum-exp: a grid
  step is one query head against its kv head's K and V, and visits every
  (key block, query block) tile once, computing s, p, dp and ds there
  once for all of dv, dk and dq. dk and dv accumulate in float32 VMEM
  over the kv head's query group and are written once, in the operands'
  dtype: no per-query-head partials, no sum after the kernel. Tiles below
  the causal diagonal (no segment ids, no key padding) run without a mask.

Interpret mode runs the same kernels on the CPU for tests
(tests/test_flash_attention.py checks parity with `reference_attention`
for values and grads). It is chosen by the caller (`interpret=True`) or,
when `interpret` is left None, by the process running on a non-TPU backend
(`JAX_PLATFORMS=cpu`); on a TPU backend None always means the compiled
kernel.

A Mosaic kernel is a one-device program: GSPMD cannot partition it, so
under a mesh with more than one device the call must sit inside a
`shard_map`. `flash_attention_sharded` is that wrapper (batch over
dp/fsdp, heads over tp); `ops.attention.attention` picks it whenever a
multi-device mesh is active.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# default tile edge. On v5e at [4, 2048, 32/8, 128] (the pretrain cell's
# step), of 256, 512 and 1024 a side: the fastest backward (2.99 ms a call;
# 1024 x 1024 3.24, 512 x 256 3.24, 256 x 256 4.29; PR 44) and, swept again
# for the forward's transposed body (PR 45, query x key edge, ms a causal
# call there / at [1, 4096, 32/8, 128] / at [1, 4096, 64/64, 192/128]): 512
# x 512 1.77 / 1.46 / 4.07, 1024 x 512 1.80-1.87 / 1.41-1.45 / 4.14-4.16,
# 512 x 1024 1.98 / 1.56 / 4.36, 512 x 256 2.10 / 1.76 / 4.54, 256 x 512
# 3.03 / 2.51 / 4.94 (a query block is the score tile's lanes: under 512
# it starves, which is why a bucket's shorter block folds its kv head's
# query heads along them, `_fold`). A call with no diagonal would take a query block of 1024
# (a 4096-key context: 2.41 -> 2.13 ms at D 128, 6.33 -> 5.99 at 192/128);
# not done: `prefill_block_visits` and `PassCost` count in these blocks
# (ROADMAP A6). benchmarks/flash_bwd_probe.py --blocks / --fwd-shapes, a
# process a pair. Not swept on other chips.
BLOCK = 512
GRAN = 128   # MXU-minimal granularity: short sequences round up to this,
             # not to BLOCK, so small prefills don't pad 4-8x


# scoped VMEM a kernel gets without asking (v5e), and what the forward's
# q / o / lse blocks and its [bk, bq] score tiles take beside K and V
_VMEM_UNASKED = 16 << 20
_VMEM_HEADROOM = 8 << 20
# what they take where there are no segment ids (2.6 MB at blocks of 512)
_VMEM_BESIDE = 4 << 20


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _heads_on_lanes(d: int, dv: int) -> bool:
    """Whether the kernels take `[B, S, H, D]` operands as the projections
    leave them: a head is then a lane block of a `[B, S, H x D]` row, which
    it can be only where the keys' and the values' widths are whole lane
    tiles. Any other width (the latent families' keys of 192, a tiny
    configuration's heads of 16) keeps `[B, H, S, D]` operands, and the
    transposes around the call that make them."""
    return d % 128 == 0 and dv % 128 == 0


def _dims(q, k, on_lanes: bool):
    """(B, Hq, Hkv, Sq_p, Sk_p) of the kernels' operands in either form:
    `[B, S, H, D]` where a head is a lane block, `[B, H, S, D]` where not."""
    (b, s1, s2, _), (_, k1, k2, _) = q.shape, k.shape
    return (b, s2, k2, s1, k1) if on_lanes else (b, s1, k1, s2, k2)


def _pick_blocks(sq: int, sk: int, block_q: int, block_k: int):
    bq = min(block_q, _round_up(sq, GRAN))
    bk = min(block_k, _round_up(sk, GRAN))
    return bq, bk


def _dummy_arg():
    """Placeholder operand for the unused segment-id refs (the kernels
    never read it when have_segs=False); (1, 1) scalar keeps SMEM happy."""
    return jnp.zeros((1, 1), jnp.int32)


def _dummy_spec():
    return pl.BlockSpec((1, 1), lambda *_: (0, 0), memory_space=pltpu.SMEM)


# =============================================================== forward
_LOG2E = math.log2(math.e)


def _fold(n_rep: int, bq: int) -> int:
    """Query heads of one kv head that a grid step takes together: a score
    tile's lanes are the query block, and a block under `BLOCK` rows (a
    short prompt's bucket) would leave them part empty, so the step holds
    as many heads of the kv head's group as fill them, side by side along
    the lanes, and reads the group's K and V once for them."""
    return max(g for g in range(1, n_rep + 1)
               if n_rep % g == 0 and g * bq <= max(BLOCK, bq))


def _fwd_trips(qblk, *, bq: int, block_k: int, sq: int, sk: int,
               causal: bool, have_segs: bool, q_len, kv_len,
               block_causal: int, xp=jnp):
    """(interior, all): the key blocks `[0, interior)` of query block `qblk`
    are INTERIOR tiles, where every real query row sees every key, and
    `[interior, all)` are EDGE tiles: those the causal (or block-causal)
    diagonal crosses, the one that holds the end of the keys (`sk`, or the
    row's `kv_len`) inside it, and every tile where there are segment ids.
    `xp`: `jnp` in the kernel; `numpy` on the host, where `qblk` may be all
    of a call's query blocks at once (`prefill_block_visits`)."""
    offset = sk - sq
    num_kb, interior = -(-sk // block_k), sk // block_k
    if kv_len is not None:
        num_kb = xp.minimum(num_kb, (kv_len + block_k - 1) // block_k)
        interior = xp.minimum(interior, kv_len // block_k)
    if causal:
        # the first row of the query block sees the fewest keys, the last
        # the most: `first_sees` keys and `(qblk + 1) * bq + offset`
        first_sees = qblk * bq + offset + max(block_causal, 1)
        num_kb = xp.minimum(
            num_kb, ((qblk + 1) * bq + offset + block_k - 1) // block_k)
        interior = xp.minimum(interior, first_sees // block_k)
    if have_segs:
        interior = 0
    if q_len is not None:
        # real blocks only: a query block of padding runs neither loop,
        # which leaves o = 0 and lse = NEG_INF, what a fully masked row
        # gives (merge_attention then returns the other part)
        num_kb = xp.where(qblk * bq < q_len, num_kb, 0)
    return xp.minimum(interior, num_kb), num_kb


def _band_offset(causal: bool, sq: int, sk: int, kv_len):
    """Where query 0 of a windowed call stands among its keys: a causal
    call's queries are the LAST `sq` of `sk` positions (its diagonal's
    offset); a call that is not causal is the context part of a resumed
    pass, and its queries FOLLOW the row's keys (`kv_len`, or all `sk`)."""
    if causal:
        return sk - sq
    return sk if kv_len is None else kv_len


def _band_trips(qblk, *, bq: int, block_k: int, window: int, woff, xp=jnp):
    """(first, below): under a window (query i sees key j iff j > i + woff -
    window) query block `qblk` visits no key block before `first`, those
    wholly behind the band of its first row, and `[first, below)` are EDGE
    tiles: each holds a key that some row of the block does not see (the
    band's lower edge crosses them). From `below` on the band cuts nothing
    and `_fwd_trips` says what a tile is."""
    first_sees = qblk * bq + woff - window + 1       # the first row's
    last_sees = (qblk + 1) * bq + woff - window      # the last row's
    return (xp.maximum(first_sees, 0) // block_k,
            (xp.maximum(last_sees, 0) + block_k - 1) // block_k)


def _fwd_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, o_ref, lse_ref, *,
                sm_scale: float, causal: bool, block_k: int,
                sq: int, sk: int, have_segs: bool, q_len=None, kv_len=None,
                block_causal: int = 0, window: Optional[int] = None):
    """One grid step: one query block of `g` query heads (`_fold`: 1 at a
    whole block) against their kv head's K and V, the heads side by side
    along the lanes. `q_len` / `kv_len`: this batch row's true lengths
    (traced scalars, `_fwd_kernel_lens`), or None for the shapes' own.
    `block_causal` = B > 0: the causal relation is between blocks of B
    positions and a block sees itself whole (`ops.attention.causal_mask`);
    B divides the query block and `sk - sq`, so the key blocks a query
    block walks are causal's. `window` = W: a query also sees only the W
    keys up to its own place, `k_pos > q_pos + woff - W` (`_band_offset`);
    the key loop starts at the first tile the band touches and the tiles
    its lower edge crosses are edge tiles too (`_band_trips`).

    Scores are held transposed, `[block_k, g * block_q]`, as `_bwd_kernel`
    holds them: the running maximum `m`, the sum `l` and `lse` are lane
    rows `[1, g * block_q]` that broadcast along sublanes, both reductions
    run down the sublanes, and the accumulator is `[Dv, g * block_q]`,
    transposed once where `o` is written. The key loop is two loops over one tile body
    (`_fwd_trips`): interior tiles run with no mask at all, edge tiles build
    theirs. `m` is of RAW scores (`sm_scale` > 0) and the scale rides in the
    exponent's float32 argument, `exp2((s - m) * c)` with `c = sm_scale *
    log2(e)`: one multiply a tile element where `exp` of scaled scores
    paid two."""
    qblk = pl.program_id(2)
    # (a block's leading ones: [1, 1, S, D] of `[B, H, S, D]` operands, [1,
    # S, D] where a head is a lane block of `[B, S, H x D]`)
    lead = (0,) * (k_ref.ndim - 2)
    if q_ref.ndim == 4:
        _, g, bq, d = q_ref.shape
        lanes = g * bq
        qt = q_ref[0].reshape(lanes, d).T  # [d, lanes]
    else:
        # the g heads are g * d contiguous lanes of a row: each a static
        # lane slice, stacked on sublanes in VMEM
        bq, d = q_ref.shape[1], k_ref.shape[2]
        g = q_ref.shape[2] // d
        lanes = g * bq
        qt = jnp.concatenate(
            [q_ref[0, :, r * d:(r + 1) * d] for r in range(g)], axis=0).T
    offset = sk - sq
    c = sm_scale * _LOG2E

    def per_head(row):
        """A `[1, bq]` lane row of the query block, once a folded head."""
        return jnp.concatenate([row] * g, axis=1) if g > 1 else row

    m0 = jnp.full((1, lanes), NEG_INF, jnp.float32)
    l0 = jnp.zeros((1, lanes), jnp.float32)
    # (the values' width: the keys' for every family but one whose values
    # are narrower than its keys, models/kimi.py)
    acc0 = jnp.zeros((v_ref.shape[-1], lanes), jnp.float32)
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
    q_pos = per_head(
        qblk * bq + jax.lax.broadcasted_iota(jnp.int32, (1, bq), 1))
    if have_segs:
        q_seg = per_head(qseg_ref[0, 0])

    def tile(kb, carry, masked):
        m, l, acc = carry
        ks = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        k = k_ref[(*lead, ks, slice(None))]
        v = v_ref[(*lead, ks, slice(None))]
        s = jax.lax.dot_general(
            k, qt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bk, lanes], raw
        if masked:
            k_pos = kb * block_k + k_iota
            mask = k_pos < sk  # kv padding
            if kv_len is not None:
                mask = jnp.logical_and(mask, k_pos < kv_len)
            if block_causal:
                # a query row sees to the end of its block
                mask = jnp.logical_and(mask, k_pos < (
                    (q_pos + offset) // block_causal + 1) * block_causal)
            elif causal:
                mask = jnp.logical_and(mask, k_pos <= q_pos + offset)
            if window is not None:
                mask = jnp.logical_and(mask, k_pos > q_pos + (
                    _band_offset(causal, sq, sk, kv_len) - window))
            if have_segs:
                mask = jnp.logical_and(
                    mask, kseg_ref[0, ks, :] == q_seg)
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp2((s - m_new) * c)
        if masked and (have_segs or window is not None):
            # a query whose every key so far is another segment's has m ==
            # NEG_INF, and its p reads exp2(0). Without segment ids key 0
            # is visible to every query of the first tile, so m is a real
            # score from there on and a masked p underflows to 0 by itself
            # (under a window the first tile a block visits is its first
            # row's: a later row may see none of it)
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp2((m - m_new) * c)
        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        pv = jax.lax.dot_general(
            v.T, p.astype(v.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [dv, lanes]
        return m_new, l, acc * alpha + pv

    interior, num_kb = _fwd_trips(
        qblk, bq=bq, block_k=block_k, sq=sq, sk=sk, causal=causal,
        have_segs=have_segs, q_len=q_len, kv_len=kv_len,
        block_causal=block_causal)
    carry, first = (m0, l0, acc0), 0
    if window is not None:
        # the band's lower edge: [first, below) masked, then as without it
        first, below = _band_trips(
            qblk, bq=bq, block_k=block_k, window=window,
            woff=_band_offset(causal, sq, sk, kv_len))
        below = jnp.clip(below, first, num_kb)
        carry = jax.lax.fori_loop(
            first, below, functools.partial(tile, masked=True), carry)
        first, interior = below, jnp.clip(interior, below, num_kb)
    carry = jax.lax.fori_loop(
        first, interior, functools.partial(tile, masked=False), carry)
    m, l, acc = jax.lax.fori_loop(
        interior, num_kb, functools.partial(tile, masked=True), carry)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l_safe).T  # [lanes, dv]
    if o_ref.ndim == 4:
        o_ref[0] = o.reshape(g, bq, -1).astype(o_ref.dtype)
    else:
        dv = o.shape[1]
        for r in range(g):
            o_ref[0, :, r * dv:(r + 1) * dv] = o[r * bq:(r + 1) * bq].astype(
                o_ref.dtype)
    # (a query that saw no key: NEG_INF itself, not NEG_INF * sm_scale)
    lse = jnp.where(l == 0.0, NEG_INF, m * sm_scale + jnp.log(l_safe))
    for r in range(g):
        lse_ref[0, r, 0] = lse[:, r * bq:(r + 1) * bq]  # [1, bq]


def _fwd_kernel_lens(lens_ref, *refs, **static):
    """`_fwd_kernel` with the lengths of its batch row, from the
    scalar-prefetched `lens_ref` ([2, B] int32: queries, keys)."""
    b = pl.program_id(0)
    _fwd_kernel(*refs, q_len=lens_ref[0, b], kv_len=lens_ref[1, b], **static)


def _lane_rows(x, block_q: int):
    """A per-query vector `[..., Sq_p]` as lane rows, one a query block:
    `[..., Sq_p / block_q, 1, block_q]`, the form both kernels hold `lse`
    (and the query segment ids) in."""
    return x.reshape(*x.shape[:-1], x.shape[-1] // block_q, 1, block_q)


def _fwd(q, k, v, q_seg, kv_seg, causal, sm_scale, block_q, block_k,
         interpret, sq, sk, lens=None, block_causal=0, window=None):
    """q: [B,Hq,Sq_p,D]; k: [B,Hkv,Sk_p,D]; v: [B,Hkv,Sk_p,Dv] (padded to
    block multiples; Dv is D but for the forward-only path of a model whose
    values are narrower than its keys); or, where a head is whole lane
    tiles (`_heads_on_lanes`), [B,Sq_p,Hq,D] / [B,Sk_p,Hkv,D|Dv], read as
    rows of [B, S, H x D]; q_seg [B,Sq_p] and kv_seg [B,Sk_p,1] int32, or
    None.

    sq/sk are the TRUE lengths of the operands: the kernels mask kv padding
    with `k_pos < sk` and compute the causal offset from them. `lens`
    ([2, B] int32, or None) is each batch row's own (queries, keys) within
    them, as data. Returns o [B,Hq,Sq_p,Dv] (rows [B,Sq_p,Hq x Dv] where
    the operands were) and lse as lane rows
    [B,Hq,Sq_p/block_q,1,block_q] (`_lane_rows`: the layout the kernel
    writes and `_bwd` reads, so the trainer's step relays nothing out).
    """
    d, dv = q.shape[3], v.shape[3]
    on_lanes = _heads_on_lanes(d, dv)
    b, hq, hkv, sq_p, sk_p = _dims(q, k, on_lanes)
    n_rep = hq // hkv
    bq, bk = block_q, block_k
    nqb = sq_p // bq
    have_segs = q_seg is not None
    # a grid step: g query heads of one kv head (`_fold`), in blocks of g
    g = _fold(n_rep, bq)
    grid = (b, hq // g, nqb)

    kernel = functools.partial(
        _fwd_kernel if lens is None else _fwd_kernel_lens,
        sm_scale=sm_scale, causal=causal, block_k=bk,
        sq=sq, sk=sk, have_segs=have_segs, block_causal=block_causal,
        **({} if window is None else {"window": window}))

    # (`*_`: the scalar-prefetched lengths, where there are any)
    if on_lanes:
        # a head is a lane block of [B, S, H x D] (a reshape, free): the g
        # folded heads of a step are g * d contiguous lanes of a row
        def heads(width):
            return pl.BlockSpec((1, bq, g * width),
                                lambda b_, h, i, *_: (b_, i, h))

        def kv_heads(width):
            return pl.BlockSpec(
                (1, sk_p, width), lambda b_, h, i, *_: (b_, 0, h * g // n_rep))

        q, k, v = (x.reshape(*x.shape[:2], -1) for x in (q, k, v))
        o_shape = (b, sq_p, hq * dv)
    else:
        def heads(width):
            return pl.BlockSpec((1, g, bq, width),
                                lambda b_, h, i, *_: (b_, h, i, 0))

        def kv_heads(width):
            return pl.BlockSpec(
                (1, 1, sk_p, width),
                lambda b_, h, i, *_: (b_, h * g // n_rep, 0, 0))

        o_shape = (b, hq, sq_p, dv)
    in_specs = [heads(d), kv_heads(d), kv_heads(dv)]
    args = [q, k, v]
    if have_segs:
        in_specs += [
            pl.BlockSpec((1, 1, 1, bq), lambda b_, h, i, *_: (b_, i, 0, 0)),
            pl.BlockSpec((1, sk_p, 1), lambda b_, h, i, *_: (b_, 0, 0)),
        ]
        args += [_lane_rows(q_seg, bq), kv_seg]
    else:
        in_specs += [_dummy_spec()] * 2
        args += [_dummy_arg(), _dummy_arg()]

    out_shape = [
        jax.ShapeDtypeStruct(o_shape, q.dtype),
        jax.ShapeDtypeStruct((b, hq, nqb, 1, bq), jnp.float32),
    ]
    out_specs = [
        heads(dv),
        pl.BlockSpec((1, g, 1, 1, bq),
                     lambda b_, h, i, *_: (b_, h, i, 0, 0)),
    ]
    compiler_params = dict(
        dimension_semantics=("parallel", "parallel", "parallel"))
    if have_segs or on_lanes:
        # K, V and the kv segment ids of one (batch, kv head) stay
        # resident, double-buffered, and an int32 [sk, 1] column is padded
        # to 128 lanes: at kv 8k and D 128 that is 18 MB, over the 16 MB a
        # kernel gets unasked. Such a call compiles only while XLA chooses
        # to hold the ids in VMEM itself (the engine's [4 x 4096] prefix
        # prefill did, until the program around it changed), so ask.
        # Without ids K and V alone pass it from 12,288 rows on (the
        # engine's calls hold no more: `FLASH_RESIDENT_KV_BYTES`; the
        # trainer's may). A lane block of a row is no operand XLA can hold
        # in VMEM whole, as it did a head of `[B, H, S, D]`.
        resident = 2 * sk_p * ((d + dv) * k.dtype.itemsize
                               + (128 * 4 if have_segs else 0))
        beside = _VMEM_HEADROOM if have_segs else _VMEM_BESIDE
        if resident + beside > _VMEM_UNASKED:
            compiler_params["vmem_limit_bytes"] = resident + _VMEM_HEADROOM
    if lens is None:
        grid_spec = dict(grid=grid, in_specs=in_specs, out_specs=out_specs)
    else:
        grid_spec = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs))
        args = [lens] + args
    o, lse = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(**compiler_params),
        interpret=interpret,
        **grid_spec,
    )(*args)
    return o, lse


# =============================================================== backward
def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
                kseg_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                sq: int, sk: int, have_segs: bool):
    """One grid step: one query head against its (batch, kv head)'s K and V.
    Every (key block, query block) tile is visited once: s, p, dp and ds are
    computed once and feed dv, dk and dq. Scores are held transposed,
    `[block_k, block_q]`, so that `lse` and `delta` broadcast as lane rows
    and dv / dk are plain products; dq accumulates transposed, `[d,
    block_q]` a query block, for the same reason. dk and dv accumulate in
    float32 scratch across the kv head's query group (grid dimension 2,
    over which their output block stays), dq across the key blocks of this
    step; each is written once, in the operands' dtype."""
    rep = pl.program_id(2)
    bq, bk = block_q, block_k
    lead = (0,) * (q_ref.ndim - 2)   # as in `_fwd_kernel`
    nqb, nkb = q_ref.shape[-2] // bq, k_ref.shape[-2] // bk
    offset = sk - sq
    # segment ids or padded keys: every tile is masked. Else only the
    # tiles the causal diagonal crosses are
    mask_all = have_segs or sk % bk != 0

    @pl.when(rep == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    dq_acc[...] = jnp.zeros_like(dq_acc)
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
    q_iota = jax.lax.broadcasted_iota(jnp.int32, (1, bq), 1)

    def key_block(kb, _):
        ks = pl.ds(pl.multiple_of(kb * bk, bk), bk)
        k = k_ref[(*lead, ks, slice(None))]
        v = v_ref[(*lead, ks, slice(None))]
        kt = k.T  # [d, bk]

        def tile(qb, _, masked):
            qs = pl.ds(pl.multiple_of(qb * bq, bq), bq)
            q = q_ref[(*lead, qs, slice(None))]
            do = do_ref[(*lead, qs, slice(None))]
            st = jax.lax.dot_general(
                k, q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [bk, bq]
            p = jnp.exp(st - lse_ref[0, 0, qb])
            if masked:
                k_pos = kb * bk + k_iota
                mask = k_pos < sk  # kv padding
                if causal:
                    mask = jnp.logical_and(
                        mask, k_pos <= qb * bq + q_iota + offset)
                if have_segs:
                    mask = jnp.logical_and(
                        mask, kseg_ref[0, ks, :] == qseg_ref[0, qb])
                # (a select, so a masked score's overflow does not matter)
                p = jnp.where(mask, p, 0.0)
            dv_acc[ks, :] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bk, d]
            dp = jax.lax.dot_general(
                v, do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bk, bq]
            # (scaled before it is rounded, as `_fwd_kernel`'s scores are:
            # scaling the float32 sums where dq and dk are written instead
            # read 0.0030 from the float32 reference where this reads
            # 0.0021, on the chip, PERF.md section 6 PR 44)
            ds = (p * (dp - delta_ref[0, 0, qb]) * sm_scale).astype(q.dtype)
            dk_acc[ks, :] += jax.lax.dot_general(
                ds, q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bk, d]
            dq_acc[qb] += jax.lax.dot_general(
                kt, ds, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [d, bq]

        # query blocks [first, whole) see a part of this key block, those
        # from `whole` on all of it; none before `first` sees any
        first, whole = 0, (nqb if mask_all else 0)
        if causal:
            first = jnp.maximum((kb * bk - offset) // bq, 0)
            if not mask_all:
                whole = jnp.clip(
                    ((kb + 1) * bk - 1 - offset + bq - 1) // bq, first, nqb)
        if mask_all or causal:
            jax.lax.fori_loop(
                first, whole, functools.partial(tile, masked=True), None)
        if not mask_all:
            jax.lax.fori_loop(
                whole, nqb, functools.partial(tile, masked=False), None)

    jax.lax.fori_loop(0, nkb, key_block, None)

    for qb in range(nqb):
        dq_ref[(*lead, slice(qb * bq, (qb + 1) * bq), slice(None))] = (
            dq_acc[qb].T.astype(dq_ref.dtype))

    @pl.when(rep == pl.num_programs(2) - 1)
    def _():
        dk_ref[lead] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[lead] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(q, k, v, q_seg, kv_seg, o, lse, do, causal, sm_scale,
         block_q, block_k, interpret, sq, sk):
    d = q.shape[3]
    on_lanes = _heads_on_lanes(d, d)
    b, hq, hkv, sq_p, sk_p = _dims(q, k, on_lanes)
    n_rep = hq // hkv
    bq, bk = block_q, block_k
    nqb = sq_p // bq
    have_segs = q_seg is not None

    # grid (batch, kv head, query head of its group): K, V and the dk / dv
    # blocks keep their index over the last dimension
    if on_lanes:
        shapes = [q.shape, k.shape, v.shape]
        q, k, v, do = (x.reshape(*x.shape[:2], -1) for x in (q, k, v, do))
        # `o` is read as the o projection reads it, rows of [B, S, H x D]:
        # a head's sum is the row's product with that head's lanes set
        # (float32 passes of the MXU), and only delta, one float32 a
        # (query, head), changes its layout
        head_lanes = (jnp.arange(hq * d)[:, None] // d
                      == jnp.arange(hq)[None]).astype(jnp.float32)
        delta = jnp.einsum(
            "bsl,lh->bhs", do.astype(jnp.float32) * o.astype(jnp.float32),
            head_lanes, precision=jax.lax.Precision.HIGHEST)
        q_spec = pl.BlockSpec((1, sq_p, d),
                              lambda b_, g, r: (b_, 0, g * n_rep + r))
        kv_spec = pl.BlockSpec((1, sk_p, d), lambda b_, g, r: (b_, 0, g))
    else:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)
        q_spec = pl.BlockSpec((1, 1, sq_p, d),
                              lambda b_, g, r: (b_, g * n_rep + r, 0, 0))
        kv_spec = pl.BlockSpec((1, 1, sk_p, d),
                               lambda b_, g, r: (b_, g, 0, 0))
    row_spec = pl.BlockSpec((1, 1, nqb, 1, bq),
                            lambda b_, g, r: (b_, g * n_rep + r, 0, 0, 0))
    if have_segs:
        seg_specs = [
            pl.BlockSpec((1, nqb, 1, bq), lambda b_, g, r: (b_, 0, 0, 0)),
            pl.BlockSpec((1, sk_p, 1), lambda b_, g, r: (b_, 0, 0))]
        seg_args = [_lane_rows(q_seg, bq), kv_seg]
    else:
        seg_specs = [_dummy_spec()] * 2
        seg_args = [_dummy_arg(), _dummy_arg()]

    # resident a step: q, do, dq and k, v, dk, dv blocks (double-buffered,
    # lane-padded), the float32 accumulators, the kv segment ids (an int32
    # column, padded to 128 lanes); beside them the [bk, bq] score tiles
    lanes = _round_up(d, 128)
    resident = (2 * (3 * sq_p + 4 * sk_p) * lanes * q.dtype.itemsize
                + (sq_p + 2 * sk_p) * lanes * 4
                + (2 * sk_p * 128 * 4 if have_segs else 0))
    need = resident + 8 * bq * bk * 4 + _VMEM_HEADROOM
    compiler_params = dict(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    if need > _VMEM_UNASKED:
        compiler_params["vmem_limit_bytes"] = need
    grads = pl.pallas_call(
        functools.partial(
            _bwd_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
            block_k=bk, sq=sq, sk=sk, have_segs=have_segs),
        grid=(b, hkv, n_rep),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                  *seg_specs],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((nqb, d, bq), jnp.float32),
            pltpu.VMEM((sk_p, d), jnp.float32),
            pltpu.VMEM((sk_p, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(**compiler_params),
        interpret=interpret,
    )(q, k, v, do, lse, _lane_rows(delta, bq), *seg_args)
    if on_lanes:
        grads = [x.reshape(s) for x, s in zip(grads, shapes)]
    return grads


# ============================================================ custom_vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, q_seg, kv_seg, causal, sm_scale, block_q, block_k,
           interpret, sq, sk):
    o, _ = _fwd(q, k, v, q_seg, kv_seg, causal, sm_scale, block_q, block_k,
                interpret, sq, sk)
    return o


# The forward kernel's outputs, by the names a remat policy can list
# (`jax.checkpoint_policies.save_only_these_names`): a policy that lists
# them runs the forward kernel once a step and not again inside the
# backward (models/llama.py: `_remat_policy`); under one that does not, a
# name is the identity. The names sit on the kernel's own outputs, inside
# the forward rule: naming the caller's `o` would keep `o` and still run
# the kernel again for `lse`.
SAVED_OUTPUTS = ("flash_o", "flash_lse")


def _flash_fwd(q, k, v, q_seg, kv_seg, causal, sm_scale, block_q, block_k,
               interpret, sq, sk):
    o, lse = map(checkpoint_name, _fwd(
        q, k, v, q_seg, kv_seg, causal, sm_scale, block_q, block_k,
        interpret, sq, sk), SAVED_OUTPUTS)
    return o, (q, k, v, q_seg, kv_seg, o, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, sq, sk, res,
               do):
    q, k, v, q_seg, kv_seg, o, lse = res
    dq, dk, dv = _bwd(q, k, v, q_seg, kv_seg, o, lse, do, causal, sm_scale,
                      block_q, block_k, interpret, sq, sk)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


# ================================================================= public
def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True,
    segment_ids: Optional[Union[jax.Array, Tuple[jax.Array, jax.Array]]] = None,
    scale: Optional[float] = None,
    block_q: int = BLOCK, block_k: int = BLOCK,
    interpret: Optional[bool] = None,
    return_lse: bool = False,
    q_lens: Optional[jax.Array] = None,
    kv_lens: Optional[jax.Array] = None,
    block_causal: int = 0,
    window: Optional[int] = None,
):
    """Flash attention. q: [B,Sq,Hq,D]; k/v: [B,Sk,Hkv,D] -> [B,Sq,Hq,D].
    With `return_lse` v may be [B,Sk,Hkv,Dv], Dv != D (latent attention's
    materialised form: keys of 192, values of 128) -> [B,Sq,Hq,Dv]; the
    backward kernel takes one width.

    segment_ids: one [B,S] array (requires Sq == Sk), or a
    (q_segment_ids [B,Sq], kv_segment_ids [B,Sk]) pair for cached decode /
    chunked prefill of packed sequences.

    return_lse: also return the log-sum-exp [B,Sq,Hq] (fp32) — the hook
    for merging attention partials over disjoint kv sets (paged prefill
    with a cached prefix, ops/paged_attention.py). The lse path is
    forward-only (no custom VJP through the merge).

    q_lens / kv_lens (int32 [B], traced values; with `return_lse` only):
    how many of a batch row's queries, counted from the first, and of its
    keys are real; one left None is the operand's whole length. Keys at or
    past a row's `kv_lens` are masked for it, and the kernel's loops run
    over real blocks only. A real query row's `o` and
    `lse` are what the same mask as segment ids gives; a query row at or
    past `q_lens` is padding and its `o` / `lse` mean nothing: 0 /
    NEG_INF where its whole block is padding; in a block that holds real
    rows too it gets no mask of its own and reads what a real row at its
    position would (finite: attention over the row's keys up to its own
    position, or all of them where not causal). The causal relation stays
    the operands' (`k_pos <= q_pos + Sk - Sq`): lengths only cut. Both
    None: the kernel and the program text are what they are without the
    operand.

    block_causal = B > 0 (static; with `causal` and `return_lse` only):
    query i sees key j iff j // B <= (i + Sk - Sq) // B, causal between
    blocks of B positions and whole inside one. B must divide the query
    block (128 and up) and Sk - Sq. 0: the kernel and the jaxpr are what
    they are without the argument.

    window = W > 0 (static; with `return_lse` only: the backward kernel has
    no band): a sliding window. With `causal`, query i sees key j iff i +
    Sk - Sq - W < j <= i + Sk - Sq: itself and the W - 1 keys before it.
    Without, the call is the CONTEXT part of a pass that resumes: its
    queries follow the row's keys (`kv_lens`, or Sk), and query i sees key
    j iff j > kv_len + i - W. Key blocks wholly behind the band are not
    visited. None: the kernel and the jaxpr are what they are without the
    argument.
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    if hq % hkv != 0:
        raise ValueError(f"num q heads {hq} not a multiple of kv heads {hkv}")
    if causal and sk < sq:
        raise ValueError(f"causal attention needs sk >= sq, got {sq=} {sk=}")
    if v.shape[-1] != d and not return_lse:
        raise ValueError(
            f"values of width {v.shape[-1]} beside keys of {d}: the "
            f"forward-only path's (return_lse=True)")
    if window is not None and not (return_lse and window > 0
                                   and not block_causal):
        raise ValueError(
            f"window={window}: a positive sliding window is the forward-"
            f"only path's (return_lse=True, no block_causal): the backward "
            f"kernel has no band")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = float(scale if scale is not None else d ** -0.5)
    if not scale > 0:
        raise ValueError(f"scale {scale}: the forward takes a row's maximum "
                         f"on raw scores, so the scale must be positive")

    from .attention import split_segment_ids

    q_seg, kv_seg = split_segment_ids(segment_ids, sq, sk)
    # padded kv positions are masked by the in-kernel `k_pos < sk` bound, and
    # padded q rows are sliced off below, so padding needs no sentinel segs
    bq, bk = _pick_blocks(sq, sk, block_q, block_k)
    sq_p, sk_p = _round_up(sq, bq), _round_up(sk, bk)
    if block_causal and not (causal and return_lse and bq % block_causal == 0
                             and (sk - sq) % block_causal == 0):
        raise ValueError(
            f"block_causal={block_causal} needs causal=True, return_lse="
            f"True (forward only) and blocks that divide the query block "
            f"{bq} and Sk - Sq = {sk - sq}")

    def pad(x, s_p, axis):
        pad_n = s_p - x.shape[axis]
        if pad_n == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad_n)
        return jnp.pad(x, widths)

    if _heads_on_lanes(d, v.shape[-1]):
        # the kernels' blocks index the operands where they are
        seq_axis = 1

        def heads_first(x):
            return x

        def seq_first(o):
            return o[:, :sq].reshape(b, sq, hq, -1)
    else:
        # [B,S,H,D] -> [B,H,S,D], and `o` back
        seq_axis = 2

        def heads_first(x):
            return x.transpose(0, 2, 1, 3)

        def seq_first(o):
            return o[:, :, :sq, :].transpose(0, 2, 1, 3)
    qt = pad(heads_first(q), sq_p, seq_axis)
    kt = pad(heads_first(k), sk_p, seq_axis)
    vt = pad(heads_first(v), sk_p, seq_axis)
    if q_seg is not None:
        q_seg = pad(q_seg.astype(jnp.int32), sq_p, 1)
        kv_seg = pad(kv_seg.astype(jnp.int32), sk_p, 1)[..., None]

    lens = None
    if q_lens is not None or kv_lens is not None:
        if not return_lse:
            raise ValueError("q_lens / kv_lens are the forward-only path's "
                             "(return_lse=True): the backward kernel takes "
                             "no lengths")
        lens = jnp.stack([
            jnp.full((b,), n, jnp.int32) if a is None
            else jnp.asarray(a, jnp.int32) for a, n in
            ((q_lens, sq), (kv_lens, sk))])
    if return_lse:
        # forward-only: bypass the custom_vjp (no bwd through the merge)
        o, lse = _fwd(qt, kt, vt, q_seg, kv_seg, causal, scale, bq, bk,
                      interpret, sq, sk, lens, block_causal, window)
        return (seq_first(o),
                lse.reshape(b, hq, sq_p)[:, :, :sq].transpose(0, 2, 1))
    o = _flash(qt, kt, vt, q_seg, kv_seg, causal, scale, bq, bk, interpret,
               sq, sk)
    return seq_first(o)


def flash_attention_sharded(
    q: jax.Array, k: jax.Array, v: jax.Array, mesh, *,
    causal: bool = True, segment_ids=None, scale: Optional[float] = None,
    batch_axes=("dp", "fsdp"), head_axis: str = "tp",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """`flash_attention` under a multi-device mesh: a `shard_map` over the
    batch axes (dp, fsdp) and the head axis (tp), so each device runs the
    one-device kernel on its own [B/n, S, H/tp, D] block. Dense attention
    needs no collective: every (batch row, head) is independent. Callable
    from inside a GSPMD-partitioned jit with global [B,S,H,D] operands
    (the ring_attention_sharded idiom). Axes of the mesh that the specs do
    not name (pp, sp, ep) see replicated operands.

    Raises when the batch or the kv heads do not divide over the mesh —
    there is no quiet reference path under a mesh.
    """
    from jax.sharding import PartitionSpec as P

    b, hkv = q.shape[0], k.shape[2]
    batch = tuple(a for a in batch_axes
                  if a in mesh.axis_names and mesh.shape[a] > 1)
    n_batch = math.prod(mesh.shape[a] for a in batch)
    head = (head_axis if head_axis in mesh.axis_names
            and mesh.shape[head_axis] > 1 else None)
    n_head = mesh.shape[head] if head else 1
    if b % n_batch or hkv % n_head:
        raise ValueError(
            f"flash attention under mesh {dict(mesh.shape)}: batch {b} must "
            f"divide over {batch or '()'} ({n_batch}) and kv heads {hkv} "
            f"over {head!r} ({n_head})")
    qkv_spec = P(batch or None, None, head, None)
    seg_spec = P(batch or None, None)
    pair = isinstance(segment_ids, tuple)
    segs = (() if segment_ids is None
            else tuple(segment_ids) if pair else (segment_ids,))

    def local(q, k, v, *segs):
        seg = None if not segs else (segs if pair else segs[0])
        return flash_attention(q, k, v, causal=causal, segment_ids=seg,
                               scale=scale, interpret=interpret)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(qkv_spec,) * 3 + (seg_spec,) * len(segs),
        out_specs=qkv_spec, check_vma=False)(q, k, v, *segs)
