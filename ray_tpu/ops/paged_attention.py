"""Paged KV-cache attention ops (the serving engine's compute core).

The reference delegates paged attention entirely to vLLM's CUDA kernels
(ref: python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py:181
wraps the external engine; no kernels in-repo). Here it is TPU-native and
owned end to end:

- KV lives in fixed-size pages, ONE pool ``[L, P, Hkv, page, 2*D]`` for
  all layers, with K in lanes ``[:D]`` and V in lanes ``[D:]``. Every op
  takes the whole pool and a ``layer`` index and touches only
  ``kv_pages[layer]``: the pool is never sliced, so a layer loop that
  carries it (models/llama.py) updates it in place. ``layer=None`` means a
  pool with no layer axis, ``[P, Hkv, page, 2*D]`` (direct callers).
  Page-major means ONE DMA descriptor moves a page's K and V for EVERY kv
  head (64 KiB contiguous for an 8-head, page-16, D-128 bfloat16 model) —
  the decode kernel's streaming unit. With K and V side by side a row's
  lanes are ``2*D``: 128 for head_dim-64 models, which satisfies Mosaic's
  128-lane slice alignment where a split K/V pool with D=64 cannot; at
  D=128 K and V are each a whole lane tile of the row.
- ``paged_write`` puts new tokens into their pages by whole pages: read
  the pages a row touches, select the new rows in, scatter the pages back
  (pure XLA, static shapes, untouched pages dropped). A whole page is the
  decode kernel's DMA unit, so the scatter keeps the pool in the row-major
  layout the kernel demands; a per-token scatter makes XLA re-lay the
  whole pool out around every write.
- ``paged_attention_decode`` is a Pallas kernel for the single-token step:
  it builds an in-kernel work list of (sequence, page-chunk) items, then
  streams ONLY the used pages HBM->VMEM with double-buffered async copies
  while accumulating a flash-style online softmax for all heads at once,
  q heads grouped by their kv head. The copies set its pace (alone on a
  v5e, 61 to 89% of the HBM bandwidth; PERF.md section 6, PR 32): an
  item's compute is a quarter to a half of its copy time. Two things
  keep it there. q, the softmax state and the output are ``[Hkv, rep,
  .]``, a head's rows an array of their own on a leading axis: slicing 4
  rows out of, or concatenating them into, 8-row sublane tiles was
  eleven twelfths of the kernel's time when the state was ``[Hq, .]``.
  And nothing touches KV element by element except on a sequence's LAST
  item, where the rows past the length are cleaned. Two lane forms,
  chosen from the head size the kernel is given: where ``D`` is a whole
  number of 128-lane tiles K and V are sliced from a row for free and
  nothing is padded; below (D=64: a row is ONE tile) queries are
  zero-padded to ``[.., 2*D]`` so ``q_pad @ kv^T`` computes q.k exactly
  (the V lanes multiply zeros) and the accumulator runs over ``2*D``
  lanes with the V half taken at the end, which keeps the vector path
  free of sub-tile lane slices. The gather-free design is what moves
  decode from O(max_pages) HBM traffic (plus a GQA broadcast) to O(used
  pages).
- ``paged_prefill_attention`` splits prefill into (1) causal flash
  attention among the new tokens themselves — no page reads at all — and
  (2) flash attention over the cached prefix pages, merged by log-sum-exp.
  Both calls are given each row's true lengths (its real new tokens, its
  prefix), so the kernel runs over real blocks only: a bucket's padding
  and the block table's unused width are not computed, and a row without
  a cached prefix runs none of part (2).
- A LATENT family (models/kimi.py: multi-head latent attention) keeps ONE
  row a token for all heads, ``[L, P, 1, page, lanes]``: ``[c_kv | k_rope |
  zeros]``, the compressed keys-and-values and the one rotated key, after
  the norm and the rotation, padded to whole lane tiles (``latent_lanes``:
  576 values in 640 lanes). ``paged_write`` writes it as any page (the
  latent as K, the pad as V). ``latent_attention_decode`` is the decode
  kernel's third lane form under the name ``_mla_decode``: the whole row
  is the key and its first ``v_width`` lanes the value, so one read of a
  page serves keys, values and all 64 query heads of the absorbed form.
  ``latent_prefill_attention`` is the materialised form: causal flash
  among the new tokens at keys wider than values, and the context in
  static chunks whose per-head keys and values exist one chunk at a time,
  each a flash call (``_mla_flash``) under a ``cond`` on the rows'
  context, merged by log-sum-exp: no gather of a whole block table and no
  kernel that holds a whole context resident.
- A layer with a SLIDING WINDOW (models/mellum.py) keeps no pages under
  the block table: its last ``window`` keys and values sit in a ring a
  decode slot (``ring_write``), which decode reads through the same kernel
  under the name ``_window_decode`` and a resumed prefill pass reads rolled
  into position order beside its own banded flash call
  (``window_prefill_attention``, ``_window_flash``); the comment above
  ``ring_tables`` says how. A full layer's context wider than one flash
  call holds resident is walked in chunks (``_walk_context``,
  ``_ctx_flash``), as a latent family's is.
- ``paged_attention_reference`` is the jnp gather path: the numerics
  oracle for kernel parity tests, the path a CPU backend runs, and the
  path tensor-parallel engines ask for by argument. A TPU backend never
  reaches it unasked.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..util import tracing

NEG_INF = -1e30
LATENT_LANE_TILE = 128


def _fori_no_unroll(lo, hi, body, init):
    """fori_loop with unrolling pinned OFF."""
    return jax.lax.fori_loop(lo, hi, body, init, unroll=False)


def make_kv_pages(num_kv_heads: int, num_pages: int, page_size: int,
                  head_dim: int, dtype) -> jax.Array:
    """Allocate a zeroed page pool [P, Hkv, page, 2*D] (K | V in lanes)."""
    return jnp.zeros((num_pages, num_kv_heads, page_size, 2 * head_dim),
                     dtype)


def _layered(kv_pages: jax.Array, layer):
    """(pool with a layer axis, layer): a pool without one (`layer=None`)
    is layer 0 of one, by a bitcast."""
    return (kv_pages[None], 0) if layer is None else (kv_pages, layer)


# ------------------------------------------------------------------ write
def paged_write(kv_pages: jax.Array, k_new: jax.Array, v_new: jax.Array,
                block_tables: jax.Array, positions: jax.Array,
                total_lens: jax.Array, layer=None) -> jax.Array:
    """Put new tokens' K/V into their sequences' pages of `layer`.

    kv_pages: [L, P, Hkv, page, 2*D] (layer: traced or static index) or
    [P, Hkv, page, 2*D] (layer=None); k_new/v_new: [B, S, Hkv, D];
    block_tables: [B, MP] page ids; positions: [B, S] absolute positions
    of the new tokens, contiguous from positions[:, 0]; total_lens: [B]
    sequence length INCLUDING the new tokens. Writes for padding rows
    (positions >= total_lens) are dropped.

    Whole pages move: each row reads the (S + page - 2) // page + 1 pages
    its span can touch, selects the new rows in and scatters the pages
    back; pages with no new row go to an out-of-bounds id and are dropped.
    Pages a row writes are its own (shared prefix pages are full and only
    read), so the scatter has no duplicate indices.
    """
    if layer is None:
        return paged_write(kv_pages[None], k_new, v_new, block_tables,
                           positions, total_lens, 0)[0]
    with tracing.scope("rtpu.attn.cache_write"):
        return _paged_write(kv_pages, k_new, v_new, block_tables, positions,
                            total_lens, layer)


def _paged_write(kv_pages, k_new, v_new, block_tables, positions, total_lens,
                 layer):
    _, num_pages, hkv, page, d2 = kv_pages.shape
    b, s = positions.shape
    mp = block_tables.shape[1]
    n_pg = (s + page - 2) // page + 1
    start = positions[:, 0]
    lp = (start // page)[:, None] + jnp.arange(n_pg)    # [B, n_pg] columns
    tok = lp[:, :, None] * page + jnp.arange(page)      # absolute positions
    src = tok - start[:, None, None]                    # index of new token
    write = ((src >= 0) & (src < s) & (lp < mp)[:, :, None]
             & (tok < total_lens[:, None, None]))       # [B, n_pg, page]
    pg = jnp.take_along_axis(block_tables, jnp.minimum(lp, mp - 1), axis=1)
    pg = jnp.where(write.any(-1), pg, num_pages)        # OOB -> mode="drop"
    kv = jnp.concatenate([k_new, v_new], axis=-1).astype(kv_pages.dtype)
    if s == 1:
        new = kv[:, :, :, None, :]                      # [B, 1, Hkv, 1, 2D]
    else:
        new = jnp.take_along_axis(
            kv, jnp.clip(src, 0, s - 1).reshape(b, -1, 1, 1), axis=1)
        new = new.reshape(b, n_pg, page, hkv, d2).transpose(0, 1, 3, 2, 4)
    old = kv_pages[layer, jnp.minimum(pg, num_pages - 1)]
    pages = jnp.where(write[:, :, None, :, None], new, old)
    return kv_pages.at[layer, pg].set(pages, mode="drop")


# -------------------------------------------------------- gather reference
def gather_kv(kv_pages: jax.Array, block_tables: jax.Array,
              layer=None) -> Tuple[jax.Array, jax.Array]:
    """[L, P, Hkv, page, 2D] at `layer` (or [P, Hkv, page, 2D]) + [B, MP]
    -> (k, v) each [B, MP*page, Hkv, D]. One gather, no slice of a layer
    first."""
    with tracing.scope("rtpu.attn.cache_write"):
        return _gather_kv(kv_pages, block_tables, layer)


def _gather_kv(kv_pages, block_tables, layer):
    kv_pages, layer = _layered(kv_pages, layer)
    hkv, page, d2 = kv_pages.shape[-3:]
    b, mp = block_tables.shape
    out = kv_pages[layer, block_tables]           # [B, MP, Hkv, page, 2D]
    out = out.transpose(0, 1, 3, 2, 4).reshape(b, mp * page, hkv, d2)
    d = d2 // 2
    return out[..., :d], out[..., d:]


def paged_attention_reference(q: jax.Array, kv_pages: jax.Array,
                              block_tables: jax.Array,
                              positions: jax.Array,
                              *, scale: Optional[float] = None,
                              layer=None) -> jax.Array:
    """Attention over paged KV, gather-based. Causal by absolute position:
    query at position p attends to kv positions <= p within its own block
    table. The numerics oracle for the Pallas kernels and the off-TPU path.

    q: [B, S, Hq, D]; kv_pages: [L, P, Hkv, page, 2D] at `layer`, or
    [P, Hkv, page, 2D]; block_tables: [B, MP]; positions: [B, S].
    Returns [B, S, Hq, D].
    """
    b, s, hq, d = q.shape
    hkv, page = kv_pages.shape[-3:-1]
    mp = block_tables.shape[1]
    k, v = gather_kv(kv_pages, block_tables, layer)  # [B, K, Hkv, D] each
    rep = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    # GQA without materialising the broadcast: contract per kv-head group
    qg = q.reshape(b, s, hkv, rep, d)
    logits = jnp.einsum("bshrd,bkhd->bhrsk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    kv_pos = jnp.arange(mp * page)
    mask = kv_pos[None, None, None, None, :] \
        <= positions[:, None, None, :, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhrsk,bkhd->bshrd", probs, v)
    return out.reshape(b, s, hq, d)


# ----------------------------------------------------------- decode kernel
def _decode_kernel(lengths_ref, bt_ref, layer_ref, # SMEM scalars
                   q_ref, kv_hbm,                  # VMEM / HBM
                   o_ref,                          # VMEM out
                   kv_buf, work_b, work_c,         # scratch
                   sems, *,
                   page: int, chunk: int, scale: float,
                   stream: bool = True, attend: bool = True,
                   v_width: int = 0):
    """Single-program decode kernel (grid=()): one flattened work list of
    (sequence, page-chunk) items, double-buffered page DMAs, all kv heads
    per item. `kv_hbm` is the whole [L, P, Hkv, page, 2D] pool; only pages
    of layer `layer_ref[0]` are streamed. q and the output are grouped by
    kv head, [B, Hkv, rep, .]: a group's rows are an array of their own,
    so the softmax state needs no sublane slice or concatenation.

    A single program (rather than a grid) keeps ONE uninterrupted DMA
    pipeline across every sequence — per-program warm-up latency would
    otherwise be paid per grid step. v5e has one TensorCore per chip, so
    there is no grid parallelism to lose. All heads ride one item because
    a page holds every head's K/V contiguously — B*chunks items total,
    not B*chunks*Hkv.

    `stream=False` starts and awaits no copy, `attend=False` skips an
    item's compute: the two halves of an item, for
    benchmarks/paged_decode_probe.py to time alone. The engine runs both.

    `v_width` > 0: the pool holds ONE latent row a token for all heads
    (`Hkv` 1; models/kimi.py): the keys are the row's every lane and the
    values its first `v_width`, so one read of a page serves both and
    every query head (`_mla_decode`).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_b = lengths_ref.shape[0]
    layer = layer_ref[0]
    bk = chunk * page                              # kv rows per work item
    hkv, rep, q_lanes = q_ref.shape[1:]
    d2 = kv_hbm.shape[4]
    d = d2 // 2
    # Two lane forms, by the head size (see _decode_pallas). q unpadded:
    # K is lanes [:D] and V lanes [D:] of a row, whole lane tiles both.
    # q zero-padded to 2D lanes: q_pad @ kv^T == q @ k^T (the V lanes
    # multiply zeros) and p @ kv carries the K half along to be dropped
    # at the end; for D = 64 a row is ONE lane tile, so that costs no more
    # than the sub-tile slice it avoids.
    k_lanes, v_lanes = ((0, d), (d, d2)) if q_lanes == d else ((0, d2),) * 2
    if v_width:
        # a third: a latent row is the key whole and the value in front
        k_lanes, v_lanes = (0, d2), (0, v_width)

    # ---- build the work list: (b, chunk) for every used page-chunk
    def fill_b(b, cnt):
        n_pages = pl.cdiv(lengths_ref[b], page)

        def fill_c(c, cnt):
            work_b[cnt] = b
            work_c[cnt] = c
            return cnt + 1

        return _fori_no_unroll(0, pl.cdiv(n_pages, chunk), fill_c, cnt)

    n_items = _fori_no_unroll(0, n_b, fill_b, 0)

    # rows not covered by any work item (inactive slots) stay zero
    o_ref[...] = jnp.zeros_like(o_ref)
    # The buffers never hold a non-finite row: they start as zeros, a
    # copy brings only pages the sequence owns, and the one page of those
    # that can hold rows past the length is cleaned below. So the pages of
    # a short last item that no copy refreshed are old but finite, and a
    # zero weight times them is zero.
    kv_buf[...] = jnp.zeros_like(kv_buf)

    def live_pages(t):
        b, c = work_b[t], work_c[t]
        return jnp.minimum(pl.cdiv(lengths_ref[b], page) - c * chunk, chunk)

    def start_item(t, slot):
        b, c = work_b[t], work_c[t]

        def start(j, _):
            pltpu.make_async_copy(
                kv_hbm.at[layer, bt_ref[b, c * chunk + j]],
                kv_buf.at[slot, j], sems.at[slot]).start()
            return _

        if stream:
            _fori_no_unroll(0, live_pages(t), start, 0)

    def wait_item(t, slot):
        def wait(j, _):
            # a wait reads the descriptor's size and semaphore only: any
            # page of the pool stands for the source
            pltpu.make_async_copy(
                kv_hbm.at[layer, 0], kv_buf.at[slot, j],
                sems.at[slot]).wait()
            return _

        if stream:
            _fori_no_unroll(0, live_pages(t), wait, 0)

    @pl.when(n_items > 0)
    def _():
        start_item(0, 0)

    def body(t, carry):
        m, l, acc = carry
        slot = jax.lax.rem(t, 2)
        b, c = work_b[t], work_c[t]

        @pl.when(t + 1 < n_items)
        def _():
            start_item(t + 1, 1 - slot)

        wait_item(t, slot)
        if not attend:
            return carry
        length = lengths_ref[b]
        # the item is its sequence's last when the NEXT one is another's
        t_next = jnp.minimum(t + 1, work_b.shape[0] - 1)
        is_last = jnp.logical_or(t + 1 >= n_items, work_b[t_next] != b)

        @pl.when(is_last)
        def _():
            # the tail: rows at or past the length came in with the page
            # that holds the sequence's end, stale and maybe not finite.
            # Only here is there any per-element work on KV.
            j = (length - 1) // page - c * chunk
            row = (c * chunk + j) * page + jax.lax.broadcasted_iota(
                jnp.int32, (1, page, 1), 1)
            kv_buf[slot, j] = jnp.where(row < length, kv_buf[slot, j], 0)

        def head(h, lanes):
            # [chunk, page, lanes] -> [bk, lanes]: page is a whole sublane
            # tile, so the merge is layout-preserving
            lo, hi = lanes
            return kv_buf[slot, :, h, :, lo:hi].reshape(bk, hi - lo)

        q = q_ref[b]                               # [Hkv, rep, D or 2D]
        s = jnp.stack([jax.lax.dot_general(
            q[h], head(h, k_lanes), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) for h in range(hkv)])
        row_pos = c * bk + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk), 2)
        mask = row_pos < length
        s = jnp.where(mask, s * scale, NEG_INF)    # [Hkv, rep, bk]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        m = m_new
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        p = p.astype(kv_buf.dtype)
        pv = jnp.stack([jax.lax.dot_general(
            p[h], head(h, v_lanes), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) for h in range(hkv)])
        acc = acc * alpha + pv                     # [Hkv, rep, D or 2D]

        @pl.when(is_last)
        def _():
            # V's lanes of the accumulator: all of it, or its upper half
            o_ref[b] = ((acc if v_width else acc[:, :, -d:]) / l).astype(
                o_ref.dtype)

        m = jnp.where(is_last, jnp.full_like(m, NEG_INF), m)
        l = jnp.where(is_last, jnp.zeros_like(l), l)
        acc = jnp.where(is_last, jnp.zeros_like(acc), acc)
        return m, l, acc

    m0 = jnp.full((hkv, rep, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((hkv, rep, 1), jnp.float32)
    acc0 = jnp.zeros((hkv, rep, v_lanes[1] - v_lanes[0]), jnp.float32)
    _fori_no_unroll(0, n_items, body, (m0, l0, acc0))


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_chunk",
                                             "interpret"))
def _decode_call(q, kv_pages, block_tables, lengths, layer, *,
                 scale: float, pages_per_chunk: int, interpret: bool):
    return _decode_pallas(_decode_kernel, q, kv_pages, block_tables, lengths,
                          layer, scale=scale, chunk=pages_per_chunk,
                          interpret=interpret)


def _decode_pallas(kernel, q, kv_pages, block_tables, lengths, layer, *,
                   scale: float, chunk: int, interpret: bool,
                   v_width: int = 0):
    """The decode kernel's one `pallas_call`, around `kernel` (the whole
    `_decode_kernel`, or a half of it for the probe). `v_width` > 0: the
    latent form; q comes at the row's whole width and `v_width` lanes come
    back."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hq, d = q.shape
    _, _, hkv, page, d2 = kv_pages.shape
    max_chunks = -(-block_tables.shape[1] // chunk)
    q = q.reshape(b, hkv, hq // hkv, d)
    if v_width:
        kernel = functools.partial(kernel, v_width=v_width)
        d = v_width
    elif d % 128:
        # K and V are not whole lane tiles: the padded-q form
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, d2 - d)))

    kernel = functools.partial(kernel, page=page, chunk=chunk, scale=scale)
    out = pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),      # lengths [B]
            pl.BlockSpec(memory_space=pltpu.SMEM),      # block_tables
            pl.BlockSpec(memory_space=pltpu.SMEM),      # layer [1]
            pl.BlockSpec(memory_space=pltpu.VMEM),      # q, by kv head
            # explicitly HBM (not ANY): the compiler would happily place
            # a small page pool in VMEM, where per-page slices violate
            # tile alignment — and the pool must not eat VMEM anyway.
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, hkv, hq // hkv, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, hkv, page, d2), kv_pages.dtype),
            pltpu.SMEM((b * max_chunks,), jnp.int32),
            pltpu.SMEM((b * max_chunks,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, kv_pages)
    return out.reshape(b, hq, d)


def decode_kernel_constraint(head_dim: int, page_size: int,
                             dtype) -> Optional[str]:
    """Why the compiled decode kernel cannot take this pool layout, or
    None when it can. Mosaic's slice-alignment contract: the page's lane
    width 2*head_dim is a multiple of 128 and a page covers whole sublane
    tiles (16 rows of bfloat16, 8 of float32)."""
    sublane = 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8
    if (2 * head_dim) % 128:
        return (f"2*head_dim must be a multiple of 128 lanes, got "
                f"head_dim={head_dim}")
    if page_size % sublane:
        return (f"page_size must be a multiple of {sublane} rows for "
                f"{jnp.dtype(dtype).name}, got page_size={page_size}")
    return None


def latent_kernel_constraint(v_width: int, page_size: int,
                             dtype) -> Optional[str]:
    """`decode_kernel_constraint` for a latent pool: its rows are whole
    lane tiles by construction (`latent_lanes`); the values' slice of a
    row must be too, and a page whole sublane tiles."""
    if v_width % LATENT_LANE_TILE:
        return (f"the values' width (kv_lora_rank) must be a multiple of "
                f"{LATENT_LANE_TILE} lanes, got {v_width}")
    return decode_kernel_constraint(LATENT_LANE_TILE, page_size, dtype)


ITEM_BYTES = 2 << 20


def default_pages_per_chunk(kv_pages: jax.Array) -> int:
    """Pages a work item of the decode kernel holds: as many as 2 MiB take
    (32 pages = 512 kv rows at Hkv 8, D 128, page 16, bfloat16). The
    copies set the kernel's pace, and they run closest to the chip's
    bandwidth with few, large items: on a v5e 32 pages beat 8 and 16 at
    8k-token and at 400-token rows alike and 48 and 64 bring nothing
    (PERF.md section 6, PR 32). A short row's dead pages cost nothing:
    they are never copied, and their products hide behind the next item's
    copies. Two such buffers and the compute's temporaries sit far inside
    the 16 MiB of VMEM a kernel may scope."""
    hkv, page, d2 = kv_pages.shape[-3:]
    return max(1, ITEM_BYTES // (hkv * page * d2 * kv_pages.dtype.itemsize))


def paged_attention_decode(q: jax.Array, kv_pages: jax.Array,
                           block_tables: jax.Array, lengths: jax.Array, *,
                           layer=None,
                           scale: Optional[float] = None,
                           pages_per_chunk: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           force_reference: bool = False,
                           _call=None) -> jax.Array:
    """Single-token decode attention over paged KV (Pallas on TPU).

    q: [B, Hq, D] (the newest token per sequence, already written to its
    page); kv_pages: [L, P, Hkv, page, 2D] at `layer`, or
    [P, Hkv, page, 2D]; block_tables: [B, MP]; lengths: [B] total tokens
    per sequence (0 = inactive row -> zero output). Returns [B, Hq, D].

    Which implementation runs is the caller's choice or the backend's,
    never a quiet substitution: `force_reference=True` is the jnp gather
    path (tensor-parallel engines, which trace under GSPMD);
    `interpret=True` is the kernel in interpreter mode (parity tests);
    with both left alone a TPU backend runs the compiled kernel — and
    raises on a pool layout the kernel cannot take — while a CPU backend
    (`JAX_PLATFORMS=cpu`) runs the reference.
    """
    d = q.shape[-1]
    scale_f = float(scale if scale is not None else d ** -0.5)
    page = kv_pages.shape[-2]
    on_tpu = jax.default_backend() == "tpu"
    if force_reference or (interpret is None and not on_tpu):
        positions = jnp.maximum(lengths - 1, 0)[:, None]
        out = paged_attention_reference(
            q[:, None], kv_pages, block_tables, positions,
            scale=scale_f, layer=layer)[:, 0]
        # honor the inactive-row contract (length 0 -> zero output):
        # the clamped position would otherwise admit kv position 0
        return jnp.where((lengths > 0)[:, None, None], out, 0)
    if not interpret:
        why = decode_kernel_constraint(d, page, kv_pages.dtype)
        if why is not None:
            raise ValueError(f"paged decode kernel: {why}")
    if pages_per_chunk is None:
        pages_per_chunk = default_pages_per_chunk(kv_pages)
    pages_per_chunk = min(pages_per_chunk, block_tables.shape[1])
    kv_pages, layer = _layered(kv_pages, layer)
    # (`_call`: the same kernel under another jit's name, `_window_decode`)
    return (_call or _decode_call)(
        q, kv_pages, block_tables, lengths, layer, scale=scale_f,
        pages_per_chunk=pages_per_chunk, interpret=bool(interpret))


# ------------------------------------------------- a window's ring a slot
# A layer with a sliding window of W tokens keeps a sequence's LAST W keys
# and values and no others: a ring a decode slot, `win_pages` [Lw, slots *
# W / page, Hkv, page, 2D] beside the full layers' pages in the one donated
# pool. Slot s owns pages [s * W / page, (s + 1) * W / page) for good (no
# allocator: a slot is the unit that is admitted), and the token at
# position p sits at ring index p % W, so a write past W tokens overwrites
# the token that has just left the window. The rotation is in the keys
# already, so the order of a ring's rows means nothing to attention: decode
# is `_decode_kernel` over the slot's pages with the length min(tokens, W),
# under a name of its own (`_window_decode`), and it reads the window's
# bytes whatever the context. A prefill pass attends its own tokens under
# the band and, where it resumes, the ring as the pass before left it,
# rolled into position order (`ring_context`), then writes its last W.

def ring_tables(slots: jax.Array, ring_pages: int) -> jax.Array:
    """[B] decode slots -> [B, ring_pages] page ids of their rings."""
    return (slots[:, None] * ring_pages
            + jnp.arange(ring_pages, dtype=jnp.int32)[None, :])


def ring_write(win_pages: jax.Array, k_new: jax.Array, v_new: jax.Array,
               slots: jax.Array, positions: jax.Array,
               total_lens: jax.Array, layer, ring_pages: int) -> jax.Array:
    """Put new tokens' K/V into their slots' rings of `layer`: the token at
    position p goes to ring index p % W (W = ring_pages * page). k_new /
    v_new [B, S, Hkv, D]; positions [B, S] contiguous from positions[:, 0];
    total_lens [B] INCLUDING the new tokens: a position at or past it is
    padding and writes nothing, an idle row (total 0) keeps its ring bit
    for bit. One token a row (decode) is `paged_write`'s page scatter at
    the ring's index; a pass of S tokens rebuilds each row's ring whole
    (its last W real tokens over what the ring held: 2 MB a layer at W
    1024, 4 kv heads of 128)."""
    with tracing.scope("rtpu.attn.cache_write"):
        return _ring_write(win_pages, k_new, v_new, slots, positions,
                           total_lens, layer, ring_pages)


def _ring_write(win_pages, k_new, v_new, slots, positions, total_lens, layer,
                ring_pages: int):
    _, num_pages, hkv, page, d2 = win_pages.shape
    b, s = positions.shape
    ring = ring_pages * page
    if s == 1:
        at = positions % ring
        live = positions[:, 0] < total_lens
        return _paged_write(win_pages, k_new, v_new,
                            ring_tables(slots, ring_pages), at,
                            jnp.where(live, at[:, 0] + 1, 0), layer)
    kv = jnp.concatenate([k_new, v_new], axis=-1).astype(win_pages.dtype)
    start = positions[:, :1]                                       # [B, 1]
    end = jnp.minimum(total_lens[:, None], start + s)
    # the newest position < end that sits at each ring index
    idx = jnp.arange(ring, dtype=jnp.int32)[None, :]
    newest = end - 1 - (end - 1 - idx) % ring                      # [B, W]
    write = ((newest >= start) & (end > start)).reshape(b, ring_pages, page)
    new = jnp.take_along_axis(
        kv, jnp.clip(newest - start, 0, s - 1)[:, :, None, None], axis=1)
    new = new.reshape(b, ring_pages, page, hkv, d2).transpose(0, 1, 3, 2, 4)
    # as `paged_write`: whole pages in one scatter, a page with no new row
    # (a padding row's, whose slot may be a real row's) to an id that drops
    pg = jnp.where(write.any(-1), ring_tables(slots, ring_pages), num_pages)
    old = win_pages[layer, jnp.minimum(pg, num_pages - 1)]
    pages = jnp.where(write[:, :, None, :, None], new, old)
    return win_pages.at[layer, pg].set(pages, mode="drop")


def ring_context(win_pages: jax.Array, slots: jax.Array, start: jax.Array,
                 layer, ring_pages: int):
    """What the rings hold for rows whose next token is `start` [B], in
    position order with the real keys first: (k, v) each [B, W, Hkv, D],
    and kv_len [B] = min(start, W). Row c of a ring read so is position
    max(start - W, 0) + c, which sits at ring index (that) % W. The rings
    are gathered as whole pages (`gather_kv`: the pool is read in the
    layout it has; a gather of single rows makes XLA copy the whole pool
    into a layout of its liking, tests/test_chip_compile.py) and their rows
    put in order afterwards, on 2 MB a row."""
    ring = ring_pages * win_pages.shape[-2]
    with tracing.scope("rtpu.attn.cache_write"):
        k, v = _gather_kv(win_pages, ring_tables(slots, ring_pages), layer)
        at = (jnp.maximum(start - ring, 0)[:, None]
              + jnp.arange(ring, dtype=jnp.int32)[None, :]) % ring  # [B, W]
        k, v = (jnp.take_along_axis(x, at[:, :, None, None], axis=1)
                for x in (k, v))
    return k, v, jnp.minimum(start, ring)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "window",
                                             "impl"))
def _window_flash(q, k, v, q_lens, kv_lens, *, causal: bool, scale: float,
                  window: int, impl: Optional[str]):
    """`_attn_lse` under a window and a name of its own (as `_mla_flash`):
    a windowed layer's flash calls are read apart from a full layer's in a
    trace."""
    return _attn_lse(q, k, v, causal=causal, scale=scale, q_lens=q_lens,
                     kv_lens=kv_lens, impl=impl, window=window)


def window_prefill_attention(q: jax.Array, k_new: jax.Array,
                             v_new: jax.Array, win_pages: jax.Array,
                             slots: jax.Array, positions: jax.Array,
                             total_lens: jax.Array, *, window: int,
                             ring_pages: int, resumes: bool, scale: float,
                             impl: Optional[str] = None,
                             layer=None) -> jax.Array:
    """Prefill attention of a layer with a sliding window: query i sees key
    j iff i - window < j <= i, by absolute position. The new tokens attend
    themselves under the band (no tile behind it is visited) and, where
    the pass `resumes` (static: the program has a context part), what
    their slot's ring holds from the passes before, the band against the
    same absolute positions; merged by log-sum-exp. Called BEFORE the pass
    writes its own tokens to the ring. Lengths as
    `paged_prefill_attention` gives them to the kernel."""
    start = positions[:, 0]
    n_new = jnp.clip(total_lens - start, 0, q.shape[1])
    o1, lse1 = _window_flash(q, k_new, v_new, n_new, None, causal=True,
                             scale=scale, window=window, impl=impl)
    if not resumes:
        return o1
    k_ctx, v_ctx, have = ring_context(win_pages, slots, start, layer,
                                      ring_pages)
    o2, lse2 = _window_flash(q, k_ctx, v_ctx, n_new, have, causal=False,
                             scale=scale, window=window, impl=impl)
    return merge_attention(o1, lse1, o2, lse2)


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_chunk",
                                             "interpret"))
def _window_decode(q, kv_pages, block_tables, lengths, layer, *,
                   scale: float, pages_per_chunk: int, interpret: bool):
    """`_decode_kernel` over the slots' rings, under a name of its own (as
    `_mla_decode`): its time is read apart from `_decode_call`'s."""
    return _decode_pallas(_decode_kernel, q, kv_pages, block_tables, lengths,
                          layer, scale=scale, chunk=pages_per_chunk,
                          interpret=interpret)


def window_attention_decode(q: jax.Array, win_pages: jax.Array,
                            total_lens: jax.Array, *, ring_pages: int,
                            layer=None, scale: Optional[float] = None,
                            interpret: Optional[bool] = None,
                            force_reference: bool = False) -> jax.Array:
    """Single-token decode over the slot set's rings: row i is slot i, its
    newest token already written (`ring_write`). q [S, Hq, D]; total_lens
    [S] (0 = idle -> zeros). The ring holds exactly the window, so the
    kernel's one length a row, min(tokens, W), is the band. The
    implementation is chosen as `paged_attention_decode` chooses."""
    page = win_pages.shape[-2]
    slots = jnp.arange(q.shape[0], dtype=jnp.int32)
    return paged_attention_decode(
        q, win_pages, ring_tables(slots, ring_pages),
        jnp.minimum(total_lens, ring_pages * page), layer=layer, scale=scale,
        interpret=interpret, force_reference=force_reference,
        _call=_window_decode)


# --------------------------------------------------- decode over latents

def latent_lanes(width: int) -> int:
    """Lanes of a latent pool's row: the latent's `width` (512 compressed
    + 64 rotated at the published sizes) rounded up to whole lane tiles
    (640). The pad is zeros and stays zeros (`paged_write` writes them),
    so a query padded with anything multiplies nothing; it costs a ninth
    more bytes a token than the latent itself, which a roofline counted
    at `width` shows as a lower share. (A 576-lane row is not a whole
    number of tiles: the chip's tiled layout pads it in memory all the
    same, and the kernel's slices would no longer be aligned.)"""
    return -(-width // LATENT_LANE_TILE) * LATENT_LANE_TILE


# pages a work item of the latent kernel holds: 1024 rows of a 64-token
# page, 1.3 MB at 640 lanes; the scores' [64, 1024] are whole lane tiles
LATENT_ITEM_ROWS = 1024


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_chunk",
                                             "v_width", "interpret"))
def _mla_decode(q, kv_pages, block_tables, lengths, layer, *, scale: float,
                pages_per_chunk: int, v_width: int, interpret: bool):
    """`_decode_kernel` in its latent form, under a name of its own: an
    instruction in a trace takes the name of the innermost jit around its
    `pallas_call`, and this kernel's time is read apart from
    `_decode_call`'s."""
    return _decode_pallas(_decode_kernel, q, kv_pages, block_tables, lengths,
                          layer, scale=scale, chunk=pages_per_chunk,
                          interpret=interpret, v_width=v_width)


def latent_attention_reference(q: jax.Array, kv_pages: jax.Array,
                               block_tables: jax.Array, lengths: jax.Array,
                               *, v_width: int, scale: float,
                               layer=None) -> jax.Array:
    """The gather path of `latent_attention_decode`: the oracle of the
    kernel and what a CPU backend runs. Row b attends its first
    `lengths[b]` latents."""
    kv_pages, layer = _layered(kv_pages, layer)
    page, lanes = kv_pages.shape[-2:]
    b, mp = block_tables.shape
    rows = kv_pages[layer, block_tables].reshape(b, mp * page, lanes)
    logits = jnp.einsum("bhd,bkd->bhk", q, rows[..., :q.shape[-1]],
                        preferred_element_type=jnp.float32) * scale
    live = jnp.arange(mp * page)[None, :] < lengths[:, None]
    logits = jnp.where(live[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(rows.dtype)
    out = jnp.einsum("bhk,bkd->bhd", probs, rows[..., :v_width])
    return jnp.where((lengths > 0)[:, None, None], out, 0)


def latent_attention_decode(q: jax.Array, kv_pages: jax.Array,
                            block_tables: jax.Array, lengths: jax.Array, *,
                            v_width: int, scale: float, layer=None,
                            pages_per_chunk: Optional[int] = None,
                            interpret: Optional[bool] = None,
                            force_reference: bool = False) -> jax.Array:
    """Single-token decode attention in latent attention's ABSORBED form:
    every query head of a row against the row's latents, one shared key
    row a token.

    q: [B, H, W] the absorbed queries (`W_UK^T q_nope | q_rope`, W the
    latent's width); kv_pages: [L, P, 1, page, lanes] at `layer` (or
    without the layer axis), a token's row `[c_kv | k_rope | zeros]`,
    lanes = `latent_lanes(W)`; lengths: [B] (0 = inactive row -> zeros).
    Returns [B, H, v_width]: the softmax-weighted sum of the rows' first
    `v_width` lanes (`c_kv`), for the caller's `W_UV`. The kernel reads a
    page ONCE for keys, values and all H heads; no [rows x context] array
    and no gather of the context exists. The implementation is chosen as
    `paged_attention_decode` chooses."""
    b, h, w = q.shape
    hkv, page, lanes = kv_pages.shape[-3:]
    if hkv != 1 or lanes != latent_lanes(w):
        raise ValueError(
            f"a latent pool is [.., 1, page, {latent_lanes(w)}] for "
            f"queries of {w}; got {kv_pages.shape}")
    on_tpu = jax.default_backend() == "tpu"
    if force_reference or (interpret is None and not on_tpu):
        return latent_attention_reference(
            q, kv_pages, block_tables, lengths, v_width=v_width,
            scale=float(scale), layer=layer)
    if not interpret:
        why = latent_kernel_constraint(v_width, page, kv_pages.dtype)
        if why is not None:
            raise ValueError(f"latent decode kernel: {why}")
    if pages_per_chunk is None:
        pages_per_chunk = max(1, LATENT_ITEM_ROWS // page)
    pages_per_chunk = min(pages_per_chunk, block_tables.shape[1])
    kv_pages, layer = _layered(kv_pages, layer)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, lanes - w)))
    return _mla_decode(q, kv_pages, block_tables, lengths, layer,
                       scale=float(scale), pages_per_chunk=pages_per_chunk,
                       v_width=v_width, interpret=bool(interpret))


def paged_attention_block(q: jax.Array, kv_pages: jax.Array,
                          block_tables: jax.Array, lengths: jax.Array, *,
                          layer=None, scale: Optional[float] = None,
                          interpret: Optional[bool] = None,
                          force_reference: bool = False) -> jax.Array:
    """One block of query tokens a row, all of which see ALL `lengths`
    keys of their row (the block's own included: already written to its
    pages), with no mask between them: a denoising pass of a model that
    generates by diffusion over blocks.

    q: [B, T, Hq, D]; lengths: [B] (0 = inactive row -> zero output).
    Returns [B, T, Hq, D]. It is `paged_attention_decode` with the T
    tokens x rep query heads of a kv head as that head's group of query
    rows: one read of a row's pages serves every token of its block. The
    implementation is chosen as that call chooses it."""
    b, t, hq, d = q.shape
    hkv = kv_pages.shape[-3]
    rep = hq // hkv
    grouped = q.reshape(b, t, hkv, rep, d).transpose(0, 2, 1, 3, 4)
    out = paged_attention_decode(
        grouped.reshape(b, hkv * t * rep, d), kv_pages, block_tables,
        lengths, layer=layer, scale=scale, interpret=interpret,
        force_reference=force_reference)
    return out.reshape(b, hkv, t, rep, d).transpose(0, 2, 1, 3, 4).reshape(
        b, t, hq, d)


# --------------------------------------------------------- prefill (+ctx)
def _attn_lse(q, k, v, *, causal, scale, q_lens=None, kv_lens=None,
              impl=None, block_causal=0, window=None):
    """Attention returning (o [B,S,Hq,D], lse [B,S,Hq]). kv_lens [B]: the
    keys a row has (the rest are masked); q_lens [B]: its real queries
    (the flash kernel computes no block past them; the jnp path computes
    every row, and a padded one means nothing either way).

    impl: None = flash kernel on a TPU backend, jnp reference on a CPU
    backend (`JAX_PLATFORMS=cpu`); "flash" forces the Pallas kernel
    (interpreter mode on a CPU backend); "reference" forces the jnp path.
    Both parts of a merged prefill go through the SAME implementation so
    their lse scales match exactly. `window`: `flash_attention`'s (a
    sliding window; a call that is not causal is a resumed pass's context
    part, its queries following the row's keys).
    """
    if impl == "flash" or (impl is None and jax.default_backend() == "tpu"):
        from .flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal, scale=scale, return_lse=True,
            q_lens=q_lens, kv_lens=kv_lens, block_causal=block_causal,
            **({} if window is None else {"window": window}))
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    rep = hq // hkv
    qg = q.reshape(b, sq, hkv, rep, d)
    logits = jnp.einsum("bshrd,bkhd->bhrsk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        from .attention import causal_mask

        logits = jnp.where(causal_mask(sq, sk, block_causal)[
            None, None, None], logits, NEG_INF)
    if kv_lens is not None:
        live = jnp.arange(sk)[None, :] < kv_lens[:, None]
        logits = jnp.where(live[:, None, None, None, :], logits, NEG_INF)
    if window is not None:
        # [B or 1, 1, 1]: where query 0 stands among the keys
        woff = (jnp.full((1,), sk - sq) if causal else
                jnp.full((1,), sk) if kv_lens is None else kv_lens)
        band = jnp.arange(sk)[None, None, :] > (
            jnp.arange(sq)[None, :, None] + woff[:, None, None] - window)
        logits = jnp.where(band[:, None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    if window is not None:
        # a query the band leaves no key (a context part's later rows):
        # o = 0 and lse = NEG_INF, as the kernel gives
        p = jnp.where(m > NEG_INF / 2, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhrsk,bkhd->bshrd", (p / l_safe).astype(v.dtype), v)
    lse = (m + jnp.log(l_safe))[..., 0]            # [B,Hkv,rep,S]
    return (o.reshape(b, sq, hq, v.shape[-1]),
            lse.reshape(b, hq, sq).transpose(0, 2, 1))


def merge_attention(o1: jax.Array, lse1: jax.Array,
                    o2: jax.Array, lse2: jax.Array,
                    return_lse: bool = False):
    """Combine two attention partials over disjoint kv sets by their
    log-sum-exp. o*: [B,S,H,D]; lse*: [B,S,H]. `return_lse`: also the
    log-sum-exp of the union, for a caller that merges a third part."""
    m = jnp.maximum(lse1, lse2)
    a1 = jnp.exp(lse1 - m)
    a2 = jnp.exp(lse2 - m)
    denom = a1 + a2
    w1 = (a1 / denom)[..., None]
    w2 = (a2 / denom)[..., None]
    out = (o1.astype(jnp.float32) * w1
           + o2.astype(jnp.float32) * w2).astype(o1.dtype)
    if return_lse:
        return out, m + jnp.log(denom)
    return out


# VMEM one flash call over a paged context may fill with one kv head's keys
# and values: the forward keeps them resident and double-buffered, in the
# 16 MB a kernel gets unasked beside its query block, scores and
# accumulators. At a head of 128 in bf16 that is 12,288 rows. A table of
# 33,792 does not compile as one call; chunks of 16,384 compile and then
# run out of VMEM in a program whose other operands XLA keeps there too
# (PERF.md section 6, PR 47); a table of 8,320 is the one call it ever was
FLASH_RESIDENT_KV_BYTES = 12 << 20


def ctx_chunks(ctx_pages: int, page: int, chunk_tokens: int) -> Tuple:
    """((first column, columns), ...) of the block table: the static chunks
    of at most `chunk_tokens` a context of `ctx_pages` columns is walked
    in."""
    n = max(1, chunk_tokens // page)
    return tuple((c, min(n, ctx_pages - c)) for c in range(0, ctx_pages, n))


def _walk_context(o, lse, ctx_len, chunks, page: int, attend):
    """Merge into (o, lse) the attention over a paged context of `ctx_len`
    [B] tokens, a static chunk of block-table columns at a time:
    `attend(first, n, have) -> (o, lse)` attends columns [first, first + n)
    of which row b has `have[b]` tokens. A chunk past every row's context
    runs nothing (a `cond`). Returns o."""
    for first, n in chunks:
        have = jnp.clip(ctx_len - first * page, 0, n * page)

        def step(o, lse, first=first, n=n, have=have):
            o2, lse2 = attend(first, n, have)
            return merge_attention(o, lse, o2, lse2, return_lse=True)

        o, lse = jax.lax.cond(jnp.any(have > 0), step,
                              lambda o, lse: (o, lse), o, lse)
    return o


@functools.partial(jax.jit, static_argnames=("scale", "impl"))
def _ctx_flash(q, k, v, q_lens, kv_lens, *, scale: float,
               impl: Optional[str]):
    """`_attn_lse` over one chunk of a paged context, under a name of its
    own: a call inside a `cond` would otherwise take the branch's name in a
    trace (`_mla_flash`)."""
    return _attn_lse(q, k, v, causal=False, scale=scale, q_lens=q_lens,
                     kv_lens=kv_lens, impl=impl)


def paged_prefill_attention(q: jax.Array, k_new: jax.Array,
                            v_new: jax.Array, kv_pages: jax.Array,
                            block_tables: jax.Array,
                            positions: jax.Array, total_lens: jax.Array,
                            *, ctx_pages: int = 0,
                            scale: Optional[float] = None,
                            impl: Optional[str] = None,
                            layer=None,
                            block_causal: int = 0) -> jax.Array:
    """Prefill attention: new tokens attend to themselves (causal) and to
    an optional cached prefix held in pages, merged by log-sum-exp.
    `block_causal` = B > 0: among themselves by blocks of B
    (`ops.attention.causal_mask`); a row starts on a block boundary (B
    divides the page or the engine aligns its passes), so the part over
    the prefix is what it was.

    q/k_new/v_new: [B, S, H*, D] — the new tokens, contiguous from each
    row's first position positions[:, 0] (the cached-prefix length, a
    multiple of page_size by the prefix-cache contract). ctx_pages is the
    STATIC number of block-table columns the prefix may span; 0 skips the
    prefix part entirely (no page reads at all). Rows whose prefix is
    shorter mask the tail; rows with no prefix mask everything. kv_pages
    is [L, P, Hkv, page, 2D] at `layer`, or [P, Hkv, page, 2D].

    A table wider than one flash call may hold resident
    (`FLASH_RESIDENT_KV_BYTES`) is walked in chunks of that size, as
    `latent_prefill_attention` walks a latent context: each chunk a gather
    and a flash call (`_ctx_flash`). A narrower one is one call over the
    table's width, the jaxpr it was.
    """
    d = q.shape[-1]
    scale_f = float(scale if scale is not None else d ** -0.5)
    ctx_len = positions[:, 0]                      # [B]
    # the new tokens a row really has: the rest of the bucket is padding
    # (its writes are dropped, its outputs thrown away), not computed
    n_new = jnp.clip(total_lens - ctx_len, 0, q.shape[1])
    o1, lse1 = _attn_lse(q, k_new, v_new, causal=True, scale=scale_f,
                         q_lens=n_new, impl=impl, block_causal=block_causal)
    if ctx_pages <= 0:
        return o1
    page, d2 = kv_pages.shape[-2:]
    chunks = ctx_chunks(ctx_pages, page, FLASH_RESIDENT_KV_BYTES // (
        2 * d2 * kv_pages.dtype.itemsize))
    if len(chunks) > 1:
        def attend(first, n, have):
            k, v = gather_kv(kv_pages, block_tables[:, first:first + n],
                             layer)
            return _ctx_flash(q, k, v, n_new, have, scale=scale_f, impl=impl)

        return _walk_context(o1, lse1, ctx_len, chunks, page, attend)
    bt = block_tables[:, :ctx_pages]
    k_ctx, v_ctx = gather_kv(kv_pages, bt, layer)  # [B, CP*page, Hkv, D]
    # the prefix is a LENGTH of the gathered columns: a row attends the
    # context it has, not the table's width
    o2, lse2 = _attn_lse(q, k_ctx, v_ctx, causal=False, scale=scale_f,
                         q_lens=n_new, kv_lens=ctx_len, impl=impl)
    return merge_attention(o1, lse1, o2, lse2)


# tokens of context whose keys and values a latent family's prefill
# materialises at once: 4096 x 64 heads x (192 + 128) x 2 bytes = 0.17 GB
# at the published sizes. The flash forward keeps a chunk's K and V
# resident and double-buffered, keys of 192 lanes padded to 256: 6 MB at
# 4096 rows; 8192 rows are 72 KB over the 16 MB a kernel gets unasked
# (compiled for a described v5e: tests/test_chip_compile.py)
LATENT_CTX_CHUNK = 4096


@functools.partial(jax.jit, static_argnames=("causal", "scale", "impl"))
def _mla_flash(q, k, v, q_lens, kv_lens, *, causal: bool, scale: float,
               impl: Optional[str]):
    """`_attn_lse` under a name of its own, as `_mla_decode`: the flash
    forward at keys wider than values, among a pass's own tokens and over
    each materialised chunk of its context (a call inside a `cond` would
    otherwise take the branch's name in a trace)."""
    return _attn_lse(q, k, v, causal=causal, scale=scale, q_lens=q_lens,
                     kv_lens=kv_lens, impl=impl)


def latent_prefill_attention(q: jax.Array, k_new: jax.Array,
                             v_new: jax.Array, kv_pages: jax.Array,
                             block_tables: jax.Array, positions: jax.Array,
                             total_lens: jax.Array, expand, *,
                             ctx_pages: int = 0, scale: float,
                             chunk_tokens: int = LATENT_CTX_CHUNK,
                             impl: Optional[str] = None,
                             layer=None) -> jax.Array:
    """Prefill attention of a latent family in its MATERIALISED form: the
    new tokens attend themselves causally (per-head keys `k_new` [B, S, H,
    Dk] and values `v_new` [B, S, H, Dv], Dv <= Dk) and, where `ctx_pages`
    > 0, the context their pages hold as LATENTS ([L, P, 1, page, lanes]).

    The context is walked in static chunks of `chunk_tokens`: a chunk's
    latent rows are gathered (5.2 MB at 4096 x 640), `expand(rows [B, T,
    lanes]) -> (k [B, T, H, Dk], v [B, T, H, Dv])` makes its per-head keys
    and values (one chunk's alive at a time), the queries attend the part
    of it their row has, and the partials merge by log-sum-exp. A chunk
    past every row's context runs nothing: neither gather, `expand` nor
    kernel. So a context is bounded by the pool, not by what a kernel
    keeps resident or by a [context x heads] array of the whole prompt.
    Lengths as `paged_prefill_attention` gives them to the kernel."""
    kv_pages, layer = _layered(kv_pages, layer)
    page, lanes = kv_pages.shape[-2:]
    b, s = q.shape[:2]
    ctx_len = positions[:, 0]
    n_new = jnp.clip(total_lens - ctx_len, 0, s)
    o, lse = _mla_flash(q, k_new, v_new, n_new, None, causal=True,
                        scale=scale, impl=impl)

    def attend(first, n, have):
        with tracing.scope("rtpu.attn.cache_write"):
            rows = kv_pages[layer, block_tables[:, first:first + n]]
        k, v = expand(rows.reshape(b, n * page, lanes))
        return _mla_flash(q, k, v, n_new, have, causal=False, scale=scale,
                          impl=impl)

    return _walk_context(o, lse, ctx_len,
                         ctx_chunks(ctx_pages, page, chunk_tokens), page,
                         attend)


def prefill_block_visits(s: int, ctx_width: int, n_new=None,
                         ctx_len=None, window=None) -> Tuple[int, int, int]:
    """What `paged_prefill_attention`'s two flash calls visit for one row,
    a layer and head, in plain integers (the engine's counters and its
    pass cost, on the host): ((query block, key block) visits, the (query,
    key) pairs in them, the visits that take the kernel's EDGE body) of
    `[s]` new tokens, `n_new` of them real, behind `ctx_len` of `ctx_width`
    gathered columns: the forward kernel's trip counts
    (`ops.flash_attention._fwd_trips`) summed over its query blocks.
    Lengths None: what the shapes alone would make. `ctx_width` 0: the
    program without a context part. An edge visit pays the mask: a query
    block's tile on the causal diagonal, and over the context the tile
    that holds the end of the row's keys inside it; the rest run bare.
    `window`: a layer with a sliding window (`window_prefill_attention`):
    its context part is the slot's ring (`window` columns wherever
    `ctx_width` > 0, min(`ctx_len`, window) of them real), its key loops
    start at the band and the tiles the band's lower edge crosses are edge
    visits too."""
    from .flash_attention import (BLOCK, _band_offset, _band_trips,
                                  _fwd_trips, _pick_blocks)

    if window is not None and ctx_width:
        ctx_width = window
        ctx_len = window if ctx_len is None else min(ctx_len, window)
    bq, bk = _pick_blocks(s, ctx_width or s, BLOCK, BLOCK)
    qblks = np.arange(-(-s // bq))
    visits = pairs = masked = 0
    # the causal call among the new tokens, then the one over the context
    for sk, block_k, causal, kv_len in ((s, bq, True, None),
                                        (ctx_width, bk, False, ctx_len)):
        if sk:
            interior, every = _fwd_trips(
                qblks, bq=bq, block_k=block_k, sq=s, sk=sk, causal=causal,
                have_segs=False, q_len=n_new, kv_len=kv_len, block_causal=0,
                xp=np)
            first = 0
            if window is not None:
                first, below = _band_trips(
                    qblks, bq=bq, block_k=block_k, window=window,
                    woff=_band_offset(causal, s, sk, kv_len), xp=np)
                first = np.minimum(first, every)
                below = np.clip(below, first, every)
                # the bare tiles are those between the two edges
                interior = np.clip(interior, below, every) - below
            # (a count no query block decides comes back as one integer)
            every, interior = (int(np.broadcast_to(n, qblks.shape).sum())
                               for n in (every - first, interior))
            visits, pairs = visits + every, pairs + every * bq * block_k
            masked += every - interior
    return visits, pairs, masked
