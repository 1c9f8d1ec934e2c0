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
  head (32 KB contiguous for an 8-head, page-16, D-64 model) — the decode
  kernel's streaming unit. K/V interleaving also makes the slice's last dim
  ``2*D`` (128 for head_dim-64 models), satisfying Mosaic's 128-lane
  slice alignment, which a split K/V pool with D=64 cannot.
- ``paged_write`` puts new tokens into their pages by whole pages: read
  the pages a row touches, select the new rows in, scatter the pages back
  (pure XLA, static shapes, untouched pages dropped). A whole page is the
  decode kernel's DMA unit, so the scatter keeps the pool in the row-major
  layout the kernel demands; a per-token scatter makes XLA re-lay the
  whole pool out around every write.
- ``paged_attention_decode`` is a Pallas kernel for the single-token step:
  it builds an in-kernel work list of (sequence, page-chunk) items, then
  streams ONLY the used pages HBM->VMEM with double-buffered async copies
  while accumulating a flash-style online softmax across all heads at
  once. Two tricks keep the vector path free of sub-tile lane slices:
  queries are zero-padded to ``[Hq, 2*D]`` so ``q_pad @ kv^T`` computes
  q·k exactly (the V lanes multiply zeros), and the accumulator runs over
  the full ``2*D`` lanes with the V half sliced once at finalize. The
  gather-free design is what moves decode from O(max_pages) HBM traffic
  (plus a GQA broadcast) to O(used pages).
- ``paged_prefill_attention`` splits prefill into (1) causal flash
  attention among the new tokens themselves — no page reads at all — and
  (2) segment-masked flash attention over the cached prefix pages, merged
  by log-sum-exp. Rows without a cached prefix mask part (2) entirely.
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

NEG_INF = -1e30


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
                   page: int, chunk: int, scale: float):
    """Single-program decode kernel (grid=()): one flattened work list of
    (sequence, page-chunk) items, double-buffered page DMAs, all kv heads
    per item. `kv_hbm` is the whole [L, P, Hkv, page, 2D] pool; only pages
    of layer `layer_ref[0]` are streamed.

    A single program (rather than a grid) keeps ONE uninterrupted DMA
    pipeline across every sequence — per-program warm-up latency would
    otherwise be paid per grid step. v5e has one TensorCore per chip, so
    there is no grid parallelism to lose. All heads ride one item because
    a page holds every head's K/V contiguously — B*chunks items total,
    not B*chunks*Hkv.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_b = lengths_ref.shape[0]
    hkv = kv_hbm.shape[2]
    layer = layer_ref[0]
    bk = chunk * page                              # kv rows per work item
    hq, d2 = q_ref.shape[1], q_ref.shape[2]
    d = d2 // 2
    rep = hq // hkv

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

    def page_dma(t, slot, j):
        """The j-th page copy of item t into buffer `slot` (descriptors
        are rebuilt at wait time — the semaphore carries the completion
        state, not the Python object)."""
        b, c = work_b[t], work_c[t]
        p = bt_ref[b, c * chunk + j]
        return pltpu.make_async_copy(
            kv_hbm.at[layer, p], kv_buf.at[slot, j], sems.at[slot])

    def n_pages_of(t):
        b, c = work_b[t], work_c[t]
        return pl.cdiv(lengths_ref[b], page) - c * chunk  # pages this item

    def start_item(t, slot):
        live = n_pages_of(t)
        for j in range(chunk):
            @pl.when(j < live)
            def _():
                page_dma(t, slot, j).start()

    def wait_item(t, slot):
        live = n_pages_of(t)
        for j in range(chunk):
            @pl.when(j < live)
            def _():
                page_dma(t, slot, j).wait()

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
        length = lengths_ref[b]
        # zero-padded q: lanes [D:] are 0, so q_pad @ kv^T == q @ k^T
        # (the V lanes of every kv row multiply zeros)
        q_pad = q_ref[b]                           # [Hq, 2D]
        row_pos = c * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        # stale rows (never DMA'd on a short final chunk) can hold
        # non-finite garbage; zero them so 0-weighted rows stay 0 in the
        # accumulator matmul (0 * NaN would poison it)
        s_heads = []
        for h in range(hkv):
            # [chunk, page, 2D] -> [bk, 2D]: page is a whole sublane
            # tile, so the merge is layout-preserving
            kv_h = kv_buf[slot, :, h].reshape(bk, d2)
            kv_h = jnp.where(row_pos < length, kv_h, 0)       # [bk, 2D]
            s_heads.append((kv_h, jax.lax.dot_general(
                q_pad[h * rep:(h + 1) * rep], kv_h,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)))          # [rep, bk]
        s = jnp.concatenate([sh for _, sh in s_heads], axis=0) * scale
        mask = (row_pos < length).reshape(1, bk)
        s = jnp.where(mask, s, NEG_INF)            # [Hq, bk]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        m = m_new
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.concatenate([
            jax.lax.dot_general(
                p[h * rep:(h + 1) * rep].astype(kv_h.dtype), kv_h,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            for h, (kv_h, _) in enumerate(s_heads)], axis=0)   # [Hq, 2D]
        acc = acc * alpha + pv

        # finalize when the NEXT item is a different sequence
        t_next = jnp.minimum(t + 1, work_b.shape[0] - 1)
        is_last = jnp.logical_or(t + 1 >= n_items, work_b[t_next] != b)

        @pl.when(is_last)
        def _():
            # the K half of acc (lanes [:D]) is discarded here — it cost
            # nothing extra: 2D lanes is one MXU tile for D=64 anyway
            o_ref[b] = (acc[:, d:] / l).astype(o_ref.dtype)

        m = jnp.where(is_last, jnp.full_like(m, NEG_INF), m)
        l = jnp.where(is_last, jnp.zeros_like(l), l)
        acc = jnp.where(is_last, jnp.zeros_like(acc), acc)
        return m, l, acc

    m0 = jnp.full((hq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((hq, 1), jnp.float32)
    acc0 = jnp.zeros((hq, d2), jnp.float32)
    _fori_no_unroll(0, n_items, body, (m0, l0, acc0))


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_chunk",
                                             "interpret"))
def _decode_call(q, kv_pages, block_tables, lengths, layer, *,
                 scale: float, pages_per_chunk: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hq, d = q.shape
    _, _, hkv, page, d2 = kv_pages.shape
    chunk = pages_per_chunk
    mp = block_tables.shape[1]
    max_chunks = -(-mp // chunk)
    q_pad = jnp.pad(q, ((0, 0), (0, 0), (0, d2 - d)))

    kernel = functools.partial(
        _decode_kernel, page=page, chunk=chunk, scale=scale)
    out = pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),      # lengths [B]
            pl.BlockSpec(memory_space=pltpu.SMEM),      # block_tables
            pl.BlockSpec(memory_space=pltpu.SMEM),      # layer [1]
            pl.BlockSpec(memory_space=pltpu.VMEM),      # q (zero-padded)
            # explicitly HBM (not ANY): the compiler would happily place
            # a small page pool in VMEM, where per-page slices violate
            # tile alignment — and the pool must not eat VMEM anyway.
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, hkv, page, d2), kv_pages.dtype),
            pltpu.SMEM((b * max_chunks,), jnp.int32),
            pltpu.SMEM((b * max_chunks,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q_pad, kv_pages)
    return out


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


def paged_attention_decode(q: jax.Array, kv_pages: jax.Array,
                           block_tables: jax.Array, lengths: jax.Array, *,
                           layer=None,
                           scale: Optional[float] = None,
                           pages_per_chunk: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           force_reference: bool = False) -> jax.Array:
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
        # target ~128 kv rows per work item (one MXU-friendly tile)
        pages_per_chunk = max(1, min(block_tables.shape[1],
                                     -(-128 // page)))
    pages_per_chunk = min(pages_per_chunk, block_tables.shape[1])
    kv_pages, layer = _layered(kv_pages, layer)
    return _decode_call(q, kv_pages, block_tables, lengths, layer,
                        scale=scale_f, pages_per_chunk=pages_per_chunk,
                        interpret=bool(interpret))


# --------------------------------------------------------- prefill (+ctx)
def _attn_lse(q, k, v, *, causal, segment_ids, scale, impl=None):
    """Attention returning (o [B,S,Hq,D], lse [B,S,Hq]).

    impl: None = flash kernel on a TPU backend, jnp reference on a CPU
    backend (`JAX_PLATFORMS=cpu`); "flash" forces the Pallas kernel
    (interpreter mode on a CPU backend); "reference" forces the jnp path.
    Both parts of a merged prefill go through the SAME implementation so
    their lse scales match exactly.
    """
    if impl == "flash" or (impl is None and jax.default_backend() == "tpu"):
        from .flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal,
                               segment_ids=segment_ids, scale=scale,
                               return_lse=True)
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    rep = hq // hkv
    qg = q.reshape(b, sq, hkv, rep, d)
    logits = jnp.einsum("bshrd,bkhd->bhrsk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    if segment_ids is not None:
        q_seg, kv_seg = segment_ids
        seg = q_seg[:, None, None, :, None] == kv_seg[:, None, None, None, :]
        logits = jnp.where(seg, logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhrsk,bkhd->bshrd", (p / l_safe).astype(v.dtype), v)
    lse = (m + jnp.log(l_safe))[..., 0]            # [B,Hkv,rep,S]
    return (o.reshape(b, sq, hq, d),
            lse.reshape(b, hq, sq).transpose(0, 2, 1))


def merge_attention(o1: jax.Array, lse1: jax.Array,
                    o2: jax.Array, lse2: jax.Array) -> jax.Array:
    """Combine two attention partials over disjoint kv sets by their
    log-sum-exp. o*: [B,S,H,D]; lse*: [B,S,H]."""
    m = jnp.maximum(lse1, lse2)
    a1 = jnp.exp(lse1 - m)
    a2 = jnp.exp(lse2 - m)
    denom = a1 + a2
    w1 = (a1 / denom)[..., None]
    w2 = (a2 / denom)[..., None]
    return (o1.astype(jnp.float32) * w1
            + o2.astype(jnp.float32) * w2).astype(o1.dtype)


def paged_prefill_attention(q: jax.Array, k_new: jax.Array,
                            v_new: jax.Array, kv_pages: jax.Array,
                            block_tables: jax.Array,
                            positions: jax.Array, total_lens: jax.Array,
                            *, ctx_pages: int = 0,
                            scale: Optional[float] = None,
                            impl: Optional[str] = None,
                            layer=None) -> jax.Array:
    """Prefill attention: new tokens attend to themselves (causal) and to
    an optional cached prefix held in pages, merged by log-sum-exp.

    q/k_new/v_new: [B, S, H*, D] — the new tokens, contiguous from each
    row's first position positions[:, 0] (the cached-prefix length, a
    multiple of page_size by the prefix-cache contract). ctx_pages is the
    STATIC number of block-table columns the prefix may span; 0 skips the
    prefix part entirely (no page reads at all). Rows whose prefix is
    shorter mask the tail; rows with no prefix mask everything. kv_pages
    is [L, P, Hkv, page, 2D] at `layer`, or [P, Hkv, page, 2D].
    """
    d = q.shape[-1]
    scale_f = float(scale if scale is not None else d ** -0.5)
    o1, lse1 = _attn_lse(q, k_new, v_new, causal=True, segment_ids=None,
                         scale=scale_f, impl=impl)
    if ctx_pages <= 0:
        return o1
    page = kv_pages.shape[-2]
    bt = block_tables[:, :ctx_pages]
    k_ctx, v_ctx = gather_kv(kv_pages, bt, layer)  # [B, CP*page, Hkv, D]
    b, sq = q.shape[:2]
    ctx_len = positions[:, 0]                      # [B]
    kv_pos = jnp.arange(ctx_pages * page)
    kv_seg = (kv_pos[None, :] < ctx_len[:, None]).astype(jnp.int32)
    q_seg = jnp.ones((b, sq), jnp.int32)
    o2, lse2 = _attn_lse(q, k_ctx, v_ctx, causal=False,
                         segment_ids=(q_seg, kv_seg), scale=scale_f,
                         impl=impl)
    return merge_attention(o1, lse1, o2, lse2)
