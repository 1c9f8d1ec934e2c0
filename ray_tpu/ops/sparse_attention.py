"""Block-sparse attention over paged KV (InfLLM-v2 style selection).

A query at position t, for each kv-head group (its `rep` query heads),
attends to the keys at positions <= t of a SELECTED set of blocks of
`block` tokens, chosen from compressed keys:

    Kc_j        = mean(K[stride*j : stride*j + kernel])     one a `stride`
    r_t^h[j]    = softmax_j(q_t^h . Kc_j * scale)   over the kernels that
                  END at or before t (stride*j + kernel <= t + 1)
    R_t[j]      = sum of r_t^h[j] over the group's heads
    score_t[b]  = max of R_t[j] over the kernels that overlap block b
    selected    = the first `init_blocks` blocks, the blocks of the last
                  `window` positions, the query's own block, and the
                  `topk` best-scored of the rest

A query at a position under `dense_len` attends to every key before it.
The rule is a query's own (not a call's): a full forward, a prefill in
passes and a decode step select the same set for the same position.

The engine's page IS a block (`block == page_size`; the model refuses
anything else), so what a sequence keeps beside its pages is one more
array indexed BY PAGE ID, `kc` [layers, P, Hkv, block/stride, D]: kernel j
lives in the page of its first key, slot j % (block/stride). A page that
goes back to the allocator takes its compressed keys with it; a slot is
read only for kernels that are complete at the query's position, and those
were written since the page was handed out.

Entry points, each ONE jitted wrapper (PERF.md section 3, "names in a
trace"), all plain XLA around the paged-decode kernel:

- `compress_keys` (`_sparse_compress`): after new keys are in their pages,
  (re)compute the kernels they complete, from the pages.
- `sparse_decode` (`_sparse_select` + the paged-decode path): one query a
  row. Scores the row's compressed keys, picks the blocks, and hands the
  decode kernel a SELECTED block table a (row, kv head): the pool viewed
  as [L, P*Hkv, 1, page, 2D], page id * Hkv + head, so a DMA moves one
  head's page and nothing of the context that was not selected is read.
- `sparse_prefill` (`_sparse_prefill`): a row of new queries over the new
  tokens AND the cached context, all read from pages (the new tokens'
  K/V are written first). A tile of queries walks the context in chunks
  with an online softmax; a score array is [tile x chunk], never [queries
  x context]. The selection is applied as a mask a (query, group, block),
  so the result is the selected-set attention exactly; the products are a
  dense pass's (what a kernel that skips unselected blocks would save is
  PERF.md section 7's).

`keys_attended` / `kernels_scored` are the same rule on the host (numpy),
for the engine's counters.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .paged_attention import NEG_INF, paged_attention_decode


class SparseParams(NamedTuple):
    kernel: int = 32       # keys a compressed key is the mean of
    stride: int = 16       # keys between two kernels' starts
    block: int = 64        # tokens a selected block holds (== page size)
    init_blocks: int = 1
    window: int = 2048     # the last positions, always attended
    topk: int = 64
    dense_len: int = 8192  # positions under it attend densely

    @property
    def kpb(self) -> int:
        """Kernels that START in a block."""
        return self.block // self.stride

    @property
    def overlap(self) -> int:
        """Kernels starting in the block before that reach into a block."""
        return -(-self.kernel // self.stride) - 1

    def check(self) -> None:
        if self.block % self.stride or self.kernel % self.stride:
            raise ValueError(f"{self}: block and kernel must be whole "
                             f"numbers of strides")
        if not 0 < self.overlap <= self.kpb or self.kernel > self.window:
            raise ValueError(f"{self}: a kernel must fit in a block's "
                             f"strides and in the window")

    def table_width(self, max_pages: int) -> int:
        """Columns of a selected block table: the most blocks any query
        attends (forced + topk, or every block under dense_len)."""
        forced = self.init_blocks + self.window // self.block + 1
        dense = -(-self.dense_len // self.block)
        return min(max_pages, max(forced + self.topk, dense))


# ------------------------------------------------------------ the selection
def _block_scores(rel, sp: SparseParams):
    """rel [..., NB * kpb] (kernel j's relevance, 0 where it is not valid)
    -> [..., NB]: a block's score, the max over the kernels that overlap
    it (its own kpb, and the last `overlap` of the block before)."""
    kpb = sp.kpb
    r4 = rel.reshape(rel.shape[:-1] + (-1, kpb))
    score = r4.max(-1)
    for o in range(1, sp.overlap + 1):
        prev = jnp.pad(r4[..., :-1, kpb - o],
                       [(0, 0)] * (r4.ndim - 2) + [(1, 0)])
        score = jnp.maximum(score, prev)
    return score


def _select(rel, t, sp: SparseParams):
    """rel [Q, G, NK] group relevance, t [Q] positions -> selected
    [Q, G, NB] bool (NB = NK / kpb)."""
    score = _block_scores(rel, sp)
    nb = score.shape[-1]
    b = jnp.arange(nb)
    own = (t // sp.block)[:, None, None]
    w0 = (jnp.maximum(t - (sp.window - 1), 0) // sp.block)[:, None, None]
    forced = (b < sp.init_blocks) | (b >= w0)
    cand = ~forced
    # by INDEX, not by a threshold: a kernel that straddles two blocks
    # gives both the same score, and `top_k` breaks a tie by the lower
    # index, so exactly topk blocks join (the table's width counts on it)
    best, idx = jax.lax.top_k(jnp.where(cand, score, -1.0),
                              min(sp.topk, nb))
    idx = jnp.where(best >= 0.0, idx, nb)           # not a candidate: none
    sel = forced | (idx[..., None] == b).any(-2)
    sel = jnp.where((t < sp.dense_len)[:, None, None], True, sel)
    return sel & (b <= own)


def _relevance(q, kc_rows, t, sp: SparseParams, scale: float):
    """q [Q, G, rep, D], kc_rows [Q or 1, G, NK, D], t [Q] -> [Q, G, NK]
    float32: the softmax over the valid kernels a head, summed over the
    group's heads."""
    if kc_rows.shape[0] == 1:      # one row's kernels for every query
        logits = jnp.einsum("qgrd,gkd->qgrk", q, kc_rows[0],
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("qgrd,qgkd->qgrk", q, kc_rows,
                            preferred_element_type=jnp.float32)
    nk = kc_rows.shape[2]
    valid = (jnp.arange(nk) * sp.stride + sp.kernel
             <= t[:, None] + 1)[:, None, None, :]
    p = jax.nn.softmax(jnp.where(valid, logits * scale, NEG_INF), axis=-1)
    return jnp.where(valid, p, 0.0).sum(2)


def _kc_rows(kc, block_tables, layer):
    """kc [L, P, G, kpb, D] at `layer` through block_tables [B, MP] ->
    [B, G, MP * kpb, D]: kernel j of a row at index j."""
    rows = kc[layer, block_tables]                     # [B, MP, G, kpb, D]
    b, mp, g, kpb, d = rows.shape
    return rows.transpose(0, 2, 1, 3, 4).reshape(b, g, mp * kpb, d)


# ------------------------------------------------------- compressed keys
def compress_keys(kv_pages, kc, block_tables, start, end, layer, *,
                  new_tokens: int, sp: SparseParams):
    """Write the compressed keys that the tokens [start, end) of each row
    complete. kv_pages [L, P, G, page, 2D] with those tokens' keys already
    in their pages; kc [L, P, G, kpb, D]; block_tables [B, MP]; start, end
    [B] (end <= start: nothing); `new_tokens`: the most a row brings (a
    static bound). A kernel is written when its LAST key is new and all
    its keys exist: stride*j + kernel - 1 >= start, stride*j + kernel <=
    end. It is computed from the pages, so one pass over a prompt and
    several passes write the same bits."""
    n_pg = (new_tokens + sp.kernel - 2) // sp.block + 2
    return _sparse_compress(kv_pages, kc, block_tables,
                            start.astype(jnp.int32), end.astype(jnp.int32),
                            jnp.asarray(layer, jnp.int32), n_pg=n_pg, sp=sp)


@functools.partial(jax.jit, static_argnames=("n_pg", "sp"))
def _sparse_compress(kv_pages, kc, block_tables, start, end, layer, *,
                     n_pg: int, sp: SparseParams):
    f32 = jnp.float32
    _, num_pages, g, page, d2 = kv_pages.shape
    d = d2 // 2
    b, mp = block_tables.shape
    kpb, r = sp.kpb, sp.kernel // sp.stride
    p0 = jnp.maximum(start - (sp.kernel - 1), 0) // page       # [B]
    lp = p0[:, None] + jnp.arange(n_pg)                        # [B, n_pg]
    pg = jnp.take_along_axis(block_tables, jnp.minimum(lp, mp - 1), axis=1)
    keys = kv_pages[layer, pg][..., :d].astype(f32)  # [B, n_pg, G, page, D]
    groups = keys.reshape(b, n_pg, g, kpb, sp.stride, d).sum(4)
    groups = groups.transpose(0, 1, 3, 2, 4).reshape(b, n_pg * kpb, g, d)
    padded = jnp.pad(groups, ((0, 0), (0, r - 1), (0, 0), (0, 0)))
    comp = sum(padded[:, o:o + n_pg * kpb] for o in range(r)) / sp.kernel
    i = jnp.arange(n_pg * kpb)
    first = (p0[:, None] * kpb + i) * sp.stride           # kernel's 1st key
    valid = ((first + sp.kernel - 1 >= start[:, None])
             & (first + sp.kernel <= end[:, None])
             & (i + r <= n_pg * kpb)
             & jnp.repeat(lp < mp, kpb, axis=1))               # [B, n_pg*kpb]
    comp = comp.reshape(b, n_pg, kpb, g, d).transpose(0, 1, 3, 2, 4)
    valid = valid.reshape(b, n_pg, 1, kpb, 1)
    ids = jnp.where(valid.any((2, 3, 4)), pg, num_pages)   # OOB: dropped
    old = kc[layer, jnp.minimum(ids, num_pages - 1)]
    new = jnp.where(valid, comp.astype(kc.dtype), old)
    return kc.at[layer, ids].set(new, mode="drop")


# ------------------------------------------------------------------ decode
def sparse_decode(q, kv_pages, kc, block_tables, lengths, layer, *,
                  sp: SparseParams, scale: float,
                  force_reference: bool = False):
    """One query a row over its selected blocks. q [B, Hq, D] (the newest
    token, its K/V and compressed key already written); kv_pages [L, P, G,
    page, 2D]; kc [L, P, G, kpb, D]; block_tables [B, MP]; lengths [B]
    (0: an idle row, zero output). -> ([B, Hq, D], the selected blocks
    [B, G, MP] bool, for whoever wants to look: nothing computes them
    twice, and unread they cost nothing)."""
    b, hq, d = q.shape
    g = kv_pages.shape[2]
    table, sel_len, sel = _sparse_select(
        q, kc, block_tables, lengths.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32), sp=sp, scale=float(scale), groups=g)
    # one head's pages a row: [L, P, G, page, 2D] -> [L, P*G, 1, page, 2D]
    lyr, num_pages, _, page, d2 = kv_pages.shape
    pool = kv_pages.reshape(lyr, num_pages * g, 1, page, d2)
    out = paged_attention_decode(
        q.reshape(b * g, hq // g, d), pool, table, sel_len, layer=layer,
        scale=scale, force_reference=force_reference)
    return out.reshape(b, hq, d), sel


@functools.partial(jax.jit, static_argnames=("sp", "scale", "groups"))
def _sparse_select(q, kc, block_tables, lengths, layer, *, sp: SparseParams,
                   scale: float, groups: int):
    """-> (table [B*G, W] ids into the pool viewed a head a page, selected
    blocks in order, the query's own block last; sel_len [B*G] the tokens
    that table holds for the row: whole blocks and the own block's part;
    the selection itself [B, G, MP] bool)."""
    b, hq, d = q.shape
    g, mp = groups, block_tables.shape[1]
    t = jnp.maximum(lengths - 1, 0)
    rel = _relevance(q.reshape(b, g, hq // g, d),
                     _kc_rows(kc, block_tables, layer), t, sp, scale)
    sel = _select(rel, t, sp)                                  # [B, G, MP]
    w = sp.table_width(mp)
    idx = jnp.argsort(jnp.logical_not(sel), axis=-1, stable=True)[..., :w]
    n_sel = sel.sum(-1).astype(jnp.int32)                      # [B, G]
    pages = jnp.take_along_axis(
        jnp.broadcast_to(block_tables[:, None], (b, g, mp)), idx, axis=-1)
    table = pages * g + jnp.arange(g)[None, :, None]
    table = jnp.where(jnp.arange(w) < n_sel[..., None], table, 0)
    sel_len = jnp.where(
        lengths[:, None] > 0,
        (n_sel - 1) * sp.block + (t % sp.block)[:, None] + 1, 0)
    return (table.reshape(b * g, w).astype(jnp.int32),
            sel_len.reshape(b * g).astype(jnp.int32), sel)


# ----------------------------------------------------------------- prefill
def sparse_prefill(q, kv_pages, kc, block_table, start, end, layer, *,
                   sp: SparseParams, scale: float, q_tile: int = 512,
                   kv_pages_chunk: int = 32):
    """One row of new queries over everything in its pages. q [S, Hq, D]
    at positions start + [0, S); kv_pages / kc as in `sparse_decode`, the
    new tokens' K/V and compressed keys already written; block_table
    [MP]; `end`: the row's length including the new tokens (queries past
    it are padding: computed or not, never read). -> ([S, Hq, D], the
    selected blocks [S, G, MP] bool, as `sparse_decode` hands them)."""
    s = q.shape[0]
    q_tile = min(q_tile, s)
    pad = (-s) % q_tile     # a last tile's padding is past `end`
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    out, sel = _sparse_prefill(
        q, kv_pages, kc, block_table, jnp.asarray(start, jnp.int32),
        jnp.minimum(jnp.asarray(end, jnp.int32), start + s),
        jnp.asarray(layer, jnp.int32), sp=sp, scale=float(scale),
        q_tile=q_tile, ppc=min(kv_pages_chunk, block_table.shape[0]))
    return out[:s], sel[:s]


@functools.partial(jax.jit, static_argnames=("sp", "scale", "q_tile", "ppc"))
def _sparse_prefill(q, kv_pages, kc, block_table, start, end, layer, *,
                    sp: SparseParams, scale: float, q_tile: int, ppc: int):
    f32 = jnp.float32
    s, hq, d = q.shape
    g, page = kv_pages.shape[2], kv_pages.shape[3]
    rep = hq // g
    mp = block_table.shape[0]
    n_chunks = -(-mp // ppc)
    bt = jnp.pad(block_table, (0, n_chunks * ppc - mp))
    tk = ppc * page
    kc_row = _kc_rows(kc, block_table[None], layer)         # [1, G, NK, D]
    blocks = jnp.arange(mp)

    def tile(i, outs):
        out, picked = outs
        t = start + i * q_tile + jnp.arange(q_tile)             # [TQ]
        qt = jax.lax.dynamic_slice_in_dim(q, i * q_tile, q_tile, 0)
        qt = qt.reshape(q_tile, g, rep, d)
        sel = jax.lax.cond(
            t[-1] < sp.dense_len,
            lambda: jnp.broadcast_to(
                (blocks <= (t // sp.block)[:, None])[:, None], (q_tile, g, mp)),
            lambda: _select(_relevance(qt, kc_row, t, sp, scale), t, sp))
        picked = jax.lax.dynamic_update_slice_in_dim(picked, sel,
                                                     i * q_tile, 0)
        sel = jnp.pad(sel, ((0, 0), (0, 0), (0, n_chunks * ppc - mp)))

        def chunk(c, carry):
            m, l, acc = carry
            pages = jax.lax.dynamic_slice_in_dim(bt, c * ppc, ppc)
            kv = kv_pages[layer, pages]                  # [ppc, G, page, 2D]
            kv = kv.transpose(1, 0, 2, 3).reshape(g, tk, 2 * d)
            kpos = c * tk + jnp.arange(tk)
            keep = jnp.repeat(
                jax.lax.dynamic_slice_in_dim(sel, c * ppc, ppc, 2), page,
                axis=2) & (kpos[None, None, :] <= t[:, None, None])
            sc = jnp.einsum("qgrd,gkd->qgrk", qt, kv[..., :d],
                            preferred_element_type=f32) * scale
            sc = jnp.where(keep[:, :, None, :], sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(-1))
            p = jnp.where(keep[:, :, None, :],
                          jnp.exp(sc - m_new[..., None]), 0.0)
            a = jnp.exp(m - m_new)
            l = a * l + p.sum(-1)
            acc = a[..., None] * acc + jnp.einsum(
                "qgrk,gkd->qgrd", p.astype(kv.dtype), kv[..., d:],
                preferred_element_type=f32)
            return m_new, l, acc

        last = jnp.minimum(jnp.minimum(t[-1], end - 1) // tk + 1, n_chunks)
        m, l, acc = jax.lax.fori_loop(0, last, chunk, (
            jnp.full((q_tile, g, rep), NEG_INF, f32),
            jnp.zeros((q_tile, g, rep), f32),
            jnp.zeros((q_tile, g, rep, d), f32)))
        o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
        return jax.lax.dynamic_update_slice_in_dim(
            out, o.reshape(q_tile, hq, d).astype(q.dtype), i * q_tile,
            0), picked

    n_tiles = jnp.clip(-(-(end - start) // q_tile), 0, s // q_tile)
    return jax.lax.fori_loop(0, n_tiles, tile, (
        jnp.zeros_like(q), jnp.zeros((s, g, mp), bool)))


# ------------------------------------------------------- the rule, on a host
def keys_attended(t, sp: SparseParams) -> np.ndarray:
    """Keys a query at position t (numpy, any shape) attends to, a kv-head
    group: every key up to t under dense_len, else the selected blocks'
    (whole blocks, and the own block up to t)."""
    t = np.asarray(t, np.int64)
    own = t // sp.block
    w0 = np.maximum(t - (sp.window - 1), 0) // sp.block
    forced = np.minimum(sp.init_blocks, w0) + (own - w0 + 1)
    rest = np.minimum(np.maximum(w0 - sp.init_blocks, 0), sp.topk)
    sparse = (forced + rest - 1) * sp.block + t % sp.block + 1
    return np.where(t < sp.dense_len, t + 1, sparse)


def kernels_scored(t, sp: SparseParams) -> np.ndarray:
    """Compressed keys a query at position t scores, a kv-head group (none
    under dense_len: no selection is made)."""
    t = np.asarray(t, np.int64)
    n = np.maximum((t + 1 - sp.kernel) // sp.stride + 1, 0)
    return np.where(t < sp.dense_len, 0, n)
