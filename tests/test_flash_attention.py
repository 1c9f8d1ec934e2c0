"""Flash-attention kernel parity vs the jnp reference (interpret mode on CPU).

Mirrors how the reference project validates numerics-by-parity in its op
tests; the kernel itself has no counterpart in the reference (it delegates
attention to external engines, SURVEY.md §2.4).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.flash_attention import NEG_INF, flash_attention
from ray_tpu.ops.paged_attention import merge_attention

from _engines import jitted

# (`flash_traced`: the call itself, for the tests that read its jaxpr)
flash_traced = flash_attention

# tiny-but-unaligned shapes exercise the padding paths; interpret mode is slow
B, D = 2, 32


def _make(sq, sk, hq=4, hkv=2, dtype=jnp.float32, seed=0, d=D):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, sq, hq, d), dtype)
    k = jax.random.normal(ks[1], (B, sk, hkv, d), dtype)
    v = jax.random.normal(ks[2], (B, sk, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sq,sk", [(128, 128), (64, 192), (200, 200)])
def test_forward_parity(causal, sq, sk):
    q, k, v = _make(sq, sk)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_forward_parity_mha_no_gqa():
    q, k, v = _make(128, 128, hq=4, hkv=4)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_forward_segment_ids_packed():
    sq = 128
    q, k, v = _make(sq, sq)
    # two packed sequences per row
    segs = jnp.concatenate(
        [jnp.zeros((B, sq // 2), jnp.int32), jnp.ones((B, sq - sq // 2), jnp.int32)],
        axis=1)
    got = flash_attention(q, k, v, causal=True, segment_ids=segs,
                          interpret=True)
    want = reference_attention(q, k, v, causal=True, segment_ids=segs)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_forward_segment_ids_tuple_decode():
    # chunked prefill: 32 query tokens attend to a 96-long kv axis
    sq, sk = 32, 96
    q, k, v = _make(sq, sk)
    kv_seg = jnp.concatenate(
        [jnp.zeros((B, 48), jnp.int32), jnp.ones((B, 48), jnp.int32)], axis=1)
    q_seg = kv_seg[:, -sq:]
    got = flash_attention(q, k, v, causal=True, segment_ids=(q_seg, kv_seg),
                          interpret=True)
    want = reference_attention(q, k, v, causal=True,
                               segment_ids=(q_seg, kv_seg))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# ------------------------------------------------ the forward's tile classes
def _visible(sq, sk, *, causal, block_causal=0, q_lens=None, kv_lens=None,
             segs=None):
    """[B, sq, sk] bool, the pairs `flash_attention`'s docstring admits, and
    [B, sq] bool, the query rows that are real."""
    qi, kj = np.arange(sq)[:, None], np.arange(sk)[None, :]
    see = np.ones((B, sq, sk), bool)
    if causal:
        blk = block_causal or 1
        see &= (kj // blk <= (qi + sk - sq) // blk)[None]
    real = np.ones((B, sq), bool)
    if kv_lens is not None:
        see &= kj[None] < np.asarray(kv_lens)[:, None, None]
    if q_lens is not None:
        real = np.arange(sq)[None] < np.asarray(q_lens)[:, None]
    if segs is not None:
        see &= (np.asarray(segs[0])[:, :, None]
                == np.asarray(segs[1])[:, None, :])
    return see, real


@jitted
def _masked_reference(q, k, v, see):
    """(o, lse) of plain softmax attention over the pairs `see` admits,
    float32 throughout; a row that sees nothing gives 0 / NEG_INF."""
    rep = q.shape[2] // k.shape[2]
    kr, vr = (jnp.repeat(x, rep, axis=2) for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * q.shape[-1] ** -0.5
    logits = jnp.where(see[:, None], logits, -jnp.inf)
    lse = jax.nn.logsumexp(logits, axis=-1)                    # [B,H,Sq]
    p = jnp.exp(logits - jnp.where(jnp.isfinite(lse), lse, 0.0)[..., None])
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vr)
    return o, jnp.where(jnp.isfinite(lse), lse, NEG_INF).transpose(0, 2, 1)


def _assert_real_rows_match(o, lse, q, k, v, see, real):
    """`o` and `lse` of the real query rows against `_masked_reference`."""
    want_o, want_lse = _masked_reference(q, k, v, see)
    rows = np.broadcast_to(real[:, :, None], lse.shape)
    for got, want in ((o, want_o), (lse, want_lse)):
        np.testing.assert_allclose(np.asarray(got)[rows],
                                   np.asarray(want)[rows],
                                   atol=2e-5, rtol=2e-5)


def _two_segments(n, cut):
    return jnp.tile((jnp.arange(n) >= cut).astype(jnp.int32)[None], (B, 1))


# the classes a (query block, key block) tile of the forward falls in, in
# tiles of 128 x 128: name -> (sq, sk, keyword arguments of the call)
TILE_CASES = {
    "interior-only-not-causal": (128, 384, dict(causal=False)),
    "diagonal-3x3": (384, 384, dict(causal=True)),
    "offset-diagonal-2x4": (256, 512, dict(causal=True)),
    "offset-inside-a-tile-and-a-key-tail": (128, 320, dict(causal=True)),
    "key-tail-not-causal": (256, 300, dict(causal=False)),
    "kv-lens-mid-block-and-on-an-edge": (
        128, 512, dict(causal=False, kv_lens=(200, 256))),
    "kv-lens-under-the-diagonal": (
        384, 384, dict(causal=True, kv_lens=(150, 384))),
    "q-lens-part-padded-and-wholly-padded-blocks": (
        384, 384, dict(causal=True, q_lens=(130, 384))),
    "q-and-kv-lens-over-a-context": (
        256, 640, dict(causal=False, q_lens=(256, 100),
                       kv_lens=(640, 513))),
    "segment-ids-every-tile-an-edge": (
        384, 384, dict(causal=True, segs=(200, 200))),
    "segment-pair-over-a-longer-kv": (
        128, 384, dict(causal=True, segs=(0, 300))),
    "block-causal-4": (256, 384, dict(causal=True, block_causal=4)),
    "block-causal-128-a-whole-query-block": (
        256, 384, dict(causal=True, block_causal=128)),
    "values-narrower-than-keys": (256, 384, dict(causal=True, dv=16)),
    "values-narrower-not-causal-kv-lens": (
        128, 384, dict(causal=False, dv=16, kv_lens=(384, 129))),
    # heads of 128 (`heads`: query heads on kv heads): q, k, v are read and
    # `o` written as rows of [B, S, H x D], a head a lane block (PR 60)
    "rows-mha-diagonal-2x2": (256, 256, dict(causal=True, heads=(2, 2))),
    "rows-4-to-1-offset-diagonal": (
        128, 384, dict(causal=True, heads=(4, 1))),
    "rows-6-to-1-a-sequence-that-pads": (
        200, 200, dict(causal=True, heads=(6, 1))),
    "rows-8-to-1-not-causal-key-tail": (
        128, 300, dict(causal=False, heads=(8, 1))),
    "rows-20-to-1-q-and-kv-lens": (
        256, 384, dict(causal=False, heads=(20, 1), q_lens=(256, 100),
                       kv_lens=(384, 257))),
    "rows-4-to-2-segment-ids": (
        256, 256, dict(causal=True, heads=(4, 2), segs=(100, 100))),
    "rows-4-to-2-segment-pair-over-a-longer-kv": (
        128, 384, dict(causal=True, heads=(4, 2), segs=(0, 300))),
    "rows-4-to-2-block-causal-4": (
        256, 384, dict(causal=True, heads=(4, 2), block_causal=4)),
    "rows-values-of-256-beside-keys-of-128": (
        128, 256, dict(causal=True, heads=(2, 1), dv=256)),
}


@pytest.mark.parametrize("case", sorted(TILE_CASES))
def test_forward_parity_by_tile_class(case):
    """`o` and `lse` of the forward against plain softmax attention over
    the same visible pairs, for every class of tile the key loop tells
    apart: interior (no mask), the causal or block-causal diagonal, the
    keys' end inside a tile (the operand's or a row's `kv_lens`), every
    tile under segment ids; beside part-padded and wholly padded query
    blocks and values narrower than keys. Real rows only: a padded one
    means nothing."""
    sq, sk, kw = TILE_CASES[case]
    kw = dict(kw)
    heads = kw.pop("heads", None)
    if heads is None:
        heads, d = (4, 2), D
    else:
        d = 128
    dv, segs = kw.pop("dv", d), kw.pop("segs", None)
    q, k, v = _make(sq, sk, *heads, seed=len(case), d=max(d, dv))
    q, k, v = q[..., :d], k[..., :d], v[..., :dv]
    lens = {n: jnp.asarray(kw.pop(n), jnp.int32)
            for n in ("q_lens", "kv_lens") if n in kw}
    if segs is not None:
        segs = (_two_segments(sk, segs[1])[:, sk - sq:] if segs[0] == 0
                else _two_segments(sq, segs[0]), _two_segments(sk, segs[1]))
    o, lse = flash_attention(
        q, k, v, interpret=True, return_lse=True, block_q=128, block_k=128,
        segment_ids=segs, **lens, **kw)
    assert o.shape == (B, sq, heads[0], dv)
    assert lse.shape == (B, sq, heads[0])
    _assert_real_rows_match(o, lse, q, k, v, *_visible(
        sq, sk, segs=segs, **lens, **kw))


# (bq, bk, sq, sk, causal, block_causal, segment ids, q_len, kv_len)
TRIP_CASES = {
    "pretrain-2048": (512, 512, 2048, 2048, True, 0, False, None, None),
    "causal-4096": (512, 512, 4096, 4096, True, 0, False, None, None),
    "context-chunk-4096": (512, 512, 4096, 4096, False, 0, False, 4096, 4096),
    "context-mid-block": (512, 512, 2048, 8320, False, 0, False, 1842, 4100),
    "context-key-tail": (512, 512, 512, 2688, False, 0, False, 302, 2688),
    "own-call-part-padded": (512, 512, 4096, 4096, True, 0, False, 2084,
                             4096),
    "one-edge-tile-128": (128, 128, 128, 128, True, 0, False, 96, 128),
    "offset-diagonal": (128, 128, 256, 512, True, 0, False, None, None),
    "offset-inside-a-tile": (128, 128, 128, 320, True, 0, False, None, None),
    "key-tile-wider": (128, 256, 512, 512, True, 0, False, None, None),
    "query-tile-wider": (256, 128, 512, 512, True, 0, False, None, None),
    "kv-len-under-diagonal": (128, 128, 384, 384, True, 0, False, 384, 150),
    "block-causal-4": (128, 128, 256, 384, True, 4, False, None, None),
    "block-causal-128": (128, 128, 256, 384, True, 128, False, None, None),
    "segments": (128, 128, 384, 384, True, 0, True, None, None),
    "nothing-real": (128, 128, 256, 256, True, 0, False, 0, 0),
}


@pytest.mark.parametrize("case", sorted(TRIP_CASES))
def test_the_key_loops_trip_counts_are_the_tiles_classes(case):
    """`_fwd_trips` against a brute-force count over tiles: a query block's
    key blocks `[0, all)` are those a real row of it may attend a key of,
    `[0, interior)` those where EVERY real row sees EVERY key of the block
    (so no mask is owed), and every tile is an edge under segment ids."""
    from ray_tpu.ops.flash_attention import _fwd_trips

    bq, bk, sq, sk, causal, blk, segs, q_len, kv_len = TRIP_CASES[case]
    see, real = _visible(
        sq, sk, causal=causal, block_causal=blk,
        q_lens=None if q_len is None else (q_len,) * B,
        kv_lens=None if kv_len is None else (kv_len,) * B)
    see, real = see[0], real[0]
    for qblk in range(-(-sq // bq)):
        rows = slice(qblk * bq, (qblk + 1) * bq)
        live = real[rows]
        want_all = want_interior = 0
        for kb in range(-(-sk // bk)):
            tile = np.zeros((live.sum(), bk), bool)
            part = see[rows][live][:, kb * bk:(kb + 1) * bk]
            tile[:, :part.shape[1]] = part
            if tile.any():
                assert want_all == kb      # a prefix of the key blocks
                want_all = kb + 1
            if tile.size and tile.all() and not segs:
                assert want_interior == kb
                want_interior = kb + 1
        interior, n_all = _fwd_trips(
            qblk, bq=bq, block_k=bk, sq=sq, sk=sk, causal=causal,
            have_segs=segs, q_len=q_len, kv_len=kv_len, block_causal=blk)
        assert (int(interior), int(n_all)) == (want_interior, want_all), qblk


def test_a_single_edge_tile_runs_no_interior_tile_and_agrees():
    """A 128-token prompt's own call (96 tokens real) is one tile, on the
    diagonal: the interior loop's trip count is 0 and the call agrees with
    plain attention, and a traced program holds both loops all the same
    (the count is data where there are lengths)."""
    from ray_tpu.ops.flash_attention import _fwd_trips

    assert tuple(int(n) for n in _fwd_trips(
        0, bq=128, block_k=128, sq=128, sk=128, causal=True, have_segs=False,
        q_len=96, kv_len=128, block_causal=0)) == (0, 1)
    q, k, v = _make(128, 128, seed=45)
    o, lse = flash_attention(q, k, v, causal=True, interpret=True,
                             return_lse=True,
                             q_lens=jnp.asarray([96, 128], jnp.int32))
    _assert_real_rows_match(o, lse, q, k, v, *_visible(
        128, 128, causal=True, q_lens=(96, 128)))


# (query heads, kv heads, sq, sk, block_q, heads a grid step folds)
FOLD_CASES = {
    "4-of-4-at-128": (8, 2, 128, 384, 128, 4),
    "2-of-4-at-256": (8, 2, 256, 384, 256, 2),
    "4-of-8-at-128": (8, 1, 128, 256, 128, 4),
    "3-of-3-at-128": (6, 2, 128, 256, 128, 3),
    "1-of-5-at-128": (5, 1, 128, 256, 128, 1),
    # a group of SIX (models/laguna.py's full layers): divisors 1, 2, 3, 6
    "3-of-6-at-128": (12, 2, 128, 256, 128, 3),
    "2-of-6-at-256": (12, 2, 256, 384, 256, 2),
    "1-of-6-at-512": (12, 2, 512, 512, 512, 1),
    "1-of-4-at-384": (8, 2, 384, 384, 384, 1),
    "1-of-4-at-512": (8, 2, 512, 512, 512, 1),
    "mha-at-128": (2, 2, 128, 256, 128, 1),
    # heads of 128: the folded heads are g * 128 contiguous lanes of a row,
    # stacked on sublanes in VMEM (PR 60)
    "rows-4-of-4-at-128": (8, 2, 128, 256, 128, 4, 128),
    "rows-2-of-6-at-256": (6, 1, 256, 256, 256, 2, 128),
    "rows-4-of-20-at-128": (20, 1, 128, 256, 128, 4, 128),
}


@pytest.mark.parametrize("case", sorted(FOLD_CASES))
def test_a_short_query_block_folds_its_kv_heads_group_along_the_lanes(case):
    """A query block under 512 rows leaves the transposed score tile's
    lanes part empty, so a grid step takes as many query heads of the kv
    head's group as fill them (`_fold`: the largest divisor of the group
    that fits 512 lanes) and the grid has that many fewer steps; `o` and
    `lse` of every head are what plain attention gives, under the causal
    diagonal, lengths and segment ids alike."""
    from ray_tpu.ops.flash_attention import _fold

    hq, hkv, sq, sk, bq, g, *d = FOLD_CASES[case]
    assert _fold(hq // hkv, bq) == g
    q, k, v = _make(sq, sk, hq=hq, hkv=hkv, seed=len(case), d=(d or [D])[0])
    lens = dict(q_lens=jnp.asarray([sq - 30, sq], jnp.int32),
                kv_lens=jnp.asarray([sk, sk - 100], jnp.int32))
    segs = (_two_segments(sk, 100)[:, sk - sq:], _two_segments(sk, 100))
    for kw in (dict(causal=True, **lens), dict(causal=False, **lens),
               dict(causal=True, segment_ids=segs)):
        kw.update(return_lse=True, block_q=bq, block_k=128)
        o, lse = flash_attention(q, k, v, interpret=True, **kw)
        see = {k: kw[k] for k in kw if k in ("causal", "q_lens", "kv_lens")}
        _assert_real_rows_match(o, lse, q, k, v, *_visible(
            sq, sk, segs=kw.get("segment_ids"), **see))
        call, = _kernel_calls(functools.partial(
            flash_traced, interpret=False, **kw), q, k, v)
        assert call.params["grid_mapping"].grid == (B, hq // g, sq // bq)


def test_a_scale_that_is_not_positive_is_refused_by_name():
    """The forward takes a row's maximum on RAW scores and scales in the
    exponent: that is the scaled scores' maximum only under a positive
    scale."""
    q, k, v = _make(128, 128)
    for scale in (0.0, -0.125):
        with pytest.raises(ValueError, match="positive"):
            flash_attention(q, k, v, scale=scale, interpret=True)


def _assert_grads_match(q, k, v, blocks=None, **kw):
    """Gradients of the kernels (tile edges `blocks`, or the file's) against
    the reference's, both given `kw`."""
    edges = {} if blocks is None else dict(block_q=blocks[0],
                                           block_k=blocks[1])

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, interpret=True, **edges, **kw)
        return jnp.sum(jnp.sin(o))  # nontrivial cotangent

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(reference_attention(q, k, v, **kw)))

    g_got = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_want = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for got, want, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


# the backward visits a (key block, query block) tile once, the tiles the
# diagonal crosses under a mask and those below it without one: (sq, sk,
# q heads, kv heads, head dim, causal, tile edges or None for the file's).
# With edges of 128 a case has several tiles of each kind.
GRAD_CASES = {
    "128-128": (128, 128, 4, 2, D, True, None),
    "64-192": (64, 192, 4, 2, D, True, None),
    "mha-d64-2x2-tiles": (256, 256, 2, 2, 64, True, (128, 128)),
    "group-of-4-d128-3x3-tiles": (384, 384, 4, 1, 128, True, (128, 128)),
    "sq-lt-sk-2x4-tiles": (256, 512, 4, 2, D, True, (128, 128)),
    "sq-lt-sk-offset-inside-a-tile": (128, 320, 4, 1, D, True, (128, 128)),
    "pads-200": (200, 200, 4, 2, D, True, (128, 128)),
    "pads-queries-only": (200, 256, 4, 2, D, True, (128, 128)),
    "query-tile-wider": (512, 512, 4, 2, D, True, (256, 128)),
    "key-tile-wider": (512, 512, 4, 2, D, True, (128, 256)),
    "not-causal-2x3-tiles": (256, 384, 4, 2, D, False, (128, 128)),
    "not-causal-pads": (130, 200, 2, 2, D, False, (128, 128)),
    # heads of 128: q, do, dq a lane block of [B, S, Hq x D], k, v, dk, dv
    # of [B, S, Hkv x D], `delta` from `o` as the o projection reads it
    "rows-mha-2x2-tiles": (256, 256, 2, 2, 128, True, (128, 128)),
    "rows-4-to-1-sq-lt-sk": (128, 384, 4, 1, 128, True, (128, 128)),
    "rows-6-to-1-pads-200": (200, 200, 6, 1, 128, True, (128, 128)),
    "rows-8-to-2-one-tile": (128, 128, 8, 2, 128, True, None),
    "rows-20-to-1-not-causal": (128, 256, 20, 1, 128, False, (128, 128)),
    "rows-d256-2-to-1": (128, 128, 2, 1, 256, True, None),
}


@pytest.mark.parametrize("case", sorted(GRAD_CASES))
def test_grad_parity(case):
    sq, sk, hq, hkv, d, causal, blocks = GRAD_CASES[case]
    q, k, v = _make(sq, sk, hq=hq, hkv=hkv, d=d)
    _assert_grads_match(q, k, v, blocks, causal=causal)


# packed rows (every tile masked): (s, q heads, kv heads, head dim,
# segments a row, tile edges)
SEGMENT_GRAD_CASES = {
    "128-four-segments": (128, 4, 2, D, 4, None),
    "group-of-4-d64-3x3-tiles": (384, 4, 1, 64, 3, (128, 128)),
    "mha-d128-segments-inside-tiles": (256, 2, 2, 128, 8, (128, 128)),
    "pads-200": (200, 4, 2, D, 4, (128, 128)),
    "rows-4-to-2-d128-pads-200": (200, 4, 2, 128, 4, (128, 128)),
}


@pytest.mark.parametrize("case", sorted(SEGMENT_GRAD_CASES))
def test_grad_parity_with_segments(case):
    s, hq, hkv, d, n_seg, blocks = SEGMENT_GRAD_CASES[case]
    q, k, v = _make(s, s, hq=hq, hkv=hkv, d=d)
    segs = jnp.tile((jnp.arange(s, dtype=jnp.int32) * n_seg // s)[None],
                    (B, 1))
    _assert_grads_match(q, k, v, blocks, causal=True, segment_ids=segs)


def test_grad_parity_with_a_segment_pair_over_a_longer_kv():
    """Chunked, packed: 128 queries at the end of 256 keys, each side with
    its own segment ids."""
    sq, sk = 128, 256
    q, k, v = _make(sq, sk, hq=4, hkv=1)
    kv_seg = jnp.tile((jnp.arange(sk, dtype=jnp.int32) // 96)[None], (B, 1))
    _assert_grads_match(q, k, v, (128, 128), causal=True,
                        segment_ids=(kv_seg[:, -sq:], kv_seg))


def test_jit_and_bf16():
    q, k, v = _make(128, 128, dtype=jnp.bfloat16)
    f = jax.jit(functools.partial(flash_attention, causal=True,
                                  interpret=True))
    got = f(q, k, v).astype(jnp.float32)
    want = reference_attention(q, k, v, causal=True).astype(jnp.float32)
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)


# ------------------------------------------- a row's true lengths as data
HEADS = {"gqa-32-8": (32, 8), "mqa": (4, 1), "gqa-4-2": (4, 2)}


def _seg_path(q, k, v, causal, q_lens, kv_lens, **blocks):
    """The path the lengths replace: the same cut as (q, kv) segment ids
    (a padded query row is segment 1, a live key segment 1)."""
    sq, sk = q.shape[1], k.shape[1]
    kv_seg = (jnp.arange(sk)[None] < jnp.asarray(kv_lens)[:, None]).astype(
        jnp.int32)
    return flash_attention(
        q, k, v, causal=causal, interpret=True, return_lse=True,
        segment_ids=(jnp.ones((q.shape[0], sq), jnp.int32), kv_seg),
        **blocks)


@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize("kv_lens", [
    (0, 1), (128, 77), (256, 192), (1, 256), (129, 0), (127, 255)],
    ids=lambda x: "kv%d-%d" % x)
def test_kv_lens_equal_the_segment_mask_they_replace(kv_lens, heads):
    """The context call: non-causal over a width of 256 columns in key
    blocks of 128, rows of one batch with different lengths: 0, 1, a block
    edge, one short of it, mid-block and the full width. `o` and `lse` of
    every row are the segment-mask path's bit for bit (a masked block adds
    exactly 0 and scales by exactly 1) and the reference's over the row's
    real keys."""
    hq, hkv = HEADS[heads]
    q, k, v = _make(64, 256, hq=hq, hkv=hkv, seed=sum(kv_lens))
    lens = jnp.asarray(kv_lens, jnp.int32)
    o, lse = flash_attention(q, k, v, causal=False, interpret=True,
                             return_lse=True, kv_lens=lens, block_k=128)
    o_seg, lse_seg = _seg_path(q, k, v, False, (64, 64), kv_lens,
                               block_k=128)
    np.testing.assert_array_equal(o, o_seg)         # bit for bit
    np.testing.assert_array_equal(lse, lse_seg)
    for row, n in enumerate(kv_lens):
        if n == 0:
            assert (np.asarray(o[row]) == 0).all()
            assert (np.asarray(lse[row]) == NEG_INF).all()
            continue
        want = reference_attention(q[row:row + 1], k[row:row + 1, :n],
                                   v[row:row + 1, :n], causal=False)
        np.testing.assert_allclose(o[row:row + 1], want, atol=2e-5,
                                   rtol=2e-5)


@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("q_lens", [(0, 256), (130, 128), (256, 1)],
                         ids=lambda x: "q%d-%d" % x)
def test_q_lens_leave_real_rows_as_they_were(q_lens, causal, heads):
    """The own call of a padded bucket (causal, 256 tokens in blocks of
    128) and a context call's query side: a real row's `o` and `lse` are
    the call's without lengths; a query block that is all padding is
    written as zeros with `lse` NEG_INF."""
    hq, hkv = HEADS[heads]
    q, k, v = _make(256, 256, hq=hq, hkv=hkv, seed=sum(q_lens))
    kw = dict(causal=causal, interpret=True, return_lse=True, block_q=128,
              block_k=128)
    o, lse = flash_attention(q, k, v, q_lens=jnp.asarray(q_lens, jnp.int32),
                             **kw)
    o_all, lse_all = flash_attention(q, k, v, **kw)
    for row, n in enumerate(q_lens):
        np.testing.assert_array_equal(o[row, :n], o_all[row, :n])
        np.testing.assert_array_equal(lse[row, :n], lse_all[row, :n])
        skipped = -(-n // 128) * 128
        assert (np.asarray(o[row, skipped:]) == 0).all()
        assert (np.asarray(lse[row, skipped:]) == NEG_INF).all()


def test_a_skipped_part_leaves_the_merge_to_the_other_part():
    """A row with no context (`kv_lens` 0) beside one with 100 columns:
    merged with its own causal part, the first row is that part unchanged,
    and the second is attention over context + own tokens."""
    q, k, v = _make(128, 128, seed=9)
    _, kc, vc = _make(128, 256, seed=10)
    own, lse_own = flash_attention(q, k, v, causal=True, interpret=True,
                                   return_lse=True)
    ctx, lse_ctx = flash_attention(
        q, kc, vc, causal=False, interpret=True, return_lse=True,
        kv_lens=jnp.asarray([0, 100], jnp.int32))
    got = merge_attention(own, lse_own, ctx, lse_ctx)
    np.testing.assert_array_equal(got[0], own[0])
    want = reference_attention(
        q[1:], jnp.concatenate([kc[1:, :100], k[1:]], 1),
        jnp.concatenate([vc[1:, :100], v[1:]], 1), causal=True)
    np.testing.assert_allclose(got[1:], want, atol=2e-5, rtol=2e-5)


def test_lengths_are_the_forward_only_paths():
    q, k, v = _make(128, 128)
    with pytest.raises(ValueError, match="forward-only"):
        flash_attention(q, k, v, interpret=True,
                        kv_lens=jnp.asarray([1, 2], jnp.int32))


def _kernel_calls(fn, *args):
    """The `pallas_call` equations of `fn`'s jaxpr, nested ones too."""
    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e
                continue
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)

    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def test_without_lengths_the_call_is_what_it_was():
    """No lengths given: the traced call has the five operands it had and
    no scalar-prefetched one, with or without `return_lse`: the trainer's,
    ring attention's and the sharded wrapper's programs are untouched.
    With lengths: one operand more, prefetched."""
    q, k, v = _make(128, 256)
    for return_lse in (True, False):
        call, = _kernel_calls(functools.partial(
            flash_traced, causal=False, interpret=False,
            return_lse=return_lse), q, k, v)
        assert len(call.invars) == 5
        assert call.params["grid_mapping"].num_index_operands == 0
    call, = _kernel_calls(lambda q, k, v, n: flash_traced(
        q, k, v, causal=False, interpret=False, return_lse=True, kv_lens=n),
        q, k, v, jnp.asarray([1, 2], jnp.int32))
    assert len(call.invars) == 6
    assert call.params["grid_mapping"].num_index_operands == 1


def _jaxpr_sha(fn, *args):
    """The traced call's text less what a checkout's path and a line
    number put in it."""
    import hashlib
    import re

    text = str(jax.make_jaxpr(fn)(*args))
    text = re.sub(r"/[^\s:\"']*/ray_tpu/", "ray_tpu/", text)
    return hashlib.sha256(
        re.sub(r"\.py:\d+", ".py", text).encode()).hexdigest()[:16]


@pytest.mark.parametrize("case, sha", [
    ("plain", "dad2d0203e442b0a"), ("packed", "2fafad410f79a346"),
    ("lse", "b7e701b10c4a1f24")])
def test_the_trainers_call_traces_to_the_jaxpr_it_had(case, sha):
    """The calls that pass no lengths, forward and backward kernels at the
    pretrain cell's shapes: the jaxpr is pinned to the character
    (`train_tok_s` has a bound of 1%). All three were read anew by PR 45,
    which rewrote the forward (scores transposed, a key loop a class of
    tile, `lse` written as the lane rows the backward reads): `lse`, the
    forward alone, which is all that serving runs, was bdb7d7bc1c8fca77
    from PR 37 on; `plain` and `packed` hold the backward too and were
    38539c9e0e3b2c09 and 40fe6a56360f4f41 since PR 44 replaced its two
    kernels with one (a4fed4a4e0c528f1 and 032ab778af83649d until then).
    Within PR 45 they stood at 0c165bf59ecf9aa4 / 1051b2069a70a24d /
    102e820fa94da2f4 before the review's round: the trip counts were Python
    integers where nothing traced decided them, and a grid step had no
    head dimension to fold (`_fold`: 1 at these shapes). PR 57 read `plain`
    and `packed` anew (77c1c24c3392e9c0 and 185f042aeedeb177 until then):
    the forward rule names `o` and `lse` for a remat policy to list (two
    `name` equations; the kernels' calls are to the character what they
    were), and `lse`, which never meets the forward rule, did not move.
    PR 60 read all three anew (13ebb5a15f4d6fa9, c43460414616763e and
    afaa56b993c2c7a3 until then): these shapes, heads of 128, fall under
    its rule (`_heads_on_lanes`), so the calls' operands are rows of `[B,
    S, H x D]` with a head a lane block, the four transposes around a call
    are gone, and the backward's `delta` is a product of `do * o` with the
    heads' lanes; the shapes the rule leaves alone are pinned to the
    parent's text below. A deliberate change of the kernels reads them
    anew."""
    q = jnp.zeros((2, 2048, 32, 128), jnp.bfloat16)
    k = v = jnp.zeros((2, 2048, 8, 128), jnp.bfloat16)

    def loss(q, k, v, seg=None):
        return flash_traced(q, k, v, causal=True, segment_ids=seg,
                               interpret=False).astype(jnp.float32).sum()

    if case == "lse":
        fn, args = (lambda q, k, v: flash_traced(
            q, k, v, causal=True, interpret=False, return_lse=True)), ()
    else:
        args = (jnp.zeros((2, 2048), jnp.int32),) if case == "packed" else ()
        fn = jax.grad(loss, argnums=(0, 1, 2))
    assert _jaxpr_sha(fn, q, k, v, *args) == sha
    # every call's q, k, v (and the backward's do, dq, dk, dv) are rows
    for call in _kernel_calls(fn, q, k, v, *args):
        rows = [x.aval.shape for x in (*call.invars, *call.outvars)
                if x.aval.dtype == jnp.bfloat16]
        assert set(rows) == {(2, 2048, 32 * 128), (2, 2048, 8 * 128)}


@pytest.mark.parametrize("case, sha", [
    ("a-head-of-16", "f0f033bcb8c431b4"),
    ("a-head-of-16-lens", "c10c7fc466c90dde"),
    ("keys-of-192-values-of-128", "2d8041e64d353148")])
def test_a_head_that_is_no_lane_tile_keeps_the_operands_it_had(case, sha):
    """The rule is the operands' shape (`_heads_on_lanes`): a head is a
    lane block of a row only where the keys' and the values' widths are
    whole lane tiles. A tiny configuration's head of 16 (forward and
    backward; the forward with lengths) and a latent family's keys of 192
    beside values of 128 (`_mla_flash`: forward only) keep `[B, H, S, D]`
    operands and the transposes that make them, and trace to the jaxpr the
    tree had before PR 60, to the character: the hashes were read on the
    parent commit (798d9e5) with this file's `_jaxpr_sha`."""
    bf16 = jnp.bfloat16
    if case == "keys-of-192-values-of-128":
        q = k = jnp.zeros((1, 512, 4, 192), bf16)
        v = jnp.zeros((1, 512, 4, 128), bf16)

        def fn(q, k, v):
            return flash_traced(
                q, k, v, causal=True, interpret=False, return_lse=True,
                kv_lens=jnp.asarray([300], jnp.int32))
    else:
        q = jnp.zeros((2, 256, 4, 16), bf16)
        k = v = jnp.zeros((2, 256, 2, 16), bf16)

        def fn(q, k, v):
            return flash_traced(
                q, k, v, causal=True, interpret=False, return_lse=True,
                q_lens=jnp.asarray([3, 200], jnp.int32))
        if case == "a-head-of-16":
            fn = jax.grad(lambda q, k, v: flash_traced(
                q, k, v, causal=True, interpret=False).astype(
                    jnp.float32).sum(), argnums=(0, 1, 2))
    assert _jaxpr_sha(fn, q, k, v) == sha
    b, s, h, d = q.shape
    for call in _kernel_calls(fn, q, k, v):
        heads_first = [x.aval.shape for x in call.invars
                       if x.aval.dtype == bf16]
        assert heads_first[0] == (b, h, s, d)
        assert all(len(shape) == 4 for shape in heads_first)


# ------------------------------------------------------- a sliding window
def _band(see, sq, sk, window, causal, kv_lens=None):
    """`see` cut to the band of `flash_attention`'s docstring: a causal
    call's queries are the last `sq` of `sk` positions; one that is not
    follows its row's keys."""
    qi, kj = np.arange(sq)[:, None], np.arange(sk)[None, :]
    woff = (np.full((B,), sk - sq) if causal else
            np.full((B,), sk) if kv_lens is None else np.asarray(kv_lens))
    return see & (kj[None] > qi[None] + woff[:, None, None] - window)


# (sq, sk, window, causal, q_lens, kv_lens, blocks): bands that start
# inside, on and between tiles; a window under a tile and over several; the
# context part of a resumed pass (not causal: the queries follow the keys)
WINDOW_CASES = {
    "inside-a-tile": (256, 256, 100, True, None, None, (128, 128)),
    "on-a-tile": (384, 384, 128, True, None, None, (128, 128)),
    "between-tiles": (512, 512, 200, True, None, None, (128, 128)),
    "two-tiles-wide": (512, 512, 256, True, None, None, (128, 128)),
    "under-a-tile": (384, 384, 32, True, None, None, (128, 128)),
    "one-key": (256, 256, 1, True, None, None, (128, 128)),
    "wider-than-all": (256, 256, 1000, True, None, None, (128, 128)),
    "wide-query-tile": (512, 512, 160, True, None, None, (256, 128)),
    "wide-key-tile": (512, 512, 160, True, None, None, (128, 256)),
    "padded-bucket": (384, 384, 150, True, (200, 384), None, (128, 128)),
    "offset-causal": (128, 384, 200, True, None, None, (128, 128)),
    "context-whole-ring": (384, 256, 256, False, None, (256, 256),
                           (128, 128)),
    "context-short-ring": (256, 256, 256, False, (256, 100), (100, 31),
                           (128, 128)),
    "context-no-ring": (256, 256, 256, False, None, (0, 256), (128, 128)),
    "context-window-under-a-tile": (256, 128, 40, False, None, (128, 77),
                                    (128, 128)),
    "context-window-over-the-ring": (256, 128, 300, False, None, (128, 64),
                                     (128, 128)),
    "default-blocks": (1024, 1024, 600, True, None, None, (512, 512)),
    # heads of 128, 8 on 1 (a sliding layer of models/laguna.py): rows
    "rows-8-to-1-between-tiles": (384, 384, 200, True, None, None,
                                  (128, 128), (8, 1, 128)),
    "rows-8-to-1-context-short-ring": (256, 256, 256, False, (256, 100),
                                       (100, 31), (128, 128), (8, 1, 128)),
}


@pytest.mark.parametrize("case", sorted(WINDOW_CASES))
def test_forward_parity_under_a_window(case):
    """The kernel under `window` against plain softmax over the band's
    pairs, `o` and `lse` of every real row (a row the band leaves no key:
    0 and NEG_INF), and its loops' trip counts (`_fwd_trips` +
    `_band_trips`) against a brute-force count over tiles: the loop starts
    at the first tile a real row sees a key of, and a tile runs bare
    exactly when every real row sees all of it."""
    from ray_tpu.ops.flash_attention import (_band_offset, _band_trips,
                                             _fwd_trips)

    sq, sk, window, causal, q_lens, kv_lens, (bq, bk), *heads = (
        WINDOW_CASES[case])
    hq, hkv, d = heads[0] if heads else (4, 2, D)
    q, k, v = _make(sq, sk, hq, hkv, seed=7, d=d)
    lens = {} if q_lens is None else {"q_lens": jnp.asarray(q_lens)}
    if kv_lens is not None:
        lens["kv_lens"] = jnp.asarray(kv_lens)
    o, lse = flash_attention(q, k, v, causal=causal, window=window,
                             return_lse=True, interpret=True, block_q=bq,
                             block_k=bk, **lens)
    see, real = _visible(sq, sk, causal=causal, q_lens=q_lens,
                         kv_lens=kv_lens)
    see = _band(see, sq, sk, window, causal, kv_lens)
    _assert_real_rows_match(o, lse, q, k, v, see, real)
    for b in range(B):
        q_len = None if q_lens is None else q_lens[b]
        kv_len = None if kv_lens is None else kv_lens[b]
        for qblk in range(-(-sq // bq)):
            rows = slice(qblk * bq, (qblk + 1) * bq)
            live = real[b, rows]
            visited, bare = [], []
            for kb in range(-(-sk // bk)):
                tile = np.zeros((live.sum(), bk), bool)
                part = see[b, rows][live][:, kb * bk:(kb + 1) * bk]
                tile[:, :part.shape[1]] = part
                if tile.any():
                    visited.append(kb)
                if tile.size and tile.all():
                    bare.append(kb)
            interior, n_all = _fwd_trips(
                qblk, bq=bq, block_k=bk, sq=sq, sk=sk, causal=causal,
                have_segs=False, q_len=q_len, kv_len=kv_len, block_causal=0,
                xp=np)
            first, below = _band_trips(
                qblk, bq=bq, block_k=bk, window=window,
                woff=_band_offset(causal, sq, sk, kv_len), xp=np)
            first = min(int(first), int(n_all))
            below = int(np.clip(below, first, n_all))
            interior = int(np.clip(interior, below, n_all))
            # every visited tile is walked, none before the band; the bare
            # loop runs bare tiles only (a padded row of a real block may
            # make the kernel mask a tile the real rows see whole)
            assert set(visited) <= set(range(first, int(n_all))), (b, qblk)
            if visited:
                assert first == visited[0], (b, qblk)
            assert set(range(below, interior)) <= set(bare), (b, qblk)
            if q_len is None:
                assert list(range(below, interior)) == bare, (b, qblk)


def test_a_window_none_is_the_call_it_was_and_the_backward_refuses_one():
    """`window=None` adds nothing to the traced call (the trainer's and
    every cell's jaxprs are pinned below and in tests/test_sdar.py,
    test_kimi.py); a window is the forward-only path's, refused by name
    anywhere else."""
    q, k, v = _make(128, 128)
    plain = _jaxpr_sha(lambda *a: flash_traced(
        *a, return_lse=True, interpret=True), q, k, v)
    none = _jaxpr_sha(lambda *a: flash_traced(
        *a, return_lse=True, interpret=True, window=None), q, k, v)
    assert plain == none
    with pytest.raises(ValueError, match="backward kernel has no band"):
        flash_traced(q, k, v, window=64, interpret=True)
    with pytest.raises(ValueError, match="positive sliding window"):
        flash_traced(q, k, v, window=0, return_lse=True, interpret=True)
    with pytest.raises(ValueError, match="backward kernel has no band"):
        jax.grad(lambda q: flash_traced(
            q, k, v, window=64, interpret=True).sum())(q)


# ------------------------------------------- what a remat policy keeps
@pytest.mark.parametrize("policy, forwards", [("dots", 1), ("nothing", 2)])
def test_a_rematted_layer_runs_the_forward_kernel_as_its_policy_says(
        policy, forwards):
    """The gradient of the trainer's loss through scanned, rematerialised
    layers with the flash kernels (interpret mode): under `"dots"` the
    backward reads the `o` and `lse` the forward call named
    (`SAVED_OUTPUTS`), so a layer holds ONE forward kernel and one
    backward; under `"nothing"` the backward scan runs the forward again:
    two and one. Either way the gradient is the un-rematerialised one to
    the bit: the values saved are the values recomputed."""
    import flax.linen as nn
    from jax.sharding import Mesh

    from ray_tpu.models.llama import LlamaModel, get_config
    from ray_tpu.parallel.mesh import AXES, active_mesh
    from ray_tpu.parallel.train_lib import ShardedTrainer

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape((1,) * len(AXES)),
                AXES)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 256), 0, 256)

    def grads_of(**remat):
        # float32: the CPU backend has no bf16 x bf16 = f32 product
        cfg = get_config("tiny", attention_impl="flash", dtype=jnp.float32,
                         **remat)
        assert cfg.scan_layers
        trainer = ShardedTrainer(LlamaModel(cfg), mesh)
        params = nn.meta.unbox(trainer.model.init(
            jax.random.PRNGKey(0), ids)["params"])
        grad = jax.grad(lambda p: trainer.loss_fn(p, {"input_ids": ids}))
        with active_mesh(mesh):
            # a scan's body counts once, a layer; the forward kernel gives
            # (o, lse), the backward (dq, dk, dv)
            return jax.jit(grad)(params), sorted(
                len(call.outvars) for call in _kernel_calls(grad, params))

    got, kernels = grads_of(remat=True, remat_policy=policy)
    want, plain = grads_of(remat=False)
    assert plain == [2, 3]
    assert kernels == [2] * forwards + [3]
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
