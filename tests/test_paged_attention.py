"""Paged-attention op parity tests (CPU; the Pallas decode kernel runs in
interpreter mode). The jnp gather path `paged_attention_reference` is the
oracle: it is itself checked against dense attention, then the decode
kernel and the lse-merged prefill path are checked against it.

The reference framework ships no attention kernels (it delegates to vLLM,
ref: llm/_internal/serve/deployments/llm/vllm/vllm_engine.py:181); the
coverage model here is the one its engine inherits from vLLM's own kernel
parity suites.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops.attention import reference_attention  # noqa: E402
from ray_tpu.ops.paged_attention import (  # noqa: E402
    gather_kv, make_kv_pages, merge_attention, paged_attention_decode,
    paged_attention_reference, paged_prefill_attention, paged_write)


N_LAYERS = 3
# None: a pool with no layer axis; 0 and L-1: the ends of a layered pool
LAYERS = [None, 0, N_LAYERS - 1]


def _make_pages(rng, *, b, hkv, d, page, num_pages, mp, lengths,
                layer=None):
    """Page pool + per-row block tables holding `lengths` real tokens
    (written via paged_write), plus the dense [B, Smax, Hkv, D] K/V they
    encode for oracle computation. With a `layer` the pool is layered
    [N_LAYERS, P, ...], only that layer is written, and the others hold
    noise that must come through the write bit-identical (a wrong layer
    index would corrupt a neighbour silently)."""
    kv_pages = make_kv_pages(hkv, num_pages, page, d, jnp.float32)
    if layer is not None:
        noise = jnp.asarray(rng.standard_normal(
            (N_LAYERS,) + kv_pages.shape), jnp.float32)
        kv_pages = noise.at[layer].set(kv_pages)
    # distinct pages per row, page 0 reserved as the null page
    perm = rng.permutation(num_pages - 1)[: b * mp] + 1
    bt = jnp.asarray(perm.reshape(b, mp), jnp.int32)
    smax = mp * page
    k_dense = jnp.asarray(rng.standard_normal((b, smax, hkv, d)),
                          jnp.float32)
    v_dense = jnp.asarray(rng.standard_normal((b, smax, hkv, d)),
                          jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(smax), (b, smax))
    lens = jnp.asarray(lengths, jnp.int32)
    before = kv_pages
    kv_pages = paged_write(kv_pages, k_dense, v_dense, bt, positions, lens,
                           layer)
    for other in range(N_LAYERS if layer is not None else 0):
        if other != layer:
            np.testing.assert_array_equal(np.asarray(kv_pages[other]),
                                          np.asarray(before[other]))
    return kv_pages, bt, k_dense, v_dense, lens


@pytest.mark.parametrize("layer", LAYERS)
def test_write_then_gather_roundtrip(layer):
    rng = np.random.default_rng(0)
    b, hkv, d, page, mp = 3, 2, 8, 4, 5
    lengths = [17, 0, 20]
    kv_pages, bt, k_dense, v_dense, lens = _make_pages(
        rng, b=b, hkv=hkv, d=d, page=page, num_pages=32, mp=mp,
        lengths=lengths, layer=layer)
    got_k, got_v = gather_kv(kv_pages, bt, layer)
    for i, n in enumerate(lengths):
        np.testing.assert_allclose(got_k[i, :n], k_dense[i, :n], rtol=1e-6)
        np.testing.assert_allclose(got_v[i, :n], v_dense[i, :n], rtol=1e-6)
        # beyond the row's length nothing was written
        assert not np.any(np.asarray(got_k[i, n:]))


def _write_per_token(kv_pages, k_new, v_new, block_tables, positions,
                     total_lens, layer):
    """The write as it was before whole pages: one scatter row a token.
    Kept here as the oracle of `paged_write`: same tokens, same places."""
    num_pages, page = kv_pages.shape[1], kv_pages.shape[3]
    valid = positions < total_lens[:, None]
    page_ix = jnp.take_along_axis(block_tables, positions // page, axis=1)
    page_ix = jnp.where(valid, page_ix, num_pages)  # OOB -> mode="drop"
    kv = jnp.concatenate([k_new, v_new], axis=-1).astype(kv_pages.dtype)
    return kv_pages.at[layer, page_ix, :, positions % page].set(
        kv, mode="drop")


PAGE = 8
# name -> (first position per row, S, total_lens per row); the block
# table has 4 columns of PAGE rows, so a sequence holds 32 tokens
WRITES = {
    **{f"decode-offset{o}": ([PAGE + o, o, 3 * PAGE + o], 1,
                             [PAGE + o + 1, o + 1, 3 * PAGE + o + 1])
       for o in range(PAGE)},
    # page-aligned prefill with ragged tails (13 and 1 of 16 real)
    "prefill-aligned-ragged": ([0, PAGE, 0], 2 * PAGE, [13, PAGE + 16, 1]),
    # unaligned spans crossing one and two page boundaries (verify, chunks)
    "span-unaligned": ([PAGE - 3, 5, 2 * PAGE - 1], 6, [PAGE + 3, 11, 21]),
    "span-unaligned-long": ([3, PAGE + 7, 1], PAGE + 4,
                            [PAGE + 7, 2 * PAGE + 11, PAGE + 5]),
    # inactive rows write nothing, wherever their positions point
    "rows-inactive": ([0, 9, 0], 5, [0, 14, 0]),
    # a row past its cap: frozen at cap - 1 (decode), or a span whose
    # tail lies beyond total_lens and beyond the block table's last page
    "decode-at-cap": ([4 * PAGE - 1, 7, 4 * PAGE - 1], 1,
                      [4 * PAGE, 8, 4 * PAGE]),
    "span-past-table": ([4 * PAGE - 2, 3 * PAGE + 5, 0], 6,
                        [4 * PAGE, 4 * PAGE, 6]),
}


@pytest.mark.parametrize("layer", [0, N_LAYERS - 1])
@pytest.mark.parametrize("case", list(WRITES))
def test_whole_page_write_equals_per_token_write(case, layer):
    """`paged_write` moves whole pages; what lands in the pool is, bit for
    bit, what a per-token scatter puts there, on a pool full of noise."""
    starts, s, totals = WRITES[case]
    rng = np.random.default_rng(6)
    b, hkv, d, mp, num_pages = len(starts), 2, 8, 4, 20
    pool = jnp.asarray(rng.standard_normal(
        (N_LAYERS, num_pages, hkv, PAGE, 2 * d)), jnp.bfloat16)
    bt = jnp.asarray(rng.permutation(num_pages - 1)[:b * mp].reshape(b, mp)
                     + 1, jnp.int32)
    for i, total in enumerate(totals):
        if total == 0:
            bt = bt.at[i].set(0)        # the engine's padding rows
    k_new, v_new = (jnp.asarray(rng.standard_normal((b, s, hkv, d)),
                                jnp.bfloat16) for _ in range(2))
    positions = jnp.asarray(starts, jnp.int32)[:, None] + jnp.arange(s)
    totals = jnp.asarray(totals, jnp.int32)
    got = jax.jit(paged_write)(pool, k_new, v_new, bt, positions, totals,
                               layer)
    want = _write_per_token(pool, k_new, v_new, bt, positions, totals,
                            layer)
    assert np.any(np.asarray(want != pool)) or not np.any(totals)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_reference_matches_dense_attention():
    rng = np.random.default_rng(1)
    b, hq, hkv, d, page, mp = 2, 4, 2, 16, 4, 4
    n = mp * page
    kv_pages, bt, k_dense, v_dense, lens = _make_pages(
        rng, b=b, hkv=hkv, d=d, page=page, num_pages=32, mp=mp,
        lengths=[n, n])
    q = jnp.asarray(rng.standard_normal((b, n, hq, d)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(n), (b, n))
    got = paged_attention_reference(q, kv_pages, bt, positions)
    want = reference_attention(q, k_dense, v_dense, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# (head_dim, page, dtype): the kernel has two lane forms, chosen from the
# head size. At 128 K and V are whole lane tiles and nothing is padded;
# below, q is zero-padded to the 2D lanes of a KV row.
FORMS = {
    "d32-page4-f32": (32, 4, jnp.float32),
    "d128-page16-f32": (128, 16, jnp.float32),
    "d32-page16-bf16": (32, 16, jnp.bfloat16),
    "d128-page16-bf16": (128, 16, jnp.bfloat16),
}


def _assert_rows_match(got, want, lengths, dtype):
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.all(np.isfinite(got))
    for i, n in enumerate(lengths):
        if n == 0:
            np.testing.assert_array_equal(got[i], 0.0)
        else:
            np.testing.assert_allclose(got[i], want[i], rtol=tol, atol=tol)


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 4)])
@pytest.mark.parametrize("pages_per_chunk", [1, 3, 8])
@pytest.mark.parametrize("form", list(FORMS))
def test_decode_kernel_matches_reference(hq, hkv, pages_per_chunk, layer,
                                         form):
    rng = np.random.default_rng(2)
    d, page, dtype = FORMS[form]
    b, mp = 4, 8
    lengths = [1, 3 * page + 1, 0, mp * page]  # incl. inactive + full rows
    kv_pages, bt, _, _, lens = _make_pages(
        rng, b=b, hkv=hkv, d=d, page=page, num_pages=64, mp=mp,
        lengths=lengths, layer=layer)
    kv_pages = kv_pages.astype(dtype)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), dtype)
    got = paged_attention_decode(q, kv_pages, bt, lens, layer=layer,
                                 pages_per_chunk=pages_per_chunk,
                                 interpret=True)
    positions = jnp.maximum(lens - 1, 0)[:, None]
    # the oracle reads the written layer as a pool of its own
    own = kv_pages if layer is None else kv_pages[layer]
    want = paged_attention_reference(q[:, None], own, bt, positions)[:, 0]
    _assert_rows_match(got, want, lengths, dtype)


@pytest.mark.parametrize("ring", [False, True], ids=["pages", "ring"])
@pytest.mark.parametrize("hq,hkv", [(6, 2), (12, 2), (48, 8)],
                         ids=["rep3", "rep6", "rep6-of-8-kv"])
@pytest.mark.parametrize("form", ["d128-page16-f32", "d128-page16-bf16"])
def test_decode_kernel_at_a_group_that_is_no_sublane_tile(form, hq, hkv,
                                                          ring):
    """Three and six query heads a kv head (models/laguna.py's full layers
    are 48 on 8): the kernel's state is `[Hkv, rep, .]` and `rep` rows are
    no whole 8-row tile; through the block table, and through a slot's ring
    under the window's name and length rule."""
    from ray_tpu.ops.paged_attention import (ring_tables,
                                             window_attention_decode)

    rng = np.random.default_rng(hq)
    d, page, dtype = FORMS[form]
    b, mp, layer = 4, 4, 1
    lengths = [1, 2 * page + 3, 0, mp * page]
    q = jnp.asarray(rng.standard_normal((b, hq, d)), dtype)
    if ring:
        # slot i's ring is pages [i * mp, (i + 1) * mp): a window of mp
        # pages, read at min(length, window)
        pool = jnp.asarray(rng.standard_normal(
            (N_LAYERS, b * mp, hkv, page, 2 * d)), dtype)
        lens = jnp.asarray([1, 2 * page + 3, 0, 9 * page], jnp.int32)
        got = window_attention_decode(q, pool, lens, ring_pages=mp,
                                      layer=layer, interpret=True)
        held = jnp.minimum(lens, mp * page)
        want = paged_attention_reference(
            q[:, None], pool[layer], ring_tables(jnp.arange(b), mp),
            jnp.maximum(held - 1, 0)[:, None])[:, 0]
        _assert_rows_match(got, want, np.asarray(held), dtype)
        return
    kv_pages, bt, _, _, lens = _make_pages(
        rng, b=b, hkv=hkv, d=d, page=page, num_pages=40, mp=mp,
        lengths=lengths, layer=layer)
    kv_pages = kv_pages.astype(dtype)
    got = paged_attention_decode(q, kv_pages, bt, lens, layer=layer,
                                 pages_per_chunk=3, interpret=True)
    want = paged_attention_reference(
        q[:, None], kv_pages[layer], bt,
        jnp.maximum(lens - 1, 0)[:, None])[:, 0]
    _assert_rows_match(got, want, lengths, dtype)


@pytest.mark.parametrize("pages_per_chunk", [2, 4])
@pytest.mark.parametrize("form", [f for f in FORMS if "page16" in f])
def test_decode_kernel_never_reads_past_a_length(form, pages_per_chunk):
    """A poisoned pool: every row at or past a sequence's length and every
    page no live row owns is NaN. Sequences end mid-page and mid-item, one
    row is inactive. The kernel masks a tail only where there is one (a
    sequence's last item), so this is what holds it to the contract: the
    outputs are finite and equal the reference on the clean pool."""
    rng = np.random.default_rng(7)
    d, page, dtype = FORMS[form]
    b, hq, hkv, mp, num_pages, layer = 4, 8, 2, 7, 40, 1
    lengths = [1, 2 * page + 5, 0, 6 * page + 4]   # GQA rep 4
    bt = rng.permutation(num_pages - 1)[:b * mp].reshape(b, mp) + 1
    clean = rng.standard_normal(
        (N_LAYERS, num_pages, hkv, page, 2 * d)).astype(np.float32)
    live = np.zeros(clean.shape[:2] + (1, page, 1), bool)
    for i, n in enumerate(lengths):
        for col in range(-(-n // page)):
            live[layer, bt[i, col], 0, :min(page, n - col * page)] = True
        bt[i, -(-n // page):] = 0                  # columns past the length
    poisoned = jnp.asarray(np.where(live, clean, np.nan), dtype)
    zeroed = jnp.asarray(np.where(live, clean, 0.0), dtype)
    bt, lens = jnp.asarray(bt, jnp.int32), jnp.asarray(lengths, jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), dtype)
    got = paged_attention_decode(q, poisoned, bt, lens, layer=layer,
                                 pages_per_chunk=pages_per_chunk,
                                 interpret=True)
    want = paged_attention_reference(
        q[:, None], zeroed, bt, jnp.maximum(lens - 1, 0)[:, None],
        layer=layer)[:, 0]
    _assert_rows_match(got, want, lengths, dtype)


def test_decode_kernel_bf16():
    rng = np.random.default_rng(3)
    b, hq, hkv, d, page, mp = 2, 4, 2, 16, 8, 4
    kv_pages = jnp.asarray(
        rng.standard_normal((16, hkv, page, 2 * d)), jnp.bfloat16)
    bt = jnp.asarray(rng.permutation(15)[: b * mp].reshape(b, mp) + 1,
                     jnp.int32)
    lens = jnp.asarray([9, 26], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.bfloat16)
    got = paged_attention_decode(q, kv_pages, bt, lens, interpret=True)
    want = paged_attention_reference(
        q[:, None], kv_pages, bt,
        jnp.maximum(lens - 1, 0)[:, None])[:, 0]
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("impl", [None, "flash"])
@pytest.mark.parametrize("ctx_lens", [(0, 0), (8, 0), (8, 16)])
def test_prefill_merge_matches_reference(ctx_lens, impl, layer):
    """New tokens starting at a (page-aligned) cached-prefix offset must
    attend prefix + themselves exactly like the one-shot gather path."""
    rng = np.random.default_rng(4)
    b, hq, hkv, d, page, mp = 2, 4, 2, 16, 8, 6
    s_new = 12
    lengths = [c + s_new for c in ctx_lens]
    kv_pages, bt, k_dense, v_dense, lens = _make_pages(
        rng, b=b, hkv=hkv, d=d, page=page, num_pages=32, mp=mp,
        lengths=lengths, layer=layer)
    positions = jnp.stack([jnp.arange(c, c + s_new) for c in ctx_lens])
    q = jnp.asarray(rng.standard_normal((b, s_new, hq, d)), jnp.float32)
    k_new = jnp.stack([k_dense[i, c:c + s_new] for i, c in
                       enumerate(ctx_lens)])
    v_new = jnp.stack([v_dense[i, c:c + s_new] for i, c in
                       enumerate(ctx_lens)])
    got = paged_prefill_attention(q, k_new, v_new, kv_pages, bt,
                                  positions, lens, ctx_pages=mp, impl=impl,
                                  layer=layer)
    want = paged_attention_reference(q, kv_pages, bt, positions,
                                     layer=layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    if max(ctx_lens) == 0:
        # ctx_pages=0 must also work (and read no pages)
        got0 = paged_prefill_attention(q, k_new, v_new, kv_pages, bt,
                                       positions, lens, ctx_pages=0,
                                       impl=impl, layer=layer)
        np.testing.assert_allclose(np.asarray(got0), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_merge_attention_equals_joint_softmax():
    rng = np.random.default_rng(5)
    b, s, h, d = 2, 4, 3, 8
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, 10, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, 10, h, d)), jnp.float32)
    from ray_tpu.ops.paged_attention import _attn_lse

    o1, l1 = _attn_lse(q, k[:, :6], v[:, :6], causal=False,
                       scale=d ** -0.5, impl="flash")
    o2, l2 = _attn_lse(q, k[:, 6:], v[:, 6:], causal=False,
                       scale=d ** -0.5, impl="flash")
    got = merge_attention(o1, l1, o2, l2)
    want = reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("impl", [None, "flash"])
@pytest.mark.parametrize("ctx_lens, n_new", [
    ((0, 0), (5, 12)), ((8, 0), (12, 1)), ((16, 40), (7, 3)),
    ((48, 24), (12, 12))])
def test_prefill_of_a_padded_bucket_attends_real_lengths(ctx_lens, n_new,
                                                         impl, layer):
    """Rows of one wave with different prefixes (none, a page, the table's
    whole width less the bucket) and different real tokens in a bucket of
    12: every REAL token attends prefix + itself as the one-shot gather
    path has it, whatever the padded positions' keys and values hold (here
    1e4: finite, as a padded token's projections are)."""
    rng = np.random.default_rng(sum(ctx_lens) + sum(n_new))
    b, hq, hkv, d, page, mp, s = 2, 4, 2, 16, 8, 8, 12
    lengths = [c + n for c, n in zip(ctx_lens, n_new)]
    kv_pages, bt, k_dense, v_dense, lens = _make_pages(
        rng, b=b, hkv=hkv, d=d, page=page, num_pages=32, mp=mp,
        lengths=lengths, layer=layer)
    positions = jnp.stack([jnp.arange(c, c + s) for c in ctx_lens])
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    real = jnp.arange(s)[None, :, None, None] < jnp.asarray(
        n_new)[:, None, None, None]
    k_new, v_new = (jnp.where(real, jnp.stack(
        [x[i, c:c + s] for i, c in enumerate(ctx_lens)]), 1e4)
        for x in (k_dense, v_dense))
    got = paged_prefill_attention(q, k_new, v_new, kv_pages, bt, positions,
                                  lens, ctx_pages=mp, impl=impl, layer=layer)
    want = paged_attention_reference(q, kv_pages, bt, positions, layer=layer)
    for row, n in enumerate(n_new):
        np.testing.assert_allclose(np.asarray(got[row, :n]),
                                   np.asarray(want[row, :n]),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s, n_new, ctx, width, want", [
    # the docbatch cycle's passes, in blocks of 512 (ISSUE 39): a fresh
    # 4096 bucket of 2084 tokens, a resumed 2048 pass of 8 and of 1842
    # tokens behind 4096 of 8320 columns, a full fresh 8192 pass. The last
    # integer (ISSUE 45): the visits that take the kernel's edge body, a
    # query block's diagonal tile and the context's tile that holds the end
    # of the row's keys inside it (none where the context ends on a block)
    (4096, 2084, 0, 0, (36, 15, 15 << 18, 5)),
    (2048, 8, 4096, 8320, (10 + 68, 1 + 8, 9 << 18, 1)),
    (2048, 1842, 4096, 8320, (78, 10 + 32, 42 << 18, 4)),
    (2048, 1842, 4100, 8320, (78, 10 + 36, 46 << 18, 4 + 4)),
    (8192, 7797, 0, 0, (136, 136, 136 << 18, 16)),
    # chat: 1326 tokens' resumed 512 pass, 1120's resumed 128 pass (its
    # query block is 128 rows), a fresh row in a wave's context program
    (512, 302, 1024, 2688, (1 + 6, 1 + 2, 3 << 18, 1)),
    (128, 96, 1024, 2688, (7, 3, 128 * 128 + 2 * 128 * 512, 1)),
    (128, 96, 1040, 2688, (7, 4, 128 * 128 + 3 * 128 * 512, 2)),
    (512, 400, 0, 2688, (7, 1, 1 << 18, 1)),
    # buckets off the block: 640 tokens are two query blocks of 512 rows,
    # 1000 columns two key blocks; nothing real at all
    (640, 513, 700, 1000, (3 + 4, 3 + 4, 7 << 18, 2 + 2)),
    (640, 100, 512, 1000, (7, 1 + 1, 2 << 18, 1)),
    (128, 0, 0, 0, (1, 0, 0, 0))])
def test_prefill_block_visits_of_the_benchmarks_passes(s, n_new, ctx, width,
                                                       want):
    """`prefill_block_visits` (the kernel's trip counts on the host) by
    hand for the cells' passes, and against the masks of the two calls:
    with lengths a block is visited exactly when a real query row of it
    may attend a real key of it; without, when any row may attend any key
    of the operands. A visited block takes the EDGE body exactly when some
    real query row of it may not attend some key of the block (brute
    force over tiles: the kernel's own split is held to the same count in
    tests/test_flash_attention.py)."""
    from ray_tpu.ops.paged_attention import prefill_block_visits

    assert prefill_block_visits(s, width)[0] == want[0]
    assert prefill_block_visits(s, width, n_new, ctx) == want[1:]

    def blocks(mask, rows, bq, bk):
        """(blocks a real row may attend a key of, those of them where a
        real row may NOT attend some key of the block)."""
        shape = (-(-mask.shape[0] // bq) * bq, -(-mask.shape[1] // bk) * bk)
        sees, real = np.zeros(shape, bool), np.zeros(shape, bool)
        sees[:mask.shape[0], :mask.shape[1]] = mask
        real[:mask.shape[0]] = rows
        tiles = lambda x: x.reshape(  # noqa: E731
            shape[0] // bq, bq, shape[1] // bk, bk)
        visited = tiles(sees).any((1, 3))
        edge = tiles(real & ~sees).any((1, 3)) & visited
        return int(visited.sum()), int(edge.sum())

    qi = np.arange(s)[:, None]
    bq = min(512, -(-s // 128) * 128)
    bk = min(512, -(-width // 128) * 128)
    for q_len, kv_len in ((s, width), (n_new, ctx)):
        real = qi < q_len
        own = (np.arange(s)[None, :] <= qi) & real
        over = (np.arange(width)[None, :] < kv_len) & real
        n_own, e_own = blocks(own, real, bq, bq)
        n_ctx, e_ctx = blocks(over, real, bq, bk) if width else (0, 0)
        assert prefill_block_visits(
            s, width, *(() if q_len == s and kv_len == width
                        else (q_len, kv_len))) == (
            n_own + n_ctx, n_own * bq * bq + n_ctx * bq * bk, e_own + e_ctx)


# ------------------------------------------- a window's ring a decode slot
def _banded_dense(q, k, v, positions, window):
    """Plain softmax attention of queries at `positions` [S] over keys
    0..len(k)-1, each seeing the `window` keys up to its own place."""
    rep = q.shape[1] // k.shape[1]
    kr, vr = (jnp.repeat(x, rep, axis=1) for x in (k, v))
    logits = jnp.einsum("qhd,khd->hqk", q, kr) * q.shape[-1] ** -0.5
    j = jnp.arange(k.shape[0])[None, :]
    see = (j <= positions[:, None]) & (j > positions[:, None] - window)
    p = jax.nn.softmax(jnp.where(see[None], logits, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, vr)


# passes (their lengths), then decode steps: under, across and far past the
# window; a pass boundary inside a band; a second pass shorter than the
# window; a pass that is several windows long
RING_CASES = {
    "under-the-window": ((20,), 5),
    "one-pass-across": ((48,), 6),
    "boundary-inside-a-band": ((16, 16, 30), 4),
    "short-second-pass": ((40, 5), 3),
    "far-past": ((96, 64, 33), 40),
    "window-starts-mid-page": ((37,), 9),
}


@pytest.mark.parametrize("impl", [None, "flash"])
@pytest.mark.parametrize("case", sorted(RING_CASES))
def test_a_ring_serves_resumed_passes_and_decode_under_the_band(case, impl):
    """A sequence prefilled in passes and then decoded through ONE slot's
    ring of the window (two pages of 16, layer 1 of 3, slot 2 of 4) gives,
    at every position, dense attention over the band of ALL the tokens so
    far; the other layers and slots of the pool come through bit for bit;
    the decode kernel (interpreted) agrees with the gather path over a
    window that starts mid-page."""
    from ray_tpu.ops.paged_attention import (ring_write,
                                             window_attention_decode,
                                             window_prefill_attention)

    passes, steps = RING_CASES[case]
    rng = np.random.default_rng(11)
    hq, hkv, d, page, rp, slots_n, layer, slot = 4, 2, 16, 16, 2, 4, 1, 2
    window = rp * page
    total = sum(passes) + steps
    q, k, v = (jnp.asarray(rng.standard_normal((total, h, d)), jnp.float32)
               for h in (hq, hkv, hkv))
    noise = jnp.asarray(rng.standard_normal(
        (N_LAYERS, slots_n * rp, hkv, page, 2 * d)), jnp.float32)
    win = noise
    slots = jnp.asarray([slot], jnp.int32)
    want = _banded_dense(q, k, v, jnp.arange(total), window)
    start = 0
    for n in passes:
        sb = -(-n // 16) * 16 + 16              # a padded bucket
        pad = lambda x: jnp.pad(x[start:start + n], (  # noqa: E731
            (0, sb - n), (0, 0), (0, 0)))[None]
        positions = (start + jnp.arange(sb))[None]
        lens = jnp.asarray([start + n], jnp.int32)
        got = window_prefill_attention(
            pad(q), pad(k), pad(v), win, slots, positions, lens,
            window=window, ring_pages=rp, resumes=start > 0, scale=d ** -0.5,
            impl=impl, layer=layer)
        np.testing.assert_allclose(np.asarray(got[0, :n]),
                                   np.asarray(want[start:start + n]),
                                   rtol=2e-5, atol=2e-5)
        win = ring_write(win, pad(k), pad(v), slots, positions, lens, layer,
                         rp)
        start += n
    # decode over the slot set: the other slots idle
    for t in range(start, total):
        lens = jnp.zeros((slots_n,), jnp.int32).at[slot].set(t + 1)
        place = lambda x: jnp.zeros(  # noqa: E731
            (slots_n, 1) + x.shape[1:], x.dtype).at[slot, 0].set(x[t])
        win = ring_write(win, place(k), place(v), jnp.arange(slots_n),
                         jnp.maximum(lens - 1, 0)[:, None], lens, layer, rp)
        for interpret in (None, True):
            got = window_attention_decode(
                place(q)[:, 0], win, lens, ring_pages=rp, layer=layer,
                interpret=interpret)
            np.testing.assert_allclose(np.asarray(got[slot]),
                                       np.asarray(want[t]), rtol=2e-5,
                                       atol=2e-5)
            assert not np.asarray(got)[np.arange(slots_n) != slot].any()
    mine = np.zeros(noise.shape, bool)
    mine[layer, slot * rp:(slot + 1) * rp] = True
    np.testing.assert_array_equal(np.asarray(win)[~mine],
                                  np.asarray(noise)[~mine])


def test_a_passes_padding_and_an_idle_row_write_nothing_to_a_ring():
    """Positions at or past a row's length, and a row with no real token,
    leave its ring bit for bit (a masked warm-up pass, a wave's padding)."""
    from ray_tpu.ops.paged_attention import ring_write

    rng = np.random.default_rng(12)
    noise = jnp.asarray(rng.standard_normal((2, 6, 2, 16, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 48, 2, 16)), jnp.float32)
    positions = (64 + jnp.arange(48))[None]
    for total in (0, 64):
        got = ring_write(noise, k, k, jnp.asarray([1]), positions,
                         jnp.asarray([total]), 0, 2)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(noise))
    got = ring_write(noise, k, k, jnp.asarray([1]), positions,
                     jnp.asarray([64 + 3]), 0, 2)
    changed = (np.asarray(got) != np.asarray(noise)).any((2, 4))
    # three rows of slot 1's ring, layer 0: ring index 64 % 32 = 0..2
    assert changed.sum() == 3 and changed[0, 2, :3].all()


@pytest.mark.parametrize("impl", [None, "flash"])
def test_a_context_walked_in_chunks_is_the_context(impl, monkeypatch):
    """A table wider than one flash call may hold resident (here two
    block-table columns of float32 rows) is walked in chunks under a `cond`
    and equals the one call over the table's width, for rows whose context
    ends inside a chunk, on one, and a row with none."""
    from ray_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(13)
    b, hq, hkv, d, page, mp, s_new = 3, 4, 2, 16, 8, 7, 12
    ctx_lens = (40, 16, 0)
    kv_pages, bt, k_dense, v_dense, lens = _make_pages(
        rng, b=b, hkv=hkv, d=d, page=page, num_pages=40, mp=mp,
        lengths=[c + s_new for c in ctx_lens], layer=1)
    positions = jnp.stack([jnp.arange(c, c + s_new) for c in ctx_lens])
    q = jnp.asarray(rng.standard_normal((b, s_new, hq, d)), jnp.float32)
    k_new, v_new = (jnp.stack([x[i, c:c + s_new] for i, c in
                               enumerate(ctx_lens)])
                    for x in (k_dense, v_dense))

    def attend():
        return jax.make_jaxpr(lambda *a: paged_prefill_attention(
            *a, ctx_pages=mp, impl=impl, layer=1))(
            q, k_new, v_new, kv_pages, bt, positions, lens)

    whole = attend()
    monkeypatch.setattr(pa, "FLASH_RESIDENT_KV_BYTES",
                        2 * page * 2 * (2 * d * 4))
    chunked = attend()
    conds = [str(j).count(" cond[") for j in (whole, chunked)]
    assert conds == [0, 4], conds            # columns 0-1, 2-3, 4-5, 6
    args = (q, k_new, v_new, kv_pages, bt, positions, lens)
    np.testing.assert_allclose(
        np.asarray(jax.core.eval_jaxpr(chunked.jaxpr, chunked.consts,
                                       *args)[0]),
        np.asarray(jax.core.eval_jaxpr(whole.jaxpr, whole.consts, *args)[0]),
        rtol=2e-5, atol=2e-5)


def test_a_wave_of_rows_writes_and_reads_each_its_own_ring():
    """`ring_write` and `ring_context` over a batch are what they are a row
    at a time: rows of different lengths in different slots (one several
    windows long, one inside the window, one resuming mid-ring), and a
    padding row with no real token that names a REAL row's slot and must
    not touch it (one scatter: a duplicate index would be a race)."""
    from ray_tpu.ops.paged_attention import ring_context, ring_write

    rng = np.random.default_rng(14)
    layers, slots_n, rp, hkv, page, d = 2, 4, 2, 2, 16, 8
    noise = jnp.asarray(rng.standard_normal(
        (layers, slots_n * rp, hkv, page, 2 * d)), jnp.float32)
    s = 80
    k, v = (jnp.asarray(rng.standard_normal((4, s, hkv, d)), jnp.float32)
            for _ in range(2))
    slots = jnp.asarray([3, 0, 2, 3], jnp.int32)
    start = jnp.asarray([0, 32, 40, 0], jnp.int32)
    total = jnp.asarray([80, 32 + 20, 40 + 70, 0], jnp.int32)
    positions = start[:, None] + jnp.arange(s)[None]
    got = ring_write(noise, k, v, slots, positions, total, 1, rp)
    want = noise
    for i in range(4):
        want = ring_write(want, k[i:i + 1], v[i:i + 1], slots[i:i + 1],
                          positions[i:i + 1], total[i:i + 1], 1, rp)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got)[1, 6:8] != np.asarray(noise)[1, 6:8]).all()
    np.testing.assert_array_equal(np.asarray(got)[1, 2:4],
                                  np.asarray(noise)[1, 2:4])   # slot 1: idle
    # what the rings hold, in position order: row 0's last 32 of 80
    kc, vc, have = ring_context(got, slots[:3], total[:3], 1, rp)
    np.testing.assert_array_equal(np.asarray(have), [32, 32, 32])
    np.testing.assert_array_equal(np.asarray(kc[0]), np.asarray(k[0, 48:80]))
    np.testing.assert_array_equal(np.asarray(vc[2]), np.asarray(v[2, 38:70]))
    # row 1 resumed at 32 and wrote 20: positions 20..31 are the noise's
    np.testing.assert_array_equal(np.asarray(kc[1, 12:]),
                                  np.asarray(k[1, :20]))
    for i in range(3):
        one = ring_context(got, slots[i:i + 1], total[i:i + 1], 1, rp)
        np.testing.assert_array_equal(np.asarray(one[0][0]),
                                      np.asarray(kc[i]))


@pytest.mark.parametrize("s, n_new, ctx, window", [
    (4096, 4096, 0, 1024), (4096, 2084, 0, 1024), (4096, 4096, 8192, 1024),
    (2048, 1842, 4100, 1024), (256, 200, 300, 1024), (512, 302, 1024, 1024),
    (1024, 1000, 16384, 1024), (640, 513, 700, 200), (256, 256, 96, 32),
    (128, 0, 0, 1024)])
def test_prefill_block_visits_under_a_window(s, n_new, ctx, window):
    """`prefill_block_visits` with `window` against a brute-force count
    over the tiles of the two calls `window_prefill_attention` makes (own
    tokens; the ring, min(ctx, window) of `window` columns real): visited
    where a real row may attend a real key, an edge visit where a real row
    may not attend some key of a visited block. At the cell's pass behind
    8192 tokens a sliding layer visits 21 + 3 blocks where a full layer
    visits 36 + 128."""
    from ray_tpu.ops.paged_attention import prefill_block_visits

    width = 33792 if ctx else 0
    got = prefill_block_visits(s, width, n_new, ctx, window)
    bq = min(512, -(-s // 128) * 128)
    qi = np.arange(s)[:, None]
    real = qi < n_new
    visits = masked = pairs = 0
    have = min(ctx, window)
    for sk, woff, live in ((s, 0, s), (window if ctx else 0, have, have)):
        if not sk:
            continue
        bk = bq if sk == s and woff == 0 and live == s else min(
            512, -(-sk // 128) * 128)
        kj = np.arange(sk)[None, :]
        see = (kj > qi + woff - window) & (kj < live) & real
        if live == s and woff == 0:
            see &= kj <= qi
        shape = (-(-s // bq) * bq, -(-sk // bk) * bk)
        sees, rows = np.zeros(shape, bool), np.zeros(shape, bool)
        sees[:s, :sk], rows[:s] = see, real
        tiles = lambda x: x.reshape(  # noqa: E731
            shape[0] // bq, bq, shape[1] // bk, bk)
        visited = tiles(sees).any((1, 3))
        # every tile from the first to the last a block's real rows see
        span = np.maximum.accumulate(visited, 1) & np.maximum.accumulate(
            visited[:, ::-1], 1)[:, ::-1]
        assert (span == visited).all()
        visits += int(visited.sum())
        pairs += int(visited.sum()) * bq * bk
        masked += int((tiles(rows & ~sees).any((1, 3)) & visited).sum())
    assert got == (visits, pairs, masked)
    if (s, n_new, ctx, window) == (4096, 4096, 8192, 1024):
        assert got[0] == 21 + 3
        assert prefill_block_visits(s, width, n_new, ctx)[0] == 36 + 128
