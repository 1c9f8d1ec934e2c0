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

    g_got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
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
            flash_attention, causal=False, interpret=False,
            return_lse=return_lse), q, k, v)
        assert len(call.invars) == 5
        assert call.params["grid_mapping"].num_index_operands == 0
    call, = _kernel_calls(lambda q, k, v, n: flash_attention(
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
    ("plain", "38539c9e0e3b2c09"), ("packed", "40fe6a56360f4f41"),
    ("lse", "bdb7d7bc1c8fca77")])
def test_the_trainers_call_traces_to_the_jaxpr_it_had(case, sha):
    """The calls that pass no lengths, forward and backward kernels at the
    pretrain cell's shapes: the jaxpr is pinned to the character
    (`train_tok_s` has a bound of 1%). `lse`, the forward alone, which is
    all that serving runs, is PR 37's; `plain` and `packed` hold the
    backward and were read anew by PR 44, which replaced its two kernels
    with one (a4fed4a4e0c528f1 and 032ab778af83649d until then). A
    deliberate change of the kernels reads them anew."""
    q = jnp.zeros((2, 2048, 32, 128), jnp.bfloat16)
    k = v = jnp.zeros((2, 2048, 8, 128), jnp.bfloat16)

    def loss(q, k, v, seg=None):
        return flash_attention(q, k, v, causal=True, segment_ids=seg,
                               interpret=False).astype(jnp.float32).sum()

    if case == "lse":
        got = _jaxpr_sha(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False, return_lse=True), q, k, v)
    else:
        seg = (jnp.zeros((2, 2048), jnp.int32),) if case == "packed" else ()
        got = _jaxpr_sha(jax.grad(loss, argnums=(0, 1, 2)), q, k, v, *seg)
    assert got == sha
