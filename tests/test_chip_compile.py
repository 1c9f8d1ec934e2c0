"""Compile the main path's Pallas kernels and the trainer's step for a
described (not attached) TPU v5e, at real widths. (The serving engine's own
programs: tests/test_chip_compile_engines.py, a file of its own so that a
worker takes one and another the other.)

Interpret-mode tests cannot see what Mosaic refuses: misaligned slices,
too much VMEM, a kernel GSPMD cannot partition. The TPU compiler is
installed here and compiles for a `v5e:2x2` that is only described, so
each case costs a second or two and no chip time. Nothing runs: a compile
that passes says nothing about results or speed.

The topology (`topo`) and the switch that keeps these compiles out of the
persistent cache (`no_persistent_cache`) are conftest.py's.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.flash_attention import (flash_attention,
                                         flash_attention_sharded)
from ray_tpu.ops.paged_attention import paged_attention_decode
from ray_tpu.parallel.mesh import AXES

BF16 = jnp.bfloat16


def _qkv(shape, sharding):
    b, s, hq, hkv, d = shape
    return tuple(jax.ShapeDtypeStruct((b, s, h, d), BF16, sharding=sharding)
                 for h in (hq, hkv, hkv))


def _fwd(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)


def _grads(attn):
    def fn(q, k, v):
        return jax.grad(lambda *a: attn(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)
    return fn


def _lse(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False,
                           return_lse=True)


def _flash(fn, shape):
    return lambda topo: (fn, _qkv(shape, SingleDeviceSharding(
        topo.devices[0])))


def _ctx_lens(sq, sk, hq=32, hkv=8, d=128, dv=None, causal=False,
              block_causal=0):
    """The context call of a resumed prefill pass as
    `paged_prefill_attention` makes it: `[1 x sq]` queries over `sk`
    gathered columns, the row's real lengths as data, no segment ids.
    `dv`: values narrower than keys (a latent family's materialised form);
    `causal`: a pass's own call instead, `block_causal` a diffusion
    family's."""
    def build(topo):
        one_chip = SingleDeviceSharding(topo.devices[0])

        def fn(q, k, v, q_lens, kv_lens):
            return flash_attention(q, k, v, causal=causal, interpret=False,
                                   return_lse=True, q_lens=q_lens,
                                   kv_lens=kv_lens,
                                   block_causal=block_causal)
        return fn, tuple(
            jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in [((1, sq, hq, d), BF16), ((1, sk, hkv, d), BF16),
                              ((1, sk, hkv, dv or d), BF16),
                              ((1,), jnp.int32), ((1,), jnp.int32)])
    return build


def _ctx_plain(sq, sk, hq=32, hkv=8, d=128):
    """A context call without lengths: every tile interior but the one
    that holds the keys' end where `sk` is no multiple of the key block."""
    def build(topo):
        def fn(q, k, v):
            return flash_attention(q, k, v, causal=False, interpret=False,
                                   return_lse=True)
        return fn, tuple(
            jax.ShapeDtypeStruct((1, s, h, d), BF16,
                                 sharding=SingleDeviceSharding(
                                     topo.devices[0]))
            for s, h in ((sq, hq), (sk, hkv), (sk, hkv)))
    return build


def _packed(s, hq=32, hkv=8, d=128):
    """Segment ids (every tile an edge tile) at a kv length whose resident
    ids need the raised VMEM limit."""
    def build(topo):
        one_chip = SingleDeviceSharding(topo.devices[0])

        def fn(q, k, v, seg):
            return flash_attention(q, k, v, causal=True, interpret=False,
                                   segment_ids=seg, return_lse=True)
        return fn, (*_qkv((1, s, hq, hkv, d), one_chip),
                    jax.ShapeDtypeStruct((1, s), jnp.int32,
                                         sharding=one_chip))
    return build


def _paged_decode(b, d, mp, hq=32, hkv=8, page=16, layers=4,
                  pages_per_chunk=None):
    """The compiled kernel as the engine calls it: `pages_per_chunk=None`
    is `paged_attention_decode`'s own rule for the item size."""
    def build(topo):
        one_chip = SingleDeviceSharding(topo.devices[0])

        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        def fn(q, kv_pages, block_tables, lengths, layer):
            return paged_attention_decode(
                q, kv_pages, block_tables, lengths, layer=layer,
                pages_per_chunk=pages_per_chunk, interpret=False)
        return fn, (sds((b, hq, d), BF16),
                    sds((layers, b * mp, hkv, page, 2 * d), BF16),
                    sds((b, mp), jnp.int32), sds((b,), jnp.int32),
                    sds((), jnp.int32))
    return build


def _rope_rows(b, s, h, d=128):
    """The rotation over rows of `[B, S, H x D]` (`ops/rotary.py:
    rotate_rows`), forward and turned back: `pltpu.roll` by half a head on
    a head's lanes, a block of whole heads."""
    def build(topo):
        from ray_tpu.ops.rotary import rotate_rows

        one_chip = SingleDeviceSharding(topo.devices[0])

        def fn(x, cos, sin):
            return jax.value_and_grad(lambda x: rotate_rows(
                x, cos, sin, interpret=False).astype(jnp.float32).sum())(x)
        return fn, (
            jax.ShapeDtypeStruct((b, s, h, d), BF16, sharding=one_chip),
            *(jax.ShapeDtypeStruct((b, s, d // 2), jnp.float32,
                                   sharding=one_chip),) * 2)
    return build


def _head_argmax(rows, h, v):
    """The head of a greedy block pass (ops/head_argmax.py): `rows`
    final-normed hidden states on the `lm_head` weights [h, v], at the
    tile its rule gives (`vocab_tile`), no `vmem_limit_bytes` asked."""
    def build(topo):
        from ray_tpu.ops import head_argmax as ha

        one_chip = SingleDeviceSharding(topo.devices[0])
        return (lambda x, w: ha.head_argmax(x, w, impl="pallas"),
                (jax.ShapeDtypeStruct((rows, h), BF16, sharding=one_chip),
                 jax.ShapeDtypeStruct((h, v), BF16, sharding=one_chip)))
    return build


def _moe_gmm(m, experts=8, h=4096, f=14336, layers=3, grads=False):
    """The expert FFN's two grouped matmuls as `MoEMLP._dropless` calls
    them on a TPU, for `m` assignments at Mixtral's widths (or the `h`,
    `f` and `experts` of another model) in the rows their layout takes
    (one call's: at most 4096), the weights a [layers, experts, ...] stack
    read in place: the tile is the rule's (`grouped_matmul.row_tile`,
    `_tile`), no `vmem_limit_bytes` asked. `grads`: the trainer's backward
    through both (the gradients to the rows and to the stacks: `gmm`
    transposed and `tgmm`, each at its own tile)."""
    def build(topo):
        from ray_tpu.ops import grouped_matmul as gm

        one_chip = SingleDeviceSharding(topo.devices[0])
        tm, aligned = gm.row_tile(m, experts)
        rows = min(gm.aligned_rows(m, experts, tm) if aligned else m, 4096)

        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        def fn(x, w_gu, w_dn, sizes, layer):
            def product(lhs, stack):
                return gm._moe_gmm(lhs, *gm.stacked_groups(stack, sizes,
                                                           layer),
                                   tm=tm, impl="megablox")
            gate, up = jnp.split(product(x, w_gu), 2, axis=-1)
            return product(jax.nn.silu(gate) * up, w_dn)
        if grads:
            fn = jax.grad(lambda *a, fwd=fn: fwd(*a).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))
        return fn, (sds((rows, h), BF16),
                    sds((layers, experts, h, 2 * f), BF16),
                    sds((layers, experts, f, h), BF16),
                    sds((experts,), jnp.int32), sds((), jnp.int32))
    return build


def _flash_on_mesh(topo):
    """The trainer's path on several chips: GSPMD cannot partition the
    kernel, so it goes through the shard_map wrapper (batch over fsdp,
    heads over tp)."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 2, 1, 1, 2), AXES)
    spec = NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None))
    return _grads(lambda q, k, v: flash_attention_sharded(
        q, k, v, mesh, causal=True, interpret=False)), _qkv(
            (4, 2048, 32, 8, 64), spec)


# id -> build(topo) -> (fn, argument shapes); shapes are (B, S, Hq, Hkv, D)
COMPILES = {
    "fwd-llama1b-train": _flash(_fwd, (3, 2048, 32, 8, 64)),
    "fwdbwd-llama1b-train": _flash(_grads(_fwd), (3, 2048, 32, 8, 64)),
    "fwd-d128-4k": _flash(_fwd, (1, 4096, 32, 8, 128)),
    "fwdbwd-d128-4k": _flash(_grads(_fwd), (1, 4096, 32, 8, 128)),
    "fwd-lse-prefill-bucket": _flash(_lse, (4, 512, 32, 8, 64)),
    # a resumed pass's context part at the two Mistral cells' widths
    "fwd-lse-lens-ctx-kv2688": _ctx_lens(512, 2688),
    "fwd-lse-lens-ctx-kv8320": _ctx_lens(2048, 8320),
    # the forward's two tile bodies (PR 45) at the other cells' shapes: the
    # pretrain step's call, a context of 2688 columns without lengths (its
    # last key block holds the keys' end), a latent family's own call and
    # a chunk of its context (keys of 192, values of 128), packed rows
    "fwd-lse-pretrain-kv2048": _flash(_lse, (4, 2048, 32, 8, 128)),
    "fwd-lse-ctx-kv2688-plain": _ctx_plain(512, 2688),
    "fwd-lse-lens-mla-own-4096": _ctx_lens(4096, 4096, 64, 64, 192, 128,
                                           causal=True),
    "fwd-lse-lens-mla-ctx-4096": _ctx_lens(4096, 4096, 64, 64, 192, 128),
    "fwd-lse-packed-kv8192": _packed(8192),
    # a diffusion family's own call (the diagonal's mask by blocks of 4
    # positions, `(q_pos + offset) // 4` on a lane row): a whole query
    # block, and a chat bucket's, whose grid step folds 2 of a kv head's 8
    # query heads along the lanes
    "fwd-lse-lens-blockcausal4-own-2048": _ctx_lens(
        2048, 2048, 32, 4, causal=True, block_causal=4),
    "fwd-lse-lens-blockcausal4-own-256": _ctx_lens(
        256, 256, 32, 4, causal=True, block_causal=4),
    # query blocks under 512 rows, their heads folded along the lanes: a
    # 128 bucket over a deep context (4 heads a step), one kv head's 20
    # query heads (4 a step), packed rows of 256 (2 a step)
    "fwd-lse-lens-ctx-128-kv2688": _ctx_lens(128, 2688),
    "fwd-lse-lens-own-128-20-heads": _ctx_lens(128, 128, 20, 1, causal=True),
    "fwd-lse-packed-256": _packed(256),
    "decode-llama1b-B8-D64-MP32": _paged_decode(8, 64, 32),
    "decode-8b-B8-D128-MP512": _paged_decode(8, 128, 512),
    "decode-7b-B32-D128-MP168": _paged_decode(32, 128, 168),
    # `laguna-xs2-agentturns`: a full layer's 48 query heads on 8 kv heads,
    # a group of SIX rows (no whole 8-row sublane tile), 64 slots behind a
    # 272-page table of 64-token pages; the same group through the flash
    # forward, own tokens and a context, at the largest bucket and the
    # smallest (`_fold(6, 512)` is 1: a grid step is one query head)
    "decode-laguna-full-B64-rep6-MP272": _paged_decode(
        64, 128, 272, hq=48, hkv=8, page=64, layers=2),
    "fwd-lse-lens-laguna-own-4096-rep6": _ctx_lens(4096, 4096, 48, 8,
                                                   causal=True),
    "fwd-lse-lens-laguna-ctx-4096-rep6": _ctx_lens(4096, 8192, 48, 8),
    "fwd-lse-lens-laguna-own-512-rep6": _ctx_lens(512, 512, 48, 8,
                                                  causal=True),
    "fwdbwd-shard_map-2x2-mesh": _flash_on_mesh,
    # the rotation beside them (PR 60): the pretrain step's q and k, a
    # [1 x 128] bucket of 48 heads, a wave of 20 heads on one kv head
    "rope-rows-pretrain-q": _rope_rows(4, 2048, 32),
    "rope-rows-pretrain-k": _rope_rows(4, 2048, 8),
    "rope-rows-bucket128-48-heads": _rope_rows(1, 128, 48),
    "rope-rows-wave-16x2048-20-heads": _rope_rows(16, 2048, 20),
    # the one-pass backward asks for the VMEM its shapes need (PR 44), so it
    # compiles as far as the forward does: these were refused at 8192
    "fwdbwd-kv8192": _flash(_grads(_fwd), (1, 8192, 32, 8, 64)),
    "fwdbwd-kv16384-d128": _flash(_grads(_fwd), (1, 16384, 32, 8, 128)),
    # `mixtral-chat`: a decode step's 32 slots x 2 experts, and a prompt's
    # [1 x bucket] pass at the four buckets whose tile is not decode's
    "moe-gmm-mixtral-decode-M64": _moe_gmm(64),
    "moe-gmm-mixtral-bucket256-M512": _moe_gmm(512),
    "moe-gmm-mixtral-bucket512-M1024": _moe_gmm(1024),
    "moe-gmm-mixtral-bucket1024-M2048": _moe_gmm(2048),
    "moe-gmm-mixtral-bucket2048-M4096": _moe_gmm(4096),
    # widths that `tk` 1024 / `tn` 8 * tm do not divide, at the tile fitted
    # to them (PR 48). `mellum2-mixedctx`: a decode step's 64 slots x 8 on
    # 64 experts, (128, 2304, 896) then (128, 896, 2304); one 4096-row
    # block of a [1 x 4096] pass, the same at tm 256. `sdar-30b-a3b-chat`:
    # a block step's 256 tokens x 8 on 128 experts, (128, 2048, 1536), 14.5
    # of the 16 MiB by `tile_vmem_bytes`
    "moe-gmm-mellum2-decode-M512": _moe_gmm(512, 64, 2304, 896, 8),
    "moe-gmm-mellum2-pass4096-M32768": _moe_gmm(32768, 64, 2304, 896, 8),
    "moe-gmm-sdar-block-M2048": _moe_gmm(2048, 128, 2048, 768, 6),
    # `laguna-xs2-agentturns`: a decode step's 64 slots x 8 on 256 experts
    # of (2048, 512), and one 4096-row block of a pass
    "moe-gmm-laguna-decode-M512": _moe_gmm(512, 256, 2048, 512, 4),
    "moe-gmm-laguna-pass4096-M32768": _moe_gmm(32768, 256, 2048, 512, 4),
    # the same block step's head, decided in its vocabulary tiles: 64 slots
    # x 4 positions on [2048, 151936], 148 tiles of 1024 columns and one of
    # 384 (128 x 1187 has no larger tile that divides it)
    "head-argmax-sdar-block-R256": _head_argmax(256, 2048, 151936),
    # the backward: refused for VMEM at tm 256 before PR 48 (tgmm held the
    # forward's [1024, 2048] tile twice over), and at a fitted tile
    "moe-gmm-grads-mixtral-M4096": _moe_gmm(4096, layers=1, grads=True),
    "moe-gmm-grads-mellum2-M512": _moe_gmm(512, 64, 2304, 896, 1,
                                           grads=True),
}
# The kernel's measured compile limits: K/V of one (batch, kv head) stay
# resident in VMEM, so long kv is refused: forward and backward pass at
# 16384 and the forward is refused at 32768 (the backward's kernel would
# need 170 MB there, more than the chip's 128 MiB). The PR that tiles K/V
# flips these knowingly.
REFUSED = {
    "fwd-kv32768-refused": _flash(_fwd, (1, 32768, 32, 8, 64)),
    # the decode kernel's work item: its own rule gives 32 pages (2 MiB a
    # buffer) at these widths and 64 still compile; 128 are two buffers of
    # 8 MiB, all the VMEM a kernel may scope
    "decode-8b-128-pages-an-item-refused": _paged_decode(
        8, 128, 512, pages_per_chunk=128),
}


@pytest.mark.parametrize("name", [*COMPILES, *REFUSED])
def test_kernel_compiles_for_v5e(topo, no_persistent_cache, name):
    fn, args = {**COMPILES, **REFUSED}[name](topo)
    lowered = jax.jit(fn).lower(*args)
    if name in REFUSED:
        with pytest.raises(Exception, match="(?i)vmem"):
            lowered.compile()
    else:
        assert "tpu_custom_call" in lowered.compile().as_text()
    if "-lens-" in name or name.startswith(("moe-gmm", "head-argmax")):
        # K and V resident and nothing beside them: inside the VMEM a
        # kernel gets unasked, where the segment ids it replaces were not
        # (a raised `vmem_limit_bytes` lowers to `scoped_memory_configs`)
        assert "scoped_memory_configs" not in lowered.as_text()


@pytest.mark.parametrize("policy, kernels", [("dots", 2), ("nothing", 3)])
def test_the_trainers_step_runs_two_kernels_a_layer(topo, policy, kernels):
    """`ShardedTrainer`'s step program for a described chip, the pretrain
    cell's form (scanned layers, remat under the `dots` policy) at tiny
    widths: a layer body's forward and its backward are ONE
    `tpu_custom_call` each. Until PR 57 there was a third, the forward
    recomputed inside the backward scan: `dots` saved products only, and
    the kernel's `o` and `lse` are none. The forward rule now names them
    (`ops/flash_attention.py: SAVED_OUTPUTS`) and `"dots"` lists the names
    (`models/llama.py: _remat_policy`), so the backward reads what the
    forward left and the recomputed call is dead: the count is the evidence
    that the saved outputs are the ones the backward reads. `"nothing"`
    lists no name and still runs three. Before PR 44 the backward was two
    kernels (dq; dk/dv), four a layer."""
    import flax.linen as nn

    from ray_tpu.models.llama import LlamaModel, get_config
    from ray_tpu.parallel.train_lib import ShardedTrainer, TrainState

    cfg = get_config("tiny", remat=True, remat_policy=policy)
    assert cfg.scan_layers
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape((1,) * len(AXES)), AXES)
    trainer = ShardedTrainer(LlamaModel(cfg), mesh)
    ids = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    batch = {"input_ids": ids}

    def init(rng):  # `ShardedTrainer.init`, which runs what it builds
        params = nn.meta.unbox(trainer.model.init(
            rng, jnp.zeros(ids.shape, ids.dtype))["params"])
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=trainer.tx.init(params))

    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        state, trainer.state_shardings(batch))
    # the ops choose kernel or reference by the backend, at trace time
    with pytest.MonkeyPatch.context() as mp_ctx:
        mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
        text = trainer.program_text(state, batch)
    assert text.count("tpu_custom_call") == kernels, \
        text.count("tpu_custom_call")


def test_the_trainers_step_moves_no_head_around_its_attention(
        topo, no_persistent_cache):
    """The same step at heads of 128, COMPILED: q, k, v, `o` and their
    gradients are read and written as rows of `[B, S, H x D]`, where the
    projections leave them. Until PR 60 the flash kernels blocked `[B, H,
    S, D]`, and XLA joined the transposes to and from it with `rope`'s
    float32 arithmetic: eleven transposing copies a step at the pretrain
    cell's shapes, and three copies a layer of the kept `o`, whose two
    readers (the o projection, `delta`) wanted two layouts. Now a head is a
    lane block of the kernels' operands (`ops/flash_attention.py:
    _heads_on_lanes`) and the rotation a kernel over the same rows
    (`ops/rotary.py: rotate_rows`; XLA's own layout of `[B, S, H, D]` keeps
    H on the sublanes, so the rotation written in jax.numpy is a copy in
    and a copy out). So the compiled step holds no `copy` and no
    `transpose` of an array of q's or k's size, in the forward scan's body
    or the backward's; a layer is two `attn` calls and six rotations (q and
    k: forward, recomputed, turned back for dq and dk); and the kept `o`
    is ONE stacked array of rows."""
    import flax.linen as nn

    from ray_tpu.models.llama import LlamaModel, get_config
    from ray_tpu.parallel.train_lib import ShardedTrainer, TrainState

    b, s, hq, hkv, d, layers = 2, 256, 4, 1, 128, 2
    # (widths at which no other array has q's or k's number of elements)
    cfg = get_config("tiny", remat=True, remat_policy="dots", head_dim=d,
                     num_heads=hq, num_kv_heads=hkv, hidden_size=192,
                     intermediate_size=320, vocab_size=384, num_layers=layers)
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape((1,) * len(AXES)), AXES)
    trainer = ShardedTrainer(LlamaModel(cfg), mesh)
    ids = jax.ShapeDtypeStruct((b, s), jnp.int32)
    batch = {"input_ids": ids}

    def init(rng):
        params = nn.meta.unbox(trainer.model.init(
            rng, jnp.zeros(ids.shape, ids.dtype))["params"])
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=trainer.tx.init(params))

    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        state, trainer.state_shardings(batch))
    with pytest.MonkeyPatch.context() as mp_ctx:
        mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
        text = trainer.program_text(state, batch, compiled=True)

    def elements(type_text):
        return int(np.prod([int(n) for n in type_text.split(",") if n]))

    moved = [line.strip()[:160] for line in text.splitlines()
             for m in [re.search(
                 r" = \w+\[([\d,]*)\]\S* (copy|transpose)\(", line)]
             if m and elements(m.group(1)) in (b * s * hq * d, b * s * hkv * d,
                                               layers * b * s * hq * d)]
    assert not moved, moved
    calls = re.findall(r"%([\w.-]+?)\.\d+ = [^=]*custom-call\([^)]*\), "
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert sorted(calls) == ["_rotate_rows"] * 6 + ["attn"] * 2, calls
    # what the layer scan keeps of q's size a layer: `o`, once, as rows
    kept = {m.group(0) for m in re.finditer(
        r"bf16\[%d,[\d,]+\]" % layers, text)
        if elements(m.group(0)[5:-1]) == layers * b * s * hq * d}
    assert kept == {f"bf16[{layers},{b},{s},{hq * d}]"}, kept
