"""Compile the main path's Pallas kernels, and the serving engine's own
programs, for a described (not attached) TPU v5e, at real widths.

Interpret-mode tests cannot see what Mosaic refuses: misaligned slices,
too much VMEM, a kernel GSPMD cannot partition. The TPU compiler is
installed here and compiles for a `v5e:2x2` that is only described, so
each case costs a second or two and no chip time. Nothing runs: a compile
that passes says nothing about results or speed. What a compiled module
does say is where the KV pool goes: the engine's programs are read for
whole-pool copies (`test_engine_program_keeps_the_pool_in_place`).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under xdist every
worker imports every test file.
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


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip (the next one warns):
    keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


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


# ------------------------------------------- the engine's own programs
# Mistral-7B widths. "short": 2 layers of the chat cell's sizes; 2200
# pages, so that one layer of the pool (144 MB) is more than the chip's
# 128 MiB of VMEM, as in a deployment: a pool that fits there is
# prefetched whole, which reads as a copy. "long": the docbatch cell's
# configuration at its full 16 layers, where the flash kernel's resident
# K, V and segment ids are largest (kv 8320).
# "mixtral": the `mixtral-chat` cell's configuration, Mixtral-8x7B widths
# (8 experts of width 14336, top-2) at its 3 layers, 2800 pages and 32
# slots: the `[32 x 1]` decode program and the `[16 x 2048]` wave.
HKV, PAGE, HEAD_DIM = 8, 16, 128
MISTRAL = dict(num_heads=32, num_kv_heads=HKV, head_dim=HEAD_DIM,
               hidden_size=4096, intermediate_size=14336, vocab_size=32768,
               rope_theta=1e6)
ENGINES = {
    "short": dict(layers=2, pages=2200, max_model_len=2688, buckets=(8, 128)),
    "long": dict(layers=16, pages=1900, max_model_len=8320, buckets=(4096,)),
    "mixtral": dict(layers=3, pages=2800, max_model_len=2688,
                    buckets=(128, 2048), max_batch=32, model="mixtral-8x7b",
                    widths={}),
}
# a pipeline's stages run the same programs over a slice of the layers
# (serve/llm/stage.py): name -> (engine, first layer, layers)
STAGES = {"short-first": ("short", 0, 1), "short-last": ("short", 1, 1),
          "mixtral-first": ("mixtral", 0, 2)}
HBM_GIB = 15.75     # what a program may use of a v5e's 16 GB
MOVES = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice",
         "slice", "transpose", "concatenate")
# program -> (engine, kind, shape key given (wave rows, pages per sequence))
ENGINE_PROGRAMS = {
    "decode-S1": ("short", "decode", lambda rb, mp: (1, mp)),
    "prefill-bucket128": ("short", "prefill", lambda rb, mp: (128, rb, 0)),
    "prefill-bucket128-prefix-hit":
        ("short", "prefill", lambda rb, mp: (128, rb, mp)),
    "verify-unaligned-span8": ("short", "verify", lambda rb, mp: (8, rb)),
    "prefill-bucket4096-prefix-hit-kv8320":
        ("long", "prefill", lambda rb, mp: (4096, rb, mp)),
    "mixtral-decode-32x1": ("mixtral", "decode", lambda rb, mp: (1, mp)),
    "mixtral-prefill-16x2048":
        ("mixtral", "prefill", lambda rb, mp: (2048, rb, 0)),
    # the programs most of the cells' waves take (a chat prompt's median
    # is 256 tokens, a document prefills with no prefix): one row a pass
    "prefill-bucket4096-kv8320":
        ("long", "prefill", lambda rb, mp: (4096, rb, 0)),
    "mixtral-prefill-16x128-prefix-hit":
        ("mixtral", "prefill", lambda rb, mp: (128, rb, mp)),
    # stages: hidden states in place of ids going in, or of tokens coming out
    "decode-S1-first-stage": ("short-first", "decode", lambda rb, mp: (1, mp)),
    "decode-S1-last-stage": ("short-last", "decode", lambda rb, mp: (1, mp)),
    "prefill-bucket128-first-stage":
        ("short-first", "prefill", lambda rb, mp: (128, rb, 0)),
    "prefill-bucket128-prefix-hit-last-stage":
        ("short-last", "prefill", lambda rb, mp: (128, rb, mp)),
    "mixtral-prefill-16x128-first-stage":
        ("mixtral-first", "prefill", lambda rb, mp: (128, rb, 0)),
}


@pytest.fixture(scope="module")
def engines():
    """name -> an `LLMEngine` whose params are shapes only: its stage's
    `program` builds the real `run_decode` / `run_prefill` / `run_verify`,
    nothing runs. Its own pool is two pages; a program takes the pool's size from
    its argument, which the test gives at `ENGINES[name]["pages"]`."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.serve.llm.stage import StageCompute, init_params

    def build(layers, pages, max_model_len, buckets, max_batch=8,
              model="llama3-8b", widths=MISTRAL, stage=None):
        cfg = EngineConfig(
            model=model, dtype="bfloat16", page_size=PAGE,
            num_pages=2, max_model_len=max_model_len, max_batch=max_batch,
            prefill_buckets=buckets,
            model_overrides=dict(num_layers=layers, **widths))
        eng = LLMEngine(cfg, params={})
        if stage:
            eng.compute = StageCompute(cfg, *stage, params={})
        c = eng.compute
        x = (jnp.zeros((1, 8), jnp.int32) if c.first
             else jnp.zeros((1, 8, c.model_cfg.hidden_size), BF16))
        c.params = jax.eval_shape(
            lambda: init_params(c.model, x, jax.random.PRNGKey(0)))
        return eng

    out = {name: build(**sizes) for name, sizes in ENGINES.items()}
    out.update({name: build(**ENGINES[which], stage=(lo, n))
                for name, (which, lo, n) in STAGES.items()})
    return out


def _program_args(kind, shape_key, rows, mp, sds, hidden=None):
    """Shapes of a program's arguments after (params, kv_pages); `hidden`
    is the width of the hidden states a stage that is not first takes in
    place of ids."""
    i32, f32 = jnp.int32, jnp.float32

    def x(span):
        return (sds((rows, span), i32) if hidden is None
                else sds((rows, span, hidden), BF16))

    if kind == "decode":
        return (sds((rows, 1), i32), sds((rows, mp), i32), sds((rows,), i32),
                sds((rows,), i32), sds((rows, 1), i32),
                sds((rows,), jnp.bool_), x(1),
                sds((rows,), f32), sds((rows,), i32),
                sds((shape_key[0], rows, 2), jnp.uint32))
    span = shape_key[0]
    # a prefill or a verify takes the number of real rows first: it loops
    # over them
    args = (sds((), i32), sds((rows, mp), i32), sds((rows,), i32), x(span),
            sds((rows, span), i32))
    if kind == "verify":
        return args
    return args + (sds((rows,), i32), sds((rows,), f32), sds((rows,), i32),
                   sds((rows, 2), jnp.uint32))


def _array_types(type_text):
    """[(dims, minor-to-major)] of every array in an HLO type."""
    return [(tuple(map(int, dims.split(","))) if dims else (),
             tuple(map(int, m2m.split(","))) if m2m else ())
            for dims, m2m in re.findall(r"\w+\[([\d,]*)\]\{([\d,]*)",
                                        type_text)]


def _check_expert_program(cfg, tokens, compiled, text):
    """An expert model's program fits the chip, runs the grouped matmul
    kernel, holds no capacity dispatch tensor and copies no layer's stack
    of expert weights (a slice handed to a kernel would be one)."""
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    assert re.search(r"%_moe_gmm\.\d+ = [^\n]*tpu_custom_call", text)
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    group = min(cfg.moe_group_size, tokens)
    capacity = int(cfg.capacity_factor * k * group / E)
    h, f = cfg.hidden_size, cfg.intermediate_size
    for dims, _ in _array_types(text):
        assert dims[-2:] != (E, capacity), dims      # [G, g, E, C] one-hot
        assert dims not in ((E, h, 2 * f), (E, f, h)), dims


@pytest.fixture(scope="module")
def compiled_programs(topo, no_persistent_cache, engines):
    """name -> (engine, kind, shape key, rows, pool dims, compiled, its
    text), each program compiled once for the cases that read it."""
    done = {}

    def get(name):
        if name in done:
            return done[name]
        one_chip = SingleDeviceSharding(topo.devices[0])

        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        which, kind, key = ENGINE_PROGRAMS[name]
        engine = engines[which]
        mp = engine.max_pages_per_seq
        rows = (engine.config.max_batch if kind == "decode"
                else engine._wave_rb)
        shape_key = key(rows, mp)
        stage = engine.compute
        pool_dims = (stage.n_layers, ENGINES[STAGES.get(
            which, (which,))[0]]["pages"], HKV, PAGE, 2 * HEAD_DIM)
        # the ops choose kernel or reference by the backend, at trace time
        with pytest.MonkeyPatch.context() as mp_ctx:
            mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
            compiled = stage.program(kind, shape_key).lower(
                jax.tree.map(lambda a: sds(a.shape, a.dtype), stage.params),
                sds(pool_dims, BF16),
                *_program_args(
                    kind, shape_key, rows, mp, sds,
                    None if stage.first else stage.model_cfg.hidden_size),
            ).compile()
        done[name] = (engine, kind, shape_key, rows, pool_dims, compiled,
                      compiled.as_text())
        return done[name]

    return get


@pytest.mark.parametrize("name", list(ENGINE_PROGRAMS))
def test_engine_program_keeps_the_pool_in_place(compiled_programs, name):
    """The donated pool is ONE buffer from argument to result: no
    instruction that moves data has an output of its size or a layer's,
    it is row-major wherever it appears (the layout the decode kernel's
    custom call demands, so nothing re-lays it out), and the argument is
    aliased to the result."""
    engine, kind, shape_key, rows, pool_dims, compiled, text = \
        compiled_programs(name)
    layer_bytes = 2 * int(np.prod(pool_dims[1:]))
    pool_bytes = (layer_bytes, pool_dims[0] * layer_bytes)  # in any shape
    if engine.model_cfg.num_experts:
        _check_expert_program(engine.model_cfg, rows * shape_key[0]
                              if kind == "prefill" else rows, compiled, text)

    assert "tpu_custom_call" in text
    moved, layouts, pool_param = [], set(), None
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if not m:
            continue
        arrays = _array_types(m.group(1))
        if m.group(2) in MOVES and any(
                2 * int(np.prod(dims)) in pool_bytes for dims, _ in arrays):
            moved.append(line.strip()[:160])
        layouts.update(m2m for dims, m2m in arrays if dims == pool_dims)
        # the entry computation's parameters are the ones with a sharding
        entry = re.search(r" parameter\((\d+)\), sharding=", line)
        if entry and arrays[0][0] == pool_dims:
            pool_param = int(entry.group(1))
    assert not moved, "\n".join(moved)
    assert layouts == {(4, 3, 2, 1, 0)}, layouts
    header = text.split("\n", 1)[0]
    assert pool_param is not None
    assert re.search(r"input_output_alias=\{[^\n]*\(%d, \{\}, (may|must)-alias\)"
                     % pool_param, header), header[:300]


@pytest.mark.parametrize("name", [n for n, (_, kind, _) in
                                  ENGINE_PROGRAMS.items()
                                  if kind in ("prefill", "verify")])
def test_prefill_program_computes_one_row_a_pass(compiled_programs, name):
    """A wave computes its real rows in a loop, one row a pass: the
    program holds a while loop beside the layer scan's, and no
    activation at the wave's width (the padded `[16, 2048, 32000]` head
    and `[16, 2048, 28672]` MLP of the program that padded every wave
    to its size). Its inputs, `[rows, span]` ids and positions, stay; so
    do the `[rows, span, hidden]` states between a pipeline's stages,
    which the loop reads and writes a row at a time. A speculative verify
    is the same loop."""
    engine, _, shape_key, rows, _, _, text = compiled_programs(name)
    span = shape_key[0]
    assert rows > 1
    stage = engine.compute
    between = set() if stage.first and stage.last else {
        (rows, span, stage.model_cfg.hidden_size)}
    wide = set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if m:
            wide.update(dims for dims, _ in _array_types(m.group(1))
                        if len(dims) > 2 and dims[:2] == (rows, span))
    assert not wide - between, wide
    # the row loop and the layer scan inside it (a scan over one layer
    # is no loop)
    assert len(re.findall(r" while\(", text)) >= 1 + (stage.n_layers > 1)


# ------------------------------- a model with state-space layers, whole
@pytest.mark.parametrize("kind, span", [("decode", 1), ("prefill", 2048)])
def test_jamba_program_fits_and_carries_both_pools_in_place(
        topo, no_persistent_cache, kind, span):
    """AI21-Jamba2-3B at its published size, all 28 layers, as the cell
    `jamba2-3b-chat` runs it (64 slots, 10,753 pages): the program fits
    the chip, holds the selective-scan kernel (prefill) and the paged
    decode kernel at 20 q heads on 1 kv head (decode), aliases the pages
    and both state arrays from argument to result, and moves no array as
    large as a state array or as a run of Mamba layers' weights (the
    period's slice of a [periods, run, ...] stack was such a copy: 2.7 GB
    a decode step)."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.serve.llm.stage import init_params

    cfg = EngineConfig(
        model="jamba2-3b", dtype="bfloat16", page_size=PAGE, num_pages=10753,
        max_model_len=2688, max_batch=64, prefill_buckets=(128, 2048))
    engine = LLMEngine(cfg, params={})
    stage = engine.compute
    stage.params = jax.eval_shape(lambda: init_params(
        stage.model, jnp.zeros((1, 8), jnp.int32), jax.random.PRNGKey(0)))
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    key = ((1, engine.max_pages_per_seq) if kind == "decode"
           else (span, engine._wave_rb, 0))
    assert stage.operands("prefill")[-1] == "slots"
    with pytest.MonkeyPatch.context() as mp_ctx:
        mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
        compiled = stage.program(kind, key).lower(*jax.tree.map(
            sds, (*stage._state(kind), *stage.dummy_args(kind, key)))
        ).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    if kind == "decode":
        assert any(k.startswith("_decode_call") for k in kernels), kernels
    else:
        assert {"_ssm_scan", "_ssm_scan.3"} <= kernels, kernels
        assert any(k.startswith("attn.") for k in kernels), kernels
    pools = {name: tuple(a.shape) for name, a in stage.kv_pages.items()}
    assert (pools["ssm_h"], pools["ssm_conv"]) == ((26, 64, 16, 8, 640),
                                                   (26, 3, 64, 5120))
    run_bytes = 2 * 2560 * 10240 * 2          # two layers' W_in
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if not m or m.group(2) not in MOVES:
            continue
        for dims, _ in _array_types(m.group(1)):
            # an update in place has the pool's shape: its operand is the
            # donated buffer, and that it aliases is asserted below
            if dims in pools.values():
                if m.group(2) != "dynamic-update-slice":
                    moved.append(line.strip()[:160])
            elif int(np.prod(dims)) * 2 >= run_bytes:
                moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (4 if kind == "decode" else 3), header[:400]


# ------------- a model with lightning and block-sparse layers, at its cut
@pytest.mark.parametrize("kind, ctx", [("decode", 0), ("prefill", 660),
                                       ("prefill", 0)])
def test_sala_program_fits_and_carries_its_pool_in_place(
        topo, no_persistent_cache, kind, ctx):
    """MiniCPM-SALA at its published widths, layers 9 to 24, as the cell
    `minicpm-sala-longdoc` runs it (16 slots, 10,560 pages of 64): the
    program fits the chip beside its 3.26 GB pool, the decode step reads
    its selected pages through the paged-decode kernel (one call a run of
    sparse layers), a pass with nothing cached goes through the flash
    kernel and a resumed one through plain XLA, all three parts of the
    pool are aliased from argument to result, and nothing as large as a
    part of the pool or a layer's FFN weights is copied (the whole-pool
    view a head a page, `[L, P*G, 1, page, 2D]`, is a bitcast)."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.serve.llm.stage import init_params

    cfg = EngineConfig(
        model="minicpm-sala", dtype="bfloat16", page_size=64, num_pages=64,
        max_model_len=42240, max_batch=16, prefill_buckets=(512, 4096),
        model_overrides=dict(num_layers=16, kept_layers=tuple(range(9, 25))))
    engine = LLMEngine(cfg, params={})
    stage = engine.compute
    stage.params = jax.eval_shape(lambda: init_params(
        stage.model, jnp.zeros((1, 8), jnp.int32), jax.random.PRNGKey(0)))
    # the cell's pool, as shapes (the engine above holds a small one)
    stage.kv_pages = {
        name: jax.ShapeDtypeStruct(shape, dtype) for name, (shape, dtype)
        in stage.family.pool_spec(stage.model_cfg, 16, 10560, 64, 16).items()}
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    key = ((1, engine.max_pages_per_seq) if kind == "decode"
           else (4096, engine._wave_rb, ctx))
    assert stage.operands("prefill")[-1] == "slots"
    with pytest.MonkeyPatch.context() as mp_ctx:
        mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
        compiled = stage.program(kind, key).lower(*jax.tree.map(
            sds, (*stage._state(kind), *stage.dummy_args(kind, key)),
            is_leaf=lambda a: isinstance(a, jax.ShapeDtypeStruct))
        ).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 12.3 * 2 ** 30 <= total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    if kind == "decode":
        assert len(kernels) == 3 and all(
            k.startswith("_decode_call") for k in kernels), kernels
    elif ctx:
        assert not kernels, kernels
    else:
        assert len(kernels) == 3, kernels
    pools = {name: tuple(a.shape) for name, a in stage.kv_pages.items()}
    assert pools == {"kv_pages": (4, 10560, 2, 64, 256),
                     "kc": (4, 10560, 2, 4, 128),
                     "lin_state": (12, 16, 32, 128, 128)}
    # a layer's weights sliced from its run's stack are read in place (the
    # slice sits inside the matmul's fusion); what may not appear is a
    # COPY of a layer's FFN weights, or any move of a part of the pool but
    # the update in place. (The one copy there is: the 0.6 GB head, into
    # the layout its one-row product wants, once a prefill program.)
    ffn, head = (1, 4096, 32768), (4096, 73448)
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if not m or m.group(2) not in MOVES:
            continue
        for dims, _ in _array_types(m.group(1)):
            if dims in pools.values():
                if m.group(2) != "dynamic-update-slice":
                    moved.append(line.strip()[:160])
            elif (m.group(2) in ("copy", "transpose", "concatenate")
                  and int(np.prod(dims)) >= int(np.prod(ffn))
                  and dims != head):
                moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (4 if kind == "decode" else 3), header[:400]


# --------- a model that generates by diffusion over blocks, at its cut
@pytest.mark.parametrize("kind, key", [
    ("block", None), ("prefill", (2048, 0)), ("prefill", (2048, 200)),
    ("prefill", (128, 0))])
def test_sdar_program_fits_and_carries_its_pool_in_place(
        topo, no_persistent_cache, kind, key):
    """SDAR-30B-A3B at its published widths, 6 layers with all 128
    experts, as the cell `sdar-30b-a3b-chat` runs it (64 slots, 12,800
    pages of 16): the program fits the chip beside its 2.5 GB pool; the
    block program's attention is the paged-decode kernel given 4 tokens x
    8 heads a kv head (two calls in the pass that opens a block, which is
    [64, 8] wide: the pending block's queries and the new block's, each
    with its own length; one in the loop's pass) and its experts the
    grouped matmul at E = 128 (4096 sorted rows in the opening pass, 2048
    in the loop's); a prefill's is the flash kernel under the block mask;
    the pool (and the block program's carry) is aliased from argument to
    result. The block program is 11.06 GiB (PR 42: what it was with the
    settling pass it had until then; 0.59 GiB of it temporaries). Since PR
    54 a greedy batch's pass decides in `_head_argmax` and writes no
    logits; the program reads 11.058 GiB with 0.588 of temporaries all the
    same: the branch that draws keeps its `f32[64,4,151936]` and XLA sizes
    a `cond` for the larger branch."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.serve.llm.stage import init_params

    cfg = EngineConfig(
        model="sdar-30b-a3b", dtype="bfloat16", page_size=16, num_pages=64,
        max_model_len=3200, max_batch=64,
        prefill_buckets=(128, 256, 512, 1024, 2048),
        model_overrides=dict(num_layers=6,
                             remasking="low_confidence_static"))
    engine = LLMEngine(cfg, params={})
    stage = engine.compute
    stage.params = jax.eval_shape(lambda: init_params(
        stage.model, jnp.zeros((1, 8), jnp.int32), jax.random.PRNGKey(0)))
    shape, dtype = stage.family.pool_spec(stage.model_cfg, 6, 12800, 16, 64)
    stage.kv_pages = jax.ShapeDtypeStruct(shape, dtype)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    key = (engine._block_shape_key() if kind == "block"
           else (key[0], engine._wave_rb, key[1]))
    assert key == ((4, 4, 200) if kind == "block" else key)
    with pytest.MonkeyPatch.context() as mp_ctx:
        mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
        compiled = stage.program(kind, key).lower(*jax.tree.map(
            sds, (*stage._state(kind), *stage.dummy_args(kind, key)),
            is_leaf=lambda a: isinstance(a, jax.ShapeDtypeStruct))
        ).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # 8.72 GB of weights + 2.52 GB of pages, under the chip's 15.75 GiB
    assert 10.4 * 2 ** 30 <= total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    names = sorted({k.split(".")[0] for k in kernels})
    if kind == "block":
        assert names == ["_decode_call", "_head_argmax", "_moe_gmm"], kernels
        assert sum(k.startswith("_decode_call") for k in kernels) == 3
        assert sum(k.startswith("_moe_gmm") for k in kernels) == 4
        # the head of a greedy batch's pass, the opening one's and the
        # loop's; the float32 logits are the drawing branch's alone
        assert sum(k.startswith("_head_argmax") for k in kernels) == 2
        assert text.count("f32[64,4,151936]") > 0
        # the opening pass's ids, and no head over its left half
        assert "s32[64,8]" in text and "[64,8,151936]" not in text
        assert total <= 11.3 * 2 ** 30, total / 2 ** 30
    else:
        # (since PR 60 q's and k's rotation is a kernel over the rows the
        # flash kernel reads: models/llama.py: rope, ops/rotary.py)
        assert names == ["_moe_gmm", "_rotate_rows", "attn"], kernels
        assert sum(k.startswith("_rotate_rows") for k in kernels) == 2
        # a resumed pass: the own-tokens part and the part over its pages
        assert sum(k.startswith("attn") for k in kernels) == (
            2 if key[2] else 1)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (2 if kind == "block" else 1), header[:400]
    assert tuple(stage.kv_pages.shape) == (6, 12800, 4, 16, 256)


# ---------------- latent attention and one chip's share of the experts
@pytest.mark.parametrize("kind, key", [
    ("decode", None), ("prefill", (4096, 529)), ("prefill", (4096, 0)),
    ("prefill", (512, 529))])
def test_kimi_program_fits_and_carries_its_pool_in_place(
        topo, no_persistent_cache, kind, key):
    """Kimi-K2.5 at its published widths as the cell `kimi-k2.5-longdoc`
    runs it: one dense layer and five expert layers, each with 12 of the
    384 routed experts beside the shared one, 20,480 rows of the
    vocabulary, 24 slots, 8192 latent pages of 64 tokens (`[6, 8192, 1,
    64, 640]`, 4.03 GB). The decode program's attention is the latent
    kernel under its own name (`_mla_decode`), once a scan body; a resumed
    `[1 x 4096]` pass behind a 33,856-token table (nine context chunks of
    4096 tokens, each a flash call under a `cond`, beside the own-tokens
    call) fits
    the chip; the experts are the grouped matmul at E = 12; the pool is
    aliased from argument to result."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.serve.llm.stage import init_params

    cfg = EngineConfig(
        model="kimi-k2.5", dtype="bfloat16", page_size=64, num_pages=64,
        max_model_len=33856, max_batch=24,
        prefill_buckets=(512, 1024, 2048, 4096),
        model_overrides=dict(num_layers=6, num_experts=12,
                             n_routed_experts=384, expert_first=0,
                             vocab_size=20480))
    engine = LLMEngine(cfg, params={})
    stage = engine.compute
    stage.params = jax.eval_shape(lambda: init_params(
        stage.model, jnp.zeros((1, 8), jnp.int32), jax.random.PRNGKey(0)))
    shape, dtype = stage.family.pool_spec(stage.model_cfg, 6, 8192, 64, 24)
    assert shape == (6, 8192, 1, 64, 640)
    stage.kv_pages = jax.ShapeDtypeStruct(shape, dtype)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    key = (engine._decode_shape_key() if kind == "decode"
           else (key[0], engine._wave_rb, key[1]))
    with pytest.MonkeyPatch.context() as mp_ctx:
        mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
        compiled = stage.program(kind, key).lower(*jax.tree.map(
            sds, (*stage._state(kind), *stage.dummy_args(kind, key)),
            is_leaf=lambda a: isinstance(a, jax.ShapeDtypeStruct))
        ).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(kind, key, "GiB", total / 2 ** 30, "temp",
          mem.temp_size_in_bytes / 2 ** 30)
    # 8.35 GB of weights + 4.03 GB of latents, under the chip's 15.75 GiB
    assert 11.4 * 2 ** 30 <= total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    names = sorted({k.split(".")[0] for k in kernels})
    if kind == "decode":
        assert names == ["_mla_decode", "_moe_gmm"], kernels
    else:
        assert names == ["_mla_flash", "_moe_gmm"], kernels
        # the own-tokens call and one a context chunk of a resumed pass,
        # in the dense run's scan body and in the expert run's
        assert sum(k.startswith("_mla_flash") for k in kernels) == 2 * (
            1 + (9 if key[2] else 0)), kernels
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (2 if kind == "decode" else 1), header[:400]


# ------------- sliding-window and full attention layers, rings beside pages
MELLUM_PAGE, MELLUM_PAGES, MELLUM_LEN = 64, 16384, 33792


def _compile_two_kind_program(topo, engine_cfg: dict, layers: int,
                              pages: int, kind: str, key):
    """A shape-only engine of a family with pages and rings (models/
    mellum.py, models/laguna.py), its `kind` program compiled for the
    described chip over a pool of `pages` pages -> (compiled, the pool's
    spec, the program's key)."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.serve.llm.stage import init_params

    cfg = EngineConfig(**engine_cfg)
    engine = LLMEngine(cfg, params={})
    stage = engine.compute
    stage.params = jax.eval_shape(lambda: init_params(
        stage.model, jnp.zeros((1, 8), jnp.int32), jax.random.PRNGKey(0)))
    spec = stage.family.pool_spec(stage.model_cfg, layers, pages,
                                  cfg.page_size, cfg.max_batch)
    stage.kv_pages = {k: jax.ShapeDtypeStruct(*sd) for k, sd in spec.items()}
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    key = (engine._decode_shape_key() if kind == "decode"
           else (key[0], engine._wave_rb, key[1]))
    with pytest.MonkeyPatch.context() as mp_ctx:
        mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
        compiled = stage.program(kind, key).lower(*jax.tree.map(
            sds, (*stage._state(kind), *stage.dummy_args(kind, key)),
            is_leaf=lambda a: isinstance(a, jax.ShapeDtypeStruct))
        ).compile()
    return compiled, spec, key


@pytest.mark.parametrize("kind, key", [
    ("decode", None), ("prefill", (4096, MELLUM_LEN // MELLUM_PAGE))])
def test_mellum_program_fits_and_carries_both_pools_in_place(
        topo, no_persistent_cache, kind, key):
    """Mellum2-12B-A2.5B-Instruct at its published widths as the cell
    `mellum2-mixedctx` runs it: layers 0-7 (two periods of three sliding
    layers and a full one), all 64 experts, the whole vocabulary, 64 slots:
    the full layers' pages `[2, 16384, 4, 64, 256]` (1.05 M tokens, 4.3 GB)
    beside the sliding layers' rings `[6, 64 x 16, 4, 64, 256]` (a window a
    slot, 0.8 GB whatever the contexts). The decode program runs the
    paged kernel under two names, `_decode_call` over the block table and
    `_window_decode` over the rings; a resumed `[1 x 4096]` pass behind a
    33,792-token table fits the chip beside 7.6 GB of weights, its sliding
    layers' flash calls (`_window_flash`: own tokens, then the ring) apart
    from its full layers'; both parts of the pool are aliased from argument
    to result."""
    cfg = dict(
        model="mellum2-12b-a2.5b", dtype="bfloat16", page_size=MELLUM_PAGE,
        num_pages=64, max_model_len=MELLUM_LEN, max_batch=64,
        prefill_buckets=(256, 512, 1024, 2048, 4096),
        model_overrides=dict(num_layers=8))
    compiled, spec, key = _compile_two_kind_program(
        topo, cfg, 8, MELLUM_PAGES, kind, key)
    assert spec["kv_pages"][0] == (2, MELLUM_PAGES, 4, MELLUM_PAGE, 256)
    assert spec["win_pages"][0] == (6, 64 * 1024 // MELLUM_PAGE, 4,
                                    MELLUM_PAGE, 256)
    mem, text = compiled.memory_analysis(), compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(kind, key, "GiB", total / 2 ** 30, "temp",
          mem.temp_size_in_bytes / 2 ** 30)
    # 7.59 GB of weights + 4.29 GB of pages + 0.81 GB of rings
    assert 11.8 * 2 ** 30 <= total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    names = sorted({k.split(".")[0] for k in kernels})
    if kind == "decode":
        assert names == ["_decode_call", "_moe_gmm", "_window_decode"], kernels
    else:
        assert "_window_flash" in names and "_moe_gmm" in names, kernels
        # a sliding run's scan body: the own-tokens call, and the ring's
        # where the pass resumes; two sliding runs
        assert sum(k.startswith("_window_flash") for k in kernels) == 2 * (
            2 if key[2] else 1), kernels
    # neither part of the pool is copied whole: the rings are gathered a
    # slot's rows and scattered back a slot's pages, as the pages are
    whole = {sd[0] for sd in spec.values()}
    copied = [line.strip()[:160] for line in text.splitlines()
              if (m := re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) copy\(",
                                line))
              and any(dims in whole for dims, _ in _array_types(m.group(1)))]
    assert not copied, "\n".join(copied)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (3 if kind == "decode" else 2), header[:400]


# ------ the same stack with the shapes a kind: 48 and 64 heads, a dense layer
LAGUNA_PAGE, LAGUNA_PAGES, LAGUNA_LEN = 64, 8192, 17408


@pytest.mark.parametrize("kind, key", [
    ("decode", None), ("prefill", (4096, LAGUNA_LEN // LAGUNA_PAGE)),
    ("prefill", (512, 0))])
def test_laguna_program_fits_and_carries_both_pools_in_place(
        topo, no_persistent_cache, kind, key):
    """Laguna-XS.2 at its published widths as the cell
    `laguna-xs2-agentturns` runs it: layers 0-4 (full attention + the dense
    FFN, three sliding layers, a full one; 48 and 64 query heads on 8 kv
    heads), all 256 experts beside the shared one, the whole vocabulary,
    64 slots: the two full layers' pages `[2, 8192, 8, 64, 256]` (524k
    tokens, 4.29 GB) beside the three sliding layers' rings `[3, 64 x 8, 8,
    64, 256]` (0.40 GB whatever the contexts). The decode program runs the
    paged kernel at a group of 6 (`_decode_call`) and of 8
    (`_window_decode`); a resumed `[1 x 4096]` pass behind a 17,408-token
    table fits the chip beside 7.74 GB of weights; both parts of the pool
    are aliased from argument to result and neither is copied whole."""
    cfg = dict(
        model="laguna-xs.2", dtype="bfloat16", page_size=LAGUNA_PAGE,
        num_pages=64, max_model_len=LAGUNA_LEN, max_batch=64,
        prefill_buckets=(512, 1024, 2048, 4096),
        model_overrides=dict(num_layers=5))
    compiled, spec, key = _compile_two_kind_program(
        topo, cfg, 5, LAGUNA_PAGES, kind, key)
    assert spec["kv_pages"][0] == (2, LAGUNA_PAGES, 8, LAGUNA_PAGE, 256)
    assert spec["win_pages"][0] == (3, 64 * 512 // LAGUNA_PAGE, 8,
                                    LAGUNA_PAGE, 256)
    mem, text = compiled.memory_analysis(), compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(kind, key, "GiB", total / 2 ** 30, "temp",
          mem.temp_size_in_bytes / 2 ** 30)
    # 7.74 GB of weights + 4.29 GB of pages + 0.40 GB of rings
    assert 11.5 * 2 ** 30 <= total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    names = sorted({k.split(".")[0] for k in kernels})
    if kind == "decode":
        assert names == ["_decode_call", "_moe_gmm", "_window_decode"], kernels
    else:
        assert "_window_flash" in names and "_moe_gmm" in names, kernels
        # ONE sliding run's scan body: the own-tokens call, and the ring's
        # where the pass resumes
        assert sum(k.startswith("_window_flash") for k in kernels) == (
            2 if key[2] else 1), kernels
    # the gate's product is the program's own name for it
    scopes = set(re.findall(r'op_name="[^"]*?(rtpu\.[\w.]+)', text))
    assert "rtpu.attn.gate" in scopes, sorted(scopes)
    whole = {sd[0] for sd in spec.values()}
    copied = [line.strip()[:160] for line in text.splitlines()
              if (m := re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) copy\(",
                                line))
              and any(dims in whole for dims, _ in _array_types(m.group(1)))]
    assert not copied, "\n".join(copied)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (3 if kind == "decode" else 2), header[:400]


# -------- gated-delta-net layers beside latent attention, three kinds of pool
GIGA_PAGES, GIGA_LEN, GIGA_SLOTS = 12288, 14400, 96


@pytest.mark.parametrize("kind, key", [
    ("decode", None), ("prefill", (4096, GIGA_LEN // 64))])
def test_gigachat_program_fits_and_carries_its_three_kind_pool_in_place(
        topo, no_persistent_cache, kind, key):
    """GigaChat3.5-432B-A28B at its published widths as the cell
    `gigachat3.5-reasoning` runs it: published layers 2-6 (a GDN layer with
    the dense FFN, an MLA layer and three GDN layers with 16 of the 256
    routed experts beside the shared one), 16,032 rows of the vocabulary, 96
    slots: ONE layer's latent pages `[1, 12288, 1, 64, 640]` (1.01 GB)
    beside the four GDN layers' matrices `[4, 96, 64, 128, 128]` float32
    (1.61 GB) and conv tails `[4, 3, 96, 16384]`. The decode program runs
    the delta-rule update as a kernel under its own name (`_gdn_update`)
    beside the latent kernel and the grouped matmul; a resumed `[1 x 4096]`
    pass behind a 14,400-token table (the fresh pass's program and its
    context chunks' calls) fits the chip beside 9.46 GB of weights; no part of
    the pool is copied whole and all three are aliased from argument to
    result."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.serve.llm.stage import init_params

    cfg = EngineConfig(
        model="gigachat3.5-432b-a28b", dtype="bfloat16", page_size=64,
        num_pages=64, max_model_len=GIGA_LEN, max_batch=GIGA_SLOTS,
        prefill_buckets=(512, 1024, 2048, 4096),
        model_overrides=dict(num_layers=5, kept_layers=(2, 3, 4, 5, 6),
                             num_experts=16, n_routed_experts=256,
                             expert_first=0, vocab_size=16032))
    engine = LLMEngine(cfg, params={})
    stage = engine.compute
    stage.params = jax.eval_shape(lambda: init_params(
        stage.model, jnp.zeros((1, 8), jnp.int32), jax.random.PRNGKey(0)))
    spec = stage.family.pool_spec(stage.model_cfg, 5, GIGA_PAGES, 64,
                                  GIGA_SLOTS)
    assert spec["latent_pages"][0] == (1, GIGA_PAGES, 1, 64, 640)
    assert spec["gdn_state"][0] == (4, GIGA_SLOTS, 64, 128, 128)
    assert spec["gdn_conv"][0] == (4, 3, GIGA_SLOTS, 16384)
    stage.kv_pages = {k: jax.ShapeDtypeStruct(*sd) for k, sd in spec.items()}
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    key = (engine._decode_shape_key() if kind == "decode"
           else (key[0], engine._wave_rb, key[1]))
    with pytest.MonkeyPatch.context() as mp_ctx:
        mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
        compiled = stage.program(kind, key).lower(*jax.tree.map(
            sds, (*stage._state(kind), *stage.dummy_args(kind, key)),
            is_leaf=lambda a: isinstance(a, jax.ShapeDtypeStruct))
        ).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(kind, key, "GiB", total / 2 ** 30, "temp",
          mem.temp_size_in_bytes / 2 ** 30)
    # 9.46 GB of weights + 1.01 GB of latents + 1.65 GB of state and tails
    assert 11.2 * 2 ** 30 <= total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    names = sorted({k.split(".")[0] for k in kernels})
    if kind == "decode":
        assert names == ["_gdn_update", "_mla_decode", "_moe_gmm"], kernels
    else:
        assert names == ["_mla_flash", "_moe_gmm"], kernels
    whole = {sd[0] for sd in spec.values()}
    copied = [line.strip()[:160] for line in text.splitlines()
              if (m := re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) copy\(",
                                line))
              and any(dims in whole for dims, _ in _array_types(m.group(1)))]
    assert not copied, "\n".join(copied)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (4 if kind == "decode" else 3), header[:400]


# ------- attention inside a compressed latent, a tail a slot beside its pages
ZAYA_PAGES, ZAYA_LEN, ZAYA_SLOTS, ZAYA_LAYERS = 3456, 14400, 64, 20


@pytest.mark.parametrize("kind, key", [
    ("decode", None), ("prefill", (4096, 0)),
    ("prefill", (4096, ZAYA_LEN // 64))])
def test_zaya_program_fits_and_carries_both_pools_in_place(
        topo, no_persistent_cache, kind, key):
    """ZAYA1-8B at its published widths as the cell `zaya1-8b-reasoning`
    runs it: published layers 0-19 with all 16 experts of 2048 and the
    whole 262,272-row tied vocabulary, 64 slots: pages of the latent's 2 kv
    heads `[20, P, 2, 64, 256]` beside a tail a slot `[20, 64, 2688]`. The
    decode program's attention is the paged-decode kernel at 8 query heads
    on 2 kv heads (a group of 4, Mistral's), once a scan body, beside the
    grouped matmul at E = 16; a `[1 x 4096]` pass, fresh and resumed behind
    a 14,400-token table, is the flash forward and fits the chip beside
    9.38 GB of weights; no part of the pool is copied whole and both are
    aliased from argument to result."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine
    from ray_tpu.serve.llm.stage import init_params

    cfg = EngineConfig(
        model="zaya1-8b", dtype="bfloat16", page_size=64, num_pages=64,
        max_model_len=ZAYA_LEN, max_batch=ZAYA_SLOTS,
        prefill_buckets=(512, 1024, 2048, 4096),
        model_overrides=dict(num_layers=ZAYA_LAYERS))
    engine = LLMEngine(cfg, params={})
    stage = engine.compute
    stage.params = jax.eval_shape(lambda: init_params(
        stage.model, jnp.zeros((1, 8), jnp.int32), jax.random.PRNGKey(0)))
    spec = stage.family.pool_spec(stage.model_cfg, ZAYA_LAYERS, ZAYA_PAGES,
                                  64, ZAYA_SLOTS)
    assert spec["kv_pages"][0] == (ZAYA_LAYERS, ZAYA_PAGES, 2, 64, 256)
    assert spec["cca_tail"][0] == (ZAYA_LAYERS, ZAYA_SLOTS, 2688)
    stage.kv_pages = {k: jax.ShapeDtypeStruct(*sd) for k, sd in spec.items()}
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    key = (engine._decode_shape_key() if kind == "decode"
           else (key[0], engine._wave_rb, key[1]))
    assert stage.operands("prefill")[-1] == "slots"
    with pytest.MonkeyPatch.context() as mp_ctx:
        mp_ctx.setattr(jax, "default_backend", lambda: "tpu")
        compiled = stage.program(kind, key).lower(*jax.tree.map(
            sds, (*stage._state(kind), *stage.dummy_args(kind, key)),
            is_leaf=lambda a: isinstance(a, jax.ShapeDtypeStruct))
        ).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(kind, key, "GiB", total / 2 ** 30, "temp",
          mem.temp_size_in_bytes / 2 ** 30)
    # 9.38 GB of weights + 4.53 GB of pages
    assert 12.9 * 2 ** 30 <= total <= HBM_GIB * 2 ** 30, total / 2 ** 30
    kernels = set(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))
    names = sorted({k.split(".")[0] for k in kernels})
    if kind == "decode":
        assert names == ["_decode_call", "_moe_gmm"], kernels
    else:
        assert names == (["_ctx_flash", "_moe_gmm", "attn"] if key[2]
                         else ["_moe_gmm", "attn"]), kernels
    whole = {sd[0] for sd in spec.values()}
    copied = [line.strip()[:160] for line in text.splitlines()
              if (m := re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) copy\(",
                                line))
              and any(dims in whole for dims, _ in _array_types(m.group(1)))]
    assert not copied, "\n".join(copied)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliased) >= (3 if kind == "decode" else 2), header[:400]
