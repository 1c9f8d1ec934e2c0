"""Attention ops with swappable backends.

The compute core the reference delegates to external engines (torch SDPA /
vLLM CUDA kernels; the reference itself ships no attention kernels — see
SURVEY.md §2.4) implemented TPU-native: a jnp reference implementation that
XLA fuses well on any backend, and a Pallas flash-attention kernel for TPU
(ray_tpu/ops/flash_attention.py). GQA (grouped KV heads) is supported
everywhere. Selection follows the backend the process runs on unless forced
via `impl`: a TPU backend always gets the compiled kernel (wrapped in a
`shard_map` when a multi-device mesh is active) and never the reference; a
CPU backend (`JAX_PLATFORMS=cpu`, the tests) gets the reference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, Hkv, D] -> [B, S, Hkv*n_rep, D] for grouped-query attention."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


def split_segment_ids(segment_ids, sq: int, sk: int):
    """Normalize segment_ids to a (q_seg [B,Sq], kv_seg [B,Sk]) pair.

    Accepts None, a single [B,S] array (requires Sq == Sk), or an explicit
    pair — the pair form is what cached decode / chunked prefill of packed
    sequences needs, where the kv axis is longer than the query axis.
    """
    if segment_ids is None:
        return None, None
    if isinstance(segment_ids, tuple):
        q_seg, kv_seg = segment_ids
    else:
        if sq != sk:
            raise ValueError(
                "single segment_ids array requires Sq == Sk; pass a "
                "(q_segment_ids, kv_segment_ids) tuple when using a kv cache")
        q_seg = kv_seg = segment_ids
    return q_seg, kv_seg


def causal_mask(sq: int, sk: int, block_causal: int = 0) -> jax.Array:
    """[sq, sk] bool: query i (the last `sq` of `sk` positions) sees key j
    iff j <= i; with `block_causal` = B > 0 iff j // B <= i // B, blocks
    counted from key 0: causal between blocks, bidirectional inside one
    (a model that generates by diffusion over blocks, models/sdar.py)."""
    if not block_causal:
        return jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
    q_blk = (jnp.arange(sq) + sk - sq) // block_causal
    return (jnp.arange(sk) // block_causal)[None, :] <= q_blk[:, None]


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        *, causal: bool = True,
                        segment_ids=None,
                        scale: Optional[float] = None,
                        block_causal: int = 0) -> jax.Array:
    """Plain softmax attention. Shapes: q [B,Sq,Hq,D], k/v [B,Sk,Hkv,D].

    segment_ids: None | [B,S] array | (q_seg [B,Sq], kv_seg [B,Sk]) tuple.
    block_causal: see `causal_mask`.
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    if hq != hkv:
        assert hq % hkv == 0, (hq, hkv)
        k = _repeat_kv(k, hq // hkv)
        v = _repeat_kv(v, hq // hkv)
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        logits = jnp.where(causal_mask(sq, sk, block_causal)[None, None],
                           logits, NEG_INF)
    q_seg, kv_seg = split_segment_ids(segment_ids, sq, sk)
    if q_seg is not None:
        seg_mask = q_seg[:, None, :, None] == kv_seg[:, None, None, :]
        logits = jnp.where(seg_mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True,
              segment_ids=None,
              scale: Optional[float] = None,
              impl: Optional[str] = None,
              block_causal: int = 0) -> jax.Array:
    """Dispatch to the best backend for this platform.

    block_causal > 0 (`causal_mask`; dense forward only: the flash
    backward and the sequence-parallel forms know no such mask).

    impl: None (flash on a TPU backend, reference elsewhere) | "reference"
    | "flash" (Pallas TPU kernel; interpret mode on a CPU backend; wrapped
    in a shard_map over batch and heads when a multi-device mesh is
    active) | "ring" | "ulysses" (sequence-parallel
    collectives over the ambient mesh's `sp` axis; fall back to the dense
    path when no mesh is active or sp == 1).

    segment_ids: None | [B,S] array | (q_seg, kv_seg) tuple (see
    reference_attention).
    """
    if block_causal:
        if not causal or impl in ("ring", "ulysses"):
            raise NotImplementedError(
                f"block_causal={block_causal} is a dense causal forward's "
                f"(got causal={causal}, impl={impl!r})")
        if impl == "flash" or (impl is None
                               and jax.default_backend() == "tpu"):
            from .flash_attention import flash_attention

            # the forward-only path: its kernel takes the mask
            return flash_attention(
                q, k, v, causal=True, segment_ids=segment_ids, scale=scale,
                return_lse=True, block_causal=block_causal)[0]
        return reference_attention(q, k, v, causal=True,
                                   segment_ids=segment_ids, scale=scale,
                                   block_causal=block_causal)
    if impl in ("ring", "ulysses"):
        from ..parallel.mesh import current_mesh

        mesh = current_mesh()
        if (mesh is not None and "sp" in mesh.axis_names
                and mesh.shape["sp"] > 1):
            if isinstance(segment_ids, tuple):
                raise NotImplementedError(
                    "sequence-parallel attention does not take a "
                    "(q_seg, kv_seg) pair (kv-cache decode is dense)")
            from .ring_attention import (ring_attention_sharded,
                                         ulysses_attention_sharded)
            fn = (ring_attention_sharded if impl == "ring"
                  else ulysses_attention_sharded)
            return fn(q, k, v, mesh, causal=causal, segment_ids=segment_ids,
                      scale=scale)
        _warn_once(
            f"impl={impl!r} requested but no active mesh with sp>1 "
            "(wrap the call in ray_tpu.parallel.mesh.active_mesh); "
            "running dense attention")
        impl = None  # no sp axis active: fall through to dense auto-select
    if impl is None:
        impl = "flash" if jax.default_backend() == "tpu" else "reference"
    if impl == "flash":
        from .flash_attention import flash_attention, flash_attention_sharded

        mesh = _mesh_to_shard_over()
        if mesh is not None:
            return flash_attention_sharded(
                q, k, v, mesh, causal=causal, segment_ids=segment_ids,
                scale=scale)
        return flash_attention(q, k, v, causal=causal,
                               segment_ids=segment_ids, scale=scale)
    return reference_attention(q, k, v, causal=causal,
                               segment_ids=segment_ids, scale=scale)


def flash_on_one_device(impl: Optional[str] = None) -> bool:
    """Whether `attention(..., impl=impl)` is the Pallas kernel called on
    this one device: not a reference, not a sequence-parallel form, not
    wrapped in a shard_map over a mesh. What stands beside such a call may
    be a one-device kernel too (models/llama.py: `rope`'s rows)."""
    if impl is None:
        impl = "flash" if jax.default_backend() == "tpu" else "reference"
    return impl == "flash" and _mesh_to_shard_over() is None


def _mesh_to_shard_over():
    """The active mesh when the flash kernel has to be wrapped in a
    shard_map: more than one device, and not already inside a manual
    region (ulysses calls `attention` from inside its own shard_map, where
    operands are per-device blocks already)."""
    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return None
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return mesh


_warned = set()


def _warn_once(message: str):
    if message not in _warned:
        _warned.add(message)
        import warnings

        warnings.warn(message)
