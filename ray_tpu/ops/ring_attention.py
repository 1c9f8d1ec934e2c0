"""Ring attention and Ulysses sequence parallelism over a mesh axis.

The reference ships NO sequence/context parallelism anywhere (verified in
SURVEY.md §5 "Long-context / sequence parallelism": no ring attention,
Ulysses, or context_parallel in python/ or rllib/ — it is delegated entirely
to external engines). This module is therefore greenfield TPU-native design:

- ``ring_attention``: blockwise-softmax attention where each device holds a
  sequence shard of q/k/v and k/v blocks rotate around the ``sp`` mesh axis
  via ``lax.ppermute`` (one ICI hop per step), overlapping compute with the
  neighbour exchange. Memory per device is O(S/n * S/n) per step instead of
  O(S^2); the full sequence never materialises anywhere.
- ``ulysses_attention``: all-to-all head scattering — reshard
  [B, S/n, H, D] -> [B, S, H/n, D] with ``lax.all_to_all``, run plain
  (flash) attention on whole sequences for a head subset, and scatter back.
  Cheaper than ring when H >= n and sequence fits a device.

Both are *collective* ops: they must run inside ``shard_map`` (or pmap) with
the named axis present. ``ring_attention_sharded`` wraps ring attention in
``shard_map`` over an existing mesh so models can call it from inside jit.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .attention import NEG_INF, _repeat_kv

# ---------------------------------------------------------------------------
# blockwise core: attention over one kv block, returning (out, lse)
# ---------------------------------------------------------------------------


def _block_attention(q, k, v, mask, scale):
    """Softmax attention of q against one k/v block.

    q [B,Sq,H,D], k/v [B,Sk,H,D] (kv heads already repeated), mask
    [B,1,Sq,Sk] boolean or None. Returns (out [B,Sq,H,D] normalized within
    the block, lse [B,H,Sq] float32 logsumexp of the block's logits).
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)          # [B,H,Sq,1]
    m_safe = jnp.maximum(m, NEG_INF)                      # avoid -inf - -inf
    unnorm = jnp.exp(logits - m_safe)
    l = jnp.sum(unnorm, axis=-1, keepdims=True)           # [B,H,Sq,1]
    out = jnp.einsum("bhqk,bkhd->bqhd", unnorm.astype(v.dtype), v)
    l_safe = jnp.maximum(l, 1e-30)
    out = (out / l_safe.squeeze(-1)[..., None].swapaxes(1, 2)).astype(q.dtype)
    # lse = m + log(l); fully-masked rows get lse ~ NEG_INF so they
    # contribute nothing in the merge.
    lse = (m_safe + jnp.log(l_safe)).squeeze(-1)          # [B,H,Sq]
    return out, lse


def _merge(o, lse, o_new, lse_new):
    """Numerically-stable merge of two normalized partial attentions."""
    max_lse = jnp.maximum(lse, lse_new)
    # Guard fully-masked rows on BOTH sides (max_lse == NEG_INF).
    max_safe = jnp.where(max_lse <= NEG_INF / 2, 0.0, max_lse)
    w_old = jnp.exp(lse - max_safe)
    w_new = jnp.exp(lse_new - max_safe)
    denom = jnp.maximum(w_old + w_new, 1e-30)
    scale_old = (w_old / denom)[..., None].swapaxes(1, 2)  # [B,Sq,H,1]
    scale_new = (w_new / denom)[..., None].swapaxes(1, 2)
    o = o * scale_old.astype(o.dtype) + o_new * scale_new.astype(o.dtype)
    lse = max_safe + jnp.log(denom)
    lse = jnp.where(max_lse <= NEG_INF / 2, NEG_INF, lse)
    return o, lse


# ---------------------------------------------------------------------------
# ring attention (inside shard_map)
# ---------------------------------------------------------------------------


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str = "sp", causal: bool = True,
                   segment_ids: Optional[jax.Array] = None,
                   scale: Optional[float] = None) -> jax.Array:
    """Ring attention over the named mesh axis. Call inside shard_map/pmap.

    q/k/v are the LOCAL sequence shards [B, S_local, H, D] (q heads may be a
    multiple of kv heads — GQA). segment_ids, if given, is the local
    [B, S_local] shard; it rotates with k/v so packed-sequence masking stays
    correct across ring steps. Design per SURVEY.md §5/§7 (greenfield — the
    reference has no API surface for this).
    """
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    n_rep = hq // hkv  # GQA: rotate the RAW kv heads; repeat only at compute
    scale = scale if scale is not None else d ** -0.5
    perm = [(i, (i + 1) % n) for i in range(n)]

    q_pos = my_idx * sq + jnp.arange(sq)                   # global q positions

    def step_fn(carry, step):
        o, lse, k_cur, v_cur, seg_cur = carry
        kv_idx = (my_idx - step) % n                       # block we now hold
        k_pos = kv_idx * sk + jnp.arange(sk)
        mask = None
        if causal:
            mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
        if seg_cur is not None:
            seg_mask = (segment_ids[:, None, :, None]
                        == seg_cur[:, None, None, :])
            mask = seg_mask if mask is None else (mask & seg_mask)
        o_new, lse_new = _block_attention(
            q, _repeat_kv(k_cur, n_rep), _repeat_kv(v_cur, n_rep), mask,
            scale)
        o, lse = _merge(o, lse, o_new, lse_new)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        seg_nxt = (lax.ppermute(seg_cur, axis_name, perm)
                   if seg_cur is not None else None)
        return (o, lse, k_nxt, v_nxt, seg_nxt), None

    o0 = jnp.zeros_like(q)
    lse0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    (o, lse, _, _, _), _ = lax.scan(
        step_fn, (o0, lse0, k, v, segment_ids), jnp.arange(n))
    return o


def ring_attention_sharded(q, k, v, mesh: Mesh, *, axis_name: str = "sp",
                           causal: bool = True, segment_ids=None,
                           scale: Optional[float] = None,
                           batch_axes=("dp", "fsdp"),
                           head_axis: Optional[str] = "tp") -> jax.Array:
    """shard_map wrapper: callable from inside jit with a global [B,S,H,D].

    Sequence dim sharded over `axis_name`; batch over `batch_axes`; heads
    over `head_axis` (tensor parallelism composes with ring attention —
    heads and sequence shard on orthogonal mesh axes).
    """
    fn = functools.partial(ring_attention, axis_name=axis_name, causal=causal,
                           scale=scale)
    return _apply_sharded(fn, q, k, v, segment_ids, mesh, axis_name,
                          batch_axes, head_axis)


def _apply_sharded(fn, q, k, v, segment_ids, mesh, axis_name, batch_axes,
                   head_axis):
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no '{axis_name}' axis")
    batch = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    head = head_axis if head_axis in mesh.axis_names else None
    qkv_spec = P(batch, axis_name, head, None)
    seg_spec = P(batch, axis_name)
    if segment_ids is None:
        wrapped = shard_map(lambda q, k, v: fn(q, k, v),
                            mesh=mesh, in_specs=(qkv_spec,) * 3,
                            out_specs=qkv_spec, check_vma=False)
        return wrapped(q, k, v)
    wrapped = shard_map(lambda q, k, v, s: fn(q, k, v, segment_ids=s),
                        mesh=mesh, in_specs=(qkv_spec,) * 3 + (seg_spec,),
                        out_specs=qkv_spec, check_vma=False)
    return wrapped(q, k, v, segment_ids)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all head scattering)
# ---------------------------------------------------------------------------


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      axis_name: str = "sp", causal: bool = True,
                      segment_ids: Optional[jax.Array] = None,
                      scale: Optional[float] = None,
                      attn_fn=None) -> jax.Array:
    """Ulysses-style sequence parallelism: all-to-all so each device sees the
    FULL sequence for H/n heads, runs dense (flash) attention, and scatters
    back to sequence shards. Call inside shard_map over `axis_name`.

    Requires kv heads divisible by the axis size (repeat kv first for GQA).
    """
    n = lax.psum(1, axis_name)
    b, s_loc, hq, d = q.shape
    _, _, hkv, _ = k.shape
    # GQA: exchange the RAW kv heads when they split evenly over the axis
    # (n_rep x less ICI traffic); repeat only after the all-to-all.
    rep_after = hkv % n == 0
    if hq != hkv and not rep_after:
        k = _repeat_kv(k, hq // hkv)
        v = _repeat_kv(v, hq // hkv)

    def scatter_heads(x):
        # [B, S/n, H, D] -> [B, S, H/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def gather_heads(x):
        # [B, S, H/n, D] -> [B, S/n, H, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qg, kg, vg = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    if hq != hkv and rep_after:
        kg = _repeat_kv(kg, hq // hkv)
        vg = _repeat_kv(vg, hq // hkv)
    seg_full = (lax.all_gather(segment_ids, axis_name, axis=1, tiled=True)
                if segment_ids is not None else None)
    if attn_fn is None:
        # dense dispatch: flash kernel on TPU, reference elsewhere — never
        # the O(S^2)-logits reference path on long-context TPU runs
        from .attention import attention
        attn_fn = functools.partial(attention, scale=scale)
    out = attn_fn(qg, kg, vg, causal=causal, segment_ids=seg_full)
    return gather_heads(out)


def ulysses_attention_sharded(q, k, v, mesh: Mesh, *, axis_name: str = "sp",
                              causal: bool = True, segment_ids=None,
                              scale: Optional[float] = None,
                              batch_axes=("dp", "fsdp"),
                              head_axis: Optional[str] = "tp") -> jax.Array:
    """shard_map wrapper for ulysses_attention (see ring_attention_sharded)."""
    fn = functools.partial(ulysses_attention, axis_name=axis_name,
                           causal=causal, scale=scale)
    return _apply_sharded(fn, q, k, v, segment_ids, mesh, axis_name,
                          batch_axes, head_axis)
