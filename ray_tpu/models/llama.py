"""Llama-family decoder-only transformer in Flax, TPU-first.

This is the flagship model family (the reference frames its LLM story around
Llama-3 via external engines; here the model is native). Design choices for
the MXU/XLA:
- bfloat16 activations, fp32 RMSNorm statistics and softmax logits
- fused QKV and gate+up projections (fewer, larger matmuls)
- `nn.scan` over layers: one compiled layer body, weights stacked with a
  leading `layers` axis (fast compiles, enables pipelining later)
- optional `jax.checkpoint` rematerialisation per layer (HBM for FLOPs)
- logical axis names on every param so one rule table maps the model onto
  any mesh (see ray_tpu/parallel/sharding.py)
- attention dispatches to the Pallas flash kernel on TPU (ray_tpu/ops)
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax import struct

from jax.ad_checkpoint import checkpoint_name

from ..ops.attention import attention, flash_on_one_device
from ..ops.flash_attention import SAVED_OUTPUTS
from ..ops.paged_attention import (paged_attention_block,
                                   paged_attention_decode,
                                   paged_prefill_attention, paged_write)
from ..ops.rotary import rotate_rows, rows_rotatable
from ..util import tracing
from ._stack import (A, default_positions, dense, embed_tokens, scan_run,
                     stacked_experts)


def _remat_policy(name: str):
    """Checkpoint policy by config key (HBM <-> recompute dial).

    `"dots"` keeps what is dear to recompute: every matmul product
    (`dots_with_no_batch_dims_saveable`) and the flash kernel's `o` and
    `lse` (`ops/flash_attention.py: SAVED_OUTPUTS`), which are no `dot`'s
    products, so that policy alone ran the forward kernel a second time
    inside the backward. It costs `o` a layer: 67 MB at 4 x 2048 x 4096 in
    bf16 (`lse` 1 MB). `"names"` and `"nothing"` list neither name: their
    purpose is memory."""
    if name == "names":
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", "mlp_out")
    if name == "dots":
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(*SAVED_OUTPUTS))
    return jax.checkpoint_policies.nothing_saveable


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 22
    num_heads: int = 16
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    max_seq_len: int = 8192
    # None: no rotation (a model whose other layers carry the order,
    # models/jamba.py)
    rope_theta: Optional[float] = 500000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # remat policy: "nothing" = recompute everything (min memory),
    # "names" = save per-layer attention/MLP outputs (skips the expensive
    # recomputes in backward, ~1GB per saved tensor set at bs8 seq2048),
    # "dots" = save all matmul outputs and the flash kernel's o and lse,
    # so the backward recomputes elementwise work only (max memory: o is
    # 67 MB a layer at 4 x 2048 x 4096)
    remat_policy: str = "nothing"
    # sequence chunk for the fused cross-entropy (targets= path)
    loss_chunk: int = 512
    scan_layers: bool = True
    attention_impl: Optional[str] = None  # None = auto (flash on TPU)
    # MoE (Mixtral-style): 0 = dense MLP. Experts are stacked [E, ...]
    # params with the "expert" logical axis -> the mesh's ep axis. Without
    # an ep axis the layer is dropless (sorted assignments, grouped
    # matmul); capacity_factor and moe_group_size size the capacity
    # dispatch that runs only when experts are sharded over ep (MoEMLP).
    num_experts: int = 0
    num_experts_per_tok: int = 2
    capacity_factor: float = 1.25
    router_aux_loss_coef: float = 0.02
    moe_group_size: int = 2048  # ep dispatch group (bounds routing memory)
    # an expert's width where it is not the dense MLP's (None: it is)
    moe_intermediate_size: Optional[int] = None
    # the k kept router probabilities are renormalised to sum 1
    norm_topk_prob: bool = True
    # RMSNorm with a learned weight over the head dim of q and of k,
    # before the rotation (Qwen3's layer)
    qk_norm: bool = False
    # An expert layer that is TOLD which experts it holds (one chip's share
    # of a layer whose experts are spread over many; models/kimi.py): the
    # router scores all `n_routed_experts` (None: `num_experts`, the layer
    # holds them all), the layer holds `num_experts` of them from
    # `expert_first` on, and an assignment to an expert it does not hold
    # costs it nothing and adds nothing (MoEMLP).
    n_routed_experts: Optional[int] = None
    expert_first: int = 0
    # "softmax": Mixtral's rule. "sigmoid": DeepSeek-V3's: scores are
    # sigmoid(x W_r), the k are CHOSEN by score + a learned bias a expert
    # and WEIGHTED by the score alone, renormalised over the k chosen
    # (`norm_topk_prob`) and scaled by `routed_scaling_factor`
    moe_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    # a gated FFN of width n x `expert_width` that every token passes,
    # beside its routed experts
    n_shared_experts: int = 0
    # > 0: attention is causal between blocks of this many positions,
    # counted from position 0, and bidirectional inside one (query i sees
    # key j iff j // block_causal <= i // block_causal): a model that
    # generates by diffusion over blocks (models/sdar.py). 0: causal.
    block_causal: int = 0
    # a clamp on every gated FFN's two halves before their product:
    # silu(min(gate, limit)) * clip(up, -limit, limit). None: no clamp
    swiglu_limit: Optional[float] = None

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def routed_experts(self) -> int:
        """The router's width: `num_experts` unless the layer holds a
        share of more."""
        return self.n_routed_experts or self.num_experts

    def num_params(self) -> int:
        h, f, v, l = (self.hidden_size, self.intermediate_size,
                      self.vocab_size, self.num_layers)
        hd = self.head_dim_
        attn = h * hd * (self.num_heads + 2 * self.num_kv_heads) \
            + self.num_heads * hd * h
        if self.num_experts:
            mlp = (self.num_experts * 3 * h * self.expert_width
                   + h * self.num_experts)
        else:
            mlp = 3 * h * f
        if self.qk_norm:
            attn += 2 * hd
        return l * (attn + mlp + 2 * h) + 2 * v * h + h

    def active_params(self) -> int:
        """Params touched per token (= num_params for dense models); the
        MFU-relevant count for MoE."""
        if not self.num_experts:
            return self.num_params()
        h, f, l = self.hidden_size, self.expert_width, self.num_layers
        dense = self.num_params() - l * self.num_experts * 3 * h * f
        return dense + l * self.num_experts_per_tok * 3 * h * f


# ---------------------------------------------------------------- components
class RMSNorm(nn.Module):
    eps: float
    dtype: Any
    # the logical axis of the weight (a head's norm is no shard of `embed`)
    axes: tuple = ("embed",)

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", A(nn.initializers.ones, self.axes),
                           (x.shape[-1],), jnp.float32)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        y = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)
        return (y * scale).astype(self.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         rows: bool = False) -> jax.Array:
    """Rotary embedding. x: [B, S, H, D], positions: [B, S]. `rows`: the
    caller's attention is the flash kernel on this one device, which reads
    x as rows of [B, S, H x D]; where the shapes allow, the rotation is then
    a kernel over those rows too (ops/rotary.py: `rotate_rows`), the same
    arithmetic with no change of layout under it."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,D/2]
    if rows and rows_rotatable(x):
        return rotate_rows(x, jnp.cos(angles), jnp.sin(angles))
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@struct.dataclass
class PagedCache:
    """Paged KV state threaded through the model as `kv_caches`.

    The serving engine owns page allocation (ray_tpu/serve/llm/cache.py);
    the model writes new tokens into pages and attends through block tables
    (ops/paged_attention.py). A caller hands the model ONE pool for all
    layers, `kv_pages` [L, P, Hkv, page, 2*D], with block_tables [L, B, MP]
    and total_lens [L, B] tiled per layer, and gets the same back. Inside
    the layer scan the pool rides the CARRY whole, never sliced or
    re-stacked, so the loop updates the donated buffer in place; a layer
    sees that pool, its own [B, MP] / [B] slices (they ride the scan's xs)
    and its index in `layer`. (A latent family keeps a cache object of its
    own over a pool of one row a token for all heads, `[L, P, 1, page,
    lanes]` = `[c_kv | k_rope | zeros]`: models/kimi.py: LatentCache.)
    """

    kv_pages: jax.Array      # [L, P, Hkv, page, 2*D] (K | V in lanes)
    block_tables: jax.Array  # [B, MP] int32 page ids ([L, B, MP] outside)
    total_lens: jax.Array    # [B] int32, INCLUDING new tokens ([L, B])
    # this layer's index into kv_pages, set by the scan; None where
    # kv_pages has no layer axis (an unscanned layer's own [P, ...] pool)
    layer: Optional[jax.Array] = None
    # STATIC number of block-table columns a cached prefix may span during
    # prefill (0 = no prefix part compiled in); decode ignores it
    ctx_pages: int = struct.field(pytree_node=False, default=0)
    # STATIC: force the jnp reference attention paths. Set by
    # tensor-parallel engines — the Pallas kernels are single-device
    # programs, so sharded steps (traced under GSPMD) must use the
    # reference einsums, which partition like any other XLA op. A static
    # field (not a process flag): each engine's jit cache keys on it, so
    # kernel and reference lowerings never mix within or across engines.
    ref_attention: bool = struct.field(pytree_node=False, default=False)
    # STATIC: the new tokens are one block of a diffusion model in a
    # denoising pass (serve/llm/stage.py, kind "block"): they are written
    # to their pages and ALL attend all of `total_lens`, themselves
    # included, with no mask between them; or two blocks, the row's
    # pending one first (`_block_step`), and the logits are the last's
    block_step: bool = struct.field(pytree_node=False, default=False)

    # what a serving program carries from dispatch to dispatch, and how a
    # fused decode step renews it (models/jamba.py: HybridCache has both)
    @property
    def pool(self):
        return self.kv_pages

    def step(self, pool, total_lens):
        return self.replace(kv_pages=pool, total_lens=jnp.broadcast_to(
            total_lens, self.block_tables.shape[:1] + total_lens.shape))


def _block_step(q, k, v, positions, pc: PagedCache, block: int):
    """A denoising pass's write and attention (`PagedCache.block_step`):
    the new tokens are whole blocks of `block`, contiguous each. A block's
    keys go to its pages (this pass's; the next overwrites them) and its
    queries see their row up to the block's own end, with no mask inside
    it. One block (every pass but a program's first, and the benchmark's
    check): it ends the row and sees all of `total_lens`. Two (the pass
    that opens a block, serve/llm/stage.py `_block_program`): the row's
    pending block beside the new one; one that lies past `total_lens` is
    padding, written nowhere and given no key. One read of a row's pages
    a block: the kernel takes one length a row.
    -> (out [B, S, Hq, D], the pool)"""
    s = q.shape[1]
    kv_pages, outs = pc.kv_pages, []
    for cut in (slice(lo, lo + block) for lo in range(0, s, block)):
        kv_pages = paged_write(kv_pages, k[:, cut], v[:, cut],
                               pc.block_tables, positions[:, cut],
                               pc.total_lens, pc.layer)
        lengths = pc.total_lens
        if cut.stop < s:
            end = positions[:, cut.stop - 1] + 1
            lengths = jnp.where(end <= lengths, end, 0)
        outs.append(paged_attention_block(
            q[:, cut], kv_pages, pc.block_tables, lengths, layer=pc.layer,
            force_reference=pc.ref_attention))
    return jnp.concatenate(outs, axis=1), kv_pages


class Attention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, segment_ids=None):
        cfg = self.config
        hd = cfg.head_dim_
        nq, nkv = cfg.num_heads, cfg.num_kv_heads
        # fused QKV: one [h, (nq+2*nkv)*hd] matmul feeds the MXU better than 3
        qkv = dense(cfg, (nq + 2 * nkv) * hd, ("embed", "qkv"), "qkv_proj")(x)
        q, k, v = jnp.split(qkv, [nq * hd, (nq + nkv) * hd], axis=-1)
        b, s = x.shape[:2]
        q = q.reshape(b, s, nq, hd)
        k = k.reshape(b, s, nkv, hd)
        v = v.reshape(b, s, nkv, hd)
        if cfg.qk_norm:
            q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, (None,),
                        name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, (None,),
                        name="k_norm")(k)
        if cfg.rope_theta is not None:
            # the rotation reads rows where the attention's kernel will
            if isinstance(kv_cache, PagedCache):
                rows = flash_on_one_device(
                    "reference" if kv_cache.ref_attention else None)
            else:   # (any other cache is a dense decode step's)
                rows = kv_cache is None and flash_on_one_device(
                    cfg.attention_impl)
            q = rope(q, positions, cfg.rope_theta, rows)
            k = rope(k, positions, cfg.rope_theta, rows)
        if isinstance(kv_cache, PagedCache):
            # Serving path: write new K/V into this layer's pages of the
            # pool, then attend. Decode (S == 1) streams only the used
            # pages through the Pallas kernel; prefill attends to itself
            # (causal flash, no page reads) merged with the cached prefix
            # by log-sum-exp.
            pc = kv_cache
            if pc.block_step:
                out, kv_pages = _block_step(q, k, v, positions, pc,
                                            cfg.block_causal or s)
            else:
                kv_pages = paged_write(pc.kv_pages, k, v, pc.block_tables,
                                       positions, pc.total_lens, pc.layer)
                if s == 1:
                    out = paged_attention_decode(
                        q[:, 0], kv_pages, pc.block_tables, pc.total_lens,
                        layer=pc.layer,
                        force_reference=pc.ref_attention)[:, None]
                else:
                    out = paged_prefill_attention(
                        q, k, v, kv_pages, pc.block_tables, positions,
                        pc.total_lens, ctx_pages=pc.ctx_pages,
                        impl="reference" if pc.ref_attention else None,
                        layer=pc.layer, block_causal=cfg.block_causal)
            new_cache = pc.replace(kv_pages=kv_pages)
        else:
            if kv_cache is not None:
                # decode path: append to cache (serving engine manages layout)
                k = jnp.concatenate([kv_cache[0], k], axis=1)
                v = jnp.concatenate([kv_cache[1], v], axis=1)
                if segment_ids is not None:
                    if not isinstance(segment_ids, tuple):
                        # a single array must cover the FULL kv axis (cache +
                        # new tokens); the query part is its suffix
                        segment_ids = (segment_ids[:, -s:], segment_ids)
                    q_seg, kv_seg = segment_ids
                    if kv_seg.shape[1] != k.shape[1]:
                        raise ValueError(
                            f"kv segment_ids length {kv_seg.shape[1]} must "
                            f"equal cache+input length {k.shape[1]}")
                    segment_ids = (q_seg, kv_seg)
            # always causal: the kernels mask relative to the end of the kv
            # axis (tril k=sk-sq), which is correct for multi-token decode
            # and chunked prefill as well as plain training
            impl = cfg.attention_impl
            if kv_cache is not None and impl in ("ring", "ulysses"):
                impl = None  # kv-cache decode is dense; sp is for training
            out = attention(q, k, v, causal=True, segment_ids=segment_ids,
                            impl=impl, block_causal=cfg.block_causal)
            new_cache = (k, v) if kv_cache is not None else None
        out = out.reshape(b, s, nq * hd)
        out = dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj")(out)
        return out, new_cache


def gated_silu(gate, up, limit: Optional[float] = None):
    """silu(gate) * up, the halves clamped first where a model has a
    `swiglu_limit`."""
    if limit is not None:
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
    return nn.silu(gate) * up


class MLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        # fused gate+up projection
        gate_up = dense(cfg, 2 * cfg.intermediate_size, ("embed", "mlp"),
                        "gate_up_proj")(x)
        gate, up = jnp.split(gate_up, 2, axis=-1)
        y = gated_silu(gate, up, getattr(cfg, "swiglu_limit", None))
        return dense(cfg, cfg.hidden_size, ("mlp", "embed"), "down_proj")(y)


class MoEMLP(nn.Module):
    """Mixtral's sparse expert FFN (HF `MixtralSparseMoeBlock`):
    `p = softmax(x W_g)` in float32, the k largest kept and renormalised
    to sum 1, `y = sum_i p_i * W2_i(silu(W1_i x) * W3_i x)`.

    Where experts are not sharded (no ep axis in the ambient mesh: every
    serving engine and the one-chip trainer) the layer is DROPLESS: the
    T*k assignments are ordered by expert and go through one grouped
    matmul each way (ops/grouped_matmul.py), un-ordered, weighted and
    summed. Every token is served by all k of its experts, whatever its
    neighbours chose; `capacity_factor` and `moe_group_size` play no part.
    `token_mask` [B, S] (False = a row or position the caller padded)
    keeps padding out of every group: it costs no expert work, cannot
    move a real token's result, gets a zero output and is not counted.
    Shapes are static (T*k rows; group sizes are data).

    With experts sharded over ep the GShard capacity dispatch below stays:
    fixed-size groups and a capacity-bounded one-hot dispatch tensor
    [G, g, E, C], which XLA lowers to all-to-alls over the ep axis, and
    which DROPS what overflows an expert's capacity.

    Returns the mixed output. Sown, for callers that make the collection
    mutable: into "losses" the Switch/GShard load-balancing loss
    E * sum_e(frac_tokens_e * frac_probs_e), pre-scaled by
    router_aux_loss_coef (see the sow call for the consumer contract);
    into "routing" `expert_counts`, the [E] int32 count of real assignments
    per expert (the serving engine's `moe_*` counters).

    `moe_scoring` "sigmoid" (DeepSeek-V3's router): `sc = sigmoid(x W_r)`
    in float32, the k CHOSEN are the largest of `sc + b` (`router_bias`, a
    float32 [E] that enters the choice only), their weights `sc_i / (sum
    of the chosen sc + 1e-20) * routed_scaling_factor`.
    `n_shared_experts`: `+ Shared(x)`, a gated FFN every token passes.

    A SHARE of a layer (`n_routed_experts` R > `num_experts` E): the router
    and the choice are over all R, the weights are the whole model's
    (normalised over all k chosen, held or not), and the layer computes
    the chosen experts in `[expert_first, expert_first + E)` only. An
    assignment to an expert it does not hold joins the dropless path's
    trailing group beside the padding: no row in a tile, no expert work,
    a zero contribution; `expert_counts` counts the HELD experts. Dropless
    means: no assignment to a held expert is dropped, whatever the routing
    (the row layout holds all T*k). What the absent experts would add is
    left out: the layer returns its share of the sum (plus the shared
    expert, which is whole).
    """

    config: LlamaConfig

    @nn.compact
    def __call__(self, x, token_mask=None, stacked=None, choice=None):
        """stacked: None, or (gate_up [L, E, h, 2f], down [L, E, f, h],
        layer): the whole scanned stack of expert weights and this layer's
        index in it, for the dropless path's kernel to read in place (see
        `_stacked_experts`). choice: None, or what a router OUTSIDE the
        layer decided (models/zaya.py: an MLP whose state runs down the
        stack), (probs [T, R] float32, gate [T, k] float32 the chosen
        experts' weights as they are applied, idx [T, k] int32): the layer
        then has no `router` of its own and serves that choice on the same
        paths."""
        cfg = self.config
        E, k = cfg.num_experts, cfg.num_experts_per_tok
        R = cfg.routed_experts               # the router's width
        f = cfg.expert_width
        b, s, h = x.shape
        T = b * s
        xt = x.reshape(T, h)

        if choice is None:
            router = self.param(
                "router", A(nn.initializers.normal(0.02), ("embed", None)),
                (h, R), jnp.float32)
        with tracing.scope("rtpu.moe.route"):
            if choice is None:
                # routing in fp32 (tiny matmul, numerically load-bearing)
                logits = jnp.einsum("th,he->te", xt.astype(jnp.float32),
                                    router)
            if choice is not None:
                probs, gate, idx = choice
            elif cfg.moe_scoring == "sigmoid":
                probs = jax.nn.sigmoid(logits)               # [T,R]
                bias = self.param(
                    "router_bias", A(nn.initializers.zeros, (None,)), (R,),
                    jnp.float32)
                _, idx = jax.lax.top_k(probs + bias, k)      # [T,k]
                gate = jnp.take_along_axis(probs, idx, axis=-1)
                if cfg.norm_topk_prob:
                    gate = gate / (gate.sum(-1, keepdims=True) + 1e-20)
                gate = gate * cfg.routed_scaling_factor
            else:
                probs = jax.nn.softmax(logits, axis=-1)      # [T,E]
                gate, idx = jax.lax.top_k(probs, k)          # [T,k]
                if cfg.norm_topk_prob:
                    gate = gate / jnp.maximum(
                        gate.sum(-1, keepdims=True), 1e-9)
            if R != E:
                # this chip's share: the held experts by their own index,
                # every other assignment to the trailing group E
                idx = idx - cfg.expert_first
                idx = jnp.where((idx >= 0) & (idx < E), idx, E)
        if R != E:
            # [B, S, 1, E] bool, the held experts each token chose, for a
            # caller that asks for the "selection" collection (the
            # benchmark's check); nobody else pays for it
            self.sow("selection", "held", (
                idx[..., None] == jnp.arange(E)).any(-2).reshape(b, s, 1, E))

        w_gu = self.param(
            "experts_gate_up",
            A(nn.initializers.lecun_normal(), ("expert", "embed", "mlp")),
            (E, h, 2 * f), cfg.param_dtype)
        w_dn = self.param(
            "experts_down",
            A(nn.initializers.lecun_normal(), ("expert", "mlp", "embed")),
            (E, f, h), cfg.param_dtype)
        if _experts_sharded():
            out = self._capacity_dispatch(xt, gate, idx, w_gu, w_dn)
        else:
            layer = None
            if stacked is not None:
                w_gu, w_dn, layer = stacked
            out = self._dropless(xt, gate, idx, w_gu, w_dn, token_mask,
                                 layer)

        if cfg.n_shared_experts:
            out = out + MLP(dataclasses.replace(
                cfg, intermediate_size=cfg.n_shared_experts * f),
                name="shared")(xt)
        if R != E:
            # a share serves (serving has no balancing loss to sow)
            return out.reshape(b, s, h)
        # Switch/GShard load-balancing aux loss
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)     # [T,k,E]
        frac_tokens = onehot.sum((0, 1)).astype(jnp.float32) / (T * k)
        frac_probs = probs.mean(0)
        aux = E * jnp.sum(frac_tokens * frac_probs)
        # Sown (not returned) so per-token nll stays pure cross-entropy;
        # trainers opt in with apply(..., mutable=["losses"]) and add the
        # (already coefficient-scaled) terms to their loss. sow is a no-op
        # for callers that don't mutate the collection (e.g. serving).
        self.sow("losses", "router_aux_scaled",
                 cfg.router_aux_loss_coef * aux,
                 reduce_fn=lambda a, b: a + b, init_fn=lambda: 0.0)
        return out.reshape(b, s, h)

    def _dropless(self, xt, gate, idx, w_gu, w_dn, token_mask, layer):
        from ..ops.grouped_matmul import grouped_matmul

        cfg = self.config
        E, k = cfg.num_experts, cfg.num_experts_per_tok
        T, h = xt.shape
        M = T * k
        tm, aligned, rows, block = moe_row_layout(T, cfg)
        with tracing.scope("rtpu.moe.layout"):
            expert = idx.reshape(M)          # assignment t*k+j: token t
            if token_mask is not None:
                # padding joins the trailing group E, which multiplies
                # nothing
                expert = jnp.where(jnp.repeat(token_mask.reshape(T), k),
                                   expert, E)
            counts, ends, at, row_of = dropless_layout(expert, E, tm,
                                                       aligned, rows)
        self.sow("routing", "expert_counts", counts,
                 reduce_fn=lambda a, b: a + b,
                 init_fn=lambda: jnp.zeros((E,), jnp.int32))
        w_gu, w_dn = w_gu.astype(cfg.dtype), w_dn.astype(cfg.dtype)

        def experts_on(lo, n):
            """The expert FFN on the `n` rows from `lo` on; rows past the
            last group's come back undefined."""
            with tracing.scope("rtpu.moe.layout"):
                here = jnp.diff(jnp.clip(ends, lo, lo + n), prepend=lo)
                rows_at = jax.lax.dynamic_slice(at, (lo,), (n,))
            with tracing.scope("rtpu.moe.gather"):
                x_rows = xt[rows_at // k]
            gu = grouped_matmul(x_rows, w_gu, here, layer, tm)
            gate_p, up_p = jnp.split(gu, 2, axis=-1)
            return grouped_matmul(
                gated_silu(gate_p, up_p, cfg.swiglu_limit), w_dn, here,
                layer, tm)

        # A wave's rows go through the experts `block` at a time: the
        # [block, 2f] intermediate stays small whatever the wave, and a
        # block past the last group (all of a wave's tail, when 15 of its
        # 16 rows are padding) is skipped, so the elementwise work between
        # the two matmuls follows the real tokens too.
        with tracing.scope("rtpu.moe.products"):
            if block == rows:
                y = experts_on(0, rows)
            else:
                y = jax.lax.map(
                    lambda lo: jax.lax.cond(
                        lo < ends[-1], lambda: experts_on(lo, block),
                        lambda: jnp.zeros((block, h), cfg.dtype)),
                    jnp.arange(0, rows, block)).reshape(rows, h)
        with tracing.scope("rtpu.moe.unsort"):
            # back to token order; the k weighted outputs are summed in
            # float32, in the same order wherever the token sits
            y = y[row_of].reshape(T, k, h)
            if cfg.routed_experts != E:
                # an assignment to an absent expert reads a row past the
                # last group, which is undefined: it adds nothing
                y = jnp.where((expert < E).reshape(T, k, 1), y, 0)
            out = jnp.einsum("tkh,tk->th", y.astype(jnp.float32),
                             gate).astype(cfg.dtype)
            if token_mask is not None:
                out = jnp.where(token_mask.reshape(T, 1), out, 0)
        return out

    def _capacity_dispatch(self, xt, gate, idx, w_gu, w_dn):
        cfg = self.config
        E, k = cfg.num_experts, cfg.num_experts_per_tok
        T, h = xt.shape
        g = min(cfg.moe_group_size, T)
        pad = (-T) % g
        G = (T + pad) // g

        def grouped(a):
            return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)
                           ).reshape((G, g) + a.shape[1:])

        xg, gate, idx = grouped(xt), grouped(gate), grouped(idx)
        capacity = max(1, int(cfg.capacity_factor * k * g / E))
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)     # [G,g,k,E]
        assigns = onehot.reshape(G, g * k, E)
        # position of each assignment within its expert's capacity buffer
        pos = (jnp.cumsum(assigns, axis=1) - assigns)
        pos = (pos * assigns).sum(-1).reshape(G, g, k)       # [G,g,k]
        keep = (pos < capacity).astype(cfg.dtype)
        disp = (onehot.astype(cfg.dtype)[..., None]
                * jax.nn.one_hot(pos, capacity, dtype=cfg.dtype)[
                    :, :, :, None, :])                       # [G,g,k,E,C]
        disp = disp * keep[..., None, None]
        combine = (disp * gate.astype(cfg.dtype)[..., None, None]).sum(2)
        dispatch = disp.sum(2)                               # [G,g,E,C]
        ex_in = jnp.einsum("Gtec,Gth->Gech", dispatch, xg)   # [G,E,C,h]
        gu = jnp.einsum("Gech,ehm->Gecm", ex_in, w_gu.astype(cfg.dtype))
        gate_p, up_p = jnp.split(gu, 2, axis=-1)
        y = gated_silu(gate_p, up_p, cfg.swiglu_limit)
        ex_out = jnp.einsum("Gecf,efh->Gech", y, w_dn.astype(cfg.dtype))
        out = jnp.einsum("Gtec,Gech->Gth", combine, ex_out)
        return out.reshape(G * g, h)[:T]


def _runs(a, starts, n: int):
    """[len(starts), n]: `a[s : s + n]` for each start `s` in `starts`
    (0 <= s <= len(a)), with `a`'s last element past its end (what a clip
    of the index would read). No gather of scalars and no loop over the
    starts: `a` in windows of `n`, a gather of the two whole windows a run
    lies in, and a shift left by the run's offset in them, one select a
    bit of it. (On a v5e, 192 runs of 256 out of 32,768: 15 us; as 192
    dynamic slices 165, as a gather of 49,152 scalars 365: PERF.md section
    6, PR 49.)"""
    windows = -(-a.shape[0] // n) + 2
    a = jnp.pad(a, (0, windows * n - a.shape[0]), mode="edge").reshape(
        windows, n)
    two = jnp.concatenate([a[:-1], a[1:]], 1)[starts // n]      # [runs, 2n]
    offset, bit = starts % n, 1
    while bit < n:
        two = jnp.where(((offset & bit) != 0)[:, None],
                        jnp.roll(two, -bit, 1), two)
        bit *= 2
    return two[:, :n]


def dropless_layout(expert, n_experts: int, tm: int, aligned: bool,
                    rows: int) -> tuple:
    """The dropless layer's rows for `expert` ([M] int32: the expert each
    assignment chose, `n_experts` for padding and for an expert a share
    does not hold), as `moe_row_layout` gave (tm, aligned, rows):
    (`counts` [E] real assignments an expert, `ends` [E] where each
    expert's rows end, `at` [rows] the assignment a row holds, `row_of`
    [M] the row an assignment's result is read from).

    Assignments are sorted by expert (stable, the trailing group last).
    Packed, row p holds the p-th of that order. Aligned, every expert's
    rows start on a tile of the kernel: group e moves `shift[e]` rows down
    and is padded to whole tiles (the padding repeats some real row:
    multiplied with the tile it shares either way, never read back), and
    the trailing group follows the last expert's.

    The integer work is sums over compares that XLA fuses, on a chip where
    a gather or a scatter of 32,768 scalars costs a third of a
    millisecond: counts and an assignment's shift from ONE [M, E + 1]
    compare (no scatter-add of assignments, no gather by expert), and the
    aligned layout from a table a TILE (a tile has one owner: the groups
    that end at or before its first row, [rows / tm, E] compares, say how
    far its rows moved; `at` is the sorted order read in runs of `tm`
    from there, `_runs`), not a search a row."""
    E, M = n_experts, expert.shape[0]
    order = jnp.argsort(expert, stable=True)
    joined = expert[:, None] == jnp.arange(E + 1)          # [M, E + 1]
    counts = joined.sum(0, dtype=jnp.int32)[:E]
    row_of = jnp.argsort(order)
    if not aligned:
        return counts, jnp.cumsum(counts), order, row_of
    sizes = -(-counts // tm) * tm
    pad = sizes - counts
    shift = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(pad)])
    ends = jnp.cumsum(sizes)
    first = jnp.arange(0, rows, tm)                        # of each tile
    run = first - jnp.sum((first[:, None] >= ends) * pad, 1)
    at = _runs(order, jnp.minimum(run, M), tm).reshape(rows)
    return counts, ends, at, row_of + jnp.sum(joined * shift, 1)


# sorted assignments per pass through the experts (MoEMLP._dropless): a
# [4096, 2f] intermediate is 235 MB at Mixtral widths. Swept in PR 49
# (benchmarks/moe_gmm_probe.py --layer, PERF.md section 6): with the stack
# of blocks below, 4096 to 16384 rows a block read the same to 2%
# at Mellum2's widths; what a pass pays for being cut is the stack, not the
# number of calls (ROADMAP A15 (c), held until its cell can measure it).
_MOE_ROWS = 4096


def moe_row_layout(tokens: int, cfg: LlamaConfig) -> tuple:
    """(tm, aligned, rows, block) of the dropless layer's pass over `tokens`
    tokens: the grouped matmul's m-tile and whether each expert's rows
    start on one (`ops/grouped_matmul.py: row_tile`, from the tokens * k
    assignments and the experts alone), the rows that layout takes, and
    how many of them ONE grouped-matmul call gets (all, or _MOE_ROWS at a
    time)."""
    from ..ops.grouped_matmul import aligned_rows, row_tile

    m, e = tokens * cfg.num_experts_per_tok, cfg.num_experts
    # the tile is chosen for the assignments a share EXPECTS (e of the
    # routed experts' under even routing); the rows hold all of them
    tm, aligned = row_tile(m * e // cfg.routed_experts, e)
    rows = aligned_rows(m, e, tm) if aligned else m
    if rows <= _MOE_ROWS or (not aligned and rows % _MOE_ROWS):
        return tm, aligned, rows, rows
    return tm, aligned, -(-rows // _MOE_ROWS) * _MOE_ROWS, _MOE_ROWS


def _tile_padded(counts, tokens: int, cfg: LlamaConfig):
    """(rows each expert's group takes in a pass of `tokens` tokens, the
    pass's layout): `counts` ([..., E], numpy) on whole tiles where the
    layout aligns them."""
    import numpy as np

    layout = moe_row_layout(tokens, cfg)
    tm, aligned = layout[:2]
    counts = np.asarray(counts)
    return (-(-counts // tm) * tm if aligned else counts), layout


def moe_tile_rows(counts, tokens: int, cfg: LlamaConfig) -> int:
    """Rows the grouped-matmul kernel MULTIPLIES (tile visits x m-tile) for
    passes of `tokens` tokens through dropless layers whose experts got
    `counts` ([..., E] real assignments, a pass a row of E): a layer's two
    products run over the same rows and tile, so this counts them once.
    The real assignments over it are the fill of the tiles. Host
    arithmetic on counts the engine fetched anyway (a pass cut into
    _MOE_ROWS blocks counts the same: blocks end on tile boundaries)."""
    from ..ops.grouped_matmul import tile_visits

    sizes, (tm, *_) = _tile_padded(counts, tokens, cfg)
    return tile_visits(sizes, tm) * tm


def moe_gmm_calls(counts, tokens: int, cfg: LlamaConfig,
                  passes: int = 1) -> tuple:
    """(grouped-matmul calls of ONE product, rows they were handed) for
    passes of `tokens` tokens through dropless layers whose experts got
    `counts` ([..., E] real assignments, a pass a row of E): a pass in one
    call hands it all its rows whatever they hold; a pass in _MOE_ROWS
    blocks makes a call for each block that holds a row of a group
    (`MoEMLP._dropless`: the blocks past the last group's end are skipped).
    The rows are what the layer gathers, multiplies by tiles and passes
    through `silu * up`; the real assignments over them say how much of
    that was padding. `passes`: the rows of a wave whose assignments a row
    of `counts` sums (each a pass of its own: one call at least; the blocks
    of their sum are a floor). Host arithmetic, as `moe_tile_rows`."""
    import numpy as np

    sizes, (_, _, rows, block) = _tile_padded(counts, tokens, cfg)
    ends = sizes.sum(-1)                  # a pass's last group's end
    blocks = -(-ends // block) if block < rows else np.ones_like(ends)
    calls = int(np.maximum(blocks, passes).sum())
    return calls, calls * block


def moe_tile_kn_fill_pct(passes, cfg: LlamaConfig) -> float:
    """Of the K x N a visit of the grouped matmul multiplies, the percent
    the weights have (`ops/grouped_matmul.py: tile_fit`; 100: `tk` and `tn`
    divide both widths): a layer's two products weighted by their
    operations, at the tile of a pass of each of `passes` tokens; the
    lowest of them. From the shapes alone: it says whether the tile rule
    fitted this model's widths, once, not what a run did."""
    from ..ops.grouped_matmul import _tile, tile_fit

    h, f = cfg.hidden_size, cfg.expert_width
    fills = []
    for tokens in passes:
        tm = moe_row_layout(tokens, cfg)[0]
        fills.append(3 * h * f / sum(
            k * n / tile_fit(k, n, _tile(tm, k, n))
            for k, n in ((h, 2 * f), (f, h))))
    return round(100 * min(fills), 2)


class ExpertFacts:
    """What an expert model's dispatches count (serve/llm/stage.py:
    model_family), written once for the families with experts. Their
    programs return, behind the tokens, the [steps, L, E] count of real
    assignments an expert (stage.py: pack); a harvest reads it into the
    record's `moe_assignments` and `moe_experts_touched` (the first two
    totals, of this record) and `moe_expert_tokens_max` (the fullest
    expert's count)."""

    STATS = {
        "moe_assignments_total":
            "real (token, expert) assignments of an expert model, all layers",
        "moe_experts_touched_total":
            "experts with at least one real token, summed over layers and "
            "steps",
        "moe_tile_rows_total":
            "rows the grouped matmul multiplied (tile visits x m-tile), all "
            "layers; moe_assignments_total over it is the fill of its tiles",
        "moe_gmm_calls_total":
            "grouped-matmul calls of ONE product, all layers: one a decode "
            "step or a short pass, and one for each block that held real "
            "rows of a pass long enough to be cut into blocks",
        "moe_layout_rows_total":
            "rows those calls were handed (gathered, multiplied by tiles, "
            "passed through silu * up), all layers; over moe_assignments_"
            "total it is the rows moved a real assignment",
        "moe_tile_kn_fill_pct":
            "percent of the K x N a visit of the grouped matmul multiplies "
            "that the expert weights have, at the decode step's tile and "
            "the largest bucket's (the lower; 100: the tile divides both)",
    }

    def __init__(self, cfg: LlamaConfig, engine_config, step_tokens: int = 1):
        """`step_tokens`: the tokens a slot a pass of the decode program
        computes (a block family's block)."""
        self.cfg = cfg
        self.layers = getattr(cfg, "n_expert_layers", cfg.num_layers)
        self.kn_fill_pct = moe_tile_kn_fill_pct(
            (engine_config.max_batch * step_tokens,
             engine_config.prefill_buckets[-1]), cfg)

    def sizes(self, pool_bytes: dict) -> dict:
        return {"moe_tile_kn_fill_pct": self.kn_fill_pct}

    def harvest(self, totals: dict, rec: dict, packed) -> dict:
        layers, experts = self.layers, self.cfg.num_experts
        # a prefill or a verify returns ONE count over the rows it computed
        # (an expert a wave touches in two rows counts once: the least a
        # wave has to read)
        counts = packed[-rec["k"] * layers * experts:].reshape(
            -1, layers, experts)
        assignments, touched = int(counts.sum()), int((counts > 0).sum())
        totals["moe_assignments_total"] += assignments
        totals["moe_experts_touched_total"] += touched
        # what the grouped matmul multiplied to serve them: a pass of the
        # model is the slot set (decode) or one row's length bucket; a wave
        # of several rows is counted as if their assignments were sorted
        # together (each row pays boundary visits of its own: a floor); a
        # decode step or a block program is ONE pass over its slot set
        rows = max(rec["rows_padded"], 1)
        per_pass, passes = {"decode": (rows, 1),
                            "block": (rec["tokens_padded"], 1)}.get(
            rec["kind"], (rec["tokens_padded"] // rows, rows))
        # the pass that opens a block is two blocks wide a row
        opening = int(rec["kind"] == "block")
        for part, tokens in ((counts[:opening], 2 * per_pass),
                             (counts[opening:], per_pass)):
            totals["moe_tile_rows_total"] += moe_tile_rows(
                part, tokens, self.cfg)
            calls, handed = moe_gmm_calls(part, tokens, self.cfg, passes)
            totals["moe_gmm_calls_total"] += calls
            totals["moe_layout_rows_total"] += handed
        return {"moe_assignments": assignments,
                "moe_experts_touched": touched,
                "moe_expert_tokens_max": int(counts.max())}


def _stacked_experts(module: nn.Module, cfg: LlamaConfig, kv_caches):
    """The scanned stack of expert weights, (gate_up [L, E, h, 2f], down
    [L, E, f, h]), for the paged serving path; None anywhere else.

    Inside the layer scan a layer's weights are a dynamic slice of the
    stack. XLA fuses that slice into a matmul of its own, but a kernel
    (the grouped matmul's custom call) needs its operand in memory, so
    every layer of every step would first COPY its experts (2.8 GB at
    Mixtral widths). The serving path therefore hands the layers the
    whole stack, beside the paged pool in the scan's carry, and the kernel
    picks the layer's experts by index (ops/grouped_matmul.py), as the
    paged ops do with the pool: on a v5e, 3 Mixtral layers at 18 rows, a
    decode step takes 12.7 ms so and 37.8 ms sliced (PERF.md section 6, PR
    29). A program that differentiates (training:
    no PagedCache) keeps the sliced weights: a carried stack would make
    every layer's backward add a stack-sized cotangent."""
    if not (cfg.num_experts and isinstance(kv_caches, PagedCache)
            and not _experts_sharded()):
        return None
    return stacked_experts(module, cfg, ("layers", "layer", "moe"))


def _experts_sharded() -> bool:
    """Whether the ambient mesh (parallel/mesh.py) shards experts."""
    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    return mesh is not None and mesh.shape.get("ep", 1) > 1


class DecoderLayer(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, kv_cache=None,
                 token_mask=None, experts=None):
        cfg = self.config
        if experts is not None:
            experts += (kv_cache.layer,)
        h, new_cache = Attention(cfg, name="attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="attn_norm")(x),
            positions, kv_cache=kv_cache, segment_ids=segment_ids)
        h = checkpoint_name(h, "attn_out")
        x = x + h
        normed = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="mlp_norm")(x)
        if cfg.num_experts:
            h = MoEMLP(cfg, name="moe")(normed, token_mask, experts)
        else:
            h = MLP(cfg, name="mlp")(normed)
        h = checkpoint_name(h, "mlp_out")
        return x + h, new_cache


class ScannedLayer(nn.Module):
    """One layer body, scanned over a stacked `layers` param axis.

    The paged pool rides the CARRY (None when there is no paged cache: a
    None leaf adds nothing to the program, and the same goes for the expert
    layer's `token_mask` and stacked `experts`); `kv_cache` is this layer's
    slice of the scan's xs: a PagedCache without its pool, or a dense
    (k, v) pair, whose grown copy goes out through the ys. `consts` is
    `scan_run`'s broadcast argument and None here: what a pass holds fixed
    rides the carry, where the trainer's pinned program has it
    (tests/test_program_pins.py: `tiny:train_step`).
    """
    config: LlamaConfig

    @nn.compact
    def __call__(self, carry, kv_cache, consts=None):
        x, positions, segment_ids, kv_pages, token_mask, experts = carry
        if kv_pages is not None:
            kv_cache = kv_cache.replace(kv_pages=kv_pages)
        x, new_cache = DecoderLayer(self.config, name="layer")(
            x, positions, segment_ids, kv_cache, token_mask, experts)
        if kv_pages is not None:
            kv_pages, new_cache = new_cache.kv_pages, None
        return (x, positions, segment_ids, kv_pages, token_mask,
                experts), new_cache


def _apply_layers(cfg: LlamaConfig, length: int, x, positions, segment_ids,
                  kv_caches, token_mask=None, experts=None):
    """Run `length` scanned layers named "layers" under the calling
    module; returns (x, new_caches). Shared by LlamaModel and LayerStack,
    and the scan is every family's (`_stack.scan_run`), so every consumer
    produces the identical "layers" param collection (leaves stacked with
    a leading [length] axis under PARTITION_NAME "layers").

    A PagedCache's pool goes through the scan's carry, the rest of it (and
    the layer's index) through the xs, and it comes back as the caller
    handed it in, with the updated pool."""
    layer_cls = ScannedLayer
    if cfg.remat:
        layer_cls = nn.remat(ScannedLayer, prevent_cse=False,
                             policy=_remat_policy(cfg.remat_policy))
    layers = scan_run(layer_cls, length, "layers", cfg)
    paged = isinstance(kv_caches, PagedCache)
    kv_pages, xs = None, kv_caches
    if paged:
        kv_pages = kv_caches.kv_pages
        xs = kv_caches.replace(kv_pages=None, layer=jnp.arange(length))
    (x, _, _, kv_pages, _, _), ys = layers(
        (x, positions, segment_ids, kv_pages, token_mask, experts), xs, None)
    return x, kv_caches.replace(kv_pages=kv_pages) if paged else ys


class LayerStack(nn.Module):
    """A sub-stack of decoder layers — one pipeline stage's worth.

    Param tree matches a [layers_per_stage]-length slice of the full
    model's scanned "layers" collection, so stage params are literally
    slices of LlamaModel params (see ops/pipeline.py stack_to_stages).
    """

    config: LlamaConfig
    layers_per_stage: int

    @nn.compact
    def __call__(self, x, positions):
        x, _ = _apply_layers(self.config, self.layers_per_stage, x,
                             positions, None, None)
        return x


class LlamaModel(nn.Module):
    """The whole decoder, or a contiguous slice of its scanned layers (one
    SERVING pipeline stage, serve/llm/stage.py): `n_layers` of them (None:
    all), with the embedding table when `first` and final_norm + lm_head
    when `last`. A slice keeps every parameter's name, so its params are
    literal slices of a whole model's (serve/llm/pp.py stage_params) and
    the same ops run on the same values wherever the layers are cut."""
    config: LlamaConfig
    n_layers: Optional[int] = None
    first: bool = True
    last: bool = True
    # train_lib feature-detects the fused chunked-CE `targets=` path
    supports_fused_loss = True

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None,
                 kv_caches=None, targets=None, token_mask=None,
                 apply_head: bool = True):
        """Forward pass.

        input_ids: [B, S] token ids; the previous stage's [B, S, h] hidden
        states when not `first`. Returns logits; hidden states when not
        `last`; with `apply_head=False` the final-normed hidden states the
        head would multiply (a block step's: of the last block), for a
        caller that folds the head into what it keeps of the logits
        (models/sdar.py: decide).

        token_mask: [B, S] bool, False where the caller padded a row or a
        position; only the expert layer reads it (MoEMLP).

        kv_caches: None (training / full prefill), a PagedCache (serving:
        one [n_layers, P, ...] pool, updated in place), or a (k, v) pair
        stacked over layers — k/v shaped [L, B, S_cache, Hkv, D] when
        scan_layers, else a list of L per-layer (k, v) tuples.  When given,
        returns (logits, new_kv_caches); `positions` must then hold the
        absolute positions of `input_ids` and `segment_ids` (if any) must
        span the full cache+input kv axis.
        """
        cfg = self.config
        n_layers = cfg.num_layers if self.n_layers is None else self.n_layers
        positions = default_positions(input_ids, positions)
        x = input_ids
        if self.first:
            _, x = embed_tokens(self, cfg, input_ids)

        if cfg.scan_layers:
            x, new_caches = _apply_layers(
                cfg, n_layers, x, positions, segment_ids, kv_caches,
                token_mask, _stacked_experts(self, cfg, kv_caches))
        else:
            layer_cls = DecoderLayer
            if cfg.remat:
                layer_cls = nn.remat(DecoderLayer, prevent_cse=False,
                                     policy=_remat_policy(cfg.remat_policy))
            new_caches = [] if kv_caches is not None else None
            for i in range(n_layers):
                cache_i = kv_caches[i] if kv_caches is not None else None
                x, new_cache = layer_cls(cfg, name=f"layer_{i}")(
                    x, positions, segment_ids, cache_i, token_mask)
                if kv_caches is not None:
                    new_caches.append(new_cache)

        if not self.last:
            return (x, new_caches) if kv_caches is not None else x
        if isinstance(kv_caches, PagedCache) and kv_caches.block_step:
            # a denoising pass decides the row's LAST block only: a pending
            # block left of it (`_block_step`) is there for its keys
            x = x[:, -(cfg.block_causal or x.shape[1]):]
        with tracing.scope("rtpu.head"):
            x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        if not apply_head:
            return (x, new_caches) if kv_caches is not None else x
        head_proj = dense(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head")

        def head(x):
            with tracing.scope("rtpu.head"):
                return head_proj(x)

        if targets is not None:
            # Fused chunked cross-entropy: the [B,S,V] logits (fp32!) never
            # materialize — each sequence chunk projects + reduces inside a
            # scan, bounding loss memory to [B,chunk,V]. This is what makes
            # long-sequence training fit in HBM (the full-logit buffer at
            # S=8192, V=32k would be 8 GB fp32 per example-batch).
            chunk = min(cfg.loss_chunk, x.shape[1])
            b, s, e = x.shape
            n_chunks = -(-s // chunk)
            pad = n_chunks * chunk - s
            x_p = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            t_p = jnp.pad(targets, ((0, 0), (0, pad)))
            x_c = x_p.reshape(b, n_chunks, chunk, e).swapaxes(0, 1)
            t_c = t_p.reshape(b, n_chunks, chunk).swapaxes(0, 1)

            def one_chunk(carry, xt):
                xc, tc = xt
                logits = head(xc).astype(jnp.float32)
                logp = jax.nn.log_softmax(logits, axis=-1)
                nll = -jnp.take_along_axis(
                    logp, tc[..., None], axis=-1)[..., 0]
                return carry, nll

            _, nll = jax.lax.scan(one_chunk, 0.0, (x_c, t_c))
            nll = nll.swapaxes(0, 1).reshape(b, n_chunks * chunk)[:, :s]
            if kv_caches is not None:
                return nll, new_caches
            return nll
        logits = head(x)
        if kv_caches is not None:
            return logits, new_caches
        return logits


# ----------------------------------------------------------------- serving
# What serve/llm/stage.py asks of a model family's module: `model_family`.
# pages are all a sequence keeps: a prefill row that starts mid-prompt
# attends to its earlier pages (the path prefix hits use)
RESUMES_PREFILL = True


def pass_cost_ratios(cfg: LlamaConfig) -> tuple:
    """What serve/llm/engine.py's `PassCost` asks of a family that resumes,
    each over the parameters one token multiplies: the weights one prefill
    pass reads (1 for a dense model; an expert model's pass of a bucket's
    length reads every expert and a token multiplies
    `num_experts_per_tok` of them), and the scores a (query, key) pair
    makes in the flash kernel (one a layer and head)."""
    active = cfg.active_params()
    return cfg.num_params() / active, cfg.num_layers * cfg.num_heads / active


def serving_model(cfg: LlamaConfig, n_layers=None, first=True, last=True):
    if first and last:
        return LlamaModel(cfg)
    return LlamaModel(cfg, n_layers=n_layers, first=first, last=last)


def pool_spec(cfg: LlamaConfig, n_layers: int, num_pages: int,
              page_size: int, slots: int):
    """(shape, dtype) of the state a serving engine keeps on the device:
    one page pool in the page-major combined layout [n_layers, P, Hkv,
    page, 2*D]: one decode DMA per page moves K and V for every head
    together; the Hkv axis is the tensor-parallel shard (each tp shard
    holds Hkv/tp heads of EVERY page, so block tables stay global +
    replicated). Nothing is kept per decode slot."""
    return ((n_layers, num_pages, cfg.num_kv_heads, page_size,
             2 * cfg.head_dim_), cfg.dtype)


def serving_cache(cfg: LlamaConfig, pool, block_tables, total_lens=None,
                  slots=None, **static) -> PagedCache:
    """The cache one program pass hands the model: block_tables [B, MP]
    and total_lens [B] (None: `PagedCache.step` brings them) tiled over
    the pool's layers. `slots` is for models with per-slot state."""
    tile = pool.shape[:1]
    return PagedCache(
        kv_pages=pool,
        block_tables=jnp.broadcast_to(block_tables,
                                      tile + block_tables.shape),
        total_lens=None if total_lens is None else jnp.broadcast_to(
            total_lens, tile + total_lens.shape),
        **static)


def dispatch_facts(cfg: LlamaConfig, engine_config) -> list:
    """(serve/llm/stage.py: model_family)"""
    return [ExpertFacts(cfg, engine_config)] if cfg.num_experts else []


# ---------------------------------------------------------------- registry
CONFIGS = {
    "tiny": LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        max_seq_len=256, remat=False),
    "debug-sharded": LlamaConfig(vocab_size=512, hidden_size=128,
                                 intermediate_size=256, num_layers=2,
                                 num_heads=8, num_kv_heads=4,
                                 max_seq_len=512, remat=False),
    "llama-500m": LlamaConfig(vocab_size=32000, hidden_size=1024,
                              intermediate_size=4096, num_layers=24,
                              num_heads=16, num_kv_heads=8),
    "llama-1b": LlamaConfig(vocab_size=32000, hidden_size=2048,
                            intermediate_size=5632, num_layers=22,
                            num_heads=32, num_kv_heads=8),
    "llama3-8b": LlamaConfig(vocab_size=128256, hidden_size=4096,
                             intermediate_size=14336, num_layers=32,
                             num_heads=32, num_kv_heads=8,
                             rope_theta=500000.0),
    "tiny-moe": LlamaConfig(vocab_size=256, hidden_size=64,
                            intermediate_size=128, num_layers=2,
                            num_heads=4, num_kv_heads=2, max_seq_len=256,
                            remat=False, num_experts=4,
                            num_experts_per_tok=2, moe_group_size=64),
    # Mixtral-8x7B shape (the open MoE reference point)
    "mixtral-8x7b": LlamaConfig(vocab_size=32000, hidden_size=4096,
                                intermediate_size=14336, num_layers=32,
                                num_heads=32, num_kv_heads=8,
                                rope_theta=1e6, num_experts=8,
                                num_experts_per_tok=2),
}


def get_config(name: str, **overrides) -> LlamaConfig:
    return dataclasses.replace(CONFIGS[name], **overrides)
