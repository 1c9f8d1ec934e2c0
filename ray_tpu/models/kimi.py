"""Kimi-K2 / DeepSeek-V3-family decoder: multi-head LATENT attention (MLA)
and sigmoid-routed experts beside a shared expert.

With `n(.)` = RMSNorm with a learned weight and `x = n(h)`:

    h <- h + Attn(x);   h <- h + FFN_l(n(h));   logits = W_head n(h_L)

`FFN_l` is a dense gated-silu FFN for the first `first_k_dense_replace`
layers and the expert layer of models/llama.py (`MoEMLP`: sigmoid scores,
a selection bias, a scale, a shared expert, and possibly one chip's SHARE
of the routed experts) for the rest.

Attention keeps ONE latent a token for all heads. `c_q = n(W_qa x)`; `q =
W_qb c_q`, H heads of `[q_nope (dn) | q_rope (dr)]`; `[c | k_r] = W_kva x`;
`c_kv = n(c)`; `k_rope = R_t(k_r)`, one for all heads; `q_rope <-
R_t(q_rope)`; `[k_nope_h | v_h] = W_kvb,h c_kv`; `score_h(t, j) = s (q_nope_h
. k_nope_h(j) + q_rope_h . k_rope(j))`, causal. `R_t` turns by YaRN's
frequencies (ops/rotary.py) and `s = (dn + dr)^-0.5 m^2`, `m` YaRN's
temperature at `mscale_all_dim` (the factor on cos and sin is `mscale /
mscale_all_dim`'s ratio of temperatures: 1 where the two are equal, and only
that is built).

A page holds `[c_kv | k_rope | zeros]` AFTER the norm and the rotation:
`latent_lanes(r + dr)` lanes a token and layer where per-head keys and
values would take H (dn + dr + dv). The same mathematics runs in two forms:

- PREFILL materialises: per-head keys `[k_nope_h | k_rope]` and values from
  the latents, the flash kernel at keys of dn + dr and values of dv; a
  context behind the pass (a resumed pass, a cached prefix) is walked in
  static chunks of `ctx_chunk_tokens` whose keys and values exist one chunk
  at a time (ops/paged_attention.py: latent_prefill_attention);
- DECODE absorbs (exact, by associativity): `q'_h = [W_UK,h^T q_nope_h |
  q_rope_h]` against the latent row itself as the key, `o'_h = sum_j p_h
  c_kv(j)`, `o_h = W_UV,h o'_h`, `W_kvb,h = [W_UK,h ; W_UV,h]`: one read of
  a row's pages serves every head (latent_attention_decode).

The stack: a scan over the leading dense layers ("dense_layers"), then one
over the expert layers ("layers"), the page pool riding both carries whole.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..ops.paged_attention import (LATENT_CTX_CHUNK, latent_attention_decode,
                                   ctx_chunks, latent_lanes,
                                   latent_prefill_attention, paged_write)
from ..ops.rotary import rotate, yarn_inv_freq, yarn_mscale
from ..util import tracing
from ._stack import (default_positions, dense as _dense, embed_tokens,
                     head_at_gather, own_cache, scan_run, stacked_experts,
                     whole_model_only)
from .llama import MLP, A, ExpertFacts, LlamaConfig, MoEMLP, RMSNorm

# the family's interface flags (serve/llm/stage.py: model_family): pages
# are all a sequence keeps, so a prefill row resumes from them and a page
# found by its content hash may be reused (a latent depends on its prefix
# alone); the head is computed at the position a row samples from only
RESUMES_PREFILL = True
HEAD_AT_GATHER = True


@dataclass(frozen=True)
class KimiConfig(LlamaConfig):
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    # rope_scaling (type "yarn")
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # context tokens whose keys and values a prefill pass materialises at
    # once (no model's key: what the program holds alive)
    ctx_chunk_tokens: int = LATENT_CTX_CHUNK

    def __post_init__(self):
        if not 0 <= self.first_k_dense_replace <= self.num_layers:
            raise ValueError("first_k_dense_replace past the layers")
        self._check_rotation_and_share()

    def _check_rotation_and_share(self) -> None:
        """What holds of every config with this attention and this expert
        layer (models/gigachat.py's too, whose layers are a list)."""
        if self.rope_mscale != self.rope_mscale_all_dim:
            raise NotImplementedError(
                "rope_scaling with mscale != mscale_all_dim scales cos and "
                "sin; only the published case (a factor of 1) is built")
        held = (self.expert_first, self.expert_first + self.num_experts)
        if not 0 <= held[0] <= held[1] <= self.routed_experts:
            raise ValueError(f"held experts {held} outside the routed "
                             f"{self.n_routed_experts}")

    # ---- what serve/llm asks of a latent family's config
    @property
    def head_dim_(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        return latent_lanes(self.latent_width)

    @property
    def n_expert_layers(self) -> int:
        return self.num_layers - self.first_k_dense_replace

    @property
    def latent_bytes_token(self) -> int:
        """What one token's latent costs to read once, all layers, as the
        pool stores it."""
        return (self.num_layers * self.latent_lanes
                * jnp.dtype(self.dtype).itemsize)

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.head_dim_ ** -0.5 * m * m

    @property
    def rope_inv_freq(self) -> np.ndarray:
        return yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_original_max, self.rope_beta_fast, self.rope_beta_slow)

    # ---- sizes
    def attn_params(self) -> int:
        h, nh = self.hidden_size, self.num_heads
        return (h * self.q_lora_rank + self.q_lora_rank
                + self.q_lora_rank * nh * self.head_dim_
                + h * self.latent_width + self.kv_lora_rank
                + self.kv_lora_rank * nh
                * (self.qk_nope_head_dim + self.v_head_dim)
                + nh * self.v_head_dim * h)

    def num_params(self) -> int:
        """The parameters this model HOLDS (a share holds its experts)."""
        h, f, routed = self.hidden_size, self.expert_width, self.routed_experts
        moe = (h * routed + routed + 3 * h * f
               * (self.num_experts + self.n_shared_experts))
        return (self.num_layers * (self.attn_params() + 2 * h)
                + self.first_k_dense_replace * 3 * h * self.intermediate_size
                + self.n_expert_layers * moe + 2 * self.vocab_size * h + h)

    def active_params(self) -> int:
        """Parameters one token multiplies HERE: of its k routed experts
        the expected part this model holds, and no head (a pass computes
        it at one position)."""
        h, f, routed = self.hidden_size, self.expert_width, self.routed_experts
        per_tok = self.num_experts_per_tok * self.num_experts / routed
        moe = h * routed + 3 * h * f * (per_tok + self.n_shared_experts)
        return (self.num_layers * self.attn_params()
                + self.first_k_dense_replace * 3 * h * self.intermediate_size
                + self.n_expert_layers * moe)


def pass_cost_ratios(cfg: KimiConfig) -> tuple:
    """(weights a prefill pass reads, scores a (query, key) pair makes),
    each over the parameters a token multiplies (serve/llm/engine.py:
    PassCost). A pass reads every HELD expert and a token multiplies a few;
    a pair costs H heads x 2 (dn + dr + dv) operations in the materialised
    form where PassCost's pair is one of 4 x 128."""
    active = cfg.active_params()
    embed_head = 2 * cfg.vocab_size * cfg.hidden_size
    pair = (cfg.head_dim_ + cfg.v_head_dim) / 256
    return ((cfg.num_params() - embed_head) / active,
            cfg.num_layers * cfg.num_heads * pair / active)


@struct.dataclass
class LatentCache:
    """Serving state of a KimiModel, threaded through it as `kv_caches`:
    one pool of latent pages `[L, P, 1, page, lanes]` for all layers.
    `gather` [B]: the position (an index into the row) whose logits a
    prefill wants, -1 for none; None: logits at every position."""

    kv_pages: jax.Array
    block_tables: jax.Array      # [B, MP]
    total_lens: jax.Array        # [B], INCLUDING the new tokens
    gather: Optional[jax.Array] = None
    # STATIC, as models/llama.py: PagedCache has them
    ctx_pages: int = struct.field(pytree_node=False, default=0)
    ref_attention: bool = struct.field(pytree_node=False, default=False)

    @property
    def pool(self):
        return self.kv_pages

    def step(self, pool, total_lens):
        return self.replace(kv_pages=pool, total_lens=total_lens)


# ----------------------------------------------------------------- serving
def serving_model(cfg: KimiConfig, n_layers=None, first=True, last=True):
    return whole_model_only(KimiModel, cfg, first, last,
                            "whose stack is a dense run and an expert run")


# (stage.py: model_family) the options that would split a head axis the pool
# has not, or that were never run on this family's two forms of attention
CANNOT_BE_GIVEN = ("keeps one latent row a token for all heads", {
    "tp": "shards the page pool over its kv-head axis "
          "(ServeSharding.kv_pages_sharding), and a latent pool has one "
          "row for every head: splitting the query heads would copy the "
          "pool to every chip, and nothing does that yet",
    "pp": "slices a uniform `layers` axis (stage_params), and this "
          "model's stack is a run of dense layers and a run of expert "
          "layers",
    "spec_lookahead":
        "verifies a draft through the materialised form while decode "
        "runs the absorbed one, and acceptance compares their argmax "
        "bit for bit: no verify dispatch of this family was ever run "
        "against its decode",
})


class LatentFacts:
    """What a latent pool's dispatches count (serve/llm/stage.py:
    model_family). Every record says `mla_layers` (each keeps one latent
    row a token) and `latent_bytes_token` (the bytes one token's latents
    cost to read once over all of them as the pool stores them); a
    prefill's `mla_ctx_chunks`, a tuple a real row: how many of the static
    context chunks the pass materialised keys and values of (WHOLE, where
    the row's context reaches into one: ops/paged_attention.py:
    latent_prefill_attention); a harvest adds `moe_assignments_routed`.
    The record's `moe_*` count the experts this model HOLDS: held / routed
    is 1/32 where a chip holds 12 of 384 under even routing."""

    STATS = {
        "mla_decode_ctx_tokens_total":
            "latent rows the absorbed decode kernel read, a layer (live "
            "rows' contexts over fused steps)",
        "mla_prefill_ctx_chunks_total":
            "context chunks whose keys and values resumed prefill passes "
            "materialised, a layer",
        "mla_prefill_ctx_tokens_materialised_total":
            "context tokens in those chunks (a chunk is materialised whole)",
        "moe_assignments_routed_total":
            "assignments the router made for real tokens (x experts per "
            "token x expert layers); moe_assignments_total over it is the "
            "share this chip's held experts got",
        "latent_pool_bytes":
            "bytes of a latent family's page pool (one row a token, all "
            "heads)",
    }

    def __init__(self, cfg: KimiConfig, engine_config):
        self.page = engine_config.page_size
        self.chunk_tokens = cfg.ctx_chunk_tokens
        self.routed_a_token = (cfg.n_expert_layers * cfg.num_experts_per_tok
                               if cfg.num_experts else None)
        self.constant = {"mla_layers": cfg.num_layers,
                         "latent_bytes_token": cfg.latent_bytes_token}

    def prefill(self, totals: dict, rows, passes, ctx_pages: int) -> dict:
        chunks = ctx_chunks(ctx_pages, self.page, self.chunk_tokens)
        per_row = []
        for _, n_new, end in rows:
            live = [n for first, n in chunks
                    if end - n_new > first * self.page]
            per_row.append(len(live))
            totals["mla_prefill_ctx_chunks_total"] += len(live)
            totals["mla_prefill_ctx_tokens_materialised_total"] += (
                sum(live) * self.page)
        return {"mla_ctx_chunks": tuple(per_row)}

    def decode(self, totals: dict, rows, k: int) -> None:
        # a row's context at each fused step
        totals["mla_decode_ctx_tokens_total"] += sum(
            k * ctx + k * (k - 1) // 2 for _, _, ctx in rows)

    def harvest(self, totals: dict, rec: dict, packed) -> Optional[dict]:
        if self.routed_a_token is None:
            return None
        routed = sum(q for _, q, _ in rec["rows"]) * self.routed_a_token
        totals["moe_assignments_routed_total"] += routed
        return {"moe_assignments_routed": routed}

    def sizes(self, pool_bytes: dict) -> dict:
        return {"latent_pool_bytes": pool_bytes["kv_pages"]}


def dispatch_facts(cfg: KimiConfig, engine_config) -> list:
    return ([ExpertFacts(cfg, engine_config)] if cfg.num_experts else []) + [
        LatentFacts(cfg, engine_config)]


def pool_spec(cfg: KimiConfig, n_layers: int, num_pages: int,
              page_size: int, slots: int):
    """(shape, dtype) of the one pool: a latent row a token, no head axis
    to speak of (1), `latent_lanes` lanes."""
    return ((n_layers, num_pages, 1, page_size, cfg.latent_lanes), cfg.dtype)


def serving_cache(cfg: KimiConfig, pool, block_tables, total_lens=None,
                  slots=None, gather=None, **static) -> LatentCache:
    """The cache one program pass hands the model (`slots` is for models
    with per-slot state: none here)."""
    return LatentCache(kv_pages=pool, block_tables=block_tables,
                       total_lens=total_lens, gather=gather, **static)


# ------------------------------------------------------------------ layers
class MLAttention(nn.Module):
    """`gated`: each head's output is multiplied, value by value, by
    sigmoid(W_g,h x) of the mixer's own input before W_o (Qiu et al. 2025,
    "Gated Attention for LLMs"; models/gigachat.py). False: no gate, and no
    parameter for one."""
    config: KimiConfig
    ctx_pages: int
    ref_attention: bool
    gated: bool = False

    @nn.compact
    def __call__(self, x, positions, kv_pages, block_tables, total_lens,
                 layer):
        cfg = self.config
        b, s, _ = x.shape
        nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        dv, r = cfg.v_head_dim, cfg.kv_lora_rank
        lanes = kv_pages.shape[-1]
        c_q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, (None,), name="q_a_norm")(
            _dense(cfg, cfg.q_lora_rank, ("embed", None), "q_a_proj")(x))
        q = _dense(cfg, nh * (dn + dr), (None, "heads"), "q_b_proj")(
            c_q).reshape(b, s, nh, dn + dr)
        kv_a = _dense(cfg, r + dr, ("embed", None), "kv_a_proj")(x)
        c_kv = RMSNorm(cfg.rms_norm_eps, cfg.dtype, (None,),
                       name="kv_a_norm")(kv_a[..., :r])
        inv_freq = cfg.rope_inv_freq
        q_nope = q[..., :dn]
        q_rope = rotate(q[..., dn:], positions, inv_freq)
        k_rope = rotate(kv_a[:, :, None, r:], positions, inv_freq)
        # what a page stores, a row a token: [c_kv | k_rope | zeros]
        latent = jnp.concatenate([c_kv[:, :, None], k_rope], axis=-1).astype(
            kv_pages.dtype)
        kv_pages = paged_write(
            kv_pages, latent,
            jnp.zeros((b, s, 1, lanes - (r + dr)), kv_pages.dtype),
            block_tables, positions, total_lens, layer)
        w_kvb = self.param(
            "kv_b_proj", A(nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", in_axis=0, out_axis=(1, 2)),
                (None, "heads", None)),
            (r, nh, dn + dv), cfg.param_dtype).astype(cfg.dtype)
        scale = cfg.softmax_scale
        if s == 1:
            # absorbed: the latent row is the key, its front the value
            q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_kvb[..., :dn])
            o = latent_attention_decode(
                jnp.concatenate([q_abs, q_rope[:, 0]], axis=-1), kv_pages,
                block_tables, total_lens, v_width=r, scale=scale,
                layer=layer, force_reference=self.ref_attention)
            o = jnp.einsum("bhr,rhd->bhd", o, w_kvb[..., dn:])[:, None]
        else:
            def expand(rows):
                """latent rows [B, T, lanes] -> per-head (k [B, T, H, dn +
                dr], v [B, T, H, dv])"""
                kv = jnp.einsum("btr,rhd->bthd", rows[..., :r], w_kvb)
                k_r = jnp.broadcast_to(rows[:, :, None, r:r + dr],
                                       kv.shape[:3] + (dr,))
                return (jnp.concatenate([kv[..., :dn], k_r], axis=-1),
                        kv[..., dn:])

            k_new, v_new = expand(latent[:, :, 0])
            o = latent_prefill_attention(
                jnp.concatenate([q_nope, q_rope], axis=-1), k_new, v_new,
                kv_pages, block_tables, positions, total_lens, expand,
                ctx_pages=self.ctx_pages, scale=scale,
                chunk_tokens=cfg.ctx_chunk_tokens,
                impl="reference" if self.ref_attention else None,
                layer=layer)
        o = o.reshape(b, s, nh * dv)
        if self.gated:
            gate = _dense(cfg, nh * dv, ("embed", "heads"), "gate_proj")(x)
            o = (o.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(cfg.dtype)
        out = _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj")(o)
        return out, kv_pages


class KimiLayer(nn.Module):
    """Scan body of a run of like layers: the pool rides the carry whole;
    (the layer's index into the pool, its index into the run) ride the
    xs; `consts` are the pass's positions and table and, for a run of
    expert layers on the serving path, the run's WHOLE stack of expert
    weights for the grouped matmul to read in place (models/llama.py:
    `_stacked_experts` says why)."""
    config: KimiConfig
    dense: bool
    ctx_pages: int
    ref_attention: bool

    @nn.compact
    def __call__(self, carry, xs, consts):
        cfg = self.config
        x, kv_pages = carry
        pool_idx, run_idx = xs
        positions, block_tables, total_lens, token_mask, experts = consts
        h, kv_pages = MLAttention(
            cfg, self.ctx_pages, self.ref_attention, name="attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="attn_norm")(x),
            positions, kv_pages, block_tables, total_lens, pool_idx)
        x = x + h
        normed = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="mlp_norm")(x)
        if self.dense:
            h = MLP(cfg, name="mlp")(normed)
        else:
            h = MoEMLP(cfg, name="moe")(
                normed, token_mask,
                None if experts is None else experts + (run_idx,))
        return (x + h, kv_pages), None


class KimiModel(nn.Module):
    config: KimiConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, kv_caches=None,
                 token_mask=None):
        """THE CALL of models/_stack.py, `kv_caches` a LatentCache: a decode
        step runs the absorbed form, a prefill pass the materialised one."""
        cfg = self.config
        positions = default_positions(input_ids, positions)
        cache = kv_caches if kv_caches is not None else own_cache(
            pool_spec, serving_cache, cfg, *input_ids.shape, token_mask)
        if token_mask is None:
            token_mask = positions < cache.total_lens[:, None]
        _, x = embed_tokens(self, cfg, input_ids)

        experts = (stacked_experts(self, cfg, ("layers", "moe"))
                   if cfg.n_expert_layers and kv_caches is not None else None)
        carry = (x, cache.kv_pages)
        at = 0
        for name, dense, n in (
                ("dense_layers", True, cfg.first_k_dense_replace),
                ("layers", False, cfg.n_expert_layers)):
            if not n:
                continue
            consts = (positions, cache.block_tables, cache.total_lens,
                      token_mask, None if dense else experts)
            carry, _ = scan_run(KimiLayer, n, name, cfg, dense=dense,
                                ctx_pages=cache.ctx_pages,
                                ref_attention=cache.ref_attention)(
                carry, (at + jnp.arange(n), jnp.arange(n)), consts)
            at += n
        x, kv_pages = carry

        with tracing.scope("rtpu.head"):
            x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        logits = head_at_gather(self, cfg, x, cache.gather)
        if kv_caches is None:
            return logits
        return logits, cache.replace(kv_pages=kv_pages)


# ---------------------------------------------------------------- registry
CONFIGS = {
    # Kimi-K2.5's language model (huggingface.co/moonshotai/Kimi-K2.5
    # config.json, model_type kimi_k2: the DeepSeek-V3 layer, n_group 1),
    # whole: 61 layers, all 384 routed experts. One chip holds a share:
    # `num_layers`, `num_experts` + `n_routed_experts` + `expert_first`
    # and `vocab_size` say which (chipbench/configs/kimi-k2.5-serve.json)
    "kimi-k2.5": KimiConfig(
        vocab_size=163840, hidden_size=7168, intermediate_size=18432,
        num_layers=61, num_heads=64, num_kv_heads=64, max_seq_len=262144,
        rope_theta=50000.0, rms_norm_eps=1e-5, num_experts=384,
        num_experts_per_tok=8, moe_intermediate_size=2048,
        norm_topk_prob=True, moe_scoring="sigmoid",
        routed_scaling_factor=2.827, n_shared_experts=1),
    # a dense layer and two expert layers; 16 routed experts of which this
    # model holds 4..7, 4 a token; a context chunk of two pages of 16
    "tiny-kimi": KimiConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=3,
        num_heads=4, num_kv_heads=4, max_seq_len=512, rope_theta=50000.0,
        rms_norm_eps=1e-5, remat=False, num_experts=4, n_routed_experts=16,
        expert_first=4, num_experts_per_tok=4, moe_intermediate_size=32,
        norm_topk_prob=True, moe_scoring="sigmoid",
        routed_scaling_factor=2.827, n_shared_experts=1, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
        v_head_dim=16, rope_original_max=64, ctx_chunk_tokens=32),
}


def get_config(name: str, **overrides) -> KimiConfig:
    return dataclasses.replace(CONFIGS[name], **overrides)
