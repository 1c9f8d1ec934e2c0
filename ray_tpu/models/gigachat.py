"""GigaChat3.5-family hybrid decoder: gated-delta-rule linear-attention
layers beside multi-head latent attention (MLA) in one stack, sigmoid-routed
experts beside a shared expert.

`full_attention_layers` names the PUBLISHED layers whose mixer is MLA (3,
7, ..., 39 of 40); every other layer's is a gated delta net.
`first_k_dense_replace` published layers have a dense FFN, the rest the
expert layer of models/llama.py (`MoEMLP`: Kimi's router, possibly one
chip's SHARE of the routed experts). `kept_layers` are the published
indices this model runs (None: all of them): a cut keeps a layer's published
index, because its mixer and its FFN depend on it. With `n_i` four norms a
layer, each with its own weight (`layernorm_type` pre_post):

    h <- h + n2(Mixer_l(n1(h)));  h <- h + n4(FFN_l(n3(h)))
    logits = W_head n_f(h_L)

Four scalar functions are READINGS of keys whose code the published config
does not hold; each is ONE function here (and one in the benchmark's plain
reference), so that another reading is a one-line change:

- R(i) `zero_centered_gated_norm`: `norm_type` ZeroCenteredGatedNorm with
  `layernorm_gating_weight` g = 2: `x / rms(x) * (g sigmoid(w))`, w learned
  and zero-centred (w = 0 is scale 1);
- R(ii) `MLAttention(gated=True)` (models/kimi.py): `gated_attention`:
  `o_h <- o_h * sigmoid(W_g,h x)` value by value before W_o;
- R(iii) `gdn_output_gate`: `linear_gating_type`
  gated_rmsnorm_sigmoid_zero_centered with `linear_sigmoid_gate_scale` 2:
  `o / rms(o) * (1 + w_o) * 2 sigmoid(z)` over a head's values;
- R(iv) `swiglu_limit` 10 (models/llama.py: gated_silu): every gated FFN
  is `W_d (silu(min(W_g x, 10)) * clip(W_u x, -10, 10))`.

GDN mixer (ops/gated_delta.py has the rule): `[q|k|v|z] = W_qkvz x`, q and
k `linear_num_key_heads` heads, v and z `linear_num_value_heads` heads of
128; `[b|a] = W_ba x`; a depthwise causal conv of 4 taps and a silu over the
q, k, v channels; `beta = sigmoid(b)`, `log alpha = -exp(A_log) softplus(a
+ dt_bias)` in float32; the delta rule a value head; R(iii); W_o.
MLA mixer: models/kimi.py's, imported, with R(ii).

The stack is one scan a RUN of like layers (same mixer, same FFN kind), the
runs in sequence (models/minicpm_sala.py, mellum.py). Parameters:
`run_<ii>/...` with a leading [run] axis, so a run's expert weights are one
stack that the grouped matmul reads in place.

Serving state is a `GdnLatentCache`, three kinds in ONE pool:
`latent_pages` [n_mla, P, 1, page, lanes] (the MLA layers ONLY keep
pages: one latent row a token), `gdn_state` [n_gdn, slots, Hv, 128, 128]
float32 and `gdn_conv` [n_gdn, 3, slots, channels] (a GDN layer keeps a
matrix a value head and the conv's last 3 inputs a decode slot, whatever
the context). A prefill row RESUMES: where its first position is 0 it
starts from zeros, else from what its slot holds; its MLA queries attend
the latent pages earlier passes wrote. PADDING-PROOF: a position past a
row's length moves no state, no tail and no page, and an idle decode slot
keeps all three bit for bit. The multi-token-prediction modules of the
published model are no part of the next-token forward and are not built.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax import struct

from ..ops.gated_delta import CHUNK, gdn_prefill, gdn_update
from ..ops.selective_scan import live_slots
from ..util import tracing
from ._stack import (default_positions, dense as _dense, embed_tokens,
                     head_at_gather, own_cache, scan_run, stacked_experts,
                     whole_model_only)
from .kimi import KimiConfig, LatentFacts, MLAttention
from .llama import MLP, A, ExpertFacts, MoEMLP

GDN, MLA = "gated-delta", "latent"
# the family's interface flags (serve/llm/stage.py: model_family): a
# prefill row resumes from its slot's state and tail and the latent pages
# written, and the head is computed at the position a row samples from only
RESUMES_PREFILL = True
HEAD_AT_GATHER = True


@dataclass(frozen=True)
class GigaChatConfig(KimiConfig):
    full_attention_layers: Tuple[int, ...] = tuple(range(3, 40, 4))
    # published indices of the layers this model runs (None: the first
    # `num_layers`); `num_layers` is how many that is
    kept_layers: Optional[Tuple[int, ...]] = None
    linear_num_key_heads: int = 32
    linear_num_value_heads: int = 64
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    linear_attn_o_norm_eps: float = 1e-6
    linear_sigmoid_gate_scale: float = 2.0
    layernorm_gating_weight: float = 2.0
    gated_attention: bool = True
    swiglu_limit: Optional[float] = 10.0

    def __post_init__(self):
        object.__setattr__(self, "full_attention_layers",
                           tuple(self.full_attention_layers))
        if self.kept_layers is not None:
            object.__setattr__(self, "kept_layers", tuple(self.kept_layers))
        kept = self.layers
        if len(kept) != self.num_layers or list(kept) != sorted(set(kept)) \
                or any(i < 0 for i in kept):
            raise ValueError(
                f"kept_layers {kept}: {self.num_layers} published indices, "
                f"in order, each once")
        if (self.linear_key_head_dim != self.linear_value_head_dim
                or self.linear_num_value_heads % self.linear_num_key_heads):
            raise NotImplementedError(
                "a gated delta net whose state is not square a value head, "
                "or whose value heads are no multiple of its key heads")
        self._check_rotation_and_share()

    # ---- the layer list
    @property
    def layers(self) -> Tuple[int, ...]:
        if self.kept_layers is not None:
            return self.kept_layers
        return tuple(range(self.num_layers))

    def kind(self, published: int) -> Tuple[str, bool]:
        """(mixer, whether the FFN is dense) of a published layer."""
        return (MLA if published in self.full_attention_layers else GDN,
                published < self.first_k_dense_replace)

    @property
    def runs(self) -> Tuple[Tuple[Tuple[str, bool], int], ...]:
        """(((mixer, dense), how many), ...): the kept layers as runs of
        like layers, in order."""
        out = []
        for i in self.layers:
            kind = self.kind(i)
            if out and out[-1][0] == kind:
                out[-1][1] += 1
            else:
                out.append([kind, 1])
        return tuple((k, n) for k, n in out)

    def _count(self, mixer=None, dense=None) -> int:
        return sum((mixer is None or self.kind(i)[0] == mixer)
                   and (dense is None or self.kind(i)[1] == dense)
                   for i in self.layers)

    @property
    def n_mla_layers(self) -> int:
        return self._count(mixer=MLA)

    @property
    def n_gdn_layers(self) -> int:
        return self._count(mixer=GDN)

    @property
    def n_expert_layers(self) -> int:
        return self._count(dense=False)

    # ---- what serve/llm asks of a family whose layers keep per-slot state
    @property
    def n_slot_state_layers(self) -> int:
        return self.n_gdn_layers

    @property
    def gdn_channels(self) -> int:
        """The q, k, v channels the conv runs over."""
        return ((2 * self.linear_num_key_heads + self.linear_num_value_heads)
                * self.linear_key_head_dim)

    def gdn_state_bytes_row(self) -> int:
        """What one sequence's GDN state costs to read or write once, all
        GDN layers: the float32 matrices and the conv tail in `dtype`."""
        d = self.linear_key_head_dim
        return self.n_gdn_layers * (
            self.linear_num_value_heads * d * d * 4
            + (self.linear_conv_kernel_dim - 1) * self.gdn_channels
            * jnp.dtype(self.dtype).itemsize)

    @property
    def latent_bytes_token(self) -> int:
        """What one token's latent costs to read once, over the MLA layers
        (the others keep none), as the pool stores it."""
        return (self.n_mla_layers * self.latent_lanes
                * jnp.dtype(self.dtype).itemsize)

    # ---- sizes
    def mla_params(self) -> int:
        gate = (self.hidden_size * self.num_heads * self.v_head_dim
                if self.gated_attention else 0)
        return self.attn_params() + gate

    def gdn_params(self) -> int:
        h, d = self.hidden_size, self.linear_key_head_dim
        nv = self.linear_num_value_heads
        wide = self.gdn_channels + nv * d                  # q, k, v and z
        return (h * wide + h * 2 * nv
                + self.linear_conv_kernel_dim * self.gdn_channels
                + 2 * nv + d + nv * d * h)

    def _ffn_params(self, active: bool) -> Tuple[int, int]:
        """(a dense layer's FFN, an expert layer's: held, or what a token
        multiplies of it)."""
        h, f, routed = self.hidden_size, self.expert_width, self.routed_experts
        experts = (self.num_experts_per_tok * self.num_experts / routed
                   if active else self.num_experts)
        return (3 * h * self.intermediate_size,
                h * routed + (0 if active else routed)
                + 3 * h * f * (experts + self.n_shared_experts))

    def _layer_params(self, active: bool) -> float:
        dense, moe = self._ffn_params(active)
        norms = 0 if active else 4 * self.hidden_size
        return (self.n_mla_layers * self.mla_params()
                + self.n_gdn_layers * self.gdn_params()
                + self.num_layers * norms
                + self._count(dense=True) * dense
                + self.n_expert_layers * moe)

    def num_params(self) -> int:
        """The parameters this model HOLDS (a share holds its experts)."""
        h = self.hidden_size
        return int(self._layer_params(False)) + 2 * self.vocab_size * h + h

    def active_params(self) -> int:
        """Parameters one token multiplies HERE: of its k routed experts
        the expected part this model holds, and no head (a pass computes
        it at one position)."""
        return int(self._layer_params(True))


def pass_cost_ratios(cfg: GigaChatConfig) -> tuple:
    """(weights a prefill pass reads, scores a (query, key) pair makes),
    each over the parameters a token multiplies (serve/llm/engine.py:
    PassCost). A pass reads every HELD expert and a token multiplies a few.
    Only the MLA layers' pairs grow with the context (a materialised pair
    is H heads x 2 (dn + dr + dv) operations where PassCost's pair is one
    of 4 x 128); a GDN layer's chunk products are linear in the pass, 3% of
    its projections', and sit in neither term."""
    active = cfg.active_params()
    embed_head = 2 * cfg.vocab_size * cfg.hidden_size
    pair = (cfg.head_dim_ + cfg.v_head_dim) / 256
    return ((cfg.num_params() - embed_head) / active,
            cfg.n_mla_layers * cfg.num_heads * pair / active)


@struct.dataclass
class GdnLatentCache:
    """Serving state of a GigaChatModel, threaded through it as
    `kv_caches`. `slots` [B]: the decode slot each row of a PREFILL keeps
    its GDN state in (None: row i is slot i, a decode step over the slot
    set). `gather` [B]: the position (an index into the row) whose logits a
    prefill wants, -1 for none; None: logits at every position."""

    latent_pages: jax.Array
    gdn_state: jax.Array
    gdn_conv: jax.Array
    block_tables: jax.Array      # [B, MP]
    total_lens: jax.Array        # [B], INCLUDING the new tokens
    slots: Optional[jax.Array] = None
    gather: Optional[jax.Array] = None
    # STATIC, as models/llama.py: PagedCache has them
    ctx_pages: int = struct.field(pytree_node=False, default=0)
    ref_attention: bool = struct.field(pytree_node=False, default=False)

    @property
    def pool(self):
        return {"latent_pages": self.latent_pages,
                "gdn_state": self.gdn_state, "gdn_conv": self.gdn_conv}

    def step(self, pool, total_lens):
        return self.replace(total_lens=total_lens, **pool)


# ----------------------------------------------------------------- serving
def serving_model(cfg: GigaChatConfig, n_layers=None, first=True, last=True):
    return whole_model_only(
        GigaChatModel, cfg, first, last,
        "whose layers are a list of two mixers and two FFNs")


# (stage.py: model_family) a prefill row RESUMES from its slot and its
# pages, so chunked prefill is served; what is refused is refused for the
# mechanism that is missing
CANNOT_BE_GIVEN = ("keeps a matrix state and a conv tail a decode slot "
                   "beside one latent row a token", {
    "spec_lookahead":
        "needs a verify dispatch whose rejected draft tokens can be "
        "rolled back, and a delta-rule state advanced past them cannot be "
        "(no state snapshot yet; the model's own prediction modules, which "
        "would draft, are not built either)",
    "tp": "would have to split the GDN value heads' per-slot matrices over "
          "the mesh and copy the latent pool (one row for every head) to "
          "every chip, and nothing does either yet",
    "pp": "slices a uniform `layers` axis (stage_params), and this "
          "model's layers are a list of two mixers and two FFNs with three "
          "kinds of state",
    "handoff": "moves KV pages only, and a request's per-slot state and "
               "conv tail would be left behind",
    "prefix_reuse":
        "a latent page found by its content hash carries no delta-rule "
        "state and no conv tail for the layers that keep those: a prompt "
        "behind a cached prefix would start them from nothing",
})


class _MlaFacts(LatentFacts):
    """models/kimi.py's `LatentFacts` where only some layers keep latents:
    `mla_layers` and `latent_bytes_token` count those."""

    def __init__(self, cfg: GigaChatConfig, engine_config):
        super().__init__(cfg, engine_config)
        self.constant = {"mla_layers": cfg.n_mla_layers,
                         "latent_bytes_token": cfg.latent_bytes_token}

    def sizes(self, pool_bytes: dict) -> dict:
        return {"latent_pool_bytes": pool_bytes["latent_pages"]}


class GdnFacts:
    """What the gated-delta-net layers' dispatches count (serve/llm/
    stage.py: model_family). Every record says `gdn_layers` and
    `gdn_state_bytes_row` (the bytes one live row's matrices and conv tail
    cost to read or write once, all GDN layers)."""

    STATS = {
        "gdn_prefill_tokens_total":
            "real prompt tokens x gated-delta-net layers (prefill)",
        "gdn_prefill_chunks_total":
            "chunks of the delta rule that held a real token x layers (a "
            "triangular solve a chunk and value head)",
        "gdn_state_updates_total":
            "live rows x fused steps x gated-delta-net layers (decode: a "
            "state read and written each)",
        "gdn_state_pool_bytes":
            "bytes of the per-slot delta-rule state and conv tail pools",
    }

    def __init__(self, cfg: GigaChatConfig):
        self.layers = cfg.n_gdn_layers
        self.constant = {"gdn_layers": self.layers,
                         "gdn_state_bytes_row": cfg.gdn_state_bytes_row()}

    def prefill(self, totals: dict, rows, passes, ctx_pages: int) -> None:
        totals["gdn_prefill_tokens_total"] += self.layers * sum(
            n for _, n, _ in rows)
        totals["gdn_prefill_chunks_total"] += self.layers * sum(
            -(-n // CHUNK) for _, n, _ in rows)

    def decode(self, totals: dict, rows, k: int) -> None:
        totals["gdn_state_updates_total"] += self.layers * len(rows) * k

    def sizes(self, pool_bytes: dict) -> dict:
        return {"gdn_state_pool_bytes": (pool_bytes["gdn_state"]
                                         + pool_bytes["gdn_conv"])}


def dispatch_facts(cfg: GigaChatConfig, engine_config) -> list:
    return ([ExpertFacts(cfg, engine_config)] if cfg.num_experts else []) + [
        _MlaFacts(cfg, engine_config), GdnFacts(cfg)]


def pool_spec(cfg: GigaChatConfig, n_layers: int, num_pages: int,
              page_size: int, slots: int) -> dict:
    """name -> (shape, dtype) of what a serving engine keeps on the device
    for this model: latent pages for the MLA layers alone, a matrix a value
    head and the conv's last inputs a slot for the GDN layers."""
    d = cfg.linear_key_head_dim
    return {
        "latent_pages": ((cfg.n_mla_layers, num_pages, 1, page_size,
                          cfg.latent_lanes), cfg.dtype),
        "gdn_state": ((cfg.n_gdn_layers, slots, cfg.linear_num_value_heads,
                       d, d), jnp.float32),
        "gdn_conv": ((cfg.n_gdn_layers, cfg.linear_conv_kernel_dim - 1,
                      slots, cfg.gdn_channels), cfg.dtype),
    }


def serving_cache(cfg: GigaChatConfig, pool: dict, block_tables,
                  total_lens=None, slots=None, gather=None,
                  **static) -> GdnLatentCache:
    """The cache one program pass hands the model: `pool` as `pool_spec`
    lays it out, block_tables [B, MP], total_lens [B] (None:
    `GdnLatentCache.step` brings them)."""
    return GdnLatentCache(block_tables=block_tables, total_lens=total_lens,
                          slots=slots, gather=gather, **pool, **static)


# ------------------------------------------------------------------ layers
def zero_centered_gated_norm(x, w, eps: float, gain: float):
    """R(i): x / rms(x) * (gain sigmoid(w)), float32; w = 0 is scale 1 at
    the published gain of 2."""
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps)
    return y * (gain * jax.nn.sigmoid(w))


def gdn_output_gate(o, z, w, eps: float, scale: float):
    """R(iii): o / rms(o) * (1 + w) * scale sigmoid(z) over a head's
    values, float32."""
    o = o.astype(jnp.float32)
    y = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + eps)
    return y * (1.0 + w) * (scale * jax.nn.sigmoid(z.astype(jnp.float32)))


class Norm(nn.Module):
    config: GigaChatConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        w = self.param("zc_weight", A(nn.initializers.zeros, ("embed",)),
                       (x.shape[-1],), jnp.float32)
        return zero_centered_gated_norm(
            x, w, cfg.rms_norm_eps, cfg.layernorm_gating_weight).astype(
                cfg.dtype)


class GatedDeltaNet(nn.Module):
    config: GigaChatConfig

    @nn.compact
    def __call__(self, x, start, n_real, slots, order, state, conv, layer):
        """x [B, S, hidden] (normed); start, n_real [B]: a row's first
        position and its real tokens; `state`, `conv` the pools, `layer`
        this layer's index in them -> (out, state, conv)."""
        cfg = self.config
        b, s, _ = x.shape
        nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        d, taps = cfg.linear_key_head_dim, cfg.linear_conv_kernel_dim
        channels = cfg.gdn_channels
        f32 = jnp.float32
        qkvz = _dense(cfg, channels + nv * d, ("embed", "qkv"),
                      "qkvz_proj")(x)
        qkv, z = qkvz[..., :channels], qkvz[..., channels:]
        ba = _dense(cfg, 2 * nv, ("embed", None), "ba_proj")(x).astype(f32)
        conv_w = self.param(
            "conv_kernel", A(nn.initializers.uniform(taps ** -0.5),
                             (None, "qkv")), (taps, channels),
            cfg.param_dtype).astype(cfg.dtype)
        a_log = self.param("A_log", A(nn.initializers.zeros, (None,)),
                           (nv,), f32)
        dt_bias = self.param("dt_bias", A(nn.initializers.zeros, (None,)),
                             (nv,), f32)
        beta = jax.nn.sigmoid(ba[..., :nv])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., nv:] + dt_bias)
        if s == 1:
            o, state, conv = gdn_update(
                qkv[:, 0], g[:, 0], beta[:, 0], conv_w, state, conv, layer,
                n_real > 0, n_k=nk, n_v=nv, order=order)
            o = o[:, None]
        else:
            rows = []
            for i in range(b):
                slot = i if slots is None else slots[i]
                with tracing.scope("rtpu.attn.cache_write"):
                    held = jax.lax.dynamic_slice(
                        state, (layer, slot, 0, 0, 0),
                        (1, 1, nv, d, d))[0, 0]
                    # a tap's row at a time: one slice of all taps makes
                    # XLA copy the pool into a layout with the taps
                    # second-minor
                    tail = jnp.stack([jax.lax.dynamic_slice(
                        conv, (layer, j, slot, 0),
                        (1, 1, 1, channels))[0, 0, 0]
                        for j in range(taps - 1)])
                fresh = start[i] == 0
                oi, s_last, t_last = gdn_prefill(
                    qkv[i], g[i], beta[i], conv_w,
                    jnp.where(fresh, 0.0, held),
                    jnp.where(fresh, jnp.zeros_like(tail), tail), n_real[i],
                    n_k=nk, n_v=nv)
                # a row with no real token (a masked warm-up pass) keeps
                # what its slot held
                keep = n_real[i] > 0
                with tracing.scope("rtpu.attn.cache_write"):
                    state = jax.lax.dynamic_update_slice(
                        state, jnp.where(keep, s_last, held)[None, None],
                        (layer, slot, 0, 0, 0))
                    t_last = jnp.where(keep, t_last, tail)
                    for j in range(taps - 1):
                        conv = jax.lax.dynamic_update_slice(
                            conv, t_last[j][None, None, None],
                            (layer, j, slot, 0))
                rows.append(oi)
            o = jnp.stack(rows)
        w_o = self.param("o_norm", A(nn.initializers.zeros, (None,)), (d,),
                         f32)
        o = gdn_output_gate(o, z.reshape(b, s, nv, d), w_o,
                            cfg.linear_attn_o_norm_eps,
                            cfg.linear_sigmoid_gate_scale).astype(cfg.dtype)
        out = _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj")(
            o.reshape(b, s, nv * d))
        return out, state, conv


class GigaChatLayer(nn.Module):
    """Scan body of a run of like layers: the pool's three parts ride the
    carry whole; (the layer's index among its mixer's kind, its index in
    the run) ride the xs; `consts` are the pass's positions, table, slots
    and live order and, for a run of expert layers on the serving path, the
    run's WHOLE stack of expert weights for the grouped matmul to read in
    place (models/llama.py: `_stacked_experts` says why)."""
    config: GigaChatConfig
    mixer: str
    dense: bool
    ctx_pages: int
    ref_attention: bool

    @nn.compact
    def __call__(self, carry, xs, consts):
        cfg = self.config
        x, pages, state, conv = carry
        pool_idx, run_idx = xs
        (positions, block_tables, total_lens, start, n_real, slots, order,
         token_mask, experts) = consts
        normed = Norm(cfg, name="mixer_norm")(x)
        if self.mixer == MLA:
            h, pages = MLAttention(
                cfg, self.ctx_pages, self.ref_attention,
                gated=cfg.gated_attention, name="attn")(
                normed, positions, pages, block_tables, total_lens, pool_idx)
        else:
            h, state, conv = GatedDeltaNet(cfg, name="gdn")(
                normed, start, n_real, slots, order, state, conv, pool_idx)
        x = x + Norm(cfg, name="mixer_post_norm")(h)
        normed = Norm(cfg, name="mlp_norm")(x)
        if self.dense:
            h = MLP(cfg, name="mlp")(normed)
        else:
            h = MoEMLP(cfg, name="moe")(
                normed, token_mask,
                None if experts is None else experts + (run_idx,))
        return (x + Norm(cfg, name="mlp_post_norm")(h), pages, state,
                conv), None


class GigaChatModel(nn.Module):
    config: GigaChatConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, kv_caches=None,
                 token_mask=None):
        """THE CALL of models/_stack.py, `kv_caches` a GdnLatentCache: a
        prefill pass resumes from the rows' slots (state and tail) and
        latent pages."""
        cfg = self.config
        s = input_ids.shape[1]
        positions = default_positions(input_ids, positions)
        cache = kv_caches if kv_caches is not None else own_cache(
            pool_spec, serving_cache, cfg, *input_ids.shape, token_mask)
        if token_mask is None:
            token_mask = positions < cache.total_lens[:, None]
        _, x = embed_tokens(self, cfg, input_ids)

        start = positions[:, 0]
        n_real = jnp.clip(cache.total_lens - start, 0, s)
        # the live slots' order, once a decode step for every GDN layer
        order = live_slots(n_real > 0) if s == 1 else None
        carry = (x, cache.latent_pages, cache.gdn_state, cache.gdn_conv)
        at = {GDN: 0, MLA: 0}
        for r, ((mixer, dense), n) in enumerate(cfg.runs):
            name = f"run_{r:02d}"
            experts = (stacked_experts(self, cfg, (name, "moe"))
                       if not dense and kv_caches is not None else None)
            consts = (positions, cache.block_tables, cache.total_lens, start,
                      n_real, cache.slots, order, token_mask, experts)
            carry, _ = scan_run(GigaChatLayer, n, name, cfg, mixer=mixer,
                                dense=dense, ctx_pages=cache.ctx_pages,
                                ref_attention=cache.ref_attention)(
                carry, (at[mixer] + jnp.arange(n), jnp.arange(n)), consts)
            at[mixer] += n
        x, pages, state, conv = carry

        with tracing.scope("rtpu.head"):
            x = Norm(cfg, name="final_norm")(x)
        logits = head_at_gather(self, cfg, x, cache.gather)
        if kv_caches is None:
            return logits
        return logits, cache.replace(latent_pages=pages, gdn_state=state,
                                     gdn_conv=conv)


# ---------------------------------------------------------------- registry
CONFIGS = {
    # GigaChat3.5-432B-A28B (huggingface.co/ai-sage/GigaChat3.5-432B-A28B
    # config.json, model_type gigachat3_5), whole: 40 layers, all 256
    # routed experts. One chip holds a share: `num_layers` + `kept_layers`,
    # `num_experts` + `n_routed_experts` + `expert_first` and `vocab_size`
    # say which (chipbench/configs/gigachat3.5-432b-a28b-serve.json)
    "gigachat3.5-432b-a28b": GigaChatConfig(
        vocab_size=128256, hidden_size=7168, intermediate_size=18432,
        num_layers=40, num_heads=64, num_kv_heads=64, max_seq_len=262144,
        rope_theta=100000.0, rms_norm_eps=1e-6, num_experts=256,
        num_experts_per_tok=8, moe_intermediate_size=2048,
        norm_topk_prob=True, moe_scoring="sigmoid",
        routed_scaling_factor=2.5, n_shared_experts=1,
        first_k_dense_replace=3, rope_factor=8.0, rope_original_max=32768),
    # published layers 1-6 of 8: a GDN layer with a dense FFN, an MLA
    # layer (3) and three GDN layers with experts, runs of 1, 1, 1 and 3
    # (layer 3 of `full_attention_layers` (3, 7), dense below 2); 16 routed
    # experts of which this model holds 4..7, 4 a token; a context chunk of
    # two pages of 16
    "tiny-gigachat": GigaChatConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=6,
        kept_layers=(1, 2, 3, 4, 5, 6), full_attention_layers=(3, 7),
        first_k_dense_replace=2, num_heads=4, num_kv_heads=4,
        max_seq_len=512, rope_theta=100000.0, rms_norm_eps=1e-6,
        remat=False, num_experts=4, n_routed_experts=16, expert_first=4,
        num_experts_per_tok=4, moe_intermediate_size=32,
        norm_topk_prob=True, moe_scoring="sigmoid",
        routed_scaling_factor=2.5, n_shared_experts=1, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
        v_head_dim=16, rope_factor=8.0, rope_original_max=64,
        ctx_chunk_tokens=32, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16),
}


def get_config(name: str, **overrides) -> GigaChatConfig:
    return dataclasses.replace(CONFIGS[name], **overrides)
