"""Jamba-family hybrid decoder: Mamba-1 state-space layers beside attention.

Layer i is an attention layer where `i % attn_layer_period ==
attn_layer_offset`, else a Mamba layer; every layer's FFN is the dense
SwiGLU (`num_experts` 1). Every layer is `x += mixer(rms(x))`, `x +=
mlp(rms(x))`; then the final norm and the head, which is the embedding
transposed when `tie_word_embeddings`. No positional encoding: attention
does not rotate (the Mamba layers carry the order).

A Mamba layer's mixer, for a sequence u [S, h] (d = expand * h, N =
d_state, K = d_conv, R = dt_rank):

    x, z = split(u W_in)                               [S, d] each
    x    = silu(causal depthwise conv_K(x; w [K, d], b [d]))
    dt, B, C = split(x W_x)                            [S, R], [S, N] x 2
    dt, B, C = rms(dt; g_dt), rms(B; g_B), rms(C; g_C)
    delta = softplus(dt W_dt + b_dt)                   [S, d]  float32
    A     = -exp(A_log)                                [N, d]  float32
    h_t   = exp(delta_t (x) A) * h_{t-1} + (delta_t * x_t) (x) B_t
    y_t   = h_t . C_t + D * x_t
    out   = (y * silu(z)) W_out

`A`, `delta`, `h` and the scan are float32 (ops/selective_scan.py),
everything else the config's dtype. What a sequence carries from token to
token is `h` [N, d] and the conv's last K-1 inputs [K-1, d]: fixed size,
whatever the length. Both are laid out with the channels LAST (lanes):
the published layout `[d, N]` / `[d, K]` would pad 16 or 4 lanes to 128.

The stack is one scan a RUN of Mamba layers: a period is
`attn_layer_offset` layers (scanned), its attention layer, `period -
offset - 1` layers (scanned), and the periods follow one another in the
program (2 for Jamba2-3B: four scan bodies and two attention layers, not
28 layers). The parameters are stacked the same way:
`period_<p>/{pre,post}/...` with a leading [run] axis, `period_<p>/attn/...`
with none. The periods are NOT one outer scan over stacks of [periods,
run, ...]: a period's slice of such a stack, handed to the inner scan, is
a COPY of seven layers' weights (2.7 GB a decode step at Jamba2-3B's
size, read from the compiled program, PR 33), as a layer's experts
sliced from their stack were (`_stacked_experts` in models/llama.py).

Serving state is a `HybridCache`: the attention layers' pages (a
`PagedCache` over those layers only) and the Mamba layers' per-slot
arrays, both carried whole through the scans and updated in place.
PADDING-PROOF: a position past a row's length has `delta = 0`, so `h`
does not move, and the conv state a row leaves is its last K-1 REAL
inputs; an idle decode slot keeps both bit for bit.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax import struct

from ..ops.selective_scan import _impl as _scan_impl
from ..ops.selective_scan import (live_slots, selective_scan,
                                  selective_update, state_shape)
from ..util import tracing
from ._stack import (default_positions, dense, embed_tokens, scan_run,
                     whole_model_only)
from .llama import MLP, A, Attention, PagedCache, RMSNorm
from .llama import serving_cache as _paged_cache


@dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_layers: int = 28
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: Optional[int] = None
    max_seq_len: int = 8192
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    tie_word_embeddings: bool = True
    # what the shared Attention / MLP modules and the engine read of a
    # config: attention does not rotate; one FFN a layer, not experts
    rope_theta: Optional[float] = None
    attention_impl: Optional[str] = None
    num_experts: int = 0
    qk_norm: bool = False
    block_causal: int = 0
    # accepted (the engine sets them for every family) and fixed here
    scan_layers: bool = True
    remat: bool = False

    def __post_init__(self):
        if self.num_layers % self.attn_layer_period:
            raise ValueError(
                f"num_layers {self.num_layers} is not a whole number of "
                f"periods of {self.attn_layer_period} layers")
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError("attn_layer_offset lies outside the period")
        if self.mamba_proj_bias:
            raise NotImplementedError("mamba_proj_bias: no published "
                                      "Jamba sets it")

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def n_periods(self) -> int:
        return self.num_layers // self.attn_layer_period

    @property
    def n_attn_layers(self) -> int:
        return self.n_periods

    @property
    def n_mamba_layers(self) -> int:
        return self.num_layers - self.n_periods

    def num_params(self) -> int:
        h, f, d = self.hidden_size, self.intermediate_size, self.d_inner
        n, r, k = self.mamba_d_state, self.mamba_dt_rank, self.mamba_d_conv
        hd = self.head_dim_
        mixer = (h * 2 * d + d * (r + 2 * n) + r * d + d * h   # matmuls
                 + k * d + 2 * d + n * d + d + r + 2 * n)      # the rest
        attn = h * hd * (self.num_heads + 2 * self.num_kv_heads) \
            + self.num_heads * hd * h
        mlp = 3 * h * f + 2 * h
        head = 0 if self.tie_word_embeddings else self.vocab_size * h
        return (self.n_mamba_layers * (mixer + mlp)
                + self.n_attn_layers * (attn + mlp)
                + self.vocab_size * h + h + head)

    # what serve/llm asks of a family whose layers keep per-slot state
    @property
    def n_slot_state_layers(self) -> int:
        return self.n_mamba_layers

    def ssm_state_bytes_row(self) -> int:
        """What one sequence's recurrent state costs to read or write
        once, all Mamba layers: h in float32, the conv tail in `dtype`."""
        d = self.d_inner
        return self.n_mamba_layers * (
            d * self.mamba_d_state * 4
            + d * (self.mamba_d_conv - 1) * jnp.dtype(self.dtype).itemsize)


@struct.dataclass
class HybridCache:
    """Serving state of a JambaModel, threaded through it as `kv_caches`.

    `paged`: the attention layers' PagedCache (pool [n_attn, P, Hkv, page,
    2*D], tables and lengths tiled over THOSE layers). `ssm_h` [n_mamba,
    slots, N, 8, d/8] float32 (ops/selective_scan.py: state_shape) and `ssm_conv` [n_mamba, K-1, slots, d]: the Mamba
    layers' state, one entry a decode slot (the conv's K-1 = 3 inputs lie
    OUTSIDE the slots: [slots, 3, d] pads 3 rows to a tile of 16, and the
    compiler re-laid the whole array out on the way into and out of every
    program, two copies of the pool a step). `slots` [B] int32 says which
    slot each row of a PREFILL writes (it starts from zero and never reads
    what the slot held); None: row i is slot i (decode: the batch IS the
    slot set, and a row whose length is 0 keeps its slot's state bit for
    bit)."""

    paged: PagedCache
    ssm_h: jax.Array
    ssm_conv: jax.Array
    slots: Optional[jax.Array] = None

    @property
    def pool(self):
        return {"kv_pages": self.paged.kv_pages, "ssm_h": self.ssm_h,
                "ssm_conv": self.ssm_conv}

    def step(self, pool, total_lens):
        """A fused decode step's cache: the pools out of the scan's
        carry, the lengths as they stand at this step."""
        return self.replace(
            paged=self.paged.step(pool["kv_pages"], total_lens),
            ssm_h=pool["ssm_h"], ssm_conv=pool["ssm_conv"])


# ----------------------------------------------------------------- serving
# a prefill row starts from ZERO state (scan and conv tail): a prompt is
# prefilled in one pass (serve/llm/stage.py: model_family)
RESUMES_PREFILL = False


def serving_model(cfg: JambaConfig, n_layers=None, first=True, last=True):
    return whole_model_only(JambaModel, cfg, first, last,
                            "with a layer pattern")


# (stage.py: model_family) the state cannot be rolled back, resumed
# mid-prompt, split, cut by layer slices or handed to another engine
CANNOT_BE_GIVEN = ("keeps recurrent state-space state", {
    "spec_lookahead":
        "needs a verify dispatch whose rejected draft tokens can be "
        "rolled back, and a state advanced past them cannot be (no "
        "state snapshot yet)",
    "prefill_chunk_tokens":
        "needs a prefill that resumes from a slot's state, and a "
        "prefill row starts from zero state",
    "tp": "would have to split the scan's d_inner axis and its per-slot "
          "state over the mesh, and nothing does yet",
    "pp": "slices a uniform `layers` axis (stage_params), and this "
          "model's layers follow a pattern of two kinds with two kinds "
          "of state",
    "handoff": "moves KV pages only, and a request's per-slot state "
               "would be left behind",
})


class ScanStateFacts:
    """What the state-space layers' dispatches count (serve/llm/stage.py:
    model_family). Every record says `ssm_layers` (the layers that keep
    recurrent state a decode slot) and `ssm_state_bytes_row` (the bytes
    one live row's state costs to read or write once over all of them), so
    that a reader needs no knowledge of the model."""

    STATS = {
        "ssm_scan_tokens_total":
            "real prompt tokens x state-space layers scanned (prefill)",
        "ssm_state_updates_total":
            "live rows x fused steps x state-space layers updated (decode)",
        "ssm_state_pool_bytes":
            "bytes of the per-slot recurrent state pool (state-space "
            "layers)",
        "ssm_slots": "decode slots of the recurrent state pool",
    }

    def __init__(self, cfg: JambaConfig, engine_config):
        self.layers = cfg.n_mamba_layers
        self.slots = engine_config.max_batch
        self.constant = {"ssm_layers": self.layers,
                         "ssm_state_bytes_row": cfg.ssm_state_bytes_row()}

    def prefill(self, totals: dict, rows, passes, ctx_pages: int) -> None:
        totals["ssm_scan_tokens_total"] += self.layers * sum(
            q for _, q, _ in rows)

    def decode(self, totals: dict, rows, k: int) -> None:
        totals["ssm_state_updates_total"] += len(rows) * k * self.layers

    def sizes(self, pool_bytes: dict) -> dict:
        return {"ssm_state_pool_bytes": (pool_bytes["ssm_h"]
                                         + pool_bytes["ssm_conv"]),
                "ssm_slots": self.slots}


def dispatch_facts(cfg: JambaConfig, engine_config) -> list:
    return [ScanStateFacts(cfg, engine_config)]


def pool_spec(cfg: JambaConfig, n_layers: int, num_pages: int,
              page_size: int, slots: int) -> dict:
    """name -> (shape, dtype) of what a serving engine keeps on the
    device for this model: pages for the attention layers only, per-slot
    arrays for the Mamba layers."""
    d = cfg.d_inner
    return {
        "kv_pages": ((cfg.n_attn_layers, num_pages, cfg.num_kv_heads,
                      page_size, 2 * cfg.head_dim_), cfg.dtype),
        "ssm_h": ((cfg.n_mamba_layers, slots)
                  + state_shape(cfg.mamba_d_state, d), jnp.float32),
        "ssm_conv": ((cfg.n_mamba_layers, cfg.mamba_d_conv - 1, slots, d),
                     cfg.dtype),
    }


def serving_cache(cfg: JambaConfig, pool: dict, block_tables,
                  total_lens=None, slots=None, **static) -> HybridCache:
    """The cache one program pass hands the model: `pool` as `pool_spec`
    lays it out, block_tables [B, MP], total_lens [B] (None:
    `HybridCache.step` brings them)."""
    return HybridCache(
        paged=_paged_cache(cfg, pool["kv_pages"], block_tables, total_lens,
                           **static),
        ssm_h=pool["ssm_h"], ssm_conv=pool["ssm_conv"], slots=slots)


# ---------------------------------------------------------------- the mixer
def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Mamba's: the inverse softplus of a step drawn log-uniform in
    [1e-3, 1e-1]."""
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _conv_init(d_conv: int):
    bound = d_conv ** -0.5

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    """log(1..N) on every channel: decay rates from e^-delta to
    e^-(N delta), a memory of tens to hundreds of tokens."""
    n = shape[0]
    return jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)
                                    )[:, None], shape).astype(dtype)


class MambaMixer(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, u, mask, state=None):
        """u [B, S, h]; mask [B, S] bool (False: padding) or None.
        state: None, or (ssm_h, ssm_conv, layer, slots) with the pools of
        HybridCache and this layer's index in them; `slots` is a prefill's
        slot of each row, a decode step's `live_slots` order. -> (out [B, S, h],
        (ssm_h, ssm_conv) updated, or None)."""
        cfg = self.config
        d, n, k, r = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
                      cfg.mamba_dt_rank)
        b, s, _ = u.shape
        f32 = jnp.float32

        xz = dense(cfg, 2 * d, ("embed", "mlp"), "in_proj")(u)
        x, z = jnp.split(xz, 2, axis=-1)
        conv_w = self.param("conv_kernel", A(_conv_init(k), (None, "mlp")),
                            (k, d), cfg.param_dtype).astype(cfg.dtype)
        conv_b = (self.param("conv_bias", A(_conv_init(k), ("mlp",)),
                             (d,), cfg.param_dtype).astype(cfg.dtype)
                  if cfg.mamba_conv_bias else jnp.zeros((d,), cfg.dtype))
        a_log = self.param("A_log", A(_a_log_init, (None, "mlp")), (n, d),
                           f32)
        d_skip = self.param("D", A(nn.initializers.ones, ("mlp",)), (d,),
                            f32)
        dt_bias = self.param("dt_bias", A(_dt_bias_init, ("mlp",)), (d,),
                             f32)

        decode = state is not None and s == 1
        if decode:
            ssm_h, ssm_conv, layer, _ = state
            # [K-1, slots, d] and the new input: the window, time-major
            tail = jax.lax.dynamic_index_in_dim(ssm_conv, layer, 0, False)
            window = jnp.concatenate([tail, x[:, 0][None]], axis=0)
            x = (sum(window[j] * conv_w[j] for j in range(k))
                 + conv_b)[:, None]
        else:
            # a prefill starts from zero: nothing of a slot's old state
            window = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))  # [B,K-1+S,d]
            x = sum(window[:, j:j + s] * conv_w[j]
                    for j in range(k)) + conv_b
        x = nn.silu(x)

        dbc = dense(cfg, r + 2 * n, ("mlp", None), "x_proj")(x)
        dt, bm, cm = jnp.split(dbc, [r, r + n], axis=-1)
        dt = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="dt_norm")(dt)
        bm = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="b_norm")(bm)
        cm = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="c_norm")(cm)
        delta = jax.nn.softplus(
            dense(cfg, d, (None, "mlp"), "dt_proj")(dt).astype(f32) + dt_bias)
        if mask is not None:
            delta = jnp.where(mask[..., None], delta, 0.0)
        a_neg = -jnp.exp(a_log)

        new_state = None
        if decode:
            live = mask[:, 0]
            y, ssm_h = selective_update(
                x[:, 0], delta[:, 0], a_neg, bm[:, 0], cm[:, 0], d_skip,
                ssm_h, layer, live, z[:, 0], order=state[3])
            y = y[:, None]
            with tracing.scope("rtpu.attn.cache_write"):
                tail_new = jnp.where(live[None, :, None], window[1:], tail)
                new_state = (ssm_h, jax.lax.dynamic_update_index_in_dim(
                    ssm_conv, tail_new, layer, 0))
        else:
            n_real = (jnp.full((b,), s, jnp.int32) if mask is None
                      else mask.sum(-1).astype(jnp.int32))
            h0 = jnp.zeros((n, d), f32)

            def one_row(row):
                xr, dr, br, cr, zr, nr = row
                return selective_scan(xr, dr, a_neg, br, cr, d_skip, h0, zr,
                                      length=nr)

            rows = (x, delta, bm, cm, z, n_real)
            if b == 1:
                y, h_last = (o[None] for o in one_row(
                    tuple(a[0] for a in rows)))
            else:
                y, h_last = jax.lax.map(one_row, rows)
            if state is not None:
                ssm_h, ssm_conv, layer, slots = state
                if slots is None:
                    slots = jnp.arange(b)
                with tracing.scope("rtpu.attn.cache_write"):
                    # the last K-1 REAL inputs of the conv: window column
                    # n_real + j is input n_real - (K-1) + j (zeros
                    # before 0)
                    tails = jax.vmap(
                        lambda w, nr: jax.lax.dynamic_slice_in_dim(
                            w, nr, k - 1, 0))(window, n_real)
                    for i in range(b):
                        ssm_h = jax.lax.dynamic_update_slice(
                            ssm_h,
                            h_last[i].reshape((1, 1) + ssm_h.shape[2:]),
                            (layer, slots[i], 0, 0, 0))
                        # a row at a time: one [K-1, 1, d] update makes
                        # the compiler re-lay the whole array out around it
                        for j in range(k - 1):
                            ssm_conv = jax.lax.dynamic_update_slice(
                                ssm_conv, tails[i][j][None, None, None],
                                (layer, j, slots[i], 0))
                new_state = (ssm_h, ssm_conv)
        out = dense(cfg, cfg.hidden_size, ("mlp", "embed"), "out_proj")(y)
        return out, new_state


class MambaLayer(nn.Module):
    """Scan body of a run of Mamba layers: the state pools ride the carry
    whole; `layer` (this layer's index in them) rides the xs."""
    config: JambaConfig

    @nn.compact
    def __call__(self, carry, layer, consts):
        cfg = self.config
        x, ssm_h, ssm_conv = carry
        mask, slots = consts
        state = None if ssm_h is None else (ssm_h, ssm_conv, layer, slots)
        h, new_state = MambaMixer(cfg, name="mixer")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_norm")(x),
            mask, state)
        x = x + h
        x = x + MLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="mlp_norm")(x))
        if new_state is not None:
            ssm_h, ssm_conv = new_state
        return (x, ssm_h, ssm_conv), None


class AttentionLayer(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, x, positions, kv_cache=None):
        cfg = self.config
        h, new_cache = Attention(cfg, name="attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_norm")(x),
            positions, kv_cache=kv_cache)
        x = x + h
        x = x + MLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="mlp_norm")(x))
        return x, new_cache


class Period(nn.Module):
    """Period `index` of the layer pattern: a run of Mamba layers, the
    attention layer, a run of Mamba layers."""
    config: JambaConfig
    index: int

    @nn.compact
    def __call__(self, carry, paged, consts):
        cfg = self.config
        x, kv_pages, ssm_h, ssm_conv = carry
        positions, mask, slots = consts
        pre, post = (cfg.attn_layer_offset,
                     cfg.attn_layer_period - cfg.attn_layer_offset - 1)
        first = self.index * (pre + post)  # this period's first Mamba layer
        run = (x, ssm_h, ssm_conv)
        if pre:
            run, _ = scan_run(MambaLayer, pre, "pre", cfg)(
                run, first + jnp.arange(pre), (mask, slots))
        x, ssm_h, ssm_conv = run
        if paged is not None:
            # this layer's tables and lengths, its index in the pool
            paged = paged.replace(
                kv_pages=kv_pages, layer=jnp.int32(self.index),
                block_tables=paged.block_tables[self.index],
                total_lens=paged.total_lens[self.index])
        x, new_paged = AttentionLayer(cfg, name="attn")(x, positions, paged)
        if paged is not None:
            kv_pages = new_paged.kv_pages
        run = (x, ssm_h, ssm_conv)
        if post:
            run, _ = scan_run(MambaLayer, post, "post", cfg)(
                run, first + pre + jnp.arange(post), (mask, slots))
        x, ssm_h, ssm_conv = run
        return x, kv_pages, ssm_h, ssm_conv


class JambaModel(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, kv_caches=None,
                 token_mask=None):
        """input_ids [B, S] -> logits [B, S, V]; with `kv_caches` (a
        HybridCache) -> (logits, the cache with its pools updated):
        S == 1 is a decode step over the slot set, S > 1 a prefill from
        zero state. `token_mask` [B, S] bool marks padding where there is
        no cache to say it (with one: positions < total_lens)."""
        cfg = self.config
        positions = default_positions(input_ids, positions)
        embed, x = embed_tokens(self, cfg, input_ids)

        cache = kv_caches
        kv_pages = ssm_h = ssm_conv = paged = slots = None
        mask = token_mask
        if cache is not None:
            kv_pages, ssm_h, ssm_conv = (cache.paged.kv_pages, cache.ssm_h,
                                         cache.ssm_conv)
            paged, slots = cache.paged, cache.slots
            mask = positions < cache.paged.total_lens[0][:, None]
            if input_ids.shape[1] == 1 and _scan_impl() != "jnp":
                # a decode step: the live slots, in order, once for
                # every layer's state update
                slots = live_slots(mask[:, 0])
        carry = (x, kv_pages, ssm_h, ssm_conv)
        for p in range(cfg.n_periods):
            carry = Period(cfg, p, name=f"period_{p}")(
                carry, paged, (positions, mask, slots))
        x, kv_pages, ssm_h, ssm_conv = carry

        with tracing.scope("rtpu.head"):
            x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
            if cfg.tie_word_embeddings:
                logits = jnp.einsum("bsh,vh->bsv", x,
                                    embed.astype(cfg.dtype))
            else:
                logits = dense(cfg, cfg.vocab_size, ("embed", "vocab"),
                               "lm_head")(x)
        if cache is None:
            return logits
        return logits, cache.replace(
            paged=cache.paged.replace(kv_pages=kv_pages),
            ssm_h=ssm_h, ssm_conv=ssm_conv)


# ---------------------------------------------------------------- registry
CONFIGS = {
    # AI21-Jamba2-3B (huggingface.co/ai21labs/AI21-Jamba2-3B config.json)
    "jamba2-3b": JambaConfig(),
    # two periods of 4 (Mamba, attention, Mamba, Mamba), one kv head, a
    # group of 6 q heads: not a whole number of 8-row sublane tiles
    "tiny-jamba": JambaConfig(
        vocab_size=256, hidden_size=96, intermediate_size=128,
        num_layers=8, num_heads=6, num_kv_heads=1, max_seq_len=256,
        attn_layer_period=4, attn_layer_offset=1, mamba_d_state=16,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8),
}


def get_config(name: str, **overrides) -> JambaConfig:
    return dataclasses.replace(CONFIGS[name], **overrides)
