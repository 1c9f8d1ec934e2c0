"""ZAYA1-family decoder: attention inside a compressed latent (CCA,
arXiv:2510.04476, its grouped form) and top-1 experts chosen by an MLP
router whose state runs down the stack (EDA, arXiv:2511.17127). Every layer
is one attention sublayer, then one expert sublayer:

    x <- x + W_o Attn(q, k, v)        [q, k, v] = cca(RMSNorm(x))
    x <- x + p_e Expert_e(RMSNorm(x)) e, p_e from the router, r_l its state
    logits = E^T RMSNorm(x_L)         E the (tied) embedding

CCA. With h the normed input, `q~ = h W_q` (`num_heads` heads of
`head_dim`, HALF the hidden size at the published sizes), `k~ = h W_k`
(`num_kv_heads` heads), and the value of kv head 0 from this token, of kv
head 1 from the token BEFORE it: `v_t = [h_t W_v1 | h_(t-1) W_v2]`. Over `z
= [q~ | k~]` two short causal convolutions in time: `u_t = sum_i a_i
z_(t-(T0-1)+i)` a channel (`cca_time0` taps), then inside each head `c_t =
sum_j u_(t-(T1-1)+j) B_j` (`cca_time1` taps of [head_dim, head_dim]). `q =
c^q + (q~ + rep(k~)) / 2`, `k = c^k + (avg(q~) + k~) / 2` (a kv head
repeated to its query heads, a kv head's query heads averaged); q and k are
L2-normed a head in float32, k times `tau_g = exp(log_tau_g)` a kv head; the
first `partial_rotary_factor` of a head's dims are rotated. The attention is
ordinary GQA on those heads with `scale=1.0` (the logit IS `tau_g cos(q,
k)`), and `W_o` goes UP from the latent to the hidden size. The latent is
never expanded: the kernels are every family's (ops/paged_attention.py).

FIVE READINGS of what the published config's keys do not settle (the
checkpoint library's `modeling_zaya.py` would), each ONE function here and
one in the benchmark's plain reference
(chipbench/references/cca_moe_decoder.py):

- R1 `value_shift`: with 2 kv heads "half the value heads" is one head each;
- R2 `cca_convolutions`: the first is depthwise, the second grouped by head,
  in that order, no bias, zeros before a sequence;
- R3 `qk_mean`: the grouped form's repeat and average, on the
  PRE-convolution rows;
- R4 `norm_temperature`: the norm comes before the rotation, `tau` is a kv
  head's, and any fixed factor of the published code is absorbed in it;
- R5 `ZayaRouter`: `g_l` is a vector, the MLP has two hidden gelu layers
  behind one norm, no skip "expert", no learned residual scale.

Router, all float32: `r_l = h W_d + g_l * r_(l-1)` (`r_(-1) = 0`; the sum is
what layer l + 1 receives), `s = W_3 gelu(W_2 gelu(W_1 RMSNorm(r_l)))`, `p =
softmax(s)`, the expert is `argmax(p + b)` (`b` enters the choice only) and
its weight `p_e`, not renormalised. The choice goes to models/llama.py's
`MoEMLP` through its `choice` seam: the dropless path, its counters and its
scopes are that layer's.

The stack is ONE scan whose carry is `(x, r, pages, tail)`: a second stream
beside the hidden states. A pipeline stage would have to hand `r` on beside
`x`; no stage is built (`serving_model`, `CANNOT_BE_GIVEN`).

Serving state is a `CcaCache`, two kinds in one pool, BOTH in every layer:
`kv_pages` [L, P, Hkv, page, 2 D] (k after convolution, mean, norm,
temperature and rotation, v after the shift: a page is final when written)
and `cca_tail` [L, slots, tail_width]: what the next token's q, k and v need
of the tokens before it, a decode slot: the last `cca_time0 + cca_time1 - 2`
rows of z and the last token's `h W_v2`. `cca_qkv` is the one function of
(this pass's rows, the tail before them) -> (q, k, v, the tail after them):
a decode step is it on one row a slot, a first pass on zeros, a resumed
pass on the slot's tail. PADDING-PROOF: the tail a pass leaves is its last
REAL tokens', a row with no real token keeps its slot's tail bit for bit.
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

from ..ops.paged_attention import (paged_attention_decode,
                                   paged_prefill_attention, paged_write)
from ..ops.rotary import rotate
from ..util import tracing
from ._stack import (default_positions, dense, embed_tokens, head_at_gather,
                     own_cache, scan_run, stacked_experts)
from .llama import A, ExpertFacts, LlamaConfig, MoEMLP, RMSNorm

# the family's interface flags (serve/llm/stage.py: model_family): a
# prefill row resumes from its slot's tail and the pages written, and the
# head is computed at the position a row samples from only
RESUMES_PREFILL = True
HEAD_AT_GATHER = True


@dataclass(frozen=True)
class ZayaConfig(LlamaConfig):
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: Optional[int] = 128
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: Optional[int] = 2048
    norm_topk_prob: bool = False
    # the published keys that are this family's
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    router_hidden_size: int = 256
    tie_word_embeddings: bool = True

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads or self.num_kv_heads != 2:
            raise NotImplementedError(
                "a value shift over other than 2 kv heads (R1: one head "
                "from the token, one from the token before it)")
        if (not self.tie_word_embeddings or not self.num_experts
                or self.norm_topk_prob):
            raise NotImplementedError(
                "a ZAYA model with an untied head, a dense FFN or a "
                "renormalised choice (the expert's weight is p_e as it is)")
        if min(self.cca_time0, self.cca_time1) < 1 or self.tail_rows < 1:
            raise NotImplementedError("CCA without a convolution in time")

    # ---- CCA's sizes
    @property
    def q_width(self) -> int:
        return self.num_heads * self.head_dim_

    @property
    def kv_width(self) -> int:
        return self.num_kv_heads * self.head_dim_

    @property
    def cca_channels(self) -> int:
        """The channels of z = [q~ | k~] the convolutions run over."""
        return self.q_width + self.kv_width

    @property
    def tail_rows(self) -> int:
        """Rows of z before a token that its q and k read."""
        return self.cca_time0 + self.cca_time1 - 2

    @property
    def tail_width(self) -> int:
        """Values a layer keeps a decode slot: the rows of z and the last
        token's shifted value half."""
        return self.tail_rows * self.cca_channels + self.head_dim_

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim_ * self.partial_rotary_factor)

    # ---- what serve/llm asks of a family whose layers keep per-slot state
    @property
    def n_slot_state_layers(self) -> int:
        return self.num_layers

    def tail_bytes_row(self) -> int:
        """What one sequence's tails cost to read or write once, all
        layers."""
        return (self.num_layers * self.tail_width
                * jnp.dtype(self.dtype).itemsize)

    # ---- sizes
    def attn_params(self) -> int:
        h, d = self.hidden_size, self.head_dim_
        heads = self.num_heads + self.num_kv_heads
        return (h * (self.cca_channels + self.kv_width)        # W_q, W_k, W_v
                + self.cca_time0 * self.cca_channels           # a
                + self.cca_time1 * heads * d * d               # B
                + self.num_kv_heads + self.q_width * h)        # tau, W_o

    def router_params(self) -> int:
        rh, e = self.router_hidden_size, self.num_experts
        return (self.hidden_size * rh + 2 * rh                 # W_d, g, norm
                + 2 * rh * rh + rh * e + e)

    def _layer_params(self, experts: int) -> int:
        h = self.hidden_size
        return (self.attn_params() + self.router_params() + 2 * h
                + experts * 3 * h * self.expert_width)

    def num_params(self) -> int:
        h = self.hidden_size
        return (self.num_layers * self._layer_params(self.num_experts)
                + self.vocab_size * h + h)

    def active_params(self) -> int:
        """Parameters one token multiplies in the layers (no head: a pass
        computes it at one position)."""
        return self.num_layers * self._layer_params(self.num_experts_per_tok)


def pass_cost_ratios(cfg: ZayaConfig) -> tuple:
    """(weights a prefill pass reads, scores a (query, key) pair makes),
    each over the parameters a token multiplies (serve/llm/engine.py:
    PassCost). A pass reads all the experts and a token multiplies one; a
    pair is one product a layer and QUERY head of the latent (8, not the
    16 a plain layer of this hidden size would have)."""
    active = cfg.active_params()
    layers = cfg.num_params() - cfg.vocab_size * cfg.hidden_size
    return layers / active, cfg.num_layers * cfg.num_heads / active


@struct.dataclass
class CcaCache:
    """Serving state of a ZayaModel, threaded through it as `kv_caches`.
    `slots` [B]: the decode slot each row of a PREFILL keeps its tail in
    (None: row i is slot i, a decode step over the slot set). `gather`
    [B]: the position (an index into the row) whose logits a prefill
    wants, -1 for none; None: logits at every position."""

    kv_pages: jax.Array          # [L, P, Hkv, page, 2 D]
    cca_tail: jax.Array          # [L, slots, tail_width]
    block_tables: jax.Array      # [B, MP]
    total_lens: jax.Array        # [B], INCLUDING the new tokens
    slots: Optional[jax.Array] = None
    gather: Optional[jax.Array] = None
    # STATIC, as models/llama.py: PagedCache has them
    ctx_pages: int = struct.field(pytree_node=False, default=0)
    ref_attention: bool = struct.field(pytree_node=False, default=False)

    @property
    def pool(self):
        return {"kv_pages": self.kv_pages, "cca_tail": self.cca_tail}

    def step(self, pool, total_lens):
        return self.replace(total_lens=total_lens, **pool)


# ----------------------------------------------------------------- serving
def serving_model(cfg: ZayaConfig, n_layers=None, first=True, last=True):
    """A later stage of a pipeline would need the router's state `r` of the
    layer before its first beside the hidden states: stages hand on `x`
    alone (serve/llm/pp.py), so only the whole model is built."""
    if not (first and last):
        raise NotImplementedError(
            "a slice of a model whose router state runs down the stack: a "
            "stage would have to hand `r` on beside the hidden states")
    return ZayaModel(cfg)


CANNOT_BE_GIVEN = ("keeps a tail of its last inputs a decode slot beside "
                   "every layer's pages", {
    "spec_lookahead":
        "needs a verify dispatch whose rejected draft tokens can be rolled "
        "back, and a tail advanced past them cannot be (no snapshot of the "
        "tail a verified position yet)",
    "tp": "would have to split the tail's channels with the heads over the "
          "mesh (the q-k mean crosses a kv head's query heads), and no "
          "sharding rule does",
    "pp": "hands the hidden states from stage to stage, and this model's "
          "router state `r` runs down the stack beside them",
    "handoff": "moves KV pages only, and a request's tail would be left "
               "behind",
    "prefix_reuse":
        "a page found by its content hash carries no tail: the first token "
        "behind a cached prefix would convolve and shift over zeros (a "
        "tail a page boundary, found and restored with the page's hash, "
        "would lift this: ROADMAP B-M4)",
})


class CcaFacts:
    """What the CCA layers' dispatches count (serve/llm/stage.py:
    model_family). Every record says `cca_layers` and `cca_tail_bytes_row`
    (the bytes one live row's tails cost to read or write once, all
    layers)."""

    STATS = {
        "cca_resumed_rows_total":
            "prefill rows that started from their slot's tail (a resumed "
            "pass of a folded prompt)",
        "cca_tail_resets_total":
            "prefill rows that started from a zero tail (a slot taken by a "
            "new request)",
        "cca_tail_pool_bytes":
            "bytes of the per-slot pool of convolution and value-shift "
            "tails",
    }

    def __init__(self, cfg: ZayaConfig):
        self.constant = {"cca_layers": cfg.num_layers,
                         "cca_tail_bytes_row": cfg.tail_bytes_row()}

    def prefill(self, totals: dict, rows, passes, ctx_pages: int) -> None:
        resumed = sum(ctx > n for _, n, ctx in rows)
        totals["cca_resumed_rows_total"] += resumed
        totals["cca_tail_resets_total"] += len(rows) - resumed

    def sizes(self, pool_bytes: dict) -> dict:
        return {"cca_tail_pool_bytes": pool_bytes["cca_tail"]}


def dispatch_facts(cfg: ZayaConfig, engine_config) -> list:
    return [ExpertFacts(cfg, engine_config), CcaFacts(cfg)]


def pool_spec(cfg: ZayaConfig, n_layers: int, num_pages: int,
              page_size: int, slots: int) -> dict:
    """name -> (shape, dtype) of what a serving engine keeps on the device
    for this model, both kinds in EVERY layer: pages of the latent's k and
    v, and a tail a decode slot (the slots second-minor, the tail's values
    along the lanes: whole tiles both ways at the published sizes)."""
    return {
        "kv_pages": ((n_layers, num_pages, cfg.num_kv_heads, page_size,
                      2 * cfg.head_dim_), cfg.dtype),
        "cca_tail": ((n_layers, slots, cfg.tail_width), cfg.dtype),
    }


def serving_cache(cfg: ZayaConfig, pool: dict, block_tables,
                  total_lens=None, slots=None, gather=None,
                  **static) -> CcaCache:
    """The cache one program pass hands the model: `pool` as `pool_spec`
    lays it out, block_tables [B, MP], total_lens [B] (None:
    `CcaCache.step` brings them)."""
    return CcaCache(block_tables=block_tables, total_lens=total_lens,
                    slots=slots, gather=gather, **pool, **static)


# ------------------------------------------------------------------- CCA
def value_shift(v_now, v_next, tail_v):
    """R1: kv head 0 is `h_t W_v1`, kv head 1 `h_(t-1) W_v2`. v_now,
    v_next [B, S, D] (`h W_v1`, `h W_v2`), tail_v [B, D] (`h W_v2` of the
    token before the first) -> (v [B, S, 2, D], `h W_v2` with the tail
    before it [B, S + 1, D])."""
    shifted = jnp.concatenate([tail_v[:, None], v_next], axis=1)
    return jnp.stack([v_now, shifted[:, :-1]], axis=2), shifted


def cca_convolutions(zz, a, b_taps, heads: int):
    """R2: zz [B, R + S, C] float32 (the tail's R rows, then the pass's),
    a [T0, C], b_taps [T1, heads, D, D] -> c [B, S, heads, D] float32: a
    depthwise causal convolution of T0 taps, then one of T1 taps that mixes
    the channels inside each head."""
    t0, t1 = a.shape[0], b_taps.shape[0]
    s = zz.shape[1] - (t0 + t1 - 2)
    a = a.astype(jnp.float32)
    u = sum(a[i] * zz[:, i:i + s + t1 - 1] for i in range(t0))
    u = u.reshape(u.shape[:2] + (heads, -1)).astype(b_taps.dtype)
    return sum(jnp.einsum("bshc,hcd->bshd", u[:, j:j + s], b_taps[j],
                          preferred_element_type=jnp.float32)
               for j in range(t1))


def qk_mean(q_pre, k_pre):
    """R3: q_pre [B, S, Hq, D], k_pre [B, S, Hkv, D] (the PRE-convolution
    rows) -> what is added to the convolved q and k: (q~ + rep(k~)) / 2 and
    (avg(q~) + k~) / 2; query head i belongs to kv head i // (Hq / Hkv)."""
    b, s, hq, d = q_pre.shape
    rep = hq // k_pre.shape[2]
    return ((q_pre + jnp.repeat(k_pre, rep, axis=2)) / 2,
            (q_pre.reshape(b, s, -1, rep, d).mean(3) + k_pre) / 2)


def norm_temperature(q, k, log_tau):
    """R4: float32 q [B, S, Hq, D] and k [B, S, Hkv, D] -> q / |q| a query
    head, k / |k| * exp(log_tau_g) a kv head. (1e-12 under the root: a
    padded row's zeros stay zeros.)"""
    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-12)

    return unit(q), unit(k) * jnp.exp(log_tau)[:, None]


def cca_qkv(cfg: ZayaConfig, z, v_now, v_next, tail, n_real, positions, a,
            b_taps, log_tau):
    """This pass's rows and the tail before them -> (q [B, S, Hq, D], k, v
    [B, S, Hkv, D], the tail after them [B, tail_width]). z [B, S, C] = [q~
    | k~], v_now, v_next [B, S, D]; tail [B, tail_width] = [z's last R rows
    | the last token's h W_v2] (zeros: a sequence's start); n_real [B]: the
    row's real tokens (the tail after them is its last REAL tokens', a row
    with none keeps the tail it came with); positions [B, S]."""
    b, s, _ = z.shape
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    rows, f32 = cfg.tail_rows, jnp.float32
    tail_z = tail[:, :rows * cfg.cca_channels].reshape(b, rows, -1)
    zz = jnp.concatenate([tail_z, z], axis=1)
    with tracing.scope("rtpu.attn.cca"):
        v, shifted = value_shift(v_now, v_next, tail[:, -d:])
        c = cca_convolutions(zz.astype(f32), a, b_taps, hq + hkv)
        pre = z.astype(f32).reshape(b, s, hq + hkv, d)
        mean_q, mean_k = qk_mean(pre[:, :, :hq], pre[:, :, hq:])
        q, k = norm_temperature(c[:, :, :hq] + mean_q, c[:, :, hq:] + mean_k,
                                log_tau)
        rd = cfg.rotary_dim
        inv_freq = cfg.rope_theta ** (-np.arange(0, rd, 2, np.float64) / rd)
        q, k = (rotate(x, positions, inv_freq, rotary_dim=rd).astype(
            cfg.dtype) for x in (q, k))
        # the rows of zz (and of the shifted value) that end at the row's
        # last real token: index n_real is R rows before its successor
        at = n_real[:, None] + jnp.arange(rows)
        new_z = jnp.take_along_axis(zz, at[:, :, None], axis=1)
        new_v = jnp.take_along_axis(shifted, n_real[:, None, None], axis=1)
        new_tail = jnp.concatenate(
            [new_z.reshape(b, -1), new_v[:, 0]], axis=-1)
    return q, k, v, new_tail


class CCAttention(nn.Module):
    config: ZayaConfig
    ctx_pages: int
    ref_attention: bool

    @nn.compact
    def __call__(self, x, positions, pages, tail, block_tables, total_lens,
                 n_real, slots, layer):
        """x [B, S, hidden] (normed); `pages`, `tail` the pools, `layer`
        this layer's index in them; n_real [B] -> (out, pages, tail)."""
        cfg = self.config
        b, s, _ = x.shape
        hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
        chan, taps1 = cfg.cca_channels, cfg.cca_time1
        # [q~ | k~ | h W_v1 | h W_v2] in one product
        qkv = dense(cfg, chan + 2 * d, ("embed", "qkv"), "qkv_proj")(x)
        a = self.param(
            "conv0", A(nn.initializers.uniform(cfg.cca_time0 ** -0.5),
                       (None, "qkv")), (cfg.cca_time0, chan), cfg.param_dtype)
        b_taps = self.param(
            "conv1", A(nn.initializers.normal((taps1 * d) ** -0.5),
                       (None, "heads", None, None)),
            (taps1, hq + hkv, d, d), cfg.param_dtype).astype(cfg.dtype)
        # tau = sqrt(D) at init: the scale a plain head would have had
        log_tau = self.param(
            "log_tau", A(nn.initializers.constant(0.5 * np.log(d)), (None,)),
            (hkv,), jnp.float32)
        fresh = positions[:, 0] == 0
        with tracing.scope("rtpu.attn.cache_write"):
            if slots is None:        # a decode step: row i is slot i
                held = jax.lax.dynamic_slice(
                    tail, (layer, 0, 0), (1,) + tail.shape[1:])[0]
            else:
                held = jnp.concatenate([jax.lax.dynamic_slice(
                    tail, (layer, slots[i], 0), (1, 1, tail.shape[2]))[0]
                    for i in range(b)])
            # a sequence's first token convolves and shifts over zeros,
            # whatever the request before it left in the slot
            before = jnp.where(fresh[:, None], jnp.zeros_like(held), held)
        q, k, v, after = cca_qkv(
            cfg, qkv[..., :chan], qkv[..., chan:chan + d],
            qkv[..., chan + d:], before, n_real, positions, a, b_taps,
            log_tau)
        with tracing.scope("rtpu.attn.cache_write"):
            # a row with no real token (an idle slot, a masked warm-up
            # pass) keeps what its slot held
            after = jnp.where((n_real > 0)[:, None], after, held)
            if slots is None:
                tail = jax.lax.dynamic_update_slice(tail, after[None],
                                                    (layer, 0, 0))
            else:
                for i in range(b):
                    tail = jax.lax.dynamic_update_slice(
                        tail, after[i][None, None], (layer, slots[i], 0))
        pages = paged_write(pages, k, v, block_tables, positions, total_lens,
                            layer)
        if s == 1:
            out = paged_attention_decode(
                q[:, 0], pages, block_tables, total_lens, layer=layer,
                scale=1.0, force_reference=self.ref_attention)[:, None]
        else:
            out = paged_prefill_attention(
                q, k, v, pages, block_tables, positions, total_lens,
                ctx_pages=self.ctx_pages, scale=1.0,
                impl="reference" if self.ref_attention else None,
                layer=layer)
        out = dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj")(
            out.reshape(b, s, hq * d))
        return out, pages, tail


# ---------------------------------------------------------------- router
class ZayaRouter(nn.Module):
    """R5, all float32 at `highest` (the products are [T, 256] wide): ->
    (MoEMLP's `choice`: probs [T, E], gate [T, k], idx [T, k]; r_l [B, S,
    router_hidden_size] for the next layer)."""
    config: ZayaConfig

    @nn.compact
    def __call__(self, x, r_prev):
        cfg = self.config
        rh, e, f32 = cfg.router_hidden_size, cfg.num_experts, jnp.float32

        def w(name, shape, init=nn.initializers.lecun_normal()):
            return self.param(name, A(init, (None,) * len(shape)), shape,
                              f32)

        def mm(a, b):
            return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)

        with tracing.scope("rtpu.moe.route"):
            r = mm(x.astype(f32), w("down", (x.shape[-1], rh)))
            r = r + w("eda", (rh,), nn.initializers.normal(0.5)) * r_prev
            y = RMSNorm(cfg.rms_norm_eps, f32, (None,), name="norm")(r)
            y = jax.nn.gelu(mm(y, w("fc1", (rh, rh))), approximate=False)
            y = jax.nn.gelu(mm(y, w("fc2", (rh, rh))), approximate=False)
            probs = jax.nn.softmax(mm(y, w("out", (rh, e))), axis=-1)
            probs = probs.reshape(-1, e)
            bias = w("bias", (e,), nn.initializers.zeros)
            _, idx = jax.lax.top_k(probs + bias, cfg.num_experts_per_tok)
            gate = jnp.take_along_axis(probs, idx, axis=-1)
        # [B, S, 1, E] bool, the experts each token chose, for a caller
        # that asks for the "selection" collection (the benchmark's check,
        # as models/llama.py: MoEMLP sows a share's); nobody else pays
        self.sow("selection", "held", (idx[..., None] == jnp.arange(e)).any(
            -2).reshape(x.shape[:2] + (1, e)))
        return (probs, gate, idx), r


class ZayaLayer(nn.Module):
    """Scan body: (x, r, pages, tail) ride the carry, the pools whole;
    the layer's index rides the xs; `consts` are the pass's positions,
    table and slots and, on the serving path, the WHOLE stack of expert
    weights for the grouped matmul to read in place (models/llama.py:
    `_stacked_experts` says why)."""
    config: ZayaConfig
    ctx_pages: int
    ref_attention: bool

    @nn.compact
    def __call__(self, carry, layer, consts):
        cfg = self.config
        x, r, pages, tail = carry
        (positions, block_tables, total_lens, n_real, slots, token_mask,
         experts) = consts
        h, pages, tail = CCAttention(
            cfg, self.ctx_pages, self.ref_attention, name="attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="attn_norm")(x),
            positions, pages, tail, block_tables, total_lens, n_real, slots,
            layer)
        x = x + h
        normed = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="mlp_norm")(x)
        choice, r = ZayaRouter(cfg, name="router")(normed, r)
        h = MoEMLP(cfg, name="moe")(
            normed, token_mask,
            None if experts is None else experts + (layer,), choice=choice)
        return (x + h, r, pages, tail), None


class ZayaModel(nn.Module):
    config: ZayaConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, kv_caches=None,
                 token_mask=None):
        """THE CALL of models/_stack.py, `kv_caches` a CcaCache: a prefill
        pass resumes from the rows' pages and their slots' tails."""
        cfg = self.config
        b, s = input_ids.shape
        positions = default_positions(input_ids, positions)
        cache = kv_caches if kv_caches is not None else own_cache(
            pool_spec, serving_cache, cfg, b, s, token_mask)
        if token_mask is None:
            token_mask = positions < cache.total_lens[:, None]
        embed, x = embed_tokens(self, cfg, input_ids)

        n_real = jnp.clip(cache.total_lens - positions[:, 0], 0, s)
        experts = (stacked_experts(self, cfg, ("layers", "moe"))
                   if kv_caches is not None else None)
        consts = (positions, cache.block_tables, cache.total_lens, n_real,
                  cache.slots, token_mask, experts)
        layers = scan_run(ZayaLayer, cfg.num_layers, "layers", cfg,
                          cache.ctx_pages, cache.ref_attention)
        # the router's state before layer 0 is zero
        r0 = jnp.zeros((b, s, cfg.router_hidden_size), jnp.float32)
        (x, _, pages, tail), _ = layers(
            (x, r0, cache.kv_pages, cache.cca_tail),
            jnp.arange(cfg.num_layers), consts)

        with tracing.scope("rtpu.head"):
            x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        logits = head_at_gather(self, cfg, x, cache.gather, weight=embed)
        if kv_caches is None:
            return logits
        return logits, cache.replace(kv_pages=pages, cca_tail=tail)


# ---------------------------------------------------------------- registry
CONFIGS = {
    # ZAYA1-8B (huggingface.co/Zyphra/ZAYA1-8B config.json, model_type
    # zaya), whole: 40 layers, 8.30 B in the layers + the 537 M tied
    # embedding. One chip holds a pipeline stage's layers: `num_layers`
    # (chipbench/configs/zaya1-8b-serve.json)
    "zaya1-8b": ZayaConfig(
        vocab_size=262272, hidden_size=2048, intermediate_size=2048,
        num_layers=40, max_seq_len=131072, rope_theta=5000000.0,
        rms_norm_eps=1e-5),
    "tiny-zaya": ZayaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=32, num_layers=3,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=512,
        rope_theta=5000000.0, rms_norm_eps=1e-5, remat=False, num_experts=4,
        moe_intermediate_size=32, router_hidden_size=16),
}


def get_config(name: str, **overrides) -> ZayaConfig:
    return dataclasses.replace(CONFIGS[name], **overrides)
