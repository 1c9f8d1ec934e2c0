"""Mellum-family decoder: sliding-window and full attention layers in one
stack, every layer's FFN a sparse expert layer.

`layer_types` names every PUBLISHED layer ("sliding_attention" or
"full_attention"; the 12B model's are three sliding and one full, seven
times); `num_layers` of them are run, from the first. With `n(.)` = RMSNorm
with a learned weight:

    h <- h + Attn_l(n(h));   h <- h + MoE(n(h));   logits = W_head n(h_L)

`Attn_l`: grouped-query attention without bias or q/k norm, q and k rotated
over the whole head by the layer KIND's table: a sliding layer by the
default rotation at `rope_theta`, a full layer by YaRN's frequencies
(ops/rotary.py) with `attention_factor` on cos and sin (so a score carries
its square: the published code's convention). A full layer is causal; a
sliding layer also sees only the `sliding_window` keys up to its own
(query i sees key j iff i - window < j <= i). `MoE` is models/llama.py's
`MoEMLP` (softmax over all experts in float32, the k best renormalised,
dropless).

What a layer's shapes are is asked of the config A KIND, and every answer
defaults to this family's one: `heads(kind)` query heads, `rotary_dim(kind)`
dims of a head rotated at `theta(kind)`, `attn_gate` (a sigmoid gate a query
head and token on the attention output), `dense_ffn(i)` (layer i's FFN is
models/llama.py's dense `MLP`, not the expert layer). models/laguna.py
answers them otherwise and runs this stack as it is.

The stack is one scan a RUN of like layers, the runs in sequence
(models/minicpm_sala.py; parameters `run_<ii>/...` with a leading [run]
axis, so a run's expert weights are one stack that the grouped matmul reads
in place).

Serving state is a `WindowCache`, two parts in ONE donated pool whose layer
counts differ: the full layers' pages `kv_pages` [n_full, P, Hkv, page, 2D]
under the request's block table, whose length follows the context; and the
sliding layers' RINGS `win_pages` [n_sliding, slots * window / page, Hkv,
page, 2D], a decode slot's last `window` keys and values a layer and no
others (ops/paged_attention.py: ring_write). What a sequence's sliding
layers hold is bounded by the window whatever its context, and its cost is
paid once a slot, not a token: the split of memory between the kinds
follows from `max_batch` and the window alone. A prefill row RESUMES: a
full layer attends the pages earlier passes wrote, a sliding layer the
ring as the pass before left it. PADDING-PROOF as models/jamba.py: a
position past a row's length moves no page and no ring, an idle decode
slot keeps both bit for bit.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..ops.paged_attention import (paged_attention_decode,
                                   paged_prefill_attention, paged_write,
                                   ring_write, window_attention_decode,
                                   window_prefill_attention)
from ..ops.rotary import rotate, yarn_inv_freq
from ..util import tracing
from ._stack import (default_positions, dense as _dense, embed_tokens,
                     head_at_gather, own_cache, scan_run, stacked_experts,
                     whole_model_only)
from .llama import A, ExpertFacts, LlamaConfig, MLP, MoEMLP, RMSNorm

SLIDING, FULL = "sliding_attention", "full_attention"
# the family's interface flags (serve/llm/stage.py: model_family): a prefill
# row resumes from its pages and its slot's rings; the head is computed at
# the position a row samples from only
RESUMES_PREFILL = True
HEAD_AT_GATHER = True

_PUBLISHED_LAYERS = (SLIDING, SLIDING, SLIDING, FULL) * 7


@dataclass(frozen=True)
class MellumConfig(LlamaConfig):
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS
    sliding_window: int = 1024
    # rope_parameters.full_attention (rope_type "yarn"); the sliding
    # layers' is the default rotation at the same `rope_theta`
    rope_factor: float = 16.0
    rope_original_max: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 1.2772588722239782
    # o_h <- sigmoid(u W_g)_h o_h, W_g [hidden, heads of the kind]
    attn_gate: bool = False

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"layer_types names {unknown}")
        if not 0 < self.num_layers <= len(self.layer_types):
            raise ValueError(
                f"num_layers {self.num_layers} of the "
                f"{len(self.layer_types)} that layer_types names")
        if self.sliding_window < 1 or not self.num_experts:
            raise ValueError("a Mellum layer has a window of at least one "
                             "token and a sparse expert FFN")

    @property
    def layers(self) -> Tuple[str, ...]:
        return self.layer_types[:self.num_layers]

    # ---- a layer's shapes, a KIND (this family: one answer for both)
    def heads(self, kind: str) -> int:
        return self.num_heads

    def rotary_dim(self, kind: str) -> int:
        return self.head_dim_

    def theta(self, kind: str) -> float:
        return self.rope_theta

    def dense_ffn(self, layer: int) -> bool:
        return False

    @property
    def runs(self) -> Tuple[Tuple[str, int], ...]:
        """((kind, how many), ...): the layers as runs of like attention."""
        return _runs_of(self.layers)

    @property
    def stack_runs(self) -> Tuple[Tuple[Tuple[str, bool], int], ...]:
        """(((kind, whether the FFN is dense), how many), ...): the layers
        as the runs the stack scans, like in attention AND in FFN."""
        return _runs_of((kind, self.dense_ffn(i))
                        for i, kind in enumerate(self.layers))

    @property
    def n_expert_layers(self) -> int:
        return sum(not self.dense_ffn(i) for i in range(self.num_layers))

    @property
    def n_window_layers(self) -> int:
        return self.layers.count(SLIDING)

    @property
    def n_full_layers(self) -> int:
        return self.layers.count(FULL)

    # what serve/llm asks of a family whose layers keep state a decode slot
    @property
    def n_slot_state_layers(self) -> int:
        return self.n_window_layers

    def inv_freq(self, kind: str) -> np.ndarray:
        """[rotary_dim(kind) // 2]: the frequencies' `dim` is the ROTATED
        dims' count."""
        d, theta = self.rotary_dim(kind), self.theta(kind)
        if kind == FULL:
            return yarn_inv_freq(d, theta, self.rope_factor,
                                 self.rope_original_max, self.rope_beta_fast,
                                 self.rope_beta_slow)
        return (theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
                ).astype(np.float32)

    def active_params(self) -> int:
        """Parameters one token multiplies, without the head (a pass
        computes it at one position)."""
        return (super().active_params()
                - 2 * self.vocab_size * self.hidden_size)


def _runs_of(keys) -> tuple:
    out = []
    for key in keys:
        if out and out[-1][0] == key:
            out[-1][1] += 1
        else:
            out.append([key, 1])
    return tuple((k, n) for k, n in out)


def attention_kinds(cfg: MellumConfig) -> tuple:
    """((layers, window or None), ...): the kinds of attention layer a pass
    runs (serve/llm/engine.py: PassCost prices a pass by the pairs each
    kind's flash calls really visit, and the `prefill_attn_blocks_*`
    counters count them so)."""
    return ((cfg.n_full_layers, None),
            (cfg.n_window_layers, cfg.sliding_window))


def pass_cost_ratios(cfg: MellumConfig) -> tuple:
    """(weights a prefill pass reads, scores a (query, key) pair makes a
    layer and head x the layers), each over the parameters a token
    multiplies (serve/llm/engine.py: PassCost). A pass reads every expert
    and a token multiplies `num_experts_per_tok` of them."""
    active = cfg.active_params()
    embed_head = 2 * cfg.vocab_size * cfg.hidden_size
    return ((cfg.num_params() - embed_head) / active,
            cfg.num_layers * cfg.num_heads / active)


@struct.dataclass
class WindowCache:
    """Serving state of a MellumModel, threaded through it as `kv_caches`.
    `slots` [B]: the decode slot each row of a PREFILL keeps its rings in
    (None: row i is slot i, a decode step over the slot set). `gather`
    [B]: the position (an index into the row) whose logits a prefill
    wants, -1 for none; None: logits at every position."""

    kv_pages: jax.Array
    win_pages: jax.Array
    block_tables: jax.Array      # [B, MP], the full layers'
    total_lens: jax.Array        # [B], INCLUDING the new tokens
    slots: Optional[jax.Array] = None
    gather: Optional[jax.Array] = None
    # STATIC, as models/llama.py: PagedCache has them. ctx_pages 0: no row
    # of this pass has anything in its pages or its rings yet
    ctx_pages: int = struct.field(pytree_node=False, default=0)
    ref_attention: bool = struct.field(pytree_node=False, default=False)

    @property
    def pool(self):
        return {"kv_pages": self.kv_pages, "win_pages": self.win_pages}

    def step(self, pool, total_lens):
        return self.replace(kv_pages=pool["kv_pages"],
                            win_pages=pool["win_pages"],
                            total_lens=total_lens)


# ----------------------------------------------------------------- serving
def serving_model(cfg: MellumConfig, n_layers=None, first=True, last=True):
    return whole_model_only(MellumModel, cfg, first, last,
                            "whose layers are a list of two kinds")


# (stage.py: model_family) what rests on a block table that holds every
# token of a sequence, or that was never run on the two kinds of layer
CANNOT_BE_GIVEN = ("keeps its sliding-window layers' last "
                   "{cfg.sliding_window} keys in a ring a decode slot", {
    "prefix_reuse":
        "finds a page by the hash of its tokens, and that page is a full "
        "layer's: the sliding layers' keys of the same tokens were a "
        "ring's and are overwritten. A hit would need the window's last "
        "keys kept beside the full layers' pages (no snapshot of a ring "
        "yet)",
    "spec_lookahead":
        "needs a verify dispatch whose rejected draft tokens can be rolled "
        "back, and a ring they overwrote cannot be (no ring snapshot yet)",
    "tp": "shards the page pool over its kv-head axis and runs the jnp "
          "attention paths under GSPMD; the rings' write and the banded "
          "context part have no sharded form that was ever run",
    "pp": "slices a uniform `layers` axis (stage_params), and this "
          "model's layers are a list of two kinds with two kinds of state",
    "handoff": "moves KV pages only, and a request's rings would be left "
               "behind",
})


class WindowFacts:
    """What the two kinds of attention layer count (serve/llm/stage.py:
    model_family), all a LAYER of the kind, from the rows' lengths alone
    (nothing is fetched for it). Every record says `window_layers` and
    `full_layers`, and the query heads a layer of each kind has
    (`window_heads`, `full_heads`); and of its real rows
    `window_tokens_read` / `full_tokens_read` (keys the kind's attention
    read: a decode step a full layer the row's context and a sliding layer
    min(context, window); a prefill pass its
    own tokens and what it resumes behind, for a sliding layer at most the
    window) and `window_tokens_held` / `full_tokens_held` (what the row's
    rings and pages hold when the dispatch starts its last step)."""

    STATS = {
        "kv_window_tokens_released_total":
            "tokens that left a sliding layer's window as their sequence "
            "advanced (their ring index was overwritten), a layer",
        "kv_window_pool_bytes":
            "bytes of the sliding layers' rings (a window a slot and "
            "layer, whatever the contexts)",
        "kv_full_pool_bytes": "bytes of the full layers' page pool",
    }

    def __init__(self, cfg: MellumConfig):
        self.window = cfg.sliding_window
        self.constant = {"window_layers": cfg.n_window_layers,
                         "full_layers": cfg.n_full_layers,
                         "window_heads": cfg.heads(SLIDING),
                         "full_heads": cfg.heads(FULL)}

    def _released(self, totals: dict, before: int, after: int) -> None:
        totals["kv_window_tokens_released_total"] += (
            max(after - self.window, 0) - max(before - self.window, 0))

    def prefill(self, totals: dict, rows, passes, ctx_pages: int) -> dict:
        w = self.window
        for _, n_new, end in rows:
            self._released(totals, end - n_new, end)
        return {
            "window_tokens_read": sum(min(end - n, w) + n
                                      for _, n, end in rows),
            "full_tokens_read": sum(end for _, _, end in rows),
            "window_tokens_held": sum(min(end, w) for _, _, end in rows),
            "full_tokens_held": sum(end for _, _, end in rows)}

    def decode(self, totals: dict, rows, k: int) -> dict:
        w = self.window
        for _, _, ctx in rows:
            # the pending token is in `ctx`; k - 1 more are written
            self._released(totals, ctx - 1, ctx + k - 1)
        return {
            "window_tokens_read": sum(min(ctx + j, w) for _, _, ctx in rows
                                      for j in range(k)),
            "full_tokens_read": sum(k * ctx + k * (k - 1) // 2
                                    for _, _, ctx in rows),
            "window_tokens_held": sum(min(ctx + k - 1, w)
                                      for _, _, ctx in rows),
            "full_tokens_held": sum(ctx + k - 1 for _, _, ctx in rows)}

    def sizes(self, pool_bytes: dict) -> dict:
        return {"kv_window_pool_bytes": pool_bytes["win_pages"],
                "kv_full_pool_bytes": pool_bytes["kv_pages"]}


def dispatch_facts(cfg: MellumConfig, engine_config) -> list:
    return [ExpertFacts(cfg, engine_config), WindowFacts(cfg)]


def ring_pages(cfg: MellumConfig, page_size: int) -> int:
    """Pages of one slot's ring, a layer: the window in whole pages."""
    if cfg.sliding_window % page_size:
        raise ValueError(
            f"page_size {page_size} does not divide the sliding window of "
            f"{cfg.sliding_window} tokens: a slot's ring is the window in "
            f"whole pages (the decode kernel's one length a row is the "
            f"band only if the ring holds the window exactly)")
    return cfg.sliding_window // page_size


def pool_spec(cfg: MellumConfig, n_layers: int, num_pages: int,
              page_size: int, slots: int) -> dict:
    """name -> (shape, dtype) of what a serving engine keeps on the device
    for this model: `num_pages` pages for the full layers, a ring of the
    window a slot for the sliding layers. The two entries' layer counts
    differ; the rings' size follows from `slots` and the window."""
    page = (cfg.num_kv_heads, page_size, 2 * cfg.head_dim_)
    return {
        "kv_pages": ((cfg.n_full_layers, num_pages) + page, cfg.dtype),
        "win_pages": ((cfg.n_window_layers,
                       slots * ring_pages(cfg, page_size)) + page,
                      cfg.dtype),
    }


def serving_cache(cfg: MellumConfig, pool: dict, block_tables,
                  total_lens=None, slots=None, gather=None,
                  **static) -> WindowCache:
    """The cache one program pass hands the model: `pool` as `pool_spec`
    lays it out, block_tables [B, MP], total_lens [B] (None:
    `WindowCache.step` brings them)."""
    return WindowCache(
        kv_pages=pool["kv_pages"], win_pages=pool["win_pages"],
        block_tables=block_tables, total_lens=total_lens, slots=slots,
        gather=gather, **static)


# ------------------------------------------------------------------ layers
def head_gate(o, x, w_g):
    """o [B, S, H, D] x sigmoid(x W_g) [B, S, H], one scalar a query head
    and token, its logits and the product in float32."""
    g = jax.nn.sigmoid(jnp.einsum("bsh,hn->bsn", x, w_g,
                                  preferred_element_type=jnp.float32))
    return (o.astype(jnp.float32) * g[..., None]).astype(o.dtype)


class MixedAttention(nn.Module):
    """One attention layer of either kind; `layer` is its index among the
    layers of its KIND (into `kv_pages` or `win_pages`)."""
    config: MellumConfig
    kind: str
    ctx_pages: int
    ref_attention: bool

    @nn.compact
    def __call__(self, x, positions, kv_pages, win_pages, block_tables,
                 total_lens, slots, layer):
        cfg = self.config
        b, s, _ = x.shape
        nq, nkv, d = cfg.heads(self.kind), cfg.num_kv_heads, cfg.head_dim_
        qkv = _dense(cfg, (nq + 2 * nkv) * d, ("embed", "qkv"), "qkv_proj")(x)
        q, k, v = jnp.split(qkv, [nq * d, (nq + nkv) * d], axis=-1)
        full = self.kind == FULL
        factor = cfg.rope_attention_factor if full else 1.0
        inv_freq, turned = cfg.inv_freq(self.kind), cfg.rotary_dim(self.kind)
        q = rotate(q.reshape(b, s, nq, d), positions, inv_freq, factor,
                   turned)
        k = rotate(k.reshape(b, s, nkv, d), positions, inv_freq, factor,
                   turned)
        v = v.reshape(b, s, nkv, d)
        impl = "reference" if self.ref_attention else None
        if full:
            kv_pages = paged_write(kv_pages, k, v, block_tables, positions,
                                   total_lens, layer)
            if s == 1:
                o = paged_attention_decode(
                    q[:, 0], kv_pages, block_tables, total_lens, layer=layer,
                    force_reference=self.ref_attention)[:, None]
            else:
                o = paged_prefill_attention(
                    q, k, v, kv_pages, block_tables, positions, total_lens,
                    ctx_pages=self.ctx_pages, impl=impl, layer=layer)
        else:
            rp = ring_pages(cfg, win_pages.shape[-2])
            if s == 1:
                win_pages = ring_write(win_pages, k, v, slots, positions,
                                       total_lens, layer, rp)
                o = window_attention_decode(
                    q[:, 0], win_pages, total_lens, ring_pages=rp,
                    layer=layer, force_reference=self.ref_attention)[:, None]
            else:
                # the ring is read as the pass before left it, then written
                o = window_prefill_attention(
                    q, k, v, win_pages, slots, positions, total_lens,
                    window=cfg.sliding_window, ring_pages=rp,
                    resumes=self.ctx_pages > 0, scale=d ** -0.5, impl=impl,
                    layer=layer)
                win_pages = ring_write(win_pages, k, v, slots, positions,
                                       total_lens, layer, rp)
        if cfg.attn_gate:
            w_g = self.param(
                "gate_proj", A(nn.initializers.lecun_normal(),
                               ("embed", "heads")),
                (cfg.hidden_size, nq), cfg.param_dtype)
            with tracing.scope("rtpu.attn.gate"):
                o = head_gate(o.reshape(b, s, nq, d), x, w_g.astype(cfg.dtype))
        out = _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj")(
            o.reshape(b, s, nq * d))
        return out, kv_pages, win_pages


class MellumLayer(nn.Module):
    """Scan body of a run of like layers: the pool's two parts ride the
    carry whole; (the layer's index among its kind, its index in the run)
    ride the xs; `consts` are the pass's positions, table and slots and, on
    the serving path, the run's WHOLE stack of expert weights for the
    grouped matmul to read in place (models/llama.py: `_stacked_experts`
    says why)."""
    config: MellumConfig
    kind: str
    ctx_pages: int
    ref_attention: bool
    dense: bool = False

    @nn.compact
    def __call__(self, carry, xs, consts):
        cfg = self.config
        x, kv_pages, win_pages = carry
        pool_idx, run_idx = xs
        (positions, block_tables, total_lens, slots, token_mask,
         experts) = consts
        h, kv_pages, win_pages = MixedAttention(
            cfg, self.kind, self.ctx_pages, self.ref_attention, name="attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="attn_norm")(x),
            positions, kv_pages, win_pages, block_tables, total_lens, slots,
            pool_idx)
        x = x + h
        normed = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="mlp_norm")(x)
        if self.dense:
            return (x + MLP(cfg, name="mlp")(normed), kv_pages,
                    win_pages), None
        moe = MoEMLP(cfg, name="moe")
        h = moe(normed, token_mask,
                None if experts is None else experts + (run_idx,))
        if (self.is_mutable_collection("selection")
                and not self.is_initializing()):
            # [B, S, 1, E] bool, the experts each token chose, for a caller
            # that asks for the "selection" collection (the benchmark's
            # check); nobody else pays for it
            held = nn.meta.unbox(moe.variables["params"])
            logits = jnp.einsum("bsh,he->bse", normed.astype(jnp.float32),
                                held["router"])
            if cfg.moe_scoring == "sigmoid":
                probs = jax.nn.sigmoid(logits) + held["router_bias"]
            else:
                probs = jax.nn.softmax(logits, axis=-1)
            _, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
            self.sow("selection", "chosen", (
                idx[..., None] == jnp.arange(cfg.num_experts)).any(-2)[
                    :, :, None])
        return (x + h, kv_pages, win_pages), None


class MellumModel(nn.Module):
    config: MellumConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, kv_caches=None,
                 token_mask=None):
        """THE CALL of models/_stack.py, `kv_caches` a WindowCache: a
        prefill pass resumes from the rows' pages and their slots' rings."""
        cfg = self.config
        positions = default_positions(input_ids, positions)
        cache = kv_caches if kv_caches is not None else own_cache(
            pool_spec, serving_cache, cfg, *input_ids.shape, token_mask)
        if token_mask is None:
            token_mask = positions < cache.total_lens[:, None]
        slots = cache.slots
        if slots is None:
            slots = jnp.arange(input_ids.shape[0], dtype=jnp.int32)
        _, x = embed_tokens(self, cfg, input_ids)

        carry = (x, cache.kv_pages, cache.win_pages)
        at = {SLIDING: 0, FULL: 0}
        for r, ((kind, dense), n) in enumerate(cfg.stack_runs):
            name = f"run_{r:02d}"
            experts = (stacked_experts(self, cfg, (name, "moe"))
                       if not dense and kv_caches is not None else None)
            consts = (positions, cache.block_tables, cache.total_lens, slots,
                      token_mask, experts)
            carry, _ = scan_run(MellumLayer, n, name, cfg, kind=kind,
                                dense=dense, ctx_pages=cache.ctx_pages,
                                ref_attention=cache.ref_attention)(
                carry, (at[kind] + jnp.arange(n), jnp.arange(n)), consts)
            at[kind] += n
        x, kv_pages, win_pages = carry

        with tracing.scope("rtpu.head"):
            x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        logits = head_at_gather(self, cfg, x, cache.gather)
        if kv_caches is None:
            return logits
        return logits, cache.replace(kv_pages=kv_pages, win_pages=win_pages)


# ---------------------------------------------------------------- registry
CONFIGS = {
    # Mellum2-12B-A2.5B-Instruct (huggingface.co/JetBrains/
    # Mellum2-12B-A2.5B-Instruct config.json, model_type mellum), whole: 28
    # layers. `intermediate_size` 7168 is used by no layer (every layer's
    # FFN is sparse)
    "mellum2-12b-a2.5b": MellumConfig(
        vocab_size=98304, hidden_size=2304, intermediate_size=7168,
        num_layers=28, num_heads=32, num_kv_heads=4, head_dim=128,
        max_seq_len=131072, rope_theta=500000.0, rms_norm_eps=1e-6,
        num_experts=64, num_experts_per_tok=8, moe_intermediate_size=896,
        norm_topk_prob=True),
    # two periods of sliding, sliding, sliding, full; a window of two
    # pages of 16; 8 experts of which 2; YaRN's original length inside the
    # tests' prompts
    "tiny-mellum": MellumConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=8,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=1024,
        rope_theta=500000.0, rms_norm_eps=1e-6, remat=False, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32, norm_topk_prob=True,
        sliding_window=32, rope_factor=16.0, rope_original_max=64),
}


def get_config(name: str, **overrides) -> MellumConfig:
    return dataclasses.replace(CONFIGS[name], **overrides)
