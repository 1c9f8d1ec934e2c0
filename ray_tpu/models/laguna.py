"""Laguna-family decoder (poolside, `model_type` laguna): models/mellum.py's
mixed stack of sliding-window and full attention layers with the shapes a
KIND, a leading dense layer, and DeepSeek-V3's router over every other
layer's experts beside a shared one.

What differs from Mellum, each a thing the config answers and the stack
asks (models/mellum.py: `heads`, `rotary_dim`, `theta`, `attn_gate`,
`dense_ffn`); nothing of the stack, the cache or the expert layer is copied
here. With `u = n(x)`, `n` = RMSNorm with a learned weight:

- a layer has `num_attention_heads_per_layer[i]` query heads over the same
  `num_kv_heads` kv heads: the published models give one count to every
  full layer and a larger one to every sliding layer (48 and 64 over 8:
  groups of 6 and 8), so q and o are a kind's shape;
- a full layer rotates the FIRST `partial_rotary_factor` x head_dim dims
  of q and k by YaRN's frequencies over those dims at `rope_theta`, with
  `attention_factor` on cos and sin, and passes the rest through; a
  sliding layer rotates the whole head at `sliding_rope_theta`;
- `gating`: `o_h <- sigmoid(u W_g)_h o_h`, `W_g` [hidden, heads of the
  layer], one scalar a query head and token (R1: the sibling config spells
  the key "per-head"; the nonlinearity is the head-wise gate's of
  arXiv:2505.06708);
- layer i's FFN is `mlp_layer_types[i]`: "dense" is a gated-silu FFN at
  `intermediate_size`, "sparse" is models/llama.py's `MoEMLP` with
  `moe_scoring="sigmoid"` (R2: sigmoid scores, a selection bias an expert,
  the k chosen renormalised and scaled by `routed_scaling_factor`) beside
  `n_shared_experts` shared ones;
- no norm over q and k (R3: no key names one).

The three readings R1-R3 are what `modeling_laguna.py` would settle; the
benchmark's configuration lists them under `assumed`.

Serving state, the family interface and what it cannot be given are
Mellum's: a `WindowCache` of the full layers' pages and the sliding layers'
rings in one donated pool.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Tuple

# (the family's interface, serve/llm/stage.py: model_family, is Mellum's
# wherever the names below are not defined here)
from .mellum import (CANNOT_BE_GIVEN, FULL, HEAD_AT_GATHER,  # noqa: F401
                     RESUMES_PREFILL, SLIDING, MellumConfig, dispatch_facts,
                     pool_spec, serving_cache, serving_model)

DENSE, SPARSE = "dense", "sparse"
_PUBLISHED_LAYERS = (FULL, SLIDING, SLIDING, SLIDING) * 10


@dataclass(frozen=True)
class LagunaConfig(MellumConfig):
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS
    sliding_window: int = 512
    # every PUBLISHED layer's query heads and FFN kind; `num_heads` is the
    # widest layer's
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64) * 10
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * 39
    # rope_parameters: the full layers' table is `rope_theta` + the YaRN
    # fields over `partial_rotary_factor` of the head, the sliding layers'
    # the default rotation at `sliding_rope_theta` over all of it
    sliding_rope_theta: float = 10000.0
    partial_rotary_factor: float = 0.5
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 64.0
    rope_attention_factor: float = 1.4158883083359672
    attn_gate: bool = True

    def __post_init__(self):
        for name in ("num_attention_heads_per_layer", "mlp_layer_types"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        super().__post_init__()
        n = len(self.layer_types)
        if (len(self.num_attention_heads_per_layer) != n
                or len(self.mlp_layer_types) != n
                or set(self.mlp_layer_types) - {DENSE, SPARSE}):
            raise ValueError(
                f"num_attention_heads_per_layer and mlp_layer_types name "
                f"each of the {n} published layers, the latter as "
                f"{DENSE!r} or {SPARSE!r}")
        for kind in set(self.layers):
            heads = sorted({h for h, k in zip(
                self.num_attention_heads_per_layer, self.layers)
                if k == kind})
            if len(heads) != 1 or heads[0] % self.num_kv_heads:
                raise NotImplementedError(
                    f"the {kind} layers' query heads {heads}: one count a "
                    f"kind (a kind's layers are one scan and one part of "
                    f"the pool), a multiple of the kv heads")
        if self.rotary_dim(FULL) % 2 or not 0 < self.rotary_dim(FULL) <= (
                self.head_dim_):
            raise ValueError(f"partial_rotary_factor "
                             f"{self.partial_rotary_factor} of a head of "
                             f"{self.head_dim_}")

    # ---- a layer's shapes, a kind (models/mellum.py asks)
    def heads(self, kind: str) -> int:
        """The kind's query heads: its first layer's (the layers run are
        the published ones' first, and hold one count a kind)."""
        return next((h for h, k in zip(self.num_attention_heads_per_layer,
                                       self.layer_types) if k == kind), 0)

    def rotary_dim(self, kind: str) -> int:
        if kind == FULL:
            return int(self.head_dim_ * self.partial_rotary_factor)
        return self.head_dim_

    def theta(self, kind: str) -> float:
        return self.rope_theta if kind == FULL else self.sliding_rope_theta

    def dense_ffn(self, layer: int) -> bool:
        return self.mlp_layer_types[layer] == DENSE

    # ---- sizes
    def _attn_params(self, kind: str) -> int:
        h, d = self.hidden_size, self.head_dim_
        nq = self.heads(kind)
        return (2 * h * nq * d + 2 * h * self.num_kv_heads * d
                + (h * nq if self.attn_gate else 0))

    def _ffn_params(self, layer: int, active: bool) -> int:
        h, f = self.hidden_size, self.expert_width
        if self.dense_ffn(layer):
            return 3 * h * self.intermediate_size
        experts = self.num_experts_per_tok if active else self.num_experts
        return (h * self.num_experts + (0 if active else self.num_experts)
                + 3 * h * f * (experts + self.n_shared_experts))

    def _layer_params(self, active: bool) -> int:
        norms = 0 if active else 2 * self.hidden_size
        return sum(self._attn_params(kind) + self._ffn_params(i, active)
                   + norms for i, kind in enumerate(self.layers))

    def num_params(self) -> int:
        h = self.hidden_size
        return self._layer_params(False) + 2 * self.vocab_size * h + h

    def active_params(self) -> int:
        """Parameters one token multiplies, without the head (a pass
        computes it at one position)."""
        return self._layer_params(True)


def attention_kinds(cfg: LagunaConfig) -> tuple:
    """((layers, window or None, query heads), ...): the kinds of
    attention layer a pass runs, each with ITS heads (serve/llm/engine.py:
    PassCost prices a pass by the (layer, head) pairs each kind's flash
    calls visit)."""
    return ((cfg.n_full_layers, None, cfg.heads(FULL)),
            (cfg.n_window_layers, cfg.sliding_window, cfg.heads(SLIDING)))


def pass_cost_ratios(cfg: LagunaConfig) -> tuple:
    """(weights a prefill pass reads, scores a (query, key) pair makes a
    layer and head x the (layer, head)s of both kinds), each over the
    parameters a token multiplies (serve/llm/engine.py: PassCost). A pass
    reads every expert and a token multiplies `num_experts_per_tok`."""
    active = cfg.active_params()
    embed_head = 2 * cfg.vocab_size * cfg.hidden_size
    pairs = sum(layers * heads for layers, _, heads in attention_kinds(cfg))
    return ((cfg.num_params() - embed_head) / active, pairs / active)


# ---------------------------------------------------------------- registry
_ROUTER = dict(norm_topk_prob=True, moe_scoring="sigmoid",
               routed_scaling_factor=2.5, n_shared_experts=1)
CONFIGS = {
    # Laguna-XS.2 (huggingface.co/poolside/Laguna-XS.2 config.json,
    # "33B-A3B"), whole: 40 layers. One chip runs the first `num_layers`
    # (chipbench/configs/laguna-xs.2-serve.json)
    "laguna-xs.2": LagunaConfig(
        vocab_size=100352, hidden_size=2048, intermediate_size=8192,
        num_layers=40, num_heads=64, num_kv_heads=8, head_dim=128,
        max_seq_len=262144, rope_theta=500000.0, rms_norm_eps=1e-6,
        num_experts=256, num_experts_per_tok=8, moe_intermediate_size=512,
        **_ROUTER),
    # the published order's first five layers (full + dense, three sliding,
    # full); heads 6 / 8 over 2 kv heads of 16: groups of 3 and 4; a window
    # of two pages of 16; 16 experts of 32, 4 a token, and a shared one;
    # YaRN's original length inside the tests' prompts
    "tiny-laguna": LagunaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=5,
        layer_types=(FULL, SLIDING, SLIDING, SLIDING) * 2,
        num_attention_heads_per_layer=(6, 8, 8, 8) * 2,
        mlp_layer_types=(DENSE,) + (SPARSE,) * 7,
        num_heads=8, num_kv_heads=2, head_dim=16, max_seq_len=1024,
        rope_theta=500000.0, rms_norm_eps=1e-6, remat=False, num_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=32, sliding_window=32,
        rope_original_max=64, **_ROUTER),
}


def get_config(name: str, **overrides) -> LagunaConfig:
    return dataclasses.replace(CONFIGS[name], **overrides)
