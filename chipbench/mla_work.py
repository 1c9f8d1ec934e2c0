"""Operations and bytes of a latent-attention (MLA) model with one chip's
share of its routed experts: of its two attention kernels, of a prefill
pass and of a decode step, from the tokens, rows, context chunks and
routing counts the engine's dispatch records report and the
configuration's published keys. The yardstick's own (nothing imported from
the program): counted by REAL prompt tokens, LIVE decode rows, REAL (query,
key) pairs, the held experts' REAL assignments and the experts they TOUCHED,
never by a bucket's padding, the slot set, the block table's width or all
the held experts, so a roofline share built on them cannot pass 100% while
the program computes at least what was asked.

A latent is counted at its own width (`kv_lora_rank + qk_rope_head_dim`,
576 values): a pool that pads its rows to whole lane tiles reads more, and
shows as a lower share.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

from chipbench import moe_work

def latent_width(pub: Dict[str, Any]) -> int:
    return pub["kv_lora_rank"] + pub["qk_rope_head_dim"]


def attn_params(pub: Dict[str, Any]) -> int:
    """Matrix parameters of one layer's attention."""
    h, nh = pub["hidden_size"], pub["num_attention_heads"]
    dn, dr, dv = (pub["qk_nope_head_dim"], pub["qk_rope_head_dim"],
                  pub["v_head_dim"])
    ql, r = pub["q_lora_rank"], pub["kv_lora_rank"]
    return (h * ql + ql * nh * (dn + dr) + h * (r + dr)
            + r * nh * (dn + dv) + nh * dv * h)


def layer_counts(pub: Dict[str, Any]) -> Dict[str, int]:
    dense = pub["first_k_dense_replace"]
    return {"dense": dense, "moe": pub["num_hidden_layers"] - dense}


def token_params(pub: Dict[str, Any]) -> int:
    """Matrix parameters every real token multiplies, outside the routed
    experts and the head: attention in every layer, the dense layers' FFN,
    each expert layer's router (over ALL routed experts) and shared
    expert."""
    h = pub["hidden_size"]
    n = layer_counts(pub)
    shared = 3 * h * pub["moe_intermediate_size"] * pub["n_shared_experts"]
    return (pub["num_hidden_layers"] * attn_params(pub)
            + n["dense"] * 3 * h * pub["intermediate_size"]
            + n["moe"] * (h * pub["published"]["n_routed_experts"] + shared))


# ------------------------------------------------------- the two kernels
def decode_kernel(ctx_tokens: int, k_steps: int, pub: Dict[str, Any],
                  bytes_per_el: int = 2) -> Dict[str, float]:
    """A live row's `k_steps` fused decode steps in the absorbed kernel,
    all layers: every latent of its context crosses HBM once a step and
    layer (576 values), the absorbed queries go in and the weighted
    latents come out once a head; a head multiplies 2 x (576 + 512) a
    latent (the row as key, its front as value)."""
    nh, w, r = pub["num_attention_heads"], latent_width(pub), pub["kv_lora_rank"]
    layers = pub["num_hidden_layers"]
    ctx = sum(ctx_tokens + j for j in range(k_steps))
    return {"ops": float(layers * nh * 2 * (w + r) * ctx),
            "bytes": float(layers * bytes_per_el
                           * (w * ctx + k_steps * nh * (w + r)))}


def real_pairs(q_tokens: int, end: int) -> int:
    """(query, key) pairs of a pass of `q_tokens` real tokens that ends at
    `end`: causal among themselves, every one of the context before."""
    return q_tokens * (q_tokens + 1) // 2 + q_tokens * (end - q_tokens)


def flash_ops(pairs: int, pub: Dict[str, Any]) -> float:
    """The materialised form: a pair costs 2 x (dn + dr) for its score and
    2 x dv for its value, a head and layer."""
    per = 2 * (pub["qk_nope_head_dim"] + pub["qk_rope_head_dim"]
               + pub["v_head_dim"])
    return float(pub["num_hidden_layers"] * pub["num_attention_heads"]
                 * per * pairs)


# ------------------------------------------------------------- the passes
def chunk_tokens(n_chunks: int, pub: Dict[str, Any]) -> int:
    """Context tokens in the first `n_chunks` static chunks of the block
    table: every chunk is `engine_facts.ctx_chunk_tokens` (the program's
    ops/paged_attention.py: LATENT_CTX_CHUNK, stated in the configuration)
    but the table's last."""
    eng = pub["engine"]
    table = eng["max_model_len"] // eng["page_size"] * eng["page_size"]
    return min(n_chunks * pub["engine_facts"]["ctx_chunk_tokens"], table)


def pass_ops(q_tokens: int, end: int, n_chunks: int, held_assignments: float,
             pub: Dict[str, Any]) -> float:
    """One row's prefill pass: 2 operations a matrix parameter and REAL
    token outside the routed experts, the held experts' real assignments
    at the expert's width, `W_kvb` over the context tokens whose chunks
    the pass materialised, and the real pairs. (The head, one row a final
    pass, is 0.3 GFLOP of some 10,000 and is left out: the record does not
    say which pass is final.)"""
    h, f = pub["hidden_size"], pub["moe_intermediate_size"]
    kvb = (pub["kv_lora_rank"] * pub["num_attention_heads"]
           * (pub["qk_nope_head_dim"] + pub["v_head_dim"]))
    return (2.0 * token_params(pub) * q_tokens
            + moe_work.gmm_ops(held_assignments, h, f)
            + 2.0 * kvb * pub["num_hidden_layers"]
            * chunk_tokens(n_chunks, pub)
            + flash_ops(real_pairs(q_tokens, end), pub))


def decode_weight_bytes(pub: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """Weights every decode step reads whatever the routing: `token_params`
    and the head over the held vocabulary rows (of the embedding a step
    reads a row a live slot: left out)."""
    return bytes_per_el * (token_params(pub)
                           + pub["hidden_size"] * pub["vocab_size"])


def decode_step_bytes(pub: Dict[str, Any], live_ctx: Sequence[int],
                      experts_touched: float, bytes_per_el: int = 2) -> float:
    """The least one decode step moves: the weights above once, the routed
    experts its live rows TOUCHED (3 h f each), each live row's latents
    once a layer."""
    h, f = pub["hidden_size"], pub["moe_intermediate_size"]
    return (decode_weight_bytes(pub, bytes_per_el)
            + bytes_per_el * 3.0 * h * f * experts_touched
            + bytes_per_el * latent_width(pub) * pub["num_hidden_layers"]
            * float(sum(live_ctx)))


def gmm_work(pub: Dict[str, Any], assignments: float,
             touched: float) -> Dict[str, float]:
    """`moe_work`'s arithmetic at the expert's width."""
    h, f = pub["hidden_size"], pub["moe_intermediate_size"]
    return {"ops": moe_work.gmm_ops(assignments, h, f),
            "bytes": moe_work.gmm_bytes(assignments, touched, h, f)}
