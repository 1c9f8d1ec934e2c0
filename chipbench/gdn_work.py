"""Operations and bytes of a model of gated-delta-net (GDN) and latent-
attention (MLA) layers with one chip's share of its routed experts: of the
delta-rule update kernel, of a decode step and of a prefill pass, from the
tokens, rows, context chunks and routing counts the engine's dispatch
records report and the configuration's published keys. The yardstick's own
(nothing imported from the program): counted by REAL prompt tokens, LIVE
decode rows, REAL (query, key) pairs, the held experts' REAL assignments and
the experts they TOUCHED, the delta rule's products at their triangular
halves, never by a bucket's padding, the slot set, the table's width or all
the held experts, so a roofline share built on them cannot pass 100% while
the program computes at least what was asked.

Which published layers this chip runs is `held.layers`; which of them are
MLA `full_attention_layers`, which have the dense FFN
`first_k_dense_replace`.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

from chipbench import mla_work, moe_work

F32 = 4


def layer_counts(pub: Dict[str, Any]) -> Dict[str, int]:
    lo, hi = pub["held"]["layers"]
    layers = range(lo, hi)
    mla = sum(l in pub["full_attention_layers"] for l in layers)
    dense = sum(l < pub["first_k_dense_replace"] for l in layers)
    return {"mla": mla, "gdn": len(layers) - mla, "dense": dense,
            "moe": len(layers) - dense}


def gdn_channels(pub: Dict[str, Any]) -> int:
    return ((2 * pub["linear_num_key_heads"] + pub["linear_num_value_heads"])
            * pub["linear_key_head_dim"])


def gdn_params(pub: Dict[str, Any]) -> int:
    """Matrix parameters of one GDN mixer: W_qkvz, W_ba, W_o."""
    h, d, nv = (pub["hidden_size"], pub["linear_key_head_dim"],
                pub["linear_num_value_heads"])
    return h * (gdn_channels(pub) + nv * d) + h * 2 * nv + nv * d * h


def mla_params(pub: Dict[str, Any]) -> int:
    """`mla_work.attn_params` and the output gate's matrix."""
    gate = (pub["hidden_size"] * pub["num_attention_heads"]
            * pub["v_head_dim"] if pub.get("gated_attention") else 0)
    return mla_work.attn_params(pub) + gate


def token_params(pub: Dict[str, Any]) -> int:
    """Matrix parameters every real token multiplies, outside the routed
    experts and the head: each layer's mixer, the dense layers' FFN, each
    expert layer's router (over ALL routed experts) and shared expert."""
    h = pub["hidden_size"]
    n = layer_counts(pub)
    shared = 3 * h * pub["moe_intermediate_size"] * pub["n_shared_experts"]
    return (n["mla"] * mla_params(pub) + n["gdn"] * gdn_params(pub)
            + n["dense"] * 3 * h * pub["intermediate_size"]
            + n["moe"] * (h * pub["published"]["n_routed_experts"] + shared))


# ----------------------------------------------------- a sequence's state
def state_bytes(pub: Dict[str, Any]) -> int:
    """One sequence's delta-rule state in ONE GDN layer, float32."""
    d = pub["linear_key_head_dim"]
    return pub["linear_num_value_heads"] * d * d * F32


def tail_bytes(pub: Dict[str, Any], bytes_per_el: int = 2) -> int:
    return ((pub["linear_conv_kernel_dim"] - 1) * gdn_channels(pub)
            * bytes_per_el)


def update_kernel(rows: int, k_steps: int, pub: Dict[str, Any]
                  ) -> Dict[str, float]:
    """`rows` live rows' `k_steps` fused decode steps in the update kernel,
    all GDN layers: a row's state crosses HBM twice a step and layer (read,
    written), its q, k, v, o once at float32 and a gate a head; a head's
    update is 7 passes over its 128 x 128 matrix (decay, the read by k and
    its sum, the written outer product and its add, the read by q and its
    sum)."""
    d, nv = pub["linear_key_head_dim"], pub["linear_num_value_heads"]
    n = rows * k_steps * layer_counts(pub)["gdn"]
    io = (4 * nv * d + 2 * nv) * F32
    return {"ops": float(n * nv * 7 * d * d),
            "bytes": float(n * (2 * state_bytes(pub) + io))}


# ------------------------------------------------------------- the passes
def gdn_chunk_ops(q_tokens: int, pub: Dict[str, Any]) -> float:
    """The chunked delta rule for `q_tokens` real tokens in ONE layer, at
    the least a chunk of C = `engine_facts.gdn_chunk` needs a token and
    value head: the lower triangles of k k^T and q k^T (D C each), the
    solved matrix times values and keys (lower-triangular: D C each), the
    in-chunk output (D C), and three products with the state (2 D^2 each).
    The solve itself (C^2 / 2 a row) is left out: a floor."""
    d, nv = pub["linear_key_head_dim"], pub["linear_num_value_heads"]
    c = pub["engine_facts"]["gdn_chunk"]
    return float(q_tokens * nv * (5 * d * c + 6 * d * d))


def pass_ops(q_tokens: int, end: int, n_chunks: int, held_assignments: float,
             pub: Dict[str, Any]) -> float:
    """One row's prefill pass: 2 operations a matrix parameter and REAL
    token outside the routed experts, the held experts' real assignments at
    the expert's width, the delta rule's chunk products in the GDN layers,
    and in the MLA layers `W_kvb` over the context tokens whose chunks the
    pass materialised and the real pairs. (The head, one row a final pass,
    is left out: the record does not say which pass is final.)"""
    h, f = pub["hidden_size"], pub["moe_intermediate_size"]
    n = layer_counts(pub)
    nh = pub["num_attention_heads"]
    kvb = (pub["kv_lora_rank"] * nh
           * (pub["qk_nope_head_dim"] + pub["v_head_dim"]))
    pair = 2 * (pub["qk_nope_head_dim"] + pub["qk_rope_head_dim"]
                + pub["v_head_dim"])
    return (2.0 * token_params(pub) * q_tokens
            + moe_work.gmm_ops(held_assignments, h, f)
            + n["gdn"] * gdn_chunk_ops(q_tokens, pub)
            + n["mla"] * (2.0 * kvb * mla_work.chunk_tokens(n_chunks, pub)
                          + float(nh * pair
                                  * mla_work.real_pairs(q_tokens, end))))


def decode_weight_bytes(pub: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """Weights every decode step reads whatever the routing: `token_params`
    and the head over the held vocabulary rows (of the embedding a step
    reads a row a live slot: left out)."""
    return bytes_per_el * (token_params(pub)
                           + pub["hidden_size"] * pub["vocab_size"])


def decode_step_bytes(pub: Dict[str, Any], live_ctx: Sequence[int],
                      experts_touched: float, bytes_per_el: int = 2) -> float:
    """The least one decode step moves: the weights above once, the routed
    experts its live rows TOUCHED (3 h f each), each live row's delta-rule
    state read and written and its conv tail read and written in every GDN
    layer, its latents (576 values a token) once in every MLA layer."""
    h, f = pub["hidden_size"], pub["moe_intermediate_size"]
    n = layer_counts(pub)
    rows = len(live_ctx)
    return (decode_weight_bytes(pub, bytes_per_el)
            + bytes_per_el * 3.0 * h * f * experts_touched
            + 2.0 * n["gdn"] * rows * (state_bytes(pub)
                                       + tail_bytes(pub, bytes_per_el))
            + bytes_per_el * mla_work.latent_width(pub) * n["mla"]
            * float(sum(live_ctx)))
