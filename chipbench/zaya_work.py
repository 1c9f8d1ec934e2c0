"""Operations and bytes of a ZAYA1-family decoder: attention inside a
compressed latent (CCA: q, k, v of 8 / 2 / 2 heads of 128, two short
convolutions in time, a tail of its last inputs a decode slot beside the
pages) and one expert of 16 a token chosen by an MLP router. Of the two
attention kernels, of the grouped matmuls, of a prefill program and of a
decode step, from the tokens, rows and routing counts the engine's dispatch
records report and the configuration's published keys. The yardstick's own
(nothing imported from the program): counted by REAL prompt tokens, LIVE
decode rows, the (query, key) pairs under the causal mask, the real
assignments and the experts they TOUCHED; a page's keys and values cross HBM
ONCE for the 4 query heads of their kv head's group; never by a bucket's
padding, the slot set, the block table's width, the tiles a kernel visits or
all the experts: a roofline share built on them cannot pass 100% while the
program computes at least what was asked.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

from chipbench import moe_work
from chipbench.window_work import full_pairs, kv_bytes_token


def heads(pub: Dict[str, Any]) -> int:
    """The heads the convolutions run over: q's and k's."""
    return pub["num_attention_heads"] + pub["num_key_value_heads"]


def attn_params(pub: Dict[str, Any]) -> int:
    """Matrix parameters of one CCA layer: [W_q | W_k | W_v1 | W_v2] (the
    value's two halves are one head each), the second convolution's taps
    (a [head_dim, head_dim] a tap and head) and W_o from the latent UP to
    the hidden size. (The depthwise taps are 2560 values: left out.)"""
    h, d = pub["hidden_size"], pub["head_dim"]
    return (h * d * (heads(pub) + pub["num_key_value_heads"])
            + pub["cca_time1"] * heads(pub) * d * d
            + pub["num_attention_heads"] * d * h)


def router_params(pub: Dict[str, Any]) -> int:
    """The router's matrices: down, two hidden layers, out."""
    h, rh = pub["hidden_size"], pub["router_hidden_size"]
    return h * rh + 2 * rh * rh + rh * pub["num_experts"]


def token_params(pub: Dict[str, Any]) -> int:
    """Matrix parameters every real token multiplies outside the routed
    experts and the head, all layers."""
    return pub["num_hidden_layers"] * (attn_params(pub) + router_params(pub))


def tail_values(pub: Dict[str, Any]) -> int:
    """Values a layer keeps a decode slot: cca_time0 + cca_time1 - 2 rows
    of [q~ | k~] and one shifted value half."""
    rows = pub["cca_time0"] + pub["cca_time1"] - 2
    return rows * heads(pub) * pub["head_dim"] + pub["head_dim"]


def tail_bytes_row(pub: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """One sequence's tails, all layers, read or written once."""
    return pub["num_hidden_layers"] * tail_values(pub) * bytes_per_el


def attention_ops(pairs: int, pub: Dict[str, Any]) -> float:
    """A pair costs 2 x d for its score and 2 x d for its value, a query
    head and layer."""
    return float(pub["num_hidden_layers"] * pub["num_attention_heads"]
                 * 4 * pub["head_dim"] * pairs)


# -------------------------------------------------------- the two kernels
def decode_kernel(ctx_tokens: int, k_steps: int, pub: Dict[str, Any],
                  bytes_per_el: int = 2) -> Dict[str, float]:
    """A live row's `k_steps` fused steps in the paged-decode kernel, all
    layers: the whole context a step, its keys and values once a kv head
    (1 KB a token and layer at 2 kv heads of 128 in bf16), the queries in
    and the outputs out once a query head."""
    keys = sum(ctx_tokens + j for j in range(k_steps))
    return {"ops": attention_ops(keys, pub),
            "bytes": float(pub["num_hidden_layers"] * (
                kv_bytes_token(pub, bytes_per_el) * keys
                + k_steps * 2 * pub["num_attention_heads"] * pub["head_dim"]
                * bytes_per_el))}


def flash_ops(rows: Sequence, pub: Dict[str, Any]) -> float:
    """The flash calls of a prefill dispatch whose real rows are (request,
    q_tokens, end): the pairs under the causal mask at 8 query heads."""
    return attention_ops(sum(full_pairs(q, end) for _, q, end in rows), pub)


# ----------------------------------------------------------- the programs
def pass_ops(q_tokens: int, end: int, assignments: float,
             pub: Dict[str, Any]) -> float:
    """One row's prefill pass: 2 operations a matrix parameter and REAL
    token outside the routed experts, the real assignments at the expert's
    width, the real pairs. (The head, one row a final pass, is left out:
    the record does not say which pass is final.)"""
    return (2.0 * token_params(pub) * q_tokens
            + moe_work.gmm_ops(assignments, pub["hidden_size"],
                               pub["moe_intermediate_size"])
            + attention_ops(full_pairs(q_tokens, end), pub))


def expert_bytes(pub: Dict[str, Any], experts_touched: float,
                 bytes_per_el: int = 2) -> float:
    return (bytes_per_el * 3.0 * pub["hidden_size"]
            * pub["moe_intermediate_size"] * experts_touched)


def program_weight_bytes(pub: Dict[str, Any], experts_touched: float,
                         head: bool, bytes_per_el: int = 2) -> float:
    """Weights ONE program reads whatever its rows: `token_params`, the
    experts its tokens TOUCHED (3 h f each) and, in a decode step, the tied
    head (all 262,272 rows; of the embedding as a lookup a program reads a
    row a token: left out)."""
    return (bytes_per_el * (token_params(pub) + (
        pub["hidden_size"] * pub["vocab_size"] if head else 0))
        + expert_bytes(pub, experts_touched, bytes_per_el))


def pass_kv_bytes(q_tokens: int, end: int, pub: Dict[str, Any],
                  bytes_per_el: int = 2) -> float:
    """Keys and values one row's pass moves: its own tokens written once a
    layer, and the context it resumes behind read once a layer."""
    return (kv_bytes_token(pub, bytes_per_el) * float(
        pub["num_hidden_layers"] * end))


def decode_step_bytes(pub: Dict[str, Any], live_ctx: Sequence[int],
                      experts_touched: float, bytes_per_el: int = 2) -> float:
    """The least one decode step moves: the weights above and the head
    once, the experts its live rows TOUCHED, each live row's keys and values
    once a layer, its tail read and written in every layer."""
    return (program_weight_bytes(pub, experts_touched, True, bytes_per_el)
            + kv_bytes_token(pub, bytes_per_el) * pub["num_hidden_layers"]
            * float(sum(live_ctx))
            + 2.0 * len(live_ctx) * tail_bytes_row(pub, bytes_per_el))


def gmm_work(pub: Dict[str, Any], assignments: float,
             touched: float) -> Dict[str, float]:
    """`moe_work`'s arithmetic at the expert's width."""
    h, f = pub["hidden_size"], pub["moe_intermediate_size"]
    return {"ops": moe_work.gmm_ops(assignments, h, f),
            "bytes": moe_work.gmm_bytes(assignments, touched, h, f)}
