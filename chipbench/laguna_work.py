"""Operations and bytes of a Laguna-family decoder: sliding-window and full
attention layers whose query-head counts differ A KIND
(`num_attention_heads_per_layer`), a per-head output gate, a leading dense
FFN, then routed experts beside a shared one. Of each kind's two kernels,
of the grouped matmuls, of a prefill program and of a decode step, from the
tokens, rows and routing counts the engine's dispatch records report and
the configuration's published keys. The yardstick's own (nothing imported
from the program): counted by REAL prompt tokens, LIVE decode rows, the
(query, key) pairs INSIDE a layer kind's mask, the real assignments and the
experts they TOUCHED; a page's keys and values cross HBM ONCE for all 6 or 8
query heads of their kv head's group; never by a bucket's padding, the slot
set, the block table's width, the tiles a kernel visits or all the experts:
a roofline share built on them cannot pass 100% while the program computes
at least what was asked. The masks' pair counts are chipbench/
window_work.py's (one source).
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

from chipbench import moe_work
from chipbench.window_work import (FULL, SLIDING, full_pairs, kv_bytes_token,
                                   window_keys, window_pairs)


def held_layers(pub: Dict[str, Any]) -> List[Tuple[str, int, str]]:
    """[(attention kind, query heads, FFN kind)] of the layers that run."""
    n = pub["num_hidden_layers"]
    return list(zip(pub["layer_types"][:n],
                    pub["num_attention_heads_per_layer"][:n],
                    pub["mlp_layer_types"][:n]))


def kind_heads(pub: Dict[str, Any], kind: str) -> Tuple[int, int]:
    """(layers of the kind, query heads of each): one count a kind."""
    heads = [h for k, h, _ in held_layers(pub) if k == kind]
    if len(set(heads)) > 1:
        raise ValueError(f"{kind} layers of {sorted(set(heads))} heads")
    return len(heads), (heads[0] if heads else 0)


def sparse_layers(pub: Dict[str, Any]) -> int:
    return sum(ffn == "sparse" for _, _, ffn in held_layers(pub))


def attn_params(pub: Dict[str, Any], heads: int) -> int:
    """Matrix parameters of one attention layer of `heads` query heads: q,
    k, v, o and the gate's [hidden, heads]."""
    h, d, nkv = pub["hidden_size"], pub["head_dim"], pub[
        "num_key_value_heads"]
    return (h * d * (heads + 2 * nkv) + heads * d * h
            + (h * heads if pub.get("gating") else 0))


def token_params(pub: Dict[str, Any]) -> int:
    """Matrix parameters every real token multiplies outside the routed
    experts and the head: each layer's attention at ITS heads, a dense
    layer's FFN, a sparse layer's router and shared expert."""
    h = pub["hidden_size"]
    dense = 3 * h * pub["intermediate_size"]
    sparse = h * pub["num_experts"] + 3 * h * pub.get(
        "shared_expert_intermediate_size", 0)
    return sum(attn_params(pub, heads) + (dense if ffn == "dense" else sparse)
               for _, heads, ffn in held_layers(pub))


def attention_ops(pairs: int, layers: int, heads: int,
                  pub: Dict[str, Any]) -> float:
    """A pair costs 2 x d for its score and 2 x d for its value, a query
    head and layer."""
    return float(layers * heads * 4 * pub["head_dim"] * pairs)


# ------------------------------------------------------- the four kernels
def decode_kernel(kind: str, ctx_tokens: int, k_steps: int,
                  pub: Dict[str, Any], bytes_per_el: int = 2
                  ) -> Dict[str, float]:
    """A live row's `k_steps` fused steps in the kind's decode kernel, all
    its layers: a full layer reads the whole context a step, a sliding one
    min(context, window); the keys and values cross HBM once a kv head
    (once for the 6 or 8 query heads of its group), the queries of
    `k_steps` steps go in and the outputs come out once a query head."""
    layers, heads = kind_heads(pub, kind)
    keys = (window_keys(ctx_tokens, k_steps, pub["sliding_window"])
            if kind == SLIDING else
            sum(ctx_tokens + j for j in range(k_steps)))
    return {"ops": attention_ops(keys, layers, heads, pub),
            "bytes": float(layers * (
                kv_bytes_token(pub, bytes_per_el) * keys
                + k_steps * 2 * heads * pub["head_dim"] * bytes_per_el))}


def kind_pairs(kind: str, q_tokens: int, end: int,
               pub: Dict[str, Any]) -> int:
    """(query, key) pairs of a pass of `q_tokens` real tokens that ends at
    `end`, inside the kind's mask."""
    if kind == SLIDING:
        return window_pairs(q_tokens, end, pub["sliding_window"])
    return full_pairs(q_tokens, end)


def flash_ops(kind: str, rows: Sequence, pub: Dict[str, Any]) -> float:
    """The kind's flash calls of a prefill dispatch whose real rows are
    (request, q_tokens, end): the pairs inside its mask at its heads."""
    layers, heads = kind_heads(pub, kind)
    return attention_ops(sum(kind_pairs(kind, q, end, pub)
                             for _, q, end in rows), layers, heads, pub)


# ----------------------------------------------------------- the programs
def pass_ops(q_tokens: int, end: int, assignments: float,
             pub: Dict[str, Any]) -> float:
    """One row's prefill pass: 2 operations a matrix parameter and REAL
    token outside the routed experts, the real assignments at the expert's
    width, and each layer kind's real pairs at its heads. (The head, one
    row a final pass, is left out: the record does not say which pass is
    final.)"""
    return (2.0 * token_params(pub) * q_tokens
            + moe_work.gmm_ops(assignments, pub["hidden_size"],
                               pub["moe_intermediate_size"])
            + sum(attention_ops(kind_pairs(kind, q_tokens, end, pub),
                                *kind_heads(pub, kind), pub)
                  for kind in (FULL, SLIDING)))


def expert_bytes(pub: Dict[str, Any], experts_touched: float,
                 bytes_per_el: int = 2) -> float:
    return (bytes_per_el * 3.0 * pub["hidden_size"]
            * pub["moe_intermediate_size"] * experts_touched)


def program_weight_bytes(pub: Dict[str, Any], experts_touched: float,
                         head: bool, bytes_per_el: int = 2) -> float:
    """Weights ONE program reads whatever its rows: `token_params`, the
    experts its tokens TOUCHED (3 h f each) and, in a decode step, the
    head (of the embedding a program reads a row a token: left out)."""
    return (bytes_per_el * (token_params(pub) + (
        pub["hidden_size"] * pub["vocab_size"] if head else 0))
        + expert_bytes(pub, experts_touched, bytes_per_el))


def pass_kv_bytes(q_tokens: int, end: int, pub: Dict[str, Any],
                  bytes_per_el: int = 2) -> float:
    """Keys and values one row's pass moves: its own tokens written once a
    layer, and what it resumes behind read once: the context in a full
    layer, at most the window in a sliding one."""
    behind = end - q_tokens
    n_full, n_win = kind_heads(pub, FULL)[0], kind_heads(pub, SLIDING)[0]
    return kv_bytes_token(pub, bytes_per_el) * float(
        (n_full + n_win) * q_tokens + n_full * behind
        + n_win * min(behind, pub["sliding_window"]))


def decode_step_bytes(pub: Dict[str, Any], live_ctx: Sequence[int],
                      experts_touched: float, bytes_per_el: int = 2) -> float:
    """The least one decode step moves: the weights above and the head
    once, the experts its live rows TOUCHED, each live row's keys and
    values once a layer: its context in a full layer, min(context, window)
    in a sliding one."""
    n_full, n_win = kind_heads(pub, FULL)[0], kind_heads(pub, SLIDING)[0]
    w = pub["sliding_window"]
    tokens = (n_full * float(sum(live_ctx))
              + n_win * float(sum(min(c, w) for c in live_ctx)))
    return (program_weight_bytes(pub, experts_touched, True, bytes_per_el)
            + kv_bytes_token(pub, bytes_per_el) * tokens)


def gmm_work(pub: Dict[str, Any], assignments: float,
             touched: float) -> Dict[str, float]:
    """`moe_work`'s arithmetic at the expert's width (the shared expert is
    a dense matmul, not the grouped kernel's)."""
    h, f = pub["hidden_size"], pub["moe_intermediate_size"]
    return {"ops": moe_work.gmm_ops(assignments, h, f),
            "bytes": moe_work.gmm_bytes(assignments, touched, h, f)}
