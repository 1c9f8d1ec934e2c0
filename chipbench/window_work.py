"""Operations and bytes of a decoder whose attention layers are of two
kinds, sliding-window and full (`layer_types`), with a sparse expert FFN in
every layer: of each layer kind's two kernels, of a prefill pass and of a
decode step, from the tokens, rows and routing counts the engine's dispatch
records report and the configuration's published keys. The yardstick's own
(nothing imported from the program): counted by REAL prompt tokens, LIVE
decode rows, the (query, key) pairs INSIDE a layer kind's mask, the real
assignments and the experts they TOUCHED, never by a bucket's padding, the
slot set, the block table's width, the tiles a kernel visits or all the
experts, so a roofline share built on them cannot pass 100% while the
program computes at least what was asked.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

from chipbench import moe_work

SLIDING, FULL = "sliding_attention", "full_attention"


def layer_counts(pub: Dict[str, Any]) -> Dict[str, int]:
    kinds = pub["layer_types"][:pub["num_hidden_layers"]]
    return {SLIDING: kinds.count(SLIDING), FULL: kinds.count(FULL)}


def kv_bytes_token(pub: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """A token's keys and values, a layer."""
    return 2 * pub["num_key_value_heads"] * pub["head_dim"] * bytes_per_el


def attn_params(pub: Dict[str, Any]) -> int:
    """Matrix parameters of one layer's attention (either kind)."""
    h, d = pub["hidden_size"], pub["head_dim"]
    nq, nkv = pub["num_attention_heads"], pub["num_key_value_heads"]
    return h * d * (nq + 2 * nkv) + nq * d * h


def token_params(pub: Dict[str, Any]) -> int:
    """Matrix parameters every real token multiplies outside the experts
    and the head: attention and the router, every layer."""
    return pub["num_hidden_layers"] * (
        attn_params(pub) + pub["hidden_size"] * pub["num_experts"])


# ------------------------------------------------------ keys, pairs, masks
def window_keys(ctx_tokens: int, k_steps: int, window: int) -> int:
    """Keys a sliding layer's decode reads for one live row over `k_steps`
    fused steps whose first sees `ctx_tokens` (the pending token among
    them): min(context, window) a step."""
    return sum(min(ctx_tokens + j, window) for j in range(k_steps))


def full_pairs(q_tokens: int, end: int) -> int:
    """(query, key) pairs of a pass of `q_tokens` real tokens that ends at
    `end` under the causal mask."""
    return q_tokens * (q_tokens + 1) // 2 + q_tokens * (end - q_tokens)


def window_pairs(q_tokens: int, end: int, window: int) -> int:
    """The same pass's pairs INSIDE the band: the query at position p sees
    min(p + 1, window) keys."""
    first = end - q_tokens
    # positions first .. end - 1; those under the window see p + 1 keys
    under = max(0, min(end, window) - first)
    return (under * (2 * first + under + 1) // 2
            + (q_tokens - under) * window)


def attention_ops(pairs: int, layers: int, pub: Dict[str, Any]) -> float:
    """A pair costs 2 x d for its score and 2 x d for its value, a query
    head and layer."""
    return float(layers * pub["num_attention_heads"] * 4 * pub["head_dim"]
                 * pairs)


# ------------------------------------------------------- the four kernels
def _decode_kernel_work(keys: int, k_steps: int, layers: int,
                        pub: Dict[str, Any],
                        bytes_per_el: int) -> Dict[str, float]:
    """`keys` keys and values cross HBM once a layer, the queries of
    `k_steps` steps go in and the outputs come out once a head."""
    nq, d = pub["num_attention_heads"], pub["head_dim"]
    return {"ops": attention_ops(keys, layers, pub),
            "bytes": float(layers * (kv_bytes_token(pub, bytes_per_el) * keys
                                     + k_steps * 2 * nq * d * bytes_per_el))}


def window_decode_kernel(ctx_tokens: int, k_steps: int, pub: Dict[str, Any],
                         bytes_per_el: int = 2) -> Dict[str, float]:
    """A live row's `k_steps` fused steps in the sliding layers' decode
    kernel, all of them: the window's real keys and values a step."""
    return _decode_kernel_work(
        window_keys(ctx_tokens, k_steps, pub["sliding_window"]), k_steps,
        layer_counts(pub)[SLIDING], pub, bytes_per_el)


def full_decode_kernel(ctx_tokens: int, k_steps: int, pub: Dict[str, Any],
                       bytes_per_el: int = 2) -> Dict[str, float]:
    """The same row and steps in the full layers' decode kernel: the whole
    context a step."""
    return _decode_kernel_work(
        sum(ctx_tokens + j for j in range(k_steps)), k_steps,
        layer_counts(pub)[FULL], pub, bytes_per_el)


def window_flash_ops(rows: Sequence, pub: Dict[str, Any]) -> float:
    """The sliding layers' flash calls of a prefill dispatch whose real
    rows are (request, q_tokens, end): the pairs inside the band."""
    return attention_ops(
        sum(window_pairs(q, end, pub["sliding_window"])
            for _, q, end in rows), layer_counts(pub)[SLIDING], pub)


def full_flash_ops(rows: Sequence, pub: Dict[str, Any]) -> float:
    """The full layers' flash calls of the same dispatch (own tokens, then
    the context, in one call or in chunks): the pairs under the causal
    mask."""
    return attention_ops(sum(full_pairs(q, end) for _, q, end in rows),
                         layer_counts(pub)[FULL], pub)


# ------------------------------------------------------------- the passes
def pass_ops(q_tokens: int, end: int, assignments: float,
             pub: Dict[str, Any]) -> float:
    """One row's prefill pass: 2 operations a matrix parameter and REAL
    token outside the experts, the real assignments at the expert's width,
    and each layer kind's real pairs. (The head, one row a final pass, is
    left out: the record does not say which pass is final.)"""
    n = layer_counts(pub)
    return (2.0 * token_params(pub) * q_tokens
            + moe_work.gmm_ops(assignments, pub["hidden_size"],
                               pub["moe_intermediate_size"])
            + attention_ops(full_pairs(q_tokens, end), n[FULL], pub)
            + attention_ops(window_pairs(q_tokens, end,
                                         pub["sliding_window"]),
                            n[SLIDING], pub))


def decode_weight_bytes(pub: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """Weights every decode step reads whatever the routing:
    `token_params` and the head (of the embedding a step reads a row a
    live slot: left out)."""
    return bytes_per_el * (token_params(pub)
                           + pub["hidden_size"] * pub["vocab_size"])


def decode_step_bytes(pub: Dict[str, Any], live_ctx: Sequence[int],
                      experts_touched: float, bytes_per_el: int = 2) -> float:
    """The least one decode step moves: the weights above once, the
    experts its live rows TOUCHED (3 h f each), each live row's keys and
    values once a layer: its context in a full layer, min(context, window)
    in a sliding one."""
    h, f = pub["hidden_size"], pub["moe_intermediate_size"]
    n, w = layer_counts(pub), pub["sliding_window"]
    tokens = (n[FULL] * float(sum(live_ctx))
              + n[SLIDING] * float(sum(min(c, w) for c in live_ctx)))
    return (decode_weight_bytes(pub, bytes_per_el)
            + bytes_per_el * 3.0 * h * f * experts_touched
            + kv_bytes_token(pub, bytes_per_el) * tokens)


def gmm_work(pub: Dict[str, Any], assignments: float,
             touched: float) -> Dict[str, float]:
    """`moe_work`'s arithmetic at the expert's width."""
    h, f = pub["hidden_size"], pub["moe_intermediate_size"]
    return {"ops": moe_work.gmm_ops(assignments, h, f),
            "bytes": moe_work.gmm_bytes(assignments, touched, h, f)}
