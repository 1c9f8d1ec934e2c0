"""Bytes and operations of SDAR's serving programs (a decoder of
sparse-expert layers that generates by diffusion over blocks), from the
published keys and what the engine's `engine.dispatch` records report. The
yardstick's own: counted by what a forward pass MUST read (every weight
that is not an expert's once, the experts that real tokens touched, each
live row's context keys and values once a layer), never by padded rows or
by all E experts, so a roofline share built on them cannot pass 100% while
the program computes at least what was asked.

A block program runs `block_passes` forward passes (the settling one
counted) over the slot set; every pass reads the same weights again: the
chip has nowhere to keep 8 GB between two passes.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench import moe_work

BYTES = 2     # bfloat16


def head_dim(pub: Dict[str, Any]) -> int:
    return pub.get("head_dim") or (pub["hidden_size"]
                                   // pub["num_attention_heads"])


def layer_dense_bytes(pub: Dict[str, Any]) -> int:
    """One layer's weights that every pass reads whatever the routing: q,
    k, v and o projections (no bias), the two head-dim norms, the two
    layer norms, and the router (float32)."""
    h, d = pub["hidden_size"], head_dim(pub)
    nq, nkv = pub["num_attention_heads"], pub["num_key_value_heads"]
    attn = h * d * (nq + 2 * nkv) + nq * d * h
    return BYTES * (attn + 2 * d + 2 * h) + 4 * h * pub["num_experts"]


def expert_bytes(pub: Dict[str, Any]) -> int:
    """One expert's three h x f matrices."""
    return BYTES * 3 * pub["hidden_size"] * pub["moe_intermediate_size"]


def head_bytes(pub: Dict[str, Any]) -> int:
    """The final norm and the lm head (untied; the embedding is gathered
    by row: `tokens` rows of h, counted with the pass)."""
    return BYTES * (pub["hidden_size"] * pub["vocab_size"]
                    + pub["hidden_size"])


def kv_token_bytes(pub: Dict[str, Any]) -> int:
    """K and V of one token in one layer."""
    return BYTES * 2 * pub["num_key_value_heads"] * head_dim(pub)


def forward_bytes(pub: Dict[str, Any], passes: int, experts_touched: int,
                  ctx_tokens: int, tokens: int) -> float:
    """The least a program of `passes` forward passes reads: each pass the
    dense weights of every layer and the head, `tokens` embedding rows and
    every layer's keys and values of the live rows' `ctx_tokens` (summed
    over the rows); `experts_touched` experts in all (summed over layers
    AND passes, as the records report them)."""
    layers = pub["num_hidden_layers"]
    per_pass = (layers * layer_dense_bytes(pub) + head_bytes(pub)
                + BYTES * pub["hidden_size"] * tokens
                + layers * kv_token_bytes(pub) * ctx_tokens)
    return float(passes * per_pass + experts_touched * expert_bytes(pub))


def gmm_work(pub: Dict[str, Any], assignments: int,
             experts_touched: int) -> Dict[str, float]:
    """The grouped matmuls' operations and bytes for `assignments` real
    (token, expert) pairs on `experts_touched` experts, at the EXPERT's
    width (`moe_intermediate_size`, not the dense `intermediate_size`
    that no layer of this model uses)."""
    h, f = pub["hidden_size"], pub["moe_intermediate_size"]
    return {"ops": moe_work.gmm_ops(assignments, h, f),
            "bytes": moe_work.gmm_bytes(assignments, experts_touched, h, f)}


def block_attn_bytes(pub: Dict[str, Any], passes: int,
                     ctx_tokens: int, rows: int, block: int) -> float:
    """What the block step's attention must move in `passes` passes: every
    layer's keys and values of the live rows' context (the block's own
    included) once a pass, and each row's block of queries in and out."""
    q = 2 * rows * block * pub["num_attention_heads"] * head_dim(pub) * BYTES
    return float(passes * pub["num_hidden_layers"]
                 * (kv_token_bytes(pub) * ctx_tokens + q))
