"""Operations and bytes of a Mamba-1 state-space layer's selective scan
and of a hybrid model's decode step, from the tokens and rows the engine's
dispatch records report. The yardstick's own: counted by REAL prompt
tokens and LIVE decode rows, never by a length bucket's padding or by the
slot set, so a roofline share built on them cannot pass 100% while the
program computes at least what was asked.

The scan, for a token and a layer (d = d_inner channels, N = d_state):
    h = exp(delta (x) A) * h + (delta * x) (x) B;   y = h . C + D * x
Per state element: delta*A, exp, *h, (delta*x)*B, +, *C, + into y: 7,
and the 2 of D*x and the gate a channel, rounded to the issue's 9 a state
element (the published kernels count the same): 9 * d * N a token.
Bytes a token: x, delta, z (the gate) in and y out, d each at the
activations' 2 bytes; B and C, N each at 4 bytes. The state itself stays on
the chip for a whole row: its one read and one write a row are left out.
"""

from __future__ import annotations


def scan_ops(tokens: int, d_inner: int, d_state: int) -> float:
    return 9.0 * d_inner * d_state * tokens


def scan_bytes(tokens: int, d_inner: int, d_state: int,
               act_bytes: int = 2) -> float:
    return float(4 * act_bytes * d_inner + 2 * 4 * d_state) * tokens


def decode_step_bytes(weight_bytes: int, live_rows: int,
                      state_bytes_row: int, kv_tokens: int,
                      kv_bytes_token: int) -> float:
    """The least a fused decode step moves: every weight once, each LIVE
    row's recurrent state read and written once, each live row's KV tokens
    read once. Dead slots, activations and the new token's KV write are
    left out (they can only make the true least larger)."""
    return float(weight_bytes + 2 * live_rows * state_bytes_row
                 + kv_tokens * kv_bytes_token)


def hybrid_weight_bytes(pub: dict, bytes_per_el: int = 2) -> int:
    """Bytes of the weights a decode step of a Jamba-family model reads,
    from the published keys: Mamba layers, attention layers, every layer's
    FFN, and the embedding once (it is also the head where tied; twice
    where not). Norm scales, biases, conv, A_log and D are counted too."""
    h, f = pub["hidden_size"], pub["intermediate_size"]
    d = pub["mamba_expand"] * h
    n, r, k = pub["mamba_d_state"], pub["mamba_dt_rank"], pub["mamba_d_conv"]
    nq, nkv = pub["num_attention_heads"], pub["num_key_value_heads"]
    hd = pub.get("head_dim") or h // nq
    layers = pub["num_hidden_layers"]
    n_attn = layers // pub["attn_layer_period"]
    mixer = (h * 2 * d + d * (r + 2 * n) + r * d + d * h
             + k * d + 2 * d + n * d + d + r + 2 * n)
    attn = h * hd * (nq + 2 * nkv) + nq * hd * h
    mlp = 3 * h * f + 2 * h
    embed = pub["vocab_size"] * h * (1 if pub.get("tie_word_embeddings")
                                     else 2)
    return bytes_per_el * ((layers - n_attn) * (mixer + mlp)
                           + n_attn * (attn + mlp) + embed + h)
