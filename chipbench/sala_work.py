"""Operations and bytes of a MiniCPM-SALA-family model's mixers and of
its decode step and prefill pass, from the tokens and rows the engine's
dispatch records report and the configuration's published keys. The
yardstick's own (nothing imported from the program): counted by REAL
prompt tokens, LIVE decode rows and the keys the selection rule SELECTS,
never by a bucket's padding, the slot set or the whole context, so a
roofline share built on them cannot pass 100% while the program computes
at least what was asked.

The selection's count is a function of the query's position alone (the
rule: the first `init_blocks` blocks, the blocks of the last `window_size`
positions, the `topk` best of the rest; every key under `dense_len`), so
the keys a query attends need no counter from the device.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"


def layer_counts(pub: Dict[str, Any]) -> Dict[str, int]:
    """How many of the KEPT layers are of each kind."""
    kept = pub.get("kept_layers") or range(pub["num_hidden_layers"])
    kinds = [pub["mixer_types"][i] for i in kept]
    return {LIGHTNING: kinds.count(LIGHTNING), SPARSE: kinds.count(SPARSE)}


def keys_attended(t, sc: Dict[str, int]) -> np.ndarray:
    """Keys a query at position t attends to, a kv-head group."""
    t = np.asarray(t, np.int64)
    bs, win = sc["block_size"], sc["window_size"]
    own = t // bs
    w0 = np.maximum(t - (win - 1), 0) // bs
    forced = np.minimum(sc["init_blocks"], w0) + (own - w0 + 1)
    rest = np.minimum(np.maximum(w0 - sc["init_blocks"], 0), sc["topk"])
    sparse = (forced + rest - 1) * bs + t % bs + 1
    return np.where(t < sc["dense_len"], t + 1, sparse)


def kernels_scored(t, sc: Dict[str, int]) -> np.ndarray:
    """Compressed keys a query at position t scores, a kv-head group."""
    t = np.asarray(t, np.int64)
    n = np.maximum((t + 1 - sc["kernel_size"]) // sc["kernel_stride"] + 1, 0)
    return np.where(t < sc["dense_len"], 0, n)


def layer_params(pub: Dict[str, Any]) -> Dict[str, int]:
    """Matrix and norm parameters of one layer of each kind."""
    h, f = pub["hidden_size"], pub["intermediate_size"]
    mlp = 3 * h * f + 2 * h
    ld = pub["lightning_nh"] * pub["lightning_head_dim"]
    nq, g, d = (pub["num_attention_heads"], pub["num_key_value_heads"],
                pub["head_dim"])
    return {LIGHTNING: 5 * h * ld + 3 * pub["lightning_head_dim"] + mlp,
            SPARSE: 3 * h * nq * d + 2 * h * g * d + 2 * d + mlp}


def decode_weight_bytes(pub: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """Bytes of the weights a decode step reads: every kept layer, the
    final norm and the head once (the head is untied; of the embedding a
    step reads a row a live slot, left out)."""
    n, per = layer_counts(pub), layer_params(pub)
    h = pub["hidden_size"]
    return bytes_per_el * (n[LIGHTNING] * per[LIGHTNING]
                           + n[SPARSE] * per[SPARSE]
                           + h * pub["vocab_size"] + h)


def lightning_state_bytes_row(pub: Dict[str, Any]) -> int:
    """One live row's lightning state, read or written once, all kept
    lightning layers (float32 [H, d, d] a layer)."""
    return (layer_counts(pub)[LIGHTNING] * pub["lightning_nh"]
            * pub["lightning_head_dim"] ** 2 * 4)


def kv_bytes_token(pub: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """K and V of one token, one sparse layer, one kv head."""
    return 2 * pub["head_dim"] * bytes_per_el


def decode_step_bytes(pub: Dict[str, Any], positions) -> float:
    """The least a decode step moves for live rows at `positions` (the
    query positions, one a live row): every weight once, each live row's
    lightning state read and written once, and in each sparse layer and
    kv head the SELECTED keys and values and the compressed keys scored."""
    t = np.asarray(positions, np.int64)
    sc = pub["sparse_config"]
    per = layer_counts(pub)[SPARSE] * pub["num_key_value_heads"]
    selected = int(keys_attended(t, sc).sum()) * kv_bytes_token(pub)
    scored = int(kernels_scored(t, sc).sum()) * pub["head_dim"] * 2
    return float(decode_weight_bytes(pub)
                 + 2 * len(t) * lightning_state_bytes_row(pub)
                 + per * (selected + scored))


def sparse_decode_bytes(pub: Dict[str, Any], positions) -> float:
    """The selected keys and values alone (what the paged-decode kernel
    reads), all sparse layers and kv heads."""
    t = np.asarray(positions, np.int64)
    per = layer_counts(pub)[SPARSE] * pub["num_key_value_heads"]
    return float(per * int(keys_attended(t, pub["sparse_config"]).sum())
                 * kv_bytes_token(pub))


def lightning_prefill_bytes(tokens: int, pub: Dict[str, Any],
                            act_bytes: int = 2) -> float:
    """HBM bytes of a lightning layer's chunked mixer for `tokens` real
    (token, layer)s: q, k, v and the gate in, o out, at the activations'
    bytes. The state stays on the chip for a row: left out."""
    return float(5 * pub["lightning_nh"] * pub["lightning_head_dim"]
                 * act_bytes * tokens)


def sparse_prefill_ops(positions, pub: Dict[str, Any]) -> float:
    """Operations of the REAL query-key pairs of the sparse layers'
    attention for queries at `positions`, all sparse layers: QK^T and PV,
    2 * d each a pair and query head."""
    t = np.asarray(positions, np.int64)
    pairs = int(keys_attended(t, pub["sparse_config"]).sum())
    return float(layer_counts(pub)[SPARSE] * pairs * 4 * pub["head_dim"]
                 * pub["num_attention_heads"])


def pass_ops(positions, final: bool, pub: Dict[str, Any]) -> float:
    """Operations a prefill row needs for real tokens at `positions`: 2 a
    matrix parameter and token through the kept layers, the head at ONE
    position where the pass is a prompt's last, the lightning layers'
    state products (4 * d a head and token: k^T v and q S) and the sparse
    layers' real query-key pairs."""
    t = np.asarray(positions, np.int64)
    n, per = layer_counts(pub), layer_params(pub)
    matmul = 2.0 * len(t) * (n[LIGHTNING] * per[LIGHTNING]
                             + n[SPARSE] * per[SPARSE])
    head = 2.0 * pub["hidden_size"] * pub["vocab_size"] if final else 0.0
    d = pub["lightning_head_dim"]
    state = 4.0 * d * d * pub["lightning_nh"] * n[LIGHTNING] * len(t)
    return matmul + head + state + sparse_prefill_ops(t, pub)
