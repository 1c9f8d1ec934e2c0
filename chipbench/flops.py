"""Operations and bytes the algorithm needs, from static shapes. These are
the yardstick's: no program code is asked how much work it did.

Convention: one multiply-add = 2 operations. Recomputed operations
(remat, the flash backward's second pass over QK^T) are NOT counted: a
roofline or MFU share says how close the chip came to the least work the
mathematics needs.
"""

from __future__ import annotations

from typing import Any, Dict


def causal_pairs(sq: int, sk: int) -> int:
    """(query, key) pairs a causal mask keeps, queries being the LAST sq
    positions of sk: query i sees keys 0 .. sk - sq + i."""
    return sq * (sk - sq) + sq * (sq + 1) // 2


def flash_fwd(b: int, sq: int, sk: int, hq: int, hkv: int, d: int,
              causal: bool = True, bytes_per_el: int = 2) -> Dict[str, float]:
    """Forward attention: QK^T and PV, 2 matmuls of 2*d ops per kept pair
    and head. Bytes: Q and O once per q head, K and V once per kv head (the
    least traffic: every tensor crosses HBM once)."""
    pairs = causal_pairs(sq, sk) if causal else sq * sk
    ops = 2 * 2 * d * pairs * hq * b
    nbytes = bytes_per_el * b * d * (2 * sq * hq + 2 * sk * hkv)
    return {"ops": float(ops), "bytes": float(nbytes)}


def flash_bwd(b: int, sq: int, sk: int, hq: int, hkv: int, d: int,
              causal: bool = True, bytes_per_el: int = 2) -> Dict[str, float]:
    """Backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q — four
    matmuls (the recomputation of QK^T is not counted). Bytes: read Q, K,
    V, O, dO once, write dQ, dK, dV once."""
    pairs = causal_pairs(sq, sk) if causal else sq * sk
    ops = 4 * 2 * d * pairs * hq * b
    nbytes = bytes_per_el * b * d * (4 * sq * hq + 4 * sk * hkv)
    return {"ops": float(ops), "bytes": float(nbytes)}


def roofline_seconds(work: Dict[str, float], peaks: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """The least time the chip could take, and which peak sets it."""
    t_ops = work["ops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes),
            "bound": "compute" if t_ops >= t_bytes else "memory",
            "t_ops": t_ops, "t_bytes": t_bytes}


def dense_decoder_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameter counts of a Llama/Mistral-style decoder from its published
    keys (HF names)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // nq
    layer = h * d * (nq + 2 * nkv) + nq * d * h + 3 * h * f + 2 * h
    return {"layer": layer, "layers": layer * cfg["num_hidden_layers"],
            "embed": cfg["vocab_size"] * h, "head": cfg["vocab_size"] * h,
            "final_norm": h}


def train_ops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward + backward operations per trained token: 6 per parameter
    that a token multiplies (all but the input embedding, which is a
    lookup) plus causal attention (forward 2 matmuls, backward 4, over the
    mean number of keys a query sees)."""
    p = dense_decoder_params(cfg)
    matmul_params = p["layers"] + p["head"]
    nq = cfg["num_attention_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // nq
    keys_mean = (seq + 1) / 2.0
    attn = (2 + 4) * 2 * d * nq * keys_mean * cfg["num_hidden_layers"]
    return 6.0 * matmul_params + attn
