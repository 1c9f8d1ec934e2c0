"""Operations and bytes of the serving kernels, from the context lengths
the engine's dispatch records report (what `flops.py` lacks: its counts are
for static shapes). The yardstick's own: counted by tokens, never by pages
or padded rows, so a roofline share built on them cannot pass 100% while
the kernel computes at least what was asked.
"""

from __future__ import annotations

from typing import Dict


def paged_decode(ctx_tokens: int, hq: int, hkv: int, d: int,
                 bytes_per_el: int = 2) -> Dict[str, float]:
    """One decode step of one row in one layer: a single query position
    attends to `ctx_tokens` tokens of KV. QK^T and PV are 2*d operations
    per token and q head each; every K and V element crosses HBM once, the
    query and the output once per q head."""
    ops = 2 * 2 * d * ctx_tokens * hq
    nbytes = bytes_per_el * d * (2 * ctx_tokens * hkv + 2 * hq)
    return {"ops": float(ops), "bytes": float(nbytes)}


def paged_decode_chunk(ctx_tokens: int, k_steps: int, hq: int, hkv: int,
                       d: int, bytes_per_el: int = 2) -> Dict[str, float]:
    """A row's k fused decode steps: the context grows by one a step."""
    out = {"ops": 0.0, "bytes": 0.0}
    for j in range(k_steps):
        step = paged_decode(ctx_tokens + j, hq, hkv, d, bytes_per_el)
        out["ops"] += step["ops"]
        out["bytes"] += step["bytes"]
    return out
