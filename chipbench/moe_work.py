"""Operations and bytes of the expert layer's grouped matmuls, from the
routing counts the engine's dispatch records report. The yardstick's own:
counted by REAL assignments and by experts that real tokens touched,
never by padded rows or by all E experts, so a roofline share built on
them cannot pass 100% while the kernels compute at least what was asked.

An assignment is one (token, expert) pair. Its expert applies
`W2(silu(W1 x) * W3 x)`: three h x f matmuls, two of them fused into the
first grouped matmul ([h, 2f]), the third being the second ([f, h]).
"""

from __future__ import annotations


def gmm_ops(assignments: int, h: int, f: int) -> float:
    """2 x 3 x h x f multiply-adds' operations per real assignment."""
    return 2.0 * 3 * h * f * assignments


def gmm_bytes(assignments: int, experts_touched: int, h: int, f: int,
              bytes_per_el: int = 2) -> float:
    """Every touched expert's three h x f matrices cross HBM once; an
    assignment's activations cross it once each way per grouped matmul:
    x [h] in and [2f] out of the first, [f] in and [h] out of the second."""
    weights = 3.0 * h * f * experts_touched
    activations = float(h + 2 * f + f + h) * assignments
    return bytes_per_el * (weights + activations)
