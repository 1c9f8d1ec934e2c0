"""Weights from the seed for a model with Mamba-1 state-space layers:
`chipbench/weights.py` for every matrix, embedding and norm scale, and
Mamba's published initialisation for the mixer's leaves that are no
matrix (state-spaces/mamba `mamba_simple.py`, as HF's `JambaMambaMixer`
keeps it), named as the program's tree names them:

    A_log       log(1..N) on every channel (decay e^-delta .. e^-(N delta))
    D           1
    dt_bias     inverse softplus of a step log-uniform in [1e-3, 1e-1]
    conv_kernel, conv_bias   uniform in +-1/sqrt(d_conv) (torch's Conv1d)

`weights.py` alone would draw these N(0, 1/fan_in) from `shape[-2]`: an
`A_log` near 0 makes every state decay by e^-1 a token at delta near 1, a
memory of three tokens, and a state carried wrongly from prefill into
decode would be forgotten before the check could see it. With the
published initialisation delta is 0.001-0.1 and the slowest state keeps
tens to hundreds of tokens. The same arrays go to the program and to the
plain reference.
"""

from __future__ import annotations

import math
from typing import Any

from chipbench import weights

SSM_LEAVES = ("A_log", "D", "dt_bias", "conv_kernel", "conv_bias")


def make_params(abstract: Any, seed: int, stacked_key: str = "layers"):
    """`weights.make_params`, then the leaves named in SSM_LEAVES made
    anew (one jitted call, the seed an argument)."""
    import jax
    import jax.numpy as jnp

    params = weights.make_params(abstract, seed, stacked_key)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [str(getattr(path[-1], "key", path[-1])) for path, _ in leaves]

    def one(key, name: str, shape, dtype, d_conv: int):
        if name == "A_log":     # [..., N, d]
            n = shape[-2]
            return jnp.broadcast_to(jnp.log(jnp.arange(
                1, n + 1, dtype=jnp.float32))[:, None], shape).astype(dtype)
        if name == "D":
            return jnp.ones(shape, dtype)
        if name == "dt_bias":
            lo, hi = math.log(1e-3), math.log(1e-1)
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                         * (hi - lo) + lo)
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        bound = d_conv ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)

    # d_conv is the conv kernel's second-to-last axis: [..., K, d]
    d_conv = next(leaf.shape[-2] for (path, leaf), name
                  in zip(leaves, names) if name == "conv_kernel")

    def build(seed_arr):
        root = jax.random.fold_in(jax.random.PRNGKey(1), seed_arr)
        return [one(jax.random.fold_in(root, i), name, leaf.shape,
                    leaf.dtype, d_conv) if name in SSM_LEAVES else None
                for i, ((_, leaf), name) in enumerate(zip(leaves, names))]

    made = jax.jit(build)(jnp.asarray(int(seed) & 0xFFFFFFFF, jnp.uint32))
    flat = [new if new is not None else old
            for new, old in zip(made, jax.tree.leaves(params))]
    return jax.tree_util.tree_unflatten(treedef, flat)
