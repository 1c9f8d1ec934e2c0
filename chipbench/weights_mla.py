"""Weights from the seed for a latent-attention model with routed experts,
whose tree is a stack a RUN of like layers (`dense_layers/...` and
`layers/...`, a leading [run] axis each): `chipbench/weights.py`'s values
(matrices N(0, 1/fan_in), the embedding N(0, 0.02^2), norm scales 1 + 0.1
N(0, 1)), each run made one layer at a time so that no temporary is larger
than one layer's largest leaf (a layer's 12 experts' gate_up is 0.7 GB).

Three leaves are no plain [.., in, out] matrix: `kv_b_proj` [r, H, dn + dv]
contracts its FIRST axis (fan_in r, not H); `router_bias` [R] is the
router's selection bias, N(0, 0.05^2): sigmoid scores of a normed token
over random routers spread by about 0.2, so a bias of that size changes
some of a token's 8 choices and no weight, which is what it is for;
`lm_head` is a bare [hidden, vocab] leaf. The same arrays go to the
program and to the plain reference.
"""

from __future__ import annotations

from typing import Any

STACKED = ("dense_layers", "layers")
BIAS_STD = 0.05


def make_params(abstract: Any, seed: int):
    """abstract: the program's param tree as ShapeDtypeStructs."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def one(key, path: str, shape, dtype):
        if path.endswith("scale"):
            return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                    ).astype(dtype)
        if path.endswith("router_bias"):
            return (BIAS_STD * jax.random.normal(key, shape, jnp.float32)
                    ).astype(dtype)
        if path.startswith("embed"):
            std = 0.02
        elif path.endswith("kv_b_proj"):
            std = float(shape[0]) ** -0.5
        else:
            std = float(shape[-2]) ** -0.5
        return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)

    def build(seed_arr):
        root = jax.random.fold_in(jax.random.PRNGKey(3), seed_arr)
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            key = jax.random.fold_in(root, i)
            if name.split("/")[0] in STACKED:
                out.append(jax.lax.map(
                    lambda k, name=name, leaf=leaf: one(
                        k, name, leaf.shape[1:], leaf.dtype),
                    jax.random.split(key, leaf.shape[0])))
            else:
                out.append(one(key, name, leaf.shape, leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jnp.asarray(int(seed) & 0xFFFFFFFF, jnp.uint32))
