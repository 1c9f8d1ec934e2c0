"""Weights from the seed for a model of CCA attention and MLP-routed top-1
experts, whose tree is ONE stack of layers (`layers/...`, a leading [layer]
axis): `chipbench/weights.py`'s values (matrices N(0, 1/fan_in), fan_in the
second-to-last axis; the tied embedding N(0, 0.02^2); norm scales 1 + 0.1
N(0, 1)), each leaf made one layer at a time so that no temporary is larger
than one layer's largest leaf (a layer's 16 experts' gate_up is 0.27 GB).

Leaves that are no plain [.., in, out] matrix: `conv0` [2, channels] (the
depthwise taps) comes out N(0, 1/2) and `conv1` [2, heads, 128, 128] N(0,
1/128) a tap by the same rule, so the convolved rows are of the size of the
rows they are added to; `log_tau` = ln U(8, 16) a kv head (the attention's
logit is tau cos(q, k): neither flat nor one-hot); the router's state gain
`eda` is N(0, 0.5^2) a channel (every layer's router sees the layers before
it); its balancing `bias` is N(0, 0.005^2): a fifth of the spread the
seeded router's probabilities have (1/16 +- 0.024), so that it moves the
choice where two experts are near and not everywhere. The seeded router's
probabilities are NEAR UNIFORM a token (p_e 0.04-0.1, where a trained one is
sharp), but its choices are not even over the experts: on the chip 64 rows
touch 11.6 of 16 experts a layer (PERF.md section 6, PR 58: the gelu layers'
outputs have a positive mean, so an expert's logit carries a fixed offset of
its own). The same arrays go to the program and to the plain reference.
"""

from __future__ import annotations

from typing import Any

BIAS_STD = 0.005
EDA_STD = 0.5
TAU = (8.0, 16.0)


def make_params(abstract: Any, seed: int):
    """abstract: the program's param tree as ShapeDtypeStructs."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    f32 = jnp.float32

    def one(key, path: str, shape, dtype):
        normal = jax.random.normal(key, shape, f32)
        if path.endswith("scale"):
            return (1.0 + 0.1 * normal).astype(dtype)
        if path.endswith("router/bias"):
            return (BIAS_STD * normal).astype(dtype)
        if path.endswith("router/eda"):
            return (EDA_STD * normal).astype(dtype)
        if path.endswith("log_tau"):
            return jnp.log(jax.random.uniform(key, shape, f32, *TAU)
                           ).astype(dtype)
        std = 0.02 if path.startswith("embed") else float(shape[-2]) ** -0.5
        return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)

    def build(seed_arr):
        root = jax.random.fold_in(jax.random.PRNGKey(7), seed_arr)
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            key = jax.random.fold_in(root, i)
            if name.startswith("layers/"):
                out.append(jax.lax.map(
                    lambda k, name=name, leaf=leaf: one(
                        k, name, leaf.shape[1:], leaf.dtype),
                    jax.random.split(key, leaf.shape[0])))
            else:
                out.append(one(key, name, leaf.shape, leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jnp.asarray(int(seed) & 0xFFFFFFFF, jnp.uint32))
