"""Weights from the seed, made on the device in one jitted call, in the type
they are served or trained in.

The benchmark makes the weights, not the program: the same arrays go to the
system under test (as its `params`) and to the plain reference (upcast
there), so the reference takes nothing the program has made. The tree's
layout is the program's input format and is read with `jax.eval_shape`.

Values: matrices ~ N(0, 1/fan_in) (fan_in = the second-to-last axis),
the embedding ~ N(0, 0.02^2), norm scales 1 + 0.1 N(0, 1) (not all ones,
so that a reference that forgot a scale would disagree).
"""

from __future__ import annotations

from typing import Any


def make_params(abstract: Any, seed: int, stacked_key: str = "layers"):
    """abstract: a pytree of ShapeDtypeStruct (the program's param tree).
    Leaves under `stacked_key` carry a leading layer axis and are made one
    layer at a time (lax.map), so no temporary is larger than one layer's
    largest matrix."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def one(key, path: str, shape, dtype):
        if path.endswith("scale"):
            return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                    ).astype(dtype)
        std = 0.02 if path.startswith("embed") else float(shape[-2]) ** -0.5
        return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)

    def build(seed_arr):
        root = jax.random.fold_in(jax.random.PRNGKey(0), seed_arr)
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            key = jax.random.fold_in(root, i)
            if name.split("/")[0] == stacked_key:
                n = leaf.shape[0]
                out.append(jax.lax.map(
                    lambda k, name=name, leaf=leaf: one(
                        k, name, leaf.shape[1:], leaf.dtype),
                    jax.random.split(key, n)))
            else:
                out.append(one(key, name, leaf.shape, leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    # the seed is an argument, not a constant: one program for every seed
    return jax.jit(build)(jnp.asarray(int(seed) & 0xFFFFFFFF, jnp.uint32))
