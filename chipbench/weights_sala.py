"""Weights from the seed for a MiniCPM-SALA-family model, whose tree is a
stack a RUN of like layers (`run_<i>/...`, a leading [run] axis) and not
one `layers` stack: `chipbench/weights.py`'s values (matrices N(0,
1/fan_in) with fan_in the second-to-last axis, the embedding N(0, 0.02^2),
norm scales 1 + 0.1 N(0, 1)), each run made one layer at a time so that no
temporary is larger than one layer's largest matrix (a run of six
lightning layers' gate_up alone is 1.6 GB).

The leaves that are no matrix are all norm scales here, the head-dim norms
of q, k and the lightning output among them: with q and k normed, a
score q.k / sqrt(d) is of order 1 on random weights, so the softmax is
neither flat nor one-hot, the selection's scores differ from block to
block, and a lightning state carried wrongly from one pass into the next
changes the logits (the slowest head's decay is e^-0.004 a token). The
same arrays go to the program and to the plain reference.
"""

from __future__ import annotations

from typing import Any


def make_params(abstract: Any, seed: int, stacked_prefix: str = "run_"):
    """abstract: the program's param tree as ShapeDtypeStructs. Leaves
    under a top-level key that starts with `stacked_prefix` carry a
    leading layer axis and are made one layer at a time."""
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
        root = jax.random.fold_in(jax.random.PRNGKey(2), seed_arr)
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            key = jax.random.fold_in(root, i)
            if name.startswith(stacked_prefix):
                out.append(jax.lax.map(
                    lambda k, name=name, leaf=leaf: one(
                        k, name, leaf.shape[1:], leaf.dtype),
                    jax.random.split(key, leaf.shape[0])))
            else:
                out.append(one(key, name, leaf.shape, leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jnp.asarray(int(seed) & 0xFFFFFFFF, jnp.uint32))
