"""Weights from the seed for a model of gated-delta-net and latent-attention
layers with routed experts, whose tree is a stack a RUN of like layers
(`run_<ii>/...`, a leading [run] axis each): `chipbench/weights.py`'s values
(matrices N(0, 1/fan_in), the embedding N(0, 0.02^2)), each run made one
layer at a time so that no temporary is larger than one layer's largest
leaf (a layer's 16 experts' gate_up is 0.94 GB).

Leaves that are no plain [.., in, out] matrix: `kv_b_proj` [r, H, dn + dv]
contracts its FIRST axis; `conv_kernel` [4, channels] comes out N(0, 1/4) by
the same rule; `router_bias` [R] is N(0, 0.05^2) (chipbench/weights_mla.py
says why); the latent's inner norms (`scale`) are 1 + 0.1 N(0, 1); every
ZERO-CENTRED weight (`zc_weight` of the four norms a layer and the final
one, `o_norm` of a GDN head) is 0.1 N(0, 1), so that a reference that forgot
one, or centred it elsewhere, would disagree; `A_log` = ln U(0, 16) and
`dt_bias` = softplus^-1(exp(U(ln 0.001, ln 0.1))) a value head, the Gated
DeltaNet initialisation: a head's state forgets over a few tokens or over
thousands. The same arrays go to the program and to the plain reference.
"""

from __future__ import annotations

from typing import Any

BIAS_STD = 0.05
CENTRED_STD = 0.1


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
        if path.endswith(("zc_weight", "o_norm")):
            return (CENTRED_STD * normal).astype(dtype)
        if path.endswith("router_bias"):
            return (BIAS_STD * normal).astype(dtype)
        if path.endswith("A_log"):
            return jnp.log(jax.random.uniform(
                key, shape, f32, 1e-3, 16.0)).astype(dtype)
        if path.endswith("dt_bias"):
            dt = jnp.exp(jax.random.uniform(
                key, shape, f32, jnp.log(1e-3), jnp.log(1e-1)))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        if path.startswith("embed"):
            std = 0.02
        elif path.endswith("kv_b_proj"):
            std = float(shape[0]) ** -0.5
        else:
            std = float(shape[-2]) ** -0.5
        return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)

    def build(seed_arr):
        root = jax.random.fold_in(jax.random.PRNGKey(5), seed_arr)
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            key = jax.random.fold_in(root, i)
            if name.startswith("run_"):
                out.append(jax.lax.map(
                    lambda k, name=name, leaf=leaf: one(
                        k, name, leaf.shape[1:], leaf.dtype),
                    jax.random.split(key, leaf.shape[0])))
            else:
                out.append(one(key, name, leaf.shape, leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jnp.asarray(int(seed) & 0xFFFFFFFF, jnp.uint32))
