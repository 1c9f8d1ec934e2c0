"""Grouped matmul for the dropless expert layer (models/llama.py:MoEMLP).

`lhs` [M, K] holds the routed assignments ordered by expert: the first
`group_sizes[0]` rows belong to expert 0, the next `group_sizes[1]` to
expert 1, ... Row i of the result is `lhs[i] @ rhs[expert of i]`
([E, K, N] weights). `sum(group_sizes)` may be less than M: the rows past
it (padding the engine masked out) belong to no expert and cost no expert
work. What comes back in them is UNDEFINED (the kernel never visits their
tiles, so it is whatever the buffer held; `ragged_dot` writes zeros): the
caller masks them where it folds assignments back into tokens, once, at
[T, h] instead of on every [M, N] here. Their gradient is zero.

On a TPU backend this is the Pallas TPU grouped matmul that jax ships
(`jax.experimental.pallas.ops.tpu.megablox`: `gmm` forward and for the
activations' gradient, `tgmm` for the weights'); on any other backend the
plain `jax.lax.ragged_dot`. The choice is made in one place (`_impl`) and
never falls back on a TPU.

Every call goes through ONE jitted wrapper, `_moe_gmm`, and the kernel's
events in a device trace are named after it (`_moe_gmm.<n>`; the backward's
after `_moe_gmm_bwd`): an instruction takes the name of the innermost jit
around its `pallas_call`, which is why megablox's own jits are unwrapped
here. PERF.md section 3 lists the name among the fragile ones; chipbench's
`moe_gmm_*` metrics match it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# (m, k, n) tile of the megablox kernel. One choice for every shape the
# engine and the trainer compile: 10 MiB of VMEM with double buffering,
# inside the 16 MiB a kernel gets unasked on v5e. Tuning it per shape is
# the next perf_opt's (PERF.md section 7).
TILING = (512, 1024, 1024)
TILE_M_SMALL = 128   # decode programs: M = max_batch * k rows


def _impl() -> str:
    """"megablox" | "ragged_dot"; a test patches in "megablox_interpret",
    the kernel in interpret mode, to run it off the TPU."""
    return "megablox" if jax.default_backend() == "tpu" else "ragged_dot"


def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   group_sizes: jax.Array,
                   layer: jax.Array = None) -> jax.Array:
    """lhs [M, K] x rhs [E, K, N] -> [M, N] in lhs's type; see the module
    docstring. With `layer` (a traced index), rhs is a whole [L, E, K, N]
    stack and the groups are layer `layer`'s experts: the stack is read in
    place, as L*E groups of which all but E are empty, instead of being
    sliced (a slice handed to a kernel is a copy)."""
    group_sizes = group_sizes.astype(jnp.int32)
    if layer is not None:
        n_layers, e = rhs.shape[:2]
        rhs = rhs.reshape((n_layers * e,) + rhs.shape[2:])
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * e,), jnp.int32), group_sizes,
            (layer * e,))
    return _moe_gmm(lhs, rhs, group_sizes, impl=_impl())


@functools.partial(jax.jit, static_argnames=("impl",))
def _moe_gmm(lhs, rhs, group_sizes, *, impl: str):
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    m = lhs.shape[0]
    tm, tk, tn = TILING
    if m <= TILE_M_SMALL * 2:
        tm = TILE_M_SMALL
    tiling = (tm, min(tk, rhs.shape[1]), min(tn, rhs.shape[2]))
    pad = (-m) % tm   # the kernel wants whole m-tiles
    return _megablox(jnp.pad(lhs, ((0, pad), (0, 0))), rhs, group_sizes,
                     tiling, impl == "megablox_interpret")[:m]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _megablox(lhs, rhs, group_sizes, tiling, interpret):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    return gmm.__wrapped__(lhs, rhs, group_sizes, lhs.dtype, tiling,
                           interpret=interpret)


def _megablox_fwd(lhs, rhs, group_sizes, tiling, interpret):
    return (_megablox(lhs, rhs, group_sizes, tiling, interpret),
            (lhs, rhs, group_sizes))


def _megablox_bwd(tiling, interpret, res, grad):
    return (*_moe_gmm_bwd(*res, grad, tiling=tiling, interpret=interpret),
            None)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def _moe_gmm_bwd(lhs, rhs, group_sizes, grad, *, tiling, interpret):
    """d lhs = grad x rhs^T by group; d rhs[e] = lhs[group e]^T x grad."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    d_lhs = gmm.__wrapped__(grad, rhs, group_sizes, lhs.dtype, tiling,
                            transpose_rhs=True, interpret=interpret)
    # rows in no group: the kernel left their gradient unwritten too
    d_lhs = jnp.where(
        (jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes))[:, None], d_lhs, 0)
    d_rhs = tgmm.__wrapped__(lhs.swapaxes(0, 1), grad, group_sizes,
                             rhs.dtype, tiling,
                             num_actual_groups=rhs.shape[0],
                             interpret=interpret)
    return d_lhs, d_rhs


_megablox.defvjp(_megablox_fwd, _megablox_bwd)
