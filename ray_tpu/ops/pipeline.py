"""Pipeline parallelism: GPipe microbatch schedule over the pp mesh axis.

The reference gets pipeline parallelism by delegating to vLLM/torch (ref:
SURVEY.md §2.4 — `pipeline_parallel_size` in llm/_internal/serve/
deployments/llm/vllm/vllm_models.py:129; no in-repo PP implementation), so
this is greenfield TPU-native surface. Design follows the standard
collective-permute pipeline (the scaling-book / praxis recipe):

- the layer stack is split into S stages; each pp rank holds its stage's
  stacked params (leading "stages" axis sharded over pp)
- the batch splits into M microbatches; a lax.scan runs M + S - 1 ticks;
  at each tick every rank applies its stage to its current activation and
  ppermutes the result to the next rank (one hop over ICI/DCN per tick)
- rank 0 injects microbatch t at tick t; rank S-1's output at tick t is
  microbatch t-(S-1); outputs are psum-broadcast back to all pp ranks so
  the (replicated-over-pp) loss/head can run everywhere
- autodiff flows straight through ppermute/psum, so one forward
  definition gives the pipelined backward for free; wrap the stage in
  jax.checkpoint to keep the per-tick activation memory bounded

The wrapper runs inside jax.shard_map with ONLY the pp axis manual
(axis_names={"pp"}); dp/fsdp/sp/ep/tp stay auto, so GSPMD still lays out
everything inside a stage.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(stage_fn: Callable[[Any, jax.Array], jax.Array],
          num_stages: int, num_microbatches: int):
    """Build the per-shard GPipe loop body.

    stage_fn(stage_params, x_mb) -> x_mb applies ONE stage's layer stack
    to one microbatch. Returns fn(stage_params_local, x_microbatches)
    usable inside shard_map with manual axis "pp":
      x_microbatches: [M, mb, ...] (same on every rank; only rank 0's
      injection matters), returns [M, mb, ...] final-stage outputs
      (identical on every rank after the psum broadcast).
    """
    S, M = num_stages, num_microbatches
    T = M + S - 1

    def run(stage_params, x_mb):
        rank = jax.lax.axis_index("pp")
        mb_shape = x_mb.shape[1:]

        def tick(carry, t):
            state, outputs = carry
            # rank 0 ingests microbatch t (clamped index: beyond M the
            # injected value is dead — it never reaches the last rank
            # within T ticks)
            inject = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.minimum(t, M - 1), axis=0, keepdims=False)
            state_in = jnp.where(rank == 0, inject, state)
            out = stage_fn(stage_params, state_in)
            # collect on the last rank: tick t carries microbatch t-(S-1)
            is_ready = (t >= S - 1) & (rank == S - 1)
            idx = jnp.maximum(t - (S - 1), 0)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(is_ready, out,
                          jax.lax.dynamic_index_in_dim(
                              outputs, idx, axis=0, keepdims=False)),
                idx, axis=0)
            # shift activations one stage forward (ring permute; the
            # wrap-around edge S-1 -> 0 carries a dead value)
            state = jax.lax.ppermute(
                out, "pp", [(i, (i + 1) % S) for i in range(S)])
            return (state, outputs), None

        # mark the carries as pp-varying (their values differ per rank)
        init = jax.lax.pcast(
            (jnp.zeros(mb_shape, x_mb.dtype),
             jnp.zeros((M,) + mb_shape, x_mb.dtype)),
            ("pp",), to="varying")
        (_, outputs), _ = jax.lax.scan(tick, init, jnp.arange(T))
        # broadcast the last stage's outputs to every pp rank (zeros
        # elsewhere, so the psum is exactly the last rank's value).
        # psum in f32: XLA's bf16 all-reduce promotion pass crashes on
        # CPU inside manual sections (and f32 reduction is what we want
        # numerically anyway).
        outputs = jnp.where(rank == S - 1, outputs,
                            jnp.zeros_like(outputs))
        summed = jax.lax.psum(outputs.astype(jnp.float32), "pp")
        return summed.astype(x_mb.dtype)

    return run


def pipeline_apply(stage_fn: Callable, stage_params, x: jax.Array, *,
                   mesh: Mesh, num_microbatches: int,
                   remat: bool = True) -> jax.Array:
    """Apply a stage-sharded layer stack to [B, ...] activations with a
    GPipe schedule over the mesh's pp axis.

    stage_params leaves carry a leading [S] stages axis sharded over
    "pp"; x is any batch-leading activation (its other axes may be
    sharded over the auto axes).
    """
    S = mesh.shape["pp"]
    M = num_microbatches
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    if S == 1:  # degenerate: no pipeline, just run the stack
        return stage_fn(jax.tree.map(lambda p: p[0], stage_params), x)
    fn = stage_fn
    if remat:
        fn = jax.checkpoint(stage_fn)
    run = gpipe(fn, S, M)

    x_mb = x.reshape((M, B // M) + x.shape[1:])

    def sharded(params, xs):
        # params arrive with the [S] axis consumed by the manual pp
        # split: strip the singleton stage axis inside the shard
        local = jax.tree.map(lambda p: p[0], params)
        return run(local, xs)

    n_spec = len(x_mb.shape) - 1
    out = jax.shard_map(
        sharded,
        mesh=mesh,
        in_specs=(P("pp"), P(*([None] * (n_spec + 1)))),
        out_specs=P(*([None] * (n_spec + 1))),
        axis_names={"pp"},
    )(stage_params, x_mb)
    return out.reshape((B,) + out.shape[2:])


def stack_to_stages(layer_params, num_stages: int):
    """Reshape stacked layer params [L, ...] -> [S, L/S, ...] (the
    leading stages axis then shards over pp)."""
    def reshape(p):
        L = p.shape[0]
        if L % num_stages:
            raise ValueError(
                f"{L} layers not divisible into {num_stages} stages")
        return p.reshape((num_stages, L // num_stages) + p.shape[1:])

    return jax.tree.map(reshape, layer_params)
