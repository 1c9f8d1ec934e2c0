"""What a family's model does NOT decide, each thing once: the scan over a
run of like layers, the embedding, the head at the position a row samples
from, the pool a call without a cache runs over, the serving path's stack of
expert weights. Plain functions a `@nn.compact` body calls; a family's module
keeps what sets it apart (its config, its cache, its layers and the walk over
its runs: serve/llm/stage.py: model_family). Below models/llama.py in the
import graph: no family and nothing of serve/ is imported here.

THE CALL a family's model answers: `model(input_ids [B, S], positions=None,
kv_caches=None, token_mask=None)` -> logits [B, S, V]; with `kv_caches` (the
family's cache, as its `serving_cache` builds it) -> (logits, the cache with
its pools updated), S == 1 a decode step over the slot set and S > 1 a
prefill pass that resumes from what the rows' pages and slots hold; where
the cache names a `gather`, the logits are [B, 1, V], at that position of
each row. Without a cache the same paged path runs over a pool of the call's
own (`own_cache`), from zero state. `token_mask` [B, S] bool marks padding
where there is no cache to say it (an expert layer gives padding no expert).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..util import tracing

A = nn.with_logical_partitioning  # annotate param init with logical axes

# every collection a scanned layer may sow, stacked a layer like its params:
# "losses" (an expert layer's auxiliary loss, the trainer's), "routing" and
# "selection" (what the benchmark's checks ask of a router and of a sparse
# layer), "intermediates" (`Module.sow`'s default)
SOWN = ("params", "losses", "routing", "selection", "intermediates")


def scan_run(layer_cls, length: int, name: str, *args, **attrs):
    """`layer_cls(*args, **attrs)` scanned over a run of `length` like
    layers named `name`: called as `run(carry, xs, consts)`, the body gets
    `xs`'s slice and `consts` whole; every leaf of its parameters gains a
    leading [length] axis under PARTITION_NAME "layers". The ONE spelling:
    a collection that no caller makes mutable adds nothing to a program (the
    lowered text of every family is the same under its own set and under
    `SOWN`: tests/test_program_pins.py), so there is one set."""
    return nn.scan(
        layer_cls, variable_axes=dict.fromkeys(SOWN, 0),
        split_rngs={"params": True}, length=length,
        in_axes=(0, nn.broadcast),
        metadata_params={nn.PARTITION_NAME: "layers"})(*args, name=name,
                                                       **attrs)


def dense(cfg, features: int, axes: tuple, name: str):
    """A projection without bias over the last axis, its kernel's logical
    `axes` named for the sharding rules."""
    return nn.DenseGeneral(
        features=features, use_bias=False, axis=-1, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        kernel_init=A(nn.initializers.lecun_normal(), axes), name=name)


def default_positions(input_ids, positions):
    """[B, S]: a row's positions from 0 where the caller named none."""
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]),
                                     input_ids.shape[:2])
    return positions


def embed_table(module: nn.Module, cfg):
    """The `embed` leaf [V, h] of `module`."""
    return module.param(
        "embed", A(nn.initializers.normal(0.02), ("vocab", "embed")),
        (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)


def embed_tokens(module: nn.Module, cfg, input_ids):
    """(the `embed` leaf, its rows at input_ids [B, S] in the compute
    dtype)."""
    embed = embed_table(module, cfg)
    return embed, embed[input_ids].astype(cfg.dtype)


def head_at_gather(module: nn.Module, cfg, x, gather, weight=None):
    """Logits of the final-normed x [B, S, h] under the scope `rtpu.head`:
    [B, S, V] where `gather` is None; else [B, 1, V] at position
    `gather[b]` of each row, and zeros without the product where no row
    samples (every `gather` < 0: a pass in the middle of a prompt).
    `weight`: a tied head's [V, h] table; None: `module`'s own `lm_head`
    [h, V], a plain leaf and not a Dense because the product runs under
    `lax.cond`."""
    if weight is None:
        w = module.param(
            "lm_head", A(nn.initializers.lecun_normal(), ("embed", "vocab")),
            (cfg.hidden_size, cfg.vocab_size), cfg.param_dtype)

    def head(a):
        with tracing.scope("rtpu.head"):
            if weight is None:
                return jnp.dot(a, w.astype(cfg.dtype))
            return jnp.einsum("bsh,vh->bsv", a, weight.astype(cfg.dtype))

    if gather is None:
        return head(x)
    at_gather = jnp.take_along_axis(
        x, jnp.maximum(gather, 0)[:, None, None], axis=1)
    return jax.lax.cond(
        jnp.any(gather >= 0), head,
        lambda a: jnp.zeros(a.shape[:2] + (cfg.vocab_size,), cfg.dtype),
        at_gather)


def own_cache(pool_spec, serving_cache, cfg, b: int, s: int, token_mask,
              page: int = 16):
    """The family's cache for a call that brings none: a zeroed pool as its
    `pool_spec` lays it out (one array or a dict of them), a page set and a
    decode slot a row (page 0 is no row's, as in an engine's pool:
    serve/llm/cache.py), lengths from `token_mask` [B, S] or all of S."""
    mp = -(-s // page) + 1
    spec = pool_spec(cfg, cfg.num_layers, 1 + b * mp, page, b)
    pool = ({k: jnp.zeros(*sd) for k, sd in spec.items()}
            if isinstance(spec, dict) else jnp.zeros(*spec))
    total = (jnp.full((b,), s, jnp.int32) if token_mask is None
             else token_mask.sum(-1).astype(jnp.int32))
    return serving_cache(
        cfg, pool, 1 + jnp.arange(b * mp, dtype=jnp.int32).reshape(b, mp),
        total)


def stacked_experts(module: nn.Module, cfg, path: tuple):
    """The WHOLE stack of a scanned run's expert weights, (gate_up [L, E, h,
    2f], down [L, E, f, h]) under `module`'s params at `path`, cast once
    outside the scan, for the grouped matmul to read in place; None while
    `module` initialises (models/llama.py: `_stacked_experts` says why a
    serving program hands the layers the stack and a training step does
    not)."""
    if module.is_initializing():
        return None
    moe = nn.meta.unbox(module.get_variable("params", path[0]))
    for key in path[1:]:
        moe = moe[key]
    return (moe["experts_gate_up"].astype(cfg.dtype),
            moe["experts_down"].astype(cfg.dtype))


def whole_model_only(model_cls, cfg, first: bool, last: bool, why: str):
    """`model_cls(cfg)`, for a `serving_model` that builds no pipeline
    stage: the stack of a model `why` is no uniform `layers` axis."""
    if not (first and last):
        raise NotImplementedError(
            f"a slice of a model {why}: pipeline stages cut a uniform "
            "`layers` axis (serve/llm/stage.py: stage_params)")
    return model_cls(cfg)
