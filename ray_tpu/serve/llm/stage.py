"""The device side of a contiguous slice of a model's layers.

`StageCompute` is everything about layers [first_layer, first_layer +
n_layers) that lives on devices: the serving model config, the module,
the mesh and the attention rule, the parameters' placement, that slice of
the paged KV pool, the decode carry, the compiled programs and their
warm-up. `LLMEngine` (engine.py) builds ONE over all layers in its own
process: an engine is a pipeline of one stage. `_StageWorker` (pp.py)
builds one for its slice in an actor process. Both run the same three
program bodies below, which differ by `(first, last)` only: a first stage
embeds token ids, a later one takes the previous stage's hidden states in
their place; a last stage samples on the device, an earlier one hands its
hidden states on.

The sampling tail of `run_prefill` and `run_decode` (`_device_sample`) is
two branches under one `cond`: a batch whose `temp` operand has no row
above 0 pays for an argmax; one with a drawing row pays for the scaling,
the cut to the top-k and the categorical draw, row by row what it always
got. The predicate is the program's own, made from the operands it already
takes: no second program a shape, no option. (`run_verify` is an argmax;
the block program's sampler, models/sdar.py: `_sample`, has its own
`cond`.)

The operands of a program after its state (params, pool[, carry]) are
`OPERANDS[kind]`, in order; a pipelined frame (pp.py) is those names in
a dict. "x" is the stage's input: ids or hidden states.

"The pool" is whatever the model's family keeps on the device between
dispatches (`model_family(...).pool_spec`): one array of pages, or a dict
of pages and per-slot arrays. Either way it is ONE donated
argument, carried whole through the row loop and the step scan, and the
model's cache object (`serving_cache`) is the only code that looks inside.
A model with per-slot state gets one more prefill operand, the decode
slot of each row (`SLOTS_OPERAND`).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

import numpy as np

from ...util import tracing

OPERANDS = {
    "prefill": ("n_rows", "bt", "total", "x", "positions", "gather",
                "temp", "topk", "keys"),
    "verify": ("n_rows", "bt", "total", "x", "positions"),
    "decode": ("bt", "total", "caps", "positions", "override_mask", "x",
               "temp", "topk", "keys"),
    # a model that generates by diffusion over blocks (models/sdar.py):
    # "total" a row's earlier tokens plus its block (0: no row), "x" the
    # block's ids, "masked" which of them are still to be fixed, "pending"
    # whether the block before it is this slot's own last one, denoised
    # and not yet settled (its ids are in the program's carry)
    "block": ("bt", "total", "x", "masked", "pending", "temp", "topk",
              "keys"),
}

# the programs that carry ids from dispatch to dispatch on the device, and
# where `StageCompute` keeps them: [S, 1] the token each slot sampled last,
# [S, B] the block each slot denoised last
_CARRY = {"decode": "slot_ids", "block": "block_ids"}

# after OPERANDS["prefill"], for a model that keeps per-slot state: the
# decode slot each row of the wave leaves its state in
SLOTS_OPERAND = "slots"

_MAX_TOP_K = 64


def _device_sample(rows, temperature, top_k, rng_keys):
    """Batched in-jit sampler. rows: [B, V] float32 -> [B] int32. Two
    bodies under one `cond` on the program's own `temp` operand, does any
    row of the batch draw:

    - greedy: a batch with no drawing row is an argmax and nothing else;
    - drawn: a row at temperature 0 gets the argmax, any other a
      categorical draw from rows / temperature, cut to its (clamped)
      top-k, with its own key.

    A row's token is the same in whichever branch its batch lands it: the
    argmax of the same float32 row, or a draw from the same scaled row,
    the same cut and the same key."""
    import jax
    import jax.numpy as jnp

    def greedy(_):
        return jnp.argmax(rows, axis=-1).astype(jnp.int32)

    def drawn(_):
        b = rows.shape[0]
        scaled = rows / jnp.maximum(temperature, 1e-6)[:, None]
        topv, _ = jax.lax.top_k(scaled, min(_MAX_TOP_K, rows.shape[-1]))
        k_idx = jnp.clip(top_k - 1, 0, topv.shape[-1] - 1)
        kth = topv[jnp.arange(b), k_idx]
        masked = jnp.where((top_k[:, None] > 0) & (scaled < kth[:, None]),
                           -jnp.inf, scaled)
        sampled = jax.vmap(
            lambda key, lg: jax.random.categorical(key, lg))(rng_keys, masked)
        return jnp.where(temperature <= 0, greedy(None),
                         sampled).astype(jnp.int32)

    return jax.lax.cond(jnp.any(temperature > 0), drawn, greedy, None)


def serve_dtype(config):
    import jax.numpy as jnp

    return jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32


def model_family(name: str):
    """The module that defines the preset `name`: the one place that
    chooses a model family from `EngineConfig.model`. Nine families (each
    module says what it is): models/llama.py, jamba.py, minicpm_sala.py,
    sdar.py (its config has `block_length` and the engine steps it with the
    "block" program), kimi.py (its config has `latent_lanes`), mellum.py
    (two kinds of attention layer: it also answers `attention_kinds(cfg)`,
    ((layers, window or None), ...), for the engine's pass cost and its
    counters of the flash kernel's visits), gigachat.py (gated-delta-net
    layers beside latent attention: three kinds of state in its pool),
    laguna.py (Mellum's stack and cache with the shapes a kind: its
    `attention_kinds(cfg)` is ((layers, window or None, query heads), ...),
    so that a pass is priced by each kind's (layer, head) pairs), zaya.py
    (attention inside a compressed latent with a tail of its last inputs a
    decode slot beside EVERY layer's pages, `cca_tail`; top-1 experts
    chosen by an MLP router whose state runs down the stack as a second
    stream of the layer scan's carry, fed to llama.py's `MoEMLP` through
    its `choice` seam; a tied head).

    This is the one description of what a family's module provides:
    `CONFIGS`, `get_config`, `serving_model`, `pool_spec`, `serving_cache`;
    `RESUMES_PREFILL` (a prefill row continues from what its pages and
    its slot hold, so a prompt may be prefilled in passes; such a family
    also answers `pass_cost_ratios(cfg)`: the weights a pass reads and
    the scores a (query, key) pair of its attention makes, over the
    parameters a token multiplies); where set, `HEAD_AT_GATHER`
    (`serving_cache` takes the position each row samples from and the
    model computes the head there only); its config answers
    `n_slot_state_layers` where layers keep state a decode slot. A family's
    model calls models/_stack.py for the scan over a run of like layers, the
    embedding, the head and its own pool, and writes its layers, its cache
    and its walk over its runs. And:

    - `dispatch_facts(model_cfg, engine_config)`: a list of small host-side
      objects (no JAX), one a feature of the model (three families list
      models/llama.py's `ExpertFacts`), that say what its dispatches
      count. Each has `STATS`, name -> help of the `stats()` counters it
      moves (`*_total`) and the sizes it reports (`sizes(pool_bytes)`: its
      pools' bytes, a figure of its shapes),
      published as `rtpu_llm_<name>`; where it has any, `constant`, the
      `engine.dispatch` record fields every dispatch has, and three hooks
      that move the engine's totals and return record fields BY NAME
      (util/tracing.py: FIELDS) or None: `prefill(totals, rows, passes,
      ctx_pages)` and `decode(totals, rows, k)` when a program is enqueued
      (`rows` the record's, `passes` a (pass index, final) a row),
      `harvest(totals, rec, packed)` when its tokens are fetched (the
      record in the making, what the program packed behind them).
    - where set, `CANNOT_BE_GIVEN`: (what sets the model apart, {option:
      the mechanism that is missing}) over `spec_lookahead`,
      `prefill_chunk_tokens`, `tp`, `pp`, `handoff` (the disaggregated
      hand-off) and `max_model_len` (one that holds no whole number of
      blocks); engine.py's `refuse` raises it by name. `prefix_reuse` is no
      option: where a family names it, the engine matches no page by its
      hash, counts what it refused and says why in `stats()`."""
    from ...models import (gigachat, jamba, kimi, laguna, llama, mellum,
                           minicpm_sala, sdar, zaya)

    families = (llama, jamba, minicpm_sala, sdar, kimi, mellum, gigachat,
                laguna, zaya)
    for family in families:
        if name in family.CONFIGS:
            return family
    raise KeyError(f"no model preset {name!r}; have "
                   f"{sorted(n for f in families for n in f.CONFIGS)}")


def serve_model_config(config):
    """The WHOLE model's config as every serving program sees it."""
    dtype = serve_dtype(config)
    return model_family(config.model).get_config(
        config.model, scan_layers=True, remat=False, dtype=dtype,
        param_dtype=dtype, max_seq_len=config.max_model_len,
        **config.model_overrides)


def init_params(model, example, rng):
    """Fresh parameters of `model`; the whole model's, from the engine's
    seed, are what every engine and every stage's slice start from."""
    import flax.linen as nn

    return nn.meta.unbox(model.init(rng, example)["params"])


def stage_params(full_params: Dict[str, Any], first_layer: int,
                 n_layers: int) -> Dict[str, Any]:
    """One stage's slice of a full LlamaModel param tree: `n_layers` of
    every stacked "layers" leaf from `first_layer` on, plus the embed
    table when the slice starts the model and final_norm + lm_head when
    it ends it. Literal slices — no reshaping, no renaming — which is what
    makes the pipelined forward bit-exact against the single engine."""
    import jax

    lo, hi = first_layer, first_layer + n_layers
    leaves = jax.tree.leaves(full_params["layers"])
    out: Dict[str, Any] = {
        "layers": jax.tree.map(lambda a: a[lo:hi], full_params["layers"])}
    if lo == 0:
        out["embed"] = full_params["embed"]
    if hi == leaves[0].shape[0]:
        out["final_norm"] = full_params["final_norm"]
        out["lm_head"] = full_params["lm_head"]
    return out


def resolve_attention(model_cfg, config, sharding) -> Dict[str, str]:
    """Which attention implementation a stage's decode and prefill
    programs contain — the one place that states the rule the ops layer
    applies (ops/paged_attention.py): sharded engines ask for the jnp
    reference, a TPU backend otherwise compiles the Pallas kernels, a CPU
    backend runs the reference. Called at construction: when the decode
    step goes to the kernel and the kernel cannot take the page pool, it
    raises, naming the constraint — never a quiet reference on a TPU."""
    import jax

    if sharding is not None:
        impl = "reference (tensor-parallel engine: ref_attention)"
        return {"decode": impl, "prefill": impl}
    if jax.default_backend() != "tpu":
        impl = f"reference ({jax.default_backend()} backend)"
        return {"decode": impl, "prefill": impl}
    from ...ops.paged_attention import (decode_kernel_constraint,
                                        latent_kernel_constraint)

    dtype = "bfloat16" if config.dtype == "bfloat16" else "float32"
    latent = bool(getattr(model_cfg, "latent_lanes", 0))
    if latent:
        why = latent_kernel_constraint(model_cfg.kv_lora_rank,
                                       config.page_size, dtype)
    else:
        why = decode_kernel_constraint(model_cfg.head_dim_,
                                       config.page_size, dtype)
    if why is not None:
        raise ValueError(
            f"EngineConfig(model={config.model!r}, page_size="
            f"{config.page_size}, dtype={config.dtype!r}) cannot run on "
            f"this TPU: the paged decode kernel needs {why}")
    if latent:
        return {"decode": "pallas latent_attention_decode (absorbed)",
                "prefill": "pallas flash_attention over materialised "
                           "chunks (+lse merge)"}
    return {"decode": "pallas paged_attention_decode",
            "prefill": "pallas flash_attention (+lse merge)"}


def dummy_operands(config, kind: str, shape_key: tuple,
                   hidden: Optional[tuple] = None,
                   slots: bool = False) -> tuple:
    """Masked `OPERANDS[kind]` for one dispatch of a program:
    total_lens=0 masks every page write and a row pass is given no real
    row, so running them leaves a stage's state untouched. Shared by
    warm-up (in process and through the stage DAG) and program_text.
    `hidden` = (width, dtype) makes "x" a later stage's hidden states;
    `slots` adds a prefill's SLOTS_OPERAND."""
    import jax.numpy as jnp

    mp = config.max_model_len // config.page_size

    def z(shape, dtype=np.int32):
        return jnp.asarray(np.zeros(shape, dtype))

    def x(rows, span):
        if hidden is None:
            return z((rows, span))
        return jnp.zeros((rows, span, hidden[0]), hidden[1])

    if kind == "block":
        block, steps, mp = shape_key
        S = config.max_batch
        # no live row: the opening pass writes nothing, the loop makes no
        # pass and the carry stays
        return (z((S, mp)), z((S,)), z((S, block)), z((S, block), bool),
                z((S,), bool),
                np.zeros((S,), np.float32), np.zeros((S,), np.int32),
                z((steps, S, 2), np.uint32))
    if kind == "decode":
        k_steps, mp = shape_key
        S = config.max_batch
        return (z((S, mp)), z((S,)), jnp.asarray(np.ones((S,), np.int32)),
                z((S, 1)), z((S,), bool), x(S, 1),
                np.zeros((S,), np.float32), np.zeros((S,), np.int32),
                z((k_steps, S, 2), np.uint32))
    sb, rb = shape_key[:2]
    # no real row: the program's loop makes no pass
    rows = (np.int32(0), z((rb, mp)), z((rb,)), x(rb, sb), z((rb, sb)))
    if kind == "verify":
        return rows
    return rows + (z((rb,)), np.zeros((rb,), np.float32),
                   np.zeros((rb,), np.int32), np.zeros((rb, 2), np.uint32)
                   ) + ((z((rb,)),) if slots else ())


class StageCompute:
    """Layers [first_layer, first_layer + n_layers) of `config`'s model
    on this process's devices (n_layers None: through the last layer).
    `params`: a tree for exactly this slice, or None: the whole model
    initialises from the config's seed, a slice waits for `load`."""

    def __init__(self, config, first_layer: int = 0,
                 n_layers: Optional[int] = None, mesh=None, params=None):
        import jax
        import jax.numpy as jnp

        from ...util.compile_cache import enable_compile_cache
        from .sharding import resolve_serve_mesh

        enable_compile_cache()
        self.config = config
        self.model_cfg = cfg = serve_model_config(config)
        self.dtype = dtype = serve_dtype(config)
        if n_layers is None:
            n_layers = cfg.num_layers - first_layer
        self.first_layer, self.n_layers = first_layer, n_layers
        self.first = first_layer == 0
        self.last = first_layer + n_layers == cfg.num_layers
        whole = self.first and self.last
        self.family = family = model_family(config.model)
        self.model = family.serving_model(cfg, n_layers, self.first,
                                          self.last)
        # layers of this slice that keep per-slot recurrent state
        self.ssm_layers = getattr(cfg, "n_slot_state_layers", 0)
        self.max_pages_per_seq = config.max_model_len // config.page_size
        # tensor parallelism: resolve mesh/tp BEFORE any compute so the
        # divisibility contract fails at construction, not first dispatch
        self.sharding = resolve_serve_mesh(mesh, tp=config.tp)
        if self.sharding is not None:
            self.sharding.validate(cfg)
        self.attention = resolve_attention(cfg, config, self.sharding)
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": jax.device_count(), "pid": os.getpid()}
        example = (jnp.zeros((1, 8), jnp.int32) if self.first
                   else jnp.zeros((1, 8, cfg.hidden_size), dtype))
        if self.sharding is not None:
            # shardings first (shape-only eval): init and the page pool
            # below materialize DIRECTLY into their sharded placement —
            # building them unsharded first would bound the servable
            # model by ONE chip's HBM, the exact limit tp removes
            self._param_shardings = self.sharding.param_shardings(
                self.model, example)
            self._kv_sharding = self.sharding.kv_pages_sharding()
            self._repl_sharding = self.sharding.replicated()
        if params is not None:
            params = self._place(params)
        elif whole:
            def init(rng):
                return init_params(self.model, example, rng)

            if self.sharding is not None:
                init = jax.jit(init, out_shardings=self._param_shardings)
            params = init(jax.random.PRNGKey(config.seed))
        self.params = params

        self.kv_pages = self.fresh_pool()
        if self.sharding is not None:
            self.slot_ids = jax.device_put(
                jnp.zeros((config.max_batch, 1), jnp.int32),
                self._repl_sharding)
        else:
            # device-resident last-sampled-token per slot: the decode
            # chain's carry (design rule 2 in engine.py's docstring)
            self.slot_ids = jnp.zeros((config.max_batch, 1), jnp.int32)
        if getattr(cfg, "block_length", 0):
            # a model that generates by diffusion over blocks: the block
            # each slot denoised last, the block program's carry
            self.block_ids = jnp.zeros(
                (config.max_batch, cfg.block_length), jnp.int32)
        self.programs: Dict[tuple, Any] = {}
        self.programs_built = 0
        # (kind,) + shape key -> instruction name -> op_name path, parsed
        # on the first `program_scopes` of a key and never before
        self._scopes: Dict[tuple, Dict[str, str]] = {}
        # the scheduler step a program is built in, for its record: the
        # engine points this at its counter, a stage worker has none
        self.step_seq = lambda: 0

    # ----------------------------------------------------------- params

    def _place(self, tree):
        """A tree for this slice onto its devices (checkpoint leaves go
        shard by shard)."""
        import jax

        if self.sharding is not None:
            return self.sharding.shard_params(tree, self._param_shardings)
        return jax.tree.map(jax.numpy.asarray, tree)

    def load(self, full_params) -> None:
        """Slice this stage's params out of the whole model's tree (under
        pp: resolved from the node-local broadcast replica) and place
        them."""
        import jax

        sliced = stage_params(full_params, self.first_layer, self.n_layers)
        self.params = self._place(jax.tree.map(
            lambda a: np.asarray(a, dtype=self.dtype), sliced))

    # ------------------------------------------------------------- pool

    def fresh_pool(self):
        """A zeroed pool as the family lays it out: one array of pages, or
        the family's name -> array. (An idle engine's pool may be dropped
        and made anew: no request holds a page or a slot.)"""
        import jax
        import jax.numpy as jnp

        config = self.config
        spec = self.family.pool_spec(
            self.model_cfg, self.n_layers, config.num_pages,
            config.page_size, config.max_batch)
        if self.sharding is not None:
            shape = spec[0]
            # zero-fill compiled WITH the sharding: each chip only ever
            # allocates its Hkv/tp slice of the pool (num_pages is sized
            # against per-shard HBM — sharding.pages_for_budget)
            return jax.jit(lambda: jnp.zeros(shape, self.dtype),
                           out_shardings=self._kv_sharding)()
        return jax.tree.map(lambda sd: jnp.zeros(*sd), spec,
                            is_leaf=lambda sd: isinstance(sd, tuple))

    def pool_bytes(self) -> Dict[str, int]:
        """Bytes of each part of the pool ("kv_pages" alone for a model
        that keeps pages only)."""
        pool = self.kv_pages
        if not isinstance(pool, dict):
            pool = {"kv_pages": pool}
        return {k: int(a.size) * a.dtype.itemsize for k, a in pool.items()}

    def read_pages(self, pages) -> np.ndarray:
        """[n_layers, len(pages), Hkv, page, 2*D] on the host. Eager: the
        caller has drained every dispatch first. (Pages only: an engine
        whose model keeps per-slot state refuses the hand-off that calls
        this, engine.py.)"""
        return np.asarray(self.kv_pages[:, np.asarray(pages, np.int32)])

    def write_pages(self, pages, kv) -> None:
        """Scatter transferred pages into the pool (eager, as above: an
        eager `.at[].set` forks the buffer, so nothing may be in
        flight)."""
        import jax
        import jax.numpy as jnp

        idx = jnp.asarray(np.asarray(pages, np.int32))
        self.kv_pages = self.kv_pages.at[:, idx].set(
            jnp.asarray(kv, self.kv_pages.dtype))
        if self.sharding is not None:
            # the eager scatter may come back with a propagated (not
            # necessarily Hkv-split) sharding; pin it before the next
            # donated dispatch
            self.kv_pages = jax.device_put(self.kv_pages,
                                           self._kv_sharding)

    # --------------------------------------------------------- programs

    def program(self, kind: str, shape_key: tuple):
        """The jitted program of one bucketed shape, built once.
        Keys: prefill (sb, rb, cp), verify (sb, rb), decode (K, mp), block
        (block_length, denoising_steps, mp)."""
        key = (kind,) + tuple(shape_key)
        fn = self.programs.get(key)
        if fn is None:
            self.programs_built += 1
            tracing.record("engine.program_built", (
                kind, shape_key, tracing.now_ns(), self.step_seq()))
            fn = self.programs[key] = self._build(kind, shape_key)
        return fn

    def _build(self, kind: str, shape_key: tuple):
        import jax
        import jax.numpy as jnp

        model, cfg, L = self.model, self.model_cfg, self.n_layers
        serving_cache = self.family.serving_cache
        # the family's model computes the head at a row's sampling
        # position only, told through its cache
        head_at_gather = getattr(self.family, "HEAD_AT_GATHER", False)
        first, last = self.first, self.last
        # sharded stages trace under GSPMD, where the single-device
        # Pallas kernels cannot run: pin the reference attention paths
        # via the cache's STATIC field (part of each jit's cache key)
        ref_attn = self.sharding is not None
        # an expert model's programs return its routing counts of this
        # stage's layers, [L, E] a pass of the model
        # (a family whose first layers are dense says how many are not)
        moe_LE = ((getattr(cfg, "n_expert_layers", L), cfg.num_experts)
                  if cfg.num_experts else None)

        def apply(params, x, positions, pc, total_lens, **how):
            """model.apply -> (logits or hidden states, cache, routing
            counts). An expert model is told which rows and positions are
            real (the padding of a wave, idle decode slots: exactly what
            `paged_write` drops) and hands back its [L, E] int32 count of
            real assignments per expert; a dense model's call is what it
            was. `how`: what one program asks of its model's call beside
            (a block program's `apply_head=False`)."""
            if moe_LE is None:
                out, new_pc = model.apply(
                    {"params": params}, x, positions=positions,
                    kv_caches=pc, **how)
                return out, new_pc, None
            (out, new_pc), sown = model.apply(
                {"params": params}, x, positions=positions, kv_caches=pc,
                token_mask=positions < total_lens[:, None],
                mutable=["routing"], **how)
            # the one leaf sown: the scanned expert layers' [L, E] (a leaf
            # a run of like layers, in the model's order, where the stack
            # is several scans: models/mellum.py)
            counts, *more = jax.tree.leaves(sown["routing"])
            if more:
                counts = jnp.concatenate([counts, *more])
            return out, new_pc, counts

        def pack(kept, counts):
            """The program's result: a last stage's tokens are
            host-bound, and an expert model's counts go behind them in
            ONE int32 array, so the harvest's single fetch brings both
            (`LLMEngine._split_packed`); hidden states go to the next
            stage with the counts beside them."""
            if counts is None:
                return kept
            if not last:
                return kept, counts
            return jnp.concatenate([kept.reshape(-1).astype(jnp.int32),
                                    counts.reshape(-1)])

        if kind in ("prefill", "verify"):
            sb, rb = shape_key[:2]
            # a prefill's ctx_pages buckets to {0, full}: a fresh-prompt
            # wave (the common case) compiles with NO prefix part — zero
            # page gathers — while any wave containing a prefix-cache hit
            # uses the full-width variant (two shapes per length bucket).
            # A verify row always continues a sequence.
            cp = shape_key[2] if kind == "prefill" else self.max_pages_per_seq

            def row_pass(params, kv_pages, n_rows, block_tables, total_lens,
                         x, positions, kept, keep, slots=None, gather=None):
                """The arrays come at the wave size; the first `n_rows`
                are requests and only those are computed, one [1 x sb]
                pass of the model a row (the trip count is data, so every
                row count is this one program). `keep(out, i)` is what
                row i leaves in `kept`. The pool rides the loop's carry as
                it rides the layer scan's, in place. `slots`: the decode
                slot a row leaves its per-slot state in; `gather`: the
                position a row samples from, for a family that computes
                its head there only."""
                def row(i, carry):
                    kvp, kept, counts = carry
                    bt, tot, xi, pos = (
                        jax.lax.dynamic_slice_in_dim(a, i, 1)
                        for a in (block_tables, total_lens, x, positions))
                    pc = serving_cache(
                        cfg, kvp, bt, tot,
                        None if slots is None else
                        jax.lax.dynamic_slice_in_dim(slots, i, 1),
                        ctx_pages=cp, ref_attention=ref_attn,
                        **({} if gather is None else {
                            "gather": jax.lax.dynamic_slice_in_dim(
                                gather, i, 1)}))
                    out, new_pc, c = apply(params, xi, pos, pc, tot)
                    kept = jax.lax.dynamic_update_slice_in_dim(
                        kept, keep(out, i), i, 0)
                    return (new_pc.pool, kept,
                            None if c is None else counts + c)

                counts0 = (None if moe_LE is None
                           else jnp.zeros(moe_LE, jnp.int32))
                return jax.lax.fori_loop(0, n_rows, row,
                                         (kv_pages, kept, counts0))

            def hidden_rows(*state_and_rows):
                """A stage that is not last: every real row's hidden
                states, for the next stage to read the same rows."""
                kvp, kept, counts = row_pass(
                    *state_and_rows,
                    jnp.zeros((rb, sb, cfg.hidden_size), cfg.dtype),
                    lambda out, i: out)
                return pack(kept, counts), kvp

            def run_prefill(params, kv_pages, n_rows, block_tables,
                            total_lens, x, positions, gather_idx,
                            temperature, top_k, rng_keys, slots=None):
                if not last:
                    return hidden_rows(params, kv_pages, n_rows,
                                       block_tables, total_lens, x,
                                       positions)
                # keep the row's sampling position only
                kvp, rows, counts = row_pass(
                    params, kv_pages, n_rows, block_tables, total_lens, x,
                    positions, jnp.zeros((rb, cfg.vocab_size), jnp.float32),
                    (lambda out, i: out[0].astype(jnp.float32))
                    if head_at_gather else
                    (lambda out, i: out[0, gather_idx[i]].astype(
                        jnp.float32)[None]), slots,
                    gather_idx if head_at_gather else None)
                # sample ON DEVICE: only B int32 tokens cross to the host
                # per step, never the [B, V] fp32 logits
                with tracing.scope("rtpu.sample"):
                    tokens = _device_sample(rows, temperature, top_k,
                                            rng_keys)
                return pack(tokens, counts), kvp

            def run_verify(params, kv_pages, n_rows, block_tables,
                           total_lens, x, positions):
                # speculative verification: the draft is a short "prompt"
                # continuing the sequence (attending to all earlier pages
                # through the same ctx-merge path), but greedy tokens come
                # back for EVERY position — the acceptance walk needs
                # argmax-after-each-draft-token, and comparing argmax
                # against the draft is what makes acceptance bit-exact
                if not last:
                    return hidden_rows(params, kv_pages, n_rows,
                                       block_tables, total_lens, x,
                                       positions)
                def greedy(out, i):
                    with tracing.scope("rtpu.sample"):
                        return jnp.argmax(out.astype(jnp.float32),
                                          axis=-1).astype(jnp.int32)

                kvp, toks, counts = row_pass(
                    params, kv_pages, n_rows, block_tables, total_lens, x,
                    positions, jnp.zeros((rb, sb), jnp.int32), greedy)
                return pack(toks, counts), kvp

            return self._jit(run_prefill if kind == "prefill"
                             else run_verify, kind, n_state=2)

        whole = first and last
        if kind == "block":
            if not whole:
                raise ValueError(
                    "a block's denoising passes run in one program: the "
                    "next pass's ids are chosen where the head is")
            return self._jit(self._block_program(apply, pack, ref_attn),
                             kind, n_state=3)

        # decode: fixed slot-set [S] batch, K fused steps, device-carry ids
        n_steps = shape_key[0]
        if n_steps != 1 and not whole:
            raise ValueError(
                "a stage of a pipeline decodes one step a dispatch: the "
                "next step's ids are sampled on another stage")

        def run_decode(params, kv_pages, slot_ids, block_tables,
                       total_lens, caps, positions, override_mask,
                       x, temperature, top_k, keys_steps):
            # x: the host-known ids of the slots `override_mask` names
            # (the others' come from the carry); on a later stage, every
            # slot's hidden states
            cache = serving_cache(cfg, kv_pages, block_tables,
                                  ref_attention=ref_attn)
            active = total_lens > 0
            x0 = (jnp.where(override_mask[:, None], x, slot_ids)
                  if first else x)

            def body(carry, keys_k):
                x_k, pos, kvp, tot = carry
                out, new_pc, counts = apply(params, x_k, pos,
                                            cache.step(kvp, tot), tot)
                if last:
                    with tracing.scope("rtpu.sample"):
                        rows = out[:, 0].astype(jnp.float32)
                        out = _device_sample(rows, temperature, top_k,
                                             keys_k)
                # caps clamp: past a slot's ceiling, positions freeze at
                # cap-1 and totals at cap, so no block-table index runs
                # off the allocated range. NOTE the frozen row keeps
                # re-writing position cap-1 with its (dropped-at-harvest)
                # samples — safe only because every token a request KEEPS
                # was appended before its cap was crossed, so no kept
                # token's attention ever reads a post-cap overwrite.
                # Inactive slots (total == 0) never write.
                new_tot = jnp.where(active, jnp.minimum(tot + 1, caps),
                                    tot)
                new_pos = jnp.minimum(pos + 1, caps[:, None] - 1)
                # the next step's ids; a stage of a pipeline has no next step
                x_next = out[:, None].astype(jnp.int32) if whole else x_k
                return ((x_next, new_pos, new_pc.pool, new_tot),
                        (out, counts))

            carry = (x0, positions, kv_pages, total_lens)
            (last_ids, _, kvp, _), (outs, counts) = jax.lax.scan(
                body, carry, keys_steps, length=n_steps)
            if not whole:
                # a pipeline's ids come from the host every step: the
                # carry passes through, the one step's hidden states go on
                return pack(outs if last else outs[0], counts), slot_ids, kvp
            # carry the last sampled token forward for ACTIVE slots only:
            # dead rows keep their (irrelevant) values instead of being
            # scribbled with garbage samples
            new_slot_ids = jnp.where(active[:, None], last_ids, slot_ids)
            return pack(outs, counts), new_slot_ids, kvp

        return self._jit(run_decode, kind, n_state=3)

    def _block_program(self, apply, pack, ref_attn):
        """`run_block`: ALL of one block's forward passes for the full slot
        set of a model that generates by diffusion over blocks. Every pass
        writes the block's keys and values to its pages as the block
        stands (the positions are fixed; a later pass overwrites them) and
        attends the row's earlier tokens and the whole block
        (`PagedCache.block_step`); `models/sdar.py: decide` fixes some
        masked positions from the new block's final-normed hidden states
        and the head's weights: a greedy batch's pass in one kernel that
        writes no logits, a batch with a drawing row by the head's product
        and `denoise` (a sharded stage, where single-device kernels cannot
        run, takes logits from the model and calls `denoise`). The loop
        leaves when no live row has a masked position (at most
        `denoising_steps` passes in all).

        A denoised block is PENDING: its pages hold the keys of its last
        pass's inputs, not of its settled ids. No pass of its own settles
        it. Its ids stay on the device, in the carry [S, B] (the host
        dispatches block n + 1 before it has harvested block n), and the
        FIRST pass of the slot's next program is two blocks wide: the
        pending block at its positions beside the new one, both written,
        the pending one's queries attending up to their own block. That is
        what a settling pass computes, on a read of the weights that the
        new block pays for anyway. The head is over the new block only.
        Where `pending` is False (a request's first block; the slot's
        carry is another request's) the left half sits past the row's
        length, where every rule drops it: no write, no expert, no key.
        A request's LAST block is never settled: nothing reads its keys
        (engine.py: `_release_slot`).

        Returns (int32 [S*B tokens | S*B the pass that fixed each, -1
        where it came fixed | forward passes run | an expert model's
        [steps, L, E] counts, a pass a row], carry, pool)."""
        import jax
        import jax.numpy as jnp

        from ...models.sdar import decide, denoise

        cfg = self.model_cfg
        B, steps = cfg.block_length, cfg.denoising_steps
        serving_cache = self.family.serving_cache
        moe = cfg.num_experts > 0

        def run_block(params, kv_pages, block_ids, block_tables, total_lens,
                      x, masked, pending, temperature, top_k, keys_steps):
            cache = serving_cache(cfg, kv_pages, block_tables,
                                  ref_attention=ref_attn, block_step=True)
            live = total_lens > 0
            start = jnp.maximum(total_lens - B, 0)
            span = jnp.arange(B, dtype=jnp.int32)
            positions = start[:, None] + span
            # the pending block's place, or past the row's end
            left = jnp.where(pending & live, start - B,
                             total_lens)[:, None] + span

            def forward(ids, positions, kvp):
                out, new_pc, counts = apply(
                    params, ids, positions, cache.step(kvp, total_lens),
                    total_lens, apply_head=ref_attn)
                return out, new_pc.pool, counts

            def put(counts, c, at):
                return (counts if c is None else
                        jax.lax.dynamic_update_slice_in_dim(
                            counts, c[None], at, 0))

            def fix(carry, out, kvp, c):
                step, ids, masked, fixed_at, _, counts = carry
                how = (ids, masked, step, cfg, temperature, top_k,
                       keys_steps[step])
                ids, masked, fixed = (
                    denoise(out, *how) if ref_attn else
                    decide(out, params["lm_head"]["kernel"], *how))
                fixed_at = jnp.where(fixed, step, fixed_at)
                return (step + 1, ids, masked, fixed_at, kvp,
                        put(counts, c, step))

            def body(carry):
                return fix(carry, *forward(carry[1], positions, carry[4]))

            carry = (jnp.int32(0), x, masked & live[:, None],
                     jnp.full(x.shape, -1, jnp.int32), kv_pages,
                     jnp.zeros((steps, self.n_layers, cfg.num_experts),
                               jnp.int32) if moe else None)
            # the opening pass: [pending block | new block]
            carry = fix(carry, *forward(
                jnp.concatenate([block_ids, x], axis=1),
                jnp.concatenate([left, positions], axis=1), kv_pages))
            step, ids, _, fixed_at, kvp, counts = jax.lax.while_loop(
                lambda c: (c[0] < steps) & jnp.any(c[2]), body, carry)
            kept = jnp.concatenate([ids.reshape(-1), fixed_at.reshape(-1),
                                    step[None]])
            return (pack(kept, counts),
                    jnp.where(live[:, None], ids, block_ids), kvp)

        return run_block

    def _jit(self, fn, kind: str, n_state: int):
        """jit with the state after the params donated: the pool (and the
        decode carry) update in place. Sharded: params + pages by their
        specs, the carry and every host-built operand replicated; results
        come back replicated so the harvest fetch is shard-agnostic."""
        import jax

        donate = tuple(range(1, n_state))
        if self.sharding is None:
            return jax.jit(fn, donate_argnums=donate)
        repl, kv = self._repl_sharding, self._kv_sharding
        return jax.jit(
            fn, donate_argnums=donate,
            in_shardings=(self._param_shardings, kv) + (repl,) * (
                n_state - 2 + len(self.operands(kind))),
            out_shardings=(repl,) * (n_state - 1) + (kv,))

    def _state(self, kind: str) -> tuple:
        carry = _CARRY.get(kind)
        return (self.params, self.kv_pages) + (
            (getattr(self, carry),) if carry else ())

    def run(self, kind: str, shape_key: tuple, *operands):
        """Enqueue one program over `OPERANDS[kind]`; the pool (and the
        carry) are rebound to its donated results. Returns the result's
        handle: tokens ([rb] prefill, [rb, sb] verify, [K, S] decode; an
        expert model's counts behind them) or hidden states."""
        out, *state = self.program(kind, shape_key)(
            *self._state(kind), *operands)
        self.kv_pages = state[-1]
        if kind in _CARRY:
            setattr(self, _CARRY[kind], state[0])
        return out

    def operands(self, kind: str) -> tuple:
        """The names of what `run(kind, ...)` takes after the shape key."""
        if kind == "prefill" and self.ssm_layers:
            return OPERANDS[kind] + (SLOTS_OPERAND,)
        return OPERANDS[kind]

    def dummy_args(self, kind: str, shape_key: tuple) -> tuple:
        return dummy_operands(
            self.config, kind, shape_key,
            None if self.first else (self.model_cfg.hidden_size, self.dtype),
            slots=kind == "prefill" and bool(self.ssm_layers))

    def program_text(self, kind: str, shape_key: tuple) -> str:
        """The lowered (StableHLO) text of one dispatch program — what
        chip_smoke.py reads to show that a Pallas kernel
        (`tpu_custom_call`) is really in the program a replica runs."""
        return self.program(kind, shape_key).lower(
            *self._state(kind), *self.dummy_args(kind, shape_key)).as_text()

    def program_scopes(self, kind: str, shape_key: tuple) -> Dict[str, str]:
        """Which scope each instruction of one dispatch program belongs
        to: instruction name as a profiler trace's op events carry it
        (`fusion.694`, `sort.3`, `_moe_gmm.26`) -> the `op_name` path jax
        wrote for it (util/tracing.py: instruction_scopes, SCOPES). The
        same lowering under the same options as the program that ran, so
        with a persistent compile cache the text is the executed
        program's, fetched and parsed once a key. For whoever reads a
        trace AFTER the run: nothing on the serving path calls this, a key
        that was never built is lowered without joining `programs`, and it
        counts as no program built."""
        key = (kind,) + tuple(shape_key)
        table = self._scopes.get(key)
        if table is None:
            fn = self.programs.get(key) or self._build(kind, shape_key)
            table = self._scopes[key] = tracing.instruction_scopes(
                fn.lower(*self._state(kind),
                         *self.dummy_args(kind, shape_key)
                         ).compile().as_text())
        return table

    def warmup(self, programs) -> int:
        """Build each (kind, shape key) by running it on masked dummy
        operands: state is untouched, and a row pass with no real row
        costs the trace and the compile or cache fetch, no device time."""
        import jax

        for kind, key in programs:
            jax.block_until_ready(
                self.run(kind, key, *self.dummy_args(kind, key)))
        return len(programs)
