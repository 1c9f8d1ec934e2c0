"""Runner `engine_ssm`: runner `engine` (the in-process `LLMEngine` under a
serving mix; chipbench/runners/engine.py, reused by import) for a model
with state-space layers beside attention (the Jamba family).

It adds what `engine` has no place for and changes nothing else:
- the published config.json keys of the family (`attn_layer_period`,
  `attn_layer_offset`, the `mamba_*` keys, `tie_word_embeddings`) reach
  the program under its names and the plain reference under theirs;
- a program without `ray_tpu/models/jamba.py` (a commit before it) is
  refused at once, before any array is made, with exit code 1 and no
  result line;
- the weights come from `chipbench/weights_ssm.py` (Mamba's published
  initialisation for the mixer's leaves that are no matrix);
- the output check's logits path builds its own pages AND per-slot state
  (`HybridCache`), as `engine` builds its own pages.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, List

import numpy as np

from chipbench import weights_ssm
from chipbench.cell import BenchError
from chipbench.runners import engine as base

SSM_KEYS = ("attn_layer_period", "attn_layer_offset", "mamba_d_state",
            "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
            "mamba_conv_bias", "mamba_proj_bias", "tie_word_embeddings",
            "num_experts", "num_experts_per_tok")


def model_overrides(published: Dict[str, Any]) -> Dict[str, Any]:
    """The published config.json keys as the program's JambaConfig names.
    No `rope_theta`: the family's attention does not rotate."""
    if (published["num_experts"], published["num_experts_per_tok"]) != (1, 1):
        raise BenchError("engine_ssm runs a dense FFN in every layer: "
                         "num_experts and num_experts_per_tok must be 1")
    return {
        "vocab_size": published["vocab_size"],
        "hidden_size": published["hidden_size"],
        "intermediate_size": published["intermediate_size"],
        "num_layers": published["num_hidden_layers"],
        "num_heads": published["num_attention_heads"],
        "num_kv_heads": published["num_key_value_heads"],
        "head_dim": published.get("head_dim"),
        "rms_norm_eps": float(published["rms_norm_eps"]),
        **{k: published[k] for k in SSM_KEYS[:9]},
    }


def _require_ssm_program() -> None:
    import importlib.util

    if importlib.util.find_spec("ray_tpu.models.jamba") is None:
        raise BenchError(
            "this program has no state-space layers "
            "(ray_tpu/models/jamba.py): it cannot run a Jamba "
            "configuration")


def _shape_probe(econf):
    """The program's parameter tree as shapes (no arrays are made)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm.stage import (init_params, model_family,
                                         serve_model_config)

    cfg = serve_model_config(econf)
    model = model_family(econf.model).serving_model(cfg)
    return jax.eval_shape(lambda: init_params(
        model, jnp.zeros((1, 8), jnp.int32), jax.random.PRNGKey(0)))


def _paged_logits(engine, prompts: List[List[int]], g: int):
    """`engine._paged_logits` for a model that keeps per-slot state beside
    its pages: prefill (every row from zero state, each into its own
    slot), then decode g-1 tokens one at a time over those slots, through
    the engine's model and params and a small pool of the benchmark's own
    (pages and state as `pool_spec` lays them out). Returns per sequence
    the float32 logits at every position [p + g - 1, V] and the g greedy
    tokens that were fed back."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm.stage import model_family

    model, mc, page = engine.model, engine.model_cfg, engine.config.page_size
    family = model_family(engine.config.model)
    b = len(prompts)
    longest = max(len(p) for p in prompts)
    sb = -(-longest // 128) * 128
    mp = -(-(longest + g + 1) // page)
    mp = -(-mp // 8) * 8
    pool = jax.tree.map(
        lambda sd: jnp.zeros(*sd),
        family.pool_spec(mc, mc.num_layers, 1 + b * mp, page, b),
        is_leaf=lambda sd: isinstance(sd, tuple))
    bt = np.arange(1, 1 + b * mp, dtype=np.int32).reshape(b, mp)

    def run(params, pool, bt, total, ids, positions, slots):
        cache = family.serving_cache(mc, pool, bt, total, slots)
        logits, new_cache = model.apply({"params": params}, ids,
                                        positions=positions, kv_caches=cache)
        return logits.astype(jnp.float32), new_cache.pool

    step = jax.jit(run, donate_argnums=(1,))
    ids = np.zeros((b, sb), np.int32)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
    positions = np.broadcast_to(np.arange(sb, dtype=np.int32), (b, sb))
    logits, pool = step(engine.params, pool, jnp.asarray(bt),
                        jnp.asarray(lens), jnp.asarray(ids),
                        jnp.asarray(positions), jnp.arange(b))
    logits = np.asarray(logits)
    rows = [[logits[i, :n]] for i, n in enumerate(lens)]
    last = np.stack([logits[i, n - 1] for i, n in enumerate(lens)])
    fed = [[int(t)] for t in last.argmax(-1)]
    for j in range(1, g):
        tok = np.asarray([[f[-1]] for f in fed], np.int32)
        total = lens + j
        lg, pool = step(engine.params, pool, jnp.asarray(bt),
                        jnp.asarray(total), jnp.asarray(tok),
                        jnp.asarray((total - 1)[:, None]), None)
        lg = np.asarray(lg)[:, 0]
        for i in range(b):
            rows[i].append(lg[i][None])
            fed[i].append(int(lg[i].argmax()))
    del pool
    return [np.concatenate(r, 0) for r in rows], fed


@contextlib.contextmanager
def _ssm_set_up():
    """`engine.Runner.setup` and its check reach for the module's
    `model_overrides`, `_shape_probe`, `weights` and `_paged_logits`; for
    the length of a set-up they are this module's. (The file may not be
    edited by this PR: PERF.md, Open questions.)"""
    mine = {"model_overrides": model_overrides, "_shape_probe": _shape_probe,
            "weights": weights_ssm, "_paged_logits": _paged_logits}
    theirs = {k: getattr(base, k) for k in mine}
    for k, v in mine.items():
        setattr(base, k, v)
    try:
        yield
    finally:
        for k, v in theirs.items():
            setattr(base, k, v)


class Runner(base.Runner):
    def __init__(self, cell, seed: int, seconds: float, log):
        _require_ssm_program()
        super().__init__(cell, seed, seconds, log)
        self.published.update({k: cell.config[k] for k in SSM_KEYS})
        self.published["num_hidden_layers"] = cell.config["num_hidden_layers"]

    def setup(self, warm: bool = True) -> Dict[str, Any]:
        with _ssm_set_up():
            check = super().setup(warm)
        got = self.engine.model_cfg
        want = (self.published["attn_layer_period"],
                self.published["attn_layer_offset"])
        if (got.attn_layer_period, got.attn_layer_offset) != want:
            raise BenchError(f"the engine's layer pattern is "
                             f"{got.attn_layer_period}/"
                             f"{got.attn_layer_offset}; the configuration "
                             f"says {want}")
        st = self.engine.stats()
        self.log(f"state: {st['ssm_slots']} slots, recurrent state pool "
                 f"{st['ssm_state_pool_bytes']:,} bytes; prefix reuse off")
        return check
