"""SDAR: a decoder that generates by diffusion over blocks.

The layer is models/llama.py's (Qwen3-MoE: RMSNorm over the head dim of q
and k before the rotation, `num_experts` small experts of
`moe_intermediate_size`, the k best a token renormalised), under another
mask: the sequence is cut into blocks of `block_length` tokens from
position 0, and a token attends every token of its own and of earlier
blocks (`LlamaConfig.block_causal`). What is new is how it generates
(the family's `block_diffusion_generate`): a block starts as [MASK] ids
and is denoised in at most `denoising_steps` forward passes, each of which
FIXES the masked positions the model is surest of; the settled block's keys
and values are then written and the next block begins. The family's script
spends a forward of its own on that; the serving program writes them in the
pass that opens the next block, beside it (serve/llm/stage.py
`_block_program`: the same ids at the same positions over the same
context, so the same keys). The logits at a masked position predict that
position's OWN token (no shift by one). A prompt's whole blocks are
prefilled and yield no token; its ragged tail opens the first block.

This module holds what the method adds to the Llama family: the config's
generation settings (they are the MODEL's, no scheduling knob), the presets,
and one denoising pass's arithmetic on the logits (`denoise`; `decide`, the
same from the hidden states in front of the head, which the serving
program, serve/llm/stage.py, kind "block", runs in its loop).
Whether a position is masked is a FLAG beside the ids, never a comparison
with `mask_token_id`: a prompt that holds that id is a prompt (the
published script compares ids).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..util import tracing
from .llama import (RESUMES_PREFILL, ExpertFacts, LlamaConfig,  # noqa: F401
                    pass_cost_ratios, pool_spec, serving_cache, serving_model)

REMASKING = ("low_confidence_dynamic", "low_confidence_static", "sequential")


@dataclass(frozen=True)
class SdarConfig(LlamaConfig):
    block_length: int = 4
    denoising_steps: int = 4
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: int = 151669

    def __post_init__(self):
        if self.remasking not in REMASKING:
            raise ValueError(f"remasking {self.remasking!r}: one of "
                             f"{REMASKING}")
        if not 1 <= self.denoising_steps:
            raise ValueError("denoising_steps must be at least 1")
        if self.block_length < 1:
            raise ValueError("block_length must be at least 1")
        # the attention mask is the generation's block
        object.__setattr__(self, "block_causal", self.block_length)


def transfer_schedule(block_length: int, steps: int) -> tuple:
    """How many masked positions pass s fixes at least (the script's
    `get_num_transfer_tokens`): block // steps, the first block % steps
    passes one more."""
    base, more = divmod(block_length, steps)
    return tuple(base + (s < more) for s in range(steps))


def _sample(logits, temperature, top_k, keys):
    """logits [S, B, V] float32 -> (x0 [S, B] int32, the probability the
    model gave it [S, B] float32). Greedy rows (temperature 0): the argmax
    and its softmax probability. Others: a draw from softmax(logits / T)
    cut to the top_k largest (serve/llm/stage.py's sampler, a position a
    key), and its probability under THAT distribution."""
    from ..serve.llm.stage import _MAX_TOP_K

    def greedy(_):
        top = jnp.max(logits, axis=-1)
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                jnp.exp(top - jax.nn.logsumexp(logits, axis=-1)))

    def drawn(_):
        g_tok, g_p = greedy(None)
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None, None]
        topv, _ = jax.lax.top_k(scaled, min(_MAX_TOP_K, logits.shape[-1]))
        kth = jnp.take_along_axis(
            topv, jnp.clip(top_k - 1, 0, topv.shape[-1] - 1)[
                :, None, None], axis=-1)
        cut = jnp.where((top_k[:, None, None] > 0) & (scaled < kth),
                        -jnp.inf, scaled)
        pos_keys = jax.vmap(lambda key: jax.random.split(
            key, logits.shape[1]))(keys)                  # [S, B, 2]
        tok = jax.vmap(jax.vmap(jax.random.categorical))(
            pos_keys, cut).astype(jnp.int32)
        p = jnp.take_along_axis(jax.nn.softmax(cut, axis=-1),
                                tok[..., None], axis=-1)[..., 0]
        hot = (temperature > 0)[:, None]
        return jnp.where(hot, tok, g_tok), jnp.where(hot, p, g_p)

    # a batch of greedy rows pays no top-k over the vocabulary
    return jax.lax.cond(jnp.any(temperature > 0), drawn, greedy, None)


def _transfer(conf, masked, n_fix, cfg: SdarConfig):
    """[S, B] bool: the masked positions this pass fixes, `n_fix` of them
    at least (all, where fewer are masked)."""
    if cfg.remasking == "sequential":
        # the first from the left
        return masked & (jnp.cumsum(masked, axis=-1) <= n_fix)
    conf = jnp.where(masked, conf, -jnp.inf)
    # a position's place among its row's confidences, the highest first
    # (ties: the leftmost first)
    rank = jnp.argsort(jnp.argsort(-conf, axis=-1, stable=True), axis=-1)
    best = masked & (rank < n_fix)
    if cfg.remasking == "low_confidence_static":
        return best
    sure = masked & (conf > cfg.confidence_threshold)
    return jnp.where(sure.sum(-1, keepdims=True) >= n_fix, sure, best)


def _settle(x0, conf, ids, masked, step, cfg: SdarConfig):
    """A pass's candidates x0 and their confidences [S, B] -> (ids, masked,
    fixed) after it: the schedule's share of the masked positions takes
    its candidate."""
    n_fix = jnp.asarray(transfer_schedule(
        cfg.block_length, cfg.denoising_steps), jnp.int32)[step]
    fixed = _transfer(conf, masked, n_fix, cfg)
    return jnp.where(fixed, x0, ids), masked & ~fixed, fixed


def denoise(logits, ids, masked, step, cfg: SdarConfig, temperature, top_k,
            keys):
    """One denoising pass's decision. logits [S, B, V] at the block's
    positions (each predicts its own token), ids / masked [S, B] the block
    before the pass, `step` which pass (traced), temperature / top_k [S],
    keys [S, 2] -> (ids, masked, fixed [S, B] bool) after it."""
    with tracing.scope("rtpu.sample"):
        x0, conf = _sample(logits.astype(jnp.float32), temperature, top_k,
                           keys)
        return _settle(x0, conf, ids, masked, step, cfg)


def decide(hidden, head, ids, masked, step, cfg: SdarConfig, temperature,
           top_k, keys):
    """`denoise` from the block's final-normed hidden states [S, B, h] and
    the head's weights [h, V] (`LlamaModel(..., apply_head=False)`), for the
    serving program. A greedy batch keeps an argmax and a confidence of a
    position's V logits, so its pass decides them where the head's product
    is (ops/head_argmax.py) and writes no logits; a batch with a row that
    draws runs the head and `denoise` as they stand. The program chooses
    from its own `temperature` operand (stage.py: `_device_sample` does
    the same): the engine counts the second kind as
    `block_drawn_dispatches_total`."""
    from ..ops.head_argmax import head_argmax

    def drawn(_):
        with tracing.scope("rtpu.head"):
            logits = jnp.dot(hidden, head.astype(hidden.dtype))
        return denoise(logits, ids, masked, step, cfg, temperature, top_k,
                       keys)

    def greedy(_):
        with tracing.scope("rtpu.head"):
            x0, top, lse = head_argmax(
                hidden.reshape(-1, hidden.shape[-1]), head)
        with tracing.scope("rtpu.sample"):
            return _settle(x0.reshape(ids.shape),
                           jnp.exp(top - lse).reshape(ids.shape), ids,
                           masked, step, cfg)

    return jax.lax.cond(jnp.any(temperature > 0), drawn, greedy, None)


# ---------------------------------------------------------------- registry
# (stage.py: model_family) a generation step fixes a block of a row's tokens
# in passes that rewrite the block's keys: the options built on one token a
# step have nothing to stand on, and `max_model_len` has to hold whole blocks
CANNOT_BE_GIVEN = ("generates by diffusion over blocks of {cfg.block_length} "
               "tokens", {
    "max_model_len": "must hold whole blocks (a block is written whole)",
    "spec_lookahead":
        "verifies a draft under a causal mask one token after the "
        "other, and a block's tokens are fixed in the order the model's "
        "confidence chooses (no draft-and-verify use of the block "
        "program yet)",
    "tp": "runs the jnp attention paths under GSPMD, and the block "
          "step's attention (every query of a block on the row's pages) "
          "has no sharded form that was ever run",
    "pp": "samples on the last stage and feeds the first, and a block's "
          "passes are one program's loop: the next pass's ids are "
          "chosen where the head is",
    "handoff":
        "moves KV pages and ONE pending token, and this model's prefill "
        "yields no token: what it would hand over is a block's state "
        "(ids and which of them are masked), which the blob has no "
        "place for",
})


class BlockFacts:
    """What a block program's dispatches count (serve/llm/stage.py:
    model_family; `_block_program`). Its record's `rows` are (request_id,
    block_len, ctx_tokens) with the block counted in the context, its
    `moe_*` as a decode's; a harvest adds `block_passes` (the forward
    passes the program ran, packed behind its tokens; the first is two
    blocks wide where it settles a row's pending block),
    `block_tokens_fixed` (the tokens its real rows emitted: the engine's
    harvest says) and `block_len`. The engine's block scheduling moves the
    totals of a block's life itself (settled, dropped, no token)."""

    STATS = {
        "block_dispatches_total":
            "block programs enqueued (a model that generates by diffusion "
            "over blocks: every denoising pass of a block, the first of "
            "them two blocks wide where it settles the slot's pending "
            "block)",
        "block_drawn_dispatches_total":
            "of block_dispatches_total, the programs whose batch had a row "
            "at a temperature above 0: their passes wrote the head's "
            "logits and drew from them, where a greedy batch's decide in "
            "one kernel over vocabulary tiles (ops/head_argmax.py)",
        "block_passes_total":
            "forward passes the block programs ran",
        "block_tokens_total":
            "tokens the block programs' real rows emitted; over "
            "block_passes_total it is what an operator trades against "
            "quality",
        "block_rows_total": "real rows of the block programs",
        "block_early_exits_total":
            "block programs that left before denoising_steps passes: every "
            "live row's block was fixed",
        "block_settles_folded_total":
            "pending blocks whose final keys the next block's opening pass "
            "wrote; over block_dispatches_total x rows it is how full the "
            "opening passes' second half runs",
        "block_unsettled_dropped_total":
            "last blocks of a request in its slot, which no pass settles: "
            "nothing reads their keys; with block_settles_folded_total, the "
            "blocks generated",
        "prefill_tokenless_total":
            "prompts whose prefill ended without a token (a block model's: "
            "its first token comes out of its first block)",
    }

    def __init__(self, cfg: SdarConfig):
        self.block = cfg.block_length

    def decode(self, totals: dict, rows, k: int) -> None:
        totals["block_dispatches_total"] += 1
        totals["block_rows_total"] += len(rows)

    def harvest(self, totals: dict, rec: dict, packed):
        if rec["kind"] != "block":
            return None
        passes = int(packed[0])
        totals["block_passes_total"] += passes
        totals["block_tokens_total"] += rec["emitted"]
        totals["block_early_exits_total"] += passes < rec["k"]
        return {"block_passes": passes, "block_tokens_fixed": rec["emitted"],
                "block_len": self.block}


def dispatch_facts(cfg: SdarConfig, engine_config) -> list:
    return ([ExpertFacts(cfg, engine_config, cfg.block_length)]
            if cfg.num_experts else []) + [BlockFacts(cfg)]


CONFIGS = {
    # SDAR-30B-A3B-Chat (huggingface.co/JetLM/SDAR-30B-A3B-Chat
    # config.json; block length, steps and rule from its generation script)
    "sdar-30b-a3b": SdarConfig(
        vocab_size=151936, hidden_size=2048, intermediate_size=6144,
        num_layers=48, num_heads=32, num_kv_heads=4, head_dim=128,
        max_seq_len=32768, rope_theta=1e6, rms_norm_eps=1e-6,
        num_experts=128, num_experts_per_tok=8, moe_intermediate_size=768,
        norm_topk_prob=True, qk_norm=True),
    # 16 experts of which 4, an expert width that is not the dense one's
    "tiny-sdar": SdarConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=256,
        rope_theta=1e6, rms_norm_eps=1e-6, remat=False, num_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=48, qk_norm=True,
        mask_token_id=255),
}


def get_config(name: str, **overrides) -> SdarConfig:
    return dataclasses.replace(CONFIGS[name], **overrides)
