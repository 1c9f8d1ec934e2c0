"""Runner `engine_diffusion`: runner `engine` (the in-process `LLMEngine`
under a serving mix; chipbench/runners/engine.py, reused by import) for a
model that generates by DIFFUSION OVER BLOCKS with a sparse-expert FFN
(SDAR: chipbench/references/sdar_decoder.py).

It adds what `engine` has no place for and changes nothing else:
- the expert keys of the published config.json (`num_experts`,
  `num_experts_per_tok`, `moe_intermediate_size`, `norm_topk_prob`) and the
  configuration's `generation` sizes (`block_length`, `denoising_steps`,
  `remasking`, ...) reach the program and the plain reference;
- a program that cannot generate so (a commit before
  `ray_tpu/models/sdar.py`) is refused at once, before JAX is touched, with
  exit code 1 and no result line;
- the trace's `jit_run_block(` programs join `chipbench/paired.py`'s
  pairing (its `PROGRAMS` table gains the kind for this process: the
  engine's records of kind `block` then pair with their programs, for this
  cell's own readers and for `chipbench/stamped.py`'s check);
- the output check is its own, because a step yields a block and not a
  token: (a) the logits of every denoising pass through the engine's model,
  params and a PagedCache against the reference's full forward over the
  settled tokens and the program's own block state before that pass; (b)
  the tokens the ENGINE emits through add_request / step(), each against
  the reference's best logit at its position over the block state rebuilt
  from the order `OutputDelta.fixed_pass` reports; (c) unjudged: how often
  the position the program fixed is not the reference's most confident,
  and `engine_moe`'s expert-choice numbers (one of them judged).
"""

from __future__ import annotations

import contextlib
import functools
import re
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from chipbench import compare, paired
from chipbench.cell import BenchError
from chipbench.runners import engine as base
from chipbench.runners import engine_moe

EXPERT_KEYS = ("num_experts", "num_experts_per_tok", "moe_intermediate_size",
               "norm_topk_prob")
GENERATION_KEYS = ("block_length", "denoising_steps", "remasking",
                   "confidence_threshold", "mask_token_id")
AGREE = engine_moe.AGREE
# the reference's sequences are padded to multiples of this: few shapes;
# and so many tokens' worth of them go through it together
PAD = 128
REF_TOKENS = 2048
# the block program in a device trace, beside paired.py's two
paired.PROGRAMS.setdefault("block", re.compile(r"^jit_run_block\("))

_dense_overrides = base.model_overrides
_dense_probe = base._shape_probe


def model_overrides(published: Dict[str, Any]) -> Dict[str, Any]:
    """`engine`'s nine dense keys, the expert layer's four, the
    generation's."""
    return {**_dense_overrides(published), "qk_norm": True,
            **{k: published[k] for k in EXPERT_KEYS + GENERATION_KEYS}}


def _shape_probe(econf):
    """The program's parameter tree as shapes, through the family's own
    module (`engine`'s probe knows the Llama presets only)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm.stage import (init_params, model_family,
                                         serve_model_config)

    cfg = serve_model_config(econf)
    model = model_family(econf.model).serving_model(cfg)
    return jax.eval_shape(lambda: init_params(
        model, jnp.zeros((1, 8), jnp.int32), jax.random.PRNGKey(0)))


@contextlib.contextmanager
def _family_overrides():
    """`engine.Runner.setup` builds its EngineConfig and its shape probe
    from the module's functions; for the length of a set-up they are this
    module's (as `engine_moe` does: that file may not be edited here)."""
    base.model_overrides, base._shape_probe = model_overrides, _shape_probe
    try:
        yield
    finally:
        base.model_overrides, base._shape_probe = (_dense_overrides,
                                                   _dense_probe)


def _require_block_program() -> None:
    import importlib.util

    if importlib.util.find_spec("ray_tpu.models.sdar") is None:
        raise BenchError(
            "this program cannot generate by diffusion over blocks "
            "(ray_tpu/models/sdar.py is missing: its engine yields one "
            "token a row and step under a causal mask), so it cannot run "
            "an SDAR configuration")


class Runner(base.Runner):
    def __init__(self, cell, seed: int, seconds: float, log):
        _require_block_program()
        super().__init__(cell, seed, seconds, log)
        self.published.update({k: cell.config[k] for k in EXPERT_KEYS})
        self.published.update(cell.config["generation"])
        missing = set(GENERATION_KEYS) - set(self.published)
        if missing:
            raise BenchError(f"the configuration's `generation` lacks "
                             f"{sorted(missing)}")

    def setup(self, warm: bool = True) -> Dict[str, Any]:
        with _family_overrides():
            check = super().setup(warm)
        got = self.engine.model_cfg
        for key in EXPERT_KEYS + GENERATION_KEYS:
            if getattr(got, key) != self.published[key]:
                raise BenchError(f"the engine runs {key}="
                                 f"{getattr(got, key)!r}; the configuration "
                                 f"says {self.published[key]!r}")
        return check

    # ------------------------------------------------------------ the check
    def _check_outputs(self) -> Dict[str, Any]:
        spec, cfg = self.mix["check"], dict(self.published)
        block = cfg["block_length"]
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, 0xC4EC])
        prompts = [rng.integers(0, self.vocab, int(n)).tolist()
                   for n in spec["prompt_lens"]]
        ep = spec["engine_prompts"]
        eprompts = [rng.integers(0, self.vocab, int(n)).tolist()
                    for n in np.rint(np.linspace(ep["min_len"], ep["max_len"],
                                                 ep["count"]))]
        t0 = time.monotonic()
        emitted = engine_generate(self.engine, eprompts,
                                  int(ep["decode_tokens"]))
        blocks = [int(spec["blocks"])] * len(prompts)
        # fewer after the longest prompt: each pass costs the reference a
        # full forward over all of it
        blocks[int(np.argmax(spec["prompt_lens"]))] = int(
            spec["blocks_after_longest"])
        logit_states, settled = paged_block_logits(self.engine, prompts,
                                                   max(blocks))
        logit_states = [s for s in logit_states if s[3] < blocks[s[0]]]
        token_states = token_states_of(eprompts, emitted, cfg)
        # whole sequences for the expert-choice numbers: the prompts with
        # the blocks the logits path settled behind them
        agree = [(seq[:len(seq) // block * block], [0]) for seq in settled]
        self.check_sample = {"logit_states": logit_states,
                             "token_states": token_states,
                             "logit_seqs": agree}
        self.log(f"output check: the program's part in "
                 f"{time.monotonic() - t0:.1f} s ({len(logit_states)} "
                 f"denoising passes' logits, {sum(len(e[0]) for e in emitted)}"
                 f" tokens of the engine's)")
        ref_w = self.reference.weights_from_program_tree(self.engine.params)
        out, notes = judge(block_rows(self.reference, ref_w, cfg),
                           self.reference, logit_states, token_states, cfg)
        check = out.result(self.cell.config["limits"])
        check["notes"].update(notes)
        row, more = engine_moe.expert_choice(
            engine_moe._program_forward(self.engine.model,
                                        self.engine.params),
            engine_moe.reference_forward(self.reference, ref_w, cfg,
                                         "float32"),
            agree, self.cell.config["limits"])
        check["numbers"].append(row)
        check["correct"] = check["correct"] and row["ok"]
        check["notes"].update(more)
        return check

    def run_window(self, tracer) -> None:
        """`engine`'s window; the block step's and the expert layer's
        cumulative counters join the run's `counters:` line (set-up's
        check included: they are the engine's since it was built)."""
        super().run_window(tracer)
        st = self.engine.stats()
        self.counters.update({k: st[k] for k in st if k.startswith(
            ("block_", "moe_", "prefill_tokenless"))})
        self.counters["moe_tile_fill"] = round(
            st["moe_assignments_total"] / max(st["moe_tile_rows_total"], 1),
            4)

    def work_facts(self) -> Dict[str, Any]:
        return {"kind": "serve", "generation": "block diffusion"}


# ------------------------------------------------------------------ helpers
def engine_generate(engine, prompts: List[List[int]], g: int
                    ) -> List[Tuple[List[int], List[int]]]:
    """`engine._engine_generate` for a model whose deltas are blocks: per
    prompt (the tokens, the denoising pass that fixed each)."""
    from ray_tpu.serve.llm import SamplingParams

    out = {f"check{i}": ([], []) for i in range(len(prompts))}
    for i, p in enumerate(prompts):
        engine.add_request(f"check{i}", p, SamplingParams(
            max_tokens=g, temperature=0.0))
    done, deadline = 0, time.monotonic() + 300
    while done < len(prompts):
        if time.monotonic() > deadline:
            raise BenchError("the engine did not finish the check prompts")
        for d in engine.step():
            if d.new_token_ids:
                if d.fixed_pass is None:
                    raise BenchError("the engine's deltas carry no "
                                     "fixed_pass: it does not generate by "
                                     "blocks")
                out[d.request_id][0].extend(d.new_token_ids)
                out[d.request_id][1].extend(d.fixed_pass)
            done += bool(d.finished)
    return [out[f"check{i}"] for i in range(len(prompts))]


def token_states_of(prompts, emitted, cfg: Dict[str, Any]) -> List[tuple]:
    """(sequence before the pass, positions it fixed, the tokens there,
    which positions were masked) for every (block, pass) that fixed a token
    the engine emitted, rebuilt from the order it reported."""
    b, mask_id = cfg["block_length"], cfg["mask_token_id"]
    states = []
    for prompt, (tokens, passes) in zip(prompts, emitted):
        seq = list(prompt)
        at = 0
        while at < len(tokens):
            start = len(seq) // b * b
            known = len(seq) - start            # a first block's prompt tail
            n = min(b - known, len(tokens) - at)
            toks, when = tokens[at:at + n], passes[at:at + n]
            if known + n < b:
                # a last block cut by max_tokens: when the dropped positions
                # were fixed is not reported, so its states cannot be rebuilt
                break
            for s in sorted(set(when)):
                state = seq + [t if w < s else mask_id
                               for t, w in zip(toks, when)]
                fixed = [i for i, w in enumerate(when) if w == s]
                states.append((
                    state, [len(seq) + i for i in fixed],
                    [toks[i] for i in fixed],
                    [len(seq) + i for i, w in enumerate(when) if w >= s]))
            seq += toks
            at += n
    return states


def paged_block_logits(engine, prompts: List[List[int]], n_blocks: int):
    """Prefill each prompt's whole blocks, then denoise `n_blocks` blocks
    pass by pass, through the engine's model and params and a small
    PagedCache pool of the benchmark's own (same layout, page size and
    kernels as the engine's), with the program's own rule
    (`ray_tpu.models.sdar.denoise`) between the passes. Returns
    ([(prompt index, the sequence BEFORE the pass: settled tokens + the
    block as it stood, the float32 logits at the block's positions [B, V],
    block index)], the final sequences)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import PagedCache
    from ray_tpu.models.sdar import denoise

    model, mc, page = engine.model, engine.model_cfg, engine.config.page_size
    B, steps = mc.block_length, mc.denoising_steps
    L, b = mc.num_layers, len(prompts)
    longest = max(len(p) for p in prompts)
    sb = -(-longest // 128) * 128
    mp = -(-(longest + (n_blocks + 1) * B) // page)
    mp = -(-mp // 8) * 8
    pool = jnp.zeros((L, 1 + b * mp, mc.num_kv_heads, page,
                      2 * mc.head_dim_), engine.kv_pages.dtype)
    bt = jnp.asarray(np.arange(1, 1 + b * mp, dtype=np.int32).reshape(b, mp))

    def run(params, kv_pages, total, ids, positions, block_step):
        pc = PagedCache(
            kv_pages=kv_pages,
            block_tables=jnp.broadcast_to(bt, (L,) + bt.shape),
            total_lens=jnp.broadcast_to(total, (L,) + total.shape),
            block_step=block_step)
        (logits, new_pc), _ = model.apply(
            {"params": params}, ids, positions=positions, kv_caches=pc,
            token_mask=positions < total[:, None], mutable=["routing"])
        return logits.astype(jnp.float32), new_pc.kv_pages

    step = jax.jit(run, donate_argnums=(1,), static_argnums=(5,))
    rule = jax.jit(lambda logits, ids, masked, s: denoise(
        logits, ids, masked, s, mc, jnp.zeros((b,), jnp.float32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b, 2), jnp.uint32))[:2])
    whole = np.asarray([len(p) // B * B for p in prompts], np.int32)
    ids = np.zeros((b, sb), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :whole[i]] = p[:whole[i]]
    positions = np.broadcast_to(np.arange(sb, dtype=np.int32), (b, sb))
    _, pool = step(engine.params, pool, jnp.asarray(whole), jnp.asarray(ids),
                   jnp.asarray(positions), False)
    seqs = [list(p[:n]) for p, n in zip(prompts, whole)]
    tails = [list(p[n:]) for p, n in zip(prompts, whole)]
    states = []
    for blk in range(n_blocks):
        start = np.asarray([len(s) for s in seqs], np.int32)
        block = np.full((b, B), mc.mask_token_id, np.int32)
        masked = np.ones((b, B), bool)
        for i, tail in enumerate(tails):
            block[i, :len(tail)] = tail
            masked[i, :len(tail)] = False
        tails = [[]] * b
        block, masked = jnp.asarray(block), jnp.asarray(masked)
        pos = jnp.asarray(start[:, None] + np.arange(B, dtype=np.int32))
        total = jnp.asarray(start + B)
        for s in range(steps + 1):
            logits, pool = step(engine.params, pool, total, block, pos, True)
            if s == steps or not bool(masked.any()):
                break                       # that was the settling pass
            now, lg = np.asarray(block), np.asarray(logits)
            for i in range(b):
                if bool(masked[i].any()):
                    states.append((i, seqs[i] + now[i].tolist(), lg[i], blk))
            block, masked = rule(logits, block, masked, s)
        for i, row in enumerate(np.asarray(block).tolist()):
            seqs[i] += row
    del pool
    return states, seqs


def judge(forward, reference, logit_states, token_states,
          cfg: Dict[str, Any]):
    """-> (a `compare.LogitCheck` holding (a) and (b), unjudged notes).
    `forward(seqs) -> [[B, V]]`: the reference's logits at the positions of
    the LAST block of each sequence (`block_rows`), or the control's in its
    place (then `logit_states` carry the float32 reference's logits where
    the program's were)."""
    b = cfg["block_length"]
    out = compare.LogitCheck()
    want = forward([s[1] for s in logit_states]
                   + [s[0] for s in token_states])
    for (_, _, logits, _), ref in zip(logit_states, want):
        out.add_logits(logits, ref)
    differ = passes = 0
    for (seq, fixed, tokens, still_masked), rows in zip(
            token_states, want[len(logit_states):]):
        lo = len(seq) - b
        out.add_tokens(rows[np.asarray(fixed) - lo], tokens)
        # where the reference is surest among what was masked then
        _, conf = reference.confidence(rows[np.asarray(still_masked) - lo])
        if cfg["remasking"].startswith("low_confidence") and len(fixed) == 1:
            passes += 1
            differ += still_masked[int(conf.argmax())] != fixed[0]
    return out, {"positions_differ": differ, "positions_compared": passes}


def block_rows(reference, weights, cfg: Dict[str, Any],
               precision: str = "float32"):
    """`forward` for `judge`: sequences (whole blocks) -> the reference's
    float32 logits [B, V] at each one's last block, at `precision`. Only
    those rows leave the device. Sequences of one padded length go through
    the reference `REF_TOKENS // length` at a time (its arithmetic a
    sequence is the same; the weights are read once a batch)."""
    import jax
    import jax.numpy as jnp

    b = cfg["block_length"]

    @functools.partial(jax.jit, static_argnums=(3,))
    def fn(w, ids, rows, batch):
        return reference.forward_rows(w, ids, rows, cfg, precision, batch)

    def forward(seqs):
        out = [None] * len(seqs)
        by_len = {}
        for i, seq in enumerate(seqs):
            by_len.setdefault(-(-len(seq) // PAD) * PAD, []).append(i)
        for n, group in sorted(by_len.items()):
            batch = max(1, REF_TOKENS // n)
            for at in range(0, len(group), batch):
                part = group[at:at + batch]
                # a short last batch repeats its last sequence: one shape
                full = part + part[-1:] * (batch - len(part))
                ids = jnp.concatenate([reference.padded(seqs[i], n)
                                       for i in full])
                rows = jnp.asarray([np.arange(len(seqs[i]) - b, len(seqs[i]))
                                    for i in full], jnp.int32)
                got = np.asarray(fn(weights, ids, rows, batch))
                for i, lg in zip(part, got):
                    out[i] = lg
        return out

    return forward


def control_states(forward, logit_states, token_states, block: int):
    """The check's sample as it would read if the program computed as
    `forward` does (`block_rows` at a precision below the stated one,
    tools/read_limits_sdar.py): its logits where the program's were, and at
    each of the engine's passes the token it would have put there."""
    got = forward([s[1] for s in logit_states]
                  + [s[0] for s in token_states])
    logits = [(i, seq, lg, blk)
              for (i, seq, _, blk), lg in zip(logit_states, got)]
    tokens = [(seq, fixed, lg[np.asarray(fixed) - (len(seq) - block)
                              ].argmax(-1).tolist(), masked)
              for (seq, fixed, _, masked), lg in zip(
                  token_states, got[len(logit_states):])]
    return logits, tokens
