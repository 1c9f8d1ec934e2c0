"""Runner `engine_cca`: runner `engine_mla` (itself `engine_sala`: the
streamed check, the drain after the window; with the chosen experts as the
judged selection; reused by import) for a model whose attention runs inside
a compressed latent (CCA) beside a tail a decode slot, with top-1 experts
chosen by an MLP router whose state runs down the stack (the ZAYA1 family).

It adds what those have no place for and changes nothing else:
- the family's published config.json keys (`cca_time0`, `cca_time1`,
  `partial_rotary_factor`, `rope_parameters`, `router_hidden_size`,
  `num_experts`, `moe_intermediate_size`, `tie_word_embeddings`) reach the
  program under its names and the plain reference under theirs;
- a program without `ray_tpu/models/zaya.py` (a commit before it) is
  refused at once, before JAX is touched, with exit code 1 and no result
  line;
- the weights come from `chipbench/weights_cca.py`;
- the check's own pool has two kinds (`pool_spec`: one slot's tail beside
  the pages), its 5000-token prompt's second pass resumes from that tail,
  and the chosen expert of EVERY layer is compared with the reference's
  (`models/zaya.py: ZayaRouter` sows it as `MoEMLP` sows a share's);
- one judged number more, `logit_rel_rms_err_behind_a_boundary`: the
  logits' error over the FIRST position of every program of the check that
  does not start a sequence (a resumed pass, every decode step): the
  positions whose q, k and v are made from the tail the program before
  left in the slot. A tail lost there moves that one position by half the
  logits' rms and the all-position numbers by nothing they can show;
- `zero_tail_numbers`: the check's logits with the tail zeroed after every
  program, for tools/read_limits_cca.py (it has to fail that limit).
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Dict

import numpy as np

from chipbench import compare, control, weights_cca
from chipbench.cell import BenchError
from chipbench.runners import engine as base
from chipbench.runners import engine_mla as mla
from chipbench.runners import engine_sala as sala

# the family's keys that the program's config has under the published name
SAME_NAME = ("cca_time0", "cca_time1", "partial_rotary_factor",
             "router_hidden_size", "num_experts", "num_experts_per_tok",
             "moe_intermediate_size", "tie_word_embeddings", "head_dim")
# all of them, copied from the configuration to the reference's cfg
FAMILY_KEYS = SAME_NAME + ("rope_parameters", "layer_types", "published")
# what the program reads only as a switch it has one setting of
BUILT = {"model_type": "zaya", "hidden_act": "silu", "attention_bias": False,
         "lm_head_bias": False, "sliding_window": None,
         "tie_word_embeddings": True}


def rope_theta(published: Dict[str, Any]) -> float:
    """Every layer is `hybrid`: its entry of `rope_parameters`."""
    rp = published["rope_parameters"]["hybrid"]
    if rp["rope_type"] != "default" or (
            rp["partial_rotary_factor"] != published["partial_rotary_factor"]):
        raise BenchError(f"rope_parameters.hybrid {rp}: only the default "
                         f"rotation at the config's partial_rotary_factor")
    return float(rp["rope_theta"])


def model_overrides(published: Dict[str, Any]) -> Dict[str, Any]:
    """The published config.json keys (and the configuration's cut) as the
    program's ZayaConfig names."""
    n = published["num_hidden_layers"]
    if set(published["layer_types"][:n]) != {"hybrid"}:
        raise BenchError("a layer that is not `hybrid`: the program builds "
                         "no sliding-window CCA layer")
    return {
        "vocab_size": published["vocab_size"],
        "hidden_size": published["hidden_size"],
        # no dense FFN anywhere: the key the base config requires
        "intermediate_size": published["moe_intermediate_size"],
        "num_layers": n,
        "num_heads": published["num_attention_heads"],
        "num_kv_heads": published["num_key_value_heads"],
        "rope_theta": rope_theta(published),
        "rms_norm_eps": float(published["rms_norm_eps"]),
        "norm_topk_prob": False,
        **{k: published[k] for k in SAME_NAME},
    }


def _require_cca_program() -> None:
    import importlib.util

    if importlib.util.find_spec("ray_tpu.models.zaya") is None:
        raise BenchError(
            "this program has no attention inside a compressed latent and "
            "no MLP router (ray_tpu/models/zaya.py, preset zaya1-8b): it "
            "cannot run a ZAYA1 configuration")


@contextlib.contextmanager
def _cca_set_up():
    """As `engine_sala._sala_set_up`: for the length of a set-up the base
    runner's `model_overrides`, `_shape_probe` and `weights` are this
    family's."""
    mine = {"model_overrides": model_overrides,
            "_shape_probe": sala._shape_probe, "weights": weights_cca}
    theirs = {k: getattr(base, k) for k in mine}
    for k, v in mine.items():
        setattr(base, k, v)
    try:
        yield
    finally:
        for k, v in theirs.items():
            setattr(base, k, v)


BEHIND = "logit_rel_rms_err_behind_a_boundary"


class TailCheck(mla.HeldExpertCheck):
    """`HeldExpertCheck` + `BEHIND`, judged: `behind` holds the positions
    whose program started from a tail another program left."""

    def __init__(self):
        super().__init__()
        self.behind = compare.LogitCheck()

    def result(self, limits):
        out = super().result(limits)
        if self.behind._n:
            value = self.behind.result(
                {"logit_rel_rms_err": math.inf,
                 "logit_max_err_over_rms": math.inf})["numbers"][0]["value"]
            if BEHIND not in limits:
                raise KeyError(f"no limit for {BEHIND!r} in the configuration")
            ok = value <= float(limits[BEHIND])
            out["numbers"].append({"name": BEHIND, "value": value,
                                   "limit": float(limits[BEHIND]), "ok": ok})
            out["correct"] = out["correct"] and ok
            out["notes"]["positions_behind_a_boundary"] = (
                self.behind._n // self.behind_width)
        return out


def _compare_sequence(paged, check: TailCheck, reference, ref_w, cfg,
                      prompt, g: int, fed=None):
    """One sequence of the logits check: the program's passes and decode
    steps through `paged`, every block against the reference's head over
    the reference's hidden states (`engine_sala.Comparer`), and the first
    row of every block that does not start the sequence into
    `check.behind`. `fed`: the tokens a run before fed back (None: this
    run finds them first, uncompared). -> the tokens fed back."""
    import jax

    if fed is None:
        fed = paged.run(prompt, g, lambda *a: None)
    seq = prompt + fed[:-1]
    h_ref, sel_ref = sala._reference_hidden(reference, ref_w, cfg, seq,
                                            want_selection=True)
    every = sala.Comparer(check, reference, ref_w, h_ref, sel_ref, 0)

    def on_block(start, logits, n, picked=None):
        every(start, logits, n, picked)
        if start > 0:
            with jax.default_matmul_precision("highest"):
                want = reference.head(ref_w, h_ref[start:start + 1],
                                      "float32")
            check.behind_width = logits.shape[-1]
            check.behind.add_logits(np.asarray(logits[:1], np.float32),
                                    np.asarray(want))

    again = paged.run(prompt, g, on_block)
    del h_ref, sel_ref
    return fed, again


class Runner(mla.Runner):
    def __init__(self, cell, seed: int, seconds: float, log):
        _require_cca_program()
        base.Runner.__init__(self, cell, seed, seconds, log)
        other = {k: cell.config[k] for k, v in BUILT.items()
                 if cell.config[k] != v}
        if other:
            raise BenchError(f"the configuration sets {other}: the program "
                             f"builds {BUILT} only")
        self.published.update({k: cell.config[k] for k in FAMILY_KEYS})
        self.published["num_hidden_layers"] = cell.config["num_hidden_layers"]
        # what the reference reads of the rotation
        self.published["rope_theta"] = rope_theta(cell.config)

    def setup(self, warm: bool = True) -> Dict[str, Any]:
        with _cca_set_up():
            check = base.Runner.setup(self, warm)
        got, pub = self.engine.model_cfg, self.published
        sizes = (got.num_layers, got.num_heads, got.num_kv_heads,
                 got.head_dim_, got.num_experts, got.num_experts_per_tok,
                 got.expert_width, got.router_hidden_size, got.vocab_size)
        want = (pub["num_hidden_layers"], pub["num_attention_heads"],
                pub["num_key_value_heads"], pub["head_dim"],
                pub["num_experts"], pub["num_experts_per_tok"],
                pub["moe_intermediate_size"], pub["router_hidden_size"],
                pub["vocab_size"])
        if sizes != want:
            raise BenchError(f"the engine runs {sizes}; the configuration "
                             f"says {want}")
        st = self.engine.stats()
        self.log(f"state: {got.num_layers} CCA layers' tails "
                 f"{st['cca_tail_pool_bytes']:,} bytes "
                 f"({got.tail_bytes_row():,} a slot) beside the pages; all "
                 f"{got.num_experts} experts of {got.expert_width} a layer, "
                 f"{got.num_experts_per_tok} a token; "
                 f"{st['prefill_resumed_passes_total']} resumed passes, "
                 f"{st['cca_resumed_rows_total']} rows resumed from a tail "
                 f"and {st['cca_tail_resets_total']} started from zeros so "
                 f"far; prefix reuse off")
        return check

    def _compare(self, prompts, eprompts, emitted, g: int):
        import time

        paged = mla.PagedLogits(self.engine, max(map(len, prompts)) + g)
        cfg = dict(self.published)
        ref_w = self.reference.weights_from_program_tree(self.engine.params)
        out, fed_all = TailCheck(), []
        for p in prompts:
            t0 = time.monotonic()
            fed, again = _compare_sequence(paged, out, self.reference, ref_w,
                                           cfg, p, g)
            if again != fed:
                raise BenchError("the check's second run of the program "
                                 "fed back other tokens than its first")
            fed_all.append(fed)
            self.log(f"check: {len(p)} + {g} positions; program, reference "
                     f"and comparison {time.monotonic() - t0:.1f} s")
        t0 = time.monotonic()
        for rows, toks in zip(control.reference_rows(
                self.reference, ref_w, cfg, "float32", eprompts, emitted),
                emitted):
            out.add_tokens(rows, toks)
        self.log(f"check: the reference on the engine's sequences in "
                 f"{time.monotonic() - t0:.1f} s")
        return out, fed_all


control_numbers = mla.control_numbers


def zero_tail_numbers(runner: Runner, limits: Dict[str, float]
                      ) -> Dict[str, Any]:
    """The check's logits path on a sound run's sequences with the slot's
    tail ZEROED after every program (a boundary that drops it): what
    `BEHIND`'s limit has to refuse. Only the logits are read (the engine's
    own tokens are the sound run's)."""
    import jax.numpy as jnp

    sample = runner.check_sample
    prompts = [p for p, _ in sample["logit_seqs"]]
    g = len(sample["logit_seqs"][0][1])
    cfg = dict(runner.published)
    ref_w = runner.reference.weights_from_program_tree(runner.engine.params)
    paged = mla.PagedLogits(runner.engine, max(map(len, prompts)) + g)
    step = paged.step

    def dropping(params, pool, *rest, **kw):
        logits, pool, held = step(params, pool, *rest, **kw)
        return logits, dict(pool, cca_tail=jnp.zeros_like(
            pool["cca_tail"])), held

    paged.step = dropping
    out = TailCheck()
    for p, fed in sample["logit_seqs"]:
        _compare_sequence(paged, out, runner.reference, ref_w, cfg, p, g,
                          fed)
    return out.result(limits)
