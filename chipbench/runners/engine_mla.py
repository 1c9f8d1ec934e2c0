"""Runner `engine_mla`: runner `engine_sala` (itself runner `engine` under
long prompts: the streamed check, the drain after the window; reused by
import) for a model with latent attention (MLA) and one chip's share of
sigmoid-routed experts beside a shared expert (the Kimi-K2 / DeepSeek-V3
family).

It adds what those have no place for and changes nothing else:
- the family's published config.json keys (the latent's ranks and head
  sizes, `rope_scaling`, the router's keys) and the configuration's own cut
  (`published`, `held`: which layers, experts and vocabulary rows this chip
  has) reach the program under its names and the plain reference under
  theirs;
- a program without `ray_tpu/models/kimi.py` (a commit before it) is
  refused at once, before JAX is touched, with exit code 1 and no result
  line;
- the weights come from `chipbench/weights_mla.py`;
- beside the judged numbers, as NOTES: the share of (position, expert
  layer) whose chosen HELD experts are not the float32 reference's (a
  near-tied eighth and ninth choice flips under bf16; only a flip that
  touches a held expert moves anything here), and the logits' error over
  the positions where every layer's agree (`StreamedLogitCheck`'s
  `*_where_selection_agrees`, the selection being the held experts).
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict

from chipbench import control, weights_mla
from chipbench.cell import BenchError
from chipbench.runners import engine as base
from chipbench.runners import engine_sala as sala

# the family's keys, copied from the configuration to the reference's cfg
FAMILY_KEYS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
               "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace",
               "moe_intermediate_size", "n_routed_experts",
               "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
               "routed_scaling_factor", "scoring_func", "rope_scaling",
               "published", "held")


def model_overrides(published: Dict[str, Any]) -> Dict[str, Any]:
    """The published config.json keys (and the configuration's cut) as the
    program's KimiConfig names."""
    rs = published["rope_scaling"]
    if rs["type"] != "yarn":
        raise BenchError(f"rope_scaling type {rs['type']!r}: only yarn")
    first, end = published["held"]["experts"]
    if end - first != published["n_routed_experts"]:
        raise BenchError("held.experts does not span n_routed_experts")
    return {
        "vocab_size": published["vocab_size"],
        "hidden_size": published["hidden_size"],
        "intermediate_size": published["intermediate_size"],
        "num_layers": published["num_hidden_layers"],
        "num_heads": published["num_attention_heads"],
        "num_kv_heads": published["num_key_value_heads"],
        "rope_theta": float(published["rope_theta"]),
        "rms_norm_eps": float(published["rms_norm_eps"]),
        **{k: published[k] for k in FAMILY_KEYS[:7]},
        # the share: the held experts, the router's width, the first held
        "num_experts": published["n_routed_experts"],
        "n_routed_experts": published["published"]["n_routed_experts"],
        "expert_first": first,
        "n_shared_experts": published["n_shared_experts"],
        "num_experts_per_tok": published["num_experts_per_tok"],
        "norm_topk_prob": published["norm_topk_prob"],
        "routed_scaling_factor": float(published["routed_scaling_factor"]),
        "moe_scoring": published["scoring_func"],
        "rope_factor": float(rs["factor"]),
        "rope_original_max": int(rs["original_max_position_embeddings"]),
        "rope_beta_fast": float(rs["beta_fast"]),
        "rope_beta_slow": float(rs["beta_slow"]),
        "rope_mscale": float(rs["mscale"]),
        "rope_mscale_all_dim": float(rs["mscale_all_dim"]),
    }


def _require_mla_program() -> None:
    import importlib.util

    if importlib.util.find_spec("ray_tpu.models.kimi") is None:
        raise BenchError(
            "this program has no latent attention or expert share "
            "(ray_tpu/models/kimi.py, preset kimi-k2.5): it cannot run a "
            "Kimi-K2.5 configuration")


class PagedLogits(sala.PagedLogits):
    """`engine_sala.PagedLogits` whose selection is the chosen HELD
    experts: `on_block` gets [L_moe, n, 1, held] bool."""

    def __init__(self, engine, longest: int):
        import jax

        super().__init__(engine, longest)
        family, mc, model = self.family, engine.model_cfg, engine.model

        def run(params, pool, bt, total, ids, positions, slots, ctx):
            cache = family.serving_cache(mc, pool, bt, total, slots,
                                         ctx_pages=ctx)
            (logits, new), sown = model.apply(
                {"params": params}, ids, positions=positions,
                kv_caches=cache, mutable=["selection"])
            held = jax.tree.leaves(sown.get("selection", {}))
            # the expert run sows [L_moe, B, S, 1, held]
            return logits[0], new.pool, (held[0][:, 0] if held else None)

        self.step = jax.jit(run, donate_argnums=(1,),
                            static_argnames=("ctx",))


AGREE = "logit_rel_rms_err_where_held_experts_agree"


class HeldExpertCheck(sala.StreamedLogitCheck):
    """`StreamedLogitCheck` whose error over the positions where every
    expert layer chose the reference's HELD experts is JUDGED, beside the
    all-position number (as `mixtral-chat`'s and `sdar-30b-a3b-chat`'s
    `..._where_experts_agree`): a flipped near-tie that touches a held
    expert moves a position's logits by several times what rounding does,
    so the agreeing positions read the arithmetic and the all-position
    number reads both."""

    def result(self, limits):
        out = super().result(limits)
        value = out["notes"].get("logit_rel_rms_err_where_selection_agrees")
        if value is not None:
            if AGREE not in limits:
                raise KeyError(f"no limit for {AGREE!r} in the configuration")
            ok = value <= float(limits[AGREE])
            out["numbers"].append({"name": AGREE, "value": value,
                                   "limit": float(limits[AGREE]), "ok": ok})
            out["correct"] = out["correct"] and ok
        return out


@contextlib.contextmanager
def _mla_set_up():
    """As `engine_sala._sala_set_up`: for the length of a set-up the base
    runner's `model_overrides`, `_shape_probe` and `weights` are this
    family's."""
    mine = {"model_overrides": model_overrides,
            "_shape_probe": sala._shape_probe, "weights": weights_mla}
    theirs = {k: getattr(base, k) for k in mine}
    for k, v in mine.items():
        setattr(base, k, v)
    try:
        yield
    finally:
        for k, v in theirs.items():
            setattr(base, k, v)


class Runner(sala.Runner):
    def __init__(self, cell, seed: int, seconds: float, log):
        _require_mla_program()
        base.Runner.__init__(self, cell, seed, seconds, log)
        self.published.update({k: cell.config[k] for k in FAMILY_KEYS})
        self.published["num_hidden_layers"] = cell.config["num_hidden_layers"]
        # what the reference reads of the share
        self.published["expert_first"] = cell.config["held"]["experts"][0]

    def setup(self, warm: bool = True) -> Dict[str, Any]:
        with _mla_set_up():
            check = base.Runner.setup(self, warm)
        got, pub = self.engine.model_cfg, self.published
        held = (got.expert_first, got.expert_first + got.num_experts)
        if (list(held) != list(pub["held"]["experts"])
                or got.n_routed_experts != pub["published"]["n_routed_experts"]
                or got.vocab_size != pub["held"]["vocab_rows"][1]):
            raise BenchError(
                f"the engine holds experts {held} of "
                f"{got.n_routed_experts} and {got.vocab_size} vocabulary "
                f"rows; the configuration says {pub['held']} of "
                f"{pub['published']}")
        st = self.engine.stats()
        self.log(f"state: latent pool {st['latent_pool_bytes']:,} bytes "
                 f"({got.latent_lanes} lanes a token and layer); experts "
                 f"{held} of {got.n_routed_experts} a layer; "
                 f"{st['prefill_resumed_passes_total']} resumed passes and "
                 f"{st['mla_prefill_ctx_chunks_total']} context chunks "
                 f"materialised so far")
        return check

    def _compare(self, prompts, eprompts, emitted, g: int):
        import time

        paged = PagedLogits(self.engine, max(map(len, prompts)) + g)
        cfg = dict(self.published)
        ref_w = self.reference.weights_from_program_tree(self.engine.params)
        out = HeldExpertCheck()
        fed_all = []
        for p in prompts:
            t0 = time.monotonic()
            fed = paged.run(p, g, lambda *a: None)
            seq = p + fed[:-1]
            h_ref, sel_ref = sala._reference_hidden(
                self.reference, ref_w, cfg, seq, want_selection=True)
            h_ref.block_until_ready()
            t1 = time.monotonic()
            again = paged.run(p, g, sala.Comparer(
                out, self.reference, ref_w, h_ref, sel_ref, 0))
            if again != fed:
                raise BenchError("the check's second run of the program "
                                 "fed back other tokens than its first")
            fed_all.append(fed)
            self.log(f"check: {len(p)} + {g} positions; program + reference "
                     f"hidden {t1 - t0:.1f} s, compared in "
                     f"{time.monotonic() - t1:.1f} s")
            del h_ref, sel_ref
        t0 = time.monotonic()
        for rows, toks in zip(control.reference_rows(
                self.reference, ref_w, cfg, "float32", eprompts, emitted),
                emitted):
            out.add_tokens(rows, toks)
        self.log(f"check: the reference on the engine's sequences in "
                 f"{time.monotonic() - t0:.1f} s")
        return out, fed_all


def control_numbers(reference, ref_w, cfg: Dict[str, Any], precision: str,
                    sample: Dict[str, Any], limits: Dict[str, float]
                    ) -> Dict[str, Any]:
    """`engine_sala.control_numbers` with the held experts as the
    selection: the reference at `precision`, teacher-forced along a sound
    run's sequences, against the float32 reference, streamed."""
    import jax

    out = HeldExpertCheck()
    head = jax.jit(lambda w, h: reference.head(w, h, precision))
    block = sala.COMPARE_BLOCK
    with jax.default_matmul_precision("highest"):
        for prompt, fed in sample["logit_seqs"]:
            seq = prompt + fed[:-1]
            h32, sel32 = sala._reference_hidden(reference, ref_w, cfg, seq,
                                                want_selection=True)
            hc, selc = sala._reference_hidden(reference, ref_w, cfg, seq,
                                              precision, want_selection=True)
            cmp = sala.Comparer(out, reference, ref_w, h32, sel32, 0)
            for lo in range(0, len(seq), block):
                cmp(lo, head(ref_w, hc[lo:lo + block]),
                    min(block, len(seq) - lo), selc[:, lo:lo + block])
            del h32, hc
    prompts, emitted = sample["engine_prompts"], sample["engine_tokens"]
    ctl, ref = (control.reference_rows(reference, ref_w, cfg, p, prompts,
                                       emitted)
                for p in (precision, "float32"))
    for c, r in zip(ctl, ref):
        out.add_tokens(r, c.argmax(-1).tolist())
    return out.result(limits)
