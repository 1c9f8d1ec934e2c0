"""Runner `engine_window`: runner `engine_sala` (itself runner `engine`
under long prompts: the streamed check, the drain after the window; reused
by import) for a model whose attention layers are of two kinds,
sliding-window and full, with a sparse expert FFN in every layer (the
Mellum family).

It adds what those have no place for and changes nothing else:
- the family's published config.json keys (`layer_types`,
  `sliding_window`, `rope_parameters` a layer kind, the experts' keys) reach
  the program under its names and the plain reference under theirs;
- a program without `ray_tpu/models/mellum.py` (a commit before it) is
  refused at once, before JAX is touched, with exit code 1 and no result
  line;
- the weights come from `chipbench/weights_sala.py` (a stack a run of like
  layers: the same tree layout);
- the check judges one number more, `logit_rel_rms_err_where_experts_agree`
  (as `mixtral-chat`'s and `sdar-30b-a3b-chat`'s: the logits' error over
  the positions where every layer chose the float32 reference's experts),
  and notes how often the choices differ;
- `kv_pages_peak_pct`'s inputs count BOTH kinds of the cache: the full
  layers' pages and the sliding layers' rings, in pages a layer (a ring is
  held whole while its slot runs).
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict

from chipbench import control, weights_sala
from chipbench.cell import BenchError
from chipbench.runners import engine as base
from chipbench.runners import engine_sala as sala

# the family's keys, copied from the configuration to the reference's cfg
FAMILY_KEYS = ("layer_types", "sliding_window", "rope_parameters",
               "num_experts", "num_experts_per_tok", "moe_intermediate_size",
               "norm_topk_prob")
AGREE = "logit_rel_rms_err_where_experts_agree"


def model_overrides(published: Dict[str, Any]) -> Dict[str, Any]:
    """The published config.json keys as the program's MellumConfig
    names."""
    rp = published["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    if (full["rope_type"], sliding["rope_type"]) != ("yarn", "default") \
            or full["rope_theta"] != sliding["rope_theta"]:
        raise BenchError(f"rope_parameters {rp}: a yarn rotation for the "
                         f"full layers and the default one for the sliding "
                         f"layers at one theta is what is built")
    return {
        "vocab_size": published["vocab_size"],
        "hidden_size": published["hidden_size"],
        "intermediate_size": published["intermediate_size"],
        "num_layers": published["num_hidden_layers"],
        "num_heads": published["num_attention_heads"],
        "num_kv_heads": published["num_key_value_heads"],
        "head_dim": published["head_dim"],
        "rms_norm_eps": float(published["rms_norm_eps"]),
        "layer_types": tuple(published["layer_types"]),
        "sliding_window": int(published["sliding_window"]),
        "num_experts": published["num_experts"],
        "num_experts_per_tok": published["num_experts_per_tok"],
        "moe_intermediate_size": published["moe_intermediate_size"],
        "norm_topk_prob": published["norm_topk_prob"],
        "rope_theta": float(full["rope_theta"]),
        "rope_factor": float(full["factor"]),
        "rope_original_max": int(full["original_max_position_embeddings"]),
        "rope_beta_fast": float(full["beta_fast"]),
        "rope_beta_slow": float(full["beta_slow"]),
        "rope_attention_factor": float(full["attention_factor"]),
    }


def _require_window_program() -> None:
    import importlib.util

    if importlib.util.find_spec("ray_tpu.models.mellum") is None:
        raise BenchError(
            "this program has no stack of sliding-window and full "
            "attention layers (ray_tpu/models/mellum.py, preset "
            "mellum2-12b-a2.5b): it cannot run a Mellum configuration")


class PagedLogits(sala.PagedLogits):
    """`engine_sala.PagedLogits` whose selection is the experts each token
    chose: `on_block` gets [L, n, 1, E] bool (a run of like layers sows its
    own, the runs in the model's order)."""

    def __init__(self, engine, longest: int):
        import jax
        import jax.numpy as jnp

        super().__init__(engine, longest)
        family, mc, model = self.family, engine.model_cfg, engine.model

        def run(params, pool, bt, total, ids, positions, slots, ctx):
            cache = family.serving_cache(mc, pool, bt, total, slots,
                                         ctx_pages=ctx)
            (logits, new), sown = model.apply(
                {"params": params}, ids, positions=positions,
                kv_caches=cache, mutable=["selection"])
            chosen = [v["chosen"][0][:, 0] for _, v in sorted(
                sown.get("selection", {}).items())]
            return (logits[0], new.pool,
                    jnp.concatenate(chosen) if chosen else None)

        self.step = jax.jit(run, donate_argnums=(1,),
                            static_argnames=("ctx",))


class ExpertCheck(sala.StreamedLogitCheck):
    """`StreamedLogitCheck` whose error over the positions where every
    layer chose the reference's experts is JUDGED, beside the all-position
    number: a near-tied eighth and ninth choice flips under bf16 and moves
    a position's logits by several times what rounding does, so the
    agreeing positions read the arithmetic and the all-position number
    reads both."""

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
def _window_set_up():
    """As `engine_sala._sala_set_up`: for the length of a set-up the base
    runner's `model_overrides`, `_shape_probe` and `weights` are this
    family's."""
    mine = {"model_overrides": model_overrides,
            "_shape_probe": sala._shape_probe, "weights": weights_sala}
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
        _require_window_program()
        base.Runner.__init__(self, cell, seed, seconds, log)
        self.published.update({k: cell.config[k] for k in FAMILY_KEYS})
        self.published["num_hidden_layers"] = cell.config["num_hidden_layers"]

    def setup(self, warm: bool = True) -> Dict[str, Any]:
        with _window_set_up():
            check = base.Runner.setup(self, warm)
        got, pub = self.engine.model_cfg, self.published
        kinds = pub["layer_types"][:pub["num_hidden_layers"]]
        if (list(got.layers) != list(kinds)
                or got.sliding_window != pub["sliding_window"]
                or (got.num_experts, got.num_experts_per_tok,
                    got.expert_width) != (
                        pub["num_experts"], pub["num_experts_per_tok"],
                        pub["moe_intermediate_size"])):
            raise BenchError(
                f"the engine runs layers {got.layers} at a window of "
                f"{got.sliding_window} with {got.num_experts} experts; the "
                f"configuration says {kinds}, {pub['sliding_window']}, "
                f"{pub['num_experts']}")
        st = self.engine.stats()
        self.log(f"state: full layers' pages {st['kv_full_pool_bytes']:,} "
                 f"bytes ({got.n_full_layers} layers x "
                 f"{self.sizes['num_pages']} pages), sliding layers' rings "
                 f"{st['kv_window_pool_bytes']:,} bytes "
                 f"({got.n_window_layers} layers x {self.sizes['max_batch']} "
                 f"slots x {got.sliding_window} tokens); prefix reuse off; "
                 f"{st['prefill_resumed_passes_total']} resumed passes so "
                 f"far")
        return check

    def run_window(self, tracer) -> None:
        """`engine_sala.Runner.run_window`; then `kv_pages_peak_pct`'s
        inputs restated over BOTH kinds of the cache, in pages a layer: a
        full layer's free pages as sampled, and a sliding layer's ring
        (window / page_size pages) free while its slot is."""
        super().run_window(tracer)
        mc, sz = self.engine.model_cfg, self.sizes
        ring = mc.sliding_window // sz["page_size"]
        full, sliding = mc.n_full_layers, mc.n_window_layers
        self.samples["free_pages"] = [
            full * free + sliding * ring * (sz["max_batch"] - running)
            for free, running in zip(self.samples["free_pages"],
                                     self.samples["running"])]
        self.counters["num_pages"] = (full * sz["num_pages"]
                                      + sliding * ring * sz["max_batch"])
        st = self.engine.stats()
        self.counters["kv_window_tokens_released"] = st[
            "kv_window_tokens_released_total"]
        self.log(f"cache: {self.counters['num_pages']} pages a layer over "
                 f"both kinds ({full} x {sz['num_pages']} pages + {sliding} x"
                 f" {sz['max_batch']} rings of {ring}); preempted "
                 f"{self.counters['preempted']}; tokens released behind "
                 f"the window {st['kv_window_tokens_released_total']:,} a "
                 f"layer")

    def _compare(self, prompts, eprompts, emitted, g: int):
        import time

        paged = PagedLogits(self.engine, max(map(len, prompts)) + g)
        cfg = dict(self.published)
        ref_w = self.reference.weights_from_program_tree(self.engine.params)
        out = ExpertCheck()
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
                    sample: Dict[str, Any], limits: Dict[str, float],
                    control_cfg: Dict[str, Any] = None) -> Dict[str, Any]:
    """What the check would read if the program computed as the reference
    does at `precision` under `control_cfg` (None: `cfg`), teacher-forced
    along a sound run's sequences, against the float32 reference under
    `cfg`, streamed as the check is. Two controls: a precision below the
    configuration's (`control_cfg` None), and `sliding_window` None at
    float32: a program whose sliding layers see their whole context."""
    import jax

    ctl_cfg = control_cfg or cfg
    out = ExpertCheck()
    head = jax.jit(lambda w, h: reference.head(w, h, precision))
    block = sala.COMPARE_BLOCK
    with jax.default_matmul_precision("highest"):
        for prompt, fed in sample["logit_seqs"]:
            seq = prompt + fed[:-1]
            h32, sel32 = sala._reference_hidden(reference, ref_w, cfg, seq,
                                                want_selection=True)
            hc, selc = sala._reference_hidden(
                reference, ref_w, ctl_cfg, seq, precision,
                want_selection=True)
            cmp = sala.Comparer(out, reference, ref_w, h32, sel32, 0)
            for lo in range(0, len(seq), block):
                cmp(lo, head(ref_w, hc[lo:lo + block]),
                    min(block, len(seq) - lo), selc[:, lo:lo + block])
            del h32, hc
    prompts, emitted = sample["engine_prompts"], sample["engine_tokens"]
    ctl = control.reference_rows(reference, ref_w, ctl_cfg, precision,
                                 prompts, emitted)
    ref = control.reference_rows(reference, ref_w, cfg, "float32", prompts,
                                 emitted)
    for c, r in zip(ctl, ref):
        out.add_tokens(r, c.argmax(-1).tolist())
    return out.result(limits)
