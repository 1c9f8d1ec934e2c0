"""Runner `engine_gdn`: runner `engine_mla` (itself `engine_sala` with the
held experts as the judged selection; reused by import) for a model whose
layers are a LIST of gated-delta-net and latent-attention mixers, with one
chip's share of sigmoid-routed experts beside a shared expert and a cut of
the published layers (the GigaChat3.5 family).

It adds what those have no place for and changes nothing else:
- the family's published config.json keys (`full_attention_layers`, the
  `linear_*` keys, the gates' constants, `swiglu_limit`, the latent's ranks,
  `rope_scaling`, the router's keys) and the configuration's own cut
  (`published`, `held`: which layers, experts and vocabulary rows this chip
  has) reach the program under its names and the plain reference under
  theirs;
- a program without `ray_tpu/models/gigachat.py` (a commit before it) is
  refused at once, before JAX is touched, with exit code 1 and no result
  line;
- the weights come from `chipbench/weights_gdn.py`;
- the check's own pool has three kinds (`pool_spec`: one slot's state and
  conv tail beside the latent pages), and the chosen held experts of EVERY
  run of expert layers are compared with the reference's, in the model's
  order.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict

from chipbench import weights_gdn
from chipbench.cell import BenchError
from chipbench.runners import engine as base
from chipbench.runners import engine_mla as mla
from chipbench.runners import engine_sala as sala

# the family's keys that the program's config has under the published name
SAME_NAME = (
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "first_k_dense_replace", "moe_intermediate_size",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim",
    "linear_attn_o_norm_eps", "linear_sigmoid_gate_scale",
    "layernorm_gating_weight", "gated_attention", "swiglu_limit",
    "n_shared_experts", "num_experts_per_tok", "norm_topk_prob")
# all of them, copied from the configuration to the reference's cfg
FAMILY_KEYS = SAME_NAME + (
    "full_attention_layers", "n_routed_experts", "routed_scaling_factor",
    "rope_scaling", "published", "held")
# what the program reads only as a switch it has one setting of
BUILT = {"norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post",
         "linear_attention_type": "GigaChat35GatedDeltaNet",
         "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered",
         "hidden_act": "silu", "attention_bias": False,
         "tie_word_embeddings": False, "use_shared_expert_sigmoid": False,
         "use_mla_scaling_factor": True, "n_group": 1, "topk_group": 1,
         "num_nextn_predict_layers": 0}


def model_overrides(published: Dict[str, Any]) -> Dict[str, Any]:
    """The published config.json keys (and the configuration's cut) as the
    program's GigaChatConfig names."""
    rs = published["rope_scaling"]
    if rs["type"] != "yarn":
        raise BenchError(f"rope_scaling type {rs['type']!r}: only yarn")
    first, end = published["held"]["experts"]
    if end - first != published["n_routed_experts"]:
        raise BenchError("held.experts does not span n_routed_experts")
    lo, hi = published["held"]["layers"]
    if hi - lo != published["num_hidden_layers"]:
        raise BenchError("held.layers does not span num_hidden_layers")
    return {
        "vocab_size": published["vocab_size"],
        "hidden_size": published["hidden_size"],
        "intermediate_size": published["intermediate_size"],
        "num_layers": published["num_hidden_layers"],
        "kept_layers": tuple(range(lo, hi)),
        "num_heads": published["num_attention_heads"],
        "num_kv_heads": published["num_key_value_heads"],
        "rope_theta": float(published["rope_theta"]),
        "rms_norm_eps": float(published["rms_norm_eps"]),
        **{k: published[k] for k in SAME_NAME},
        "full_attention_layers": tuple(published["full_attention_layers"]),
        # the share: the held experts, the router's width, the first held
        "num_experts": published["n_routed_experts"],
        "n_routed_experts": published["published"]["n_routed_experts"],
        "expert_first": first,
        "routed_scaling_factor": float(published["routed_scaling_factor"]),
        "moe_scoring": "sigmoid",
        "rope_factor": float(rs["factor"]),
        "rope_original_max": int(rs["original_max_position_embeddings"]),
        "rope_beta_fast": float(rs["beta_fast"]),
        "rope_beta_slow": float(rs["beta_slow"]),
        "rope_mscale": float(rs["mscale"]),
        "rope_mscale_all_dim": float(rs["mscale_all_dim"]),
    }


def _require_gdn_program() -> None:
    import importlib.util

    if importlib.util.find_spec("ray_tpu.models.gigachat") is None:
        raise BenchError(
            "this program has no gated-delta-net layers beside latent "
            "attention (ray_tpu/models/gigachat.py, preset "
            "gigachat3.5-432b-a28b): it cannot run a GigaChat3.5 "
            "configuration")


class PagedLogits(sala.PagedLogits):
    """`engine_sala.PagedLogits` whose selection is the chosen HELD experts
    of every run of expert layers, in the model's order: `on_block` gets
    [L_moe, n, 1, held] bool."""

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
            # a run of expert layers sows [run, B, S, 1, held]
            held = [jax.tree.leaves(v)[0][:, 0] for _, v in sorted(
                sown.get("selection", {}).items())]
            return (logits[0], new.pool,
                    jnp.concatenate(held) if held else None)

        self.step = jax.jit(run, donate_argnums=(1,),
                            static_argnames=("ctx",))


@contextlib.contextmanager
def _gdn_set_up():
    """As `engine_sala._sala_set_up`: for the length of a set-up the base
    runner's `model_overrides`, `_shape_probe` and `weights` are this
    family's."""
    mine = {"model_overrides": model_overrides,
            "_shape_probe": sala._shape_probe, "weights": weights_gdn}
    theirs = {k: getattr(base, k) for k in mine}
    for k, v in mine.items():
        setattr(base, k, v)
    try:
        yield
    finally:
        for k, v in theirs.items():
            setattr(base, k, v)


class Runner(mla.Runner):
    def __init__(self, cell, seed: int, seconds: float, log):
        _require_gdn_program()
        base.Runner.__init__(self, cell, seed, seconds, log)
        other = {k: cell.config[k] for k, v in BUILT.items()
                 if cell.config[k] != v}
        if other:
            raise BenchError(f"the configuration sets {other}: the program "
                             f"builds {BUILT} only")
        self.published.update({k: cell.config[k] for k in FAMILY_KEYS})
        self.published["num_hidden_layers"] = cell.config["num_hidden_layers"]
        # what the reference reads of the share
        self.published["expert_first"] = cell.config["held"]["experts"][0]

    def setup(self, warm: bool = True) -> Dict[str, Any]:
        with _gdn_set_up():
            check = base.Runner.setup(self, warm)
        got, pub = self.engine.model_cfg, self.published
        held = (got.expert_first, got.expert_first + got.num_experts)
        layers = [got.layers[0], got.layers[-1] + 1]
        if (list(held) != list(pub["held"]["experts"])
                or layers != list(pub["held"]["layers"])
                or got.n_routed_experts != pub["published"]["n_routed_experts"]
                or got.vocab_size != pub["held"]["vocab_rows"][1]):
            raise BenchError(
                f"the engine runs layers {layers} with experts {held} of "
                f"{got.n_routed_experts} and {got.vocab_size} vocabulary "
                f"rows; the configuration says {pub['held']} of "
                f"{pub['published']}")
        st = self.engine.stats()
        self.log(f"state: {got.n_gdn_layers} GDN layers' state and tails "
                 f"{st['gdn_state_pool_bytes']:,} bytes "
                 f"({got.gdn_state_bytes_row():,} a slot), "
                 f"{got.n_mla_layers} MLA layer's latent pool "
                 f"{st['latent_pool_bytes']:,} bytes; runs {got.runs}; "
                 f"experts {held} of {got.n_routed_experts} a layer; "
                 f"{st['prefill_resumed_passes_total']} resumed passes, "
                 f"{st['gdn_prefill_chunks_total']} delta-rule chunks and "
                 f"{st['gdn_state_updates_total']} state updates so far; "
                 f"prefix reuse off")
        return check

    def _compare(self, prompts, eprompts, emitted, g: int):
        with _paged_logits():
            return super()._compare(prompts, eprompts, emitted, g)


@contextlib.contextmanager
def _paged_logits():
    """`engine_mla.Runner._compare` builds the module's `PagedLogits`; for
    the length of a comparison it is this family's."""
    theirs, mla.PagedLogits = mla.PagedLogits, PagedLogits
    try:
        yield
    finally:
        mla.PagedLogits = theirs


control_numbers = mla.control_numbers
