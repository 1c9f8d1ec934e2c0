"""Runner `engine_laguna`: runner `engine_window` (two kinds of attention
layer, rings beside pages, the experts' choices judged; reused by import)
for the Laguna family, whose kinds differ in their shapes.

It adds what `engine_window` has no place for and changes nothing else:
- the family's published config.json keys (`num_attention_heads_per_layer`,
  `mlp_layer_types`, `rope_parameters` a layer kind with its theta and its
  `partial_rotary_factor`, `gating`, the router's keys, the shared expert's
  width) reach the program under its names and the plain reference under
  theirs;
- a program without `ray_tpu/models/laguna.py` (a commit before it) is
  refused at once, before JAX is touched, with exit code 1 and no result
  line;
- the weights come from `chipbench/weights_gdn.py` (a stack a run of like
  layers; a router's selection bias N(0, 0.05^2), so that the bias is in
  the choices the check compares).
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict

from chipbench import weights_gdn
from chipbench.cell import BenchError
from chipbench.runners import engine as base
from chipbench.runners import engine_window as window

# the family's keys, copied from the configuration to the reference's cfg
FAMILY_KEYS = ("layer_types", "mlp_layer_types",
               "num_attention_heads_per_layer", "sliding_window",
               "rope_parameters", "gating", "num_experts",
               "num_experts_per_tok", "moe_intermediate_size",
               "shared_expert_intermediate_size",
               "moe_routed_scaling_factor")
control_numbers = window.control_numbers


def model_overrides(published: Dict[str, Any]) -> Dict[str, Any]:
    """The published config.json keys as the program's LagunaConfig
    names."""
    rp = published["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    if (full["rope_type"], sliding["rope_type"]) != ("yarn", "default") \
            or sliding.get("partial_rotary_factor", 1) != 1:
        raise BenchError(f"rope_parameters {rp}: a yarn rotation over a "
                         f"share of the full layers' head and the default "
                         f"one over the sliding layers' whole head is what "
                         f"is built")
    f = published["moe_intermediate_size"]
    if not published["gating"] or published[
            "shared_expert_intermediate_size"] % f:
        raise BenchError("a per-head output gate and a shared expert of a "
                         "whole number of expert widths is what is built")
    return {
        "vocab_size": published["vocab_size"],
        "hidden_size": published["hidden_size"],
        "intermediate_size": published["intermediate_size"],
        "num_layers": published["num_hidden_layers"],
        "num_heads": max(published["num_attention_heads_per_layer"]),
        "num_kv_heads": published["num_key_value_heads"],
        "head_dim": published["head_dim"],
        "rms_norm_eps": float(published["rms_norm_eps"]),
        "layer_types": tuple(published["layer_types"]),
        "mlp_layer_types": tuple(published["mlp_layer_types"]),
        "num_attention_heads_per_layer": tuple(
            published["num_attention_heads_per_layer"]),
        "sliding_window": int(published["sliding_window"]),
        "num_experts": published["num_experts"],
        "num_experts_per_tok": published["num_experts_per_tok"],
        "moe_intermediate_size": f,
        "n_shared_experts": published["shared_expert_intermediate_size"] // f,
        "routed_scaling_factor": float(
            published["moe_routed_scaling_factor"]),
        "attn_gate": True,
        "rope_theta": float(full["rope_theta"]),
        "sliding_rope_theta": float(sliding["rope_theta"]),
        "partial_rotary_factor": float(full["partial_rotary_factor"]),
        "rope_factor": float(full["factor"]),
        "rope_original_max": int(full["original_max_position_embeddings"]),
        "rope_beta_fast": float(full["beta_fast"]),
        "rope_beta_slow": float(full["beta_slow"]),
        "rope_attention_factor": float(full["attention_factor"]),
    }


def _require_laguna_program() -> None:
    import importlib.util

    if importlib.util.find_spec("ray_tpu.models.laguna") is None:
        raise BenchError(
            "this program has no mixed stack whose kinds of layer have "
            "their own shapes (ray_tpu/models/laguna.py, preset "
            "laguna-xs.2): it cannot run a Laguna configuration")


@contextlib.contextmanager
def _laguna_set_up():
    """As `engine_window._window_set_up`, which `engine_window.Runner.setup`
    enters: for the length of a set-up ITS `model_overrides` and weights
    are this family's."""
    mine = {"model_overrides": model_overrides, "weights_sala": weights_gdn}
    theirs = {k: getattr(window, k) for k in mine}
    for k, v in mine.items():
        setattr(window, k, v)
    try:
        yield
    finally:
        for k, v in theirs.items():
            setattr(window, k, v)


class Runner(window.Runner):
    def __init__(self, cell, seed: int, seconds: float, log):
        _require_laguna_program()
        base.Runner.__init__(self, cell, seed, seconds, log)
        self.published.update({k: cell.config[k] for k in FAMILY_KEYS})
        self.published["num_hidden_layers"] = cell.config["num_hidden_layers"]

    def setup(self, warm: bool = True) -> Dict[str, Any]:
        with _laguna_set_up():
            check = super().setup(warm)
        got, pub = self.engine.model_cfg, self.published
        n = pub["num_hidden_layers"]
        heads = [got.heads(kind) for kind in got.layers]
        dense = [got.dense_ffn(i) for i in range(n)]
        if (heads != list(pub["num_attention_heads_per_layer"][:n])
                or dense != [t == "dense" for t in pub["mlp_layer_types"][:n]]
                or not got.attn_gate or got.moe_scoring != "sigmoid"):
            raise BenchError(
                f"the engine runs layers of {heads} heads, dense FFNs "
                f"{dense}, gate {got.attn_gate}, {got.moe_scoring} scores; "
                f"the configuration says "
                f"{pub['num_attention_heads_per_layer'][:n]}, "
                f"{pub['mlp_layer_types'][:n]}, a gate, sigmoid scores")
        self.log(f"laguna: full layers of {got.heads('full_attention')} "
                 f"query heads ({got.rotary_dim('full_attention')} of "
                 f"{got.head_dim_} dims rotated), sliding layers of "
                 f"{got.heads('sliding_attention')}, on {got.num_kv_heads} "
                 f"kv heads; {got.n_expert_layers} of {n} layers sparse; "
                 f"pass cost floor {self.engine._pass_cost.floor:.0f} "
                 f"tokens")
        return check
