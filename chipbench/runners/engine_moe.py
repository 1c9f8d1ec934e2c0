"""Runner `engine_moe`: runner `engine` (the in-process `LLMEngine` under a
serving mix; chipbench/runners/engine.py, reused by import) for a model
with a sparse-expert FFN.

It adds what `engine` has no place for and changes nothing else:
- the expert keys of the published config.json (`num_local_experts`,
  `num_experts_per_tok`) reach the program (`num_experts`,
  `num_experts_per_tok`) and the plain reference;
- a program without the dropless expert layer (a commit before it: its
  expert layer drops what overflows a capacity, which is another function
  than the published one) is refused at once, before JAX is touched, with
  exit code 1 and no result line;
- the output check judges one number more,
  `logit_rel_rms_err_where_experts_agree`, and prints how often the
  program's experts differ from the float32 reference's (unjudged).
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Callable, Dict

import numpy as np

from chipbench.cell import BenchError
from chipbench.runners import engine as base

EXPERT_KEYS = ("num_local_experts", "num_experts_per_tok")
AGREE = "logit_rel_rms_err_where_experts_agree"


_dense_overrides = base.model_overrides


def model_overrides(published: Dict[str, Any]) -> Dict[str, Any]:
    """`engine`'s nine dense keys plus the expert layer's two."""
    return {**_dense_overrides(published),
            "num_experts": published["num_local_experts"],
            "num_experts_per_tok": published["num_experts_per_tok"]}


@contextlib.contextmanager
def _expert_overrides():
    """`engine.Runner.setup` builds its EngineConfig from the module's
    `model_overrides`; for the length of a set-up that is this module's.
    (The file may not be edited by this PR: PERF.md, Open questions.)"""
    base.model_overrides = model_overrides
    try:
        yield
    finally:
        base.model_overrides = _dense_overrides


def _require_dropless_program() -> None:
    import importlib.util

    if importlib.util.find_spec("ray_tpu.ops.grouped_matmul") is None:
        raise BenchError(
            "this program has no dropless expert layer "
            "(ray_tpu/ops/grouped_matmul.py): its MoEMLP drops the "
            "assignments that overflow a capacity, so it cannot run a "
            "Mixtral configuration as published")


class Runner(base.Runner):
    def __init__(self, cell, seed: int, seconds: float, log):
        _require_dropless_program()
        super().__init__(cell, seed, seconds, log)
        self.published.update({k: cell.config[k] for k in EXPERT_KEYS})

    def setup(self, warm: bool = True) -> Dict[str, Any]:
        with _expert_overrides():
            check = super().setup(warm)
        got = self.engine.model_cfg
        want = (self.published["num_local_experts"],
                self.published["num_experts_per_tok"])
        if (got.num_experts, got.num_experts_per_tok) != want:
            raise BenchError(f"the engine runs {got.num_experts} experts, "
                             f"{got.num_experts_per_tok} a token; the "
                             f"configuration says {want}")
        return check

    def _check_outputs(self) -> Dict[str, Any]:
        check = super()._check_outputs()
        cfg = dict(self.published)
        ref_w = self.reference.weights_from_program_tree(self.engine.params)
        row, notes = expert_choice(
            _program_forward(self.engine.model, self.engine.params),
            reference_forward(self.reference, ref_w, cfg, "float32"),
            self.check_sample["logit_seqs"], self.cell.config["limits"])
        check["numbers"].append(row)
        check["correct"] = check["correct"] and row["ok"]
        check["notes"].update(notes)
        return check


def _program_forward(model, params) -> Callable:
    """ids [1, S] -> (logits [S, V], the experts the program keeps
    [L, S, k], ascending): the program's plain full forward (no cache),
    which rounds as the served path does. The experts are the k largest of
    the router's logits, as `MoEMLP` takes them, on the layer's own router
    input (the output of its `mlp_norm`, captured from that forward)."""
    import jax
    import jax.numpy as jnp

    k = model.config.num_experts_per_tok

    def forward(params, ids):
        logits, got = model.apply(
            {"params": params}, ids, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "mlp_norm")
        normed, = got["intermediates"]["layers"]["layer"]["mlp_norm"][
            "__call__"]                                       # [L, 1, S, h]
        probs = jax.nn.softmax(jnp.einsum(
            "lsh,lhe->lse", normed[:, 0].astype(jnp.float32),
            params["layers"]["layer"]["moe"]["router"]), axis=-1)
        return logits[0].astype(jnp.float32), jnp.sort(
            jax.lax.top_k(probs, k)[1], axis=-1)

    forward = jax.jit(forward)
    return lambda ids: forward(params, ids)


def reference_forward(reference, weights, cfg: Dict[str, Any],
                      precision: str) -> Callable:
    """The same pair from the plain reference at `precision` (other than
    float32: the control, for tools/read_limits_moe.py)."""
    import jax

    logits = jax.jit(lambda w, ids: reference.forward(
        w, ids, cfg, precision)[0])
    chosen = jax.jit(lambda w, ids: reference.routing(
        w, ids, cfg, precision)[0])
    return lambda ids: (logits(weights, ids), chosen(weights, ids))


def expert_choice(got: Callable, want: Callable, seqs, limits):
    """-> (the judged row `AGREE`, unjudged notes), over the logit check's
    sequences. Under bfloat16 activations a near-tied third expert can
    swap into the top k where the float32 reference keeps the other, which
    moves that position's FFN output, and every later position's a
    little, by far more than rounding; the reference stays the published
    math and no number is teacher-forced on routing. So the logit error
    is also taken apart: `AGREE` is `logit_rel_rms_err` over the positions
    where every layer's set of experts is the reference's (there it reads
    rounding, as the dense cells' does); the notes count the (layer,
    position)s that differ and give the error at the other positions."""
    from chipbench import compare, control

    differ = total = 0
    agree, other = compare.LogitCheck(), compare.LogitCheck()
    for prompt, fed in seqs:
        n = len(prompt) + len(fed) - 1
        ids = control.padded(prompt + fed[:-1])
        (logits, chosen), (ref_logits, ref_chosen) = got(ids), want(ids)
        flipped = (np.asarray(chosen) != np.asarray(ref_chosen)).any(-1)[
            :, :n]                                            # [L, S]
        differ += int(flipped.sum())
        total += flipped.size
        got_l, want_l = np.asarray(logits)[:n], np.asarray(ref_logits)[:n]
        clean = ~flipped.any(0)
        if clean.any():
            agree.add_logits(got_l[clean], want_l[clean])
        if not clean.all():
            other.add_logits(got_l[~clean], want_l[~clean])

    def rel_rms(check):
        res = check.result({"logit_rel_rms_err": math.inf,
                            "logit_max_err_over_rms": math.inf})
        return (res["numbers"][0]["value"] if res["notes"]["positions"]
                else math.inf)

    value, limit = rel_rms(agree), float(limits[AGREE])
    return ({"name": AGREE, "value": value, "limit": limit,
             "ok": value <= limit},
            {"expert_sets_differ": differ, "expert_sets_compared": total,
             "logit_rel_rms_err_where_they_differ":
                 rel_rms(other) if differ else None})
