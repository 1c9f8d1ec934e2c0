"""The control of `correct`: the plain reference put in the program's place
and computed in the nearest precision BELOW the one the configuration
states (bfloat16 states: fp8 and int8; float32 states: bfloat16). It has to
come out as not correct under the configuration's limits; if it passed, a
later PR could drop to that precision unseen.

The benchmark's own runs never call this. The builder reads it on the chip
at the cell's size over several seeds before setting a limit (PERF.md gives
both readings), and tests/test_control.py keeps it at a tiny size.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from chipbench import compare

BELOW = {"bfloat16": ("fp8", "int8"), "float32": ("bfloat16",)}


def padded(seq: List[int], multiple: int = 64):
    """[1, S'] ids, zero-padded at the end to a multiple: causal attention
    leaves the real positions untouched, and shapes stay few."""
    import jax.numpy as jnp

    n = -(-len(seq) // multiple) * multiple
    return jnp.asarray([list(seq) + [0] * (n - len(seq))], jnp.int32)


def reference_rows(reference, weights, cfg: Dict[str, Any], precision: str,
                   prompts: List[List[int]], emitted: List[List[int]]
                   ) -> np.ndarray:
    """[B, G, V]: the reference's logits, at `precision`, at the positions
    where each of the G emitted tokens was chosen, over prompt +
    emitted[:-1] (one call, sequences zero-padded to one length)."""
    import jax
    import jax.numpy as jnp

    seqs = [list(p) + list(e[:-1]) for p, e in zip(prompts, emitted)]
    n = -(-max(len(x) for x in seqs) // 64) * 64
    ids = np.zeros((len(seqs), n), np.int32)
    for i, x in enumerate(seqs):
        ids[i, :len(x)] = x
    rows = np.asarray([[len(p) - 1 + k for k in range(len(e))]
                       for p, e in zip(prompts, emitted)], np.int32)
    fn = jax.jit(lambda w, i, r: reference.forward_rows(w, i, r, cfg,
                                                        precision))
    return np.asarray(fn(weights, jnp.asarray(ids), jnp.asarray(rows)))


def serve_numbers(reference, weights, cfg: Dict[str, Any], precision: str,
                  sample: Dict[str, Any], limits: Dict[str, float]
                  ) -> Dict[str, Any]:
    """What the serving check would read if the program computed as the
    reference does at `precision`. `sample` is what a sound run's check
    kept (`Runner.check_sample`): the sequences its logits were compared
    on, and the engine's prompts with the tokens it emitted. The control is
    teacher-forced along those same sequences (it has no cache, and one
    forward per sequence is what a run can afford at the cell's size): its
    logits at every position against the float32 reference's, and the token
    it would emit at each of the engine's steps, judged as the engine's
    are."""
    import jax

    fwd = {p: jax.jit(lambda w, ids, p=p: reference.forward(w, ids, cfg, p))
           for p in (precision, "float32")}
    out = compare.LogitCheck()
    for prompt, fed in sample["logit_seqs"]:
        seq = prompt + fed[:-1]
        ctl, ref = (np.asarray(fwd[p](weights, padded(seq))[0][:len(seq)])
                    for p in (precision, "float32"))
        out.add_logits(ctl, ref)
    prompts, emitted = sample["engine_prompts"], sample["engine_tokens"]
    ctl, ref = (reference_rows(reference, weights, cfg, p, prompts, emitted)
                for p in (precision, "float32"))
    for c, r in zip(ctl, ref):
        out.add_tokens(r, c.argmax(-1).tolist())
    return out.result(limits)


def train_numbers(reference, weights, cfg: Dict[str, Any], precision: str,
                  ids, limits: Dict[str, float]) -> Dict[str, Any]:
    """The training check's numbers for the control: per-token loss and
    gradient of the first batch at `precision` (the backward's matmuls
    too) against float32, and what the step's tie to the checked backward
    would read if the step alone dropped to `precision`. Only the limits
    of these numbers are judged."""
    import jax

    res = {p: jax.jit(lambda w, x, p=p: reference.loss_and_grads(
        w, x, cfg, p))(weights, ids) for p in (precision, "float32")}
    out = compare.LossCheck()
    out.set_nll(np.asarray(res[precision][0][1]),
                np.asarray(res["float32"][0][1]))
    sums = compare.grad_sums(res[precision][1], res["float32"][1])
    out.set_grads(sums)
    out.set_step_grad_norm(compare.grad_norm(sums, "prog2"),
                           compare.grad_norm(sums, "ref2"))
    out.values = {k: v for k, v in out.values.items() if k in limits}
    return out.result(limits)
