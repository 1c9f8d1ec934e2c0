"""The comparison that decides `correct`: the program's numbers against the
plain reference's. Every number is printed beside its limit in every run.
The limits live in the configuration's file (`limits`), set from readings
on the chip of sound runs and of the control (PERF.md gives both)."""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence

import numpy as np


class Numbers:
    """Named numbers, each held to `<=` a limit of the same name."""

    def __init__(self):
        self.values: Dict[str, float] = {}
        self.notes: Dict[str, Any] = {}

    def result(self, limits: Dict[str, float]) -> Dict[str, Any]:
        rows, ok = [], True
        for name, value in self.values.items():
            if name not in limits:
                raise KeyError(f"no limit for {name!r} in the configuration")
            limit = float(limits[name])
            good = math.isfinite(value) and value <= limit
            ok = ok and good
            rows.append({"name": name, "value": value, "limit": limit,
                         "ok": good})
        return {"correct": ok, "numbers": rows, "notes": self.notes}


class LogitCheck(Numbers):
    """Serving: logits at every position, and the engine's own tokens."""

    def __init__(self):
        super().__init__()
        self._err2 = self._ref2 = 0.0
        self._n = 0
        self._max_err = self._max_ref = 0.0
        self._gaps: List[float] = []

    def add_logits(self, prog: np.ndarray, ref: np.ndarray) -> None:
        if prog.shape != ref.shape:
            raise ValueError(f"logit shapes differ: {prog.shape} {ref.shape}")
        prog = prog.astype(np.float64)
        ref = ref.astype(np.float64)
        # logits are defined up to a constant per position: compare them
        # centred, as softmax sees them
        prog = prog - prog.mean(-1, keepdims=True)
        ref = ref - ref.mean(-1, keepdims=True)
        err = prog - ref
        self._err2 += float((err * err).sum())
        self._ref2 += float((ref * ref).sum())
        self._n += err.size
        self._max_err = max(self._max_err, float(np.abs(err).max()))
        self._max_ref = max(self._max_ref, float(np.abs(ref).max()))

    def add_tokens(self, ref_rows: np.ndarray,
                   emitted: Sequence[int]) -> None:
        """`ref_rows` [G, V]: the reference's logits, over prompt +
        emitted[:-1], at the position where each emitted token was chosen;
        the tokens are the ENGINE's own (greedy, through
        add_request/step()). For each, how far below the reference's best
        logit it lies: 0 where the engine chose the reference's token, the
        size of the near-tie it fell on the other side of where not."""
        for row, tok in zip(ref_rows, emitted):
            self._gaps.append(float(row.max() - row[tok]))

    def result(self, limits: Dict[str, float]) -> Dict[str, Any]:
        rms_ref = math.sqrt(self._ref2 / max(self._n, 1))
        self.values = {
            "logit_rel_rms_err": math.sqrt(self._err2 / max(self._n, 1))
            / max(rms_ref, 1e-30),
            "logit_max_err_over_rms": self._max_err / max(rms_ref, 1e-30),
        }
        gaps = np.asarray(self._gaps, np.float64) / max(rms_ref, 1e-30)
        if gaps.size:
            # the mean of that gap over all the engine's tokens. Errors of
            # size e flip a share ~ e of the tokens by ~ e each, so the mean
            # goes with e squared: it separates a lower precision 30-70
            # times, the share of flipped tokens 4-6 times, the largest
            # single gap hardly (PERF.md section 2); and one token that is
            # plainly wrong (a gap of 3 in 512 tokens) is over the limit too
            self.values["token_gap_mean_over_rms"] = float(gaps.mean())
        self.notes = {"logit_rms": rms_ref, "logit_max_abs": self._max_ref,
                      "positions": self._n, "tokens_checked": int(gaps.size),
                      "token_gap_max_over_rms":
                          float(gaps.max()) if gaps.size else None,
                      "tokens_flipped": int((gaps > 0).sum())}
        return super().result(limits)


class LossCheck(Numbers):
    """Training: per-token loss of the first batch, and the loss falling."""

    def set_nll(self, prog: np.ndarray, ref: np.ndarray) -> None:
        prog, ref = prog.astype(np.float64), ref.astype(np.float64)
        err = prog - ref
        spread = float(ref.std())
        self.values["nll_rms_err_over_std"] = float(
            np.sqrt((err * err).mean())) / max(spread, 1e-30)
        # the mean's error is a note, not a judged number: read on the chip
        # it does not separate the control (int8: 0.0003-0.001) from sound
        # runs (up to 0.0002); the per-token error above does
        self.notes.update(nll_mean_ref=float(ref.mean()), nll_std_ref=spread,
                          nll_mean_prog=float(prog.mean()),
                          loss_abs_err=abs(float(prog.mean() - ref.mean())))

    def set_grads(self, sums: Dict[str, Dict[str, np.ndarray]]) -> None:
        """The gradient of the first batch's loss: the program's (its own
        loss function under `jax.grad`: flash backward kernels, remat)
        against the plain reference's. `sums` is `grad_sums`'s."""
        ref_norm = grad_norm(sums, "ref2")
        self.values["grad_rel_err"] = grad_norm(sums, "err2") / ref_norm
        worst, where = 0.0, None
        for name, v in sums.items():
            rel = np.sqrt(np.asarray(v["err2"], np.float64)
                          / np.maximum(np.asarray(v["ref2"], np.float64),
                                       1e-300)).reshape(-1)
            k = int(rel.argmax())
            if rel[k] > worst:
                worst, where = float(rel[k]), f"{name}[{k}]"
        self.values["grad_worst_leaf_rel_err"] = worst
        self.notes.update(grad_norm_ref=ref_norm, grad_worst=where)

    def set_step_grad_norm(self, step_norm: float, prog_norm: float) -> None:
        """The train step's own gradient norm on the first batch against
        the norm of the same gradient through the checked backward (the
        trainer's loss function under `jax.grad`): ties the step program's
        backward to the one that was compared. The step reports its norm in
        the gradients' type, so bfloat16 rounding (2^-9) is its floor."""
        self.values["step_grad_norm_vs_backward"] = abs(
            step_norm - prog_norm) / max(prog_norm, 1e-300)
        self.notes.update(grad_norm_step=step_norm, grad_norm_prog=prog_norm)

    def set_step_loss(self, step_loss: float, prog_mean: float) -> None:
        """The train step's own loss on that batch against the mean of the
        checked per-token losses: ties the step program to the forward that
        was compared."""
        self.values["step_loss_vs_forward"] = abs(step_loss - prog_mean)

    def set_fall(self, losses: List[float]) -> None:
        k = max(1, len(losses) // 4)
        first, last = np.mean(losses[:k]), np.mean(losses[-k:])
        finite = all(math.isfinite(x) for x in losses)
        # a number held to <= 0: last - first, +inf if anything is not finite
        self.values["loss_last_minus_first"] = (
            float(last - first) if finite and losses else float("inf"))
        self.notes.update(loss_first=float(first), loss_last=float(last),
                          steps=len(losses))


def grad_sums(prog: Dict[str, Any], ref: Optional[Dict[str, Any]]
              ) -> Dict[str, Dict[str, np.ndarray]]:
    """Two gradients in the reference's layout ({name: array, "layers":
    {name: [L, ...]}}): per leaf, and per layer of a stacked leaf, the
    squared sums of the error (`err2`), of the reference (`ref2`) and of
    the program's (`prog2`), taken on the device in float32. Without a
    reference, `prog2` alone."""
    import jax
    import jax.numpy as jnp

    def sums(p, r, stacked: bool):
        p = p.astype(jnp.float32)
        ax = tuple(range(1, p.ndim)) if stacked else None
        out = {"prog2": jnp.sum(p * p, axis=ax)}
        if r is not None:
            r = r.astype(jnp.float32)
            out.update(err2=jnp.sum((p - r) ** 2, axis=ax),
                       ref2=jnp.sum(r * r, axis=ax))
        return out

    def run(prog, ref):
        ref = ref or {"layers": {}}
        out = {k: sums(prog[k], ref.get(k), False)
               for k in prog if k != "layers"}
        out.update({f"layers.{k}": sums(v, ref["layers"].get(k), True)
                    for k, v in prog["layers"].items()})
        return out

    return jax.tree.map(np.asarray, jax.jit(run)(prog, ref))


def grad_norm(sums: Dict[str, Dict[str, np.ndarray]], which: str) -> float:
    return math.sqrt(sum(float(np.sum(v[which], dtype=np.float64))
                         for v in sums.values()))
