"""Runner `trainer`: `ShardedTrainer` on a one-chip mesh, steps back to back
on a new seeded batch each step.

Set-up: weights from the seed (chipbench/weights.py) placed into a
`TrainState` with the trainer's own optimizer state; the first batch's
per-token loss through the program's model against the plain reference's;
two warm-up steps (compile or load the step program). Window: steps back
to back, a few in flight, each waited for in order by `block_until_ready`
on its loss.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from chipbench import compare, generator, weights
from chipbench.cell import BenchError, Cell, load_module
from chipbench.runners.engine import model_overrides, published_keys


# steps dispatched and not yet waited for (3 s of work at this cell's step:
# longer than the host stalls seen on a shared machine, PERF.md section 6)
STEPS_IN_FLIGHT = 8


class Runner:
    def __init__(self, cell: Cell, seed: int, seconds: float, log):
        self.cell, self.seed, self.seconds, self.log = cell, seed, seconds, log
        self.job = cell.traffic
        self.published = published_keys(cell.config)
        self.reference = load_module("references", cell.config["reference"])
        self.losses: List[float] = []
        self.step_end_s: List[float] = []
        self.samples: Dict[str, List[float]] = {}
        self.counters: Dict[str, Any] = {}
        self.records: list = []

    def setup(self) -> Dict[str, Any]:
        import flax.linen as nn
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.llama import LlamaModel, get_config
        from ray_tpu.parallel.mesh import MeshConfig, create_mesh
        from ray_tpu.parallel.train_lib import (ShardedTrainer, TrainState,
                                                default_optimizer)

        tr = self.cell.config["trainer"]
        dtype = jnp.bfloat16 if tr["param_dtype"] == "bfloat16" \
            else jnp.float32
        cfg = get_config(self.cell.config["program_preset"],
                         param_dtype=dtype, remat_policy=tr["remat_policy"],
                         **model_overrides(self.published))
        model = LlamaModel(cfg)
        self.vocab = cfg.vocab_size
        batch, seq = int(self.job["batch"]), int(self.job["seq"])
        # more batches than the fastest conceivable run can take
        n = int(self.job["max_steps_per_s"] * self.seconds) + 8 \
            + STEPS_IN_FLIGHT
        self.batches = generator.make_token_batches(
            self.job, self.seed, n, self.vocab)
        trainer = ShardedTrainer(
            model, create_mesh(MeshConfig(dp=1, fsdp=1, sp=1, tp=1),
                               devices=jax.devices()[:1]),
            optimizer=default_optimizer())
        example = {"input_ids": self.batches[0]}
        shardings = trainer.state_shardings(example)
        t0 = time.monotonic()
        probe = jax.eval_shape(lambda: nn.meta.unbox(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
        params = weights.make_params(probe, self.seed)
        jax.block_until_ready(params)
        self.n_params = int(sum(x.size for x in jax.tree.leaves(params)))
        self.log(f"weights: {self.n_params:,} params in "
                 f"{time.monotonic()-t0:.1f} s")

        # first batch, before the optimizer state fills the chip: per-token
        # loss and the loss's gradient, program vs plain reference
        t0 = time.monotonic()
        check, prog_loss, prog_gnorm = self._check_first_batch(
            trainer, model, params)
        self.log(f"loss and gradient check in {time.monotonic()-t0:.1f} s")

        t0 = time.monotonic()
        state = jax.jit(
            lambda p: TrainState(step=jnp.zeros((), jnp.int32), params=p,
                                 opt_state=trainer.tx.init(p)),
            out_shardings=shardings, donate_argnums=(0,))(params)
        state, metrics = trainer.step(state, example)
        loss0 = float(metrics["loss"])
        check.set_step_loss(loss0, prog_loss)
        check.set_step_grad_norm(float(metrics["grad_norm"]), prog_gnorm)
        # one more: the first call after a compile or a cache load can
        # carry one-off work that is not the step's
        state, metrics = trainer.step(state, {"input_ids": self.batches[1]})
        jax.block_until_ready(metrics["loss"])
        self.log(f"optimizer state and warm-up: 2 steps in "
                 f"{time.monotonic()-t0:.1f} s, loss {loss0:.4f}")
        self.trainer, self.state, self.check = trainer, state, check
        self.tokens_per_step = batch * seq
        self.warm_steps = 2
        return check.result(self.cell.config["limits"]) | {"partial": True}

    def _check_first_batch(self, trainer, model, params):
        """The first batch's per-token loss through the program's model, and
        the gradient of its first `grad_rows` sequences through the
        trainer's own loss function under `jax.grad` (flash forward and
        backward kernels, remat), against the plain reference's. Returns the
        check, the program's mean loss and the norm of its gradient on the
        whole batch (the step program is tied to both later)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.parallel.mesh import active_mesh

        ids = jnp.asarray(self.batches[0])

        def prog_nll(p, ids):
            targets = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
            return model.apply({"params": p}, ids, targets=targets)[:, :-1]

        nll_prog = np.asarray(jax.jit(prog_nll)(params, ids))
        pub = dict(self.published)
        ref = self.reference
        ref_w = ref.weights_from_program_tree(params)
        nll_ref = np.asarray(jax.jit(
            lambda w, x: ref.next_token_nll(w, x, pub))(ref_w, ids))
        check = compare.LossCheck()
        check.set_nll(nll_prog, nll_ref)

        grad = jax.jit(jax.grad(
            lambda p, x: trainer.loss_fn(p, {"input_ids": x})))
        rows = int(self.job["check"]["grad_rows"])
        with active_mesh(trainer.mesh):
            full_norm = compare.grad_norm(compare.grad_sums(
                ref.weights_from_program_tree(grad(params, ids)), None),
                "prog2")
            g_prog = grad(params, ids[:rows])
        _, g_ref = jax.jit(lambda w, x: ref.loss_and_grads(w, x, pub))(
            ref_w, ids[:rows])
        check.set_grads(compare.grad_sums(
            ref.weights_from_program_tree(g_prog), g_ref))
        del g_prog, g_ref
        return check, float(nll_prog.mean()), full_norm

    def run_window(self, tracer) -> None:
        """Steps are dispatched back to back with up to STEPS_IN_FLIGHT of
        them not yet waited for, as a training loop runs (nobody blocks on
        every step's loss): the device has work queued while the host
        prepares the next batch, and a host stall shorter than the queue
        costs nothing. Every step is waited for, in order, and its end is
        the instant its loss is ready."""
        import collections

        import jax
        from jax.profiler import TraceAnnotation

        trainer, state = self.trainer, self.state
        seconds, batches = self.seconds, self.batches
        pending = collections.deque()
        i = self.warm_steps
        t0 = self.t0 = time.monotonic()

        def wait_for_oldest():
            loss = pending.popleft()
            with TraceAnnotation("chipbench.trainer.wait"):
                jax.block_until_ready(loss)
            self.step_end_s.append(time.monotonic() - t0)
            self.losses.append(loss)

        while True:
            now = time.monotonic() - t0
            tracer.poll(now)
            # no step starts at or after `seconds`; the window closes when
            # the last one started has ended: whole steps over the whole
            # time they took
            if now >= seconds:
                break
            if i >= len(batches):
                raise BenchError("ran out of batches: raise the job's "
                                 "max_steps_per_s")
            with TraceAnnotation("chipbench.trainer.step"):
                state, metrics = trainer.step(state,
                                              {"input_ids": batches[i]})
            pending.append(metrics["loss"])
            i += 1
            if len(pending) > STEPS_IN_FLIGHT:
                wait_for_oldest()
        while pending:
            wait_for_oldest()
        tracer.finish()
        self.losses = [float(x) for x in self.losses]
        del self.state   # donated away step by step
        self.counters = {"steps": len(self.step_end_s)}

    def final_check(self) -> Dict[str, Any]:
        self.check.set_fall(self.losses)
        return self.check.result(self.cell.config["limits"])

    def window_seconds(self) -> float:
        return self.step_end_s[-1]

    def counts(self) -> Dict[str, int]:
        # the loop waits for every step it starts
        return {"attempted": len(self.step_end_s),
                "failed": sum(1 for x in self.losses if not np.isfinite(x))}

    def tokens_completed(self) -> int:
        """Tokens of the steps of the window, all of which finished."""
        return self.tokens_per_step * len(self.step_end_s)

    def work_facts(self) -> Dict[str, Any]:
        return {"kind": "train", "batch": int(self.job["batch"]),
                "seq": int(self.job["seq"]), "published": self.published,
                "tokens_per_step": self.tokens_per_step}
