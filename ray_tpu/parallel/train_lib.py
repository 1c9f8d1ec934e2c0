"""Sharded training-step builder (pure JAX; used by bench, Train, tests).

The compute-path counterpart of the reference's training loop utilities
(ref: python/ray/train/torch/train_loop_utils.py prepare_model/prepare_data):
instead of wrapping a model in DDP/FSDP, we jit one train step whose
in/out shardings place parameters by the logical rule table and let GSPMD
derive gradient collectives (reduce-scatter/all-gather over fsdp, psum over
dp) on ICI.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..util import tracing
from ..util.compile_cache import enable_compile_cache
from . import sharding as shd
from .mesh import active_mesh, create_mesh, MeshConfig


@dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any

    def tree_flatten(self):
        return ((self.step, self.params, self.opt_state), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


def masked_mean(values: jax.Array, mask) -> jax.Array:
    if mask is None:
        return values.mean()
    mask = mask.astype(values.dtype)
    return jnp.sum(values * mask) / jnp.maximum(jnp.sum(mask), 1)


def cross_entropy_loss(logits: jax.Array, targets: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return masked_mean(nll, mask)


def default_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                      warmup: int = 100, total_steps: int = 10000,
                      grad_clip: float = 1.0) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup, max(total_steps, warmup + 1), lr * 0.1)
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


class ShardedTrainer:
    """Holds model + mesh + jitted step. One instance per host process.

    Usage:
        trainer = ShardedTrainer(model, mesh)
        state = trainer.init(rng, example_batch)
        state, metrics = trainer.step(state, batch)
    """

    def __init__(self, model: nn.Module, mesh: Optional[Mesh] = None,
                 optimizer: Optional[optax.GradientTransformation] = None,
                 rules=shd.DEFAULT_RULES,
                 loss_fn: Optional[Callable] = None,
                 donate_state: bool = True):
        enable_compile_cache()
        self.model = model
        self.mesh = mesh if mesh is not None else create_mesh(MeshConfig())
        self.tx = optimizer or default_optimizer()
        self.rules = rules
        self.loss_fn = loss_fn or self._default_loss
        seq_axis = ("sp" if "sp" in self.mesh.axis_names
                    and self.mesh.shape.get("sp", 1) > 1 else None)
        self._batch_sharding = NamedSharding(
            self.mesh, P(("dp", "fsdp"), seq_axis))
        self._state_shardings = None
        self._jit_step = None
        self._jit_eval = None
        self._step_seq = 0
        self._donate = donate_state
        # the shapes and shardings of the last step's (state, batch), for
        # `program_scopes`; its table, parsed once
        self._step_avals = None
        self._scopes = None

    # -------------------------------------------------------------- loss
    def _default_loss(self, params, batch):
        # Forward over the FULL sequence (keeps seq length divisible by the
        # sp axis for ring attention); targets are the input shifted left.
        input_ids = batch["input_ids"]
        targets = jnp.concatenate(
            [input_ids[:, 1:], input_ids[:, :1]], axis=1)
        mask = batch.get("loss_mask")
        mask = mask[:, 1:] if mask is not None else None
        if getattr(self.model, "supports_fused_loss", False):
            # fused chunked CE: [B,S,V] fp32 logits never materialize.
            # mutable=["losses"] collects auxiliary regularizers the model
            # sows (MoE router load-balancing) WITHOUT polluting the
            # per-token nll, which stays pure cross-entropy.
            nll, variables = self.model.apply(
                {"params": params}, input_ids, targets=targets,
                mutable=["losses"])
            nll = nll[:, :-1]  # final position has no next token
            loss = masked_mean(nll, mask)
            for leaf in jax.tree.leaves(variables.get("losses", {})):
                loss = loss + jnp.sum(leaf)
            return loss
        # model without a fused-loss path: dense logits + CE
        logits = self.model.apply({"params": params}, input_ids)[:, :-1]
        return cross_entropy_loss(logits, input_ids[:, 1:], mask)

    # -------------------------------------------------------------- init
    def state_shardings(self, example_batch):
        if self._state_shardings is not None:
            return self._state_shardings
        ids = example_batch["input_ids"]
        # full example-batch shape (not batch 1): collective attention needs
        # the batch/seq dims divisible by the mesh axes even under eval_shape
        with active_mesh(self.mesh):
            abstract = jax.eval_shape(
                lambda: self.model.init(
                    jax.random.PRNGKey(0),
                    jnp.zeros(tuple(ids.shape), jnp.int32)))
        logical = nn.get_partition_spec(abstract)
        params_shardings = shd.logical_to_sharding(
            logical, self.mesh, self.rules)["params"]
        opt_shardings = self._opt_shardings(nn.meta.unbox(abstract["params"]),
                                            params_shardings)
        self._state_shardings = TrainState(
            step=NamedSharding(self.mesh, P()),
            params=params_shardings,
            opt_state=opt_shardings)
        return self._state_shardings

    def _opt_shardings(self, abstract_params, params_shardings):
        """Optimizer slots whose subtree mirrors the param tree (adam mu/nu,
        momentum, …) get the params' shardings; everything else (counts,
        scalars) is replicated.  Matching is by tree structure, not shape,
        so same-shaped params with different layouts can't collide."""
        abstract_opt = jax.eval_shape(
            lambda p: self.tx.init(p),
            jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                         abstract_params))
        params_treedef = jax.tree.structure(abstract_params)
        replicated = NamedSharding(self.mesh, P())

        def is_params_like(subtree):
            try:
                return jax.tree.structure(subtree) == params_treedef
            except Exception:
                return False

        def assign(subtree):
            if is_params_like(subtree):
                return params_shardings
            return jax.tree.map(lambda _: replicated, subtree)

        return jax.tree.map(assign, abstract_opt, is_leaf=is_params_like)

    def init(self, rng, example_batch) -> TrainState:
        shardings = self.state_shardings(example_batch)

        def _init(rng):
            params = self.model.init(
                rng, jnp.zeros_like(example_batch["input_ids"]))["params"]
            params = nn.meta.unbox(params)
            return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                              opt_state=self.tx.init(params))

        with active_mesh(self.mesh):
            init_jit = jax.jit(_init, out_shardings=shardings)
            return init_jit(rng)

    # -------------------------------------------------------------- step
    def _build_step(self, example_batch):
        shardings = self.state_shardings(example_batch)

        def _step(state: TrainState, batch):
            def loss_fn(params):
                with tracing.scope("rtpu.loss"):
                    return self.loss_fn(params, batch)

            loss, grads = jax.value_and_grad(loss_fn)(state.params)
            with tracing.scope("rtpu.optimizer"):
                updates, new_opt = self.tx.update(grads, state.opt_state,
                                                  state.params)
                new_params = optax.apply_updates(state.params, updates)
                gnorm = optax.global_norm(grads)
            return (TrainState(step=state.step + 1, params=new_params,
                               opt_state=new_opt),
                    {"loss": loss, "grad_norm": gnorm})

        metric_shardings = {"loss": NamedSharding(self.mesh, P()),
                            "grad_norm": NamedSharding(self.mesh, P())}
        self._jit_step = jax.jit(
            _step,
            in_shardings=(shardings, self._batch_sharding),
            out_shardings=(shardings, metric_shardings),
            donate_argnums=(0,) if self._donate else ())
        return self._jit_step

    def step(self, state: TrainState, batch) -> Tuple[TrainState, Dict]:
        if not isinstance(batch, dict):
            batch = {"input_ids": batch}
        if self._jit_step is None:
            self._build_step(batch)
        # the host's time to dispatch one step (not the step's): one
        # `train.step` flight record a call
        self._step_seq += 1
        with tracing.region("rtpu.train.step") as r:
            batch = {k: jax.device_put(v, self._batch_sharding)
                     for k, v in batch.items()}
            if self._step_avals is None:
                # once: a trainer's state and batch keep their shapes (the
                # caller gives its state away to the step, so no array)
                self._step_avals = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=a.sharding),
                    (state, batch))
            with active_mesh(self.mesh):
                out = self._jit_step(state, batch)
        tracing.record("train.step", (self._step_seq, r.start_ns, r.end_ns))
        return out

    def program_text(self, state: TrainState, batch,
                     compiled: bool = False) -> str:
        """The lowered (StableHLO) text of the train step for this state
        and batch — what chip_smoke.py reads to show that the Pallas
        flash kernel (`tpu_custom_call`) is in the program. `compiled`:
        the compiler's own text of it instead (the copies and layouts XLA
        chose: tests/test_chip_compile.py reads them)."""
        if not isinstance(batch, dict):
            batch = {"input_ids": batch}
        if self._jit_step is None:
            self._build_step(batch)
        with active_mesh(self.mesh):
            lowered = self._jit_step.lower(state, batch)
            return (lowered.compile() if compiled else lowered).as_text()

    def program_scopes(self) -> Optional[Dict[str, str]]:
        """Which scope each instruction of the train step belongs to:
        instruction name as a profiler trace's op events carry it ->
        the `op_name` path jax wrote for it (util/tracing.py:
        instruction_scopes, SCOPES; the backward pass is under
        `transpose(jvp(...))`, a rematerialised forward under
        `rematted_computation`). From the shapes and shardings of the
        first step: the lowering that ran, so with a persistent compile
        cache the text is the executed program's. None before a step.
        For whoever reads a trace after the run: no step calls it."""
        if self._scopes is None and self._step_avals is not None:
            with active_mesh(self.mesh):
                self._scopes = tracing.instruction_scopes(
                    self._jit_step.lower(*self._step_avals)
                    .compile().as_text())
        return self._scopes

    def eval_loss(self, state: TrainState, batch) -> jax.Array:
        if self._jit_eval is None:
            self._jit_eval = jax.jit(self.loss_fn)
        with active_mesh(self.mesh):
            return self._jit_eval(state.params, batch)
