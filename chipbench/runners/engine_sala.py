"""Runner `engine_sala`: runner `engine` (the in-process `LLMEngine` under
a serving mix; chipbench/runners/engine.py, reused by import) for a model
with lightning linear-attention layers beside block-sparse attention
layers (the MiniCPM-SALA family), under long prompts.

It adds what `engine` has no place for and changes nothing else:
- the published config.json keys of the family (`mixer_types`, the
  `lightning_*` keys, the muP scalings) and the configuration's own
  `kept_layers` and `sparse_config` reach the program under its names and
  the plain reference under theirs;
- a program without `ray_tpu/models/minicpm_sala.py` (a commit before it)
  is refused at once, before any array is made, with exit code 1 and no
  result line;
- the weights come from `chipbench/weights_sala.py` (a stack a run of
  like layers, made a layer at a time);
- the output check's logits path prefills in PASSES of the largest bucket
  through the engine's model and params and a pool of its own (pages,
  compressed keys and per-slot state, as `pool_spec` lays them out), each
  pass resuming from the one before, then decodes token by token; prompts
  of 20k tokens have 1.5 G logits, so program and reference are compared
  a block of positions at a time ON THE DEVICE and only the sums come
  back (`compare.LogitCheck` holds sums and takes them as they are);
- after the window every dispatch still in flight is harvested
  (`Runner._leave_idle`): a prompt's passes are all enqueued at admission,
  and a process that ends with seconds of them queued can die at exit.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, List

import numpy as np

from chipbench import compare, control, weights_sala
from chipbench.cell import BenchError
from chipbench.runners import engine as base

# the family's keys, copied from the configuration to the reference's cfg
FAMILY_KEYS = ("mixer_types", "kept_layers", "sparse_config",
               "lightning_nh", "lightning_nkv", "lightning_head_dim",
               "lightning_use_rope", "qk_norm", "use_output_norm",
               "use_output_gate", "attn_use_rope", "attn_use_output_gate",
               "scale_emb", "scale_depth", "dim_model_base",
               "tie_word_embeddings")
SPARSE_KEYS = {"kernel_size": "sparse_kernel_size",
               "kernel_stride": "sparse_kernel_stride",
               "block_size": "sparse_block_size",
               "init_blocks": "sparse_init_blocks",
               "window_size": "sparse_window_size", "topk": "sparse_topk",
               "dense_len": "sparse_dense_len"}
COMPARE_BLOCK = 1024    # positions a device-side comparison holds at once


def model_overrides(published: Dict[str, Any]) -> Dict[str, Any]:
    """The published config.json keys (and the configuration's cut and
    selection) as the program's SalaConfig names."""
    return {
        "vocab_size": published["vocab_size"],
        "hidden_size": published["hidden_size"],
        "intermediate_size": published["intermediate_size"],
        "num_layers": published["num_hidden_layers"],
        "num_heads": published["num_attention_heads"],
        "num_kv_heads": published["num_key_value_heads"],
        "head_dim": published["head_dim"],
        "rope_theta": float(published["rope_theta"]),
        "rms_norm_eps": float(published["rms_norm_eps"]),
        "mixer_types": tuple(published["mixer_types"]),
        "kept_layers": tuple(published["kept_layers"]),
        **{k: published[k] for k in FAMILY_KEYS[3:]},
        **{mine: int(published["sparse_config"][theirs])
           for theirs, mine in SPARSE_KEYS.items()},
    }


def _require_sala_program() -> None:
    import importlib.util

    if importlib.util.find_spec("ray_tpu.models.minicpm_sala") is None:
        raise BenchError(
            "this program has no lightning or block-sparse layers "
            "(ray_tpu/models/minicpm_sala.py): it cannot run a "
            "MiniCPM-SALA configuration")


def _shape_probe(econf):
    """The program's parameter tree as shapes (no arrays are made)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm.stage import (init_params, model_family,
                                         serve_model_config)

    cfg = serve_model_config(econf)
    model = model_family(econf.model).serving_model(cfg)
    return jax.eval_shape(lambda: init_params(
        model, jnp.zeros((1, 8), jnp.int32), jax.random.PRNGKey(0)))


# ---------------------------------------------------------- the logits path
class PagedLogits:
    """Prefill in passes, then decode, through the engine's model and
    params and a pool of the benchmark's own: one sequence at a time, one
    slot. `run(prompt, g, on_block)` hands `on_block` every block of
    logits ON THE DEVICE, (first position, [n, V], n real, the sparse
    layers' selected blocks [n_sparse, n, G, MP] bool or None where the
    pass made no selection) and returns the g greedy tokens that were fed
    back."""

    def __init__(self, engine, longest: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.serve.llm.stage import model_family

        self.engine = engine
        mc, conf = engine.model_cfg, engine.config
        self.family = family = model_family(conf.model)
        self.page = conf.page_size
        self.bucket = conf.prefill_buckets[-1]
        mp = -(-(longest + 1) // self.page) + 1
        self.mp = mp = -(-mp // 8) * 8
        self.spec = family.pool_spec(mc, mc.num_layers, 1 + mp, self.page, 1)
        self.bt = jnp.arange(1, 1 + mp, dtype=jnp.int32)[None]
        model = engine.model

        def run(params, pool, bt, total, ids, positions, slots, ctx):
            cache = family.serving_cache(mc, pool, bt, total, slots,
                                         ctx_pages=ctx)
            (logits, new), sown = model.apply(
                {"params": params}, ids, positions=positions,
                kv_caches=cache, mutable=["selection"])
            # a run of sparse layers sows [run, B, S, G, MP]; the runs in
            # the model's order are the sparse layers in order
            picked = [v["blocks"][0][:, 0] for _, v in sorted(
                sown.get("selection", {}).items(),
                key=lambda kv: int(kv[0].split("_")[1]))]
            return (logits[0], new.pool,
                    jnp.concatenate(picked) if picked else None)

        self.step = jax.jit(run, donate_argnums=(1,),
                            static_argnames=("ctx",))

    def run(self, prompt: List[int], g: int, on_block) -> List[int]:
        import jax
        import jax.numpy as jnp

        params, sb = self.engine.params, self.bucket
        pool = jax.tree.map(lambda sd: jnp.zeros(*sd), self.spec,
                            is_leaf=lambda sd: isinstance(sd, tuple))
        n, last = len(prompt), None
        for start in range(0, n, sb):
            m = min(sb, n - start)
            ids = np.zeros((1, sb), np.int32)
            ids[0, :m] = prompt[start:start + m]
            logits, pool, picked = self.step(
                params, pool, self.bt, jnp.asarray([start + m], jnp.int32),
                jnp.asarray(ids),
                jnp.asarray(start + np.arange(sb, dtype=np.int32))[None],
                jnp.zeros((1,), jnp.int32), ctx=self.mp if start else 0)
            on_block(start, logits, m, picked)
            last = logits[m - 1]
        fed = [int(jnp.argmax(last))]
        for j in range(1, g):
            t = n + j - 1
            logits, pool, picked = self.step(
                params, pool, self.bt, jnp.asarray([t + 1], jnp.int32),
                jnp.asarray([[fed[-1]]], jnp.int32),
                jnp.asarray([[t]], jnp.int32), None, ctx=0)
            on_block(t, logits, 1, picked)
            fed.append(int(jnp.argmax(logits[0])))
        del pool
        return fed


class StreamedLogitCheck(compare.LogitCheck):
    """`compare.LogitCheck` fed with sums taken on the device, and beside
    the judged numbers, as NOTES: the share of (position, sparse layer, kv
    head) past dense_len whose selected blocks are not the reference's,
    and the logits' error over the positions where every layer's are."""

    def __init__(self):
        super().__init__()
        self.agree = compare.LogitCheck()     # positions where sets agree
        self.sets = self.sets_differ = 0
        self.worst_at = None                  # position of the largest error

    @staticmethod
    def _add(check, err2, ref2, max_err, max_ref, n) -> None:
        check._err2 += float(err2)
        check._ref2 += float(ref2)
        check._n += int(n)
        check._max_err = max(check._max_err, float(max_err))
        check._max_ref = max(check._max_ref, float(max_ref))

    def add_sums(self, every, agreeing=None, worst_at=None) -> None:
        """`every`, `agreeing`: (err2, ref2, max err, max ref, logits);
        `worst_at`: the position of `every`'s max err."""
        if worst_at is not None and float(every[2]) > self._max_err:
            self.worst_at = int(worst_at)
        self._add(self, *every)
        if agreeing is not None:
            self._add(self.agree, *agreeing)

    def result(self, limits):
        out = super().result(limits)
        out["notes"]["logit_max_err_position"] = self.worst_at
        if self.sets:
            where = self.agree.result({"logit_rel_rms_err": float("inf"),
                                       "logit_max_err_over_rms":
                                           float("inf")})
            out["notes"].update(
                selection_sets=self.sets,
                selection_differs_share=self.sets_differ / self.sets,
                positions_where_selection_agrees=self.agree._n,
                **{r["name"] + "_where_selection_agrees": r["value"]
                   for r in where["numbers"]})
        return out


class Comparer:
    """`on_block` of `PagedLogits.run`: compares a block of logits (first
    position, [rows, V] on the device, n real, the program's selected
    blocks or None) with the reference's head over `h_ref` [S, H]
    (`reference.hidden`'s output) at the same positions, COMPARE_BLOCK
    positions at a time, and adds the sums of the centred logits' error
    to `check`: over every real position, and over those where every
    sparse layer selected the reference's blocks (`sel_ref` [n_sparse, S,
    G, NB], None: not looked at)."""

    def __init__(self, check: StreamedLogitCheck, reference, ref_w, h_ref,
                 sel_ref=None, dense_len: int = 0):
        import jax
        import jax.numpy as jnp

        self.check, self.ref_w = check, ref_w
        self.h = jnp.pad(h_ref, ((0, COMPARE_BLOCK), (0, 0)))
        self.sel_ref = sel_ref       # [n_sparse, S, G, NB] or None

        def part(err, ref, keep):
            err = jnp.where(keep, err, 0.0)
            ref = jnp.where(keep, ref, 0.0)
            return ((err * err).sum(), (ref * ref).sum(), jnp.abs(err).max(),
                    jnp.abs(ref).max(), keep.sum() * err.shape[-1])

        def sums(w, h, prog, n, agree):
            ref = reference.head(w, h, "float32")
            p = prog.astype(jnp.float32)
            p = p - p.mean(-1, keepdims=True)
            ref = ref - ref.mean(-1, keepdims=True)
            real = (jnp.arange(prog.shape[0]) < n)[:, None]
            worst = jnp.where(real[:, 0], jnp.abs(p - ref).max(-1), -1.0)
            return (part(p - ref, ref, real),
                    part(p - ref, ref, real & agree[:, None]),
                    worst.argmax())

        def differ(picked, want, first, n):
            """picked [L, rows, G, MP], want [L, rows, G, NB] -> (per
            (layer, row, group): are the sets different, past dense_len
            and among the n real rows)."""
            nb = want.shape[-1]
            diff = (picked[..., :nb] != want).any(-1)
            rows = jnp.arange(picked.shape[1])
            live = ((rows < n) & (first + rows >= dense_len))[None, :, None]
            return diff & live, jnp.broadcast_to(live, diff.shape).sum()

        self.sums, self.differ = jax.jit(sums), jax.jit(differ)

    def __call__(self, start: int, logits, n: int, picked=None) -> None:
        import jax
        import jax.numpy as jnp

        rows_all = logits.shape[0]
        agree = jnp.ones((rows_all,), bool)
        looked = self.sel_ref is not None and picked is not None
        if looked:
            # a last pass reaches past the sequence: pad what is missing
            want = self.sel_ref[:, start:start + rows_all]
            want = jnp.pad(want, ((0, 0), (0, rows_all - want.shape[1]),
                                  (0, 0), (0, 0)))
            diff, sets = self.differ(picked, want, start, n)
            self.check.sets += int(sets)
            self.check.sets_differ += int(diff.sum())
            agree = ~diff.any((0, 2))
        with jax.default_matmul_precision("highest"):
            for lo in range(0, n, COMPARE_BLOCK):
                rows = min(COMPARE_BLOCK, rows_all - lo)
                every, agreeing, worst = self.sums(
                    self.ref_w, self.h[start + lo:start + lo + rows],
                    logits[lo:lo + rows], min(n - lo, rows),
                    agree[lo:lo + rows])
                self.check.add_sums(every, agreeing,
                                    start + lo + int(worst))


def _reference_hidden(reference, ref_w, cfg, seq: List[int],
                      precision: str = "float32", want_selection=False):
    """-> (`reference.hidden`'s [S, H], its selected blocks or None)."""
    import jax

    fn = jax.jit(lambda w, ids: reference.hidden(w, ids, cfg, precision,
                                                 want_selection))
    with jax.default_matmul_precision("highest"):
        return fn(ref_w, control.padded(seq)[0])


@contextlib.contextmanager
def _sala_set_up():
    """`engine.Runner.setup` reaches for the module's `model_overrides`,
    `_shape_probe` and `weights`; for the length of a set-up they are
    this module's. (The file may not be edited by this PR: PERF.md, Open
    questions.)"""
    mine = {"model_overrides": model_overrides, "_shape_probe": _shape_probe,
            "weights": weights_sala}
    theirs = {k: getattr(base, k) for k in mine}
    for k, v in mine.items():
        setattr(base, k, v)
    try:
        yield
    finally:
        for k, v in theirs.items():
            setattr(base, k, v)


class Runner(base.Runner):
    def __init__(self, cell, seed: int, seconds: float, log):
        _require_sala_program()
        super().__init__(cell, seed, seconds, log)
        self.published.update({k: cell.config[k] for k in FAMILY_KEYS})
        self.published["num_hidden_layers"] = cell.config["num_hidden_layers"]

    def setup(self, warm: bool = True) -> Dict[str, Any]:
        with _sala_set_up():
            check = super().setup(warm)
        got = self.engine.model_cfg
        if list(got.layers) != list(self.published["kept_layers"]):
            raise BenchError(f"the engine runs published layers "
                             f"{got.layers}; the configuration says "
                             f"{self.published['kept_layers']}")
        st = self.engine.stats()
        self.log(f"state: lightning state pool {st['lin_state_pool_bytes']:,}"
                 f" bytes, compressed keys {st['sparse_index_pool_bytes']:,}"
                 f" bytes beside the pages; prefix reuse off; "
                 f"{st['prefill_resumed_passes_total']} resumed passes so "
                 f"far")
        return check

    def run_window(self, tracer) -> None:
        """`engine.Runner.run_window`, then a line on what the window's
        count holds: `serve_tok_s` takes a prompt's tokens when its
        prefill ENDS, and a prompt of this mix is prefilled for seconds,
        so the prompts that were admitted in the ramp and end in the
        window's first seconds are part of the count (no later ones make
        up for them at the end: those still in progress are left out)."""
        super().run_window(tracer)
        self._leave_idle()
        ended = sorted((r.first_s, r.prompt_tokens) for r in self.records
                       if r.first_s is not None
                       and 0.0 < r.first_s <= self.seconds)
        early = [(t, n) for t, n in ended if t <= 5.0]
        self.log(f"window: {len(ended)} prompts' prefills ended in it, "
                 f"{sum(n for _, n in ended)} prompt tokens; of them "
                 f"{len(early)} in its first 5 s ({sum(n for _, n in early)}"
                 f" tokens: admitted in the ramp); "
                 f"{sum(n for t, n in self.token_events if 0.0 < t <= self.seconds)}"
                 f" tokens generated")

    def _leave_idle(self) -> None:
        """`engine.Runner.run_window` aborts what is open and steps once,
        which harvests ONE dispatch. A prompt of this mix is enqueued as 2
        to 10 passes of 0.4-0.6 s in the step that admits it, so up to 5 s
        of passes may still be queued on the device then, each with a
        copy of its tokens to the host pending. A process that ends so can
        die at exit (SIGSEGV in `xla::TpuClient::pending_event_logger()`
        under `TpuRawBuffer::CopyToLiteralAsync()`, on a worker thread: a
        pass ends and its copy starts while the client is torn down; seen
        once in 26 runs, after the result line was printed, and it is what
        the benchmark check met). Harvest them all: nothing is queued and
        no copy is pending when the window's numbers are read."""
        import time

        import jax

        t0, n = time.monotonic(), 0
        while self.engine.has_work():
            self.engine.step()
            n += 1
        jax.block_until_ready(self.engine.compute.kv_pages)
        self.log(f"after the window: {n} more steps and "
                 f"{time.monotonic() - t0:.2f} s until the device was idle")

    def _compare(self, prompts, eprompts, emitted, g: int):
        import time

        paged = PagedLogits(self.engine, max(map(len, prompts)) + g)
        cfg = dict(self.published)
        ref_w = self.reference.weights_from_program_tree(self.engine.params)
        out = StreamedLogitCheck()
        fed_all = []
        for p in prompts:
            t0 = time.monotonic()
            fed = paged.run(p, g, lambda *a: None)
            seq = p + fed[:-1]
            h_ref, sel_ref = _reference_hidden(self.reference, ref_w, cfg,
                                               seq, want_selection=True)
            h_ref.block_until_ready()
            t1 = time.monotonic()
            again = paged.run(p, g, Comparer(
                out, self.reference, ref_w, h_ref, sel_ref,
                int(cfg["sparse_config"]["dense_len"])))
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

    def _check_outputs(self) -> Dict[str, Any]:
        """As `engine.Runner._check_outputs`, with (A) streamed: the
        program's passes and decode steps run twice, once for the tokens
        it feeds back and once to be compared, block by block, with the
        reference's head over the reference's hidden states of the same
        sequence."""
        import time

        spec = self.mix["check"]
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, 0xC4EC])
        prompts = [rng.integers(0, self.vocab, int(n)).tolist()
                   for n in spec["prompt_lens"]]
        ep = spec["engine_prompts"]
        eprompts = [rng.integers(0, self.vocab, int(n)).tolist()
                    for n in np.rint(np.linspace(ep["min_len"], ep["max_len"],
                                                 ep["count"]))]
        t0 = time.monotonic()
        emitted = base._engine_generate(self.engine, eprompts,
                                        int(ep["decode_tokens"]))
        self.log(f"check: the engine's {len(eprompts)} prompts in "
                 f"{time.monotonic() - t0:.1f} s")
        # the engine is idle and its pool (3.3 GB at the cell's size) holds
        # nothing a request needs: dropped while the float32 reference
        # runs beside the weights, made anew after
        stage = self.engine.compute
        stage.kv_pages = None
        try:
            out, fed_all = self._compare(prompts, eprompts, emitted,
                                         int(spec["decode_tokens"]))
        finally:
            stage.kv_pages = stage.fresh_pool()
        self.check_sample = {
            "logit_seqs": list(zip(prompts, fed_all)),
            "engine_prompts": eprompts, "engine_tokens": emitted}
        return out.result(self.cell.config["limits"])


def control_numbers(reference, ref_w, cfg: Dict[str, Any], precision: str,
                    sample: Dict[str, Any], limits: Dict[str, float]
                    ) -> Dict[str, Any]:
    """`control.serve_numbers` for sequences whose logits do not fit: the
    reference at `precision`, teacher-forced along a sound run's
    sequences, against the float32 reference, streamed as the check is."""
    import jax
    import jax.numpy as jnp

    out = StreamedLogitCheck()
    head = jax.jit(lambda w, h: reference.head(w, h, precision))
    with jax.default_matmul_precision("highest"):
        for prompt, fed in sample["logit_seqs"]:
            seq = prompt + fed[:-1]
            h32, sel32 = _reference_hidden(reference, ref_w, cfg, seq,
                                           want_selection=True)
            hc, selc = _reference_hidden(reference, ref_w, cfg, seq,
                                         precision, want_selection=True)
            cmp = Comparer(out, reference, ref_w, h32, sel32,
                           int(cfg["sparse_config"]["dense_len"]))
            for lo in range(0, len(seq), COMPARE_BLOCK):
                cmp(lo, head(ref_w, hc[lo:lo + COMPARE_BLOCK]),
                    min(COMPARE_BLOCK, len(seq) - lo),
                    selc[:, lo:lo + COMPARE_BLOCK])
            del h32, hc
    prompts, emitted = sample["engine_prompts"], sample["engine_tokens"]
    ctl, ref = (control.reference_rows(reference, ref_w, cfg, p, prompts,
                                       emitted)
                for p in (precision, "float32"))
    for c, r in zip(ctl, ref):
        out.add_tokens(r, c.argmax(-1).tolist())
    return out.result(limits)
