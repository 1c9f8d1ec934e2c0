"""MiniCPM-SALA-family hybrid decoder: lightning linear-attention layers
beside block-sparse attention layers.

`mixer_types` names every PUBLISHED layer ("lightning-attn" or
"minicpm4"): a list, not a period (the sparse layers of the 9B model sit
at 0, 9, 16, 17, 22, 29, 30, 31). `kept_layers` are the published indices
this model runs (None: all of them); a cut keeps a layer's published index,
because the lightning decay and the residual scale depend on it. With x_n
= RMSNorm(x) and r = scale_depth / sqrt(published depth):

    h_0    = scale_emb * embed(ids)
    h     <- h + r * mixer(h_n);   h <- h + r * W_down(silu(W_gate h_n) * W_up h_n)
    logits = W_head (RMSNorm(h_L) / (hidden_size / dim_model_base))

Lightning layer (H heads of D): q, k, v, g = W x_n; q, k <- RMSNorm over
the head dim, then RoPE; S_t = lambda_h S_{t-1} + k_t^T v_t, o_t = q_t S_t
/ sqrt(D) (ops/lightning_attention.py; S and its recurrence float32); o <-
RMSNorm over the head dim; o <- o * sigmoid(g); W_o o. lambda_h = exp(-s_h
(1 - l / (published depth - 1) + 1e-5)), s_h = 2^(-8 h / H), h = 1..H, l
the published layer index.

Sparse layer (Hq query heads on G kv heads of D, NO rotation): q, k <-
RMSNorm over the head dim; attention over the SELECTED blocks of the
context (ops/sparse_attention.py: the rule, a query's own); o <- o *
sigmoid(g); W_o o.

The stack is one scan a RUN of like layers, the runs in sequence
(models/jamba.py says why not an outer scan). Parameters: `run_<i>/...`
with a leading [run] axis.

Serving state is a `SalaCache`, three parts in ONE pool: the sparse
layers' pages `kv_pages` [n_sparse, P, G, page, 2D], their compressed
keys `kc` [n_sparse, P, G, page/stride, D] indexed by page id (they live
and die with pages), and the lightning layers' per-slot matrices
`lin_state` [n_lin, slots, H, D, D] float32. A prefill row RESUMES: where
its first position is 0 it starts from a zero state, else from what its
slot holds, and it leaves its final state there; its queries attend to the
pages earlier passes wrote. PADDING-PROOF as models/jamba.py: a position
past a row's length moves no state and no page, and an idle decode slot
keeps all three parts bit for bit.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..ops.lightning_attention import lightning_prefill, lightning_update
from ..ops.paged_attention import paged_prefill_attention, paged_write
from ..ops.sparse_attention import (SparseParams, compress_keys,
                                    kernels_scored, keys_attended,
                                    sparse_decode, sparse_prefill)
from ..util import tracing
from ._stack import (default_positions, dense, embed_table, head_at_gather,
                     own_cache, scan_run, whole_model_only)
from .llama import MLP, RMSNorm, rope

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
# the family's interface flags (serve/llm/stage.py: model_family): a
# prefill row resumes from its slot's state and the pages written, and
# the model computes the head at the position a row samples from only
RESUMES_PREFILL = True
HEAD_AT_GATHER = True


def pass_cost_ratios(cfg) -> tuple:
    """(weights a prefill pass reads, scores a (query, key) pair of a
    pass's attention makes), each over the parameters a token multiplies
    (serve/llm/engine.py: PassCost, which prices a score as the flash
    kernel makes it). Every layer is dense; only the sparse layers attend
    a context, and their masked pass is plain XLA that scores a pair for
    the selection and again for the attention, and sorts: 6.5 of the flash
    kernel's scores on the chip, 8 at 10k tokens of context and 5 at 40k
    (benchmarks/prefill_split_probe.py; PERF.md section 6, PR 39)."""
    return 1.0, 6.5 * cfg.n_sparse_layers * cfg.num_heads / cfg.num_params()


_PUBLISHED_MIXERS = tuple(
    SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31) else LIGHTNING
    for i in range(32))


@dataclass(frozen=True)
class SalaConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    mixer_types: Tuple[str, ...] = _PUBLISHED_MIXERS
    # published indices of the layers this model runs (None: all);
    # num_layers is how many that is
    kept_layers: Optional[Tuple[int, ...]] = None
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    lightning_use_rope: bool = True
    rope_theta: float = 10000.0
    qk_norm: bool = True
    use_output_norm: bool = True
    use_output_gate: bool = True
    attn_use_rope: bool = False
    attn_use_output_gate: bool = True
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    # the sparse layers' selection (the family's published sparse_config)
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_topk: int = 64
    sparse_dense_len: int = 8192
    max_seq_len: int = 524288
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    num_experts: int = 0       # what the engine reads of every config
    # accepted (the engine sets them for every family) and fixed here
    scan_layers: bool = True
    remat: bool = False

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        if self.kept_layers is not None:
            object.__setattr__(self, "kept_layers", tuple(self.kept_layers))
        kept = self.layers
        if len(kept) != self.num_layers:
            raise ValueError(
                f"num_layers {self.num_layers} but {len(kept)} layers kept "
                f"of the {len(self.mixer_types)} that mixer_types names")
        if any(not 0 <= i < len(self.mixer_types) for i in kept) \
                or list(kept) != sorted(set(kept)):
            raise ValueError(f"kept_layers {kept}: published indices, in "
                             f"order, each once")
        unknown = set(self.mixer_types) - {LIGHTNING, SPARSE}
        if unknown:
            raise ValueError(f"mixer_types names {unknown}")
        if (self.lightning_nkv != self.lightning_nh
                or not (self.qk_norm and self.use_output_norm
                        and self.use_output_gate and self.attn_use_output_gate
                        and self.lightning_use_rope)
                or self.attn_use_rope or self.tie_word_embeddings):
            raise NotImplementedError(
                "only the published MiniCPM-SALA switches are built: "
                "lightning_nkv == lightning_nh, qk_norm, output norm and "
                "gates on, rotation in the lightning layers only, an "
                "untied head")
        self.sparse.check()

    @property
    def layers(self) -> Tuple[int, ...]:
        if self.kept_layers is not None:
            return self.kept_layers
        return tuple(range(len(self.mixer_types)))[:self.num_layers]

    @property
    def head_dim_(self) -> int:
        return self.head_dim

    @property
    def runs(self) -> Tuple[Tuple[str, Tuple[int, ...]], ...]:
        """((mixer type, published indices), ...): the kept layers as
        runs of like layers, in order."""
        out = []
        for i in self.layers:
            kind = self.mixer_types[i]
            if out and out[-1][0] == kind:
                out[-1][1].append(i)
            else:
                out.append((kind, [i]))
        return tuple((k, tuple(v)) for k, v in out)

    def _count(self, kind: str) -> int:
        return sum(self.mixer_types[i] == kind for i in self.layers)

    @property
    def n_lightning_layers(self) -> int:
        return self._count(LIGHTNING)

    @property
    def n_sparse_layers(self) -> int:
        return self._count(SPARSE)

    # what serve/llm asks of a family whose layers keep per-slot state
    @property
    def n_slot_state_layers(self) -> int:
        return self.n_lightning_layers

    def slot_state_bytes_row(self) -> int:
        """What one sequence's lightning state costs to read or write
        once, all lightning layers (float32 [H, D, D] a layer)."""
        return (self.n_lightning_layers * self.lightning_nh
                * self.lightning_head_dim ** 2 * 4)

    @property
    def sparse(self) -> SparseParams:
        return SparseParams(
            kernel=self.sparse_kernel_size, stride=self.sparse_kernel_stride,
            block=self.sparse_block_size, init_blocks=self.sparse_init_blocks,
            window=self.sparse_window_size, topk=self.sparse_topk,
            dense_len=self.sparse_dense_len)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(len(self.mixer_types))

    def num_params(self) -> int:
        h, f = self.hidden_size, self.intermediate_size
        hd, ld = self.head_dim, self.lightning_head_dim
        mlp = 3 * h * f + 2 * h
        lin = 5 * h * self.lightning_nh * ld + 3 * ld
        sp = (3 * h * self.num_heads * hd + 2 * h * self.num_kv_heads * hd
              + 2 * hd)
        return (self.n_lightning_layers * (lin + mlp)
                + self.n_sparse_layers * (sp + mlp)
                + 2 * self.vocab_size * h + h)


@struct.dataclass
class SalaCache:
    """Serving state of a SalaModel, threaded through it as `kv_caches`.
    `slots` [B]: the decode slot each row of a PREFILL keeps its lightning
    state in (None: row i is slot i, a decode step over the slot set).
    `gather` [B]: the position (an index into the row) whose logits a
    prefill wants, -1 for none (a pass that is not the prompt's last); None:
    logits at every position."""

    kv_pages: jax.Array
    kc: jax.Array
    lin_state: jax.Array
    block_tables: jax.Array      # [B, MP]
    total_lens: jax.Array        # [B], INCLUDING the new tokens
    slots: Optional[jax.Array] = None
    gather: Optional[jax.Array] = None
    # STATIC, as models/llama.py: PagedCache has them. ctx_pages 0: no row
    # of this pass has anything in its pages yet
    ctx_pages: int = struct.field(pytree_node=False, default=0)
    ref_attention: bool = struct.field(pytree_node=False, default=False)

    @property
    def pool(self):
        return {"kv_pages": self.kv_pages, "kc": self.kc,
                "lin_state": self.lin_state}

    def step(self, pool, total_lens):
        return self.replace(kv_pages=pool["kv_pages"], kc=pool["kc"],
                            lin_state=pool["lin_state"],
                            total_lens=total_lens)


# ----------------------------------------------------------------- serving
def serving_model(cfg: SalaConfig, n_layers=None, first=True, last=True):
    return whole_model_only(SalaModel, cfg, first, last,
                            "whose layers are a list of two kinds")


# (stage.py: model_family) a prefill row RESUMES from its slot's state, so
# chunked prefill is served
CANNOT_BE_GIVEN = ("keeps per-slot linear-attention state and compressed "
                   "keys", {
    "spec_lookahead":
        "needs a verify dispatch whose rejected draft tokens can be "
        "rolled back, and a state advanced past them cannot be (no "
        "state snapshot yet)",
    "tp": "would have to split the lightning heads' per-slot matrices "
          "and the sparse layers' selection a kv head over the mesh, "
          "and nothing does yet",
    "pp": "slices a uniform `layers` axis (stage_params), and this "
          "model's layers are a list of two kinds with three kinds of "
          "state",
    "handoff": "moves KV pages only, and a request's per-slot state "
               "would be left behind",
})


class LightningSparseFacts:
    """What the linear-attention and block-sparse layers' dispatches count
    (serve/llm/stage.py: model_family). Every record says `lin_layers`,
    `lin_state_bytes_row` (the bytes one live row's state costs to read or
    write once) and `sparse_layers`; and of its real rows
    `sparse_tokens_read` (keys the sparse layers attended, summed over
    layers, kv-head groups and fused steps) and `sparse_kernels_scored`
    (compressed keys scored the same way); a prefill's also `pass_index`
    and `final`, a tuple a real row: how many passes of its prompt came
    before this one, and whether it is the last. The selection's COUNT is
    a function of the position alone (ops/sparse_attention.py:
    keys_attended), so nothing is fetched from the device for it."""

    STATS = {
        "lightning_prefill_tokens_total":
            "real prompt tokens x linear-attention layers (prefill)",
        "lightning_state_updates_total":
            "live rows x fused steps x linear-attention layers (decode)",
        "sparse_blocks_selected_total":
            "blocks the sparse layers attended, over queries, layers, kv "
            "heads",
        "sparse_ctx_tokens_total":
            "keys a dense layer would have attended for the same queries",
        "sparse_dense_rows_total":
            "decode (row, step)s at a position under dense_len (no "
            "selection)",
        "lin_state_pool_bytes":
            "bytes of the per-slot linear-attention state pool",
        "sparse_index_pool_bytes":
            "bytes of the compressed keys kept beside the pages",
    }

    def __init__(self, cfg: SalaConfig):
        self.lin_layers = cfg.n_lightning_layers
        self.sparse = cfg.sparse
        # a layer and kv-head group selects for itself
        self.selections = cfg.n_sparse_layers * cfg.num_kv_heads
        self.constant = {"lin_layers": self.lin_layers,
                         "lin_state_bytes_row": cfg.slot_state_bytes_row(),
                         "sparse_layers": cfg.n_sparse_layers}

    def _attended(self, totals: dict, positions: list, decode: bool) -> dict:
        read = scored = 0
        if self.selections and positions:
            sp, per = self.sparse, self.selections
            t = np.concatenate(positions)
            keys = keys_attended(t, sp)
            read = per * int(keys.sum())
            scored = per * int(kernels_scored(t, sp).sum())
            totals["sparse_blocks_selected_total"] += per * int(
                ((keys - t % sp.block - 1) // sp.block + 1).sum())
            totals["sparse_ctx_tokens_total"] += per * int((t + 1).sum())
            if decode:
                totals["sparse_dense_rows_total"] += int(
                    (t < sp.dense_len).sum())
        return {"sparse_tokens_read": read, "sparse_kernels_scored": scored}

    def prefill(self, totals: dict, rows, passes, ctx_pages: int) -> dict:
        totals["lightning_prefill_tokens_total"] += self.lin_layers * sum(
            n for _, n, _ in rows)
        return {**self._attended(
            totals, [np.arange(end - n, end) for _, n, end in rows], False),
            "pass_index": tuple(p for p, _ in passes),
            "final": tuple(f for _, f in passes)}

    def decode(self, totals: dict, rows, k: int) -> dict:
        totals["lightning_state_updates_total"] += (self.lin_layers
                                                    * len(rows) * k)
        return self._attended(
            totals, [ctx - 1 + np.arange(k) for _, _, ctx in rows], True)

    def sizes(self, pool_bytes: dict) -> dict:
        return {"lin_state_pool_bytes": pool_bytes["lin_state"],
                "sparse_index_pool_bytes": pool_bytes["kc"]}


def dispatch_facts(cfg: SalaConfig, engine_config) -> list:
    return [LightningSparseFacts(cfg)]


def pool_spec(cfg: SalaConfig, n_layers: int, num_pages: int,
              page_size: int, slots: int) -> dict:
    """name -> (shape, dtype) of what a serving engine keeps on the device
    for this model: pages and compressed keys for the sparse layers, a
    matrix a head and slot for the lightning layers."""
    sp = cfg.sparse
    if page_size != sp.block:
        raise ValueError(
            f"page_size {page_size} is not the sparse layers' block of "
            f"{sp.block} tokens: a selected block must be one page (the "
            f"decode path hands the kernel a table of selected PAGES)")
    return {
        "kv_pages": ((cfg.n_sparse_layers, num_pages, cfg.num_kv_heads,
                      page_size, 2 * cfg.head_dim), cfg.dtype),
        "kc": ((cfg.n_sparse_layers, num_pages, cfg.num_kv_heads, sp.kpb,
                cfg.head_dim), cfg.dtype),
        "lin_state": ((cfg.n_lightning_layers, slots, cfg.lightning_nh,
                       cfg.lightning_head_dim, cfg.lightning_head_dim),
                      jnp.float32),
    }


def serving_cache(cfg: SalaConfig, pool: dict, block_tables,
                  total_lens=None, slots=None, gather=None,
                  **static) -> SalaCache:
    """The cache one program pass hands the model: `pool` as `pool_spec`
    lays it out, block_tables [B, MP], total_lens [B] (None:
    `SalaCache.step` brings them)."""
    return SalaCache(
        kv_pages=pool["kv_pages"], kc=pool["kc"],
        lin_state=pool["lin_state"], block_tables=block_tables,
        total_lens=total_lens, slots=slots, gather=gather, **static)


# ------------------------------------------------------------------ layers
def _head_norm(cfg, name):
    return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)


def _finish(cfg, x, mixed):
    x = x + cfg.residual_scale * mixed
    return x + cfg.residual_scale * MLP(cfg, name="mlp")(
        RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="mlp_norm")(x))


def lightning_log_decay(cfg: SalaConfig, published_layer):
    """[H] float32: log lambda_h of published layer l (traced or not)."""
    nh = cfg.lightning_nh
    slopes = 2.0 ** (-8.0 * jnp.arange(1, nh + 1, dtype=jnp.float32) / nh)
    depth = len(cfg.mixer_types)
    return -slopes * (1.0 - jnp.asarray(published_layer, jnp.float32)
                      / max(depth - 1, 1) + 1e-5)


class LightningLayer(nn.Module):
    """Scan body of a run of lightning layers: the pool's parts ride the
    carry whole; (index into `lin_state`, published index) ride the xs."""
    config: SalaConfig

    @nn.compact
    def __call__(self, carry, xs, consts):
        cfg = self.config
        x, kv_pages, kc, lin = carry
        idx, published = xs
        positions, start, n_real, slots, _ = consts
        b, s, _ = x.shape
        nh, d = cfg.lightning_nh, cfg.lightning_head_dim
        xn = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_norm")(x)
        qkvg = dense(cfg, 4 * nh * d, ("embed", "qkv"), "qkvg_proj")(xn)
        q, k, v, gate = (a.reshape(b, s, nh, d)
                         for a in jnp.split(qkvg, 4, axis=-1))
        q = rope(_head_norm(cfg, "q_norm")(q), positions, cfg.rope_theta)
        k = rope(_head_norm(cfg, "k_norm")(k), positions, cfg.rope_theta)
        log_decay = lightning_log_decay(cfg, published)
        scale = d ** -0.5
        if s == 1:
            o, lin = lightning_update(q[:, 0], k[:, 0], v[:, 0], log_decay,
                                      lin, idx, n_real > 0, scale=scale)
            o = o[:, None]
        else:
            rows = []
            for i in range(b):
                slot = i if slots is None else slots[i]
                with tracing.scope("rtpu.attn.cache_write"):
                    held = jax.lax.dynamic_slice(
                        lin, (idx, slot, 0, 0, 0), (1, 1, nh, d, d))[0, 0]
                    s0 = jnp.where(start[i] > 0, held, 0.0)
                oi, s_last = lightning_prefill(
                    q[i], k[i], v[i], log_decay, s0, n_real[i], scale=scale)
                # a row with no real token (a masked warm-up pass) keeps
                # what its slot held
                with tracing.scope("rtpu.attn.cache_write"):
                    s_last = jnp.where(n_real[i] > 0, s_last, held)
                    lin = jax.lax.dynamic_update_slice(
                        lin, s_last[None, None], (idx, slot, 0, 0, 0))
                rows.append(oi)
            o = jnp.stack(rows)
        o = _head_norm(cfg, "o_norm")(o) * jax.nn.sigmoid(gate)
        out = dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj")(
            o.reshape(b, s, nh * d))
        return (_finish(cfg, x, out), kv_pages, kc, lin), None


class SparseLayer(nn.Module):
    """Scan body of a run of sparse layers; its xs are (index into
    `kv_pages` and `kc`, published index)."""
    config: SalaConfig
    ctx_pages: int
    ref_attention: bool

    @nn.compact
    def __call__(self, carry, xs, consts):
        cfg = self.config
        x, kv_pages, kc, lin = carry
        idx, _ = xs
        positions, start, _, _, (block_tables, total_lens) = consts
        b, s, _ = x.shape
        nq, g, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        sp, scale = cfg.sparse, d ** -0.5
        xn = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_norm")(x)
        qkvg = dense(cfg, (2 * nq + 2 * g) * d, ("embed", "qkv"),
                     "qkvg_proj")(xn)
        q, k, v, gate = jnp.split(
            qkvg, [nq * d, (nq + g) * d, (nq + 2 * g) * d], axis=-1)
        q = _head_norm(cfg, "q_norm")(q.reshape(b, s, nq, d))
        k = _head_norm(cfg, "k_norm")(k.reshape(b, s, g, d))
        v = v.reshape(b, s, g, d)
        kv_pages = paged_write(kv_pages, k, v, block_tables, positions,
                               total_lens, idx)
        kc = compress_keys(kv_pages, kc, block_tables, start, total_lens,
                           idx, new_tokens=s, sp=sp)
        picked = None
        if s == 1:
            o, picked = sparse_decode(
                q[:, 0], kv_pages, kc, block_tables, total_lens, idx, sp=sp,
                scale=scale, force_reference=self.ref_attention)
            o, picked = o[:, None], picked[:, None]
        elif self.ctx_pages == 0 and s <= sp.dense_len:
            # every query is under dense_len and nothing is cached: plain
            # causal attention among the new tokens
            o = paged_prefill_attention(
                q, k, v, kv_pages, block_tables, positions, total_lens,
                ctx_pages=0, scale=scale,
                impl="reference" if self.ref_attention else None, layer=idx)
        else:
            o, picked = (jnp.stack(a) for a in zip(*(sparse_prefill(
                q[i], kv_pages, kc, block_tables[i], start[i],
                total_lens[i], idx, sp=sp, scale=scale) for i in range(b))))
        if picked is not None:
            # [B, S, G, MP] bool, for a caller that asks for the
            # "selection" collection (the benchmark's check); nobody else
            # pays for it
            self.sow("selection", "blocks", picked)
        o = o.reshape(b, s, nq * d) * jax.nn.sigmoid(gate)
        out = dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj")(o)
        return (_finish(cfg, x, out), kv_pages, kc, lin), None


class SalaModel(nn.Module):
    config: SalaConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, kv_caches=None,
                 token_mask=None):
        """THE CALL of models/_stack.py, `kv_caches` a SalaCache;
        `token_mask` only sizes the call's own cache (no layer reads it)."""
        cfg = self.config
        s = input_ids.shape[1]
        positions = default_positions(input_ids, positions)
        cache = kv_caches if kv_caches is not None else own_cache(
            pool_spec, serving_cache, cfg, *input_ids.shape, token_mask,
            page=cfg.sparse.block)
        x = (embed_table(self, cfg)[input_ids] * cfg.scale_emb).astype(
            cfg.dtype)

        start = positions[:, 0]
        n_real = jnp.clip(cache.total_lens - start, 0, s)
        consts = (positions, start, n_real, cache.slots,
                  (cache.block_tables, cache.total_lens))
        carry = (x, cache.kv_pages, cache.kc, cache.lin_state)
        at = {LIGHTNING: 0, SPARSE: 0}
        for r, (kind, published) in enumerate(cfg.runs):
            n = len(published)
            xs = (at[kind] + jnp.arange(n), jnp.asarray(published))
            at[kind] += n
            if kind == LIGHTNING:
                body = scan_run(LightningLayer, n, f"run_{r}", cfg)
            else:
                body = scan_run(SparseLayer, n, f"run_{r}", cfg,
                                ctx_pages=cache.ctx_pages,
                                ref_attention=cache.ref_attention)
            carry, _ = body(carry, xs, consts)
        x, kv_pages, kc, lin = carry

        with tracing.scope("rtpu.head"):
            x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
            x = x / (cfg.hidden_size / cfg.dim_model_base)
        logits = head_at_gather(self, cfg, x, cache.gather)
        if kv_caches is None:
            return logits
        return logits, cache.replace(kv_pages=kv_pages, kc=kc,
                                     lin_state=lin)


# ---------------------------------------------------------------- registry
CONFIGS = {
    # MiniCPM-SALA 9B (huggingface.co/openbmb/MiniCPM-SALA config.json)
    "minicpm-sala": SalaConfig(),
    # six layers of the two kinds, runs of 1, 2 and 1; a selection that is
    # live from position 64 on (blocks of 16, kernels of 8 every 4, the
    # last 32 positions and the 2 best of the rest)
    "tiny-sala": SalaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        mixer_types=(SPARSE, LIGHTNING, LIGHTNING, SPARSE, SPARSE,
                     LIGHTNING),
        num_layers=6, num_heads=4, num_kv_heads=2, head_dim=16,
        lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
        dim_model_base=32, max_seq_len=512,
        sparse_kernel_size=8, sparse_kernel_stride=4, sparse_block_size=16,
        sparse_init_blocks=1, sparse_window_size=32, sparse_topk=2,
        sparse_dense_len=64),
}


def get_config(name: str, **overrides) -> SalaConfig:
    return dataclasses.replace(CONFIGS[name], **overrides)
