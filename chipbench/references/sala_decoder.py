"""Plain reference for a MiniCPM-SALA-family decoder: lightning
linear-attention layers beside block-sparse attention layers, written from
ISSUE 35's statement of the published description, in straightforward
jax.numpy. `mixer_types[l]` names published layer l; `kept_layers` are
the published indices that run (a cut keeps a layer's published index: the
decay and the residual scale depend on it). With x_n = rmsnorm(x), L the
PUBLISHED depth and r = scale_depth / sqrt(L):

    x = scale_emb * E[ids]
    per kept layer l:
      x = x + r * mixer_l(x_n);   m = rmsnorm(x)
      x = x + r * (silu(m Wgate) * (m Wup)) Wdown
    logits = (rmsnorm(x) / (hidden_size / dim_model_base)) Whead

  lightning-attn (H heads of d):
      q, k, v, g = x_n Wq, x_n Wk, x_n Wv, x_n Wg
      q, k = rope(rmsnorm_d(q)), rope(rmsnorm_d(k))   (half-split pairing,
                                                      theta = rope_theta)
      S_t = lam_h S_{t-1} + k_t^T v_t;   o_t = q_t S_t / sqrt(d)
            TOKEN BY TOKEN, float32, from S = 0
      out = (rmsnorm_d(o) * sigmoid(g)) Wo
      lam_h = exp(-s_h (1 - l / (L - 1) + 1e-5)),  s_h = 2^(-8 h / H),
              h = 1..H                                     (`assumed`)
  minicpm4 (Hq query heads on G kv heads of d, no rotation):
      q, k = rmsnorm_d(q), rmsnorm_d(k)
      Kc_j = mean(K[stride j : stride j + kernel])
      for a query t >= dense_len, a kv-head group g (its Hq/G heads):
        r^h[j]  = softmax_j(q_t^h . Kc_j / sqrt(d)) over the kernels with
                  stride j + kernel <= t + 1
        R[j]    = sum_h r^h[j];  score[b] = max R[j] over the kernels that
                  overlap block b
        blocks  = {b < init_blocks} + {blocks of positions t-window+1..t}
                  + the topk best of the rest (a tie: the lower index)
        o_t^h   = softmax attention over the keys <= t of those blocks
      a query t < dense_len attends to every key <= t
      out = (o * sigmoid(g)) Wo

DEPARTURES from the family's published code, each `assumed` in the
configuration file: (1) dense below `dense_len` is decided a QUERY (by
its position), where the family's code switches a whole call by the
call's length, which makes a token's output depend on what follows it;
(2) the family's two-stage LSE shortcut (`compress_k2`) approximates its
CUDA kernels, not the model: left out; (3) the output norm of a lightning
layer is taken over the head dim, a head at a time; (4) the decay slopes
are Lightning Attention-2's (the config has no slope key).

float32 throughout, `jax.default_matmul_precision("highest")`, no
kernels, no cache: dense scores for the selection and the attention, by
blocks of queries, and every token-wise matmul by blocks of positions, so
that 20k positions fit beside the program. Nothing is imported from the
program. The weights are the benchmark's own, handed over in the
program's layout (one stacked tree a RUN of like layers) and upcast one
layer at a time inside the scan:

    embed [V, H], lm_head [H, V], final_norm [H]
    runs[i]: lightning  qkvg [n, H, 4 H d]  columns [q | k | v | g]
             sparse     qkvg [n, H, (2 Hq + 2 G) d]  columns [q | k | v | g]
             o [n, Hq d, H], gate_up [n, H, 2F] columns [gate | up],
             down [n, F, H], input_norm, mlp_norm [n, H], q_norm, k_norm
             [n, d], and o_norm [n, d] (lightning)

`precision` other than "float32" is for the CONTROL: every matmul operand
(projections, scores, probabilities; the compressed keys' scores too)
rounded as `dense_decoder` rounds them. The lightning recurrence has no
matmul and stays float32.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

from chipbench.references.dense_decoder import (_mm, _rmsnorm, _rope,
                                                _round_operand)

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
Q_BLOCK = 256      # queries per block of the dense scores (memory only)
POS_BLOCK = 2048   # positions per block of a token-wise matmul


def runs_of(cfg: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...]]]:
    """[(mixer type, published indices)]: the kept layers as runs of like
    layers, in order."""
    types = cfg["mixer_types"]
    kept = cfg.get("kept_layers") or list(range(cfg["num_hidden_layers"]))
    out: List[Tuple[str, List[int]]] = []
    for i in kept:
        if out and out[-1][0] == types[i]:
            out[-1][1].append(i)
        else:
            out.append((types[i], [i]))
    return [(k, tuple(v)) for k, v in out]


def weights_from_program_tree(params: Any) -> Dict[str, Any]:
    """Name the leaves of the program's SalaModel tree (no copies)."""
    runs = []
    for i in range(sum(k.startswith("run_") for k in params)):
        run = params[f"run_{i}"]
        w = {"qkvg": run["qkvg_proj"]["kernel"],
             "o": run["o_proj"]["kernel"],
             "gate_up": run["mlp"]["gate_up_proj"]["kernel"],
             "down": run["mlp"]["down_proj"]["kernel"],
             "input_norm": run["input_norm"]["scale"],
             "mlp_norm": run["mlp_norm"]["scale"],
             "q_norm": run["q_norm"]["scale"],
             "k_norm": run["k_norm"]["scale"]}
        if "o_norm" in run:
            w["o_norm"] = run["o_norm"]["scale"]
        runs.append(w)
    return {"embed": params["embed"], "lm_head": params["lm_head"],
            "final_norm": params["final_norm"]["scale"], "runs": runs}


def _by_blocks(fn, x, block: int = POS_BLOCK):
    """fn over blocks of rows of x [S, ...] (memory only)."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    blk = min(block, s)
    pad = (-s) % blk
    xp = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, xp.reshape((-1, blk) + x.shape[1:]))
    return out.reshape((-1,) + out.shape[2:])[:s]


def _sparse_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    a = cfg["sparse_config"]
    return {k: int(a[k]) for k in (
        "kernel_size", "kernel_stride", "block_size", "init_blocks",
        "window_size", "topk", "dense_len")}


def selected_blocks(q, k, cfg: Dict[str, Any], precision: str = "float32"):
    """q [S, Hq, d], k [S, G, d] (normed) -> [S, G, NB] bool: the blocks
    each query attends a kv-head group (everything <= t under dense_len).
    Dense scores against every compressed key, by blocks of queries."""
    import jax
    import jax.numpy as jnp

    sp = _sparse_params(cfg)
    ks, st, bs = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    s, hq, d = q.shape
    g = k.shape[1]
    nb = -(-s // bs)
    # a sequence shorter than a kernel: one kernel that no query may use
    nk = max((s - ks) // st + 1, 1)
    starts = jnp.arange(nk) * st
    kc = jax.vmap(lambda a: jax.lax.dynamic_slice_in_dim(
        k, a, ks, 0).mean(0))(starts)                         # [NK, G, d]
    kcq = _round_operand(kc, precision, -1)
    # the kernels that overlap block b: a run of (bs + ks) / st - 1 from
    # the first whose last key lies in it
    blocks = jnp.arange(nb)
    near = ((blocks * bs - ks) // st + 1)[:, None] \
        + jnp.arange((bs + ks) // st - 1)[None, :]            # [NB, m]
    overlap = ((near >= 0) & (near < nk)
               & (near * st + ks - 1 >= blocks[:, None] * bs)
               & (near * st <= blocks[:, None] * bs + bs - 1))
    near = jnp.clip(near, 0, nk - 1)

    def block(args):
        qb, t = args                                     # [Q, Hq, d], [Q]
        qg = _round_operand(qb, precision, -1).reshape(-1, g, hq // g, d)
        logits = jnp.einsum("qgrd,kgd->qgrk", qg, kcq) / math.sqrt(d)
        valid = (starts[None, :] + ks <= t[:, None] + 1)[:, None, None, :]
        p = jax.nn.softmax(jnp.where(valid, logits, -jnp.inf), axis=-1)
        rel = jnp.where(valid, p, 0.0).sum(2)                 # [Q, G, NK]
        score = jnp.where(overlap, rel[:, :, near], 0.0).max(-1)
        own = (t // bs)[:, None, None]
        w0 = (jnp.maximum(t - (sp["window_size"] - 1), 0) // bs
              )[:, None, None]
        forced = (blocks < sp["init_blocks"]) | (blocks >= w0)
        masked = jnp.where(forced, -1.0, score)
        best, idx = jax.lax.top_k(masked, min(sp["topk"], nb))
        idx = jnp.where(best >= 0.0, idx, nb)
        chosen = (idx[..., None] == blocks).any(-2)
        sel = jnp.where((t < sp["dense_len"])[:, None, None], True,
                        forced | chosen)
        return sel & (blocks <= own)

    blk = min(Q_BLOCK, s)
    pad = (-s) % blk
    # a padded query's kernels: none valid -> softmax of -inf is nan, but
    # it is cut off below; keep it finite by giving it the last position
    t_all = jnp.minimum(jnp.arange(s + pad), s - 1)
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    out = jax.lax.map(block, (qp.reshape(-1, blk, hq, d),
                              t_all.reshape(-1, blk)))
    return out.reshape(-1, g, nb)[:s]


def _sparse_attention(q, k, v, sel, cfg, precision: str):
    """q [S, Hq, d], k, v [S, G, d], sel [S, G, NB] -> [S, Hq, d]: softmax
    attention over the keys <= t of the selected blocks, dense scores."""
    import jax
    import jax.numpy as jnp

    bs = _sparse_params(cfg)["block_size"]
    s, hq, d = q.shape
    g = k.shape[1]
    kq = _round_operand(k, precision, -1)
    vq = _round_operand(v, precision, 0)
    kblock = jnp.arange(s) // bs

    def block(args):
        qb, sb, t = args
        qg = _round_operand(qb, precision, -1).reshape(-1, g, hq // g, d)
        scores = jnp.einsum("qgrd,kgd->qgrk", qg, kq) / math.sqrt(d)
        keep = (jnp.take(sb, kblock, axis=2)
                & (jnp.arange(s)[None, None, :] <= t[:, None, None]))
        scores = jnp.where(keep[:, :, None, :], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("qgrk,kgd->qgrd", _round_operand(p, precision, -1),
                       vq)
        return o.reshape(-1, hq, d)

    blk = min(Q_BLOCK, s)
    pad = (-s) % blk
    t_all = jnp.minimum(jnp.arange(s + pad), s - 1)
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    sp_ = jnp.pad(sel, ((0, pad), (0, 0), (0, 0)), constant_values=True)
    out = jax.lax.map(block, (qp.reshape(-1, blk, hq, d),
                              sp_.reshape((-1, blk) + sel.shape[1:]),
                              t_all.reshape(-1, blk)))
    return out.reshape(-1, hq, d)[:s]


def _lightning(q, k, v, lam):
    """q, k, v [S, H, d] float32, lam [H] -> o [S, H, d]: the recurrence,
    token by token, float32, from a zero state."""
    import jax
    import jax.numpy as jnp

    _, h, d = q.shape

    def step(state, qkv):
        qt, kt, vt = qkv
        state = lam[:, None, None] * state + kt[:, :, None] * vt[:, None, :]
        return state, (qt[:, :, None] * state).sum(1) / math.sqrt(d)

    _, o = jax.lax.scan(step, jnp.zeros((h, d, d), jnp.float32), (q, k, v))
    return o


def _layer(x, w, published, kind: str, cfg: Dict[str, Any], precision: str,
           want_selection: bool):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps, f = cfg["rms_norm_eps"], cfg["intermediate_size"]
    depth = len(cfg["mixer_types"])
    r = cfg["scale_depth"] / math.sqrt(depth)
    s = x.shape[0]
    pos = jnp.arange(s)
    norm_d = lambda a, g_: _rmsnorm(a, g_.astype(f32), eps)   # noqa: E731
    a = _rmsnorm(x, w["input_norm"].astype(f32), eps)
    qkvg = _by_blocks(lambda b: _mm(b, w["qkvg"], precision), a)
    sel = None
    if kind == LIGHTNING:
        nh, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
        q, k, v, gate = (qkvg[:, i * nh * d:(i + 1) * nh * d]
                         for i in range(4))
        q = _rope(norm_d(q.reshape(s, nh, d), w["q_norm"]), pos,
                  cfg["rope_theta"])
        k = _rope(norm_d(k.reshape(s, nh, d), w["k_norm"]), pos,
                  cfg["rope_theta"])
        slopes = 2.0 ** (-8.0 * jnp.arange(1, nh + 1, dtype=f32) / nh)
        lam = jnp.exp(-slopes * (1.0 - published.astype(f32)
                                 / max(depth - 1, 1) + 1e-5))
        o = norm_d(_lightning(q, k, v.reshape(s, nh, d), lam), w["o_norm"])
        o = o.reshape(s, nh * d)
    else:
        nq, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        d = cfg["head_dim"]
        cut = [nq * d, (nq + g) * d, (nq + 2 * g) * d]
        q = norm_d(qkvg[:, :cut[0]].reshape(s, nq, d), w["q_norm"])
        k = norm_d(qkvg[:, cut[0]:cut[1]].reshape(s, g, d), w["k_norm"])
        v = qkvg[:, cut[1]:cut[2]].reshape(s, g, d)
        gate = qkvg[:, cut[2]:]
        sel = selected_blocks(q, k, cfg, precision)
        o = _sparse_attention(q, k, v, sel, cfg, precision)
        o = o.reshape(s, nq * d)
    o = o * jax.nn.sigmoid(gate)
    x = x + r * _by_blocks(lambda b: _mm(b, w["o"], precision), o)

    def mlp(b):
        m = _rmsnorm(b, w["mlp_norm"].astype(f32), eps)
        gu = _mm(m, w["gate_up"], precision)
        return _mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], w["down"], precision)

    x = x + r * _by_blocks(mlp, x)
    return x, (sel if want_selection else None)


def hidden(weights, ids, cfg: Dict[str, Any], precision: str = "float32",
           want_selection: bool = False):
    """ids [S] -> (the final norm's output [S, H] float32, ready for
    `head`; the sparse layers' selected blocks [n_sparse, S, G, NB] bool,
    or None). Under `jax.default_matmul_precision("highest")`."""
    import jax
    import jax.numpy as jnp

    x = weights["embed"][ids].astype(jnp.float32) * cfg["scale_emb"]
    picked = []
    for (kind, published), w in zip(runs_of(cfg), weights["runs"]):
        def body(x, wl, kind=kind):
            w_one, pub = wl
            x, sel = _layer(x, w_one, pub, kind, cfg, precision,
                            want_selection)
            return x, sel

        x, sel = jax.lax.scan(body, x, (w, jnp.asarray(published)))
        if kind == SPARSE and want_selection:
            picked.append(sel)
    x = _rmsnorm(x, weights["final_norm"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
    x = x / (cfg["hidden_size"] / cfg["dim_model_base"])
    return x, (jnp.concatenate(picked) if picked else None)


def head(weights, h, precision: str = "float32"):
    """h [N, H] (of `hidden`) -> logits [N, V] float32."""
    return _mm(h, weights["lm_head"], precision)


def _forward_one(weights, ids, cfg, precision):
    h, _ = hidden(weights, ids, cfg, precision)
    return _by_blocks(lambda b: head(weights, b, precision), h)


def forward(weights, ids, cfg: Dict[str, Any], precision: str = "float32"):
    """ids [B, S] int32 -> logits [B, S, V] float32; one sequence at a
    time."""
    import jax

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda row: _forward_one(weights, row, cfg, precision), ids)


def forward_rows(weights, ids, rows, cfg: Dict[str, Any],
                 precision: str = "float32"):
    """ids [B, S], rows [B, G] positions -> logits [B, G, V] float32 at
    those positions only."""
    import jax

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda a: head(weights, hidden(weights, a[0], cfg,
                                           precision)[0][a[1]], precision),
            (ids, rows))
