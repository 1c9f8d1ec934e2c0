"""Plain reference for the Mellum family (JetBrains/Mellum2-12B-A2.5B-
Instruct, `model_type` `mellum`): sliding-window and full attention layers
in one stack (`layer_types`), every layer's FFN 64 softmax-routed experts
of which 8 a token. Written from the published config's keys in
straightforward jax.numpy; norms and the rounded matmul are the dense
decoder's (imported: one source).

    n(x) = rmsnorm with a learned weight, eps 1e-6
    h <- h + Attn_l(n(h));  h <- h + MoE(n(h));  logits = W_head n(h_L)
    Attn_l, u = n(h), position t, kind = layer_types[l]:
      q = u W_q (32 heads of 128), k = u W_k, v = u W_v (4 heads of 128: 8
      query heads a kv head), no bias, no q/k norm
      q, k <- R_kind,t(q), R_kind,t(k) over all 128 dims, dims i and i + 64
      turning together:
        sliding_attention: angles t f_i, f_i = theta^(-2i/128), theta 5e5
        full_attention: YaRN: dim(r) = 128 ln(orig / (2 pi r)) / (2 ln
          theta); low = max(floor(dim(beta_fast)), 0), high =
          min(ceil(dim(beta_slow)), 127); ramp_i = clip((i - low) / (high -
          low), 0, 1); inv_freq_i = f_i / factor ramp_i + f_i (1 - ramp_i);
          cos and sin TIMES attention_factor (a score carries its square)
      score(t, j) = q(t) . k(j) / sqrt(128), softmax in float32 over
        j <= t, and in a sliding layer also j > t - sliding_window
      W_o [o_1 .. o_32]
    MoE, m = n(h): p = softmax(m W_r) over all 64 in float32; the 8
      largest; w = p / (their sum) (norm_topk_prob); sum_e w_e
      W_down_e(silu(W_gate_e m) * W_up_e m), width 896; no shared expert

float32, `highest` matmul precision, dense scores of a block of queries
against the WHOLE sequence under the layer kind's mask, an expert at a
time, no cache, no kernel. Computed in blocks of positions so that 20k
tokens fit beside the program on the chip; the program's bf16 weights are
read a layer at a time (a `lax.scan` over each run's stack: a Python loop
over layers would copy every layer's slice, 7.6 GB a second time). Nothing
is imported from the program.

`cfg["sliding_window"] = None` is the SECOND CONTROL: the same weights and
rotations with every layer's mask the full one (a program whose sliding
layers saw their whole context would compute this). `precision` other than
"float32" is the first control's (the router and the rotation stay
float32). Departures from the published code, each an `assumed` entry of
the configuration: the fused qkv and gate_up layouts of the program's
tree, split.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax

from chipbench.references.dense_decoder import (_mm, _rmsnorm,
                                                _round_operand)

POS_BLOCK = 1024    # positions a projection or an FFN holds at once
Q_BLOCK = 128       # queries whose scores over the whole sequence exist
SLIDING, FULL = "sliding_attention", "full_attention"


def runs_of(cfg: Dict[str, Any]) -> List[Tuple[str, int]]:
    """[(kind, layers)]: the configuration's layers as runs of like
    layers, the stacks the program's tree holds them in."""
    out: List[List[Any]] = []
    for kind in cfg["layer_types"][:cfg["num_hidden_layers"]]:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [(k, n) for k, n in out]


def weights_from_program_tree(params: Any) -> Dict[str, Any]:
    """Name the leaves of the program's tree (no copies): a stack a run."""
    runs = []
    for name in sorted(k for k in params if k.startswith("run_")):
        run = params[name]
        runs.append({"qkv": run["attn"]["qkv_proj"]["kernel"],
                     "o": run["attn"]["o_proj"]["kernel"],
                     "attn_norm": run["attn_norm"]["scale"],
                     "mlp_norm": run["mlp_norm"]["scale"],
                     "router": run["moe"]["router"],
                     "gate_up": run["moe"]["experts_gate_up"],
                     "down": run["moe"]["experts_down"]})
    return {"embed": params["embed"], "lm_head": params["lm_head"],
            "final_norm": params["final_norm"]["scale"], "runs": runs}


def inv_freq(cfg: Dict[str, Any], kind: str):
    """[64] float32 and the factor on cos and sin, from `rope_parameters`
    of the layer kind."""
    import jax.numpy as jnp

    d = cfg["head_dim"]
    rp = cfg["rope_parameters"][kind]
    theta = float(rp["rope_theta"])
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / d)
    if rp["rope_type"] == "default":
        return f, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    orig = rp["original_max_position_embeddings"]

    def dim(turns):
        return d * math.log(orig / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(rp["beta_fast"])), 0)
    high = min(math.ceil(dim(rp["beta_slow"])), d - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / rp["factor"] * ramp + f * (1.0 - ramp),
            float(rp["attention_factor"]))


def _rotate(x, positions, freq, factor: float):
    """x [S, n, d], positions [S]; dims i and i + d/2 turn together."""
    import jax.numpy as jnp

    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(x, block: int):
    """x [S, ...] -> ([n, block, ...] zero-padded, S)."""
    import jax.numpy as jnp

    s = x.shape[0]
    blk = min(block, s)
    pad = (-s) % blk
    xp = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return xp.reshape((-1, blk) + x.shape[1:]), s


def _by_blocks(fn, x, block: int = POS_BLOCK):
    """fn over blocks of x's leading axis (memory only)."""
    xb, s = _blocks(x, block)
    out = jax.lax.map(fn, xb)
    return out.reshape((-1,) + out.shape[2:])[:s]


def _attention(a, w, cfg: Dict[str, Any], kind: str, precision: str):
    """a = n(h) [S, hidden] -> W_o [o_1 .. o_H] [S, hidden]: every query
    against the whole sequence under the kind's mask."""
    import jax.numpy as jnp

    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    window = cfg.get("sliding_window") if kind == SLIDING else None
    s = a.shape[0]
    pos = jnp.arange(s)
    freq, factor = inv_freq(cfg, kind)
    w_q, w_k, w_v = (w["qkv"][:, :nq * d], w["qkv"][:, nq * d:(nq + nkv) * d],
                     w["qkv"][:, (nq + nkv) * d:])

    def keys_values(blk):
        ab, pb = blk
        k = _rotate(_mm(ab, w_k, precision).reshape(-1, nkv, d), pb, freq,
                    factor)
        return k, _mm(ab, w_v, precision).reshape(-1, nkv, d)

    ab, _ = _blocks(a, POS_BLOCK)
    pb, _ = _blocks(pos, POS_BLOCK)
    k, v = jax.lax.map(keys_values, (ab, pb))
    k = _round_operand(k.reshape(-1, nkv, d)[:s], precision, -1)
    v = _round_operand(v.reshape(-1, nkv, d)[:s], precision, 0)

    def queries(blk):
        ab, pb = blk
        q = _rotate(_mm(ab, w_q, precision).reshape(-1, nq, d), pb, freq,
                    factor)
        q = _round_operand(q, precision, -1).reshape(-1, nkv, nq // nkv, d)
        scores = jnp.einsum("qgrd,kgd->grqk", q, k) * d ** -0.5
        j = jnp.arange(s)[None, :]
        mask = j <= pb[:, None]
        if window is not None:
            mask = mask & (j > pb[:, None] - window)
        p = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf),
                           axis=-1)
        o = jnp.einsum("grqk,kgd->qgrd", _round_operand(p, precision, -1), v)
        return _mm(o.reshape(-1, nq * d), w["o"], precision)

    ab, _ = _blocks(a, Q_BLOCK)
    pb, _ = _blocks(pos, Q_BLOCK)
    return jax.lax.map(queries, (ab, pb)).reshape(-1, a.shape[1])[:s]


def route(m, router, cfg: Dict[str, Any]):
    """m [S, H] -> (w [S, E] float32: a chosen expert's weight, 0
    elsewhere; chosen [S, E] bool)."""
    import jax.numpy as jnp

    p = jax.nn.softmax(jnp.matmul(m, router.astype(jnp.float32)), axis=-1)
    _, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    chosen = jnp.zeros(p.shape, bool).at[
        jnp.arange(p.shape[0])[:, None], idx].set(True)
    kept = jnp.where(chosen, p, 0.0)
    if cfg.get("norm_topk_prob", True):
        kept = kept / jnp.maximum(kept.sum(-1, keepdims=True), 1e-9)
    return kept, chosen


def _expert_layer(m, w, cfg: Dict[str, Any], precision: str):
    """-> (the weighted sum of a token's chosen experts, chosen [S, E])."""
    import jax.numpy as jnp

    weights, chosen = route(m, w["router"], cfg)
    f = w["down"].shape[1]

    def one(acc, ew):
        gate_up, down, w_e = ew
        gu = _mm(m, gate_up, precision)
        y = _mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], down, precision)
        return acc + jnp.where(w_e[:, None] > 0, w_e[:, None] * y, 0.0), None

    acc, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(m),
                          (w["gate_up"], w["down"], weights.T))
    return acc, chosen


def _layer(x, w, cfg: Dict[str, Any], kind: str, precision: str):
    import jax.numpy as jnp

    f32, eps = jnp.float32, cfg["rms_norm_eps"]
    a = _rmsnorm(x, w["attn_norm"].astype(f32), eps)
    x = x + _attention(a, w, cfg, kind, precision)

    def ffn(b):
        return _expert_layer(_rmsnorm(b, w["mlp_norm"].astype(f32), eps), w,
                             cfg, precision)

    xb, s = _blocks(x, POS_BLOCK)
    y, chosen = jax.lax.map(ffn, xb)
    return (x + y.reshape(-1, x.shape[1])[:s],
            chosen.reshape(-1, chosen.shape[-1])[:s])


def hidden(weights, ids, cfg: Dict[str, Any], precision: str = "float32",
           want_selection: bool = False):
    """ids [S] -> (the final norm's output [S, H] float32, ready for
    `head`; the chosen experts [L, S, 1, E] bool, or None). Under
    `jax.default_matmul_precision("highest")`."""
    import jax.numpy as jnp

    x = weights["embed"][ids].astype(jnp.float32)
    picked = []
    for (kind, n), run in zip(runs_of(cfg), weights["runs"]):
        if run["qkv"].shape[0] != n:
            raise ValueError(f"a run of {run['qkv'].shape[0]} layers where "
                             f"layer_types has {n} {kind}")

        def body(x, w, kind=kind):
            return _layer(x, w, cfg, kind, precision)

        x, chosen = jax.lax.scan(body, x, run)
        picked.append(chosen[:, :, None, :])
    x = _rmsnorm(x, weights["final_norm"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
    return x, (jnp.concatenate(picked) if want_selection else None)


def head(weights, h, precision: str = "float32"):
    """h [N, H] (of `hidden`) -> logits [N, V] float32."""
    return _mm(h, weights["lm_head"], precision)


def _forward_one(weights, ids, cfg, precision):
    h, _ = hidden(weights, ids, cfg, precision)
    return _by_blocks(lambda b: head(weights, b, precision), h)


def forward(weights, ids, cfg: Dict[str, Any], precision: str = "float32"):
    """ids [B, S] int32 -> logits [B, S, V] float32; one sequence at a
    time."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda row: _forward_one(weights, row, cfg, precision), ids)


def forward_rows(weights, ids, rows, cfg: Dict[str, Any],
                 precision: str = "float32"):
    """ids [B, S], rows [B, G] positions -> logits [B, G, V] float32 at
    those positions only."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda a: head(weights, hidden(weights, a[0], cfg,
                                           precision)[0][a[1]], precision),
            (ids, rows))
