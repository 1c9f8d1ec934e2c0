"""Plain reference for the Kimi-K2 / DeepSeek-V3 language model
(moonshotai/Kimi-K2.5, `model_type` `kimi_k2`): multi-head latent attention
in its MATERIALISED form at every position, sigmoid-routed experts beside
a shared expert, a leading dense layer; given one chip's SHARE of the
routed experts. Written from the published config's keys and the family's
modelling code's description in straightforward jax.numpy; norms and the
rounded matmul are the dense decoder's (imported: one source).

    n(x) = rmsnorm with a learned weight, eps 1e-5
    h <- h + Attn(n(h));  h <- h + FFN_l(n(h));  logits = W_head n(h_L)
    Attn, x = n(h), position t:
      c_q = n(W_qa x);  q = W_qb c_q = H heads of [q_nope (dn) | q_rope (dr)]
      [c | k_r] = W_kva x;  c_kv = n(c);  k_rope = R_t(k_r)  (one for all
      heads);  q_rope <- R_t(q_rope);  [k_nope_h | v_h] = W_kvb,h c_kv
      score_h(t, j) = s (q_nope_h(t) . k_nope_h(j) + q_rope_h(t) . k_rope(j))
      causal softmax in float32; o_h = sum_j p_h(t, j) v_h(j); W_o [o_1..o_H]
      s = (dn + dr)^-0.5 m^2, m = 0.1 mscale_all_dim ln(factor) + 1
      R_t: YaRN's frequencies: f_i = theta^(-2i/dr); dim(r) = dr ln(orig /
      (2 pi r)) / (2 ln theta); low = max(floor(dim(beta_fast)), 0), high =
      min(ceil(dim(beta_slow)), dr - 1); ramp_i = clip((i - low) / (high -
      low), 0, 1); inv_freq_i = f_i / factor ramp_i + f_i (1 - ramp_i)
    FFN_0 (the first `first_k_dense_replace` layers): W_down(silu(W_gate x)
      * W_up x)
    FFN_l, l >= 1: sc = sigmoid(x W_r) over ALL routed experts, float32;
      chosen = the k largest of sc + b; w_i = sc_i / (sum of the chosen sc +
      1e-20) * routed_scaling_factor; Shared(x) + sum over the chosen i in
      [first, first + held) of w_i E_i(x): the weights are the whole
      model's, the sum is this chip's share, the rest is left out and the
      partial result goes on to the next layer.

float32, `highest` matmul precision, no cache, no kernel, no batching, an
expert at a time, NEVER the absorbed form (the program's decode is checked
against other algebra). Computed in blocks of positions so that it fits
beside the program on the chip, and the program's bf16 weights are upcast
a layer at a time. Nothing is imported from the program.

Departures from the published code, each an `assumed` entry of the
configuration: (1) the pairing of the rotated dims: dims i and i + dr/2
turn together (the published code de-interleaves `[x0, x1, x2, ..]` to
`[x0, x2, .. | x1, x3, ..]` before its `rotate_half`; with seeded weights
either layout is a permutation of W_qb's and W_kva's columns, and a
checkpoint loader would permute); (2) the fused gate_up layout of the
program's tree, split; (3) the share: `cfg["expert_first"]` and the held
count (the experts' leading axis) say which experts this chip has.
`precision` other than "float32" is the CONTROL's (the router and the
rotation stay float32).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax

from chipbench.references.dense_decoder import (_mm, _rmsnorm,
                                                _round_operand)

POS_BLOCK = 1024    # positions a projection or an FFN holds at once
Q_BLOCK = 128       # queries whose scores over the whole sequence exist


def weights_from_program_tree(params: Any) -> Dict[str, Any]:
    """Name the leaves of the program's tree (no copies)."""
    def attn(run):
        a = run["attn"]
        return {"q_a": a["q_a_proj"]["kernel"], "q_a_norm":
                a["q_a_norm"]["scale"], "q_b": a["q_b_proj"]["kernel"],
                "kv_a": a["kv_a_proj"]["kernel"], "kv_a_norm":
                a["kv_a_norm"]["scale"], "kv_b": a["kv_b_proj"],
                "o": a["o_proj"]["kernel"],
                "attn_norm": run["attn_norm"]["scale"],
                "mlp_norm": run["mlp_norm"]["scale"]}

    out = {"embed": params["embed"], "lm_head": params["lm_head"],
           "final_norm": params["final_norm"]["scale"]}
    if "dense_layers" in params:
        run = params["dense_layers"]
        out["dense"] = {**attn(run),
                        "gate_up": run["mlp"]["gate_up_proj"]["kernel"],
                        "down": run["mlp"]["down_proj"]["kernel"]}
    if "layers" in params:
        run = params["layers"]
        moe = run["moe"]
        out["moe"] = {**attn(run), "router": moe["router"],
                      "router_bias": moe["router_bias"],
                      "gate_up": moe["experts_gate_up"],
                      "down": moe["experts_down"],
                      "shared_gate_up":
                          moe["shared"]["gate_up_proj"]["kernel"],
                      "shared_down": moe["shared"]["down_proj"]["kernel"]}
    return out


def yarn_inv_freq(cfg: Dict[str, Any]):
    """[dr / 2] float32, from the config's rope_theta and rope_scaling."""
    import jax.numpy as jnp

    dr, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    orig = rs["original_max_position_embeddings"]

    def dim(turns):
        return dr * math.log(orig / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(rs["beta_fast"])), 0)
    high = min(math.ceil(dim(rs["beta_slow"])), dr - 1)
    i = jnp.arange(dr // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / dr)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / rs["factor"] * ramp + f * (1.0 - ramp)


def softmax_scale(cfg: Dict[str, Any]) -> float:
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rotate(x, positions, inv_freq):
    """x [S, n, dr], positions [S]; dims i and i + dr/2 turn together."""
    import jax.numpy as jnp

    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _by_blocks(fn, x, block: int = POS_BLOCK):
    """fn over blocks of x's leading axis (memory only)."""
    import jax.numpy as jnp

    s = x.shape[0]
    blk = min(block, s)
    pad = (-s) % blk
    xp = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, xp.reshape((-1, blk) + x.shape[1:]))
    return out.reshape((-1,) + out.shape[2:])[:s]


def _attention(a, w, cfg: Dict[str, Any], precision: str):
    """a = n(h) [S, hidden] -> W_o [o_1 .. o_H] [S, hidden]: materialised
    per-head keys and values at every position."""
    import jax.numpy as jnp

    nh = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    f32 = jnp.float32
    s = a.shape[0]
    pos = jnp.arange(s)
    inv_freq = yarn_inv_freq(cfg)
    scale = softmax_scale(cfg)

    def keys_values(blk):
        ab, pb = blk
        kv_a = _mm(ab, w["kv_a"], precision)
        c_kv = _rmsnorm(kv_a[:, :r], w["kv_a_norm"].astype(f32), eps)
        k_rope = _rotate(kv_a[:, None, r:], pb, inv_freq)       # [n, 1, dr]
        kv = _mm(c_kv, w["kv_b"].reshape(r, nh * (dn + dv)),
                 precision).reshape(-1, nh, dn + dv)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_rope, (kv.shape[0], nh, dr))], axis=-1)
        return k, kv[..., dn:]

    blk = min(POS_BLOCK, s)
    pad = (-s) % blk
    ap = jnp.pad(a, ((0, pad), (0, 0))).reshape(-1, blk, a.shape[1])
    pp = jnp.pad(pos, (0, pad)).reshape(-1, blk)
    k, v = jax.lax.map(keys_values, (ap, pp))
    k = _round_operand(k.reshape(-1, nh, dn + dr)[:s], precision, -1)
    v = _round_operand(v.reshape(-1, nh, dv)[:s], precision, 0)

    def queries(blk):
        ab, pb = blk
        c_q = _rmsnorm(_mm(ab, w["q_a"], precision),
                       w["q_a_norm"].astype(f32), eps)
        q = _mm(c_q, w["q_b"], precision).reshape(-1, nh, dn + dr)
        q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], pb,
                                                  inv_freq)], axis=-1)
        scores = jnp.einsum("qhd,khd->hqk", _round_operand(q, precision, -1),
                            k) * scale
        mask = jnp.arange(s)[None, :] <= pb[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", _round_operand(p, precision, -1), v)
        return _mm(o.reshape(-1, nh * dv), w["o"], precision)

    qb = min(Q_BLOCK, s)
    pad = (-s) % qb
    ap = jnp.pad(a, ((0, pad), (0, 0))).reshape(-1, qb, a.shape[1])
    pp = jnp.pad(pos, (0, pad)).reshape(-1, qb)
    return jax.lax.map(queries, (ap, pp)).reshape(-1, a.shape[1])[:s]


def _ffn(m, gate_up, down, precision: str):
    f = down.shape[0]
    gu = _mm(m, gate_up, precision)
    return _mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], down, precision)


def route(m, router, bias, cfg: Dict[str, Any]):
    """m [S, H] -> (w [S, R] float32: the whole model's weight of a chosen
    expert, 0 elsewhere; chosen [S, R] bool)."""
    import jax.numpy as jnp

    sc = jax.nn.sigmoid(jnp.matmul(m, router.astype(jnp.float32)))
    _, idx = jax.lax.top_k(sc + bias.astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    chosen = jnp.zeros(sc.shape, bool).at[
        jnp.arange(sc.shape[0])[:, None], idx].set(True)
    kept = jnp.where(chosen, sc, 0.0)
    if cfg.get("norm_topk_prob", True):
        kept = kept / (kept.sum(-1, keepdims=True) + 1e-20)
    return kept * cfg["routed_scaling_factor"], chosen


def _expert_layer(m, w, cfg: Dict[str, Any], precision: str):
    """-> (Shared(m) + this chip's share of the routed sum, the chosen
    HELD experts [S, held] bool)."""
    import jax.numpy as jnp

    first, held = int(cfg.get("expert_first", 0)), w["gate_up"].shape[0]
    weights, chosen = route(m, w["router"], w["router_bias"], cfg)
    weights = weights[:, first:first + held]
    chosen = chosen[:, first:first + held]

    def one(acc, ew):
        gate_up, down, w_e = ew
        y = _ffn(m, gate_up, down, precision)
        return acc + jnp.where(w_e[:, None] > 0, w_e[:, None] * y, 0.0), None

    acc, _ = jax.lax.scan(
        jax.checkpoint(one),
        _ffn(m, w["shared_gate_up"], w["shared_down"], precision),
        (w["gate_up"], w["down"], weights.T))
    return acc, chosen


def _layer(x, w, cfg: Dict[str, Any], precision: str, dense: bool):
    import jax.numpy as jnp

    f32, eps = jnp.float32, cfg["rms_norm_eps"]
    a = _rmsnorm(x, w["attn_norm"].astype(f32), eps)
    x = x + _attention(a, w, cfg, precision)

    def ffn(b):
        m = _rmsnorm(b, w["mlp_norm"].astype(f32), eps)
        if dense:
            return _ffn(m, w["gate_up"], w["down"], precision), jnp.zeros(
                (b.shape[0], 1), bool)
        return _expert_layer(m, w, cfg, precision)

    blk = min(POS_BLOCK, x.shape[0])
    pad = (-x.shape[0]) % blk
    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, blk, x.shape[1])
    y, chosen = jax.lax.map(ffn, xp)
    s = x.shape[0]
    return (x + y.reshape(-1, x.shape[1])[:s],
            chosen.reshape(-1, chosen.shape[-1])[:s])


def hidden(weights, ids, cfg: Dict[str, Any], precision: str = "float32",
           want_selection: bool = False):
    """ids [S] -> (the final norm's output [S, H] float32, ready for
    `head`; the chosen held experts [L_moe, S, 1, held] bool, or None).
    Under `jax.default_matmul_precision("highest")`."""
    import jax.numpy as jnp

    x = weights["embed"][ids].astype(jnp.float32)
    picked = None
    for name, dense in (("dense", True), ("moe", False)):
        if name not in weights:
            continue

        def body(x, w, dense=dense):
            return _layer(x, w, cfg, precision, dense)

        x, chosen = jax.lax.scan(body, x, weights[name])
        if not dense:
            picked = chosen[:, :, None, :]
    x = _rmsnorm(x, weights["final_norm"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
    return x, (picked if want_selection else None)


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
