"""Plain reference for the GigaChat3.5 language model (ai-sage/
GigaChat3.5-432B-A28B, `model_type` `gigachat3_5`): gated-delta-rule
linear-attention layers as the TOKEN-BY-TOKEN recurrence, multi-head latent
attention in its MATERIALISED form at every position, sigmoid-routed experts
beside a shared expert, leading dense layers; given one chip's SHARE of the
routed experts and a cut of the published layers. Written from the published
config's keys in straightforward jax.numpy; the rounded matmul is the dense
decoder's, YaRN's table, the rotation and the router are the latent
decoder's (imported: one source each).

    n(x)  = x / sqrt(mean(x^2) + eps) * (g sigmoid(w)), g = 2      R(i)
    h <- h + n2(Mixer_l(n1(h)));  h <- h + n4(FFN_l(n3(h)))
    logits = W_head n_f(h_L)
    GDN mixer, x = n1(h), position t, value head i on key head i // rep:
      [q|k|v|z] = W_qkvz x;  [b|a] = W_ba x
      u_t = silu(sum_{j<4} c_j qkv_{t-3+j}), zeros before the sequence
      q <- q / |q| / sqrt(D);  k <- k / |k|   (eps 1e-6 under the root)
      beta = sigmoid(b);  alpha = exp(-exp(A_log) softplus(a + dt_bias))
      S' = alpha_t S_{t-1};  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
      o_t = S_t^T q_t
      y = W_o [o / sqrt(mean(o^2) + eps_o) (1 + w_o) 2 sigmoid(z)]   R(iii)
    MLA mixer: `mla_moe_decoder`'s equations, and before W_o
      o_h <- o_h * sigmoid(W_g,h x) value by value                   R(ii)
    FFN: a published layer below `first_k_dense_replace` is dense, the
      rest Shared(x) + this chip's share of the routed sum (the latent
      decoder's router); every gated FFN is
      W_d (silu(min(W_g x, L)) * clip(W_u x, -L, L)), L = 10         R(iv)

float32, `highest` matmul precision, no cache, no kernel, no chunk, no
batching, an expert at a time. Computed in blocks of positions so that it
fits beside the program on the chip, and the program's bf16 weights are
upcast a layer at a time. Nothing is imported from the program.

Departures from the published model, each an `assumed` entry of the
configuration: R(i)-R(iv) are READINGS of keys whose code the published
config does not hold (one function each: `_norm`, the gate in `_attention`,
`_gdn_gate`, `_ffn`); the `[q|k|v|z]` and `[b|a]` column layouts are flat
(the published code groups them by key head: a permutation of seeded
weights); the rotated dims pair as the latent decoder's; the two
multi-token-prediction modules are no part of the next-token forward and
are left out; the share: `cfg["expert_first"]` and the held count say which
experts this chip has, `cfg["held"]["layers"]` which published layers.
`precision` other than "float32" is the CONTROL's (the router, the rotation
and the recurrence's state stay float32; the recurrence's q, k, v are
rounded as a matmul's operands are).
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax

from chipbench.references.dense_decoder import _mm, _round_operand
from chipbench.references.mla_moe_decoder import (POS_BLOCK, Q_BLOCK,
                                                  _by_blocks, _rotate, route,
                                                  softmax_scale,
                                                  yarn_inv_freq)


def weights_from_program_tree(params: Any) -> Dict[str, Any]:
    """Name the leaves of the program's tree (no copies): a stack a run of
    like layers, in order."""
    runs: List[Dict[str, Any]] = []
    for name in sorted(k for k in params if k.startswith("run_")):
        run = params[name]
        w = {n: run[n]["zc_weight"] for n in (
            "mixer_norm", "mixer_post_norm", "mlp_norm", "mlp_post_norm")}
        if "attn" in run:
            a = run["attn"]
            w["mla"] = {
                "q_a": a["q_a_proj"]["kernel"],
                "q_a_norm": a["q_a_norm"]["scale"],
                "q_b": a["q_b_proj"]["kernel"],
                "kv_a": a["kv_a_proj"]["kernel"],
                "kv_a_norm": a["kv_a_norm"]["scale"],
                "kv_b": a["kv_b_proj"], "gate": a["gate_proj"]["kernel"],
                "o": a["o_proj"]["kernel"]}
        else:
            g = run["gdn"]
            w["gdn"] = {
                "qkvz": g["qkvz_proj"]["kernel"], "ba": g["ba_proj"]["kernel"],
                "conv": g["conv_kernel"], "A_log": g["A_log"],
                "dt_bias": g["dt_bias"], "o_norm": g["o_norm"],
                "o": g["o_proj"]["kernel"]}
        if "mlp" in run:
            w["dense"] = {"gate_up": run["mlp"]["gate_up_proj"]["kernel"],
                          "down": run["mlp"]["down_proj"]["kernel"]}
        else:
            moe = run["moe"]
            w["moe"] = {
                "router": moe["router"], "router_bias": moe["router_bias"],
                "gate_up": moe["experts_gate_up"],
                "down": moe["experts_down"],
                "shared_gate_up": moe["shared"]["gate_up_proj"]["kernel"],
                "shared_down": moe["shared"]["down_proj"]["kernel"]}
        runs.append(w)
    return {"embed": params["embed"], "lm_head": params["lm_head"],
            "final_norm": params["final_norm"]["zc_weight"], "runs": runs}


def _norm(x, w, cfg: Dict[str, Any]):
    """R(i): `norm_type` ZeroCenteredGatedNorm, `layernorm_gating_weight`
    g: x / rms(x) * (g sigmoid(w)); the published modeling file's norm class
    would settle it."""
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                     + cfg["rms_norm_eps"])
    return y * (cfg["layernorm_gating_weight"]
                * jax.nn.sigmoid(w.astype(jnp.float32)))


def _rmsnorm(x, scale, eps):
    """The latent's inner norms (q_a, kv_a): a plain learned scale, as the
    DeepSeek-V3 layer has them."""
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _ffn(m, gate_up, down, cfg: Dict[str, Any], precision: str):
    """R(iv): `swiglu_limit` L: silu(min(gate, L)) * clip(up, -L, L); the
    published modeling file's MLP would settle where the clamp sits."""
    import jax.numpy as jnp

    f = down.shape[0]
    gu = _mm(m, gate_up, precision)
    gate, up = gu[:, :f], gu[:, f:]
    limit = cfg.get("swiglu_limit")
    if limit is not None:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return _mm(jax.nn.silu(gate) * up, down, precision)


def _attention(a, w, cfg: Dict[str, Any], precision: str):
    """a = n1(h) [S, hidden] -> W_o [g_1 o_1 .. g_H o_H]: materialised
    per-head keys and values at every position (`mla_moe_decoder.
    _attention`), each head's output gated (R(ii): `gated_attention`, the
    head-specific elementwise gate of Qiu et al. 2025 from the mixer's own
    input; the published attention class would settle its place and its
    width)."""
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
        gate = jax.nn.sigmoid(_mm(ab, w["gate"], precision))
        return _mm(o.reshape(-1, nh * dv) * gate, w["o"], precision)

    qb = min(Q_BLOCK, s)
    pad = (-s) % qb
    ap = jnp.pad(a, ((0, pad), (0, 0))).reshape(-1, qb, a.shape[1])
    pp = jnp.pad(pos, (0, pad)).reshape(-1, qb)
    return jax.lax.map(queries, (ap, pp)).reshape(-1, a.shape[1])[:s]


def _gdn_gate(o, z, w_o, cfg: Dict[str, Any]):
    """R(iii): `linear_gating_type` gated_rmsnorm_sigmoid_zero_centered,
    `linear_sigmoid_gate_scale` c: o / rms(o) * (1 + w_o) * c sigmoid(z)
    over a head's values; the published GigaChat35GatedDeltaNet class would
    settle it."""
    import jax.numpy as jnp

    y = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                     + cfg["linear_attn_o_norm_eps"])
    return (y * (1.0 + w_o.astype(jnp.float32))
            * (cfg["linear_sigmoid_gate_scale"] * jax.nn.sigmoid(z)))


def _unit(x, eps: float = 1e-6):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _gated_delta_net(a, w, cfg: Dict[str, Any], precision: str):
    """a = n1(h) [S, hidden] -> the GDN mixer's output [S, hidden]: the
    recurrence a token at a time, from a zero state."""
    import jax.numpy as jnp

    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    d, taps = cfg["linear_key_head_dim"], cfg["linear_conv_kernel_dim"]
    f32 = jnp.float32
    s = a.shape[0]
    channels = (2 * nk + nv) * d
    qkvz = _by_blocks(lambda b: _mm(b, w["qkvz"], precision), a)
    ba = _by_blocks(lambda b: _mm(b, w["ba"], precision), a)
    qkv, z = qkvz[:, :channels], qkvz[:, channels:]
    conv = w["conv"].astype(f32)
    window = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(window[j:j + s] * conv[j] for j in range(taps)))
    q = _unit(u[:, :nk * d].reshape(s, nk, d)) * d ** -0.5
    k = _unit(u[:, nk * d:2 * nk * d].reshape(s, nk, d))
    v = u[:, 2 * nk * d:].reshape(s, nv, d)
    q, k = (jnp.repeat(x, nv // nk, axis=1) for x in (q, k))
    q, k, v = (_round_operand(x, precision, -1) for x in (q, k, v))
    beta = jax.nn.sigmoid(ba[:, :nv])
    alpha = jnp.exp(-jnp.exp(w["A_log"].astype(f32)) * jax.nn.softplus(
        ba[:, nv:] + w["dt_bias"].astype(f32)))

    def token(state, xs):
        qt, kt, vt, at, bt = xs
        kept = at[:, None, None] * state                      # [H, Dk, Dv]
        delta = bt[:, None] * (vt - jnp.sum(kt[:, :, None] * kept, axis=1))
        state = kept + kt[:, :, None] * delta[:, None, :]
        return state, jnp.sum(qt[:, :, None] * state, axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((nv, d, d), f32),
                        (q, k, v, alpha, beta))
    y = _gdn_gate(o, z.reshape(s, nv, d), w["o_norm"], cfg)
    return _by_blocks(lambda b: _mm(b, w["o"], precision),
                      y.reshape(s, nv * d))


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
        y = _ffn(m, gate_up, down, cfg, precision)
        return acc + jnp.where(w_e[:, None] > 0, w_e[:, None] * y, 0.0), None

    acc, _ = jax.lax.scan(
        jax.checkpoint(one),
        _ffn(m, w["shared_gate_up"], w["shared_down"], cfg, precision),
        (w["gate_up"], w["down"], weights.T))
    return acc, chosen


def _layer(x, w, cfg: Dict[str, Any], precision: str):
    import jax.numpy as jnp

    a = _norm(x, w["mixer_norm"], cfg)
    mixed = (_attention(a, w["mla"], cfg, precision) if "mla" in w
             else _gated_delta_net(a, w["gdn"], cfg, precision))
    x = x + _norm(mixed, w["mixer_post_norm"], cfg)

    def ffn(b):
        m = _norm(b, w["mlp_norm"], cfg)
        if "dense" in w:
            y = _ffn(m, w["dense"]["gate_up"], w["dense"]["down"], cfg,
                     precision)
            chosen = jnp.zeros((b.shape[0], 1), bool)
        else:
            y, chosen = _expert_layer(m, w["moe"], cfg, precision)
        return _norm(y, w["mlp_post_norm"], cfg), chosen

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
    picked = []
    for run in weights["runs"]:
        x, chosen = jax.lax.scan(
            lambda x, w: _layer(x, w, cfg, precision), x, run)
        if "moe" in run:
            picked.append(chosen[:, :, None, :])
    x = _norm(x, weights["final_norm"], cfg)
    if not (want_selection and picked):
        return x, None
    return x, jnp.concatenate(picked)


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
