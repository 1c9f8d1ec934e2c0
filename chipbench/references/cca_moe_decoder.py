"""Plain reference for the ZAYA1 language model (Zyphra/ZAYA1-8B,
`model_type` `zaya`): attention inside a compressed latent (CCA,
arXiv:2510.04476 sections 3-4, the grouped form CCGQA) and one expert of 16
a token chosen by an MLP router whose state runs down the stack (EDA,
arXiv:2511.17127). Written from the layer's equations in straightforward
jax.numpy; the rounded matmul is the dense decoder's (imported: one source).

    x = E[ids];  per layer l, h = rmsnorm(x, g_attn), position t:
      q~ = h W_q  (Hq heads of D)    k~ = h W_k  (Hkv heads of D)
      v_t = [h_t W_v1 | h_(t-1) W_v2], h_(-1) = 0                       R1
      z = [q~ | k~];  u_t = a_1 z_t + a_0 z_(t-1)  a channel            R2
      c_t = u_t B_1 + u_(t-1) B_0  inside each of the Hq + Hkv heads    R2
      q = c^q + (q~ + rep(k~)) / 2;  k = c^k + (avg(q~) + k~) / 2       R3
      q <- q / |q|;  k <- k / |k| * tau_g  (tau_g = exp(log_tau_g))     R4
      q, k <- the first D/2 dims of each head rotated, theta 5e6
      o = softmax(q k^T + causal mask) v   (no 1/sqrt(D): R4)
      x <- x + o W_o                        (W_o [Hq D, hidden]: UP)
    m = rmsnorm(x, g_mlp), all of the router in float32:
      r_l = m W_d + g_l * r_(l-1), r_(-1) = 0                           R5
      s = W_3 gelu(W_2 gelu(W_1 rmsnorm(r_l, g_r))); p = softmax(s)     R5
      e = argmax(p + b);  x <- x + p_e (silu(m W_g,e) * (m W_u,e)) W_dn,e
    logits = rmsnorm(x_L, g_final) E^T     (tied)
    (rows before position 0 are zero; taps `cca_time0`, `cca_time1` = 2)

float32, `highest` matmul precision, no cache, no kernel, no batching: the
convolutions as explicit shifted sums over the whole sequence, [Q, T]
scores a head materialised for a block of queries, a Python loop over the
experts with every expert over every position. Computed in blocks of
positions so that it fits beside the program on the chip, the program's
bf16 weights upcast a layer at a time. Nothing is imported from the
program.

R1-R5 are READINGS of what the published config's keys do not settle
(the checkpoint library's `modeling_zaya.py` would), each one function:
`_value_shift`, `_convolutions`, `_qk_mean`, `_norm_temperature`, `_route`;
each an `assumed` entry of the configuration. `precision` other than
"float32" is the CONTROL's: every projection's, the second convolution's
and the attention's operands rounded (the router, the norms, the rotation
and the depthwise taps stay float32).
"""

from __future__ import annotations

from typing import Any, Dict

import jax

from chipbench.references.dense_decoder import _mm, _rmsnorm, _round_operand

POS_BLOCK = 1024    # positions a projection or an FFN holds at once
Q_BLOCK = 256       # queries whose scores over the whole sequence exist


def weights_from_program_tree(params: Any) -> Dict[str, Any]:
    """Name the leaves of the program's tree (no copies): one stack of
    layers; the fused projection's columns are [q~ | k~ | v1 | v2]."""
    layer = params["layers"]
    attn, router, moe = layer["attn"], layer["router"], layer["moe"]
    return {
        "embed": params["embed"], "final_norm": params["final_norm"]["scale"],
        "layers": {
            "attn_norm": layer["attn_norm"]["scale"],
            "mlp_norm": layer["mlp_norm"]["scale"],
            "qkv": attn["qkv_proj"]["kernel"], "o": attn["o_proj"]["kernel"],
            "a": attn["conv0"], "B": attn["conv1"],
            "log_tau": attn["log_tau"],
            "r_down": router["down"], "r_eda": router["eda"],
            "r_norm": router["norm"]["scale"], "r_fc1": router["fc1"],
            "r_fc2": router["fc2"], "r_out": router["out"],
            "r_bias": router["bias"],
            "gate_up": moe["experts_gate_up"], "down": moe["experts_down"],
        },
    }


def _by_blocks(fn, x, block: int = POS_BLOCK):
    """fn over blocks of the leading axis of x (an array or a tuple of
    arrays of one length), memory only."""
    import jax.numpy as jnp

    s = jax.tree.leaves(x)[0].shape[0]
    blk = min(block, s)
    pad = (-s) % blk
    xp = jax.tree.map(lambda a: jnp.pad(
        a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (-1, blk) + a.shape[1:]), x)
    return jax.tree.map(lambda o: o.reshape((-1,) + o.shape[2:])[:s],
                        jax.lax.map(fn, xp))


def _before(x, n: int = 1):
    """Row t of the result is row t - n of x; zeros before the sequence."""
    import jax.numpy as jnp

    return jnp.pad(x, ((n, 0),) + ((0, 0),) * (x.ndim - 1))[:x.shape[0]]


def _value_shift(v1, v2):
    """R1: kv head 0 sees the token, kv head 1 the token before it; with 2
    kv heads "half the value heads" is one head each. -> [S, 2, D]"""
    import jax.numpy as jnp

    return jnp.stack([v1, _before(v2)], axis=1)


def _convolutions(z, a, b_taps, heads: int, precision: str):
    """R2: z [S, C]; a [T0, C]: depthwise, causal, the LAST tap on the
    token itself; then b_taps [T1, heads, D, D]: inside each head the
    channels mix, heads do not; in that order, no bias. -> [S, heads, D]"""
    import jax.numpy as jnp

    t0, t1 = a.shape[0], b_taps.shape[0]
    u = sum(a[i] * _before(z, t0 - 1 - i) for i in range(t0))
    u = u.reshape(z.shape[0], heads, -1)
    return sum(jnp.stack(
        [_mm(_before(u, t1 - 1 - j)[:, h], b_taps[j, h], precision)
         for h in range(heads)], axis=1) for j in range(t1))


def _qk_mean(q_pre, k_pre):
    """R3: the grouped form's repeat and average, on the PRE-convolution
    rows: query head i belongs to kv head i // (Hq / Hkv)."""
    import jax.numpy as jnp

    s, hq, d = q_pre.shape
    rep = hq // k_pre.shape[1]
    return ((q_pre + jnp.repeat(k_pre, rep, axis=1)) / 2,
            (q_pre.reshape(s, -1, rep, d).mean(2) + k_pre) / 2)


def _norm_temperature(q, k, log_tau):
    """R4: before the rotation; tau is a kv head's and carries every fixed
    factor the published code may have."""
    import jax.numpy as jnp

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))

    return unit(q), unit(k) * jnp.exp(log_tau)[:, None]


def _rotate_part(x, positions, theta: float, rotary_dim: int):
    """x [S, n, D]: the first `rotary_dim` dims of each head turn (dims i
    and i + rotary_dim / 2 together, angle pos * theta^(-2i / rotary_dim)),
    the rest pass through."""
    import jax.numpy as jnp

    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                         / rotary_dim)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary_dim:]], axis=-1)


def _attention(q, k, v, precision: str):
    """q [S, Hq, D], k, v [S, Hkv, D] -> [S, Hq D]: causal softmax of q
    k^T as it is (no scale), [Q_BLOCK, S] scores a head at a time."""
    import jax.numpy as jnp

    s, hq, d = q.shape
    rep = hq // k.shape[1]
    k = _round_operand(jnp.repeat(k, rep, axis=1), precision, -1)
    v = _round_operand(jnp.repeat(v, rep, axis=1), precision, 0)
    pos = jnp.arange(s)

    def block(blk):
        qb, pb = blk
        scores = jnp.einsum("qhd,khd->hqk", _round_operand(qb, precision, -1),
                            k)
        mask = pos[None, :] <= pb[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _round_operand(p, precision, -1), v)

    qb = min(Q_BLOCK, s)
    pad = (-s) % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, hq, d)
    pp = jnp.pad(pos, (0, pad)).reshape(-1, qb)
    return jax.lax.map(block, (qp, pp)).reshape(-1, hq * d)[:s]


def _cca(h, w, cfg: Dict[str, Any], precision: str):
    """h = rmsnorm(x) [S, hidden] -> o W_o [S, hidden]."""
    import jax.numpy as jnp

    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    f32 = jnp.float32
    s = h.shape[0]
    qkv = _by_blocks(lambda b: _mm(b, w["qkv"], precision), h)
    chan = (hq + hkv) * d
    z = qkv[:, :chan]
    v = _value_shift(qkv[:, chan:chan + d], qkv[:, chan + d:])
    c = _convolutions(z, w["a"].astype(f32), w["B"].astype(f32), hq + hkv,
                      precision)
    pre = z.reshape(s, hq + hkv, d)
    mean_q, mean_k = _qk_mean(pre[:, :hq], pre[:, hq:])
    q, k = _norm_temperature(c[:, :hq] + mean_q, c[:, hq:] + mean_k,
                             w["log_tau"].astype(f32))
    rd = int(d * cfg["partial_rotary_factor"])
    pos = jnp.arange(s)
    theta = float(cfg["rope_theta"])
    o = _attention(_rotate_part(q, pos, theta, rd),
                   _rotate_part(k, pos, theta, rd), v, precision)
    return _by_blocks(lambda b: _mm(b, w["o"], precision), o)


def _route(m, r_prev, w, cfg: Dict[str, Any]):
    """R5, float32 whatever the control: m = rmsnorm(x) [S, hidden], r_prev
    [S, router_hidden_size] -> (p [S, E], the chosen expert [S], r_l). g_l
    is a vector; two hidden gelu layers behind one norm; the bias enters the
    choice only."""
    import jax.numpy as jnp

    f32 = jnp.float32
    r = jnp.matmul(m, w["r_down"].astype(f32)) + w["r_eda"].astype(f32) * r_prev
    y = _rmsnorm(r, w["r_norm"].astype(f32), cfg["rms_norm_eps"])
    y = jax.nn.gelu(jnp.matmul(y, w["r_fc1"].astype(f32)), approximate=False)
    y = jax.nn.gelu(jnp.matmul(y, w["r_fc2"].astype(f32)), approximate=False)
    p = jax.nn.softmax(jnp.matmul(y, w["r_out"].astype(f32)), axis=-1)
    return p, jnp.argmax(p + w["r_bias"].astype(f32), axis=-1), r


def _expert(m, gate_up, down, precision: str):
    f = down.shape[0]
    gu = _mm(m, gate_up, precision)
    return _mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], down, precision)


def _layer(carry, w, cfg: Dict[str, Any], precision: str):
    import jax.numpy as jnp

    x, r = carry
    eps, f32 = cfg["rms_norm_eps"], jnp.float32
    x = x + _cca(_rmsnorm(x, w["attn_norm"].astype(f32), eps), w, cfg,
                 precision)

    def experts(blk):
        xb, rb = blk
        m = _rmsnorm(xb, w["mlp_norm"].astype(f32), eps)
        p, chosen, r_l = _route(m, rb, w, cfg)
        y = jnp.zeros_like(m)
        for e in range(cfg["num_experts"]):      # every expert, every row
            p_e = jnp.where(chosen == e, p[:, e], 0.0)[:, None]
            y = y + p_e * _expert(m, w["gate_up"][e], w["down"][e],
                                  precision)
        return xb + y, r_l, chosen

    x, r, chosen = _by_blocks(experts, (x, r))
    return (x, r), chosen


def hidden(weights, ids, cfg: Dict[str, Any], precision: str = "float32",
           want_selection: bool = False):
    """ids [S] -> (the final norm's output [S, H] float32, ready for
    `head`; the chosen experts [L, S, 1, E] bool, or None). Under
    `jax.default_matmul_precision("highest")`."""
    import jax.numpy as jnp

    x = weights["embed"][ids].astype(jnp.float32)
    r0 = jnp.zeros((x.shape[0], cfg["router_hidden_size"]), jnp.float32)
    (x, _), chosen = jax.lax.scan(
        lambda c, w: _layer(c, w, cfg, precision), (x, r0), weights["layers"])
    x = _rmsnorm(x, weights["final_norm"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
    if not want_selection:
        return x, None
    return x, (chosen[..., None] == jnp.arange(cfg["num_experts"]))[
        :, :, None, :]


def head(weights, h, precision: str = "float32"):
    """h [N, H] (of `hidden`) -> logits [N, V] float32: the tied
    embedding's transpose."""
    return _mm(h, weights["embed"].T, precision)


def _forward_one(weights, ids, cfg, precision):
    h, _ = hidden(weights, ids, cfg, precision)
    return _by_blocks(lambda b: head(weights, b, precision), h, 256)


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


def next_token_nll(weights, ids, cfg: Dict[str, Any],
                   precision: str = "float32"):
    """Per-token negative log-likelihood of ids[:, 1:] given the prefix:
    [B, S-1] float32, the head a block of positions at a time."""
    import jax.numpy as jnp

    def one(row):
        h, _ = hidden(weights, row, cfg, precision)

        def nll(blk):
            hb, tb = blk
            logp = jax.nn.log_softmax(head(weights, hb, precision), axis=-1)
            return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

        return _by_blocks(nll, (h[:-1], row[1:]), 256)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, ids)
