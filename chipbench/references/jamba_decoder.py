"""Plain reference for a Jamba-family hybrid decoder (Mamba-1 state-space
layers beside attention layers, a dense SwiGLU FFN in every layer), written
from the published description (HF `modeling_jamba.py`: `JambaMambaMixer`,
`JambaAttention`, `JambaMLP`) in straightforward jax.numpy:

    x = E[ids]
    layer i is an attention layer where i % period == offset, else Mamba:
      x = x + mixer(rmsnorm(x, g_in))
      x = x + (silu(m Wgate) * (m Wup)) Wdown,   m = rmsnorm(x, g_ff)
    logits = rmsnorm(x, g_final) E^T             (tie_word_embeddings)

    attention mixer: q, k, v = a Wq, a Wk, a Wv; NO rotation (Jamba has no
      positional encoding); o = softmax(q k^T / sqrt(d) + causal) v; o Wo
    Mamba mixer, u [S, h], d = expand * h, N = d_state, K = d_conv:
      x, z  = split(u W_in)
      x     = silu(b + sum_j w_j * x_{t-(K-1)+j})      causal, depthwise
      dt, B, C = split(x W_x);  each rmsnorm'ed (g_dt, g_B, g_C)
      delta = softplus(dt W_dt + b_dt)
      A     = -exp(A_log)
      h_t   = exp(delta_t (x) A) * h_{t-1} + (delta_t * x_t) (x) B_t, h_0=0
      y_t   = h_t . C_t + D * x_t
      out   = (y * silu(z)) W_out

The scan is TOKEN BY TOKEN (`lax.scan` over time, one step a token): no
chunks, no associative scan, no kernel. float32 throughout,
`jax.default_matmul_precision("highest")`, no cache, no batching tricks, no
padding (ids zero-padded at the END by the caller never reach an earlier
position: every mixer is causal). Nothing is imported from the program.
The weights are the benchmark's own (chipbench/weights.py and
weights_ssm.py) in the program's stacked layout, upcast one layer at a
time inside the scans so that the reference fits beside the engine:

    embed [V, H] (also the head), final_norm [H]
    per period p, runs "pre" [n_pre, ...] and "post" [n_post, ...]:
      in_proj [H, 2d] columns [x | z], conv_kernel [K, d], conv_bias [d],
      x_proj [d, R + 2N] columns [dt | B | C], dt_norm [R], b_norm [N],
      c_norm [N], dt_proj [R, d], dt_bias [d], A_log [N, d], D [d],
      out_proj [d, H], input_norm [H], mlp_norm [H], gate_up [H, 2F]
      columns [gate | up], down [F, H]
    per period, "attn" (no leading axis): qkv [H, (nq + 2 nkv) hd] columns
      [q | k | v], o [nq hd, H], norms, gate_up, down

Departures from the published description, none of the mathematics: the
program's layouts (channels last: conv_kernel [K, d] for torch's
[d, 1, K], A_log [N, d] for [d, N]; fused qkv and gate_up); `head_dim`
= hidden / heads (not in the published file).

`precision` other than "float32" is for the CONTROL (control.py): every
matmul operand rounded as in `dense_decoder.py`, the backward's too. The
scan, the conv, the norms and softplus stay float32 in the control: they
have no matmul.
"""

from __future__ import annotations

from typing import Any, Dict

import jax

from chipbench.references.dense_decoder import _attention, _mm, _rmsnorm


def weights_from_program_tree(params: Any) -> Dict[str, Any]:
    """Name the leaves of the program's JambaModel tree (no copies)."""
    def mlp(layer):
        return {"input_norm": layer["input_norm"]["scale"],
                "mlp_norm": layer["mlp_norm"]["scale"],
                "gate_up": layer["mlp"]["gate_up_proj"]["kernel"],
                "down": layer["mlp"]["down_proj"]["kernel"]}

    def mamba(layer):
        m = layer["mixer"]
        return {**mlp(layer),
                "in_proj": m["in_proj"]["kernel"],
                "conv_kernel": m["conv_kernel"], "conv_bias": m["conv_bias"],
                "x_proj": m["x_proj"]["kernel"],
                "dt_norm": m["dt_norm"]["scale"],
                "b_norm": m["b_norm"]["scale"],
                "c_norm": m["c_norm"]["scale"],
                "dt_proj": m["dt_proj"]["kernel"], "dt_bias": m["dt_bias"],
                "A_log": m["A_log"], "D": m["D"],
                "out_proj": m["out_proj"]["kernel"]}

    out = {"embed": params["embed"],
           "final_norm": params["final_norm"]["scale"], "periods": []}
    if "lm_head" in params:
        out["lm_head"] = params["lm_head"]["kernel"]
    p = 0
    while f"period_{p}" in params:
        period = params[f"period_{p}"]
        attn = period["attn"]
        out["periods"].append({
            "attn": {**mlp(attn),
                     "qkv": attn["attn"]["qkv_proj"]["kernel"],
                     "o": attn["attn"]["o_proj"]["kernel"]},
            **{run: mamba(period[run]) for run in ("pre", "post")
               if run in period}})
        p += 1
    return out


def _f32(tree):
    import jax.numpy as jnp

    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _ffn(x, w, f: int, eps: float, precision: str):
    m = _rmsnorm(x, w["mlp_norm"], eps)
    gu = _mm(m, w["gate_up"], precision)
    return x + _mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], w["down"], precision)


def _mamba_layer(x, w, cfg: Dict[str, Any], precision: str):
    """x [S, H] -> [S, H]; `w` one layer's weights."""
    import jax.numpy as jnp

    w = _f32(w)
    eps = cfg["rms_norm_eps"]
    n, k, r = (cfg["mamba_d_state"], cfg["mamba_d_conv"],
               cfg["mamba_dt_rank"])
    s = x.shape[0]
    d = w["D"].shape[0]
    xz = _mm(_rmsnorm(x, w["input_norm"], eps), w["in_proj"], precision)
    xs, z = xz[:, :d], xz[:, d:]
    xp = jnp.pad(xs, ((k - 1, 0), (0, 0)))
    xs = w["conv_bias"] + sum(xp[j:j + s] * w["conv_kernel"][j]
                              for j in range(k))
    xs = jax.nn.silu(xs)
    dbc = _mm(xs, w["x_proj"], precision)
    dt = _rmsnorm(dbc[:, :r], w["dt_norm"], eps)
    bm = _rmsnorm(dbc[:, r:r + n], w["b_norm"], eps)
    cm = _rmsnorm(dbc[:, r + n:], w["c_norm"], eps)
    delta = jax.nn.softplus(_mm(dt, w["dt_proj"], precision) + w["dt_bias"])
    a = -jnp.exp(w["A_log"])                                   # [N, d]

    def token(h, t):
        x_t, delta_t, b_t, c_t = t
        h = jnp.exp(delta_t[None] * a) * h \
            + (delta_t * x_t)[None] * b_t[:, None]
        return h, (h * c_t[:, None]).sum(0) + w["D"] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((n, d), jnp.float32),
                        (xs, delta, bm, cm))
    x = x + _mm(y * jax.nn.silu(z), w["out_proj"], precision)
    return _ffn(x, w, cfg["intermediate_size"], eps, precision)


def _attention_layer(x, w, cfg: Dict[str, Any], precision: str):
    w = _f32(w)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // nq
    eps = cfg["rms_norm_eps"]
    s = x.shape[0]
    qkv = _mm(_rmsnorm(x, w["input_norm"], eps), w["qkv"], precision)
    q = qkv[:, : nq * hd].reshape(s, nq, hd)
    k = qkv[:, nq * hd: (nq + nkv) * hd].reshape(s, nkv, hd)
    v = qkv[:, (nq + nkv) * hd:].reshape(s, nkv, hd)
    o = _attention(q, k, v, precision)            # no rotation
    x = x + _mm(o.reshape(s, nq * hd), w["o"], precision)
    return _ffn(x, w, cfg["intermediate_size"], eps, precision)


def _forward_one(weights, ids, cfg: Dict[str, Any], precision: str):
    """ids [S] -> logits [S, V] float32."""
    import jax.numpy as jnp

    x = weights["embed"][ids].astype(jnp.float32)

    def run(x, stacked):
        # a layer is recomputed in the backward (the same mathematics)
        x, _ = jax.lax.scan(jax.checkpoint(
            lambda x, w: (_mamba_layer(x, w, cfg, precision), None)),
            x, stacked)
        return x

    assert len(weights["periods"]) * cfg["attn_layer_period"] \
        == cfg["num_hidden_layers"]
    for period in weights["periods"]:
        if "pre" in period:
            x = run(x, period["pre"])
        x = _attention_layer(x, period["attn"], cfg, precision)
        if "post" in period:
            x = run(x, period["post"])
    x = _rmsnorm(x, weights["final_norm"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
    head = (weights["embed"].T if cfg.get("tie_word_embeddings", True)
            else weights["lm_head"])
    return _mm(x, head, precision)


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
            lambda a: _forward_one(weights, a[0], cfg, precision)[a[1]],
            (ids, rows))


def next_token_nll(weights, ids, cfg: Dict[str, Any],
                   precision: str = "float32"):
    """Per-token negative log-likelihood of ids[:, 1:] given the prefix:
    [B, S-1] float32."""
    import jax.numpy as jnp

    def one(row):
        logits = _forward_one(weights, row, cfg, precision)[:-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, row[1:, None], axis=-1)[:, 0]

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, ids)
