"""Plain reference for the Laguna family (poolside/Laguna-XS.2, `model_type`
`laguna`): sliding-window and full attention layers in one stack with a
head count, a rotation and a rotated share of the head A KIND, a per-head
output gate, a leading dense layer, then sigmoid-routed experts beside a
shared one. Written from the published config's keys in straightforward
jax.numpy; norms and the rounded matmul are the dense decoder's (imported:
one source). Nothing is imported from the program.

    n(x) = rmsnorm with a learned weight, eps 1e-6
    h <- h + Attn_l(n(h));  h <- h + FFN_l(n(h));  logits = W_head n(h_L)
    Attn_l, u = n(h), position t, kind = layer_types[l],
      H = num_attention_heads_per_layer[l] (48 full, 64 sliding):
      q = u W_q (H heads of 128), k = u W_k, v = u W_v (8 heads of 128: 6 or
      8 query heads a kv head), no bias; no norm over q or k (R3: `qk_norm`)
      rope_parameters[kind]: r = 128 x partial_rotary_factor dims rotated,
      the FIRST r of the head, dims i and i + r/2 turning together, the
      other 128 - r passed through with no factor:
        sliding_attention: r = 128, angles t f_i, f_i = theta^(-2i/r),
          theta 1e4
        full_attention: r = 64, theta 5e5, YaRN over the rotated dims:
          dim(x) = r ln(orig / (2 pi x)) / (2 ln theta); low =
          max(floor(dim(beta_fast)), 0), high = min(ceil(dim(beta_slow)),
          r - 1); ramp_i = clip((i - low) / (high - low), 0, 1); inv_freq_i
          = f_i / factor ramp_i + f_i (1 - ramp_i); cos and sin TIMES
          attention_factor (a score of two rotated halves carries its
          square)
      score(t, j) = q(t) . k(j) / sqrt(128), softmax in float32 over
        j <= t, and in a sliding layer also j > t - sliding_window
      gating (R1: `head_gate`): g = sigmoid(u W_g), W_g [hidden, H];
        o_h <- g_h o_h, one scalar a query head and token
      W_o [o_1 .. o_H]
    FFN_l, m = n(h), mlp_layer_types[l]:
      dense: W_down(silu(W_gate m) * W_up m), width intermediate_size
      sparse (R2: `route`): s = sigmoid(m W_r) over all 256 in float32; the
        8 largest of s + b (b a bias an expert, in the choice only); w_e =
        moe_routed_scaling_factor s_e / (sum of the chosen s + 1e-20);
        sum_e w_e E_e(m) + Shared(m), each a gated-silu FFN of width
        moe_intermediate_size / shared_expert_intermediate_size; weights on
        the output

R1, R2 and R3 are READINGS of a config that does not say (`assumed` in the
configuration; `modeling_laguna.py` would settle each): each is ONE function
here.

float32, `highest` matmul precision, dense scores of a block of queries
against the WHOLE sequence under the layer kind's mask, an expert at a
time, no cache, no kernel, no batching. Computed in blocks of positions so
that 12k tokens fit beside the program on the chip; the program's bf16
weights are read a layer at a time (a `lax.scan` over each run's stack).

`cfg["sliding_window"] = None` is the SECOND CONTROL: the same weights and
rotations with every layer's mask the full one. `precision` other than
"float32" is the first control's (the router, the gate's logits and the
rotation stay float32). Departures from the published code, each an
`assumed` entry of the configuration: the fused qkv and gate_up layouts of
the program's tree, split.
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


def runs_of(cfg: Dict[str, Any]) -> List[Tuple[Tuple[str, str], int]]:
    """[((attention kind, FFN kind), layers)]: the configuration's layers
    as runs of like layers, the stacks the program's tree holds them in."""
    n = cfg["num_hidden_layers"]
    out: List[List[Any]] = []
    for key in zip(cfg["layer_types"][:n], cfg["mlp_layer_types"][:n]):
        if out and out[-1][0] == key:
            out[-1][1] += 1
        else:
            out.append([key, 1])
    return [(k, c) for k, c in out]


def weights_from_program_tree(params: Any) -> Dict[str, Any]:
    """Name the leaves of the program's tree (no copies): a stack a run."""
    runs = []
    for name in sorted(k for k in params if k.startswith("run_")):
        run = params[name]
        w = {"qkv": run["attn"]["qkv_proj"]["kernel"],
             "o": run["attn"]["o_proj"]["kernel"],
             "gate": run["attn"]["gate_proj"],
             "attn_norm": run["attn_norm"]["scale"],
             "mlp_norm": run["mlp_norm"]["scale"]}
        if "mlp" in run:
            w.update(gate_up=run["mlp"]["gate_up_proj"]["kernel"],
                     down=run["mlp"]["down_proj"]["kernel"])
        else:
            moe = run["moe"]
            w.update(router=moe["router"], router_bias=moe["router_bias"],
                     gate_up=moe["experts_gate_up"],
                     down=moe["experts_down"],
                     shared_gate_up=moe["shared"]["gate_up_proj"]["kernel"],
                     shared_down=moe["shared"]["down_proj"]["kernel"])
        runs.append(w)
    return {"embed": params["embed"], "lm_head": params["lm_head"],
            "final_norm": params["final_norm"]["scale"], "runs": runs}


def inv_freq(cfg: Dict[str, Any], kind: str):
    """([r / 2] float32, the factor on cos and sin, r): `rope_parameters`
    of the layer kind; r the dims of a head that turn."""
    import jax.numpy as jnp

    rp = cfg["rope_parameters"][kind]
    r = int(cfg["head_dim"] * rp.get("partial_rotary_factor", 1))
    theta = float(rp["rope_theta"])
    i = jnp.arange(r // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / r)
    if rp["rope_type"] == "default":
        return f, 1.0, r
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    orig = rp["original_max_position_embeddings"]

    def dim(turns):
        return r * math.log(orig / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(rp["beta_fast"])), 0)
    high = min(math.ceil(dim(rp["beta_slow"])), r - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / rp["factor"] * ramp + f * (1.0 - ramp),
            float(rp["attention_factor"]), r)


def _rotate(x, positions, freq, factor: float, r: int):
    """x [S, n, d], positions [S]: the first r dims turn, dims i and
    i + r/2 together; the rest pass through."""
    import jax.numpy as jnp

    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def qk_norm(q, k, cfg: Dict[str, Any]):
    """R3: no key of the config names a norm over q and k: none."""
    return q, k


def head_gate(o, u, w_g, cfg: Dict[str, Any]):
    """R1: `gating`. o [S, H, d], u = n(h) [S, hidden], w_g [hidden, H] ->
    o_h sigmoid(u w_g)_h: one scalar a query head and token, its logits in
    float32 at every precision."""
    import jax.numpy as jnp

    if not cfg.get("gating", False):
        return o
    g = jax.nn.sigmoid(jnp.matmul(u, w_g.astype(jnp.float32)))
    return o * g[..., None]


def route(m, router, bias, cfg: Dict[str, Any]):
    """R2: DeepSeek-V3's router. m [S, H] -> (w [S, E] float32: a chosen
    expert's weight, 0 elsewhere; chosen [S, E] bool)."""
    import jax.numpy as jnp

    s = jax.nn.sigmoid(jnp.matmul(m, router.astype(jnp.float32)))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], idx].set(True)
    kept = jnp.where(chosen, s, 0.0)
    kept = kept / (kept.sum(-1, keepdims=True) + 1e-20)
    return kept * float(cfg["moe_routed_scaling_factor"]), chosen


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
    """a = n(h) [S, hidden] -> W_o [g_1 o_1 .. g_H o_H] [S, hidden]: every
    query against the whole sequence under the kind's mask. The layer's
    head count is read from its weights' shapes (`hidden` checks them
    against `num_attention_heads_per_layer`)."""
    import jax.numpy as jnp

    nkv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    nq = w["gate"].shape[-1]
    window = cfg.get("sliding_window") if kind == SLIDING else None
    s = a.shape[0]
    pos = jnp.arange(s)
    freq, factor, r = inv_freq(cfg, kind)
    w_q, w_k, w_v = (w["qkv"][:, :nq * d], w["qkv"][:, nq * d:(nq + nkv) * d],
                     w["qkv"][:, (nq + nkv) * d:])

    def keys_values(blk):
        ab, pb = blk
        k = _mm(ab, w_k, precision).reshape(-1, nkv, d)
        _, k = qk_norm(None, k, cfg)
        return (_rotate(k, pb, freq, factor, r),
                _mm(ab, w_v, precision).reshape(-1, nkv, d))

    ab, _ = _blocks(a, POS_BLOCK)
    pb, _ = _blocks(pos, POS_BLOCK)
    k, v = jax.lax.map(keys_values, (ab, pb))
    k = _round_operand(k.reshape(-1, nkv, d)[:s], precision, -1)
    v = _round_operand(v.reshape(-1, nkv, d)[:s], precision, 0)

    def queries(blk):
        ab, pb = blk
        q = _mm(ab, w_q, precision).reshape(-1, nq, d)
        q, _ = qk_norm(q, None, cfg)
        q = _rotate(q, pb, freq, factor, r)
        q = _round_operand(q, precision, -1).reshape(-1, nkv, nq // nkv, d)
        scores = jnp.einsum("qgrd,kgd->grqk", q, k) * d ** -0.5
        j = jnp.arange(s)[None, :]
        mask = j <= pb[:, None]
        if window is not None:
            mask = mask & (j > pb[:, None] - window)
        p = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf),
                           axis=-1)
        o = jnp.einsum("grqk,kgd->qgrd", _round_operand(p, precision, -1), v)
        o = head_gate(o.reshape(-1, nq, d), ab, w["gate"], cfg)
        return _mm(o.reshape(-1, nq * d), w["o"], precision)

    ab, _ = _blocks(a, Q_BLOCK)
    pb, _ = _blocks(pos, Q_BLOCK)
    return jax.lax.map(queries, (ab, pb)).reshape(-1, a.shape[1])[:s]


def _ffn(m, gate_up, down, precision: str):
    f = down.shape[0]
    gu = _mm(m, gate_up, precision)
    return _mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], down, precision)


def _expert_layer(m, w, cfg: Dict[str, Any], precision: str):
    """-> (the weighted sum of a token's chosen experts + the shared one,
    chosen [S, E])."""
    import jax.numpy as jnp

    weights, chosen = route(m, w["router"], w["router_bias"], cfg)

    def one(acc, ew):
        gate_up, down, w_e = ew
        y = _ffn(m, gate_up, down, precision)
        return acc + jnp.where(w_e[:, None] > 0, w_e[:, None] * y, 0.0), None

    acc, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(m),
                          (w["gate_up"], w["down"], weights.T))
    if cfg.get("shared_expert_intermediate_size", 0):
        acc = acc + _ffn(m, w["shared_gate_up"], w["shared_down"], precision)
    return acc, chosen


def _layer(x, w, cfg: Dict[str, Any], kind: str, ffn_kind: str,
           precision: str):
    import jax.numpy as jnp

    f32, eps = jnp.float32, cfg["rms_norm_eps"]
    a = _rmsnorm(x, w["attn_norm"].astype(f32), eps)
    x = x + _attention(a, w, cfg, kind, precision)

    def ffn(b):
        m = _rmsnorm(b, w["mlp_norm"].astype(f32), eps)
        if ffn_kind == "dense":
            return (_ffn(m, w["gate_up"], w["down"], precision),
                    jnp.zeros((b.shape[0], 0), bool))
        return _expert_layer(m, w, cfg, precision)

    xb, s = _blocks(x, POS_BLOCK)
    y, chosen = jax.lax.map(ffn, xb)
    return (x + y.reshape(-1, x.shape[1])[:s],
            chosen.reshape(xb.shape[0] * xb.shape[1], -1)[:s])


def hidden(weights, ids, cfg: Dict[str, Any], precision: str = "float32",
           want_selection: bool = False):
    """ids [S] -> (the final norm's output [S, H] float32, ready for
    `head`; the experts each SPARSE layer chose [L_sparse, S, 1, E] bool,
    or None). Under `jax.default_matmul_precision("highest")`."""
    import jax.numpy as jnp

    x = weights["embed"][ids].astype(jnp.float32)
    picked, at = [], 0
    for ((kind, ffn_kind), n), run in zip(runs_of(cfg), weights["runs"]):
        heads = set(cfg["num_attention_heads_per_layer"][at:at + n])
        if run["qkv"].shape[0] != n or heads != {run["gate"].shape[-1]} or (
                "router" in run) != (ffn_kind == "sparse"):
            raise ValueError(
                f"a run of {run['qkv'].shape[0]} layers of "
                f"{run['gate'].shape[-1]} heads where the configuration has "
                f"{n} {kind} layers of {sorted(heads)} with a {ffn_kind} FFN")
        at += n

        def body(x, w, kind=kind, ffn_kind=ffn_kind):
            return _layer(x, w, cfg, kind, ffn_kind, precision)

        x, chosen = jax.lax.scan(body, x, run)
        if ffn_kind == "sparse":
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


def next_token_nll(weights, ids, cfg: Dict[str, Any],
                   precision: str = "float32"):
    """ids [B, S] -> [B, S - 1] float32: -log p(ids[t + 1] | ids[..t])."""
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(forward(weights, ids, cfg, precision)[:, :-1])
    return -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
