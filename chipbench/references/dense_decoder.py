"""Plain reference for a dense pre-norm decoder (Llama / Mistral family),
written from the published description, in straightforward jax.numpy:

    x   = E[ids]
    per layer:
      a   = rmsnorm(x, g_attn)
      q,k,v = a Wq, a Wk, a Wv        (heads of size d; nkv <= nq: GQA)
      q,k = rope(q), rope(k)          (half-split "rotate_half" pairing,
                                       angle = pos * theta^(-2i/d))
      o   = softmax(q k^T / sqrt(d) + causal mask) v
      x   = x + o Wo
      m   = rmsnorm(x, g_mlp)
      x   = x + (silu(m Wgate) * (m Wup)) Wdown
    logits = rmsnorm(x, g_final) Whead
    rmsnorm(x, g) = x / sqrt(mean(x^2) + eps) * g

float32 throughout, `jax.default_matmul_precision("highest")`, no kernels,
no cache, no batching tricks. Nothing is imported from the program. The
weights are the benchmark's own (chipbench/weights.py), handed over in the
program's stacked layout and upcast one layer at a time inside the scan
(the whole model does not fit a second time in float32):

    embed [V, H], lm_head [H, V], final_norm [H]
    qkv [L, H, (nq + 2 nkv) d]  columns [q | k | v]   (fused by the program)
    o [L, nq d, H], gate_up [L, H, 2F] columns [gate | up], down [L, F, H]
    attn_norm [L, H], mlp_norm [L, H]

`precision` other than "float32" is for the CONTROL (see tests/ and
PERF.md): the same mathematics with every matmul operand rounded to
bfloat16, to float8_e4m3 (per-tensor scale) or to int8 (per-row scale),
in the backward as well: the gradient matmuls of a projection round their
operands (the incoming gradient too) the same way. The control has to come
out as not correct.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax

PRECISIONS = ("float32", "bfloat16", "fp8", "int8")
Q_BLOCK = 512  # queries per block of the plain attention (memory only)


def weights_from_program_tree(params: Any) -> Dict[str, Any]:
    """Name the leaves of the program's LlamaModel tree (no copies)."""
    layer = params["layers"]["layer"]
    return {
        "embed": params["embed"],
        "lm_head": params["lm_head"]["kernel"],
        "final_norm": params["final_norm"]["scale"],
        "layers": {
            "qkv": layer["attn"]["qkv_proj"]["kernel"],
            "o": layer["attn"]["o_proj"]["kernel"],
            "gate_up": layer["mlp"]["gate_up_proj"]["kernel"],
            "down": layer["mlp"]["down_proj"]["kernel"],
            "attn_norm": layer["attn_norm"]["scale"],
            "mlp_norm": layer["mlp_norm"]["scale"],
        },
    }


def _rounded(x, precision: str, axis: int):
    """x (float32) rounded to the control's precision, as float32. `axis`
    is the contraction axis (int8 scales are per row of it)."""
    import jax.numpy as jnp

    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    if precision == "int8":
        scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                            1e-30) / 127.0
        return jnp.clip(jnp.rint(x / scale), -127, 127) * scale
    raise ValueError(f"precision {precision!r} not in {PRECISIONS}")


def _round_operand(x, precision: str, axis: int):
    """A matmul operand at the stated precision, in float32. Its gradient
    passes straight through the rounding (attention's backward stays plain
    in the control; the projections' is rounded, see `_mm`)."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    if precision == "float32":
        return x
    return x + jax.lax.stop_gradient(_rounded(x, precision, axis) - x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm_control(a, b, precision: str):
    """a [M, K] @ b [K, N], operands rounded; so are the backward's."""
    import jax.numpy as jnp

    return jnp.matmul(_rounded(a, precision, -1), _rounded(b, precision, 0))


def _mm_control_fwd(a, b, precision):
    return _mm_control(a, b, precision), (a, b)


def _mm_control_bwd(precision, res, g):
    import jax.numpy as jnp

    a, b = res
    # da = g b^T contracts over N; db = a^T g contracts over M
    da = jnp.matmul(_rounded(g, precision, -1), _rounded(b, precision, 1).T)
    db = jnp.matmul(_rounded(a, precision, 0).T, _rounded(g, precision, 0))
    return da, db


_mm_control.defvjp(_mm_control_fwd, _mm_control_bwd)


def _mm(a, b, precision: str):
    """a [S, K] @ b [K, N] at the stated operand precision."""
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision == "float32":
        return jnp.matmul(a, b)
    return _mm_control(a, b, precision)


def _rmsnorm(x, g, eps: float):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, positions, theta: float):
    """x [S, n, d], positions [S]."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]   # [S, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, precision: str):
    """q [S, nq, d], k, v [S, nkv, d]; causal; plain softmax, by blocks of
    queries so that the score matrix stays small."""
    import jax
    import jax.numpy as jnp

    s, nq, d = q.shape
    nkv = k.shape[1]
    k = jnp.repeat(k, nq // nkv, axis=1)
    v = jnp.repeat(v, nq // nkv, axis=1)
    kq = _round_operand(k, precision, -1)
    blk = min(Q_BLOCK, s)
    pad = (-s) % blk
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, blk, nq, d)
    starts = jnp.arange(qp.shape[0]) * blk

    def block(args):
        qb, start = args
        scores = jnp.einsum("qhd,khd->hqk", _round_operand(qb, precision, -1),
                            kq) / jnp.sqrt(jnp.float32(d))
        qpos = start + jnp.arange(blk)
        mask = jnp.arange(s)[None, :] <= qpos[:, None]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _round_operand(p, precision, -1),
                          _round_operand(v, precision, 0))

    out = jax.lax.map(block, (qp, starts))
    return out.reshape(-1, nq, d)[:s]


def _forward_one(weights, ids, cfg: Dict[str, Any], precision: str):
    """ids [S] -> logits [S, V] float32."""
    import jax
    import jax.numpy as jnp

    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // nq
    f = cfg["intermediate_size"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = ids.shape[0]
    pos = jnp.arange(s)
    x = weights["embed"][ids].astype(jnp.float32)

    def layer(x, w):
        a = _rmsnorm(x, w["attn_norm"].astype(jnp.float32), eps)
        qkv = _mm(a, w["qkv"], precision)
        q = qkv[:, : nq * d].reshape(s, nq, d)
        k = qkv[:, nq * d: (nq + nkv) * d].reshape(s, nkv, d)
        v = qkv[:, (nq + nkv) * d:].reshape(s, nkv, d)
        o = _attention(_rope(q, pos, theta), _rope(k, pos, theta), v,
                       precision)
        x = x + _mm(o.reshape(s, nq * d), w["o"], precision)
        m = _rmsnorm(x, w["mlp_norm"].astype(jnp.float32), eps)
        gu = _mm(m, w["gate_up"], precision)
        h = jax.nn.silu(gu[:, :f]) * gu[:, f:]
        return x + _mm(h, w["down"], precision), None

    # under jax.grad a layer is recomputed in the backward (the same
    # mathematics): one layer's activations at a time, not all of them
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, weights["layers"])
    x = _rmsnorm(x, weights["final_norm"].astype(jnp.float32), eps)
    return _mm(x, weights["lm_head"], precision)


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
    those positions only (the full [B, S, V] of long sequences would not
    fit)."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda a: _forward_one(weights, a[0], cfg, precision)[a[1]],
            (ids, rows))


def next_token_nll(weights, ids, cfg: Dict[str, Any],
                   precision: str = "float32"):
    """Per-token negative log-likelihood of ids[:, 1:] given the prefix:
    [B, S-1] float32 (the causal-LM training loss before its mean)."""
    import jax
    import jax.numpy as jnp

    def one(row):
        logits = _forward_one(weights, row, cfg, precision)[:-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, row[1:, None], axis=-1)[:, 0]

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, ids)


def loss_and_grads(weights, ids, cfg: Dict[str, Any],
                   precision: str = "float32"):
    """((loss, nll), grads): the causal-LM training loss of ids [B, S] (the
    mean of `next_token_nll`), those per-token losses, and the loss's
    gradient with respect to every weight, by `jax.grad` of the plain
    forward. The gradient has the weights' tree and their types (a float32
    cotangent is rounded once where it meets a bfloat16 leaf)."""

    def loss(w):
        nll = next_token_nll(w, ids, cfg, precision)
        return nll.mean(), nll

    (value, nll), grads = jax.value_and_grad(loss, has_aux=True)(weights)
    return (value, nll), grads
