"""Plain reference for a pre-norm decoder with a sparse-expert FFN (Mixtral
family), written from the published description (HF `MixtralSparseMoeBlock`)
in straightforward jax.numpy. Attention, norms and rope are the dense
decoder's (`dense_decoder.py`, imported: one source); the FFN of a layer is

    m   = rmsnorm(x, g_mlp)
    p   = softmax(m W_g)                 (float32, over all E experts)
    keep the k largest p_e, renormalise them to sum 1, 0 for the others
    x   = x + sum_e p_e * W2_e( silu(W1_e m) * (W3_e m) )

EVERY token is served by all k of its experts: no capacity, nothing is
dropped. Computed the plain way: a loop over the E experts, each applied to
every position, and a `where` on the top-k mask (dense over experts: E/k
times the needed work, and no routing machinery to get wrong).

float32 throughout, `jax.default_matmul_precision("highest")`, no kernels,
no cache, no batching tricks. Nothing is imported from the program. The
weights are the benchmark's own (chipbench/weights.py) in the program's
stacked layout, upcast one layer at a time inside the scan and, for the
experts, one EXPERT at a time inside the loop (one layer of float32
experts is 5.6 GB at Mixtral widths; three are 17 GB):

    embed, lm_head, final_norm, qkv, o, attn_norm, mlp_norm: as dense
    router [L, H, E]  (float32 in the program too)
    gate_up [L, E, H, 2F]   columns [W1 = gate | W3 = up]
    down [L, E, F, H]       W2

Departure from the published description: the fused `gate_up` layout, split
in halves. Nothing else.

`precision` other than "float32" is for the CONTROL (control.py): every
matmul operand of the attention, the experts and the head rounded as in
`dense_decoder.py`, the backward's too. The router's small matmul stays
in float32 in the control as well: the program keeps it there on purpose,
and a control that rounded it would differ from float32 mostly through
flipped expert choices and so make the limits looser than the precision
of the expert matmuls warrants.
"""

from __future__ import annotations

from typing import Any, Dict

import jax

from chipbench.references.dense_decoder import (_attention, _mm, _rmsnorm,
                                                _rope)


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
            "router": layer["moe"]["router"],
            "gate_up": layer["moe"]["experts_gate_up"],
            "down": layer["moe"]["experts_down"],
            "attn_norm": layer["attn_norm"]["scale"],
            "mlp_norm": layer["mlp_norm"]["scale"],
        },
    }


def _route(m, router, k: int):
    """m [S, H] -> (weights [S, E] float32: the renormalised probability of
    a kept expert, 0 elsewhere; the k kept expert ids [S, k], ascending)."""
    import jax.numpy as jnp

    p = jax.nn.softmax(jnp.matmul(m, router.astype(jnp.float32)), axis=-1)
    top, idx = jax.lax.top_k(p, k)
    kept = jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None], idx].set(
        top / top.sum(-1, keepdims=True))
    return kept, jnp.sort(idx, axis=-1)


def _experts(m, w, kept, f: int, precision: str):
    """sum_e kept[:, e] * W2_e(silu(W1_e m) * W3_e m), an expert at a
    time, every expert over every position."""
    import jax.numpy as jnp

    def one(acc, ew):
        gate_up, down, p_e = ew
        gu = _mm(m, gate_up, precision)
        y = _mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], down, precision)
        return acc + jnp.where(p_e[:, None] > 0, p_e[:, None] * y, 0.0), None

    acc, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(m),
                          (w["gate_up"], w["down"], kept.T))
    return acc


def _forward_one(weights, ids, cfg: Dict[str, Any], precision: str,
                 want_routing: bool = False):
    """ids [S] -> logits [S, V] float32 (or, for `routing`, the kept
    expert ids [L, S, k])."""
    import jax.numpy as jnp

    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // nq
    f, k = cfg["intermediate_size"], cfg["num_experts_per_tok"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = ids.shape[0]
    pos = jnp.arange(s)
    x = weights["embed"][ids].astype(jnp.float32)

    def layer(x, w):
        a = _rmsnorm(x, w["attn_norm"].astype(jnp.float32), eps)
        qkv = _mm(a, w["qkv"], precision)
        q = qkv[:, : nq * d].reshape(s, nq, d)
        kk = qkv[:, nq * d: (nq + nkv) * d].reshape(s, nkv, d)
        v = qkv[:, (nq + nkv) * d:].reshape(s, nkv, d)
        o = _attention(_rope(q, pos, theta), _rope(kk, pos, theta), v,
                       precision)
        x = x + _mm(o.reshape(s, nq * d), w["o"], precision)
        m = _rmsnorm(x, w["mlp_norm"].astype(jnp.float32), eps)
        kept, chosen = _route(m, w["router"], k)
        return x + _experts(m, w, kept, f, precision), chosen

    # under jax.grad a layer is recomputed in the backward (the same
    # mathematics): one layer's activations at a time, not all of them
    x, chosen = jax.lax.scan(jax.checkpoint(layer), x, weights["layers"])
    if want_routing:
        return chosen
    x = _rmsnorm(x, weights["final_norm"].astype(jnp.float32), eps)
    return _mm(x, weights["lm_head"], precision)


def forward(weights, ids, cfg: Dict[str, Any], precision: str = "float32"):
    """ids [B, S] int32 -> logits [B, S, V] float32; one sequence at a
    time."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda row: _forward_one(weights, row, cfg, precision), ids)


def routing(weights, ids, cfg: Dict[str, Any], precision: str = "float32"):
    """ids [B, S] -> [B, L, S, k] int32: the experts the reference keeps
    at every layer and position, ascending (for counts, and for the
    positions where a program's choice differs)."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda row: _forward_one(weights, row, cfg, precision, True),
            ids)


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
    [B, S-1] float32 (the causal-LM loss before its mean; the router's
    load-balancing term is the trainer's, not the model's)."""
    import jax.numpy as jnp

    def one(row):
        logits = _forward_one(weights, row, cfg, precision)[:-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, row[1:, None], axis=-1)[:, 0]

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, ids)


def loss_and_grads(weights, ids, cfg: Dict[str, Any],
                   precision: str = "float32"):
    """((loss, nll), grads): the mean of `next_token_nll`, those per-token
    losses, and the loss's gradient with respect to every weight, by
    `jax.grad` of the plain forward, in the weights' tree and types."""

    def loss(w):
        nll = next_token_nll(w, ids, cfg, precision)
        return nll.mean(), nll

    (value, nll), grads = jax.value_and_grad(loss, has_aux=True)(weights)
    return (value, nll), grads
