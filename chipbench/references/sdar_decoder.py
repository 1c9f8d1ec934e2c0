"""Plain reference for SDAR (JetLM/SDAR-30B-A3B-Chat, `model_type`
`sdar_moe`): the Qwen3-MoE layer under a BLOCK mask, and generation by
diffusion over blocks. Written from the published description and the
family's generation script (`block_diffusion_generate`) in straightforward
jax.numpy and numpy; norms, rope and the rounded matmul are the dense
decoder's, routing and the expert loop the expert decoder's (imported: one
source).

    x_n = rmsnorm(x, g_attn)
    q, k, v = W_q x_n, W_k x_n, W_v x_n          (no bias)
    q, k = rmsnorm over the head dim with a learned weight, then rope
    query i attends key j  iff  j // B <= i // B  (blocks from position 0)
    x = x + W_o attn;   x = x + MoE(rmsnorm(x, g_mlp))
    MoE: p = softmax(m W_r) in float32 over all E, the k largest
         renormalised to sum 1, y = sum p_e W2_e(silu(W1_e m) * W3_e m)
    logits = W_head rmsnorm(x_L)

Generation, for a prompt of n tokens and a block length B: the first
(n // B) * B tokens are settled; the next block holds the prompt's last
n % B tokens and [MASK] elsewhere. A denoising pass runs the WHOLE
sequence so far (settled tokens and the block as it stands); the logits at
a masked position predict that position's own token; greedy: x0 = argmax,
confidence its softmax probability in float32. Pass s fixes m_s masked
positions (`transfer_schedule`): `low_confidence_static` the m_s of highest
confidence, `low_confidence_dynamic` every masked position above the
threshold if there are at least m_s, else the m_s highest, `sequential`
the first m_s from the left. When none is masked the block is settled and
the next begins. Generation ends at the first stop token (what lies right
of it in its block is dropped) or at `max_tokens`.

float32, `highest` matmul precision, no cache (a full forward a pass), no
kernel. Nothing is imported from the program. Departures from the script:
which positions are masked is kept as flags, not read off the ids (a
prompt may hold the mask id); the fused qkv / gate_up layouts of the
program's tree, split. `precision` other than "float32" is the CONTROL's,
as in `moe_decoder.py` (the router stays float32).
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import numpy as np

from chipbench.references.dense_decoder import (Q_BLOCK, _mm, _rmsnorm,
                                                _rope, _round_operand)
from chipbench.references.moe_decoder import _experts, _route


def weights_from_program_tree(params: Any) -> Dict[str, Any]:
    """Name the leaves of the program's tree (no copies)."""
    layer = params["layers"]["layer"]
    return {
        "embed": params["embed"],
        "lm_head": params["lm_head"]["kernel"],
        "final_norm": params["final_norm"]["scale"],
        "layers": {
            "qkv": layer["attn"]["qkv_proj"]["kernel"],
            "o": layer["attn"]["o_proj"]["kernel"],
            "q_norm": layer["attn"]["q_norm"]["scale"],
            "k_norm": layer["attn"]["k_norm"]["scale"],
            "router": layer["moe"]["router"],
            "gate_up": layer["moe"]["experts_gate_up"],
            "down": layer["moe"]["experts_down"],
            "attn_norm": layer["attn_norm"]["scale"],
            "mlp_norm": layer["mlp_norm"]["scale"],
        },
    }


def _attention(q, k, v, block: int, precision: str):
    """q [S, nq, d], k, v [S, nkv, d]; query i sees key j iff j // block
    <= i // block; plain softmax, by blocks of queries."""
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

    def some(args):
        qb, start = args
        scores = jnp.einsum("qhd,khd->hqk", _round_operand(qb, precision, -1),
                            kq) / jnp.sqrt(jnp.float32(d))
        qpos = start + jnp.arange(blk)
        mask = (jnp.arange(s) // block)[None, :] <= (qpos // block)[:, None]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _round_operand(p, precision, -1),
                          _round_operand(v, precision, 0))

    return jax.lax.map(some, (qp, starts)).reshape(-1, nq, d)[:s]


def _forward_one(weights, ids, cfg: Dict[str, Any], precision: str,
                 want_routing: bool = False, rows=None):
    """ids [S] -> logits [S, V] float32 (`rows` [G]: at those positions
    only, [G, V]; or the kept expert ids [L, S, k])."""
    import jax.numpy as jnp

    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    f, k = cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    block = cfg["block_length"]
    s = ids.shape[0]
    pos = jnp.arange(s)
    x = weights["embed"][ids].astype(jnp.float32)

    def layer(x, w):
        a = _rmsnorm(x, w["attn_norm"].astype(jnp.float32), eps)
        qkv = _mm(a, w["qkv"], precision)
        q = qkv[:, : nq * d].reshape(s, nq, d)
        kk = qkv[:, nq * d: (nq + nkv) * d].reshape(s, nkv, d)
        v = qkv[:, (nq + nkv) * d:].reshape(s, nkv, d)
        q = _rmsnorm(q, w["q_norm"].astype(jnp.float32), eps)
        kk = _rmsnorm(kk, w["k_norm"].astype(jnp.float32), eps)
        o = _attention(_rope(q, pos, theta), _rope(kk, pos, theta), v,
                       block, precision)
        x = x + _mm(o.reshape(s, nq * d), w["o"], precision)
        m = _rmsnorm(x, w["mlp_norm"].astype(jnp.float32), eps)
        kept, chosen = _route(m, w["router"], k)
        return x + _experts(m, w, kept, f, precision), chosen

    x, chosen = jax.lax.scan(jax.checkpoint(layer), x, weights["layers"])
    if want_routing:
        return chosen
    if rows is not None:
        x = x[rows]
    x = _rmsnorm(x, weights["final_norm"].astype(jnp.float32), eps)
    return _mm(x, weights["lm_head"], precision)


def forward(weights, ids, cfg: Dict[str, Any], precision: str = "float32"):
    """ids [B, S] int32 -> logits [B, S, V] float32, one sequence at a
    time. Padding belongs behind a WHOLE last block: inside a block every
    position sees every other."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda row: _forward_one(weights, row, cfg, precision), ids)


def forward_rows(weights, ids, rows, cfg: Dict[str, Any],
                 precision: str = "float32", batch: int = 1):
    """ids [B, S], rows [B, G] positions -> logits [B, G, V] float32 at
    those positions only (the head is computed there only). `batch`
    sequences go through together (the same arithmetic a sequence, the
    weights read once for all of them): a forward of a short sequence is
    the time to read 8.7 GB of experts, whatever its length."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda a: _forward_one(weights, a[0], cfg, precision,
                                   rows=a[1]),
            (ids, rows), batch_size=batch)


def routing(weights, ids, cfg: Dict[str, Any], precision: str = "float32"):
    """ids [B, S] -> [B, L, S, k] int32: the experts the reference keeps,
    ascending."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda row: _forward_one(weights, row, cfg, precision, True),
            ids)


# --------------------------------------------------------------- generation
def transfer_schedule(block: int, steps: int) -> List[int]:
    """The script's `get_num_transfer_tokens`: block // steps masked
    positions a pass, the first block % steps passes one more."""
    return [block // steps + (s < block % steps) for s in range(steps)]


def choose(conf: Sequence[float], masked: Sequence[bool], n_fix: int,
           cfg: Dict[str, Any]) -> List[int]:
    """The positions of a block that a pass fixes, given each position's
    confidence and which are still masked."""
    open_ = [i for i, m in enumerate(masked) if m]
    if cfg["remasking"] == "sequential":
        return open_[:n_fix]
    best = sorted(open_, key=lambda i: (-conf[i], i))[:n_fix]
    if cfg["remasking"] == "low_confidence_static":
        return best
    if cfg["remasking"] != "low_confidence_dynamic":
        raise ValueError(f"remasking {cfg['remasking']!r}")
    sure = [i for i in open_ if conf[i] > cfg["confidence_threshold"]]
    return sure if len(sure) >= n_fix else best


def confidence(logits: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """logits [B, V] -> (argmax [B], its softmax probability [B]), in
    float32 as the script's."""
    logits = logits.astype(np.float32)
    x0 = logits.argmax(-1)
    z = logits - logits.max(-1, keepdims=True)
    return x0, (1.0 / np.exp(z).sum(-1)).astype(np.float32)


def open_block(prompt: Sequence[int], cfg: Dict[str, Any]):
    """(settled tokens, the first block's ids, its masked flags) of a
    prompt: whole blocks are settled, the ragged tail opens the block."""
    b = cfg["block_length"]
    n = len(prompt) // b * b
    tail = list(prompt[n:])
    return (list(prompt[:n]), tail + [cfg["mask_token_id"]] * (b - len(tail)),
            [False] * len(tail) + [True] * (b - len(tail)))


def padded(seq: Sequence[int], multiple: int = 64):
    """[1, S'] ids, zero-padded behind the (whole) last block to a
    multiple of `multiple` (itself a multiple of the block length): later
    blocks are seen by nothing before them, and shapes stay few."""
    import jax.numpy as jnp

    n = -(-len(seq) // multiple) * multiple
    return jnp.asarray([list(seq) + [0] * (n - len(seq))], jnp.int32)


def generate(weights, prompt: Sequence[int], cfg: Dict[str, Any],
             max_tokens: int, stop_ids: Sequence[int] = (),
             precision: str = "float32", fwd=None
             ) -> Tuple[List[int], List[int]]:
    """The script's loop, greedy, one sequence, a full forward a pass ->
    (the tokens, the pass that fixed each). `fwd(ids [1, S]) -> [1, S, V]`:
    a jitted `forward`, for a caller that keeps one."""
    if fwd is None:
        fwd = jax.jit(lambda ids: forward(weights, ids, cfg, precision))
    b, steps = cfg["block_length"], cfg["denoising_steps"]
    schedule = transfer_schedule(b, steps)
    settled, block, masked = open_block(prompt, cfg)
    out: List[int] = []
    fixed_pass: List[int] = []
    while True:
        fresh = list(masked)
        at = [-1] * b
        for step in range(steps):
            if not any(masked):
                break
            seq = settled + block
            logits = np.asarray(fwd(padded(seq))[0][len(settled):len(seq)])
            x0, conf = confidence(logits)
            for i in choose(conf.tolist(), masked, schedule[step], cfg):
                block[i], masked[i], at[i] = int(x0[i]), False, step
        for i in range(b):
            if not fresh[i]:
                continue
            out.append(block[i])
            fixed_pass.append(at[i])
            if block[i] in stop_ids or len(out) >= max_tokens:
                return out, fixed_pass
        settled += block
        block, masked = [cfg["mask_token_id"]] * b, [True] * b
