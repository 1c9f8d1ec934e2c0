"""Linear attention with a per-head decay ("lightning attention").

A head keeps a matrix state S [D, D] in float32, and for a token t

    S_t = lambda * S_{t-1} + k_t^T v_t          o_t = scale * q_t S_t

with lambda = exp(log_decay) in (0, 1) a constant of the head. Two entry
points, each ONE jitted wrapper so that a trace and a compiled program
name it (PERF.md section 3, "names in a trace"):

- `lightning_prefill` (`_lightning_prefill`): one row of S tokens in
  chunks of C. Inside a chunk the outputs are two matmuls ((Q K^T) masked
  by the decay matrix, times V) plus the carried state's share (Q decayed,
  times S); between chunks the state moves by one matmul (K^T decayed,
  times V). It takes the state the row STARTS from and returns the state
  it ends in, so a prompt prefilled in passes resumes where it stopped.
  PADDING-PROOF: the real tokens are a prefix of the row (`length`); a
  position past it neither decays the state nor adds to it.
- `lightning_update` (`_lightning_update`): one token for the decode slot
  set, in place in the state pool [layers, slots, H, D, D]. It walks the
  LIVE slots only (a loop whose trip count is their number): a slot that
  is not live is neither read nor written and keeps its state bit for
  bit, and its output is 0.

Both are plain XLA (matmuls and a loop), on every backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 128


def lightning_prefill(q, k, v, log_decay, s0, length, *, scale: float,
                      chunk: int = CHUNK):
    """q, k, v [S, H, D]; log_decay [H] float32 (<= 0); s0 [H, D, D]
    float32, the state before the row's first token; length: how many of
    the S tokens are real (a prefix). -> (o [S, H, D] in q's type, the
    state after the last REAL token [H, D, D] float32)."""
    s = q.shape[0]
    chunk = min(chunk, s)
    pad = (-s) % chunk      # a last chunk's padding is past `length`
    if pad:
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
    out, state = _lightning_prefill(
        q, k, v, log_decay.astype(jnp.float32), s0,
        jnp.minimum(jnp.asarray(length, jnp.int32), s), scale=float(scale),
        chunk=chunk)
    return out[:s], state


@functools.partial(jax.jit, static_argnames=("scale", "chunk"))
def _lightning_prefill(q, k, v, log_decay, s0, length, *, scale: float,
                       chunk: int):
    f32 = jnp.float32
    s, h, d = q.shape
    nc = s // chunk

    def chunks(a):                 # [S, H, D] -> [NC, H, C, D]
        return a.reshape(nc, chunk, h, d).transpose(0, 2, 1, 3)

    i = jnp.arange(chunk)
    causal = i[:, None] >= i[None, :]

    def step(state, xs):
        qc, kc, vc, c = xs
        n_c = jnp.clip(length - c * chunk, 0, chunk)
        real = i < n_c
        # decay steps taken up to and including token i of the chunk
        b = log_decay[:, None] * jnp.minimum(i + 1, n_c).astype(f32)  # [H,C]
        expo = b[:, :, None] - b[:, None, :]
        mask = causal[None] & real[None, None, :]
        dmat = jnp.where(mask, jnp.exp(jnp.where(mask, expo, 0.0)), 0.0)
        scores = jnp.einsum("hid,hjd->hij", qc, kc,
                            preferred_element_type=f32)
        intra = jnp.einsum("hij,hjd->hid", (scores * dmat).astype(vc.dtype),
                           vc, preferred_element_type=f32)
        inter = jnp.einsum("hid,hde->hie",
                           qc.astype(f32) * jnp.exp(b)[..., None], state,
                           preferred_element_type=f32)
        tail = jnp.where(real[None], jnp.exp(b[:, -1:] - b), 0.0)
        new = jnp.einsum("hjd,hje->hde", kc.astype(f32) * tail[..., None],
                         vc.astype(f32), preferred_element_type=f32)
        state = jnp.exp(b[:, -1])[:, None, None] * state + new
        return state, ((intra + inter) * scale).astype(q.dtype)

    state, out = jax.lax.scan(
        step, s0.astype(f32), (chunks(q), chunks(k), chunks(v),
                               jnp.arange(nc)))
    return out.transpose(0, 2, 1, 3).reshape(s, h, d), state


def lightning_update(q, k, v, log_decay, pool, layer, live, *, scale: float):
    """One token for the slot set. q, k, v [B, H, D] (row i is slot i);
    log_decay [H]; pool [layers, B, H, D, D] float32; `layer` this layer's
    index in it; live [B] bool -> (o [B, H, D] in q's type, 0 for a slot
    that is not live; the pool with the LIVE slots of `layer` advanced by
    one token)."""
    return _lightning_update(q, k, v, log_decay.astype(jnp.float32), pool,
                             jnp.asarray(layer, jnp.int32), live,
                             scale=float(scale))


@functools.partial(jax.jit, static_argnames=("scale",))
def _lightning_update(q, k, v, log_decay, pool, layer, live, *,
                      scale: float):
    f32 = jnp.float32
    b, h, d = q.shape
    n_live = jnp.sum(live).astype(jnp.int32)
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    lam = jnp.exp(log_decay)[:, None, None]

    def one(i, carry):
        pool, out = carry
        slot = order[i]
        state = jax.lax.dynamic_slice(
            pool, (layer, slot, 0, 0, 0), (1, 1, h, d, d))[0, 0]
        qi, ki, vi = (jax.lax.dynamic_index_in_dim(a, slot, 0, False)
                      .astype(f32) for a in (q, k, v))
        state = lam * state + ki[:, :, None] * vi[:, None, :]
        o = jnp.sum(qi[:, :, None] * state, axis=1) * scale
        pool = jax.lax.dynamic_update_slice(
            pool, state[None, None], (layer, slot, 0, 0, 0))
        out = jax.lax.dynamic_update_index_in_dim(out, o, slot, 0)
        return pool, out

    pool, out = jax.lax.fori_loop(
        0, n_live, one, (pool, jnp.zeros((b, h, d), f32)))
    return out.astype(q.dtype), pool
