"""The gated delta rule (Yang, Kautz, Hatamizadeh: "Gated Delta Networks",
ICLR 2025) behind a short causal convolution, as a linear-attention layer
keeps it (models/gigachat.py).

A value head keeps a matrix state S [D, D] (key dim x value dim) in
float32. For a token t, with alpha_t = exp(g_t) in (0, 1] and beta_t in
[0, 1] the head's own gates and `k_t` of unit length:

    S' = alpha_t S_{t-1}
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T        o_t = S_t^T q_t

It is NOT a decayed sum (ops/lightning_attention.py): before a key's new
value is written, what the state already holds for that key is taken out.
q, k and v come from ONE projection through a depthwise causal convolution
of K taps and a silu: `u_t = silu(sum_i c_i x_{t-K+1+i})`, zeros before a
sequence's start, so beside S a sequence keeps the conv's last K - 1 real
inputs (the CONV TAIL). q and k are divided by their length a head (eps
1e-6), q also by sqrt(D); key head j serves value heads `rep j .. rep j +
rep - 1`.

Two entry points that are the same mathematics, each ONE jitted wrapper so
that a trace and a compiled program name it:

- `gdn_prefill` (`_gdn_prefill`): one row of S tokens in chunks of C (the
  paper's WY / UT transform). Inside a chunk, with `G` the running sum of g
  and `L[i, j] = beta_i (k_i . k_j) exp(G_i - G_j)` for j < i, the tokens'
  corrected values are `(I + L)^-1` times what they would be alone: a
  unit-lower-triangular solve a chunk and head (`_unit_lower_inverse`:
  forward substitution in blocks of 16, merged by matmuls), then products;
  between chunks the state moves by two products. It takes the state and
  the conv tail the row STARTS from and returns those it ends in, so a
  prompt prefilled in passes resumes where it stopped. PADDING-PROOF: the
  real tokens are a prefix (`length`); a position past it neither decays,
  subtracts nor adds, and is no part of the tail. Plain XLA on every
  backend; the products take q's type as operands (bf16 in serving) and
  accumulate in float32, the solve is float32 at `highest`.
- `gdn_update` (`_gdn_update`): one token for the decode slot set, in place
  in the pools `[layers, slots, H, D, D]` float32 and `[layers, K - 1,
  slots, channels]` (the slots, not the 3 taps, beside the channels: a
  second-minor axis of 3 is padded to a tile, and XLA then copies the pool
  into a layout of its own at every program's start and end). On a TPU backend a Pallas kernel that brings in and takes
  back out the LIVE slots' states only (4.19 MB a row and layer at 64 heads
  of 128 x 128: the step is bound by those bytes); anywhere else plain
  jax.numpy. A slot that is not live keeps state and tail bit for bit and
  its output is 0. Every product of the update is elementwise float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .selective_scan import live_slots

CHUNK = 64
_SOLVE_BLOCK = 16
_HIGHEST = jax.lax.Precision.HIGHEST
# the update kernel holds a slot's whole state twice over (in and out),
# each double-buffered: 16.8 MB at 64 heads of 128 x 128
_UPDATE_VMEM_BYTES = 48 * 1024 * 1024


def _impl() -> str:
    """"pallas" | "jnp"; a test passes "pallas_interpret" to run the
    kernel off the TPU."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def l2norm(x, eps: float = 1e-6):
    """x / |x| over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _conv(window, conv_w, s: int):
    """window [s + K - 1, C] (tail, then the new inputs), conv_w [K, C] ->
    silu of the causal depthwise convolution at the s new positions,
    float32."""
    f32 = jnp.float32
    u = sum(window[i:i + s].astype(f32) * conv_w[i].astype(f32)
            for i in range(conv_w.shape[0]))
    return jax.nn.silu(u)


def _heads(u, n_k: int, n_v: int, dtype):
    """u [..., (2 n_k + n_v) D] float32 -> q, k [..., n_v, D] (normed, q
    scaled, a key head repeated for its value heads), v [..., n_v, D]."""
    d = u.shape[-1] // (2 * n_k + n_v)
    lead = u.shape[:-1]
    q = l2norm(u[..., :n_k * d].reshape(lead + (n_k, d))) * d ** -0.5
    k = l2norm(u[..., n_k * d:2 * n_k * d].reshape(lead + (n_k, d)))
    v = u[..., 2 * n_k * d:].reshape(lead + (n_v, d))
    rep = n_v // n_k
    q, k = (jnp.repeat(a, rep, axis=-2) for a in (q, k))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


def _unit_lower_inverse(low):
    """low [..., n, n] strictly lower triangular (whatever sits on or above
    the diagonal must be 0) -> (I + low)^-1, float32. Forward substitution
    row by row inside diagonal blocks of 16 (the rows of every block, chunk
    and head at once, the batch along the lanes), then
    `[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]` by matmuls:
    exact in the order a row-by-row solve is, with no power of `low` ever
    formed."""
    n = low.shape[-1]
    b = min(_SOLVE_BLOCK, n)
    nb = n // b
    assert nb * b == n and nb & (nb - 1) == 0, (
        f"a chunk of {n} is no power-of-two number of blocks of {b}")
    lead = low.shape[:-2]
    blocks = low.reshape(lead + (nb, b, nb, b))
    # [b, b, ..., nb]: a block's row and column in front, the batch behind
    a = -jnp.stack([blocks[..., i, :, i, :] for i in range(nb)], axis=-1)
    a = jnp.moveaxis(a, (-3, -2), (0, 1))
    for i in range(1, b):
        row = a[i]
        a = a.at[i].add(jnp.sum(row[:, None] * a, axis=0))
    a = jnp.moveaxis(a, (0, 1), (-3, -2)) + jnp.eye(b, dtype=low.dtype)[
        :, :, None]
    inv = [a[..., i] for i in range(nb)]
    size = b
    while len(inv) > 1:
        merged = []
        for p in range(0, len(inv), 2):
            lo = p * size
            below = low[..., lo + size:lo + 2 * size, lo:lo + size]
            corner = -jnp.matmul(
                jnp.matmul(inv[p + 1], below, precision=_HIGHEST), inv[p],
                precision=_HIGHEST)
            top = jnp.concatenate([inv[p], jnp.zeros_like(inv[p])], axis=-1)
            merged.append(jnp.concatenate(
                [top, jnp.concatenate([corner, inv[p + 1]], axis=-1)],
                axis=-2))
        inv, size = merged, 2 * size
    return inv[0]


def gdn_prefill(qkv, g, beta, conv_w, s0, tail0, length, *, n_k: int,
                n_v: int, chunk: int = CHUNK):
    """One row. qkv [S, (2 n_k + n_v) D]: the projection BEFORE the conv;
    g [S, n_v] float32 (log alpha, <= 0), beta [S, n_v] float32; conv_w
    [K, channels]; s0 [n_v, D, D] float32 and tail0 [K - 1, channels]: the
    state and the conv's last inputs before the row's first token (zeros at
    a sequence's start); length: how many of the S tokens are real (a
    prefix). -> (o [S, n_v, D] in qkv's type, the state after the last
    REAL token, the last K - 1 real inputs)."""
    s = qkv.shape[0]
    if s <= _SOLVE_BLOCK:   # one block of the solve: no padding to a chunk
        chunk = min(chunk, s)
    pad = (-s) % chunk      # a last chunk's padding is past `length`
    if pad:
        qkv, g, beta = (jnp.pad(a, ((0, pad), (0, 0)))
                        for a in (qkv, g, beta))
    out, state, tail = _gdn_prefill(
        qkv, g.astype(jnp.float32), beta.astype(jnp.float32), conv_w, s0,
        tail0, jnp.minimum(jnp.asarray(length, jnp.int32), s), n_k=n_k,
        n_v=n_v, chunk=chunk)
    return out[:s], state, tail


@functools.partial(jax.jit, static_argnames=("n_k", "n_v", "chunk"))
def _gdn_prefill(qkv, g, beta, conv_w, s0, tail0, length, *, n_k: int,
                 n_v: int, chunk: int):
    f32 = jnp.float32
    s, channels = qkv.shape
    taps = conv_w.shape[0]
    nc, h = s // chunk, n_v
    mm = qkv.dtype
    window = jnp.concatenate([tail0.astype(mm), qkv])
    # input t sits at window row t + K - 1: the last K - 1 real ones
    tail = jax.lax.dynamic_slice(window, (length, 0), (taps - 1, channels))
    q, k, v = _heads(_conv(window, conv_w, s), n_k, n_v, mm)
    d = q.shape[-1]
    real = (jnp.arange(s) < length)[:, None]
    g = jnp.where(real, g, 0.0)
    beta = jnp.where(real, beta, 0.0)

    def chunks(a):                 # [S, H, ...] -> [NC, H, C, ...]
        a = a.reshape((nc, chunk) + a.shape[1:])
        return jnp.moveaxis(a, 1, 2)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                                  # [NC,H,C]
    i = jnp.arange(chunk)
    expo = gc[..., :, None] - gc[..., None, :]
    at_or_below = i[:, None] >= i[None, :]
    decay = jnp.where(at_or_below, jnp.exp(jnp.where(at_or_below, expo, 0.0)),
                      0.0)
    kb = (k.astype(f32) * beta[..., None]).astype(mm)
    vb = (v.astype(f32) * beta[..., None]).astype(mm)
    kk = jnp.einsum("nhid,nhjd->nhij", kb, k, preferred_element_type=f32)
    solve = _unit_lower_inverse(
        jnp.where(i[:, None] > i[None, :], kk * decay, 0.0)).astype(mm)
    # a chunk's tokens' values and keys as the tokens before them in the
    # chunk leave them (the keys carry the decay from the chunk's start)
    value = jnp.einsum("nhij,nhjd->nhid", solve, vb,
                       preferred_element_type=f32)
    k_start = jnp.einsum(
        "nhij,nhjd->nhid", solve,
        (kb.astype(f32) * jnp.exp(gc)[..., None]).astype(mm),
        preferred_element_type=f32).astype(mm)
    qk = (jnp.einsum("nhid,nhjd->nhij", q, k, preferred_element_type=f32)
          * decay).astype(mm)
    q_start = (q.astype(f32) * jnp.exp(gc)[..., None]).astype(mm)
    k_end = (k.astype(f32) * jnp.exp(gc[..., -1:] - gc)[..., None]).astype(mm)
    through = jnp.exp(gc[..., -1])                               # [NC,H]

    def step(state, xs):
        value_c, k_start_c, qk_c, q_start_c, k_end_c, through_c = xs
        held = state.astype(mm)
        new = value_c - jnp.einsum("hid,hde->hie", k_start_c, held,
                                   preferred_element_type=f32)
        out = (jnp.einsum("hid,hde->hie", q_start_c, held,
                          preferred_element_type=f32)
               + jnp.einsum("hij,hje->hie", qk_c, new.astype(mm),
                            preferred_element_type=f32))
        state = through_c[:, None, None] * state + jnp.einsum(
            "hjd,hje->hde", k_end_c, new.astype(mm),
            preferred_element_type=f32)
        return state, out.astype(mm)

    state, out = jax.lax.scan(
        step, s0.astype(f32), (value, k_start, qk, q_start, k_end, through))
    return jnp.moveaxis(out, 1, 2).reshape(s, h, d), state, tail


def gdn_update(qkv, g, beta, conv_w, state_pool, conv_pool, layer, live, *,
               n_k: int, n_v: int, order=None, impl=None):
    """One token for the slot set, in place in the pools. qkv [B,
    channels] (row i is slot i); g, beta [B, n_v] float32; conv_w [K,
    channels]; state_pool [layers, B, n_v, D, D] float32; conv_pool
    [layers, K - 1, B, channels]; `layer` this layer's index in both; live
    [B] bool; `order`: `live_slots(live)` where the caller has it -> (o [B,
    n_v, D] in qkv's type, 0 for a slot that is not live; the pools with
    the LIVE slots of `layer` advanced by one token)."""
    impl = impl or _impl()
    if order is None and impl != "jnp":
        order = live_slots(live)
    return _gdn_update(qkv, g.astype(jnp.float32), beta.astype(jnp.float32),
                       conv_w, state_pool, conv_pool,
                       jnp.asarray(layer, jnp.int32), live, order, n_k=n_k,
                       n_v=n_v, impl=impl)


@functools.partial(jax.jit, static_argnames=("n_k", "n_v", "impl"))
def _gdn_update(qkv, g, beta, conv_w, state_pool, conv_pool, layer, live,
                order, *, n_k: int, n_v: int, impl: str):
    f32 = jnp.float32
    b = qkv.shape[0]
    tails = jax.lax.dynamic_index_in_dim(conv_pool, layer, 0, False)
    window = jnp.concatenate([tails, qkv[None].astype(tails.dtype)])
    u = jax.nn.silu(jnp.sum(                                # [K, B, C]
        window.astype(f32) * conv_w.astype(f32)[:, None], axis=0))
    conv_pool = jax.lax.dynamic_update_index_in_dim(
        conv_pool, jnp.where(live[None, :, None], window[1:], tails),
        layer, 0)
    q, k, v = _heads(u, n_k, n_v, f32)
    alpha = jnp.exp(jnp.where(live[:, None], g, 0.0))
    beta = jnp.where(live[:, None], beta, 0.0)
    if impl == "jnp":
        held = jax.lax.dynamic_index_in_dim(state_pool, layer, 0, False)
        kept = alpha[..., None, None] * held
        delta = beta[..., None] * (v - jnp.sum(k[..., None] * kept, axis=-2))
        new = kept + k[..., None] * delta[..., None, :]
        o = jnp.sum(q[..., None] * new, axis=-2)
        state_pool = jax.lax.dynamic_update_index_in_dim(
            state_pool, jnp.where(live[:, None, None, None], new, held),
            layer, 0)
    else:
        d = v.shape[-1]
        # [B, D, 2H]: a head's q and k as COLUMNS of a tile (the state's
        # rows are the key dim)
        qk = jnp.swapaxes(jnp.concatenate([q, k], axis=1), 1, 2)
        wide = (b, n_v, d)
        o, state_pool = _update_pallas(
            qk, v, jnp.broadcast_to(alpha[..., None], wide),
            jnp.broadcast_to(beta[..., None], wide), state_pool, layer,
            *order, interpret=impl == "pallas_interpret")
    return (jnp.where(live[:, None, None], o, 0.0).astype(qkv.dtype),
            state_pool, conv_pool)


def _update_kernel(layer_ref, order_ref, n_live_ref, qk_ref, v_ref, a_ref,
                   b_ref, h_ref, o_ref, ho_ref, *, heads: int):
    """Grid (slot of `order`): a step owns one live slot's state, all
    heads, which `order` brought in and takes back out. A head's state is
    [D keys, D values]: its key and query are columns (one lane of the
    `qk` tile, spread along the lanes), its value and gates rows."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    n_live = n_live_ref[0]

    @pl.when(n_live == 0)
    def _():   # nothing is live: the block that was brought in goes back
        ho_ref[...] = h_ref[...]

    @pl.when(i < n_live)
    def _():
        for h in range(heads):
            q_col = qk_ref[0, :, h:h + 1]
            k_col = qk_ref[0, :, heads + h:heads + h + 1]
            kept = a_ref[0, h:h + 1, :] * h_ref[0, 0, h]
            delta = b_ref[0, h:h + 1, :] * (
                v_ref[0, h:h + 1, :]
                - jnp.sum(k_col * kept, axis=0, keepdims=True))
            new = kept + k_col * delta
            ho_ref[0, 0, h] = new
            o_ref[0, h:h + 1, :] = jnp.sum(q_col * new, axis=0,
                                           keepdims=True)


def _update_pallas(qk, v, alpha, beta, state_pool, layer, order, n_live, *,
                   interpret: bool):
    """qk [B, D, 2H], v, alpha, beta [B, H, D] float32; o [B, H, D] (a slot
    that is not live: whatever the buffer held)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = v.shape

    def row(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda i, layer, order, n_live: (order[i], 0, 0))

    state = pl.BlockSpec(
        (1, 1, h, d, d),
        lambda i, layer, order, n_live: (layer[0], order[i], 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_update_kernel, heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[row((d, 2 * h)), row((h, d)), row((h, d)),
                      row((h, d)), state],
            out_specs=[row((h, d)), state]),
        out_shape=[jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state_pool.shape, state_pool.dtype)],
        # operand 7 (after the 3 prefetched scalars): the pool, in place
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_UPDATE_VMEM_BYTES),
        interpret=interpret,
    )(layer.reshape(1), order, n_live.reshape(1), qk, v, alpha, beta,
      state_pool)
