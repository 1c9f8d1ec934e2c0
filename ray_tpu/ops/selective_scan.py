"""Selective scan of a Mamba-1 state-space layer (models/jamba.py).

For one sequence, channel c of `d_inner` and state index n of `N`:

    h_t[n, c] = exp(delta_t[c] * A[n, c]) * h_{t-1}[n, c]
                + delta_t[c] * x_t[c] * B_t[n]
    y_t[c]    = sum_n h_t[n, c] * C_t[n] + D[c] * x_t[c]

`A`, `delta`, `h` and every product above are float32. The state is laid
out `[N, d_inner]`: the channels fill the lanes, so a state of N = 16 is
two sublane tiles a 128 channels and no lane is padding (`[d_inner, N]`
would pad 16 lanes to 128 on a TPU, eight times the bytes).

Two entry points, each inside ONE jitted wrapper of its own so that a
device trace names their ops by it (an instruction takes the name of the
innermost jit around its `pallas_call`; PERF.md section 3 lists both names
among the fragile ones, chipbench's `ssm_scan_*` metrics match the first):

- `selective_scan` -> `_ssm_scan`: a whole row of S tokens from a given
  `h_0` (prefill). On a TPU backend the Pallas kernel below; anywhere else
  the chunked `jax.numpy` form. The choice is made in one place (`_impl`)
  and never falls back on a TPU.
- `selective_update` -> `_ssm_update`: one token for the slot set
  (decode), in place in the state pool. On a TPU backend a Pallas kernel
  that brings in and takes back out the LIVE slots' state only (plain XLA
  ops over a layer's whole [slots, N, d] cost 2.4 ms of a 12.3 ms decode
  step at 21 live rows of 64: five times the live rows' bytes; PERF.md
  section 6, PR 33); anywhere else plain jax.numpy.

Padding-proof by the caller's `delta`: where `delta_t == 0` the state does
not move (`exp(0) = 1`, the input term is 0), so a row padded to its
length bucket leaves in `h_last` the state after its last REAL token. The
kernel is also told the row's `length` and skips every time chunk that
lies wholly past it (their `y` comes back zero).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# time steps per chunk: of the jnp form's associative scan ([T, N, d]
# float32 temporaries: 21 MB at d 5120), and of the kernel's grid
CHUNK = 64
KERNEL_CHUNK = 256
# channels a kernel grid step owns: one float32 vreg of [8, 128] a state
# index, so the 16 states of a block are 16 registers and every product is
# a full-width vector operation with B_t[n] and C_t[n] as scalars
_SUB, _LANE = 8, 128
BLOCK = _SUB * _LANE


def _impl() -> str:
    """"pallas" | "jnp"; a test passes "pallas_interpret" to run the
    kernel off the TPU."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def selective_scan(x, delta, A, B, C, D, h0, z=None, length=None,
                   impl=None):
    """One row. x [S, d] (any float type), delta [S, d] float32 (0 where
    the caller padded), A [N, d] float32 (negative), B, C [S, N], D [d],
    h0 [N, d] float32, z [S, d] or None (the gate: y * silu(z)), length:
    the row's real tokens (None: S). -> (y [S, d] in x's type, h_last
    [N, d] float32)."""
    s = x.shape[0]
    if length is None:
        length = s
    return _ssm_scan(x, delta, A, B, C, D, h0, z,
                     jnp.asarray(length, jnp.int32), impl=impl or _impl())


@functools.partial(jax.jit, static_argnames=("impl",))
def _ssm_scan(x, delta, A, B, C, D, h0, z, length, *, impl: str):
    f32 = jnp.float32
    args = (x.astype(f32), delta.astype(f32), A.astype(f32), B.astype(f32),
            C.astype(f32), D.astype(f32), h0.astype(f32))
    if impl == "jnp":
        y, h_last = _scan_chunked(*args)
    else:
        y, h_last = _scan_pallas(*args, length,
                                 interpret=impl == "pallas_interpret")
    if z is not None:
        y = y * jax.nn.silu(z.astype(f32))
    return y.astype(x.dtype), h_last


def _scan_chunked(x, delta, A, B, C, D, h0):
    """The plain form: time in chunks of CHUNK steps, an associative scan
    over (a, b) -> h = a * h_prev + b inside a chunk, the state carried
    from chunk to chunk."""
    s, d = x.shape
    pad = (-s) % CHUNK
    if pad:   # delta 0: the state stands still over the padding
        x, delta, B, C = (jnp.pad(a, ((0, pad), (0, 0)))
                          for a in (x, delta, B, C))

    def chunk(h, blk):
        xc, dc, bc, cc = blk
        a = jnp.exp(dc[:, None, :] * A[None])                # [T, N, d]
        b = (dc * xc)[:, None, :] * bc[:, :, None]
        a_run, b_run = jax.lax.associative_scan(
            lambda l, r: (l[0] * r[0], r[0] * l[1] + r[1]), (a, b))
        hs = a_run * h[None] + b_run
        y = jnp.einsum("tnd,tn->td", hs, cc) + D[None] * xc
        return hs[-1], y

    blocks = tuple(a.reshape(-1, CHUNK, a.shape[-1])
                   for a in (x, delta, B, C))
    h_last, y = jax.lax.scan(chunk, h0, blocks)
    return y.reshape(-1, d)[:s], h_last


def _scan_kernel(len_ref, b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, h0_ref,
                 y_ref, hl_ref, h_scr, *, n_state: int, chunk: int):
    """Grid (channel block, time chunk), time innermost: the block's state
    h [N, 8, 128] stays in VMEM (in registers inside a chunk) while the
    chunks of x and delta stream past once and y streams out once."""
    from jax.experimental import pallas as pl

    t_blk = pl.program_id(1)

    @pl.when(t_blk == 0)
    def _():
        h_scr[...] = h0_ref[0]

    live = t_blk * chunk < len_ref[0]

    @pl.when(live)
    def _():
        d_skip = d_ref[0]

        def step(t, hs):
            dt, xt = dt_ref[t, 0], x_ref[t, 0]
            dtx = dt * xt
            y = d_skip * xt
            out = []
            for n in range(n_state):
                h = jnp.exp(dt * a_ref[n, 0]) * hs[n] \
                    + b_ref[t * n_state + n] * dtx
                y = y + c_ref[t * n_state + n] * h
                out.append(h)
            y_ref[t, 0] = y
            return tuple(out)

        hs = jax.lax.fori_loop(
            0, chunk, step, tuple(h_scr[n] for n in range(n_state)))
        for n in range(n_state):
            h_scr[n] = hs[n]

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(t_blk == pl.num_programs(1) - 1)
    def _():
        hl_ref[0] = h_scr[...]


def _scan_pallas(x, delta, A, B, C, D, h0, length, *, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, d = x.shape
    n = A.shape[0]
    t = min(KERNEL_CHUNK, -(-s // 8) * 8)
    pad_s, pad_d = (-s) % t, (-d) % BLOCK
    if pad_s or pad_d:
        x, delta = (jnp.pad(a, ((0, pad_s), (0, pad_d))) for a in (x, delta))
        B, C = (jnp.pad(a, ((0, pad_s), (0, 0))) for a in (B, C))
        A, h0 = (jnp.pad(a, ((0, 0), (0, pad_d))) for a in (A, h0))
        D = jnp.pad(D, (0, pad_d))
    sp, g = s + pad_s, (d + pad_d) // BLOCK

    def rows(a):      # [S, d] -> [S, g, 8, 128]: a block's step is a vreg
        return a.reshape(sp, g, _SUB, _LANE)

    def states(a):    # [N, d] -> [g, N, 8, 128]
        return a.reshape(n, g, _SUB, _LANE).swapaxes(0, 1)

    row_spec = pl.BlockSpec((t, 1, _SUB, _LANE),
                            lambda c, i, *_: (i, c, 0, 0))
    state_spec = pl.BlockSpec((1, n, _SUB, _LANE),
                              lambda c, i, *_: (c, 0, 0, 0))
    flat_spec = pl.BlockSpec((t * n,), lambda c, i, *_: (i,),
                             memory_space=pltpu.SMEM)
    y, h_last = pl.pallas_call(
        functools.partial(_scan_kernel, n_state=n, chunk=t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g, sp // t),
            in_specs=[
                flat_spec, flat_spec, row_spec, row_spec,
                pl.BlockSpec((n, 1, _SUB, _LANE),
                             lambda c, i, *_: (0, c, 0, 0)),
                pl.BlockSpec((1, _SUB, _LANE), lambda c, i, *_: (c, 0, 0)),
                state_spec],
            out_specs=[row_spec, state_spec],
            scratch_shapes=[pltpu.VMEM((n, _SUB, _LANE), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((sp, g, _SUB, _LANE), jnp.float32),
                   jax.ShapeDtypeStruct((g, n, _SUB, _LANE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(length.reshape(1), B.reshape(-1), C.reshape(-1), rows(x), rows(delta),
      A.reshape(n, g, _SUB, _LANE), D.reshape(g, _SUB, _LANE), states(h0))
    return (y.reshape(sp, -1)[:s, :d],
            h_last.swapaxes(0, 1).reshape(n, -1)[:, :d])


def live_slots(live):
    """live [S] bool -> (order [S] int32, n_live []): the live slots'
    indices first, in order, the rest of `order` repeating the last live
    one (the update kernel's grid walks `order`; a repeated index moves
    no block). Computed once a decode step, for every layer."""
    n_live = jnp.sum(live).astype(jnp.int32)
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    last = order[jnp.maximum(n_live - 1, 0)]
    return jnp.where(jnp.arange(live.shape[0]) < n_live, order, last), n_live


def state_shape(n_state: int, d_inner: int) -> tuple:
    """One sequence's state as the pool stores it: [N, 8, d/8], a state
    index an [8, d/8] tile of full vector registers (channel c at
    [c // (d/8), c % (d/8)]), so that the update kernel multiplies whole
    registers by the scalars B_t[n] and C_t[n], as the scan kernel does.
    [N, d] is its logical form (`h.reshape(N, d)`)."""
    assert d_inner % _SUB == 0, f"d_inner {d_inner} is no multiple of 8"
    return (n_state, _SUB, d_inner // _SUB)


def selective_update(x, delta, A, B, C, D, h_pool, layer, live, z=None,
                     order=None, impl=None):
    """One token for the slot set, in place in the state pool. x, delta
    [S, d], A [N, d], B, C [S, N], D [d], h_pool [L, S, *state_shape]
    float32, `layer` this layer's index in it, live [S] bool, `order`:
    `live_slots(live)` where the caller has it -> (y [S, d] in x's type,
    h_pool with layer `layer` of the LIVE slots advanced by one token). A
    slot that is not live keeps its state bit for bit, and its `y` is 0.
    On a TPU backend the Pallas kernel reads and writes the live slots'
    state only; anywhere else plain jax.numpy over the layer's slots."""
    impl = impl or _impl()
    if order is None and impl != "jnp":
        order = live_slots(live)
    return _ssm_update(x, delta, A, B, C, D, h_pool,
                       jnp.asarray(layer, jnp.int32), live, z, order,
                       impl=impl)


@functools.partial(jax.jit, static_argnames=("impl",))
def _ssm_update(x, delta, A, B, C, D, h_pool, layer, live, z, order, *,
                impl: str):
    f32 = jnp.float32
    s, d = x.shape
    n = A.shape[0]
    xf = x.astype(f32)
    dt = jnp.where(live[:, None], delta.astype(f32), 0.0)
    A, B, C = A.astype(f32), B.astype(f32), C.astype(f32)
    if impl == "jnp":
        stored = jax.lax.dynamic_index_in_dim(h_pool, layer, 0, False)
        h = stored.reshape(s, n, d)
        h_new = (jnp.exp(dt[:, None, :] * A[None]) * h
                 + (dt * xf)[:, None, :] * B[:, :, None])
        y = jnp.sum(h_new * C[:, :, None], axis=1)
        h_new = jnp.where(live[:, None, None], h_new, h)
        h_pool = jax.lax.dynamic_update_index_in_dim(
            h_pool, h_new.reshape(stored.shape), layer, 0)
    else:
        tile = h_pool.shape[-2:]
        y, h_pool = _update_pallas(
            xf.reshape((s,) + tile), dt.reshape((s,) + tile),
            A.reshape((n,) + tile), B.reshape(-1), C.reshape(-1), h_pool,
            layer, *order, interpret=impl == "pallas_interpret")
        y = y.reshape(s, d)
    y = y + D.astype(f32)[None] * xf
    if z is not None:
        y = y * jax.nn.silu(z.astype(f32))
    return jnp.where(live[:, None], y, 0.0).astype(x.dtype), h_pool


def _update_kernel(layer_ref, order_ref, n_live_ref, b_ref, c_ref, x_ref,
                   dt_ref, a_ref, h_ref, y_ref, ho_ref, *, n_state: int):
    """Grid (slot of `order`): a step owns one live slot's state, which
    `order` brought in and takes back out. A state index is an [8, d/8]
    tile; B_t[n] and C_t[n] are scalars."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    n_live = n_live_ref[0]

    @pl.when(n_live == 0)
    def _():   # nothing is live: the block that was brought in goes back
        ho_ref[...] = h_ref[...]

    @pl.when(i < n_live)
    def _():
        at = order_ref[i] * n_state
        dt = dt_ref[0]
        dtx = dt * x_ref[0]
        y = jnp.zeros_like(dt)
        for n in range(n_state):
            h = jnp.exp(dt * a_ref[n]) * h_ref[0, 0, n] + b_ref[at + n] * dtx
            ho_ref[0, 0, n] = h
            y = y + c_ref[at + n] * h
        y_ref[0] = y


def _update_pallas(x, delta, A, B, C, h_pool, layer, order, n_live, *,
                   interpret: bool):
    """x, delta [S, 8, d/8], A [N, 8, d/8], B, C [S * N]; y [S, 8, d/8]
    (a slot that is not live: whatever the buffer held)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = x.shape[0]
    n = A.shape[0]
    tile = x.shape[1:]
    row = pl.BlockSpec((1,) + tile,
                       lambda i, layer, order, n_live: (order[i], 0, 0))
    state = pl.BlockSpec(
        (1, 1, n) + tile,
        lambda i, layer, order, n_live: (layer[0], order[i], 0, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_update_kernel, n_state=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s,),
            in_specs=[smem, smem, row, row,
                      pl.BlockSpec((n,) + tile, lambda i, *_: (0, 0, 0)),
                      state],
            out_specs=[row, state]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(h_pool.shape, h_pool.dtype)],
        # operand 8 (after the 3 prefetched scalars): the pool, in place
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer.reshape(1), order, n_live.reshape(1), B, C, x, delta, A, h_pool)
