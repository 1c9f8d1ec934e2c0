"""The gated delta rule (ops/gated_delta.py) on the CPU at a tiny size: the
chunked form against the token-by-token recurrence in float32, a row split
anywhere and resumed, padding, the one-token update over live slots (jnp and
the Pallas kernel in interpret mode), and the rule's corner cases.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta as gd

NK, NV, D, TAPS = 2, 4, 16, 4
CHANNELS = (2 * NK + NV) * D
HEADS = dict(n_k=NK, n_v=NV)


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _reference(qkv, g, beta, conv_w, s0, tail0, length, *, n_k: int,
                  n_v: int):
    """`gdn_prefill`'s results by the token-by-token recurrence in float32:
    no chunk, no solve."""
    f32 = jnp.float32
    s = qkv.shape[0]
    taps = conv_w.shape[0]
    window = jnp.concatenate([tail0.astype(qkv.dtype), qkv])
    tail = jax.lax.dynamic_slice(window, (length, 0),
                                 (taps - 1, qkv.shape[1]))
    q, k, v = gd._heads(gd._conv(window, conv_w, s), n_k, n_v, f32)

    def step(state, xs):
        qt, kt, vt, gt, bt, real = xs
        kept = jnp.exp(gt)[:, None, None] * state
        delta = bt[:, None] * (vt - jnp.sum(kt[..., None] * kept, axis=-2))
        new = kept + kt[..., None] * delta[..., None, :]
        state = jnp.where(real, new, state)
        return state, jnp.sum(qt[..., None] * state, axis=-2)

    state, out = jax.lax.scan(
        step, s0.astype(f32),
        (q, k, v, g.astype(f32), beta.astype(f32), jnp.arange(s) < length))
    return out.astype(qkv.dtype), state, tail


def _row(s, seed=0, state=True):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return dict(
        qkv=f(s, CHANNELS), g=-jnp.abs(f(s, NV)) * 0.3,
        beta=jax.nn.sigmoid(f(s, NV)), conv_w=f(TAPS, CHANNELS) * 0.5,
        s0=f(NV, D, D) if state else jnp.zeros((NV, D, D)),
        tail0=f(TAPS - 1, CHANNELS) if state
        else jnp.zeros((TAPS - 1, CHANNELS)))


def _close(a, b, tol=2e-5):
    if not a.size:
        return
    assert float(jnp.abs(a - b).max()) < tol, float(jnp.abs(a - b).max())


@pytest.mark.parametrize("s, chunk", [(200, 64), (64, 64), (96, 32), (40, 8),
                                      (7, 64)])
def test_the_chunked_form_is_the_token_recurrence(s, chunk):
    r = _row(s, seed=s)
    got = gd.gdn_prefill(*r.values(), s, chunk=chunk, **HEADS)
    want = _reference(*r.values(), s, **HEADS)
    for a, b in zip(got, want):
        _close(a, b)


def test_the_solve_is_the_inverse_of_a_unit_lower_matrix():
    rng = np.random.default_rng(1)
    low = jnp.tril(jnp.asarray(0.1 * rng.normal(size=(3, 5, 64, 64)),
                               jnp.float32), -1)
    inv = gd._unit_lower_inverse(low)
    eye = jnp.eye(64)
    _close(jnp.matmul(inv, eye + low), jnp.broadcast_to(eye, low.shape), 1e-4)
    # repeated keys at beta 1: entries of 1 below the diagonal, an inverse
    # of 1 and -1 that no power series of `low` reaches in float32
    ones = jnp.tril(jnp.ones((64, 64)), -1)
    _close(gd._unit_lower_inverse(ones), jnp.eye(64) - jnp.eye(64, k=-1),
           1e-6)


@pytest.mark.parametrize("cut", [1, 3, 63, 64, 65, 150])
def test_a_row_split_anywhere_and_resumed_is_one_pass(cut):
    r = _row(200, seed=5, state=False)
    whole = gd.gdn_prefill(*r.values(), 200, **HEADS)
    first = {**r, **{k: r[k][:cut] for k in ("qkv", "g", "beta")}}
    o1, s1, t1 = gd.gdn_prefill(*first.values(), cut, **HEADS)
    rest = {**r, **{k: r[k][cut:] for k in ("qkv", "g", "beta")},
            "s0": s1, "tail0": t1}
    o2, s2, t2 = gd.gdn_prefill(*rest.values(), 200 - cut, **HEADS)
    _close(jnp.concatenate([o1, o2]), whole[0])
    _close(s2, whole[1])
    assert bool((t2 == whole[2]).all())


@pytest.mark.parametrize("length", [0, 1, 2, 63, 100])
def test_a_position_past_the_length_moves_nothing(length):
    r = _row(128, seed=7)
    o, s, t = gd.gdn_prefill(*r.values(), length, **HEADS)
    short = {**r, **{k: r[k][:max(length, 1)] for k in ("qkv", "g", "beta")}}
    o_w, s_w, t_w = gd.gdn_prefill(*short.values(), length, **HEADS)
    _close(o[:length], o_w[:length])
    _close(s, s_w)
    assert bool((t == t_w).all()) and bool(jnp.isfinite(o).all())
    if length == 0:
        assert bool((s == r["s0"]).all()) and bool((t == r["tail0"]).all())
    # garbage past the length changes nothing either
    junk = {**r, "qkv": r["qkv"].at[length:].set(1e3),
            "g": r["g"].at[length:].set(-5.0)}
    o_j, s_j, t_j = gd.gdn_prefill(*junk.values(), length, **HEADS)
    _close(o_j[:length], o[:length])
    _close(s_j, s)
    assert bool((t_j == t).all())


def _pools(seed=3, layers=2, slots=5):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(layers, slots, NV, D, D)),
                        jnp.float32),
            jnp.asarray(rng.normal(size=(layers, TAPS - 1, slots, CHANNELS)),
                        jnp.float32))


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("live", [(True, False, True, True, False),
                                  (False,) * 5, (True,) * 5])
def test_the_update_moves_live_slots_only_and_is_the_recurrence(impl, live):
    state, conv = _pools()
    r = _row(5, seed=9)
    live = jnp.asarray(live)
    o, state2, conv2 = gd.gdn_update(
        r["qkv"], r["g"], r["beta"], r["conv_w"], state, conv, 1, live,
        impl=impl, **HEADS)
    assert bool((state2[0] == state[0]).all())
    assert bool((conv2[0] == conv[0]).all())
    for i in range(5):
        if not bool(live[i]):
            # bit for bit, and an output of 0
            assert bool((state2[1, i] == state[1, i]).all())
            assert bool((conv2[1, :, i] == conv[1, :, i]).all())
            assert float(jnp.abs(o[i]).max()) == 0.0
            continue
        o_w, s_w, t_w = _reference(
            r["qkv"][i:i + 1], r["g"][i:i + 1], r["beta"][i:i + 1],
            r["conv_w"], state[1, i], conv[1, :, i], 1, **HEADS)
        _close(o[i], o_w[0])
        _close(state2[1, i], s_w)
        assert bool((conv2[1, :, i] == t_w).all())


def test_prefill_then_updates_is_one_longer_prefill():
    r = _row(70, seed=11, state=False)
    whole = gd.gdn_prefill(*r.values(), 70, **HEADS)
    first = {**r, **{k: r[k][:64] for k in ("qkv", "g", "beta")}}
    _, s, t = gd.gdn_prefill(*first.values(), 64, **HEADS)
    state, conv = s[None, None], t[None, :, None]
    for j in range(64, 70):
        o, state, conv = gd.gdn_update(
            r["qkv"][j:j + 1], r["g"][j:j + 1], r["beta"][j:j + 1],
            r["conv_w"], state, conv, 0, jnp.asarray([True]), **HEADS)
        _close(o[0], whole[0][j])
    _close(state[0, 0], whole[1])


@pytest.mark.parametrize("case", ["alpha=1,beta=1", "beta=0", "alpha=0"])
def test_the_rules_corner_cases(case):
    r = _row(80, seed=13, state=True)
    if case == "alpha=1,beta=1":
        r["g"], r["beta"] = jnp.zeros_like(r["g"]), jnp.ones_like(r["beta"])
    elif case == "beta=0":
        r["beta"] = jnp.zeros_like(r["beta"])
    else:
        r["g"] = jnp.full_like(r["g"], -60.0)
    o, s, t = gd.gdn_prefill(*r.values(), 80, **HEADS)
    o_w, s_w, _ = _reference(*r.values(), 80, **HEADS)
    _close(o, o_w, 1e-4)
    _close(s, s_w, 1e-4)
    window = jnp.concatenate([r["tail0"], r["qkv"]])
    _, k, v = gd._heads(gd._conv(window, r["conv_w"], 80), NK, NV,
                        jnp.float32)
    if case == "alpha=1,beta=1":
        # the last key reads back exactly its value: what the state held
        # for it was taken out first
        read = jnp.einsum("hk,hkv->hv", k[-1], s)
        _close(read, v[-1] * (1 - 1e-6 / (k[-1] ** 2).sum(-1, keepdims=True)),
               1e-3)
    elif case == "beta=0":
        # nothing is written: the state only decays
        _close(s, jnp.exp(r["g"].sum(0))[:, None, None] * r["s0"], 1e-5)
    else:
        # nothing is remembered: the state is the last token's own write
        want = r["beta"][-1][:, None, None] * k[-1][:, :, None] * v[-1][
            :, None, :]
        _close(s, want, 1e-5)
