"""What models/_stack.py states for every family and no family's test states
on its own. CPU, tiny shapes."""

import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import _stack, kimi, mellum

CFG = types.SimpleNamespace(vocab_size=24, hidden_size=8, dtype=jnp.float32,
                            param_dtype=jnp.float32)


class Head(nn.Module):
    tied: bool

    @nn.compact
    def __call__(self, ids, gather):
        embed, x = _stack.embed_tokens(self, CFG, ids)
        return _stack.head_at_gather(self, CFG, x, gather,
                                     weight=embed if self.tied else None)


def _head(tied):
    model = Head(tied)
    ids = jnp.arange(10).reshape(2, 5) % CFG.vocab_size
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), ids, None))
    assert sorted(params["params"]) == (["embed"] if tied
                                        else ["embed", "lm_head"])
    return model, params, ids


@pytest.mark.parametrize("tied", [False, True], ids=["lm_head", "tied"])
def test_the_head_at_a_rows_position_is_the_full_heads_row(tied):
    model, params, ids = _head(tied)
    full = model.apply(params, ids, None)
    assert full.shape == (2, 5, CFG.vocab_size)
    got = model.apply(params, ids, jnp.array([3, -1]))
    assert got.shape == (2, 1, CFG.vocab_size)
    # a row that samples nothing reads position 0: its logits are dropped
    np.testing.assert_allclose(got[:, 0], full[jnp.arange(2), [3, 0]],
                               rtol=1e-6)


@pytest.mark.parametrize("tied", [False, True], ids=["lm_head", "tied"])
def test_no_row_sampling_writes_zeros_and_runs_no_product(tied):
    model, params, ids = _head(tied)
    none = jnp.array([-1, -1])
    got = model.apply(params, ids, none)
    assert got.shape == (2, 1, CFG.vocab_size) and not np.asarray(got).any()
    # the product is in one branch of a `cond` and nowhere else
    jaxpr = jax.make_jaxpr(lambda g: model.apply(params, ids, g))(none)
    outer = [e.primitive.name for e in jaxpr.eqns]
    assert "cond" in outer and "dot_general" not in outer
    cond = next(e for e in jaxpr.eqns if e.primitive.name == "cond")
    with_product = ["dot_general" in [e.primitive.name for e in b.eqns]
                    for b in cond.params["branches"]]
    assert sorted(with_product) == [False, True]


@pytest.mark.parametrize("family,preset,page", [
    (kimi, "tiny-kimi", 16), (mellum, "tiny-mellum", 16),
    (kimi, "tiny-kimi", 8)], ids=["one-array", "dict", "page-8"])
def test_a_calls_own_pool_gives_every_row_its_own_pages(family, preset, page):
    cfg = family.get_config(preset)
    b, s = 3, 37
    mask = jnp.arange(s)[None] < jnp.array([37, 5, 0])[:, None]
    cache = _stack.own_cache(family.pool_spec, family.serving_cache, cfg, b,
                             s, mask, page=page)
    tables = np.asarray(cache.block_tables)
    # room for every position of a row, page 0 no row's, no page twice
    assert tables.shape[0] == b and tables.shape[1] * page >= s
    assert tables.min() == 1 and len(set(tables.ravel())) == tables.size
    pools = (cache.pool if isinstance(cache.pool, dict)
             else {"kv_pages": cache.pool})
    assert pools["kv_pages"].shape[1] == tables.max() + 1
    assert not any(np.asarray(p).any() for p in pools.values())
    np.testing.assert_array_equal(cache.total_lens, [37, 5, 0])
    whole = _stack.own_cache(family.pool_spec, family.serving_cache, cfg, b,
                             s, None, page=page)
    np.testing.assert_array_equal(whole.total_lens, [s] * b)


class Body(nn.Module):
    width: int

    @nn.compact
    def __call__(self, carry, x, consts):
        w = self.param("w", _stack.A(nn.initializers.ones, ("embed",)),
                       (self.width,))
        self.sow("selection", "seen", x)
        return carry * w + x + consts, None


class Stack(nn.Module):
    @nn.compact
    def __call__(self, carry):
        return _stack.scan_run(Body, 3, "run_00", 4)(
            carry, jnp.arange(3.0), jnp.float32(10))[0]


def test_a_scanned_run_stacks_its_layers_under_an_axis_named_layers():
    boxed = Stack().init(jax.random.PRNGKey(0), jnp.zeros(4))["params"]
    leaf = boxed["run_00"]["w"]
    assert leaf.names == ("layers", "embed") and leaf.value.shape == (3, 4)
    params = {"params": nn.meta.unbox(boxed)}
    # xs a layer, consts whole: ((0 + 0 + 10) + 1 + 10) + 2 + 10
    out, sown = Stack().apply(params, jnp.zeros(4), mutable=["selection"])
    np.testing.assert_array_equal(out, [33.0] * 4)
    np.testing.assert_array_equal(sown["selection"]["run_00"]["seen"][0],
                                  [0.0, 1.0, 2.0])
    # a collection nobody asked for costs nothing and returns nothing
    np.testing.assert_array_equal(Stack().apply(params, jnp.zeros(4)), out)
