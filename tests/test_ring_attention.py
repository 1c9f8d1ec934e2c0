"""Ring / Ulysses sequence-parallel attention vs the dense reference.

The reference has no sequence parallelism to mirror (SURVEY.md §5), so the
correctness bar here is internal: sharded collectives must match the dense
single-device computation bit-for-bit-ish (fp32 tolerances).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.ring_attention import (
    ring_attention,
    ring_attention_sharded,
    ulysses_attention,
)


def make_qkv(rng, b=2, s=64, hq=4, hkv=4, d=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, s, hq, d), dtype)
    k = jax.random.normal(kk, (b, s, hkv, d), dtype)
    v = jax.random.normal(kv, (b, s, hkv, d), dtype)
    return q, k, v


@pytest.fixture(scope="module")
def sp_mesh(cpu_mesh_devices):
    return Mesh(np.asarray(cpu_mesh_devices[:4]).reshape(4), ("sp",))


def run_ring(mesh, q, k, v, **kw):
    spec = P(None, "sp", None, None)
    fn = shard_map(functools.partial(ring_attention, axis_name="sp", **kw),
                   mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                   check_vma=False)
    return jax.jit(fn)(q, k, v)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_reference(sp_mesh, causal):
    q, k, v = make_qkv(jax.random.PRNGKey(0))
    expected = reference_attention(q, k, v, causal=causal)
    got = run_ring(sp_mesh, q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_ring_gqa(sp_mesh):
    q, k, v = make_qkv(jax.random.PRNGKey(1), hq=8, hkv=2)
    expected = reference_attention(q, k, v, causal=True)
    got = run_ring(sp_mesh, q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_ring_segment_ids(sp_mesh):
    q, k, v = make_qkv(jax.random.PRNGKey(2))
    b, s = q.shape[:2]
    seg = jnp.asarray(np.repeat(np.arange(4), s // 4)[None].repeat(b, 0))
    expected = reference_attention(q, k, v, causal=True, segment_ids=seg)

    spec = P(None, "sp", None, None)
    seg_spec = P(None, "sp")
    fn = shard_map(
        lambda q, k, v, s_: ring_attention(q, k, v, axis_name="sp",
                                           causal=True, segment_ids=s_),
        mesh=sp_mesh, in_specs=(spec,) * 3 + (seg_spec,), out_specs=spec,
        check_vma=False)
    got = jax.jit(fn)(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_ring_grad_matches_reference(sp_mesh):
    q, k, v = make_qkv(jax.random.PRNGKey(3), s=32)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    def loss_ring(q, k, v):
        return jnp.sum(run_ring(sp_mesh, q, k, v, causal=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_ring_sharded_wrapper(cpu_mesh_devices):
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(dp=2, fsdp=1, sp=2, tp=2),
                       devices=cpu_mesh_devices[:8])
    q, k, v = make_qkv(jax.random.PRNGKey(4), b=4, s=32, hq=4, hkv=4)
    expected = reference_attention(q, k, v, causal=True)

    @jax.jit
    def f(q, k, v):
        return ring_attention_sharded(q, k, v, mesh, causal=True)

    got = f(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_reference(sp_mesh, causal):
    q, k, v = make_qkv(jax.random.PRNGKey(5))
    expected = reference_attention(q, k, v, causal=causal)
    spec = P(None, "sp", None, None)
    fn = shard_map(
        functools.partial(ulysses_attention, axis_name="sp", causal=causal),
        mesh=sp_mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_gqa_segment_ids(sp_mesh):
    q, k, v = make_qkv(jax.random.PRNGKey(6), hq=8, hkv=4)
    b, s = q.shape[:2]
    seg = jnp.asarray(np.repeat(np.arange(2), s // 2)[None].repeat(b, 0))
    expected = reference_attention(q, k, v, causal=True, segment_ids=seg)
    spec = P(None, "sp", None, None)
    fn = shard_map(
        lambda q, k, v, s_: ulysses_attention(q, k, v, axis_name="sp",
                                              causal=True, segment_ids=s_),
        mesh=sp_mesh, in_specs=(spec,) * 3 + (P(None, "sp"),),
        out_specs=spec, check_vma=False)
    got = jax.jit(fn)(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_llama_train_step_with_ring_matches_dense(cpu_mesh_devices):
    """End-to-end: one ShardedTrainer step on an sp=2 mesh with ring
    attention produces the same loss as the dense path."""
    from ray_tpu.models.llama import LlamaModel, get_config
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.train_lib import ShardedTrainer, default_optimizer

    batch = {"input_ids": np.asarray(
        np.random.RandomState(0).randint(0, 256, (4, 64)), np.int32)}
    losses = {}
    for name, (impl, mcfg) in {
        "dense": (None, MeshConfig(dp=1, fsdp=1, sp=1, tp=1)),
        "ring": ("ring", MeshConfig(dp=1, fsdp=2, sp=2, tp=2)),
    }.items():
        cfg = get_config("tiny", attention_impl=impl, dtype=jnp.float32)
        n = 1
        for v in (mcfg.dp, mcfg.fsdp, mcfg.sp, mcfg.tp):
            n *= v
        mesh = create_mesh(mcfg, devices=cpu_mesh_devices[:n])
        trainer = ShardedTrainer(LlamaModel(cfg), mesh,
                                 optimizer=default_optimizer())
        state = trainer.init(jax.random.PRNGKey(0), batch)
        _, metrics = trainer.step(state, batch)
        losses[name] = float(metrics["loss"])
    assert abs(losses["ring"] - losses["dense"]) < 1e-3, losses
