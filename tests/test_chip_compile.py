"""Compile the main path's Pallas kernels for a described (not attached)
TPU v5e, at real widths.

Interpret-mode tests cannot see what Mosaic refuses: misaligned slices,
too much VMEM, a kernel GSPMD cannot partition. The TPU compiler is
installed here and compiles for a `v5e:2x2` that is only described, so
each case costs a second or two and no chip time. Nothing runs: a compile
that passes says nothing about results or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under xdist every
worker imports every test file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.flash_attention import (flash_attention,
                                         flash_attention_sharded)
from ray_tpu.ops.paged_attention import _decode_call
from ray_tpu.parallel.mesh import AXES

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip (the next one warns):
    keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _qkv(shape, sharding):
    b, s, hq, hkv, d = shape
    return tuple(jax.ShapeDtypeStruct((b, s, h, d), BF16, sharding=sharding)
                 for h in (hq, hkv, hkv))


def _fwd(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)


def _grads(attn):
    def fn(q, k, v):
        return jax.grad(lambda *a: attn(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)
    return fn


def _lse(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False,
                           return_lse=True)


def _flash(fn, shape):
    return lambda topo: (fn, _qkv(shape, SingleDeviceSharding(
        topo.devices[0])))


def _paged_decode(b, d, mp, hq=32, hkv=8, page=16):
    def build(topo):
        one_chip = SingleDeviceSharding(topo.devices[0])

        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        def fn(q, kv_pages, block_tables, lengths):
            return _decode_call(q, kv_pages, block_tables, lengths,
                                scale=d ** -0.5, pages_per_chunk=128 // page,
                                interpret=False)
        return fn, (sds((b, hq, d), BF16),
                    sds((b * mp, hkv, page, 2 * d), BF16),
                    sds((b, mp), jnp.int32), sds((b,), jnp.int32))
    return build


def _flash_on_mesh(topo):
    """The trainer's path on several chips: GSPMD cannot partition the
    kernel, so it goes through the shard_map wrapper (batch over fsdp,
    heads over tp)."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 2, 1, 1, 2), AXES)
    spec = NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None))
    return _grads(lambda q, k, v: flash_attention_sharded(
        q, k, v, mesh, causal=True, interpret=False)), _qkv(
            (4, 2048, 32, 8, 64), spec)


# id -> build(topo) -> (fn, argument shapes); shapes are (B, S, Hq, Hkv, D)
COMPILES = {
    "fwd-llama1b-train": _flash(_fwd, (3, 2048, 32, 8, 64)),
    "fwdbwd-llama1b-train": _flash(_grads(_fwd), (3, 2048, 32, 8, 64)),
    "fwd-d128-4k": _flash(_fwd, (1, 4096, 32, 8, 128)),
    "fwdbwd-d128-4k": _flash(_grads(_fwd), (1, 4096, 32, 8, 128)),
    "fwd-lse-prefill-bucket": _flash(_lse, (4, 512, 32, 8, 64)),
    "decode-llama1b-B8-D64-MP32": _paged_decode(8, 64, 32),
    "decode-8b-B8-D128-MP512": _paged_decode(8, 128, 512),
    "fwdbwd-shard_map-2x2-mesh": _flash_on_mesh,
}
# The kernel's measured compile limits: K/V of one (batch, kv head) stay
# resident in VMEM, so long kv is refused (bwd passes at 4096, fwd at
# 16384). The PR that tiles K/V flips these knowingly.
REFUSED = {
    "fwdbwd-kv8192-refused": _flash(_grads(_fwd), (1, 8192, 32, 8, 64)),
    "fwd-kv32768-refused": _flash(_fwd, (1, 32768, 32, 8, 64)),
}


@pytest.mark.parametrize("name", [*COMPILES, *REFUSED])
def test_kernel_compiles_for_v5e(topo, no_persistent_cache, name):
    fn, args = {**COMPILES, **REFUSED}[name](topo)
    lowered = jax.jit(fn).lower(*args)
    if name in REFUSED:
        with pytest.raises(Exception, match="(?i)vmem"):
            lowered.compile()
    else:
        assert "tpu_custom_call" in lowered.compile().as_text()
