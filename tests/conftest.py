"""Test fixtures.

Mirrors the reference's test strategy (ref: python/ray/tests/conftest.py —
ray_start_regular :588, ray_start_cluster :678): a shared session fixture for
cheap tests, fresh-session fixtures for fault-tolerance/cluster tests.

JAX tests run on a virtual 8-device CPU mesh (the reference tests multi-node
without a real cluster the same way, via cluster_utils.Cluster).
"""

import os

# Force the CPU backend with 8 virtual devices, before any test initializes a
# backend. The driver also exports JAX_PLATFORMS=cpu; setting the config here
# keeps a bare `pytest tests/...` on the CPU too. Subprocesses (cluster
# workers) inherit the env vars instead.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: stress-scale tests excluded from tier-1 "
        "(-m 'not slow')")
    config.addinivalue_line(
        "markers", "transfer: bulk data-plane (cross-host object "
        "transfer) tests")
    config.addinivalue_line(
        "markers", "perf: microbench-style smoke tests (timing-sensitive; "
        "also marked slow so tier-1 stays within budget)")
    config.addinivalue_line(
        "markers", "llm_kv: distributed KV-cache plane (bulk handoff + "
        "prefix registry) tests; tier-1 on the CPU tiny-model config")
    config.addinivalue_line(
        "markers", "sched: decentralized scheduling plane (gossiped "
        "views, p2p spill, locality) tests")
    config.addinivalue_line(
        "markers", "lint: rtpulint/rtpuproto static-analysis tier "
        "(per-rule fixture self-tests + the zero-unsuppressed-findings "
        "gates: per-file RTPU001-007 over the whole package, "
        "whole-program protocol RTPU101-106 over package+tests+"
        "benchmarks)")
    config.addinivalue_line(
        "markers", "dag: compiled-graph data plane (cross-host "
        "channels, ring collectives, teardown) tests")
    config.addinivalue_line(
        "markers", "chaos: deterministic fault plane (runtime/faults.py) "
        "unit tests + the cluster-wide failure-drill suite")
    config.addinivalue_line(
        "markers", "stream: streaming data plane (pull-based operator "
        "pipeline, streaming_split coordinator, elastic Train ingest) "
        "tests")
    config.addinivalue_line(
        "markers", "overload: Serve admission plane (deadline "
        "propagation, bounded-queue load shedding to typed "
        "429s/ServiceOverloadedError, engine expiry pruning) tests + "
        "the 10x-overload drill in benchmarks/overload_drill.py")
    config.addinivalue_line(
        "markers", "tiering: tiered object store (shm/disk/URI spill + "
        "restore, pressure-driven lineage/borrower-aware eviction, "
        "replica broadcast trees) tests")
    config.addinivalue_line(
        "markers", "persist: durable control plane (crash-consistent "
        "persist-dir journal framing, torn-write fuzz matrix, "
        "replay↔reattach reconciliation) tests + the kill -9 restart "
        "drill in tests/test_chaos.py")
    config.addinivalue_line(
        "markers", "simscale: scheduler scale envelope over the "
        "in-process many-node harness (runtime/simcluster.py: real "
        "nodelets, fake workers — task-burst drain, O(changed) gossip "
        "fan-out, warm-standby failover reattach); the 100-node/100k "
        "envelope itself is slow-marked + benchmarks/scale_envelope.py")
    config.addinivalue_line(
        "markers", "pp: pipeline-parallel serving (multi-process stage "
        "engines over compiled-DAG channels: bit-exact greedy parity vs "
        "the single-process engine, zero steady-state control RPCs, "
        "bubble accounting, stage gang placement) tests + the stage-rank "
        "kill drill in tests/test_chaos.py")


@pytest.fixture
def shared_cluster():
    """A cluster shared by tests that only need basic cluster services.

    Function-scoped but lazy: re-initializes only if a fresh_cluster test (or
    an explicit shutdown) tore the shared session down in between.
    """
    import ray_tpu

    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield ray_tpu


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """After a test module, drop what JAX compiled for it. Every compiled
    CPU program holds memory maps until its jit cache lets go (seven engine
    tests of one file: 6,000 maps; `jax.clear_caches()`: 700 again), a
    worker runs dozens of files, and past the kernel's 65,530 a process's
    next compile dies of a segmentation fault inside LLVM: with the tests of
    PR 58 added, the worker that ran `tests/test_gigachat.py` last did, in
    two whole runs of two, while the file passes alone."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session", autouse=True)
def _shutdown_at_exit():
    yield
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


@pytest.fixture
def fresh_cluster():
    """A private session for tests that mutate cluster state."""
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    session = ray_tpu.init(num_cpus=4)
    yield session
    ray_tpu.shutdown()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax

    devices = jax.devices("cpu")
    assert len(devices) >= 8, (
        "tests expect XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return devices


@pytest.fixture
def dense_greedy():
    """The serving engines' oracle, `run(model, params, prompts, n_tokens)`:
    greedy continuations of `prompts` by the model run densely with no
    cache, one causal forward over the zero-padded batch per token (what
    follows a position cannot reach it, so one compiled width serves)."""
    import jax.numpy as jnp
    import numpy as np

    def run(model, params, prompts, n_tokens):
        seqs = [list(p) for p in prompts]
        width = -(-(max(map(len, seqs)) + n_tokens) // 16) * 16
        fwd = jax.jit(lambda ids: model.apply({"params": params}, ids))
        for _ in range(n_tokens):
            ids = np.zeros((len(seqs), width), np.int32)
            for i, seq in enumerate(seqs):
                ids[i, :len(seq)] = seq
            logits = np.asarray(fwd(jnp.asarray(ids)))
            for i, seq in enumerate(seqs):
                seq.append(int(logits[i, len(seq) - 1].argmax()))
        return [seq[len(p):] for seq, p in zip(seqs, prompts)]
    return run
