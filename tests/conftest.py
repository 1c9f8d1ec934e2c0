"""Test fixtures.

Mirrors the reference's test strategy (ref: python/ray/tests/conftest.py —
ray_start_regular :588, ray_start_cluster :678): a shared session fixture for
cheap tests, fresh-session fixtures for fault-tolerance/cluster tests.

JAX tests run on a virtual 8-device CPU mesh (the reference tests multi-node
without a real cluster the same way, via cluster_utils.Cluster).
"""

import os
import signal
import traceback
from contextlib import contextmanager

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

from _engines import close_all  # noqa: E402

# A test that waits fails by name. The dearest case of a whole loaded run
# is 75 s (PR 62); a run of the suite is cut at 1470 s, and a cut run has
# no exit code of its own and counts only the lines it finished: so a
# stall has to end well inside that, as ONE failure that says where it was.
TEST_TIME_LIMIT_S = 300.0


@contextmanager
def time_limit(seconds: float, what: str = "the test"):
    """Fail what runs inside once it has run `seconds`, from the line it
    is waiting at (the alarm arrives in the main thread, between two
    bytecodes or out of an interruptible wait; it repeats every 5 s, for
    code that swallows the first). Nests: leaving restores the limit that
    was running."""
    def expire(signum, frame):
        pytest.fail(f"{what} ran past its limit of {seconds:g} s; it was "
                    "waiting at:\n" + "".join(traceback.format_stack(frame)))

    was = signal.signal(signal.SIGALRM, expire)
    left, again = signal.setitimer(signal.ITIMER_REAL, seconds, 5.0)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, left, again)
        signal.signal(signal.SIGALRM, was)


def _limited(item, phase):
    with time_limit(TEST_TIME_LIMIT_S, f"{item.nodeid} ({phase})"):
        return (yield)


# The dear files, dearest first (seconds of a whole loaded run, PR 62;
# `tools/t1_times.py` says when the order is stale). `--dist loadfile` hands
# files out in collection order, which is the alphabet's: its last big file
# (`test_zaya.py`, 140-230 s) then runs while five workers have nothing left.
# With these in front the run ends on files of a few seconds.
DEAR_FIRST = (
    "test_flash_attention.py", "test_paged_attention.py",
    "test_engine_tracing.py", "test_chip_compile_engines.py",
    "test_program_pins.py", "test_sdar.py", "test_laguna.py",
    "test_minicpm_sala.py", "test_moe.py", "test_prefill_plan.py",
    "test_zaya.py", "test_chip_compile_families.py", "test_gigachat.py",
    "test_program_scopes.py", "test_jamba.py", "test_kimi.py",
    "test_mellum.py", "test_chip_compile.py", "test_llm_serve.py",
    "test_gated_delta.py", "test_rotary.py")


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(DEAR_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    return (yield from _limited(item, "setup"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _limited(item, "call"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    return (yield from _limited(item, "teardown"))


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
    two whole runs of two, while the file passes alone. The module's shared
    engines (tests/_engines.py) are left first: they hold the programs."""
    yield
    close_all()
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


@pytest.fixture(scope="module")
def topo():
    """A `v5e:2x2` that is described, not attached, for the files that
    compile for it (tests/test_chip_compile*.py). Described inside a
    fixture, never at import: only one process may load the TPU library,
    and under xdist every worker imports every test file."""
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


@pytest.fixture(scope="module")
def shape_engine():
    """EngineConfig -> an `LLMEngine` whose parameters are shapes only,
    one a configuration: its stage's `program` builds the real programs,
    nothing runs."""
    import jax.numpy as jnp

    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.serve.llm.stage import init_params

    done = {}

    def get(cfg):
        engine = done.get(repr(cfg))
        if engine is None:
            engine = done[repr(cfg)] = LLMEngine(cfg, params={})
            stage = engine.compute
            stage.params = jax.eval_shape(lambda: init_params(
                stage.model, jnp.zeros((1, 8), jnp.int32),
                jax.random.PRNGKey(0)))
        return engine

    return get


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
