"""Compiled-graph (aDAG) tests.

Mirrors the reference's compiled-graph coverage (ref:
python/ray/dag/tests/experimental/test_accelerated_dag.py): build/execute
uncompiled, compile, linear + fan-out/fan-in shapes, pipelined executes,
error propagation, teardown, and the headline property — compiled
execution beats the per-call actor path on throughput.
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.dag import InputNode, MultiOutputNode


@ray_tpu.remote
class Adder:
    def __init__(self, inc):
        self.inc = inc

    def add(self, x):
        return x + self.inc

    def boom(self, x):
        raise ValueError("kaboom")

    def combine(self, a, b):
        return a + b

    def echo_array(self, arr):
        return arr * 2


def test_uncompiled_dag_execute(shared_cluster):
    a = Adder.remote(1)
    b = Adder.remote(10)
    with InputNode() as inp:
        dag = b.add.bind(a.add.bind(inp))
    ref = dag.execute(5)
    assert ray_tpu.get(ref) == 16


def test_compiled_linear_chain(shared_cluster):
    a = Adder.remote(1)
    b = Adder.remote(10)
    with InputNode() as inp:
        dag = b.add.bind(a.add.bind(inp))
    cdag = dag.experimental_compile()
    try:
        for i in range(20):
            assert cdag.execute(i).get() == i + 11
    finally:
        cdag.teardown()


def test_compiled_fan_out_fan_in(shared_cluster):
    a = Adder.remote(1)
    b = Adder.remote(100)
    c = Adder.remote(0)
    with InputNode() as inp:
        x = a.add.bind(inp)
        y = b.add.bind(inp)
        dag = c.combine.bind(x, y)
    cdag = dag.experimental_compile()
    try:
        assert cdag.execute(5).get() == (5 + 1) + (5 + 100)
        assert cdag.execute(0).get() == 101
    finally:
        cdag.teardown()


def test_compiled_multi_output(shared_cluster):
    a = Adder.remote(1)
    b = Adder.remote(2)
    with InputNode() as inp:
        dag = MultiOutputNode([a.add.bind(inp), b.add.bind(inp)])
    cdag = dag.experimental_compile()
    try:
        assert cdag.execute(10).get() == [11, 12]
    finally:
        cdag.teardown()


def test_compiled_pipelined_executes(shared_cluster):
    a = Adder.remote(1)
    with InputNode() as inp:
        dag = a.add.bind(inp)
    cdag = dag.experimental_compile()
    try:
        refs = [cdag.execute(i) for i in range(2)]  # in flight together
        assert [r.get() for r in refs] == [1, 2]
        # out-of-order get is buffered
        r1 = cdag.execute(100)
        r2 = cdag.execute(200)
        assert r2.get() == 201
        assert r1.get() == 101
    finally:
        cdag.teardown()


def test_compiled_numpy_payload(shared_cluster):
    a = Adder.remote(0)
    with InputNode() as inp:
        dag = a.echo_array.bind(inp)
    cdag = dag.experimental_compile()
    try:
        arr = np.arange(100_000, dtype=np.float32)
        out = cdag.execute(arr).get()
        np.testing.assert_array_equal(out, arr * 2)
    finally:
        cdag.teardown()


def test_compiled_error_propagates_and_recovers(shared_cluster):
    a = Adder.remote(1)
    b = Adder.remote(10)
    with InputNode() as inp:
        dag = b.add.bind(a.boom.bind(inp))
    cdag = dag.experimental_compile()
    try:
        with pytest.raises(RuntimeError, match="kaboom"):
            cdag.execute(1).get()
        # later executes still fail cleanly (channels stay aligned)
        with pytest.raises(RuntimeError, match="kaboom"):
            cdag.execute(2).get()
    finally:
        cdag.teardown()


def test_compiled_beats_per_call_path(shared_cluster):
    """The aDAG's reason to exist: a compiled graph's steady state moves
    channel frames only — ZERO control-plane RPCs an execute, where the
    per-call path submits a task per hop. (Counted, not timed: two wall
    times on a shared CPU say little about either path.)"""
    from ray_tpu.runtime import rpc

    a = Adder.remote(1)
    b = Adder.remote(1)
    n = 50
    ambient = {"heartbeat", "report_metrics", "view_update"}

    def sends_during(fn):
        before = rpc.transport_sends()
        out = [fn(i) for i in range(n)]
        after = rpc.transport_sends()
        return out, {k: after[k] - before.get(k, 0) for k in after
                     if after[k] != before.get(k, 0) and k not in ambient}

    # warm both paths
    ray_tpu.get(b.add.remote(ray_tpu.get(a.add.remote(0))))
    want, per_call = sends_during(
        lambda i: ray_tpu.get(b.add.remote(ray_tpu.get(a.add.remote(i)))))
    assert per_call.get("actor_call", 0) >= 2 * n, per_call

    with InputNode() as inp:
        dag = b.add.bind(a.add.bind(inp))
    cdag = dag.experimental_compile()
    try:
        cdag.execute(0).get()  # warm
        got, compiled = sends_during(lambda i: cdag.execute(i).get())
    finally:
        cdag.teardown()
    assert got == want == [i + 2 for i in range(n)]
    assert not compiled, f"steady-state execute() issued RPCs: {compiled}"


def test_channel_basics(shared_cluster):
    from ray_tpu.runtime.channel import Channel, ChannelClosed
    from ray_tpu.runtime.core import get_core

    session = get_core().session_name
    ch = Channel(session, "test-basic", item_size=1024, num_slots=2)
    ch.write({"a": 1})
    ch.write([1, 2])
    assert ch.read() == {"a": 1}
    assert ch.read() == [1, 2]
    ch.write(None, sentinel=True)
    with pytest.raises(ChannelClosed):
        ch.read()
    with pytest.raises(TimeoutError):
        ch.read(timeout=0.05)
    ch.unlink()


# ------------------------------------------------- collectives (aDAG)

@ray_tpu.remote
class GradWorker:
    """A participant in collective-in-DAG tests (ref:
    test_accelerated_dag's AllReduce coverage via collective_node.py)."""

    def __init__(self, delay=0.0):
        self.delay = delay
        self.times = {}

    def produce(self, x):
        if self.delay:
            time.sleep(self.delay)
        self.times["produce_done"] = time.monotonic()
        return np.asarray(x, np.float64) * 1.0

    def produce2(self, x):
        return np.asarray(x, np.float64) + 100.0

    def indep(self, x):
        self.times["indep_done"] = time.monotonic()
        return x * 0

    def consume(self, reduced, other):
        return (reduced, other)

    def get_times(self):
        return dict(self.times)


def test_collective_allreduce_sum(shared_cluster):
    from ray_tpu.dag import allreduce

    a, b = GradWorker.remote(), GradWorker.remote()
    with InputNode() as inp:
        ga = a.produce.bind(inp)
        gb = b.produce2.bind(inp)
        ra, rb = allreduce.bind([ga, gb], op="sum")
        dag = MultiOutputNode([ra, rb]).experimental_compile()
    try:
        for k in range(3):
            va, vb = dag.execute(np.arange(4.0) + k).get()
            want = (np.arange(4.0) + k) + ((np.arange(4.0) + k) + 100.0)
            np.testing.assert_allclose(va, want)
            np.testing.assert_allclose(vb, want)
    finally:
        dag.teardown()


def test_collective_allreduce_mean_uncompiled(shared_cluster):
    from ray_tpu.dag import allreduce

    a, b = GradWorker.remote(), GradWorker.remote()
    with InputNode() as inp:
        ga = a.produce.bind(inp)
        gb = b.produce2.bind(inp)
        ra, rb = allreduce.bind([ga, gb], op="mean")
        dag = MultiOutputNode([ra, rb])
    refs = dag.execute(np.zeros(3))
    va, vb = ray_tpu.get(refs)
    np.testing.assert_allclose(va, np.full(3, 50.0))
    np.testing.assert_allclose(vb, np.full(3, 50.0))


def test_collective_result_feeds_downstream(shared_cluster):
    from ray_tpu.dag import allreduce

    a, b = GradWorker.remote(), GradWorker.remote()
    with InputNode() as inp:
        ga = a.produce.bind(inp)
        gb = b.produce2.bind(inp)
        ra, rb = allreduce.bind([ga, gb], op="sum")
        out = b.consume.bind(rb, b.indep.bind(inp))
        dag = MultiOutputNode([ra, out]).experimental_compile()
    try:
        va, (reduced, zeros) = dag.execute(np.ones(2)).get()
        np.testing.assert_allclose(reduced, np.full(2, 102.0))
        np.testing.assert_allclose(va, reduced)
        np.testing.assert_allclose(zeros, 0 * np.ones(2))
    finally:
        dag.teardown()


def test_collective_overlap_schedule(shared_cluster):
    """Compute/comm overlap: ops independent of the collective run
    while a slow peer's contribution is still in flight (ref:
    dag_node_operation.py's overlapped schedule). The non-leader's
    `indep` must complete BEFORE the delayed leader finishes producing
    its contribution."""
    from ray_tpu.dag import allreduce

    slow, fast = GradWorker.remote(delay=0.6), GradWorker.remote()
    with InputNode() as inp:
        ga = slow.produce.bind(inp)
        gb = fast.produce.bind(inp)
        ra, rb = allreduce.bind([ga, gb], op="sum")
        out = fast.consume.bind(rb, fast.indep.bind(inp))
        dag = MultiOutputNode([ra, out]).experimental_compile()
    try:
        dag.execute(np.ones(2)).get()
        t_slow = ray_tpu.get(slow.get_times.remote())
        t_fast = ray_tpu.get(fast.get_times.remote())
        assert t_fast["indep_done"] < t_slow["produce_done"], (
            "indep ran only after the collective completed: the recv was "
            "not scheduled late")
    finally:
        dag.teardown()


def test_collective_error_propagates(shared_cluster):
    from ray_tpu.dag import allreduce

    a, b = GradWorker.remote(), Adder.remote(1)
    with InputNode() as inp:
        ga = a.produce.bind(inp)
        gb = b.boom.bind(inp)
        ra, rb = allreduce.bind([ga, gb], op="sum")
        dag = MultiOutputNode([ra, rb]).experimental_compile()
    try:
        with pytest.raises(RuntimeError, match="kaboom"):
            dag.execute(np.ones(2)).get()
        # the DAG survives: the next execution still works... with the
        # same failing op it fails again, per-execution semantics
        with pytest.raises(RuntimeError, match="kaboom"):
            dag.execute(np.ones(2)).get()
    finally:
        dag.teardown()


def test_collective_validation(shared_cluster):
    from ray_tpu.dag import allreduce

    a = GradWorker.remote()
    with InputNode() as inp:
        ga = a.produce.bind(inp)
        gb = a.produce2.bind(inp)
        with pytest.raises(ValueError, match="distinct actors"):
            allreduce.bind([ga, gb])
        with pytest.raises(ValueError, match="op must be"):
            allreduce.bind([ga], op="xor")


def test_collective_realigns_after_error(shared_cluster):
    """A failed execution must not desynchronize the collective's
    channels: the NEXT execution returns correct values, not stale
    error markers (one-item-per-iteration invariant incl. skipped
    recv/reduce inputs)."""
    from ray_tpu.dag import allreduce

    @ray_tpu.remote
    class Maybe:
        def maybe_boom(self, x):
            if np.any(np.asarray(x) < 0):
                raise ValueError("negative grad")
            return np.asarray(x, np.float64)

        def produce(self, x):
            return np.asarray(x, np.float64) * 2

    a, b = Maybe.remote(), Maybe.remote()
    with InputNode() as inp:
        ga = a.produce.bind(inp)
        gb = b.maybe_boom.bind(inp)
        ra, rb = allreduce.bind([ga, gb], op="sum")
        dag = MultiOutputNode([ra, rb]).experimental_compile()
    try:
        with pytest.raises(RuntimeError, match="negative grad"):
            dag.execute(-np.ones(2)).get()
        va, vb = dag.execute(np.ones(2)).get()
        np.testing.assert_allclose(va, np.full(2, 3.0))
        np.testing.assert_allclose(vb, np.full(2, 3.0))
        # and again after two interleaved failures
        with pytest.raises(RuntimeError, match="negative grad"):
            dag.execute(-np.ones(2)).get()
        va, vb = dag.execute(np.ones(2) * 2).get()
        np.testing.assert_allclose(va, np.full(2, 6.0))
    finally:
        dag.teardown()
