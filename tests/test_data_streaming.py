"""Streaming data plane: pull-based operator pipeline + streaming_split.

What must hold (ISSUE 11 acceptance):
- time-to-first-batch on a slow many-block pipeline is a small multiple
  of ONE task's latency, far ahead of full materialization;
- a slow consumer backpressures the pipeline: in-flight blocks stay
  queue-depth-proportional, never dataset-proportional;
- streamed rows match the materialized path exactly;
- streaming_split serves n concurrent consumers disjoint exactly-once
  shards with per-epoch barriers, and a consumer killed mid-epoch (via
  the PR-10 fault plane, runtime-injected into the LIVE worker) has its
  blocks redistributed so every row still reaches a survivor.
"""

import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rd
from ray_tpu.data.plan import compile_plan
from ray_tpu.data.streaming import (StreamingTopology, split_iterators,
                                    stream_refs)

pytestmark = pytest.mark.stream


@pytest.fixture(autouse=True)
def _cluster(shared_cluster):
    yield shared_cluster


def _slow_map(delay):
    def fn(batch):
        time.sleep(delay)
        return batch

    return fn


# ------------------------------------------------------------ the pipeline
@pytest.mark.slow
def test_ttfb_streams_far_ahead_of_full_drain():
    """>=100-block pipeline with a non-trivial map: the first batch must
    arrive >=5x earlier than full materialization (the streamed pump
    yields block 1 while upstream tasks for block 100 still run)."""
    n_blocks, delay = 100, 0.15

    def build():
        return rd.range(400, parallelism=n_blocks).map_batches(
            _slow_map(delay))

    rd.range(16, parallelism=8).count()  # warm the worker pool first:
    # TTFB measures the PIPELINE's pickup, not cold worker spawns

    t0 = time.perf_counter()
    it = build().iter_batches(batch_size=4, batch_format="numpy")
    first = next(it)
    ttfb = time.perf_counter() - t0
    rows = len(first["id"]) + sum(len(b["id"]) for b in it)
    assert rows == 400

    t0 = time.perf_counter()
    mat = build().materialize()
    drain = time.perf_counter() - t0
    assert sum(1 for _ in mat.iter_rows()) == 400
    assert drain / ttfb >= 5.0, (
        f"ttfb={ttfb * 1e3:.0f}ms vs full drain={drain * 1e3:.0f}ms — "
        f"streaming must beat materialization by >=5x")


def test_backpressure_bounds_in_flight_blocks():
    """A deliberately slow consumer must park the pipeline: peak
    in-flight blocks stays proportional to the per-operator queue
    depths (here 2 ops x 2 x depth), not the 60-block dataset, and the
    store never holds more than that many blocks' bytes."""
    from ray_tpu.data.executor import _store_capacity, _store_used_fraction

    depth = 2
    n_blocks = 60
    # ~256KB blocks: big enough to live in the shm pool, so store
    # accounting sees them
    ds = rd.range_tensor(n_blocks * 40, shape=(800,),
                         parallelism=n_blocks).map_batches(_slow_map(0.002))
    stages = compile_plan(ds._plan)
    topo = StreamingTopology(stages, queue_depth=depth)
    cap = _store_capacity()
    base_frac = _store_used_fraction()
    rows = 0
    while not topo.done():
        for ref in topo.advance(wait_s=60):
            block = ray_tpu.get(ref, timeout=60)
            rows += len(block["data"])
            time.sleep(0.02)  # slow consumer
    assert rows == n_blocks * 40
    bound = 2 * 2 * depth + 2  # ops x (inbox + in-flight/out) x depth
    assert topo.stats["peak_in_flight_blocks"] <= bound, topo.stats
    if cap:
        block_bytes = 800 * 40 * 8
        peak_extra = (topo.stats["peak_store_frac"] - base_frac) * cap
        assert peak_extra <= (bound + 4) * block_bytes, (
            f"store grew by {peak_extra / 1e6:.1f}MB — not queue-bounded")


def test_streamed_rows_match_materialized_exactly():
    def build():
        return (rd.range(120, parallelism=8)
                .map(lambda r: {"id": r["id"], "v": r["id"] * 3})
                .filter(lambda r: r["id"] % 2 == 0)
                .flat_map(lambda r: [r, {"id": r["id"], "v": -r["v"]}]))

    streamed = [(r["id"], r["v"]) for r in build().iter_rows()]
    mat = [(r["id"], r["v"]) for r in build().materialize().iter_rows()]
    assert streamed == mat  # exact order, not just content


def test_barrier_stages_stream_through():
    """A shuffle is a genuine barrier, but the map prefix streams into
    it and the suffix streams out — results must match the seeded
    materialized path exactly."""
    def build():
        return (rd.range(90, parallelism=6)
                .map(lambda r: {"id": r["id"]})
                .random_shuffle(seed=11)
                .map(lambda r: {"id": r["id"] + 1}))

    streamed = [r["id"] for r in build().iter_rows()]
    mat = [r["id"] for r in build().materialize().iter_rows()]
    assert streamed == mat
    assert sorted(streamed) == list(range(1, 91))


def test_limit_short_circuits_upstream():
    """limit(n) closes the upstream operators once satisfied: wall time
    is a few tasks', not the whole 100-block pipeline's."""
    rd.range(8, parallelism=4).count()  # warm the pool: the wall-time
    # bound measures the cutoff, not cold worker spawns
    ds = (rd.range(1000, parallelism=100)
          .map_batches(_slow_map(0.05)).limit(30))
    t0 = time.perf_counter()
    rows = [r["id"] for r in ds.iter_rows()]
    wall = time.perf_counter() - t0
    assert rows == list(range(30))
    # full drain would be ~100 tasks x 50ms / parallelism; the cutoff
    # must finish in a small fraction of that
    assert wall < 2.0, f"limit did not short-circuit: {wall:.1f}s"


def test_stream_stats_recorded():
    ds = rd.range(40, parallelism=4).map(lambda r: r)
    list(ds.iter_rows())
    stats = ds._last_stream_stats
    assert stats and stats["blocks_out"] == 4
    assert stats["tasks_launched"] >= 8  # 4 reads + 4 maps


# --------------------------------------------------------- streaming_split
def _consume_all(iterator, out, pace=0.0):
    got = []
    for row in iterator.iter_rows():
        got.append(row["id"])
        if pace:
            time.sleep(pace)
    out[iterator.rank] = got


def test_streaming_split_disjoint_exactly_once():
    its = rd.range(200, parallelism=10).streaming_split(2)
    out = {}
    threads = [threading.Thread(target=_consume_all,
                                args=(its[r], out, 0.005), daemon=True)
               for r in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert sorted(out[0] + out[1]) == list(range(200))
    assert not set(out[0]) & set(out[1])
    assert out[0] and out[1], "both consumers must participate"


def test_streaming_split_equal_rows():
    """equal=True splits EVERY block evenly: shard sizes differ by at
    most one row per block."""
    n_blocks = 10
    its = rd.range(105, parallelism=n_blocks).streaming_split(
        2, equal=True)
    out = {}
    threads = [threading.Thread(target=_consume_all,
                                args=(its[r], out), daemon=True)
               for r in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert sorted(out[0] + out[1]) == list(range(105))
    assert abs(len(out[0]) - len(out[1])) <= n_blocks


def test_streaming_split_epoch_barrier():
    """An epoch opens only when EVERY consumer asks for it; later epochs
    replay the cached blocks without re-executing the plan."""
    its = split_iterators(rd.range(40, parallelism=2), 2)
    coord = its[0].coordinator
    ray_tpu.get(coord.register.remote(0, 2), timeout=30)
    ray_tpu.get(coord.register.remote(1, 2), timeout=30)
    # consumer 0 alone cannot open the epoch
    d = ray_tpu.get(coord.begin_epoch.remote(0), timeout=30)
    assert d == {"wait": True}
    d = ray_tpu.get(coord.begin_epoch.remote(1), timeout=30)
    assert d == {"epoch": 0}
    assert ray_tpu.get(coord.begin_epoch.remote(0),
                       timeout=30) == {"epoch": 0}

    def drain(rank):
        got = 0
        while True:
            d = ray_tpu.get(coord.next_block.remote(rank, 0), timeout=30)
            if d.get("eof"):
                return got
            if d.get("ref") is not None:
                got += 1
                continue
            time.sleep(0.02)

    # interleaved drains complete via the tail rendezvous
    out = {}
    threads = [threading.Thread(
        target=lambda r: out.__setitem__(r, drain(r)), args=(r,),
        daemon=True)
        for r in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert out[0] + out[1] == 2  # both blocks served exactly once
    # next epoch: barrier again, blocks replayed from cache
    assert ray_tpu.get(coord.begin_epoch.remote(0),
                       timeout=30) == {"wait": True}
    assert ray_tpu.get(coord.begin_epoch.remote(1),
                       timeout=30) == {"epoch": 1}
    desc = ray_tpu.get(coord.describe.remote(), timeout=30)
    assert desc["cache_blocks"] == 2 and desc["cache_done"]


def test_streaming_split_consumer_killed_mid_epoch(shared_cluster):
    """The chaos drill: one of two consumers is killed MID-EPOCH by a
    PR-10 fault rule injected at runtime into its live worker process
    (kill_at on the data.split_pull syncpoint -> exit 43). Every block
    it was handed must be redistributed: the survivor alone covers the
    whole dataset exactly once, within the same epoch."""
    session = ray_tpu.init(ignore_reinit_error=True)
    its = split_iterators(rd.range(300, parallelism=15), 2,
                          consumer_timeout_s=3.0)

    @ray_tpu.remote
    class Consumer:
        def wid(self):
            from ray_tpu.runtime.core import get_core

            return get_core().worker_id.hex()

        def consume(self, it, pace=0.05):
            from ray_tpu.data.block import BlockAccessor

            got = []
            for ref in it.iter_block_refs():
                block = ray_tpu.get(ref, timeout=60)
                got.extend(r["id"] for r in
                           BlockAccessor(block).iter_rows())
                time.sleep(pace)
            return got

    survivor, victim = Consumer.remote(), Consumer.remote()
    victim_wid = ray_tpu.get(victim.wid.remote(), timeout=30)
    r_victim = victim.consume.remote(its[1])
    time.sleep(0.3)  # let the victim enter the epoch and take blocks
    r_survivor = survivor.consume.remote(its[0])
    time.sleep(0.3)
    # runtime-injected kill: the rule reaches the LIVE worker via the
    # nodelet's fault_inject forwarding (no respawn, no RTPU_FAULTS env)
    session.core.controller.call(
        "fault_inject",
        spec=f"split_kill:kill_at(data.split_pull,nth=2)@{victim_wid}",
        node_id="*")
    try:
        got = ray_tpu.get(r_survivor, timeout=120)
        stats = ray_tpu.get(its[0].coordinator.describe.remote(),
                            timeout=30)
        assert sorted(got) == list(range(300)), (
            f"survivor covered {len(got)} rows "
            f"({len(set(got))} unique) of 300")
        assert stats["dead"] == [1], stats
        assert stats["epoch"] == 0, (
            "must converge WITHIN the epoch, not via a restart")
        with pytest.raises(Exception):
            ray_tpu.get(r_victim, timeout=10)  # the victim really died
    finally:
        session.core.controller.call("fault_inject", clear="*",
                                     node_id="*")


def test_streaming_split_early_exit_consumer_is_not_evicted():
    """A consumer that BREAKS out of its epoch early (steps_per_epoch
    cutoff — the normal training pattern) must not be evicted: the
    drain-on-close signal finishes its epoch, peers complete without
    redistribution, and BOTH ranks proceed into the next epoch."""
    its = split_iterators(rd.range(120, parallelism=12), 2,
                          consumer_timeout_s=5.0)
    out = {0: [], 1: []}
    # blocks come off ONE shared queue, whoever asks first: the peer
    # holds after its first row until the early-exiter has left the
    # epoch, or on a loaded box it could drain the queue before the
    # early-exiter's second pull and leave it under its cutoff
    left = [threading.Event(), threading.Event()]

    def run(rank, cutoff):
        for epoch in range(2):
            got = []
            for row in its[rank].iter_rows():
                got.append(row["id"])
                if cutoff and len(got) >= cutoff:
                    break  # early exit mid-epoch
                if not cutoff and len(got) == 1:
                    assert left[epoch].wait(timeout=4.0)
            out[rank].append(got)
            if cutoff:
                left[epoch].set()

    threads = [threading.Thread(target=run, args=(0, 15), daemon=True),
               threading.Thread(target=run, args=(1, 0), daemon=True)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=90)
    stats = its[0].stats()
    assert stats["dead"] == [], stats  # the early-exiter stayed alive
    assert stats["epoch"] == 1
    for epoch in range(2):
        # no duplicate delivery: the early-exiter's consumed rows are
        # NOT re-served to its peer
        assert not set(out[0][epoch]) & set(out[1][epoch]), epoch
        assert len(out[0][epoch]) == 15


def test_streaming_split_equal_early_exit_respills_backlog():
    """equal=True + early exit: the finished rank's UNDELIVERED slice
    backlog must respill to the active peer (left queued it would
    exhaust the refill cap and wedge the epoch forever) — the peer
    receives every row the early-exiter didn't consume."""
    its = split_iterators(rd.range(200, parallelism=20), 2, equal=True,
                          consumer_timeout_s=5.0)
    out = {0: [], 1: []}

    def run(rank, cutoff):
        got = []
        for row in its[rank].iter_rows():
            got.append(row["id"])
            if cutoff and len(got) >= cutoff:
                break
        out[rank] = got

    threads = [threading.Thread(target=run, args=(0, 10), daemon=True),
               threading.Thread(target=run, args=(1, 0), daemon=True)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads), "epoch wedged"
    stats = its[0].stats()
    assert stats["dead"] == [], stats  # early exit is not death
    assert len(out[0]) == 10
    # the peer got everything except the 10 rows rank 0 consumed
    assert not set(out[0]) & set(out[1])
    assert len(out[0]) + len(out[1]) == 200


def test_streaming_split_evicted_consumer_rejoins_next_epoch():
    """Eviction is an epoch-level verdict: an evicted-but-alive rank
    re-admits at the next barrier instead of crashing forever."""
    its = split_iterators(rd.range(40, parallelism=4), 2,
                          consumer_timeout_s=2.0)
    coord = its[0].coordinator
    ray_tpu.get(coord.register.remote(0, 2), timeout=30)
    ray_tpu.get(coord.register.remote(1, 2), timeout=30)
    ray_tpu.get(coord.begin_epoch.remote(0), timeout=30)
    assert ray_tpu.get(coord.begin_epoch.remote(1),
                       timeout=30) == {"epoch": 0}
    ray_tpu.get(coord.mark_dead.remote(1), timeout=30)
    # rank 0 drains the whole epoch alone (redistribution)
    served = 0
    while True:
        d = ray_tpu.get(coord.next_block.remote(0, 0), timeout=30)
        if d.get("eof"):
            break
        if d.get("ref") is not None:
            served += 1
            continue
        time.sleep(0.02)
    assert served == 4
    # the dead rank asks for the next epoch -> revived at the boundary
    assert ray_tpu.get(coord.begin_epoch.remote(1),
                       timeout=30) == {"wait": True}
    assert ray_tpu.get(coord.begin_epoch.remote(0),
                       timeout=30) == {"epoch": 1}
    desc = ray_tpu.get(coord.describe.remote(), timeout=30)
    assert desc["dead"] == [] and sorted(desc["members"]) == [0, 1]


def test_streaming_split_late_registrant_does_not_reset_generation():
    """A peer that registers AFTER the barrier timeout evicted it (slow
    spawn / long compile) is a late arrival, not a restart: it rejoins
    at the next epoch boundary, and the survivor mid-epoch is NOT
    evicted by a generation reset."""
    its = split_iterators(rd.range(40, parallelism=4), 2,
                          consumer_timeout_s=1.0)
    coord = its[0].coordinator
    ray_tpu.get(coord.register.remote(0, 2), timeout=30)
    assert ray_tpu.get(coord.begin_epoch.remote(0),
                       timeout=30) == {"wait": True}
    time.sleep(1.2)  # rank 1 misses the barrier window
    assert ray_tpu.get(coord.begin_epoch.remote(0),
                       timeout=30) == {"epoch": 0}
    d = ray_tpu.get(coord.next_block.remote(0, 0), timeout=30)
    assert d.get("ref") is not None
    # the late peer registers mid-epoch: NO reset, survivor unaffected
    ray_tpu.get(coord.register.remote(1, 2), timeout=30)
    served = 1
    while True:
        d = ray_tpu.get(coord.next_block.remote(0, 0), timeout=30)
        assert not d.get("evicted"), "survivor was reset mid-epoch"
        if d.get("eof"):
            break
        if d.get("ref") is not None:
            served += 1
            continue
        time.sleep(0.02)
    assert served == 4  # the whole epoch stayed with the survivor
    # both enter the next epoch together (rank 1 revived at the boundary)
    ray_tpu.get(coord.begin_epoch.remote(1), timeout=30)
    assert ray_tpu.get(coord.begin_epoch.remote(0),
                       timeout=30) == {"epoch": 1}
    desc = ray_tpu.get(coord.describe.remote(), timeout=30)
    assert desc["dead"] == [] and sorted(desc["members"]) == [0, 1]


def test_streaming_split_seeds_from_cached_refs():
    """streaming_split on an already-materialized dataset serves the
    CACHED blocks — the plan must not re-execute inside the
    coordinator."""
    calls = []

    def counting(b):
        calls.append(1)
        return b

    ds = rd.range(40, parallelism=4).map_batches(counting)
    assert ds.count() == 40  # executes once, caches refs
    its = ds.streaming_split(2)
    out = {}
    threads = [threading.Thread(target=_consume_all,
                                args=(its[r], out), daemon=True)
               for r in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert sorted(out[0] + out[1]) == list(range(40))
    desc = its[0].stats()
    assert desc["cache_blocks"] == 4 and desc["cache_done"]


def test_streaming_split_equal_consumer_death_mid_stream(shared_cluster):
    """equal=True death drill: the victim's per-block slices backlog in
    its queue while the source is still producing — the starved
    survivor must evict it MID-STREAM (not only at the drained tail)
    and receive every requeued slice: full coverage on the survivor."""
    its = split_iterators(rd.range(240, parallelism=12), 2, equal=True,
                          consumer_timeout_s=3.0)

    @ray_tpu.remote
    class Consumer:
        def consume(self, it, pace=0.05, die_after=0):
            from ray_tpu.data.block import BlockAccessor

            got = []
            for i, ref in enumerate(it.iter_block_refs()):
                block = ray_tpu.get(ref, timeout=60)
                got.extend(r["id"] for r in
                           BlockAccessor(block).iter_rows())
                if die_after and i + 1 >= die_after:
                    import os

                    os._exit(43)
                time.sleep(pace)
            return got

    survivor, victim = Consumer.remote(), Consumer.remote()
    r_victim = victim.consume.remote(its[1], die_after=2)
    time.sleep(0.2)
    r_survivor = survivor.consume.remote(its[0])
    got = ray_tpu.get(r_survivor, timeout=120)
    stats = ray_tpu.get(its[0].coordinator.describe.remote(), timeout=30)
    assert sorted(got) == list(range(240)), (len(got), len(set(got)))
    assert stats["dead"] == [1], stats
    assert stats["epoch"] == 0
    with pytest.raises(Exception):
        ray_tpu.get(r_victim, timeout=10)


# ------------------------------------------------------------ train ingest
def test_trainer_streaming_ingest_two_workers(tmp_path):
    """streaming_split drives two concurrent Train workers to epoch
    completion with disjoint exactly-once row coverage, two epochs in
    lockstep (the trainer.py get_dataset_shard wiring)."""
    import json
    import os

    from ray_tpu import train

    outdir = str(tmp_path / "ids")
    os.makedirs(outdir, exist_ok=True)

    def loop(config):
        import json as _json
        import os as _os

        from ray_tpu import train as _train
        from ray_tpu.train.trainer import get_dataset_shard

        ctx = _train.get_context()
        shard = get_dataset_shard("train")
        per_epoch = []
        for epoch in range(2):
            ids = []
            for batch in shard.iter_batches(batch_size=16,
                                            batch_format="numpy"):
                ids.extend(int(x) for x in batch["id"])
            per_epoch.append(ids)
            _train.report({"epoch": epoch, "rows": len(ids)})
        with open(_os.path.join(config["out"],
                                f"rank{ctx.get_world_rank()}.json"),
                  "w") as f:
            _json.dump(per_epoch, f)

    ds = rd.range(200, parallelism=10).map(lambda r: {"id": r["id"]})
    trainer = train.JaxTrainer(
        loop, train_loop_config={"out": outdir},
        scaling_config=train.ScalingConfig(num_workers=2),
        run_config=train.RunConfig(name="stream_ingest",
                                   storage_path=str(tmp_path / "run")),
        datasets={"train": ds})
    result = trainer.fit()
    assert result.error is None, result.error
    with open(os.path.join(outdir, "rank0.json")) as f:
        r0 = json.load(f)
    with open(os.path.join(outdir, "rank1.json")) as f:
        r1 = json.load(f)
    for epoch in range(2):
        a, b = r0[epoch], r1[epoch]
        assert not set(a) & set(b), f"epoch {epoch}: overlapping shards"
        assert sorted(a + b) == list(range(200)), (
            f"epoch {epoch}: coverage hole")
