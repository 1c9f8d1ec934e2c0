"""rtpulint + rtpuproto: the repo's static-analysis tier, wired into
tier-1.

Four layers:
1. analyzer self-tests — one fixture file per rule under
   tests/lint_fixtures/, where every line that must flag carries a
   trailing ``# EXPECT[RTPUxxx]`` marker; flagging, non-flagging and
   pragma-suppression variants live side by side. Per-file rules
   (RTPU001-007) run through analyze_file; whole-program protocol rules
   (RTPU101-106, tools/rtpulint/proto.py) run through run_proto with
   the fixture as its own mini protocol definition;
2. the tier-1 gates — zero unsuppressed per-file findings over the
   WHOLE package, zero unsuppressed protocol findings over the package
   + tests + benchmarks, every pragma carrying a reason, both passes
   fast enough for the 2-vCPU box, and the proto pass proven
   import-free (it never imports ray_tpu — hermetic collection);
3. ground-truth checks that the extracted RPC graph contains edges we
   know exist (a silently-empty model would make the gate vacuous);
4. regression tests for the real defects the analyzers surfaced, each
   named for the rule that caught it.
"""

import asyncio
import json
import os
import re
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")
sys.path.insert(0, REPO)

from tools.rtpulint import RULES, analyze_file, render_json, run  # noqa: E402
from tools.rtpulint.proto import (ProtoModel, _scan_files,  # noqa: E402
                                  default_aux_paths, run_proto)

_EXPECT_RE = re.compile(r"#\s*EXPECT\[(RTPU\d{3})\]")


def _expected_findings(path):
    out = []
    with open(path) as fh:
        for lineno, line in enumerate(fh, start=1):
            for m in _EXPECT_RE.finditer(line):
                out.append((lineno, m.group(1)))
    return sorted(out)


# ------------------------------------------------------------ rule self-tests
@pytest.mark.parametrize("rule", ["RTPU001", "RTPU002", "RTPU003",
                                  "RTPU004", "RTPU005", "RTPU006",
                                  "RTPU007"])
def test_rule_fixture(rule):
    """Each rule's fixture flags EXACTLY its EXPECT-marked lines (so both
    false negatives and false positives fail), and its pragma'd variant
    is suppressed with the recorded reason."""
    path = os.path.join(FIXTURES, rule.lower() + ".py")
    findings = analyze_file(path)
    assert not [f for f in findings if f.rule == "RTPU000"], \
        "fixture pragmas must be well-formed"
    got = sorted((f.line, f.rule) for f in findings if not f.suppressed)
    assert got == _expected_findings(path), (
        f"{rule}: analyzer findings diverge from the fixture's EXPECT "
        f"markers: {got}")
    suppressed = [f for f in findings if f.suppressed and f.rule == rule]
    assert suppressed, f"{rule}: fixture must exercise pragma suppression"
    for f in suppressed:
        assert f.reason and f.reason.strip(), \
            "suppression must record a reason"


def test_pragma_without_reason_is_flagged(tmp_path):
    src = ("import time\n"
           "async def f():\n"
           "    time.sleep(1)  # rtpulint: ignore[RTPU001]\n")
    p = tmp_path / "noreason.py"
    p.write_text(src)
    findings = analyze_file(str(p))
    rules = {f.rule for f in findings if not f.suppressed}
    # the reasonless pragma does NOT suppress, and is itself reported
    assert "RTPU000" in rules and "RTPU001" in rules


def test_pragma_on_line_above(tmp_path):
    src = ("import time\n"
           "async def f():\n"
           "    # rtpulint: ignore[RTPU001] — pragma above a multi-line statement\n"
           "    time.sleep(\n"
           "        1)\n")
    p = tmp_path / "above.py"
    p.write_text(src)
    findings = analyze_file(str(p))
    assert all(f.suppressed for f in findings), findings


def test_json_output_shape(tmp_path):
    p = tmp_path / "j.py"
    p.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    findings, n_files = run([str(p)])
    doc = json.loads(render_json(findings, n_files))
    assert doc["version"] == 1
    assert doc["files_scanned"] == 1
    assert doc["unsuppressed"] == 1
    assert doc["counts"] == {"RTPU001": 1}
    (f,) = doc["findings"]
    assert {"path", "line", "col", "rule", "severity", "message",
            "suppressed", "reason"} <= set(f)
    assert f["rule"] == "RTPU001" and f["severity"] == "error"
    assert set(doc["rules"]) == set(RULES)


def test_cli_exit_codes(tmp_path):
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-m", "tools.rtpulint",
                        str(dirty), "--json"],
                       capture_output=True, text=True, cwd=REPO, env=env)
    assert r.returncode == 1
    assert json.loads(r.stdout)["unsuppressed"] == 1
    r = subprocess.run([sys.executable, "-m", "tools.rtpulint",
                        str(clean)],
                       capture_output=True, text=True, cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr


# ------------------------------------------------------------ tier-1 gate
# Scanned paths. PR 7 gated runtime+serve; PR 8 added dag; the client
# link and the data package joined with the fault-plane PR; train+tune
# with the streaming-data-plane PR; the protocol-analyzer PR closed the
# gap — the WHOLE package is gated (autoscaler/rllib/util/ops/models
# and the root modules included).
def test_whole_package_is_clean():
    """The acceptance gate: zero unsuppressed findings over the entire
    package, and every suppression carries a recorded reason."""
    findings, n_files = run([os.path.join(REPO, "ray_tpu")])
    assert n_files > 120
    unsuppressed = [f for f in findings if not f.suppressed]
    assert not unsuppressed, "\n".join(
        f"{f.path}:{f.line}: {f.rule} {f.message}" for f in unsuppressed)
    for f in findings:
        assert f.reason and f.reason.strip(), f"{f.path}:{f.line}"


def test_analyzer_fast_enough_for_tier1():
    """Whole-package scan must stay well under the tier-1 budget on the
    2-vCPU box (~1.5s measured; 10s is the hard ceiling)."""
    t0 = time.perf_counter()
    run([os.path.join(REPO, "ray_tpu")])
    assert time.perf_counter() - t0 < 10.0


# ----------------------------------------------- protocol pass (rtpuproto)
@pytest.mark.parametrize("rule", ["RTPU101", "RTPU102", "RTPU103",
                                  "RTPU104", "RTPU105", "RTPU106"])
def test_proto_rule_fixture(rule):
    """Each protocol rule's fixture — its own mini protocol definition —
    flags EXACTLY its EXPECT-marked lines (false positives fail the gate
    exactly like false negatives), and its pragma'd variant is
    suppressed with the recorded reason."""
    path = os.path.join(FIXTURES, rule.lower() + ".py")
    findings, n_files = run_proto([path])
    assert n_files == 1
    got = sorted((f.line, f.rule) for f in findings if not f.suppressed)
    assert got == _expected_findings(path), (
        f"{rule}: proto findings diverge from the fixture's EXPECT "
        f"markers: {got}")
    suppressed = [f for f in findings if f.suppressed and f.rule == rule]
    assert suppressed, f"{rule}: fixture must exercise pragma suppression"
    for f in suppressed:
        assert f.reason and f.reason.strip(), \
            "suppression must record a reason"


def test_proto_gate_whole_program_clean():
    """The acceptance gate: zero unsuppressed RTPU101-106 findings over
    the package, with tests/ and benchmarks/ as auxiliary evidence, and
    a guard on the pass's cost A FILE (it parses ~240 modules once)."""
    pkg = os.path.join(REPO, "ray_tpu")
    # CPU time, not wall, and a file, not the whole pass: the guard is
    # about analyzer complexity (the pass is single-process and
    # compute-bound; 12-15 ms a file on an idle box, 21 in the driver's run of
    # PR 60 under six workers), and a bound on seconds failed two trees for
    # the box's load alone while every test file a PR adds is parsed here.
    t0 = time.process_time()
    findings, n_files = run_proto([pkg], aux_paths=default_aux_paths(pkg))
    elapsed = time.process_time() - t0
    assert n_files > 150  # package + tests + benchmarks
    unsuppressed = [f for f in findings if not f.suppressed]
    assert not unsuppressed, "\n".join(
        f"{f.path}:{f.line}: {f.rule} {f.message}" for f in unsuppressed)
    for f in findings:
        assert f.reason and f.reason.strip(), f"{f.path}:{f.line}"
    assert elapsed / n_files < 0.060, (
        f"proto pass took {elapsed:.1f}s over {n_files} files")


def test_proto_rpc_graph_ground_truth():
    """The extracted model must contain edges we KNOW exist — an
    extraction regression that empties the model would otherwise make
    the clean gate vacuous."""
    pkg = os.path.join(REPO, "ray_tpu")
    model = ProtoModel(_scan_files([pkg], [pkg]))

    def reg_files(method):
        return {os.path.basename(r.path)
                for r in model.registered_pkg.get(method, ())}

    def call_files(method):
        return {os.path.basename(c.path)
                for c in model.called.get(method, ())}

    # owner → nodelet batched submission edge
    assert "nodelet.py" in reg_files("submit_task_batch")
    assert "core.py" in call_files("submit_task_batch")
    # nodelet → controller liveness edge
    assert "controller.py" in reg_files("heartbeat")
    assert "nodelet.py" in call_files("heartbeat")
    # nodelet → worker dispatch edge rides the _notify_worker wrapper
    assert "worker.py" in reg_files("execute_task")
    assert "nodelet.py" in call_files("execute_task")
    # client → proxy edge through the client's _call wrapper
    assert "client_proxy.py" in reg_files("c_submit")
    assert "client.py" in call_files("c_submit")
    # classification sets parsed from rpc.py AND in sync with the
    # imported runtime registry (the AST view cannot silently drift)
    from ray_tpu.runtime import rpc as rpc_mod

    parsed = {name: {m for m, _l in entries}
              for name, (entries, _l, _p) in model.class_sets.items()}
    assert parsed["IDEMPOTENT_METHODS"] == set(rpc_mod.IDEMPOTENT_METHODS)
    assert parsed["UNBOUNDED_METHODS"] == set(rpc_mod.UNBOUNDED_METHODS)
    assert parsed["NON_IDEMPOTENT_METHODS"] == \
        set(rpc_mod.NON_IDEMPOTENT_METHODS)
    # the partition covers the whole registered surface, disjointly
    universe = set(model.registered_pkg)
    all_classified = (parsed["IDEMPOTENT_METHODS"]
                      | parsed["UNBOUNDED_METHODS"]
                      | parsed["NON_IDEMPOTENT_METHODS"])
    assert universe <= all_classified
    assert not (parsed["IDEMPOTENT_METHODS"]
                & parsed["NON_IDEMPOTENT_METHODS"])
    # fault-plane grammar facts made it in
    assert "nodelet.dispatch" in {sp for sp, _l, _p
                                  in model.syncpoints_decl}
    assert "worker_start_timeout_s" in {f for f, _l, _p
                                        in model.config_fields}


def test_proto_pass_never_imports_ray_tpu():
    """Deflake guard: the proto pass is pure AST — it must analyze the
    package WITHOUT importing it (hermetic tier-1 collection). A meta
    importer that explodes on any ray_tpu import proves it."""
    prog = (
        "import sys\n"
        "class _Tripwire:\n"
        "    def find_spec(self, name, path=None, target=None):\n"
        "        if name == 'ray_tpu' or name.startswith('ray_tpu.'):\n"
        "            raise AssertionError('proto pass imported ' + name)\n"
        "        return None\n"
        "sys.meta_path.insert(0, _Tripwire())\n"
        "from tools.rtpulint.proto import default_aux_paths, run_proto\n"
        "findings, n = run_proto([sys.argv[1]],\n"
        "                        aux_paths=default_aux_paths(sys.argv[1]))\n"
        "bad = sum(1 for f in findings if not f.suppressed)\n"
        "print('files', n, 'unsuppressed', bad)\n"
        "sys.exit(0 if bad == 0 and n > 150 else 3)\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, "-c", prog, os.path.join(REPO, "ray_tpu")],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


# ------------------------------------- regressions for defects it caught
def test_rtpu001_log_scan_runs_off_loop_and_keeps_semantics(tmp_path):
    """RTPU001 caught the nodelet's log monitor doing stat+read of up to
    256 files x 256KiB per tick ON the hub loop. The scan now runs on an
    executor thread via module function _scan_worker_logs; these are the
    tailing semantics that must survive the refactor."""
    from ray_tpu.runtime.nodelet import Nodelet, _scan_worker_logs

    log_dir = str(tmp_path)
    offsets = {}
    pa = os.path.join(log_dir, "worker-aaaa.log")

    # (a) whole \n-terminated lines only; the partial carries over
    with open(pa, "wb") as f:
        f.write(b"line1\nline2\npart")
    batch = _scan_worker_logs(log_dir, ["aaaa"], offsets, "n0")
    assert batch == [{"worker": "aaaa", "node_id": "n0",
                      "lines": ["line1", "line2"]}]
    with open(pa, "ab") as f:
        f.write(b"ial3\n")
    batch = _scan_worker_logs(log_dir, ["aaaa"], offsets, "n0")
    assert batch[0]["lines"] == ["partial3"]

    # (b) at most 200 lines per tick, offset advanced exactly past them
    pb = os.path.join(log_dir, "worker-bbbb.log")
    with open(pb, "wb") as f:
        f.write(b"".join(b"l%d\n" % i for i in range(250)))
    batch = _scan_worker_logs(log_dir, ["bbbb"], {}, "n0")
    assert len(batch[0]["lines"]) == 200
    offs = {}
    _scan_worker_logs(log_dir, ["bbbb"], offs, "n0")
    batch = _scan_worker_logs(log_dir, ["bbbb"], offs, "n0")
    assert batch[0]["lines"] == ["l%d" % i for i in range(200, 250)]

    # (c) a single unterminated line filling the window is force-consumed
    pc = os.path.join(log_dir, "worker-cccc.log")
    with open(pc, "wb") as f:
        f.write(b"x" * (256 << 10))
    offs = {}
    batch = _scan_worker_logs(log_dir, ["cccc"], offs, "n0")
    assert "unterminated line truncated" in batch[0]["lines"][0]
    assert offs[pc] == 256 << 10  # tail not wedged

    # (d) the loop itself must never touch files again: the analyzer
    # keeps _log_monitor_loop free of blocking I/O (RTPU001)
    import inspect

    from tools.rtpulint import analyze_source

    src = inspect.getsource(Nodelet)
    flagged = [f for f in analyze_source("class N:\n" + "".join(
        "    " + line + "\n" for line in src.splitlines()))
        if f.rule == "RTPU001" and not f.suppressed]
    assert not flagged, flagged


def test_rtpu003_spawn_logged_logs_and_counts():
    """spawn_logged is the RTPU003 fix: a failing fire-and-forget task
    is logged and counted instead of vanishing with its dropped handle."""
    from ray_tpu.runtime import procutil

    async def boom():
        raise ValueError("swallowed no more")

    async def driver():
        procutil.spawn_logged(boom(), name="test.boom")
        await asyncio.sleep(0.05)

    records = []

    class _Cap:
        def __init__(self):
            import logging

            self.h = logging.Handler()
            self.h.emit = lambda rec: records.append(rec)

    cap = _Cap()
    procutil.log.addHandler(cap.h)
    try:
        before = procutil.spawn_exception_counts().get("rtpu:test.boom", 0)
        asyncio.run(driver())
        after = procutil.spawn_exception_counts().get("rtpu:test.boom", 0)
    finally:
        procutil.log.removeHandler(cap.h)
    assert after == before + 1
    assert any("rtpu:test.boom" in rec.getMessage() for rec in records)
    # the finished task left the pending set (a live shared cluster
    # legitimately keeps e.g. rpc.read_loop tasks pending, so only OUR
    # task's absence is asserted)
    assert "rtpu:test.boom" not in procutil.pending_spawned()


def test_rtpu003_resubmit_failure_reaches_owner():
    """RTPU003 caught the nodelet's respill path dropping the handle of
    submit_task: an exception there silently LOST the task and hung its
    owner. _spawn_resubmit now fails the task to the owner instead."""
    from ray_tpu.runtime.nodelet import Nodelet

    class Stub:
        node_id = "deadbeefcafe"
        _spawn_resubmit = Nodelet._spawn_resubmit
        reported = None

        async def submit_task(self, spec, **kw):
            raise RuntimeError("placement exploded")

        async def _report_failure(self, spec, msg):
            self.reported = (spec, msg)

    stub = Stub()

    async def driver():
        stub._spawn_resubmit({"task_id": "t1", "owner_addr": "tcp:x:1"})
        await asyncio.sleep(0.05)

    asyncio.run(driver())
    assert stub.reported is not None
    spec, msg = stub.reported
    assert spec["task_id"] == "t1"
    assert "resubmission failed" in msg and "placement exploded" in msg


def test_rtpu005_batch_request_tags_are_stable():
    """RTPU005 caught llm/batch.py keying engine requests on id(rows):
    a recycled list address could collide with a stale request id in the
    cached engine. Tags now come from a process-wide monotonic counter."""
    import itertools

    from ray_tpu.serve.llm import batch as batch_mod

    assert isinstance(batch_mod._BATCH_SEQ, type(itertools.count()))
    a, b = next(batch_mod._BATCH_SEQ), next(batch_mod._BATCH_SEQ)
    assert b == a + 1  # monotonic, never address-derived
    # and the analyzer keeps id()/hash() out of the module for good
    flagged = [f for f in analyze_file(os.path.join(
        REPO, "ray_tpu", "serve", "llm", "batch.py"))
        if f.rule == "RTPU005" and not f.suppressed]
    assert not flagged, flagged


def test_rtpu101_object_accounting_balances(shared_cluster):
    """RTPU101 caught `object_deleted` registered with NO caller: seals
    incremented the nodelet's object_bytes gauge but nothing ever
    decremented it, so a long-lived node's accounting only grew. The
    delete path (and the driver put path, for symmetry) now send the
    advisory notices; a put+delete round trip must return the gauge to
    where it started."""
    import gc

    import ray_tpu
    from ray_tpu.runtime.core import get_core

    core = get_core()

    def object_bytes():
        return core.nodelet.call("get_node_info",
                                 _timeout=10)["object_bytes"]

    base = object_bytes()
    payload = os.urandom(512 * 1024)  # > max_direct_call_object_size
    ref = ray_tpu.put(payload)
    deadline = time.time() + 10
    while object_bytes() < base + len(payload) and time.time() < deadline:
        time.sleep(0.05)
    grown = object_bytes()
    assert grown >= base + len(payload), (grown, base)
    del ref
    gc.collect()
    deadline = time.time() + 10  # fresh budget: the delete notice is async
    while object_bytes() > grown - len(payload) and time.time() < deadline:
        time.sleep(0.05)
    assert object_bytes() <= grown - len(payload), \
        "object_deleted notice never reached the nodelet"


def test_rtpu105_pool_capacity_knobs(monkeypatch):
    """RTPU105 caught object_store_memory / object_store_fraction as
    dead knobs: pool sizing read only the RTPU_POOL_SIZE env var. The
    precedence now is env var > object_store_memory > fraction-of-shm
    auto sizing."""
    from ray_tpu.runtime.config import get_config
    from ray_tpu.runtime.object_store import pool_capacity

    cfg = get_config()
    saved = (cfg.object_store_memory, cfg.object_store_fraction)
    try:
        monkeypatch.setenv("RTPU_POOL_SIZE", str(11 << 20))
        cfg.object_store_memory = 99 << 20
        assert pool_capacity("s1") == 11 << 20  # env wins
        monkeypatch.delenv("RTPU_POOL_SIZE")
        assert pool_capacity("s1") == 99 << 20  # knob wins
        cfg.object_store_memory = 0  # auto: fraction of the shm fs
        cfg.object_store_fraction = 0.25
        auto = pool_capacity("s1")
        st = os.statvfs(os.environ.get("RTPU_SHM_ROOT", "/dev/shm"))
        expected = max(64 << 20, int(st.f_frsize * st.f_blocks * 0.25))
        # the fs can move a little between the two statvfs reads
        assert abs(auto - expected) <= (1 << 20), (auto, expected)
    finally:
        cfg.object_store_memory, cfg.object_store_fraction = saved


def test_rtpu105_event_buffer_size_knob():
    """RTPU105 caught event_buffer_size as a dead knob: the
    controller's task-event and trace-span deques were hard-coded to
    100000 — RTPU_event_buffer_size silently did nothing."""
    from ray_tpu.runtime.config import get_config
    from ray_tpu.runtime.controller import Controller

    cfg = get_config()
    saved = cfg.event_buffer_size
    try:
        cfg.event_buffer_size = 123
        c = Controller("lint-ebs-session", "tcp:127.0.0.1:0")
        assert c.task_events.maxlen == 123
        assert c.trace_spans.maxlen == 123
    finally:
        cfg.event_buffer_size = saved


def test_rtpu105_metrics_interval_knob():
    """RTPU105 caught metrics_report_interval_s as a dead knob:
    maybe_flush_metrics hard-coded its 30s floor. The knob is now the
    default floor (an explicit argument still overrides)."""
    from ray_tpu.runtime.config import get_config
    from ray_tpu.runtime.core import CoreWorker

    class Stub:
        maybe_flush_metrics = CoreWorker.maybe_flush_metrics

    cfg = get_config()
    saved = cfg.metrics_report_interval_s
    try:
        cfg.metrics_report_interval_s = 10_000.0
        stub = Stub()
        stub._metrics_flushed_at = time.monotonic() - 100.0
        before = stub._metrics_flushed_at
        stub.maybe_flush_metrics()  # inside the floor: early return
        assert stub._metrics_flushed_at == before
        cfg.metrics_report_interval_s = 1.0
        stub.mode = "driver"
        sent = []
        stub.controller = type("C", (), {
            "notify_async": staticmethod(
                lambda *a, **k: sent.append(k))})()
        stub.node_id = "lint-node"
        import uuid

        stub.worker_id = uuid.uuid4()
        stub.maybe_flush_metrics()  # floor elapsed: proceeds
        assert stub._metrics_flushed_at > before
    finally:
        cfg.metrics_report_interval_s = saved


def test_rtpu103_registry_is_live_in_rpc_layer():
    """RTPU103's registry is not documentation: _retry_budget gives a
    transparent-retry budget to IDEMPOTENT methods only — an
    unclassified or NON_IDEMPOTENT method (actor_died, the PR-10
    double-restart) gets zero."""
    from ray_tpu.runtime import rpc as rpc_mod

    assert rpc_mod._retry_budget("heartbeat") >= 1
    assert rpc_mod._retry_budget("actor_died") == 0
    assert rpc_mod._retry_budget("submit_task") == 0
    assert "actor_died" in rpc_mod.NON_IDEMPOTENT_METHODS
    # om_read joined IDEMPOTENT with this PR: the pull fallback is a
    # pure range read, and retrying it is strictly better than failing
    assert rpc_mod._retry_budget("om_read") >= 1


def test_rtpu004_staged_drain_rearm_survives_burst(shared_cluster):
    """RTPU004 flagged _drain_staged's re-arm call_soon on a held loop
    handle; it now re-arms via get_running_loop() (proof of on-loop
    execution). A burst larger than submit_batch_max exercises the
    multi-pass re-arm path end to end."""
    import ray_tpu
    from ray_tpu.runtime.config import get_config
    from ray_tpu.runtime.core import get_core

    cfg = get_config()
    old = cfg.submit_batch_max
    core = get_core()
    cfg.submit_batch_max = 4
    try:
        core._submit_batch_max = 4

        @ray_tpu.remote
        def f(x):
            return x + 1

        refs = [f.remote(i) for i in range(64)]
        assert ray_tpu.get(refs, timeout=120) == [i + 1 for i in range(64)]
    finally:
        cfg.submit_batch_max = old
        core._submit_batch_max = old
