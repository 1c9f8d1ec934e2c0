"""Mini failure drill for the bench round: controller restart + node
death + persist-dir restart, timed.

Prints ONE JSON line:
  recovery_controller_ms — wall time from killing the in-proc controller
      (a BRAND-NEW controller with empty tables takes over the address)
      until both nodelets have re-registered, the live actor reattached,
      and a fresh task scheduled through the restarted control plane;
  recovery_node_death_ms — wall time from SIGKILLing a nodelet until the
      controller declares it dead AND a task soft-pinned to the dead
      node completes elsewhere (placement failover);
  recovery_controller_persist_ms — wall time from crash-stopping a
      PERSISTING controller (no clean close, journal tail torn to
      simulate the mid-append kill) until a replacement replays the
      persist dir, the named actor reattaches WITHOUT re-creation, and
      the acked KV reads back bit-exact (the torn record discarded);
  recovery_pp_rank_ms — wall time from SIGKILLing one pipeline stage
      rank of a 2-stage pipelined serve engine mid-decode (the driver
      must surface a typed ActorDiedError naming the dead rank, never
      an untyped hang) until a REPLACEMENT stage gang emits its first
      recovered token;
  persist_drill_green / chaos_drills_green / pp_drill_green — drills
      converged inside their deadlines (the pp drill carries its own
      green key so a pipeline regression never masks the control-plane
      drills' signal, and vice versa).

The full scripted-disaster catalog lives in tests/test_chaos.py (the
real kill -9 at the controller.persist syncpoint runs there, against a
standalone controller process); this guarded set gives every bench
round a robustness trend line next to the throughput keys.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CONTROLLER_DEADLINE_S = 30.0
NODE_DEATH_DEADLINE_S = 45.0


def main():
    parser = argparse.ArgumentParser()
    parser.parse_args()

    import ray_tpu
    from ray_tpu.runtime.config import get_config
    from ray_tpu.runtime.controller import Controller
    from ray_tpu.runtime.rpc import EventLoopThread
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    out = {"chaos_drills_green": False, "persist_drill_green": False}
    cfg = get_config()
    cfg.node_death_timeout_s = 3.0  # bound the death verdict
    session = ray_tpu.init(num_cpus=2)
    try:
        node_b = session.add_node(num_cpus=2)

        @ray_tpu.remote
        class Pinger:
            def ping(self):
                return "pong"

        @ray_tpu.remote
        def probe():
            return "alive"

        pinger = Pinger.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=node_b)).remote()
        assert ray_tpu.get(pinger.ping.remote(), timeout=60) == "pong"

        # ---- drill 1: controller kill + restart under a live actor
        elt = EventLoopThread.get()
        old = session.controller_inproc
        t0 = time.monotonic()
        elt.loop.call_soon_threadsafe(old._health_task.cancel)
        elt.run(old._server.stop())
        new = Controller(session.session_name, session.controller_addr)
        elt.run(new.start())
        session.controller_inproc = new
        deadline = time.monotonic() + CONTROLLER_DEADLINE_S
        while time.monotonic() < deadline:
            nodes = session.core.controller.call("list_nodes",
                                                 _timeout=10)
            info = session.core.controller.call(
                "get_actor", actor_id=pinger._actor_id, _timeout=10)
            if len(nodes) == 2 and all(n["alive"] for n in nodes.values()) \
                    and info is not None and info["state"] == "ALIVE":
                break
            time.sleep(0.1)
        else:
            raise TimeoutError("controller-restart drill never converged")
        assert ray_tpu.get(probe.remote(), timeout=30) == "alive"
        assert ray_tpu.get(pinger.ping.remote(), timeout=30) == "pong"
        out["recovery_controller_ms"] = round(
            (time.monotonic() - t0) * 1000.0, 1)

        # ---- drill 2: node death → declared dead + placement failover
        proc = session._extra_nodelet_procs[-1]
        t0 = time.monotonic()
        proc.kill()
        proc.wait(timeout=10)
        deadline = time.monotonic() + NODE_DEATH_DEADLINE_S
        while time.monotonic() < deadline:
            nodes = session.core.controller.call("list_nodes",
                                                 _timeout=10)
            if not nodes[node_b]["alive"]:
                break
            time.sleep(0.1)
        else:
            raise TimeoutError("node death was never declared")
        # work soft-pinned to the dead node must fail over, not hang
        got = ray_tpu.get(probe.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=node_b, soft=True)).remote(), timeout=60)
        assert got == "alive"
        out["recovery_node_death_ms"] = round(
            (time.monotonic() - t0) * 1000.0, 1)

        # ---- drill 3: persist-dir restart — replay + reattach from disk
        import shutil
        import tempfile

        pdir = tempfile.mkdtemp(prefix="rtpu_persist_drill_")
        try:
            @ray_tpu.remote
            class Keeper:
                def pid(self):
                    return os.getpid()

            # swap in a PERSISTING controller on the same address
            old = session.controller_inproc
            elt.loop.call_soon_threadsafe(old._health_task.cancel)
            elt.run(old._server.stop())
            cp = Controller(session.session_name, session.controller_addr,
                            persist_dir=pdir)
            elt.run(cp.start())
            session.controller_inproc = cp
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                nodes = session.core.controller.call("list_nodes",
                                                     _timeout=10)
                if any(n["alive"] for n in nodes.values()):
                    break
                time.sleep(0.1)
            keeper = Keeper.options(name="persist_keeper").remote()
            k_pid = ray_tpu.get(keeper.pid.remote(), timeout=30)
            acked = {f"k{i}": b"v%d" % i for i in range(4)}
            for key, value in acked.items():
                session.core.controller.call("kv_put", ns="drill",
                                             key=key, value=value)
            session.core.controller.call("kv_put", ns="drill", key="tail",
                                         value=b"torn-away")
            # crash-stop: no backend close, no compaction — then TEAR
            # the journal tail (the mid-append kill -9 artifact)
            t0 = time.monotonic()
            elt.loop.call_soon_threadsafe(cp._health_task.cancel)
            elt.run(cp._server.stop())
            jpath = os.path.join(pdir, "kv.journal")
            with open(jpath, "r+b") as f:
                f.truncate(os.path.getsize(jpath) - 3)
            cr = Controller(session.session_name, session.controller_addr,
                            persist_dir=pdir)
            elt.run(cr.start())
            session.controller_inproc = cr
            deadline = time.monotonic() + 30
            info = None
            while time.monotonic() < deadline:
                try:
                    nodes = session.core.controller.call(
                        "list_nodes", _timeout=5)
                    info = session.core.controller.call(
                        "get_actor", name="persist_keeper", namespace="",
                        _timeout=5)
                except Exception:  # noqa: BLE001 — replacement still booting
                    time.sleep(0.1)
                    continue
                if any(n["alive"] for n in nodes.values()) \
                        and info is not None and info["state"] == "ALIVE":
                    break
                time.sleep(0.1)
            else:
                raise TimeoutError(
                    "persist-dir restart drill never converged")
            # reattached, not re-created: same process, zero restarts
            assert ray_tpu.get(keeper.pid.remote(), timeout=30) == k_pid
            assert info["num_restarts"] == 0
            for key, value in acked.items():
                got = session.core.controller.call("kv_get", ns="drill",
                                                   key=key)
                assert got == value, (key, got)
            # the torn (never-fully-written) record is discarded
            assert session.core.controller.call(
                "kv_get", ns="drill", key="tail") is None
            out["recovery_controller_persist_ms"] = round(
                (time.monotonic() - t0) * 1000.0, 1)
            out["persist_drill_green"] = True
        finally:
            shutil.rmtree(pdir, ignore_errors=True)

        out["chaos_drills_green"] = True

        # ---- drill 4: pipeline stage-rank SIGKILL → typed error →
        # rebuilt stage gang serves traffic (ray_tpu/serve/llm/pp.py).
        # Own try + green key: a serve-plane regression must not mask
        # the control-plane drills above, and vice versa.
        out["pp_drill_green"] = False
        try:
            import signal

            import numpy as np

            # virtual CPU devices for the engine and — via the env the
            # fresh session's nodelet (and so its stage workers)
            # inherits — the stage processes; config set directly too,
            # in case jax was imported before this point
            flag = "--xla_force_host_platform_device_count=8"
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = f"{flags} {flag}".strip()
            os.environ["JAX_PLATFORMS"] = "cpu"
            import jax

            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_num_cpu_devices", 8)

            from ray_tpu import exceptions
            from ray_tpu.serve.llm import (
                EngineConfig,
                PipelinedEngine,
                SamplingParams,
            )

            # fresh session: drills 1-3 killed a node and swapped the
            # controller; the stage gang deserves a clean cluster
            ray_tpu.shutdown()
            session = ray_tpu.init(num_cpus=4)
            cfg.rpc_connect_timeout_s = 2.0  # fail fast vs the corpse
            cfg.rpc_retry_max = 1
            pcfg = dict(model="tiny", page_size=8, num_pages=64,
                        max_model_len=128, max_batch=2,
                        prefill_buckets=(16, 32, 64), dtype="float32",
                        model_overrides={"vocab_size": 512},
                        pp=2, pp_fetch_timeout_s=6.0)
            prompt = list(np.random.default_rng(3).integers(0, 400, 12))
            ppe = PipelinedEngine(EngineConfig(**pcfg))
            ppe.add_request("pre", prompt, SamplingParams(max_tokens=32))
            got = 0
            for _ in range(100):
                got += sum(len(d.new_token_ids) for d in ppe.step())
                if got >= 3:
                    break
            assert got >= 3, "decode never reached steady state"
            victim = ray_tpu.get(ppe._stage_handles[1].pid.remote(),
                                 timeout=30)
            t0 = time.monotonic()
            os.kill(victim, signal.SIGKILL)
            try:
                for _ in range(50):
                    ppe.step()
                raise AssertionError(
                    "stage death never surfaced as ActorDiedError")
            except exceptions.ActorDiedError:
                pass  # the typed verdict the drill demands
            ppe.shutdown()
            # gang replaced: kill → first recovered token, timed
            ppe2 = PipelinedEngine(EngineConfig(**pcfg))
            ppe2.add_request("post", prompt, SamplingParams(max_tokens=4))
            first = None
            for _ in range(200):
                if any(d.new_token_ids for d in ppe2.step()):
                    first = time.monotonic()
                    break
            assert first is not None, "rebuilt gang produced no tokens"
            out["recovery_pp_rank_ms"] = round((first - t0) * 1000.0, 1)
            ppe2.shutdown()
            out["pp_drill_green"] = True
        except Exception as e:  # noqa: BLE001 — the bench line reports it
            out["pp_error"] = repr(e)[:200]
    except Exception as e:  # noqa: BLE001 — the bench line reports it
        out["error"] = repr(e)[:200]
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001 — drill teardown is best-effort
            pass
    print(json.dumps(out))


if __name__ == "__main__":
    main()
