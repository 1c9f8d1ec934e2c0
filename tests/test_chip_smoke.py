"""chip_smoke.py has no CPU arm: without a TPU it must fail, and a phase
that raises must fail it. The slow test is the CPU rehearsal the script's
docstring names: it drives the phase functions at tiny sizes."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _ok_lines(out: str):
    return [ln for ln in out.splitlines() if '"ok": true' in ln]


def test_no_tpu_and_failing_phase_both_exit_nonzero(monkeypatch, capsys):
    # 1. as a command, on this CPU-only box: "no TPU found", no result line
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert not _ok_lines(proc.stdout)

    # 2. with a TPU "found" and the native store "built", a phase that
    #    raises still fails the script, and later phases do not run
    ran = []

    def fake_phase(name, prior):
        ran.append(name)
        if name == "engine":
            raise chip_smoke.SmokeFailure("injected")
        return {"device": {"platform": "tpu", "kind": "fake", "count": 1}}

    monkeypatch.setattr(chip_smoke, "probe_device", lambda: {
        "platform": "tpu", "kind": "fake", "count": 1})
    monkeypatch.setattr(chip_smoke, "check_native_store",
                        lambda: {"phase": "native", "built": True})
    monkeypatch.setattr(chip_smoke, "run_phase", fake_phase)
    assert chip_smoke.main([]) != 0
    assert ran == ["serve", "engine"]
    assert not _ok_lines(capsys.readouterr().out)
    # and when every phase passes, the last line is the contract's, exactly
    monkeypatch.setattr(chip_smoke, "run_phase", lambda name, prior: {
        "device": {"platform": "tpu", "kind": "fake", "count": 1}})
    assert chip_smoke.main([]) == 0
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "fake", "count": 1}}


TINY_SERVE = chip_smoke.ServeSizes(
    model="tiny", model_overrides=(("vocab_size", 512),), dtype="float32",
    page_size=8, num_pages=64, max_model_len=256, max_batch=4,
    prefill_buckets=(64, 128), decode_steps_per_dispatch=2,
    pipeline_depth=2, max_tokens=6, ready_timeout_s=120,
    chats=("ab", "a much longer question " * 3, "ab c"),
    expert_overrides=(("vocab_size", 512),))
TINY_TRAIN = chip_smoke.TrainSizes(
    model="tiny", model_overrides=(("attention_impl", "flash"),),
    batch=4, seq=128, steps=3, lr=1e-2)


TINY_MULTICHIP = chip_smoke.MultichipSizes(
    # 4 kv heads, so that tp=4 divides them
    serve=dataclasses.replace(TINY_SERVE, model="debug-sharded"),
    train=TINY_TRAIN, loss_rtol=1e-3,
    big_model="debug-sharded", big_overrides=(("vocab_size", 512),),
    big_num_pages=64, big_bucket=64)
TINY = {"serve": TINY_SERVE, "engine": TINY_SERVE, "train": TINY_TRAIN,
        "multichip": TINY_MULTICHIP}


def _rehearse(phase: str, prior=None, devices: int = 1):
    """One phase at tiny sizes in a process of its own, as chip_smoke runs
    them (the serving driver must find no JAX backend initialised)."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), phase],
        input=json.dumps(prior or {}), capture_output=True, text=True,
        timeout=900, env=dict(
            os.environ, JAX_PLATFORMS="cpu",
            XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}"))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.slow
def test_rehearse_one_chip_phases_on_cpu():
    serve = _rehearse("serve")
    assert serve["requests_answered"] == 3
    assert serve["replica_is_own_process"]
    assert not serve["driver_touched_backend"]
    engine = _rehearse("engine", prior={"serve": serve})
    assert engine["completions_match_http"] and engine["logits"]["finite"]
    assert engine["experts"]["moe_assignments_total"] > 0
    train = _rehearse("train")
    assert train["loss"][-1] < train["loss"][0]


@pytest.mark.slow
def test_rehearse_multichip_phase_on_virtual_devices():
    assert _rehearse("multichip", devices=4)["failed"] == []


if __name__ == "__main__":
    # the child of _rehearse: steer the three checks a CPU cannot meet
    # (no TPU, no Mosaic kernel in a CPU lowering, sub-second compiles
    # that the persistent cache does not keep); everything else runs
    chip_smoke.require_tpu = lambda device: None
    chip_smoke.require_kernel = lambda program, text: "not checked on CPU"
    chip_smoke.require_cache_hits = lambda phase, hits: None
    name = sys.argv[1]
    print(json.dumps(chip_smoke.PHASES[name](
        TINY[name], prior=json.loads(sys.stdin.read() or "{}"))))
