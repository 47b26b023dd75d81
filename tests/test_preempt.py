"""Graceful preemption (train.handle_preemption): SIGTERM mid-training →
finish the in-flight step, force-save a checkpoint, exit cleanly; a restart
resumes from the preemption step. The SIGKILL (no-grace) variant lives in
tests/test_kill_restart.py."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _train_lines(path):
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(l) for l in f if l.strip()]


@pytest.mark.slow
def test_sigterm_checkpoints_and_exits_cleanly(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    metrics = os.path.join(ckpt, "metrics.jsonl")
    # preemption semantics are platform-independent: the child trains on
    # the CPU whatever the machine holds
    env = {**os.environ, "TF_CPP_MIN_LOG_LEVEL": "3", "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    cmd = [sys.executable, os.path.join(REPO, "train.py"),
           "--config", "vggf_synthetic",
           "--set", "train.steps=100000",          # runs "forever"
           "--set", "train.log_every=1",
           "--set", f"train.checkpoint_dir={ckpt}",
           "--set", "train.checkpoint_every_steps=1000",
           "--set", "data.global_batch_size=8",
           "--set", "data.image_size=32",
           "--set", "model.num_classes=10"]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 600
        while not any(e.get("event") == "train"
                      for e in _train_lines(metrics)):
            if proc.poll() is not None:
                out = proc.stdout.read().decode(errors="replace")
                pytest.fail(f"exited before training started:\n{out[-3000:]}")
            if time.monotonic() > deadline:
                pytest.fail("no train step within 600s")
            time.sleep(0.2)
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=300)
    finally:
        if proc.poll() is None:
            proc.kill()

    assert proc.returncode == 0, out.decode(errors="replace")[-3000:]
    events = _train_lines(metrics)
    preempts = [e for e in events if e.get("event") == "preempt"]
    assert len(preempts) == 1 and preempts[0]["checkpointed"]
    stop_step = preempts[0]["step"]
    assert stop_step >= 1

    # the preemption checkpoint is durable and a restart resumes from it
    from distributed_vgg_f_tpu.checkpoint.manager import CheckpointManager
    mngr = CheckpointManager(ckpt)
    assert mngr.latest_step() == stop_step

    out2 = subprocess.run(
        cmd[:4] + ["--set", f"train.steps={stop_step + 2}"] + cmd[6:],
        env=env, capture_output=True, timeout=600)
    assert out2.returncode == 0, out2.stdout.decode(errors="replace")[-3000:]
    restores = [e for e in _train_lines(metrics) if e.get("event") == "restore"]
    assert restores and restores[-1]["step"] == stop_step
