"""Benchmark harness smoke tests (SURVEY.md §4: the judged metric's
measurement code is itself tested) — run bench.py and benchmarks/scaling.py as
real subprocesses on tiny shapes and validate their JSON contracts."""

import json
import os
import re
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, extra_env=None):
    env = {**os.environ, "TF_CPP_MIN_LOG_LEVEL": "3",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
           **(extra_env or {})}
    return subprocess.run([sys.executable] + args, env=env, cwd=REPO,
                          capture_output=True, timeout=560)


def _json_lines(out):
    return [json.loads(l) for l in out.stdout.decode().splitlines()
            if l.startswith("{")]


def test_bench_bad_model_extra_value_fails_fast():
    """An invalid --model-extra VALUE (not just an unknown key) must die as
    a bad_config record before any device work: the jax.eval_shape pass
    traces init abstractly, reaching the __call__-time validation
    (ADVICE r3)."""
    t0 = time.monotonic()
    out = _run(["bench.py", "--model", "vit_s16", "--image-size", "224",
                "--model-extra", "attention_layout=flashh"])
    assert time.monotonic() - t0 < 120   # interpreter + trace only
    assert out.returncode == 1
    (rec,) = _json_lines(out)
    assert rec["error"] == "bad_config"
    assert "flashh" in rec["detail"]
    assert rec["value"] is None


def test_bench_refuses_a_platform_that_is_not_the_chip():
    """JAX falls back to the CPU with a warning when no accelerator
    answers; a bench that meant the chip must then fail — value null, no
    number from anywhere — unless the CPU was asked for by name. Here
    `JAX_PLATFORMS=''` lets JAX choose, and it finds only the CPU."""
    out = _run(["bench.py", "--batch-size", "4", "--image-size", "32",
                "--steps", "1", "--warmup", "0"],
               extra_env={"JAX_PLATFORMS": ""})
    (rec,) = _json_lines(out)
    if rec.get("platform") == "tpu":
        pytest.skip("a chip is attached here: nothing to refuse")
    assert out.returncode == 1, out.stdout.decode() + out.stderr.decode()
    assert rec["error"] == "no_accelerator"
    assert rec["value"] is None and rec["vs_baseline"] is None
    assert "JAX_PLATFORMS=cpu" in rec["detail"]
    assert not {"last_committed", "stale"} & set(rec)


def test_bench_result_line_names_the_device():
    """The CPU asked for by name runs — in the process that was started,
    no child — and the result line says which device the number is from."""
    out = _run(["bench.py", "--batch-size", "4", "--image-size", "32",
                "--steps", "2", "--warmup", "1"],
               extra_env={"JAX_PLATFORMS": "cpu",
                          "XLA_FLAGS": "--xla_force_host_platform_device_"
                                       "count=1"})
    assert out.returncode == 0, out.stderr.decode(errors="replace")[-2000:]
    (rec,) = _json_lines(out)
    # contract keys required; extras (e.g. mfu_est on a chip) allowed
    assert set(rec) >= {"metric", "value", "unit", "vs_baseline",
                        "platform", "device_kind", "device_count"}
    assert rec["unit"] == "images/sec/chip" and rec["value"] > 0
    assert (rec["platform"], rec["device_count"]) == ("cpu", 1)
    # no peak is known for a CPU, so no MFU is claimed for it
    assert "mfu_est" not in rec


@pytest.mark.slow
def test_pipeline_bench_end_to_end(tmp_path):
    """--pipeline imagenet: generates fake JPEG TFRecords, drives the jitted
    step through the real tf.data path, reports e2e vs device-only vs host
    pipeline rates and the infeed stall fraction (VERDICT r1 #1)."""
    out = _run(["bench.py", "--pipeline", "imagenet",
                "--data-dir", str(tmp_path / "records"), "--num-files", "2",
                "--per-file", "16", "--batch-size", "4", "--image-size", "32",
                "--steps", "2", "--warmup", "1"],
               extra_env={"JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, (out.stdout + out.stderr).decode(
        errors="replace")[-3000:]
    (rec,) = _json_lines(out)
    assert rec["metric"].endswith("e2e_imagenet_images_per_sec_per_chip")
    assert rec["platform"] == "cpu"
    assert rec["value"] > 0
    assert rec["device_only_images_per_sec_per_chip"] > 0
    assert rec["host_pipeline_images_per_sec"] > 0
    assert 0.0 <= rec["infeed_stall_fraction"] <= 1.0


@pytest.mark.slow
def test_scaling_harness_reports_efficiency():
    out = _run(["benchmarks/scaling.py", "--fake-devices", "4",
                "--image-size", "32", "--per-chip-batch", "2",
                "--steps", "2", "--warmup", "1", "--sizes", "1", "2"],
               extra_env={"XLA_FLAGS": re.sub(
                   r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))})
    assert out.returncode == 0, out.stderr.decode(errors="replace")[-2000:]
    lines = [json.loads(l) for l in out.stdout.decode().splitlines()
             if l.startswith("{")]
    per_size = [l for l in lines if "mesh_size" in l]
    summary = [l for l in lines if "efficiency" in l]
    assert [l["mesh_size"] for l in per_size] == [1, 2]
    assert all(l["images_per_sec_per_chip"] > 0 for l in per_size)
    assert len(summary) == 1 and len(summary[0]["efficiency"]) == 2
    assert summary[0]["efficiency"][0] == 1.0
