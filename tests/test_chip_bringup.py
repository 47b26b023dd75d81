"""What stands between the program and a chip that is not there: the
compile cache's one fixed home, chip_smoke.py's refusal of any platform
but `tpu`, and ingest workers that never touch a backend (a chip belongs to
one process; the trainer that holds it starts them)."""

import json
import os
import subprocess
import sys

import jax
import pytest

from distributed_vgg_f_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Record `jax.config.update` calls instead of making them: the suite's
    own cache placement must survive these tests."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_follows_the_environment_variable(monkeypatch,
                                                        config_updates):
    """JAX_COMPILATION_CACHE_DIR set: JAX already keeps its cache there, and
    the program sets no other."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert compile_cache.enable_compile_cache("cpu_abc") == "/some/dir"
    assert config_updates == []


def test_compile_cache_default_is_one_fixed_ignored_path(monkeypatch,
                                                         config_updates):
    """Unset: one fixed directory inside the checkout, listed in .gitignore
    — the path is part of a cache key, so a moving one never hits."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == fixed
    assert compile_cache.enable_compile_cache("cpu_abc") \
        == os.path.join(fixed, "cpu_abc")
    assert [value for _, value in config_updates] \
        == [fixed, os.path.join(fixed, "cpu_abc")]
    assert len({option for option, _ in config_updates}) == 1
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_chip_smoke_refuses_the_cpu_and_starts_no_child(monkeypatch, capsys,
                                                        config_updates):
    """On the CPU (this suite's platform) chip_smoke.py stops in its first
    phase: non-zero, `"ok": false` on the last line, never `"ok": true`, and
    no process started on the way."""
    def no_children(*args, **kwargs):
        raise AssertionError(f"chip_smoke started a child: {args}")

    monkeypatch.setattr(subprocess, "Popen", no_children)
    monkeypatch.setattr(os, "fork", no_children)
    monkeypatch.setattr(os, "posix_spawn", no_children)
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke
    rc = chip_smoke.main([])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok": true' not in out
    last = json.loads(out.strip().splitlines()[-1])
    assert last == {"ok": False, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    assert "[smoke:train]" not in out


def test_ingest_workers_never_import_jax():
    """Decode-service workers and grain workers are started by a trainer
    that holds the chip; they must not initialise a backend. Strongest form:
    their modules do not import jax at all."""
    code = ("import sys\n"
            "import distributed_vgg_f_tpu.data.ingest_service\n"
            "import distributed_vgg_f_tpu.data.grain_imagenet\n"
            "import distributed_vgg_f_tpu.data.native_jpeg\n"
            "sys.exit(1 if 'jax' in sys.modules else 0)\n")
    env = {**os.environ,
           "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, timeout=120)
    assert out.returncode == 0, out.stderr.decode(errors="replace")[-2000:]
