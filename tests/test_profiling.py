"""Profiling subsystem (utils/profiling.py): trace window start/stop mechanics
and end-to-end capture through Trainer.fit (SURVEY.md §5 tracing)."""

import glob
import io
import os

from distributed_vgg_f_tpu.config import (
    DataConfig, ExperimentConfig, MeshConfig, ModelConfig, OptimConfig,
    TrainConfig)
from distributed_vgg_f_tpu.train.trainer import Trainer
from distributed_vgg_f_tpu.utils.logging import MetricLogger
from distributed_vgg_f_tpu.utils.profiling import StepProfiler


def test_step_profiler_window(tmp_path, monkeypatch):
    calls = []
    monkeypatch.setattr("jax.profiler.start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr("jax.profiler.stop_trace",
                        lambda: calls.append(("stop",)))
    prof = StepProfiler(str(tmp_path), start_step=3, num_steps=2)
    for i in range(10):
        prof.step(i)
    prof.stop()  # idempotent
    assert calls == [("start", str(tmp_path)), ("stop",)]
    assert prof.captured


def test_step_profiler_stops_on_interrupt(tmp_path, monkeypatch):
    calls = []
    monkeypatch.setattr("jax.profiler.start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr("jax.profiler.stop_trace",
                        lambda: calls.append("stop"))
    prof = StepProfiler(str(tmp_path), start_step=0, num_steps=100)
    prof.step(0)   # trace opens, window never completes
    prof.stop()    # the trainer's finally-block path
    assert calls == ["start", "stop"]


def test_trainer_fit_captures_real_trace(tmp_path):
    logdir = str(tmp_path / "trace")
    cfg = ExperimentConfig(
        name="profile_test",
        model=ModelConfig(name="vggf", num_classes=10, compute_dtype="float32"),
        optim=OptimConfig(base_lr=0.01, reference_batch_size=8),
        data=DataConfig(name="synthetic", image_size=32, global_batch_size=8,
                        num_train_examples=32),
        mesh=MeshConfig(num_data=8),
        train=TrainConfig(steps=4, seed=0, log_every=100, profile=True,
                          profile_dir=logdir, profile_start_step=1,
                          profile_num_steps=2),
    )
    trainer = Trainer(cfg, logger=MetricLogger(stream=io.StringIO()))
    trainer.fit(num_steps=4)
    # jax.profiler writes plugins/profile/<run>/ with .xplane.pb files
    traces = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                       recursive=True)
    assert traces, f"no trace files under {logdir}"
    # the program's own spans ride the profiler's clock (telemetry's
    # bridge): the host plane holds them beside the runtime's events
    from chipbench import scope_reduce
    host = {e["name"] for e in scope_reduce.load(traces[-1])["spans"]}
    assert "dvggf:infeed:next_batch" in host
    assert "dvggf:dispatch:train_step_dispatch" in host


def test_spans_open_the_profiler_hook_only_while_enabled():
    """The bridge itself, with a fake in the hook's place: a span opens it
    under `dvggf:<category>:<name>` and closes it, a disabled recorder
    opens nothing, and `record(...)` after the fact never does."""
    import contextlib

    from distributed_vgg_f_tpu.telemetry.spans import SpanRecorder
    seen = []

    @contextlib.contextmanager
    def fake(name):
        seen.append(("open", name))
        yield
        seen.append(("close", name))

    rec = SpanRecorder()
    rec.annotate = fake
    with rec.span("next_batch", "infeed") as span:
        assert seen == [("open", "dvggf:infeed:next_batch")]
    assert seen[-1] == ("close", "dvggf:infeed:next_batch")
    assert span.dur_ns >= 0 and rec.recorded == 1
    rec.record("late", "host", 0, 1)
    rec.enabled = False
    with rec.span("next_batch", "infeed"):
        pass
    assert len(seen) == 2
