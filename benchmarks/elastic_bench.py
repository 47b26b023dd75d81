"""Elastic-resize downtime receipt (r19, ISSUE 16 satellite): measure what
surviving a k-of-N preemption actually costs under the two recovery
semantics —

- **elastic** (parallel/elastic.py, `mesh.elastic.enabled=true`): the
  trainer keeps running. Survivors shrink the mesh in place, reshard
  params/opt-state through the retopology converter, and take over the
  data stream through the r18 cursor blob. Downtime = the trainer's own
  `elastic_downtime` receipt: preemption consensus → first completed step
  on the survivor mesh, recompile included. Replayed batches MUST be 0
  (the cursor-handoff contract — enforced by the artifact schema,
  telemetry/schema.validate_elastic_row).
- **restart** (the r18-era control): the process dies at the forced
  preempt checkpoint and a FRESH interpreter comes up on the survivor
  mesh — python + jax import, trainer construction, checkpoint restore,
  recompile, first step. Timed as a real subprocess because that is what
  a restart is; in-process timing would flatter it by the whole runtime
  warm-up.

Both paths share one persistent XLA compilation cache at its fixed home
(utils/compile_cache: JAX_COMPILATION_CACHE_DIR when set, else the
in-checkout directory — the restart subprocess resolves the same path): a
preempted fleet has a warm compile cache, and min-of-N timings therefore
compare the warm path on BOTH sides — without it the receipt would mostly
race two cold compiles of the same survivor-mesh program.

A CPU receipt by construction: the restart control IS a second
interpreter, and an accelerator belongs to one process at a time, so a
parent that held the chip could never time it. Both sides are pinned to
four virtual CPU devices and the artifact names that platform.

The artifact (--json-out) carries `metric:
elastic_resize_downtime_seconds` with `value` = the elastic row's min
downtime, one `mode: elastic_bench` layout row (the r19 regression-
sentinel basis rides its `topology` key, telemetry/regress.Basis). It is
schema-gated, never pin-gated: zero replay and the >= 3x bar are
correctness claims, not rates to band (regress.check_artifact routes it
accordingly; validate_elastic_row fails any committed receipt below 3x).

Committed receipts: benchmarks/runs/host_r18/.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from distributed_vgg_f_tpu.config import (  # noqa: E402
    DataConfig, ElasticConfig, ExperimentConfig, MeshConfig, ModelConfig,
    OptimConfig, TrainConfig)
from distributed_vgg_f_tpu.telemetry import schema  # noqa: E402
from distributed_vgg_f_tpu.telemetry.regress import ELASTIC_METRIC  # noqa: E402

DEVICES = 4


def _spread(values) -> float:
    med = sorted(values)[len(values) // 2]
    return (max(values) - min(values)) / max(med, 1e-9)


def _cfg(ckpt_dir: str, *, batch: int, image_size: int, steps: int,
         preempt_at: int, elastic: bool, faults: str) -> ExperimentConfig:
    return ExperimentConfig(
        name="elastic_bench",
        model=ModelConfig(name="vggf", num_classes=10,
                          compute_dtype="float32", dropout_rate=0.0),
        optim=OptimConfig(base_lr=0.05, reference_batch_size=batch,
                          momentum=0.9, weight_decay=1e-4),
        data=DataConfig(name="synthetic", image_size=image_size,
                        global_batch_size=batch,
                        num_train_examples=4 * batch),
        mesh=MeshConfig(num_data=0,
                        elastic=ElasticConfig(enabled=elastic)),
        train=TrainConfig(steps=steps, seed=0, log_every=1,
                          checkpoint_dir=ckpt_dir,
                          checkpoint_every_steps=100,
                          eval_every_steps=10_000,
                          fault_injection=faults),
    )


def _build_trainer(cfg, mesh_size: int, jsonl_path: str | None = None):
    import jax
    from distributed_vgg_f_tpu.parallel.mesh import MeshSpec, build_mesh
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger
    mesh = build_mesh(MeshSpec(("data",), (mesh_size,)),
                      devices=jax.devices()[:mesh_size])
    logger = MetricLogger(jsonl_path=jsonl_path, stream=io.StringIO())
    return Trainer(cfg, mesh=mesh, logger=logger)


def elastic_once(args, workdir: str) -> dict:
    """One full elastic run; returns the resize + downtime receipts."""
    jsonl = os.path.join(workdir, "elastic.jsonl")
    cfg = _cfg(os.path.join(workdir, "ck_el"),
               batch=args.batch, image_size=args.image_size,
               steps=args.steps, preempt_at=args.preempt_at, elastic=True,
               faults=f"preempt@rank1:{args.preempt_at}")
    trainer = _build_trainer(cfg, DEVICES, jsonl_path=jsonl)
    trainer.fit()
    trainer.logger.close()
    records = [json.loads(ln) for ln in open(jsonl)]
    resize = next(r for r in records if r.get("event") == "elastic_resize")
    downtime = next(r for r in records
                    if r.get("event") == "elastic_downtime")
    assert resize["cursor"]["replayed_batches"] == 0, resize
    return {"downtime_seconds": downtime["downtime_ns"] / 1e9,
            "topology": resize["topology"],
            "batch_policy": resize["batch_policy"]}


def restart_control_once(args, workdir: str, fresh_checkpoint: bool) -> float:
    """Time the r18 path: a fresh interpreter from launch to the first
    completed step on the survivor mesh. The stop-run (elastic off, forced
    preempt checkpoint) is re-created per repeat only when asked — its
    cost is NOT part of the restart (the elastic path pays the same forced
    save before resizing)."""
    ck = os.path.join(workdir, "ck_ctl")
    if fresh_checkpoint:
        cfg = _cfg(ck, batch=args.batch, image_size=args.image_size,
                   steps=args.steps, preempt_at=args.preempt_at,
                   elastic=False,
                   faults=f"preempt@rank1:{args.preempt_at}")
        trainer = _build_trainer(cfg, DEVICES)
        trainer.fit()
        trainer.logger.close()
    child_steps = args.preempt_at + 1  # restore at k, run exactly one step
    t0 = time.perf_counter()
    subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--_child-restart",
         "--ckpt-dir", ck, "--batch", str(args.batch),
         "--image-size", str(args.image_size),
         "--steps", str(child_steps),
         "--preempt-at", str(args.preempt_at),
         "--survivors", str(DEVICES - 1)],
        check=True, stdout=subprocess.DEVNULL)
    return time.perf_counter() - t0


def child_restart(args) -> int:
    """The subprocess body: survivor-mesh trainer, restore, one step."""
    cfg = _cfg(args.ckpt_dir, batch=args.batch,
               image_size=args.image_size, steps=args.steps,
               preempt_at=args.preempt_at, elastic=False, faults="")
    trainer = _build_trainer(cfg, args.survivors)
    state = trainer.fit()
    import jax
    final = int(jax.device_get(state.step))
    if final != args.steps:
        raise SystemExit(f"restart control ran to step {final}, "
                         f"expected {args.steps} — not a restore")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=12,
                    help="global batch; must divide by 4 and 3 "
                         "(keep_global across the 4->3 resize)")
    ap.add_argument("--image-size", type=int, default=32)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--preempt-at", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--json-out", default="")
    # subprocess plumbing (restart_control_once)
    ap.add_argument("--_child-restart", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--ckpt-dir", default="", help=argparse.SUPPRESS)
    ap.add_argument("--survivors", type=int, default=3,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    # platform and virtual device count are pinned before jax initializes
    # (CPU receipt: 4 virtual devices, resize 4->3 on rank-1 preemption);
    # the restart subprocess inherits both
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{DEVICES}").strip()
    # one warm compilation cache for BOTH paths — see the module docstring
    # for why this is the honest comparison
    import jax

    from distributed_vgg_f_tpu.utils.compile_cache import enable_compile_cache
    from distributed_vgg_f_tpu.utils.device import device_facts
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

    if args._child_restart:
        return child_restart(args)

    elastic_runs, restart_s = [], []
    with tempfile.TemporaryDirectory(prefix="elastic_bench_") as workdir:
        for i in range(args.repeats):
            run_dir = os.path.join(workdir, f"r{i}")
            os.makedirs(run_dir)
            elastic_runs.append(elastic_once(args, run_dir))
            restart_s.append(restart_control_once(
                args, run_dir, fresh_checkpoint=True))

    elastic_s = [r["downtime_seconds"] for r in elastic_runs]
    downtime = min(elastic_s)
    restart = min(restart_s)
    row = {
        "mode": "elastic_bench",
        "topology": elastic_runs[0]["topology"],
        "batch_policy": elastic_runs[0]["batch_policy"],
        "downtime_seconds": round(downtime, 4),
        "downtime_seconds_median": round(
            sorted(elastic_s)[len(elastic_s) // 2], 4),
        "restart_seconds": round(restart, 4),
        "restart_seconds_median": round(
            sorted(restart_s)[len(restart_s) // 2], 4),
        "speedup_vs_restart": round(restart / max(downtime, 1e-9), 3),
        "replayed_batches": 0,
        "resizes": 1,
        "spread": round(_spread(elastic_s), 4),
        "repeats": args.repeats,
        "preempt_at": args.preempt_at, "steps": args.steps,
        "devices": DEVICES, "survivors": DEVICES - 1,
        "batch": args.batch, "image_size": args.image_size,
        "model": "vggf", "dataset": "synthetic",
    }
    artifact = {
        "schema_version": schema.SCHEMA_VERSION,
        "metric": ELASTIC_METRIC,
        "value": row["downtime_seconds"],
        "unit": "seconds",
        **device_facts(),
        "layouts": [row],
    }
    errors = schema.validate_bench_artifact(artifact)
    if errors:
        print(json.dumps(artifact, indent=1), file=sys.stderr)
        print("SCHEMA ERRORS:", errors, file=sys.stderr)
        return 1
    print(json.dumps(artifact, indent=1))
    print(f"\nelastic resize: {downtime:7.2f} s downtime "
          f"(0 replayed batches)")
    print(f"restart control:{restart:7.2f} s (fresh interpreter + restore)"
          f" -> elastic is {row['speedup_vs_restart']}x faster")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"wrote {args.json_out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
