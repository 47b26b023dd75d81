"""Console entry point (`dvggf-train`, also `python train.py`) — the
reference's `python train.py --flags` CLI surface (SURVEY.md §1), packaged
so an installed framework exposes the same commands as the checkout:

    dvggf-train --config vggf_cifar10_smoke --set train.steps=100
    dvggf-train --mode eval --config vggf_imagenet_dp \
        --set train.checkpoint_dir=/ckpts
    dvggf-train --config vggf_imagenet_dp --set data.wire=u8  # uint8 ingest
        # wire: ship raw resampled pixels, finish normalize/cast/space-to-
        # depth on device (data/device_ingest.py; falls back to the host
        # wire with a logged warning when the native u8 path is refused)
    dvggf-train --mode serve --config vggf_imagenet_dp \
        --set train.checkpoint_dir=/ckpts --set serving.enabled=true
        # always-on dynamic-batching predict server (serving/, r17): u8
        # payloads over HTTP, bounded admission + typed-503 shed; prints
        # "serving on host:port" (port-0 contract) and runs until SIGINT
"""

from __future__ import annotations

import sys


def main(argv=None) -> None:
    from distributed_vgg_f_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from distributed_vgg_f_tpu.config import parse_cli
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger

    cfg, args = parse_cli(argv, with_mode=True)
    mode = args.mode
    # Context-managed logger: a crashing run still flushes/closes the JSONL
    # stream and the TB writer exactly once, so the on-disk record archive
    # is complete up to the failure.
    with MetricLogger(jsonl_path=(f"{cfg.train.checkpoint_dir}/metrics.jsonl"
                                  if cfg.train.checkpoint_dir else None),
                      tensorboard_dir=cfg.train.tensorboard_dir
                      or None) as logger:
        trainer = Trainer(cfg, logger=logger)

        def require_checkpoint():
            # eval/predict must fail loudly rather than silently score random
            # weights (run_predict also guards internally for library callers)
            if trainer.checkpoints is None or \
                    trainer.checkpoints.latest_step() is None:
                raise SystemExit(
                    f"{mode} mode: no checkpoint found under "
                    f"{cfg.train.checkpoint_dir!r} (set train.checkpoint_dir "
                    "to a directory containing checkpoints)")

        if mode == "serve":
            # explicit double opt-in (kill-switch discipline): the mode
            # names the intent, the config flag arms the subsystem — a
            # preset with serving off must never start listening because
            # of a mistyped --mode
            if not cfg.serving.enabled:
                raise SystemExit(
                    "serve mode: serving is disabled — pass "
                    "--set serving.enabled=true (the server is off by "
                    "default; see README 'Serving')")
            from distributed_vgg_f_tpu.serving.server import (
                serve_from_trainer)
            require_checkpoint()
            server = serve_from_trainer(trainer)
            # launchers scrape this line for the bound port (the port-0
            # contract, same as the exporter sidecar and ingest workers)
            print(f"serving on {server.endpoint}", flush=True)
            try:
                server.wait()
            except KeyboardInterrupt:
                pass
            except BaseException as e:
                # a serving crash leaves the same black box a trainer
                # crash does — the ring already holds the admission
                # windows and controller actuations triage needs
                trainer.dump_flight_black_box(exc=e)
                raise
            finally:
                server.close()
                trainer.export_telemetry()
            return
        if mode == "predict":
            from distributed_vgg_f_tpu.train.predict import run_predict
            require_checkpoint()
            if not args.images:
                raise SystemExit("predict mode: pass --images <files/dirs>")
            # finally: like fit(), crashing standalone modes still export —
            # the telemetry of a failed pass is the diagnosis material
            try:
                run_predict(trainer, args.images)
            finally:
                trainer.export_telemetry()
            return
        if mode == "eval":
            # Standalone validation (SURVEY.md §3.4): restore latest
            # checkpoint, run the full held-out split, report top-1/top-5.
            require_checkpoint()
            try:
                trainer.evaluate(trainer.restore_or_init(),
                                 trainer.make_dataset("eval"))
            finally:
                trainer.export_telemetry()
            return
        eval_ds = None
        try:
            eval_ds = trainer.make_dataset("eval")
        except (FileNotFoundError, NotADirectoryError, ValueError) as e:
            # train-mode eval cadence is best-effort (e.g. no data_dir yet) —
            # but say so, and let anything unexpected propagate.
            logger.log("eval_dataset_unavailable", {"error": repr(e)})
        trainer.fit(eval_dataset=eval_ds)


if __name__ == "__main__":
    main(sys.argv[1:])
