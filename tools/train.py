"""Training entry point (reference tools/train.py:44-73):
config -> dist init -> build module -> dataloaders -> engine.fit.

Crash-loop contract: relaunching the same command auto-resumes from the
newest restorable checkpoint (corrupt ones are quarantined and skipped —
docs/fault_tolerance.md).  A SIGTERM mid-run checkpoints and exits 0;
``--exit-after-save`` bounds the run to one checkpoint interval."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddlefleetx_tpu.utils.device import apply_platform_env

apply_platform_env()  # tpu unless a CPU pin is set; before backend init

from paddlefleetx_tpu.core.engine import Engine
from paddlefleetx_tpu.core.module import build_module
from paddlefleetx_tpu.data.builders import build_dataloader
from paddlefleetx_tpu.parallel.env import init_dist_env
from paddlefleetx_tpu.utils.config import get_config, parse_args
from paddlefleetx_tpu.utils.log import advertise, logger


def main(argv=None):
    """Run the configured fit; returns the Engine (callers that drive this
    in-process — tools/auto.py, chip_smoke.py — inspect it afterwards)."""
    args = parse_args(argv)
    cfg = get_config(args.config, overrides=args.override)
    advertise()

    # crash postmortem: an uncaught exception dumps the flight recorder
    # ring (recent step records, data_skips, rollback/preempt events) to
    # flight_recorder.jsonl (PFX_FLIGHT_RECORDER) before the traceback —
    # no longer dependent on Engine.metrics_file being configured
    from paddlefleetx_tpu.utils.telemetry import get_flight_recorder

    get_flight_recorder().install_excepthook(
        path=os.path.join(
            cfg.Engine.save_load.get("output_dir", "./output"),
            "flight_recorder.jsonl",
        )
    )

    mesh = init_dist_env(cfg)
    module = build_module(cfg)

    from paddlefleetx_tpu.utils.checkpoint import (
        latest_checkpoint,
        resume_with_fallback,
    )

    output_dir = cfg.Engine.save_load.get("output_dir", "./output")
    ckpt_dir = cfg.Engine.save_load.get("ckpt_dir")
    auto_resume = not ckpt_dir and bool(cfg.Engine.save_load.get("auto_resume"))
    if auto_resume:
        # crash-loop restart contract (reference _load_recovery,
        # eager_engine.py:244,816-825): newest restorable step_N dir wins.
        # This peek only decides whether pretrained warm-start applies, so
        # it must be side-effect free (quarantine=False); the real resolve
        # below quarantines as needed.
        resuming = latest_checkpoint(output_dir, quarantine=False) is not None
    else:
        resuming = bool(ckpt_dir)
    if resuming and cfg.Engine.save_load.get("pretrained_params"):
        # the resume load replaces params wholesale — skip the (possibly
        # multi-GB) warm-start restore on every crash-loop restart
        logger.info("pretrained_params skipped: resume checkpoint takes over")
        cfg.Engine.save_load.pretrained_params = None

    with mesh:
        engine = Engine(cfg, module, mesh)
        if getattr(args, "exit_after_save", False):
            engine.exit_after_save = True
        if ckpt_dir:
            engine.load(ckpt_dir)
        elif auto_resume:
            loaded = resume_with_fallback(engine, output_dir)
            if loaded is None and resuming:
                # the peek promised a resume (and may have skipped the
                # pretrained warm start on its word): silently training
                # from scratch would be the worst outcome — stop loudly
                raise RuntimeError(
                    f"auto_resume: checkpoints exist under {output_dir} "
                    "but none restored (see QUARANTINED logs); refusing "
                    "to silently restart from scratch — inspect/remove "
                    "the *.corrupt dirs, or disable auto_resume to "
                    "intentionally start over"
                )
        # loaders built after load so the sampler resumes the data order
        # from the checkpoint's consumed_samples
        train_loader = build_dataloader(
            cfg, "Train", consumed_samples=engine._consumed_samples
        )
        eval_loader = (
            build_dataloader(cfg, "Eval")
            if "Eval" in cfg.get("Data", {}) and int(cfg.Engine.get("eval_freq", 0) or 0)
            else None
        )
        engine.fit(train_loader, eval_loader)
        # data-pipeline health epilogue: skips spent and host-side wait are
        # the two numbers an operator checks after a flaky-storage run
        skips = int(getattr(train_loader, "skips", 0) or 0)
        if skips:
            logger.warning(
                f"run finished with {skips} corrupt sample(s) skipped "
                "(data_skip events in the metrics stream — inspect the "
                "shard before the next run)"
            )
        stats_fn = getattr(train_loader, "stats", None)
        if callable(stats_fn):
            wait = stats_fn().get("data_wait_s", 0)
            if wait:
                logger.info(f"host data pipeline: {wait}s total step wait")
        # observatory epilogue: the run's memory watermark + compile tally
        # and the one-liner that turns this run's artifacts into a report
        from paddlefleetx_tpu.utils.model_stats import get_compile_watcher
        from paddlefleetx_tpu.utils.tracing import export_chrome_trace

        if engine._fit_peak_bytes:
            logger.info(
                f"memory watermark: {engine._fit_peak_bytes / (1 << 20):.0f} "
                "MiB peak this fit (per-record detail under 'mem')"
            )
        compiles = get_compile_watcher().snapshot()
        if compiles:
            total = sum(c.get("elapsed_s", 0.0) for c in compiles)
            logger.info(
                f"compile events: {len(compiles)} ({total:.1f}s backend "
                "compile) — retrace attribution rides the flight ring"
            )
        trace_path = export_chrome_trace()
        report_cmd = f"python tools/report.py --run-dir {output_dir}"
        if cfg.Engine.get("metrics_file"):
            report_cmd += f" --metrics {cfg.Engine.metrics_file}"
        if trace_path:
            report_cmd += f" --trace {trace_path}"
        logger.info(f"run report: {report_cmd} -o report.html")
        if engine.preempted:
            # final checkpoint already written (preemption / exit_after_save
            # path); exit 0 so the orchestrator relaunches with auto_resume
            logger.info("clean early exit: final checkpoint saved; exiting 0")
            return engine
        if cfg.Engine.save_load.get("save_steps"):
            engine.save()
    return engine


if __name__ == "__main__":
    main()
