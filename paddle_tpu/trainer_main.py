"""paddle_tpu trainer CLI — `python -m paddle_tpu.trainer_main --config=...`.

TPU-native analog of the `paddle_trainer` binary (ref:
paddle/trainer/TrainerMain.cpp:36-110: flag parsing, config load, job
dispatch train/test/checkgrad/time).  The pserver self-hosting flags are gone
— distribution is a mesh + jax.distributed, not a server fleet.
"""

from __future__ import annotations

import sys

import jax

from paddle_tpu.config.parser import parse_config
from paddle_tpu.parallel.mesh import mesh_from_flag
from paddle_tpu.trainer.trainer import Trainer
from paddle_tpu.utils import (FLAGS, enable_compile_cache, get_logger,
                              parse_flags)

log = get_logger("main")


def main(argv=None) -> int:
    rest = parse_flags(argv)
    if not FLAGS.config:
        print("usage: python -m paddle_tpu.trainer_main --config=<config.py> "
              "[--job=train|test|checkgrad|time] [--num_passes=N] "
              "[--save_dir=DIR] [--config_args=k=v,...] [--mesh_shape=data:8] "
              "[--steps_per_dispatch=K] [--detect_nan] [--profile_dir=DIR] "
              "[--show_parameter_stats_period=N]", file=sys.stderr)
        return 2
    enable_compile_cache()

    if FLAGS.coordinator_address:
        from paddle_tpu.parallel.mesh import init_distributed
        init_distributed(FLAGS.coordinator_address, FLAGS.num_processes,
                         FLAGS.process_id)
        log.info("joined cluster as process %d/%d (coordinator %s)",
                 FLAGS.process_id, FLAGS.num_processes,
                 FLAGS.coordinator_address)
    devs = jax.devices()
    log.info("devices: %d x %s (platform=%s)", len(devs),
             devs[0].device_kind, devs[0].platform)

    if FLAGS.detect_nan:
        # FP-anomaly trapping (ref: feenableexcept(FE_INVALID|...) at trainer
        # start, TrainerMain.cpp:97; utils/Excepts.h): XLA re-runs the
        # offending computation uncompiled and raises at the bad primitive
        jax.config.update("jax_debug_nans", True)

    try:
        config = parse_config(FLAGS.config, FLAGS.config_args)
    except Exception as e:   # noqa: BLE001 — configs run arbitrary user code
        # ANY failure while parsing/executing the config file is a usage
        # error (exit 2), not a job failure (exit 1) — wrapper scripts
        # branch on the distinction; exc_info keeps the config-side
        # traceback visible so the offending statement is findable
        log.error("failed to parse config %s: %s: %s", FLAGS.config,
                  type(e).__name__, e, exc_info=True)
        return 2
    log.info("parsed config %s: %d layers, %d parameters", FLAGS.config,
             len(config.model_config.layers), len(config.model_config.parameters))
    mesh = mesh_from_flag(FLAGS.mesh_shape) if FLAGS.mesh_shape else None
    if mesh is not None:
        log.info("mesh: %s over %d devices", dict(zip(mesh.axis_names, mesh.devices.shape)),
                 mesh.devices.size)

    trainer = Trainer(config, seed=FLAGS.seed, mesh=mesh)
    if FLAGS.init_model_path:
        trainer.load(FLAGS.init_model_path)
        log.info("loaded initial model from %s", FLAGS.init_model_path)

    if FLAGS.profile_dir:
        # device-side tracing (ref: REGISTER_TIMER/WITH_TIMER Stat.h:130-256
        # + hl_profiler_start/end -> jax.profiler traces viewable in
        # tensorboard/xprof)
        jax.profiler.start_trace(FLAGS.profile_dir)

    job = FLAGS.job
    try:
        if job == "train":
            trainer.train(num_passes=FLAGS.num_passes, log_period=FLAGS.log_period,
                          save_dir=FLAGS.save_dir or None,
                          saving_period=FLAGS.saving_period)
        elif job == "test":
            if trainer.config.test_data_config is None:
                log.error("--job=test: this config declares no test data "
                          "source — add define_py_data_sources2("
                          "test_list=...) (ref: TrainerMain.cpp)")
                return 2
            stats = trainer.test()
            log.info("test result: %s", stats)
        elif job == "time":
            stats = trainer.benchmark(trainer.train_batches())
            log.info("benchmark: %.1f samples/sec (%d samples in %.2fs)",
                     stats["samples_per_sec"], stats["samples"], stats["seconds"])
        elif job == "checkgrad":
            batch = next(iter(trainer.train_batches()), None)
            if batch is None:
                log.error("checkgrad: data source produced no batches")
                return 2
            errors = trainer.check_gradient(
                batch, refine_threshold=FLAGS.checkgrad_bar)
            worst = max(errors.values(), default=0.0)
            log.info("checkgrad: %d parameters, worst max_rel_err=%.3e",
                     len(errors), worst)
            if worst > FLAGS.checkgrad_bar:
                log.error("gradient check FAILED")
                return 1
        else:
            log.error("unknown --job=%s", job)
            return 2
    finally:
        if FLAGS.profile_dir:
            jax.profiler.stop_trace()
            log.info("profiler trace written to %s", FLAGS.profile_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
