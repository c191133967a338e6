"""GAME training driver: config file → trained, evaluated, saved models.

Reference counterpart: ``GameTrainingDriver``
(photon-client ``com.linkedin.photon.ml.cli.game.training`` [expected
path, mount unavailable — see SURVEY.md §2.8/§3.1]): parse params,
prepare feature maps, read train/validation data, build datasets, run
``GameEstimator.fit`` over the optimization grid, select/save models.

Usage::

    python -m photon_ml_tpu.cli.game_training_driver --config cfg.json

The classic single-GLM path (reference's legacy ``Driver``) is the
degenerate case: one fixed-effect coordinate, LIBSVM input — exactly how
the reference folded its pre-GAME trainer into GAME (SURVEY §3.3).
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

from photon_ml_tpu.config import (
    CoordinateKind,
    TrainingConfig,
    config_to_json,
    load_training_config,
)
from photon_ml_tpu.estimators.game_estimator import FitResult, GameEstimator
from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.io.dataset import (
    build_index_maps,
    detect_format,
    read_game_dataset,
)
from photon_ml_tpu.io.index_map import load_index_maps, save_index_maps
from photon_ml_tpu.io.libsvm import read_libsvm
from photon_ml_tpu.io.model_io import save_game_model
from photon_ml_tpu.utils.run_log import DEFAULT_FLUSH_EVERY_S, RunLogger


def _read_libsvm_dataset(path: str, config: TrainingConfig,
                         n_features: int | None = None) -> GameDataset:
    """LIBSVM → single-shard GameDataset (a1a-class fixtures, §3.3)."""
    fixed = [c for c in config.coordinates
             if c.kind == CoordinateKind.FIXED_EFFECT]
    if len(config.coordinates) != 1 or not fixed:
        raise ValueError(
            "LIBSVM input supports exactly one fixed-effect coordinate; "
            "use JSONL records for GAME configs"
        )
    shard = fixed[0].feature_shard
    rows, labels, dim = read_libsvm(path, n_features=n_features)
    return GameDataset(
        labels=labels, features={shard: rows}, entity_ids={},
        feature_dims={shard: dim},
    )


def prepare_data(config: TrainingConfig, log: RunLogger):
    """Read (+ index) train/validation data; the driver's ETL phase.

    Returns (train, validation, feature_maps, entity_maps); maps are
    None for LIBSVM input (indices are literal in the file).
    """
    fmt = detect_format(config.input_path, config.input_format)
    feature_maps = entity_maps = None
    if fmt == "libsvm":
        with log.timed("read_training_data", format=fmt):
            train = _read_libsvm_dataset(config.input_path, config)
        valid = None
        if config.validation_path:
            with log.timed("read_validation_data", format=fmt):
                valid = _read_libsvm_dataset(
                    config.validation_path, config,
                    n_features=train.feature_dim(
                        next(iter(train.features))),
                )
    else:
        shards = sorted({c.feature_shard for c in config.coordinates})
        entity_keys = sorted({c.entity_key for c in config.coordinates
                              if c.entity_key})
        with log.timed("prepare_feature_maps"):
            if config.index_dir:
                feature_maps, entity_maps = load_index_maps(config.index_dir)
            else:
                feature_maps, entity_maps = build_index_maps(
                    config.input_path, shards, entity_keys
                )
        dense = tuple(config.dense_feature_shards)
        with log.timed("read_training_data", format=fmt):
            # Training extends the entity maps with ids the prebuilt
            # maps miss; the extended maps are what gets persisted.
            train = read_game_dataset(
                config.input_path, feature_maps, entity_maps,
                dense_shards=dense, extend_entity_maps=True,
            )
        valid = None
        if config.validation_path:
            with log.timed("read_validation_data", format=fmt):
                valid = read_game_dataset(
                    config.validation_path, feature_maps, entity_maps,
                    dense_shards=dense,
                )

    if valid is None and config.validation_fraction > 0.0:
        rng = np.random.default_rng(config.seed)
        perm = rng.permutation(train.n)
        n_valid = int(round(train.n * config.validation_fraction))
        valid = train.take(perm[:n_valid])
        train = train.take(perm[n_valid:])
        log.event("validation_split", n_train=train.n, n_valid=valid.n)

    return train, valid, feature_maps, entity_maps


def _save_result(result: FitResult, estimator: GameEstimator,
                 model_dir: str) -> dict:
    save_game_model(result.model, estimator.task, model_dir)
    return {
        "model_dir": model_dir,
        "reg_weights": result.reg_weights,
        "evaluations": {ev.value: v for ev, v in result.evaluations.items()},
        # Per-CD-iteration validation trace (reference per-sweep
        # evaluator logging); [] when trained without validation data.
        "validation_history": [
            {str(getattr(ev, "value", ev)): float(v)
             for ev, v in entry.items()} if isinstance(entry, dict)
            else float(entry)
            for entry in result.validation_history
        ],
    }


def distributed_init_from_env() -> None:
    """Join the JAX coordination service before first backend use
    (multi-host scale-out, SURVEY §7 stage 9).  Coordinator address /
    process count / index come from JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID (mapped here — JAX only
    auto-detects managed clusters like TPU pods/SLURM).  Idempotent so
    a caller-initialized process doesn't crash."""
    import jax

    if jax.distributed.is_initialized():
        return
    from photon_ml_tpu.config import read_env

    kw = {}
    if read_env("JAX_COORDINATOR_ADDRESS"):
        kw["coordinator_address"] = read_env("JAX_COORDINATOR_ADDRESS")
    if read_env("JAX_NUM_PROCESSES"):
        kw["num_processes"] = int(read_env("JAX_NUM_PROCESSES"))
    if read_env("JAX_PROCESS_ID"):
        kw["process_id"] = int(read_env("JAX_PROCESS_ID"))
    jax.distributed.initialize(**kw)


def run(config: TrainingConfig, log: RunLogger | None = None) -> dict:
    """Full training pipeline; returns the written summary dict."""
    config.validate()
    # Warm path first: the persistent compilation cache must be wired
    # before any jit compiles.
    from photon_ml_tpu.cache import enable_compilation_cache

    enable_compilation_cache()
    if config.distributed_init:
        distributed_init_from_env()
    # Multi-host streaming (ISSUE 16): join the fleet if this process
    # was launched as one host of a sharded-streaming run (initialized
    # jax.distributed runtime → psum transport; PHOTON_FLEET_* env trio
    # → local tcp transport).  Each host then writes its OWN output
    # tree (run_log, summary, models, telemetry) under a host_NNN/
    # subdir — `telemetry fleet-report` joins the per-host logs into
    # the aggregated fleet view.
    from photon_ml_tpu.parallel import fleet

    fctx = fleet.initialize_from_env()
    if fctx is not None and fctx.is_fleet:
        config.output_dir = fleet.host_dir(config.output_dir, fctx)
    os.makedirs(config.output_dir, exist_ok=True)
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.telemetry import monitor as _mon

    # Context-managed logger lifecycle (ISSUE 7 satellite: the handle
    # used to leak on paths that bypassed close); the telemetry session
    # shares the logger so spans/heartbeats land in the same JSONL the
    # report CLI reads.  A RESUMED run appends: the stitched log (first
    # run's torn tail + the resumed run's events) is the forensic
    # record `telemetry report` reconciles segment by segment.
    # Cadence flushing (ISSUE 10): a driver log plausibly has a live
    # consumer (`telemetry watch`, kill forensics), so it trades the
    # per-line flush syscall for a bounded staleness window.
    # The monitor spans the WHOLE pipeline (ETL phases included), not
    # just the fit — the estimator's own maybe_monitor nests as a
    # no-op under this one.
    with (log or RunLogger(os.path.join(config.output_dir,
                                        "run_log.jsonl"),
                           mode=("a" if config.resume else "w"),
                           header=True,
                           run_info={"driver": "game_training",
                                     "telemetry": config.telemetry,
                                     "resume": config.resume,
                                     **({"fleet_host": fctx.host_id,
                                         "fleet_hosts": fctx.n_hosts,
                                         "fleet_transport": fctx.transport}
                                        if fctx is not None
                                        and fctx.is_fleet else {})},
                           flush_every_s=DEFAULT_FLUSH_EVERY_S)
          ) as log, \
            telemetry.maybe_session(
                config.telemetry,
                config.telemetry_dir or config.output_dir,
                run_logger=log), \
            _mon.maybe_monitor(
                config.monitor == "on", run_logger=log,
                status_port=config.status_port,
                every_s=config.monitor_every_s):
        return _run(config, log)


def _run(config: TrainingConfig, log: RunLogger) -> dict:
    log.event("config", config=json.loads(config_to_json(config)))

    train, valid, feature_maps, entity_maps = prepare_data(config, log)
    log.event("datasets", n_train=train.n,
              n_valid=(valid.n if valid is not None else 0))

    estimator = GameEstimator(config)
    if config.tuning is not None:
        if valid is None:
            raise ValueError(
                "hyperparameter tuning needs validation data "
                "(validation_path or validation_fraction)")
        with log.timed("fit", profile_dir=config.profile_dir,
                       mode="tuning", trials=config.tuning.n_trials):
            results = estimator.fit_tuned(train, valid, run_logger=log)
    else:
        with log.timed("fit", profile_dir=config.profile_dir):
            results = estimator.fit(train, validation=valid, run_logger=log)
    best = estimator.best(results)

    for i, r in enumerate(results):
        log.event("grid_result", index=i, reg_weights=r.reg_weights,
                  evaluations={ev.value: v
                               for ev, v in r.evaluations.items()},
                  best=(r is best))

    # Identity, not ==: FitResult equality would recurse into jax arrays.
    summary = {"models": [],
               "best_index": next(i for i, r in enumerate(results)
                                  if r is best)}
    with log.timed("save_models", mode=config.model_output_mode):
        if config.model_output_mode == "ALL":
            for i, r in enumerate(results):
                summary["models"].append(_save_result(
                    r, estimator,
                    os.path.join(config.output_dir, f"model_{i}")))
        else:  # BEST (EXPLICIT reduces to BEST without a tuning run)
            summary["models"].append(_save_result(
                best, estimator, os.path.join(config.output_dir, "model")))
        if feature_maps is not None:
            save_index_maps(os.path.join(config.output_dir, "index_maps"),
                            feature_maps, entity_maps)

    with open(os.path.join(config.output_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=2)
    with open(os.path.join(config.output_dir, "config.json"), "w") as f:
        f.write(config_to_json(config))
    log.event("done", best_index=summary["best_index"])
    return summary


def main(argv: list[str] | None = None) -> dict:
    parser = argparse.ArgumentParser(
        description="photon-ml-tpu GAME training driver"
    )
    parser.add_argument("--config", required=True,
                        help="training config JSON file.  Its input_path "
                             "names JSON-lines or Avro records of label, "
                             "weight, offset, features and ids; a record's "
                             "`offset` (a Poisson model's log exposure, a "
                             "prior model's margin) enters every TRAINING "
                             "margin as it enters validation and scoring; "
                             "cd_fused refuses a dataset that has any")
    parser.add_argument("--output-dir", default=None,
                        help="override config output_dir")
    parser.add_argument("--spill-dir", default=None,
                        help="override config spill_dir: out-of-core "
                             "chunk store directory (default also "
                             "$PHOTON_ML_TPU_SPILL_DIR)")
    parser.add_argument("--host-max-resident", type=int, default=None,
                        help="override config host_max_resident: "
                             "decoded chunks kept live in host RAM "
                             "when spilling")
    parser.add_argument("--prefetch-depth", type=int, default=None,
                        help="override config prefetch_depth: chunks "
                             "prefetched disk->host->device ahead of "
                             "compute (0 disables the thread)")
    parser.add_argument("--re-chunk-entities", type=int, default=None,
                        help="override config re_chunk_entities: "
                             "out-of-core random-effect training — "
                             "entities per streamed chunk per size "
                             "bucket (requires a spill dir)")
    parser.add_argument("--re-retirement", choices=("on", "off"),
                        default=None,
                        help="override config re_retirement: freeze "
                             "converged entities between CD sweeps "
                             "(streamed random effects only)")
    parser.add_argument("--cd-fused", choices=("on", "off"),
                        default=None,
                        help="override config cd_fused: one streamed "
                             "store pass per CD cycle accumulates every "
                             "coordinate's statistics (Jacobi solves "
                             "against cycle-start offsets); requires "
                             "chunk_rows and smooth regularization")
    parser.add_argument("--telemetry", choices=("off", "metrics", "trace"),
                        default=None,
                        help="override config telemetry: pipeline "
                             "spans/metrics (metrics) + Chrome "
                             "trace.json export (trace); analyze with "
                             "python -m photon_ml_tpu.telemetry report")
    parser.add_argument("--telemetry-dir", default=None,
                        help="override config telemetry_dir (default: "
                             "the output dir)")
    parser.add_argument("--monitor", choices=("off", "on"),
                        default=None,
                        help="override config monitor: live progress/"
                             "ETA snapshots + online anomaly alerts in "
                             "the run log; follow with python -m "
                             "photon_ml_tpu.telemetry watch "
                             "<run_log.jsonl>")
    parser.add_argument("--monitor-every-s", type=float, default=None,
                        help="override config monitor_every_s: "
                             "snapshot/alert cadence in seconds")
    parser.add_argument("--status-port", type=int, default=None,
                        help="serve GET /status (JSON) and /metrics "
                             "(Prometheus text) from a localhost "
                             "thread on this port (0 = ephemeral, "
                             "logged as a status_server event); "
                             "implies --monitor on")
    parser.add_argument("--checkpoint-dir", default=None,
                        help="override config checkpoint_dir: "
                             "reliability checkpoints (CD sweep state, "
                             "mid-solve solver state) land here")
    parser.add_argument("--resume", action="store_true", default=None,
                        help="resume from the most advanced checkpoint "
                             "in checkpoint_dir (run log appends; "
                             "analyze the stitched log with "
                             "python -m photon_ml_tpu.telemetry report)")
    parser.add_argument("--checkpoint-every-sweeps", type=int,
                        default=None,
                        help="override config checkpoint_every_sweeps: "
                             "CD sweep-boundary snapshot cadence")
    parser.add_argument("--checkpoint-every-solver-iters", type=int,
                        default=None,
                        help="override config "
                             "checkpoint_every_solver_iters: streaming-"
                             "solver mid-solve snapshot cadence (0 = "
                             "sweep boundaries only)")
    args = parser.parse_args(argv)
    config = load_training_config(args.config)
    if args.output_dir:
        config.output_dir = args.output_dir
    if args.spill_dir is not None:
        config.spill_dir = args.spill_dir
    if args.host_max_resident is not None:
        config.host_max_resident = args.host_max_resident
    if args.prefetch_depth is not None:
        config.prefetch_depth = args.prefetch_depth
    if args.re_chunk_entities is not None:
        config.re_chunk_entities = args.re_chunk_entities
    if args.re_retirement is not None:
        config.re_retirement = args.re_retirement == "on"
    if args.cd_fused is not None:
        config.cd_fused = args.cd_fused == "on"
    if args.telemetry is not None:
        config.telemetry = args.telemetry
    if args.telemetry_dir is not None:
        config.telemetry_dir = args.telemetry_dir
    if args.monitor is not None:
        config.monitor = args.monitor
    if args.monitor_every_s is not None:
        config.monitor_every_s = args.monitor_every_s
    if args.status_port is not None:
        config.status_port = args.status_port
    if args.checkpoint_dir is not None:
        config.checkpoint_dir = args.checkpoint_dir
    if args.resume is not None:
        config.resume = args.resume
    if args.checkpoint_every_sweeps is not None:
        config.checkpoint_every_sweeps = args.checkpoint_every_sweeps
    if args.checkpoint_every_solver_iters is not None:
        config.checkpoint_every_solver_iters = (
            args.checkpoint_every_solver_iters)
    # Re-validate with the overrides applied (the spill/streamed-RE
    # cross-field rules must hold for the effective config).
    config.validate()
    return run(config)


if __name__ == "__main__":
    main()
