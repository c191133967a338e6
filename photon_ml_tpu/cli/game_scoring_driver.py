"""GAME scoring driver: saved model + data → scores (+ evaluation).

Reference counterpart: ``GameScoringDriver``
(photon-client ``com.linkedin.photon.ml.cli.game.scoring`` [expected
path, mount unavailable — see SURVEY.md §2.8/§3.2]): load model Avro +
data, ``GameTransformer.transform``, write ``ScoringResultAvro``,
optionally evaluate against true labels.

Usage::

    python -m photon_ml_tpu.cli.game_scoring_driver --config score.json

Output is an ``.npz`` with raw margins (``scores``), mean-space
predictions (``predictions`` — sigmoid/identity/exp per task), and the
input ``labels`` — the same fields ``ScoringResultAvro`` carries —
plus ``evaluation.json`` next to it when evaluators are configured.
An ``output_path`` ending in ``.avro`` writes reference-parity
``ScoringResultAvro`` records instead.

Two execution paths (ISSUE 4):

- ``score_chunk_rows`` unset: the resident per-coordinate
  ``GameTransformer.transform`` (validation-sized data).  The mean
  function is applied chunk-wise and Avro output is written in
  per-block batches either way — no full-array device round trip, no
  per-row Python encode loop.
- ``score_chunk_rows`` set: the streaming fused pipeline
  (``estimators.streaming_scorer``) — one pass in fixed-shape chunks,
  one fused device program per chunk, overlapped disk→host→device
  prefetch (``spill_dir``/``host_max_resident``/``prefetch_depth``),
  sinks and evaluators fed chunk-wise so nothing ``[n]``-sized stays
  resident.
"""

from __future__ import annotations

import argparse
import json
import os

import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.config import ScoringConfig, load_scoring_config
from photon_ml_tpu.estimators.game_transformer import GameTransformer
from photon_ml_tpu.evaluation import evaluate
from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.io.dataset import detect_format, read_game_dataset
from photon_ml_tpu.io.index_map import load_index_maps
from photon_ml_tpu.io.libsvm import read_libsvm
from photon_ml_tpu.io.model_io import load_game_model
from photon_ml_tpu.models.game import FixedEffectModel, RandomEffectModel
from photon_ml_tpu.utils.run_log import DEFAULT_FLUSH_EVERY_S, RunLogger

# Chunk size for the resident path's chunk-wise mean application — the
# device sees [MEAN_CHUNK] slices, never the full margins array.
_MEAN_CHUNK = 1 << 20


def _read_data(config: ScoringConfig, model, log: RunLogger) -> GameDataset:
    fmt = detect_format(config.input_path, config.input_format)
    if fmt == "libsvm":
        fixed = [m for m in model.models.values()
                 if isinstance(m, FixedEffectModel)]
        if len(model.models) != 1 or not fixed:
            raise ValueError("LIBSVM scoring needs a single fixed-effect "
                             "model; use JSONL records for GAME models")
        shard = fixed[0].feature_shard
        # Model width fixes the feature space (minus the intercept column
        # the estimator appended at training time).
        dim = len(np.asarray(fixed[0].coefficients.means))
        if fixed[0].intercept:
            dim -= 1
        with log.timed("read_scoring_data", format=fmt):
            rows, labels, _ = read_libsvm(config.input_path, n_features=dim)
        return GameDataset(labels=labels, features={shard: rows},
                           entity_ids={}, feature_dims={shard: dim})

    index_dir = config.index_dir or os.path.join(
        os.path.dirname(os.path.abspath(config.model_dir)), "index_maps")
    with log.timed("prepare_feature_maps"):
        feature_maps, entity_maps = load_index_maps(index_dir)
    # Non-projected random effects score with a dense per-entity shard;
    # the model knows which those are — no config repetition required.
    dense = set(config.dense_feature_shards)
    dense.update(
        m.feature_shard for m in model.models.values()
        if isinstance(m, RandomEffectModel) and m.projection is None
    )
    with log.timed("read_scoring_data", format=fmt):
        return read_game_dataset(
            config.input_path, feature_maps, entity_maps,
            dense_shards=tuple(dense),
        )


def _mean_chunked(task, margins: np.ndarray) -> np.ndarray:
    """Mean-space predictions, applied device-chunk-wise (ISSUE 4
    satellite: the full-margins ``device_put`` round trip served only
    to evaluate an elementwise function)."""
    out = np.empty(len(margins), np.float32)
    for lo in range(0, len(margins), _MEAN_CHUNK):
        hi = min(lo + _MEAN_CHUNK, len(margins))
        out[lo:hi] = np.asarray(task.loss.mean(jnp.asarray(margins[lo:hi])))
    return out


def _make_sinks(config: ScoringConfig, n: int, entity_keys) -> list:
    if config.output_path.endswith(".avro"):
        from photon_ml_tpu.io.score_sink import AvroScoreSink

        return [AvroScoreSink(config.output_path,
                              ids_keys=tuple(entity_keys))]
    from photon_ml_tpu.io.score_sink import NpzScoreSink

    # np.savez appends ".npz" to extensionless paths; the streamed sink
    # must land on the same file name as the resident path.
    path = config.output_path
    if not path.endswith(".npz"):
        path += ".npz"
    return [NpzScoreSink(path, n)]


def run(config: ScoringConfig, log: RunLogger | None = None) -> dict:
    # Wire the persistent compilation cache before the scoring programs
    # compile.
    from photon_ml_tpu.cache import enable_compilation_cache

    enable_compilation_cache()
    config.validate()
    out_dir = os.path.dirname(os.path.abspath(config.output_path))
    os.makedirs(out_dir, exist_ok=True)
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.telemetry import monitor as _mon

    # Context-managed logger lifecycle + shared telemetry session (see
    # the training driver): spans/heartbeats land in scoring_log.jsonl,
    # trace.json (telemetry=trace) in telemetry_dir.  Cadence flushing
    # + the live monitor (ISSUE 10): `telemetry watch` follows the
    # scoring log while the pass runs.
    with (log or RunLogger(os.path.join(out_dir,
                                        "scoring_log.jsonl"),
                           run_info={"driver": "game_scoring",
                                     "telemetry": config.telemetry},
                           flush_every_s=DEFAULT_FLUSH_EVERY_S)
          ) as log, \
            telemetry.maybe_session(
                config.telemetry, config.telemetry_dir or out_dir,
                run_logger=log), \
            _mon.maybe_monitor(
                config.monitor == "on", run_logger=log,
                status_port=config.status_port,
                every_s=config.monitor_every_s):
        return _run(config, log)


def _run_streamed(config: ScoringConfig, model, task, data,
                  log: RunLogger) -> dict:
    from photon_ml_tpu.data.chunk_store import resolve_spill_dir
    from photon_ml_tpu.estimators.streaming_scorer import (
        StreamingGameScorer,
    )
    from photon_ml_tpu.evaluation.streaming import make_streaming_evaluator

    scorer = StreamingGameScorer(
        model=model, task=task,
        chunk_rows=config.score_chunk_rows,
        spill_dir=resolve_spill_dir(config.spill_dir),
        host_max_resident=config.host_max_resident,
        prefetch_depth=config.prefetch_depth)
    sinks = _make_sinks(config, data.n, data.entity_ids)
    evaluators = [make_streaming_evaluator(ev)
                  for ev in config.evaluators]
    with log.timed("transform_streamed",
                   chunk_rows=config.score_chunk_rows):
        result = scorer.score(data, sinks=sinks, evaluators=evaluators)
    log.event("stream_stats",
              **{k: v for k, v in result.items()
                 if k not in ("evaluation",)})
    return result["evaluation"]


def _run(config: ScoringConfig, log: RunLogger) -> dict:
    out_dir = os.path.dirname(os.path.abspath(config.output_path))
    with log.timed("load_model"):
        model, task = load_game_model(config.model_dir)
    data = _read_data(config, model, log)
    log.event("dataset", n=data.n)

    if config.score_chunk_rows is not None:
        evaluation = _run_streamed(config, model, task, data, log)
    else:
        transformer = GameTransformer(model=model, task=task)
        with log.timed("transform"):
            margins = transformer.transform(data)
        predictions = _mean_chunked(task, margins)

        if config.output_path.endswith(".avro"):
            # Reference-parity output: ScoringResultAvro records,
            # written one container block per chunk (the per-row
            # dict-building Python loop is gone — ISSUE 4).  Same sink
            # wiring as the streamed path (_make_sinks), so the two
            # paths cannot diverge.
            sink = _make_sinks(config, data.n, data.entity_ids)[0]
            try:
                for lo in range(0, data.n, _MEAN_CHUNK):
                    hi = min(lo + _MEAN_CHUNK, data.n)
                    sink.write(lo, hi, margins[lo:hi],
                               predictions[lo:hi], data.labels[lo:hi],
                               ids={k: v[lo:hi]
                                    for k, v in data.entity_ids.items()})
                sink.close()
            except BaseException:
                sink.abort()
                raise
        else:
            np.savez(config.output_path, scores=margins,
                     predictions=predictions, labels=data.labels)

        evaluation = {}
        if config.evaluators:
            labels = jnp.asarray(data.labels.astype(np.float32))
            weights = jnp.asarray(data.weight_array())
            for ev in config.evaluators:
                scores = jnp.asarray(margins)
                if ev.value in ("RMSE", "SQUARED_LOSS"):
                    scores = jnp.asarray(predictions)
                evaluation[ev.value] = float(
                    evaluate(ev, scores, labels, weights))

    if config.evaluators:
        with open(os.path.join(out_dir, "evaluation.json"), "w") as f:
            json.dump(evaluation, f, indent=2)
        log.event("evaluation", **evaluation)

    log.event("done", output=config.output_path)
    return {"output_path": config.output_path, "n": int(data.n),
            "evaluation": evaluation}


def main(argv: list[str] | None = None) -> dict:
    parser = argparse.ArgumentParser(
        description="photon-ml-tpu GAME scoring driver"
    )
    parser.add_argument("--config", required=True,
                        help="scoring config JSON file")
    parser.add_argument("--score-chunk-rows", type=int, default=None,
                        help="override: chunk size for the streaming "
                             "fused scoring pipeline")
    parser.add_argument("--spill-dir", default=None,
                        help="override: disk tier for prepared score "
                             "chunks (default $PHOTON_ML_TPU_SPILL_DIR)")
    parser.add_argument("--host-max-resident", type=int, default=None,
                        help="override: LRU host window (chunks)")
    parser.add_argument("--prefetch-depth", type=int, default=None,
                        help="override: background prefetch depth "
                             "(0 = synchronous)")
    parser.add_argument("--telemetry", choices=("off", "metrics", "trace"),
                        default=None,
                        help="override config telemetry: pipeline "
                             "spans/metrics (metrics) + Chrome "
                             "trace.json export (trace); analyze with "
                             "python -m photon_ml_tpu.telemetry report")
    parser.add_argument("--telemetry-dir", default=None,
                        help="override config telemetry_dir (default: "
                             "the output file's directory)")
    parser.add_argument("--monitor", choices=("off", "on"),
                        default=None,
                        help="override config monitor: live progress/"
                             "ETA snapshots + online anomaly alerts; "
                             "follow with python -m photon_ml_tpu"
                             ".telemetry watch <scoring_log.jsonl>")
    parser.add_argument("--monitor-every-s", type=float, default=None,
                        dest="monitor_every_s",
                        help="override config monitor_every_s: "
                             "snapshot/alert cadence in seconds")
    parser.add_argument("--status-port", type=int, default=None,
                        dest="status_port",
                        help="serve GET /status + /metrics from a "
                             "localhost thread on this port (0 = "
                             "ephemeral); implies --monitor on")
    args = parser.parse_args(argv)
    config = load_scoring_config(args.config)
    for name in ("score_chunk_rows", "spill_dir", "host_max_resident",
                 "prefetch_depth", "telemetry", "telemetry_dir",
                 "monitor", "monitor_every_s", "status_port"):
        val = getattr(args, name)
        if val is not None:
            setattr(config, name, val)
    return run(config)   # run() re-validates (the overrides included)


if __name__ == "__main__":
    main()
