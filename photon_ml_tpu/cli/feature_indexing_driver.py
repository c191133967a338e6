"""Feature indexing driver: scan data, build + persist index maps.

Reference counterpart: ``FeatureIndexingDriver``
(photon-client [expected path, mount unavailable — see SURVEY.md
§2.8/§3.4]): a dedicated Spark job that collects distinct ``(name,
term)`` feature keys per shard and writes one PalDB store per (shard,
partition) for executors to mmap.

Here: one host pass over the JSONL records → deterministic sorted-order
JSON maps per feature shard and per entity key (see
``photon_ml_tpu.io.index_map``).  Pre-building maps lets training
(``index_dir`` config field) and scoring skip the scan and guarantees
train/score index agreement across datasets.

Usage::

    python -m photon_ml_tpu.cli.feature_indexing_driver \
        --input data.jsonl --output-dir maps/ [--shards global user_re]
"""

from __future__ import annotations

import argparse

from photon_ml_tpu.io.dataset import build_index_maps
from photon_ml_tpu.io.index_map import save_index_maps
from photon_ml_tpu.utils.run_log import RunLogger


def run(input_path: str, output_dir: str,
        shards: list[str] | None = None,
        entity_keys: list[str] | None = None,
        log: RunLogger | None = None,
        telemetry_mode: str = "off",
        monitor: str = "off",
        status_port: int | None = None) -> dict:
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.telemetry import monitor as _mon

    # Context-managed logger + optional telemetry session (the driver
    # knob discipline of the other two drivers): the scan phase becomes
    # a span and the summary/trace land under the output dir.  The
    # monitor/status knobs match too (ISSUE 10) — a large scan is a
    # silent single phase without them.
    with (log or RunLogger()) as log, \
            telemetry.maybe_session(telemetry_mode, output_dir,
                                    run_logger=log), \
            _mon.maybe_monitor(monitor == "on", run_logger=log,
                               status_port=status_port):
        with log.timed("build_index_maps", input=input_path):
            feature_maps, entity_maps = build_index_maps(
                input_path, shards, entity_keys
            )
        save_index_maps(output_dir, feature_maps, entity_maps)
        sizes = {
            "features": {s: len(m) for s, m in feature_maps.items()},
            "entities": {k: len(m) for k, m in entity_maps.items()},
        }
        log.event("index_maps_written", output_dir=output_dir, **sizes)
        return sizes


def main(argv: list[str] | None = None) -> dict:
    parser = argparse.ArgumentParser(
        description="photon-ml-tpu feature indexing driver"
    )
    parser.add_argument("--input", required=True, help="JSONL data file")
    parser.add_argument("--output-dir", required=True)
    parser.add_argument("--shards", nargs="*", default=None,
                        help="feature shards to index (default: all)")
    parser.add_argument("--entity-keys", nargs="*", default=None,
                        help="entity id keys to index (default: all)")
    parser.add_argument("--telemetry",
                        choices=("off", "metrics", "trace"),
                        default="off",
                        help="pipeline telemetry for the scan phase "
                             "(summary/trace land in --output-dir)")
    parser.add_argument("--monitor", choices=("off", "on"),
                        default="off",
                        help="live progress snapshots + online alerts "
                             "in the run log (ISSUE 10)")
    parser.add_argument("--status-port", type=int, default=None,
                        help="serve GET /status + /metrics from a "
                             "localhost thread on this port (0 = "
                             "ephemeral); implies --monitor on")
    args = parser.parse_args(argv)
    return run(args.input, args.output_dir, args.shards,
               args.entity_keys, telemetry_mode=args.telemetry,
               monitor=args.monitor, status_port=args.status_port)


if __name__ == "__main__":
    main()
