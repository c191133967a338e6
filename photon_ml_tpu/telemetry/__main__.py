"""Telemetry CLI: ``python -m photon_ml_tpu.telemetry
<report|watch|serve-report|fleet-report>``.

``report <log>`` prints the per-phase / stage-span / overlap /
convergence / device / reconciliation report for a run's
``run_log.jsonl`` (see ``telemetry.report``); exit code 1 when the
span-vs-wall-clock reconciliation or the convergence sweep-odometer
check fails.

``watch <log>`` follows a LIVE, still-being-written run log (ISSUE
10): a refreshing status view — phase, per-stage progress/ETA, loss
trajectory, reliability counters, active alerts — that exits when the
run logs ``done`` (or ``--once`` for a single snapshot); see
``telemetry.watch``.

``serve-report <logs...>`` joins the serving fleet's sampled request
traces across processes by trace id (ISSUE 14) into a stage-level
latency-decomposition table (p50/p99 per stage, retry cost, dominant
stage per tail request) and optionally exports a Perfetto flow trace
(``--trace-out``); exit code 1 when no trace records are found or the
cross-process join falls below ``--join-threshold``; see
``telemetry.serve_report``.

``fleet-report <host_logs...>`` joins a multi-host training run's
per-host ``run_log.jsonl`` files (ISSUE 16) into one fleet view:
per-host chunks streamed / reductions / barrier-wait / peak RSS rows,
the barrier-agreement check (every host must count the same
reductions), and the fleet-wide sweep odometer (replicated solver
state ⇒ per-host odometers must agree and each must reconcile); exit
code 1 on any disagreement; see ``telemetry.fleet_report``.

All subcommands print one machine-parseable JSON object as the last
stdout line (the repo's CLI contract).
"""

from __future__ import annotations

import argparse
import sys

from photon_ml_tpu.telemetry import fleet_report as fleet_report_mod
from photon_ml_tpu.telemetry import serve_report as serve_report_mod
from photon_ml_tpu.telemetry import watch as watch_mod
from photon_ml_tpu.telemetry.report import report


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m photon_ml_tpu.telemetry",
        description=__doc__.split("\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)
    rp = sub.add_parser(
        "report", help="per-phase wall-clock tables, prefetcher overlap "
                       "efficiency, convergence + device accounting, "
                       "and the reconciliation checks")
    rp.add_argument("log", help="path to a run_log.jsonl")
    rp.add_argument("--threshold", type=float, default=0.9,
                    help="reconciliation pass threshold (default 0.9)")
    wp = sub.add_parser(
        "watch", help="follow a live run_log.jsonl: phase, per-stage "
                      "progress/ETA, loss trajectory, alerts; exits "
                      "when the run logs its done event")
    wp.add_argument("log", help="path to a (possibly still-being-"
                                "written) run_log.jsonl")
    wp.add_argument("--once", action="store_true",
                    help="print one snapshot and exit (scripting mode; "
                         "the JSON last line is the snapshot)")
    wp.add_argument("--interval", type=float,
                    default=watch_mod.DEFAULT_INTERVAL_S,
                    help="refresh cadence in seconds (default "
                         f"{watch_mod.DEFAULT_INTERVAL_S})")
    wp.add_argument("--max-wait-s", type=float, default=None,
                    help="give up following after this many seconds "
                         "without a done event (a killed run's log "
                         "stops growing but never finishes)")
    sp = sub.add_parser(
        "serve-report",
        help="join frontend + replica request traces by trace id into "
             "a cross-process stage-latency decomposition (p50/p99 "
             "per stage, retry cost, dominant stage per tail request)")
    sp.add_argument("logs", nargs="+",
                    help="serving run logs (the frontend's and each "
                         "replica's run_log JSONL; one server's log "
                         "also works — the join check is then N/A)")
    sp.add_argument("--join-threshold", type=float,
                    default=serve_report_mod.DEFAULT_JOIN_THRESHOLD,
                    help="minimum fraction of replica-side tail "
                         "requests that must match a frontend trace "
                         "(default "
                         f"{serve_report_mod.DEFAULT_JOIN_THRESHOLD})")
    sp.add_argument("--trace-out", default=None,
                    help="also write a Perfetto-loadable Chrome trace "
                         "with cross-process flow events here")
    fp = sub.add_parser(
        "fleet-report",
        help="join a multi-host training run's per-host run logs into "
             "one fleet view: per-host chunk/reduce/barrier-wait rows, "
             "the barrier-agreement check, and the fleet-wide sweep "
             "odometer")
    fp.add_argument("logs", nargs="+",
                    help="per-host run logs (each host_NNN/ output "
                         "subdir's run_log.jsonl)")
    args = p.parse_args(argv)
    if args.cmd == "fleet-report":
        result = fleet_report_mod.run_fleet_report(args.logs)
        return 0 if result["ok"] else 1
    if args.cmd == "serve-report":
        result = serve_report_mod.run_serve_report(
            args.logs, join_threshold=args.join_threshold,
            trace_out=args.trace_out)
        return 0 if result["ok"] else 1
    if args.cmd == "watch":
        snap = watch_mod.watch(args.log, once=args.once,
                               interval_s=args.interval,
                               max_wait_s=args.max_wait_s)
        return 0 if not snap["thread_exceptions"] else 1
    result = report(args.log, threshold=args.threshold)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
