"""Run logging: structured JSONL event log + phase wall-clock timers.

Reference counterparts: ``PhotonLogger`` (a log file written to the
output dir in addition to log4j) and the ``Timed { }`` driver-phase
timer utility (photon-client/photon-api utils [expected paths, mount
unavailable — see SURVEY.md §5.1/§5.5]).

The rebuild upgrades free-text logs to structured JSONL — one event per
line with a monotonic timestamp — so convergence traces and phase
timings are machine-readable (the reference's observability gap).  The
same events also go to the stdlib logger for human eyes.

Since ISSUE 7 the logger is the telemetry tier's event channel too:
``event`` is thread-safe (heartbeats arrive from prefetch/sink
threads), ``timed`` phases double as telemetry spans when a session is
active, and the file handle has a real lifecycle — ``close()``,
context-manager support, and an ``atexit`` flush fallback so an
abandoned logger can no longer leak its handle (or its last buffered
events) on interpreter exit.
"""

from __future__ import annotations

import atexit
import contextlib
import json
import logging
import os
import sys
import threading
import time
import uuid

logger = logging.getLogger("photon_ml_tpu")

# run_header schema version (ISSUE 8): bump when header fields change
# meaning; the report CLIs key their parsing on it and must
# tolerate ABSENCE entirely (pre-ISSUE-8 logs have no header).
RUN_LOG_SCHEMA = 1

# Cadence-flush default for the drivers (ISSUE 10): a live consumer
# (`telemetry watch`, crash forensics) sees events at most this stale,
# while hot instrumented paths stop paying one flush syscall per line.
DEFAULT_FLUSH_EVERY_S = 2.0

# Events a live consumer (or a post-mortem) must never find missing:
# flushed immediately regardless of the cadence.  ``progress`` is
# already cadence-throttled at the monitor, so flushing each one costs
# nothing extra and keeps `watch` within one snapshot cadence of truth.
_FLUSH_NOW = frozenset({
    "run_header", "alert", "thread_exception", "progress",
    "phase_start", "phase_end", "telemetry_summary", "monitor_summary",
    "status_server", "done",
})


def _runtime_info() -> dict:
    """Best-effort runtime facts for the header: jax version/platform
    only when jax is ALREADY imported (a header must never pull a
    backend into a host-only driver), configured-platform string over
    backend init for the same reason."""
    info = {
        "schema": RUN_LOG_SCHEMA,
        "run_id": uuid.uuid4().hex[:12],
        "argv": list(sys.argv),
        "pid": os.getpid(),
        "host_platform": sys.platform,
    }
    jax = sys.modules.get("jax")
    if jax is not None:
        info["jax"] = getattr(jax, "__version__", None)
        try:
            platforms = jax.config.jax_platforms
        except Exception:
            platforms = None
        if platforms:
            info["jax_platforms"] = platforms
    return info


class RunLogger:
    """JSONL event sink; the reference's PhotonLogger role.

    Events: ``{"t": <seconds-since-start>, "event": <kind>, ...}``.
    A ``None`` path makes it a pure stdlib-logging sink (tests, library
    use); drivers point it at ``<output_dir>/run_log.jsonl``.
    """

    def __init__(self, path: str | None = None, mode: str = "w",
                 run_info: dict | None = None,
                 header: bool | None = None,
                 flush_every_s: float | None = None):
        """``mode="w"`` (default) makes each run's log self-contained —
        rerunning into the same output dir must not interleave events
        from prior runs; pass ``"a"`` to accumulate deliberately.

        A schema-versioned ``run_header`` event (run id, argv, jax
        version, platform — plus caller facts via ``run_info``, e.g.
        the telemetry mode) is written as the FIRST JSONL line of every
        fresh file; append mode skips it by default (the original
        header stands).  ``header`` overrides that default: a RESUMED
        driver run appends WITH a header, so the stitched log carries
        one ``run_header`` per process segment and ``telemetry
        report`` can reconcile the segments separately (their clocks
        restart at each header).  ``report`` consumes it and
        tolerates its absence in pre-existing logs.

        ``flush_every_s`` (ISSUE 10): None (default) flushes after
        EVERY event — maximal freshness for library/test use; a
        positive cadence batches flushes so a hot instrumented path
        pays one syscall per cadence window instead of per line, while
        ``_FLUSH_NOW`` event kinds (headers, alerts, progress
        snapshots, thread deaths, phase boundaries) still flush
        immediately — a live ``telemetry watch`` and a kill-forensic
        read both stay current.  Drivers pass
        ``DEFAULT_FLUSH_EVERY_S``."""
        self.path = path
        self._t0 = time.monotonic()
        self._f = None
        if flush_every_s is not None and flush_every_s < 0:
            raise ValueError(
                f"flush_every_s must be >= 0, got {flush_every_s!r}")
        self._flush_every_s = flush_every_s
        self._last_flush = time.monotonic()
        self.run_info = dict(run_info or {})
        # Events arrive from pipeline threads too (telemetry heartbeats,
        # span merges): one lock keeps lines whole and the handle state
        # coherent (photon-lint unlocked-shared-write contract).
        self._lock = threading.Lock()
        if path is not None:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            self._f = open(path, mode)
            if mode == "a":
                # A killed predecessor can leave a TORN final line with
                # no newline; appending straight after it would fuse
                # this run's first event into the garbage.  Terminate
                # the tail so the stitch is line-clean (ISSUE 9).
                torn = False
                try:
                    with open(path, "rb") as tail:
                        tail.seek(0, os.SEEK_END)
                        if tail.tell() > 0:
                            tail.seek(-1, os.SEEK_END)
                            torn = tail.read(1) != b"\n"
                    if torn:
                        self._f.write("\n")
                except OSError:  # photon-lint: disable=swallowed-exception (tail probe is best-effort; worst case is one fused line, the pre-fix behavior)
                    pass
            # Flush fallback: a logger abandoned without close() (the
            # pre-ISSUE-7 driver bug) still lands its buffered tail on
            # interpreter exit.  Unregistered again in close().
            atexit.register(self.close)
            if header if header is not None else mode == "w":
                self.event("run_header", **_runtime_info(),
                           **self.run_info)

    def now(self) -> float:
        """Seconds on this logger's monotonic clock (the ``t`` field);
        telemetry spans stamp themselves on the same clock."""
        return time.monotonic() - self._t0

    def event(self, kind: str, **fields) -> None:
        rec = {"t": round(self.now(), 6), "event": kind}
        rec.update(fields)
        with self._lock:
            if self._f is not None:
                self._f.write(json.dumps(rec) + "\n")
                now_m = time.monotonic()
                if (not self._flush_every_s or kind in _FLUSH_NOW
                        or now_m - self._last_flush
                        >= self._flush_every_s):
                    self._f.flush()
                    self._last_flush = now_m
        logger.info("%s %s", kind, fields)

    def flush(self) -> None:
        """Force buffered events to disk (the cadence path flushes on
        its own; this is for callers handing the file to a reader)."""
        with self._lock:
            if self._f is not None:
                self._f.flush()
                self._last_flush = time.monotonic()

    @contextlib.contextmanager
    def timed(self, phase: str, profile_dir: str | None = None, **fields):
        """The reference's ``Timed { }``: log phase start/end + duration.

        ``profile_dir``: when set, the phase also runs under
        ``jax.profiler.trace`` — a TensorBoard/XProf device trace lands
        there (SURVEY §5.1: tracing is a first-class aux subsystem).

        When a telemetry session is active the phase is also a span
        (cat ``phase``), so driver phases appear on the trace timeline
        and in the report's reconciliation alongside the streaming
        tier's stage spans.
        """
        from photon_ml_tpu import telemetry
        from photon_ml_tpu.telemetry import monitor as _monitor

        self.event("phase_start", phase=phase, **fields)
        # The live monitor's /status "phase" field tracks the innermost
        # open driver phase (no-op when monitoring is off, ISSUE 10).
        _monitor.phase_begin(phase)
        start = time.monotonic()
        prof = contextlib.nullcontext()
        if profile_dir:
            import jax

            prof = jax.profiler.trace(profile_dir)
        try:
            with telemetry.span(phase, cat="phase"), prof:
                yield
        finally:
            _monitor.phase_end(phase)
            self.event(
                "phase_end", phase=phase,
                duration_s=round(time.monotonic() - start, 6),
                **({"profile_dir": profile_dir} if profile_dir else {}),
                **fields,
            )

    def close(self) -> None:
        """Flush and release the file handle.  Idempotent (also runs
        as the atexit fallback)."""
        with self._lock:
            f, self._f = self._f, None
        if f is not None:
            f.close()
            # An explicitly closed logger must not resurrect at exit
            # (atexit holds a ref to the bound method otherwise).
            with contextlib.suppress(Exception):
                atexit.unregister(self.close)

    def __enter__(self) -> "RunLogger":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close()
        return False


def read_run_log(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]
