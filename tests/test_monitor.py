"""Live run monitoring (ISSUE 10): progress snapshots, online alert
rules, counter ``rate()``, cadence flushing, the ``telemetry watch``
CLI and the status endpoint.

The alert-rule tests are the acceptance check: synthetic event streams
pin EXACTLY which rules fire (an injected divergence produces one
``alert``, a healthy stream produces none) — a rule that over- or
under-fires is an operator paging themselves at 3am for nothing, or
sleeping through a dead run.
"""

from __future__ import annotations

import json
import math
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu import telemetry
from photon_ml_tpu.analysis.guards import count_compiles
from photon_ml_tpu.data.chunked_batch import build_chunked_batch
from photon_ml_tpu.data.normalization import NormalizationContext
from photon_ml_tpu.data.sparse_rows import SparseRows
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optim.streaming import ChunkedGLMObjective
from photon_ml_tpu.telemetry import monitor
from photon_ml_tpu.telemetry import watch as watch_mod
from photon_ml_tpu.telemetry.__main__ import main as telemetry_main
from photon_ml_tpu.utils.run_log import RunLogger, read_run_log

pytestmark = pytest.mark.fast

D = 61
K = 4


@pytest.fixture(autouse=True)
def _no_leaked_monitor():
    """Every test must leave the module-global monitor AND telemetry
    session closed (the same discipline as test_telemetry)."""
    assert monitor.active() is None
    assert telemetry.active() is None
    yield
    leaked = []
    m = monitor.active()
    if m is not None:
        m.close()
        leaked.append("monitor")
    t = telemetry.active()
    if t is not None:
        t.close()
        leaked.append("telemetry")
    if leaked:
        raise AssertionError(f"test leaked active sessions: {leaked}")


class _FakeClock:
    """Deterministic monotonic clock for cadence/rate math."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> float:
        self.t += dt
        return self.t


class _EventSink:
    """RunLogger stand-in collecting (kind, fields) pairs."""

    def __init__(self, clock=None):
        self.events: list = []
        self._clock = clock or _FakeClock()

    def now(self) -> float:
        return self._clock()

    def event(self, kind: str, **fields) -> None:
        self.events.append({"event": kind, **fields})

    def close(self) -> None:
        pass

    def kinds(self) -> list:
        return [e["event"] for e in self.events]

    def of(self, kind: str) -> list:
        return [e for e in self.events if e["event"] == kind]


def _registry(clock=None):
    """A raw (never-activated) Telemetry registry on a fake clock —
    pure counter/gauge/rate state, no threads, no global session."""
    sink = _EventSink(clock)
    return telemetry.Telemetry("metrics", sink, None)


def _monitor(clock=None, every_s=0.0, session=None, **kw):
    """A Monitor wired to an event sink + fake clock, NOT activated as
    the module global (rule evaluation is driven by progress())."""
    clock = clock or _FakeClock()
    sink = _EventSink(clock)
    m = monitor.Monitor(run_logger=sink, every_s=every_s, clock=clock,
                        telemetry_session=session
                        if session is not None else _registry(clock),
                        **kw)
    return m, sink, clock


# ---------------------------------------------------------------------------
# off path + lifecycle
# ---------------------------------------------------------------------------


def test_off_module_helpers_are_noops():
    """No active monitor: progress/phase helpers early-return — the
    hot-loop contract instrumented pipelines rely on."""
    assert monitor.active() is None
    monitor.progress("stage", 1, 10, loss=float("nan"))
    monitor.phase_begin("fit")
    monitor.phase_end("fit")


def test_start_close_lifecycle_and_double_start():
    m = monitor.start()
    try:
        assert monitor.active() is m
        with pytest.raises(RuntimeError, match="already active"):
            monitor.start()
        assert monitor.active() is m     # failed start didn't clobber
    finally:
        m.close()
    assert monitor.active() is None
    m.close()                            # idempotent


def test_maybe_monitor_gating():
    with monitor.maybe_monitor(False) as m:
        assert m is None and monitor.active() is None
    with monitor.maybe_monitor(True) as m:
        assert monitor.active() is m
        # Nested request no-ops (driver-over-estimator rule).
        with monitor.maybe_monitor(True) as inner:
            assert inner is m
    assert monitor.active() is None
    # A requested endpoint implies monitoring even with enabled=False.
    with monitor.maybe_monitor(False, status_port=0) as m:
        assert m is not None and m.status_port > 0
    assert monitor.active() is None


def test_monitor_validates_knobs():
    with pytest.raises(ValueError, match="every_s"):
        monitor.Monitor(_EventSink(), every_s=-1.0)
    with pytest.raises(ValueError, match="unknown alert thresholds"):
        monitor.Monitor(_EventSink(), thresholds={"no_such_knob": 1})


# ---------------------------------------------------------------------------
# progress snapshots: cadence, rate, ETA
# ---------------------------------------------------------------------------


def test_progress_throttles_to_cadence():
    """A hot loop reporting every 10ms at a 1s cadence emits the first
    call, one event per elapsed second, and the completion call — not
    one event per call."""
    m, sink, clock = _monitor(every_s=1.0)
    n = 300
    for i in range(n):
        clock.tick(0.01)
        m.progress("hot", i + 1, n, unit="chunks")
    evs = sink.of("progress")
    # 3s of wall clock: first + ~3 cadence emissions + completion.
    assert 3 <= len(evs) <= 6, [e["done"] for e in evs]
    assert evs[0]["done"] == 1.0
    assert evs[-1]["done"] == float(n)   # completion always emits
    m.close()
    # The run-end summary event carries the final stage state.
    summ = sink.of("monitor_summary")[0]
    assert summ["stages"]["hot"]["done"] == float(n)


def test_progress_rate_and_eta_from_observed_throughput():
    """10 units/s observed → rate ≈ 10, ETA == remaining/rate (the
    ISSUE acceptance: ETA derived from observed chunk rates)."""
    m, sink, clock = _monitor(every_s=0.0)
    for i in range(50):
        clock.tick(0.1)
        m.progress("sweep", i + 1, 100, unit="chunks")
    st = m.status()["stages"]["sweep"]
    assert st["rate"] == pytest.approx(10.0, rel=1e-6)
    assert st["eta_s"] == pytest.approx(5.0, rel=1e-6)
    # The emitted event carries the same derivation.
    last = sink.of("progress")[-1]
    assert last["rate"] == pytest.approx(10.0, abs=0.01)
    assert last["eta_s"] == pytest.approx(5.0, abs=0.1)
    m.close()


def test_progress_restart_resets_rate_window():
    """A new pass restarting the unit count (done decreasing) resets
    the rolling window — throughput never goes negative."""
    m, _, clock = _monitor(every_s=0.0)
    for i in range(10):
        clock.tick(0.1)
        m.progress("pass", i + 1, 10)
    clock.tick(0.1)
    m.progress("pass", 1, 10)            # second pass begins
    clock.tick(0.1)
    m.progress("pass", 2, 10)
    st = m.status()["stages"]["pass"]
    assert st["rate"] is not None and st["rate"] > 0
    m.close()


def test_phase_tracking_nested():
    m, _, _ = _monitor()
    m.phase_begin("fit")
    m.phase_begin("sweep")
    assert m.status()["phase"] == "sweep"
    m.phase_end("sweep")
    assert m.status()["phase"] == "fit"
    m.phase_end("no_such_phase")         # missed begin must not corrupt
    assert m.status()["phase"] == "fit"
    m.phase_end("fit")
    assert m.status()["phase"] is None
    m.close()


# ---------------------------------------------------------------------------
# online alert rules: synthetic streams pin exactly which rules fire
# ---------------------------------------------------------------------------


def _rules(sink) -> list:
    return [e["rule"] for e in sink.of("alert")]


def test_healthy_stream_fires_no_rules():
    """Steady throughput, monotone loss, quiet registry → ZERO alerts
    (the false-positive gate for every rule at once)."""
    m, sink, clock = _monitor(every_s=0.0)
    loss = 100.0
    for i in range(60):
        clock.tick(0.5)
        loss *= 0.98
        m.progress("solver", i + 1, 100, unit="iters", loss=loss)
    assert _rules(sink) == []
    assert m.status()["alerts"] == []
    m.close()


def test_loss_nonfinite_fires_once_latched():
    """An injected NaN loss produces EXACTLY ONE alert event no matter
    how many snapshots repeat it (the rule latches per rule×stage)."""
    m, sink, clock = _monitor(every_s=0.0)
    for i in range(10):
        clock.tick(0.5)
        m.progress("solver", i + 1, 100, loss=float("nan"))
    assert _rules(sink) == ["loss_nonfinite"]
    alert = sink.of("alert")[0]
    assert alert["severity"] == "error"
    assert alert["stage"] == "solver"
    m.close()


def test_loss_divergence_fires_exactly_one_alert():
    """The ISSUE-10 acceptance fault: loss improves, then blows past
    divergence_ratio × best → one loss_diverging alert, nothing else."""
    m, sink, clock = _monitor(every_s=0.0)
    for i, loss in enumerate([100.0, 80.0, 60.0, 50.0,   # improving
                              70.0, 90.0,                # worse, < 2x best
                              150.0, 400.0, 900.0]):     # diverged
        clock.tick(0.5)
        m.progress("solver", i + 1, 20, loss=loss)
    assert _rules(sink) == ["loss_diverging"]
    alert = sink.of("alert")[0]
    assert alert["severity"] == "error" and alert["best"] == 50.0
    assert alert["loss"] == 150.0        # fired at first crossing
    m.close()


def test_throughput_collapse_vs_rolling_median():
    m, sink, clock = _monitor(every_s=0.0)
    done = 0
    for _ in range(8):                   # healthy: 20 units/s
        clock.tick(0.5)
        done += 10
        m.progress("sweep", done, 10_000, unit="chunks")
    for _ in range(40):                  # collapse: 0.2 units/s
        clock.tick(5.0)
        done += 1
        m.progress("sweep", done, 10_000, unit="chunks")
    assert "throughput_collapse" in _rules(sink)
    assert _rules(sink).count("throughput_collapse") == 1   # latched
    m.close()


def test_retry_storm_rate_and_gave_up():
    """Transient retries above the windowed rate threshold fire
    retry_storm; any store.gave_up fires it as an error."""
    clock = _FakeClock()
    reg = _registry(clock)
    m, sink, _ = _monitor(clock=clock, session=reg)
    for i in range(20):
        clock.tick(0.5)
        reg.count("store.retries")       # 2/s >> 0.5/s threshold
        m.progress("sweep", i + 1, 100)
    assert _rules(sink) == ["retry_storm"]
    assert sink.of("alert")[0]["severity"] == "warn"
    m.close()

    clock2 = _FakeClock()
    reg2 = _registry(clock2)
    m2, sink2, _ = _monitor(clock=clock2, session=reg2)
    reg2.count("store.gave_up")
    clock2.tick(0.5)
    m2.progress("sweep", 1, 100)
    assert _rules(sink2) == ["retry_storm"]
    assert sink2.of("alert")[0]["severity"] == "error"
    m2.close()


def test_prefetch_stall_rules():
    """A hard stall timeout fires immediately (error); absent that, a
    consumer blocked most of recent wall clock fires the soft rule."""
    clock = _FakeClock()
    reg = _registry(clock)
    m, sink, _ = _monitor(clock=clock, session=reg)
    reg.count("prefetch.stall_timeouts")
    clock.tick(0.5)
    m.progress("sweep", 1, 100)
    assert _rules(sink) == ["prefetch_stall"]
    assert sink.of("alert")[0]["severity"] == "error"
    m.close()

    clock2 = _FakeClock()
    reg2 = _registry(clock2)
    m2, sink2, _ = _monitor(clock=clock2, session=reg2)
    for i in range(10):                  # blocked 0.45s of every 0.5s
        clock2.tick(0.5)
        reg2.count("prefetch.consumer_wait_s", 0.45)
        m2.progress("sweep", i + 1, 100)
    assert _rules(sink2) == ["prefetch_stall"]
    m2.close()


def test_sink_saturation_needs_a_streak():
    """One deep-queue sample is normal burst; a sustained streak at
    snapshot cadence names the sink tier as the bottleneck."""
    clock = _FakeClock()
    reg = _registry(clock)
    m, sink, _ = _monitor(clock=clock, session=reg)
    reg.gauge("sink.queue_depth", 4.0)
    clock.tick(0.5)
    m.progress("score", 1, 100)          # streak 1: no alert yet
    assert _rules(sink) == []
    reg.gauge("sink.queue_depth", 1.0)   # drained: streak resets
    clock.tick(0.5)
    m.progress("score", 2, 100)
    reg.gauge("sink.queue_depth", 4.0)
    for i in range(3, 5):
        clock.tick(0.5)
        m.progress("score", i, 100)
    assert _rules(sink) == ["sink_saturation"]
    m.close()


def test_device_memory_growth_needs_ratio_and_floor():
    """Fires only when device memory grew by BOTH the ratio and the
    absolute floor since monitoring started — a tiny run tripling a
    10MB footprint is not a leak."""
    clock = _FakeClock()
    reg = _registry(clock)
    m, sink, _ = _monitor(clock=clock, session=reg)
    reg.gauge("device.bytes_in_use", 1e9)
    clock.tick(0.5)
    m.progress("sweep", 1, 100)
    reg.gauge("device.bytes_in_use", 1.4e9)   # +400MB but < 1.5x
    clock.tick(0.5)
    m.progress("sweep", 2, 100)
    assert _rules(sink) == []
    reg.gauge("device.bytes_in_use", 2.1e9)   # 2.1x AND +1100MB
    clock.tick(0.5)
    m.progress("sweep", 3, 100)
    assert _rules(sink) == ["device_memory_growth"]
    m.close()


def test_serve_tail_latency_fires_over_threshold_latched():
    """ISSUE 12 satellite (positive): a request stream whose p99 sits
    above the threshold fires serve_tail_latency exactly once, stamped
    with the observed p99."""
    clock = _FakeClock()
    reg = _registry(clock)
    m, sink, _ = _monitor(clock=clock, session=reg)
    for _ in range(30):
        reg.count("serve.requests")
        reg.observe("serve.request_s", 0.9)     # every request slow
    clock.tick(0.5)
    m.progress("serve", 30, unit="requests")
    assert _rules(sink) == ["serve_tail_latency"]
    alert = sink.of("alert")[0]
    assert alert["stage"] == "serve"
    assert alert["p99_ms"] > 500.0
    # Latched: the next snapshot with the same registry re-fires
    # nothing.
    clock.tick(0.5)
    m.progress("serve", 60, unit="requests")
    assert _rules(sink) == ["serve_tail_latency"]
    m.close()


def test_serve_tail_latency_negative_paths():
    """ISSUE 12 satellite (negative): a fast stream never fires, and a
    slow p99 below the minimum request count is start-up noise, not an
    SLO breach."""
    clock = _FakeClock()
    reg = _registry(clock)
    m, sink, _ = _monitor(clock=clock, session=reg)
    for _ in range(200):                        # fast stream
        reg.count("serve.requests")
        reg.observe("serve.request_s", 0.005)
    clock.tick(0.5)
    m.progress("serve", 200, unit="requests")
    assert _rules(sink) == []
    m.close()

    clock2 = _FakeClock()
    reg2 = _registry(clock2)
    m2, sink2, _ = _monitor(clock=clock2, session=reg2)
    for _ in range(5):                          # slow but too few
        reg2.count("serve.requests")
        reg2.observe("serve.request_s", 2.0)
    clock2.tick(0.5)
    m2.progress("serve", 5, unit="requests")
    assert _rules(sink2) == []
    m2.close()


def test_serve_shed_rate_fires_on_sustained_shedding_latched():
    """ISSUE 13 satellite (positive): a shed fraction above the
    threshold over the rolling window fires serve_shed_rate exactly
    once, stamped with the observed fraction."""
    clock = _FakeClock()
    reg = _registry(clock)
    m, sink, _ = _monitor(clock=clock, session=reg)
    for _ in range(10):                 # 50% shed, well over 20%
        clock.tick(0.1)
        reg.count("serve.requests")
        reg.count("serve.shed")
    clock.tick(0.1)
    m.progress("serve", 10, unit="requests")
    assert _rules(sink) == ["serve_shed_rate"]
    alert = sink.of("alert")[0]
    assert alert["stage"] == "serve"
    assert alert["shed_fraction"] == pytest.approx(0.5, abs=0.05)
    # Latched: continued shedding re-fires nothing.
    clock.tick(0.5)
    reg.count("serve.shed")
    m.progress("serve", 11, unit="requests")
    assert _rules(sink) == ["serve_shed_rate"]
    m.close()


def test_serve_shed_rate_negative_paths():
    """ISSUE 13 satellite (negative): a small shed fraction never
    fires, and heavy shedding below the minimum event count is
    start-up noise."""
    clock = _FakeClock()
    reg = _registry(clock)
    m, sink, _ = _monitor(clock=clock, session=reg)
    for i in range(40):                 # 2.5% shed, under 20%
        clock.tick(0.1)
        reg.count("serve.requests")
        if i == 0:
            reg.count("serve.shed")
    clock.tick(0.1)
    m.progress("serve", 40, unit="requests")
    assert _rules(sink) == []
    m.close()

    clock2 = _FakeClock()
    reg2 = _registry(clock2)
    m2, sink2, _ = _monitor(clock=clock2, session=reg2)
    for _ in range(5):                  # 100% shed but too few events
        clock2.tick(0.1)
        reg2.count("serve.shed")
    clock2.tick(0.1)
    m2.progress("serve", 0, unit="requests")
    assert _rules(sink2) == []
    m2.close()


def test_replica_restarts_any_restart_latches():
    """ISSUE 13 satellite (positive): ANY replica restart fires the
    rule once — and only once, however many more restarts follow."""
    clock = _FakeClock()
    reg = _registry(clock)
    m, sink, _ = _monitor(clock=clock, session=reg)
    reg.count("fleet.replica_restarts")
    clock.tick(0.5)
    m.progress("serve", 1, unit="requests")
    assert _rules(sink) == ["replica_restarts"]
    assert sink.of("alert")[0]["restarts"] == 1
    reg.count("fleet.replica_restarts", 3)
    clock.tick(0.5)
    m.progress("serve", 2, unit="requests")
    assert _rules(sink) == ["replica_restarts"]      # latched
    m.close()


def test_replica_restarts_negative_without_restarts():
    """ISSUE 13 satellite (negative): recycles (deploy bounces) and
    ordinary traffic never fire replica_restarts."""
    clock = _FakeClock()
    reg = _registry(clock)
    m, sink, _ = _monitor(clock=clock, session=reg)
    reg.count("fleet.replica_recycles", 2)   # rolling swap, not crash
    for _ in range(30):
        clock.tick(0.1)
        reg.count("serve.requests")
        reg.observe("serve.request_s", 0.005)
    m.progress("serve", 30, unit="requests")
    assert _rules(sink) == []
    m.close()


def test_alerts_disabled_evaluates_nothing():
    m, sink, clock = _monitor(every_s=0.0, alerts=False)
    for i in range(5):
        clock.tick(0.5)
        m.progress("solver", i + 1, 10, loss=float("nan"))
    assert _rules(sink) == []
    m.close()


# ---------------------------------------------------------------------------
# metrics registry: rolling-window counter rate()
# ---------------------------------------------------------------------------


def test_counter_rate_bounded_error():
    """The satellite's bounded-error contract: a rate step is resolved
    within one inter-sample spacing of the window boundary — a counter
    that was fast an hour ago and stalled NOW reports the NOW rate."""
    clock = _FakeClock()
    reg = _registry(clock)
    for _ in range(500):                 # phase A: 10/s for 50s
        clock.tick(0.1)
        reg.count("x")
    for _ in range(1000):                # phase B: 100/s for 10s
        clock.tick(0.01)
        reg.count("x")
    # A 5s trailing window sits entirely inside phase B: exact.
    assert reg.rate("x", 5.0) == pytest.approx(100.0, rel=0.01)
    # A 60s window spans both phases: the true mean over the bracketed
    # interval (1500 increments / 60s = 25/s), within one spacing.
    assert reg.rate("x", 60.0) == pytest.approx(1500 / 60.0, rel=0.02)
    # Lifetime average would be 1500/60 too here, so pin the contrast
    # explicitly: a stall after phase B collapses the windowed rate
    # while the lifetime counter stays put.
    clock.tick(30.0)
    reg.count("x")
    assert reg.counter("x") == 1501
    assert reg.rate("x", 5.0, now=clock()) < 1.0
    m = reg.rate("x", 5.0)
    assert m is not None


def test_counter_rate_decimation_stays_bounded():
    """Overflowing the per-counter series cap decimates to every-other
    sample; a constant-rate stream's reported rate must stay exact to
    within two sample spacings (the documented error bound)."""
    clock = _FakeClock()
    reg = _registry(clock)
    n = 10_000                           # >> _RATE_SERIES_CAP (4096)
    for _ in range(n):
        clock.tick(0.01)                 # 100/s, all within horizon
        reg.count("y")
    r = reg.rate("y", 10.0)
    # Window bracket error ≤ 2 spacings of the DECIMATED series; at
    # ~4096 retained samples over 100s that is ~0.05s on a 10s window.
    assert r == pytest.approx(100.0, rel=0.02)


def test_counter_rate_edge_contracts():
    clock = _FakeClock()
    reg = _registry(clock)
    assert reg.rate("unknown") is None
    reg.count("z")
    assert reg.rate("z") is None         # one sample: no interval
    clock.tick(1.0)
    reg.count("z", 5)
    assert reg.rate("z", 30.0) == pytest.approx(5.0)
    with pytest.raises(ValueError, match="window_s"):
        reg.rate("z", 0.0)
    assert reg.gauge_value("no.gauge") is None
    reg.gauge("g", 2.0)
    assert reg.gauge_value("g")["last"] == 2.0


# ---------------------------------------------------------------------------
# RunLogger cadence flushing
# ---------------------------------------------------------------------------


def test_runlogger_cadence_batches_ordinary_events(tmp_path):
    """With a long cadence an ordinary event may sit in the userspace
    buffer, but _FLUSH_NOW kinds (alerts, progress, phase boundaries)
    hit disk immediately — `watch` and kill-forensics stay current."""
    path = str(tmp_path / "log.jsonl")
    log = RunLogger(path, flush_every_s=3600.0)
    log.event("ordinary", x=1)
    buffered = read_run_log(path)
    # run_header is _FLUSH_NOW; the ordinary event is cadence-buffered.
    assert [e["event"] for e in buffered] == ["run_header"]
    log.event("alert", rule="loss_diverging")
    flushed = read_run_log(path)
    assert [e["event"] for e in flushed] == [
        "run_header", "ordinary", "alert"]
    log.event("ordinary2", x=2)
    log.flush()                          # explicit force
    assert read_run_log(path)[-1]["event"] == "ordinary2"
    log.close()
    assert [e["event"] for e in read_run_log(path)] == [
        "run_header", "ordinary", "alert", "ordinary2"]


def test_runlogger_flush_validation(tmp_path):
    with pytest.raises(ValueError, match="flush_every_s"):
        RunLogger(str(tmp_path / "x.jsonl"), flush_every_s=-1.0)
    # None (default) keeps the flush-every-event behavior.
    path = str(tmp_path / "y.jsonl")
    log = RunLogger(path)
    log.event("anything", x=1)
    assert read_run_log(path)[-1]["event"] == "anything"
    log.close()


# ---------------------------------------------------------------------------
# telemetry watch
# ---------------------------------------------------------------------------


def _write_live_log(path, alerts=0, done=False, segments=1):
    """A driver-shaped run log: header, open `fit` phase, progress
    snapshots with a loss trajectory — optionally still-running (no
    `done`, phase left open), resumed (extra segments), alerted."""
    for seg in range(segments):
        log = RunLogger(path, mode=("w" if seg == 0 else "a"),
                        header=True, run_info={"driver": "test"})
        log.event("phase_start", phase="fit")
        for i in range(5):
            log.event("progress", stage="solver", done=float(i + 1),
                      total=20.0, unit="iters", rate=2.0, eta_s=7.5,
                      loss=100.0 * (0.9 ** i), phase="fit")
        for k in range(alerts if seg == segments - 1 else 0):
            log.event("alert", rule="loss_diverging", severity="error",
                      stage="solver", message="loss 900 is 18x best")
        final = seg == segments - 1
        if done or not final:
            log.event("phase_end", phase="fit", duration_s=2.5)
            if done and final:
                log.event("done", best_index=0)
        log.close()


def test_watch_once_on_live_unterminated_log(tmp_path, capsys):
    """`watch --once` on a log whose run is still mid-fit: live=true,
    the open phase, per-stage progress/ETA/loss — and the JSON last
    line carries all of it (the scripting contract)."""
    path = str(tmp_path / "run_log.jsonl")
    _write_live_log(path)
    rc = telemetry_main(["watch", path, "--once"])
    out = capsys.readouterr().out
    assert rc == 0
    snap = json.loads(out.strip().splitlines()[-1])
    assert snap["live"] is True
    assert snap["phase"] == "fit"
    assert snap["current_stage"] == "solver"
    assert snap["stages"]["solver"]["done"] == 5.0
    assert snap["eta_s"] == 7.5
    assert snap["loss"] == pytest.approx(100.0 * 0.9 ** 4)
    assert snap["losses"]["solver"][0] == 100.0
    assert snap["alerts"] == []
    # The human view leads with the run state and the current stage.
    assert "[RUNNING]" in out and "solver" in out


def test_watch_once_on_stitched_resumed_log(tmp_path, capsys):
    """A resumed run appends a fresh header: watch reports the LAST
    segment (the live one), not the interrupted predecessor."""
    path = str(tmp_path / "run_log.jsonl")
    _write_live_log(path, segments=2, done=True)
    rc = telemetry_main(["watch", path, "--once"])
    out = capsys.readouterr().out
    assert rc == 0
    snap = json.loads(out.strip().splitlines()[-1])
    assert snap["segments"] == 2
    assert snap["live"] is False         # last segment logged done
    assert snap["stages"]["solver"]["done"] == 5.0
    assert "segment 2 of a resumed run" in out


def test_watch_once_tolerates_torn_final_line(tmp_path, capsys):
    """A live writer's partial final line (the kill-mid-write case) is
    counted, not fatal."""
    path = str(tmp_path / "run_log.jsonl")
    _write_live_log(path)
    with open(path, "a") as f:
        f.write('{"event": "progress", "stage": "solver", "done": 6')
    rc = telemetry_main(["watch", path, "--once"])
    snap = json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert snap["torn_lines"] == 1
    assert snap["stages"]["solver"]["done"] == 5.0   # torn line skipped
    assert snap["live"] is True


def test_watch_follow_bounded_by_max_wait(tmp_path, capsys):
    """Follow mode on a log that stops growing without `done` (a
    killed run) exits at --max-wait-s instead of watching forever."""
    path = str(tmp_path / "run_log.jsonl")
    _write_live_log(path)
    rc = telemetry_main(["watch", path, "--interval", "0.05",
                         "--max-wait-s", "0.2"])
    snap = json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and snap["live"] is True


def test_watch_surfaces_alerts_and_thread_deaths(tmp_path, capsys):
    path = str(tmp_path / "run_log.jsonl")
    _write_live_log(path, alerts=1)
    with open(path, "a") as f:
        f.write(json.dumps({"event": "thread_exception",
                            "stage": "prefetch", "error": "boom",
                            "thread": "chunk-prefetch"}) + "\n")
    rc = telemetry_main(["watch", path, "--once"])
    out = capsys.readouterr().out
    snap = json.loads(out.strip().splitlines()[-1])
    assert rc == 1                       # a dead thread is a failure
    assert [a["rule"] for a in snap["alerts"]] == ["loss_diverging"]
    assert snap["thread_exceptions"][0]["stage"] == "prefetch"
    assert "ALERTS:" in out and "DIED prefetch" in out


def test_watch_rejects_bad_interval(tmp_path):
    path = str(tmp_path / "run_log.jsonl")
    _write_live_log(path)
    with pytest.raises(ValueError, match="interval_s"):
        watch_mod.watch(path, interval_s=0.0)


# ---------------------------------------------------------------------------
# status endpoint
# ---------------------------------------------------------------------------


def _get(port, route):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{route}", timeout=5) as r:
        return r.status, r.headers.get("Content-Type"), r.read().decode()


def test_status_endpoint_routes():
    """/status serves the live JSON snapshot, /metrics the Prometheus
    text exposition, unknown routes 404 with the route list."""
    m = monitor.start(status_port=0)
    try:
        port = m.status_port
        assert port and port > 0
        # Warming until work flows (ISSUE 12 satellite): before the
        # first progress snapshot — the plan/compile window — a probe
        # gets 503, not the old unconditional 200.
        with pytest.raises(urllib.error.HTTPError) as warm:
            _get(port, "/healthz")
        assert warm.value.code == 503
        assert json.loads(warm.value.read().decode())["state"] == \
            "warming"
        monitor.progress("sweep", 3, 12, unit="chunks")
        code, ctype, body = _get(port, "/status")
        assert code == 200 and ctype == "application/json"
        st = json.loads(body)
        assert st["stages"]["sweep"]["done"] == 3.0
        assert st["stages"]["sweep"]["total"] == 12.0
        assert st["alerts"] == []
        code, ctype, body = _get(port, "/metrics")
        assert code == 200 and "version=0.0.4" in ctype
        assert 'photon_monitor_progress_done{stage="sweep"} 3.0' in body
        assert "photon_monitor_alerts_total 0" in body
        code, _, body = _get(port, "/healthz")
        assert code == 200
        assert json.loads(body) == {"ok": True, "state": "ready"}
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(port, "/no_such")
        assert err.value.code == 404
        assert "/status" in err.value.read().decode()
    finally:
        m.close()
    # The server thread is down with the monitor.
    with pytest.raises(OSError):
        _get(port, "/status")


def test_prometheus_text_exposition_format():
    """Counters → counter, gauges → gauge, histograms → summary with
    reservoir quantiles; metric names sanitized to the charset."""
    clock = _FakeClock()
    reg = _registry(clock)
    reg.count("store.loads", 7)
    reg.gauge("sink.queue_depth", 2.0)
    for v in range(100):
        reg.observe("sink.write_s", float(v))
    m, _, _ = _monitor(clock=clock, session=reg)
    m.progress("score", 5, 10, unit="rows")
    text = monitor.prometheus_text(m, session=reg)
    lines = text.splitlines()
    assert "# TYPE photon_store_loads_total counter" in lines
    assert "photon_store_loads_total 7" in lines
    assert "photon_sink_queue_depth 2.0" in lines
    assert "# TYPE photon_sink_write_s summary" in lines
    assert any(l.startswith('photon_sink_write_s{quantile="0.5"}')
               for l in lines)
    assert "photon_sink_write_s_count 100" in lines
    assert 'photon_monitor_progress_total{stage="score"} 10.0' in lines
    m.close()


# ---------------------------------------------------------------------------
# guard budget: monitoring compiles nothing
# ---------------------------------------------------------------------------


def _tiny_spilled_objective(tmp_path, n_chunks=4, chunk_rows=100):
    rng = np.random.default_rng(11)
    n = chunk_rows * n_chunks
    cols = np.stack([np.sort(rng.choice(D, K, replace=False))
                     for _ in range(n)]).astype(np.int64)
    vals = rng.normal(size=(n, K)).astype(np.float32)
    labels = (rng.uniform(size=n) < 0.5).astype(np.float32)
    rows = SparseRows.from_flat(np.arange(n + 1, dtype=np.int64) * K,
                                cols.reshape(-1), vals.reshape(-1))
    obj = GLMObjective(loss=losses.LOGISTIC,
                       reg=RegularizationContext.l2(1.0),
                       norm=NormalizationContext.identity())
    cb = build_chunked_batch(rows, D, labels, n_chunks=n_chunks,
                             layout="ell",
                             spill_dir=str(tmp_path / "spill"),
                             host_max_resident=2)
    return ChunkedGLMObjective(obj, cb, max_resident=0, prefetch_depth=1)


def test_monitored_sweeps_compile_nothing_new(tmp_path):
    """The guard-pinned acceptance budget: warm streamed sweeps with
    the live monitor ON (snapshots + alert evaluation at a hot
    cadence + the status thread) add ZERO compile records — the
    monitor never touches jax."""
    cobj = _tiny_spilled_objective(tmp_path)
    w = jnp.zeros(D, jnp.float32)
    import jax

    jax.block_until_ready(cobj.value_and_gradient(w)[1])   # warm
    m = monitor.start(every_s=0.0, status_port=0)
    try:
        with count_compiles() as log:
            for _ in range(2):
                jax.block_until_ready(cobj.value_and_gradient(w)[1])
        assert log.count == 0, log.programs
        # The hot loop DID report through the live monitor.
        assert m.status()["stages"]["train.sweep"]["done"] == 4.0
    finally:
        m.close()


# ---------------------------------------------------------------------------
# e2e: one injected divergence → one alert in watch + /status + report
# ---------------------------------------------------------------------------


def test_divergence_alert_visible_in_watch_status_and_report(
        tmp_path, capsys):
    """The ISSUE-10 acceptance chain: an injected loss divergence
    produces EXACTLY ONE alert event, and that one alert surfaces in
    all three consumers — `watch --once`, GET /status, and the
    report's Alerts section."""
    path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(path, header=True,
                    run_info={"driver": "game_training"})
    m = monitor.start(run_logger=log, every_s=0.0, status_port=0)
    try:
        with log.timed("fit"):
            for i, loss in enumerate([100.0, 50.0, 40.0,
                                      90.0, 200.0, 500.0]):
                m.progress("solver", i + 1, 10, unit="iters",
                           loss=loss)
        _, _, body = _get(m.status_port, "/status")
        status_alerts = json.loads(body)["alerts"]
    finally:
        m.close()
        log.close()

    events = read_run_log(path)
    assert [e["rule"] for e in events
            if e["event"] == "alert"] == ["loss_diverging"]

    assert [a["rule"] for a in status_alerts] == ["loss_diverging"]

    rc = telemetry_main(["watch", path, "--once"])
    out = capsys.readouterr().out
    snap = json.loads(out.strip().splitlines()[-1])
    assert rc == 0
    assert [a["rule"] for a in snap["alerts"]] == ["loss_diverging"]
    assert "loss_diverging" in out

    rc = telemetry_main(["report", path])
    out = capsys.readouterr().out
    tail = json.loads(out.strip().splitlines()[-1])
    assert rc == 0
    assert [a["rule"] for a in tail["alerts"]] == ["loss_diverging"]
    assert "Alerts" in out and "loss_diverging" in out


# ---------------------------------------------------------------------------
# Request tracing (ISSUE 14): serve_queue_wait rule, dominant-stage
# naming, the labeled stage family, and the watch stage table
# ---------------------------------------------------------------------------


def test_serve_queue_wait_fires_when_batcher_dominates_latched():
    """ISSUE 14 satellite (positive): queue-wait p99 above the
    configured fraction of the request p99 fires serve_queue_wait
    exactly once — the 'batcher is the bottleneck' signal."""
    clock = _FakeClock()
    reg = _registry(clock)
    m, sink, _ = _monitor(clock=clock, session=reg)
    for _ in range(30):
        reg.count("serve.requests")
        reg.observe("serve.request_s", 0.100)
        reg.observe("serve.stage.queue_wait_s", 0.080)   # 80% wait
    clock.tick(0.5)
    m.progress("serve", 30, unit="requests")
    assert _rules(sink) == ["serve_queue_wait"]
    alert = sink.of("alert")[0]
    assert alert["stage"] == "serve"
    assert alert["fraction"] == pytest.approx(0.8, abs=0.05)
    assert "batcher" in alert["message"]
    # Latched: the next snapshot re-fires nothing.
    clock.tick(0.5)
    m.progress("serve", 60, unit="requests")
    assert _rules(sink) == ["serve_queue_wait"]
    m.close()


def test_serve_queue_wait_negative_paths():
    """ISSUE 14 satellite (negative): a compute-dominated tail never
    fires, and a wait-dominated tail below the minimum request count
    is start-up noise."""
    clock = _FakeClock()
    reg = _registry(clock)
    m, sink, _ = _monitor(clock=clock, session=reg)
    for _ in range(50):                   # 10% queue wait: healthy
        reg.count("serve.requests")
        reg.observe("serve.request_s", 0.100)
        reg.observe("serve.stage.queue_wait_s", 0.010)
    clock.tick(0.5)
    m.progress("serve", 50, unit="requests")
    assert _rules(sink) == []
    m.close()

    clock2 = _FakeClock()
    reg2 = _registry(clock2)
    m2, sink2, _ = _monitor(clock=clock2, session=reg2)
    for _ in range(5):                    # dominated, but too few
        reg2.count("serve.requests")
        reg2.observe("serve.request_s", 0.100)
        reg2.observe("serve.stage.queue_wait_s", 0.090)
    clock2.tick(0.5)
    m2.progress("serve", 5, unit="requests")
    assert _rules(sink2) == []
    m2.close()


def test_serve_tail_latency_names_dominant_stage():
    """ISSUE 14: with the stage histograms populated, the
    serve_tail_latency alert names the dominant stage — the first
    diagnostic step rides the page."""
    clock = _FakeClock()
    reg = _registry(clock)
    m, sink, _ = _monitor(clock=clock, session=reg)
    for _ in range(30):
        reg.count("serve.requests")
        reg.observe("serve.request_s", 0.9)
        reg.observe("serve.stage.dispatch_s", 0.7)
        reg.observe("serve.stage.queue_wait_s", 0.1)
    clock.tick(0.5)
    m.progress("serve", 30, unit="requests")
    assert _rules(sink) == ["serve_tail_latency"]
    alert = sink.of("alert")[0]
    assert alert["dominant_stage"] == "dispatch"
    assert "dominant stage: dispatch" in alert["message"]
    m.close()


def test_serve_progress_event_carries_stage_table():
    """Serve progress snapshots embed the stage p50/p99 table so
    `telemetry watch` renders the live latency decomposition."""
    clock = _FakeClock()
    reg = _registry(clock)
    m, sink, _ = _monitor(clock=clock, session=reg)
    for _ in range(4):
        reg.observe("serve.stage.queue_wait_s", 0.004)
        reg.observe("serve.stage.dispatch_s", 0.002)
    clock.tick(0.5)
    m.progress("serve", 4, unit="requests")
    prog = sink.of("progress")[0]
    assert prog["stages_ms"]["queue_wait"]["p50_ms"] == pytest.approx(
        4.0, rel=0.01)
    assert prog["stages_ms"]["dispatch"]["count"] == 4
    # Non-serve stages stay lean: no table attached.
    clock.tick(0.5)
    m.progress("solver", 1, 10, unit="iters")
    assert "stages_ms" not in sink.of("progress")[-1]
    m.close()


def test_prometheus_serve_stage_labeled_family():
    """serve.stage.<stage>_s histograms export as ONE labeled family
    photon_serve_stage_seconds{stage=...} (ISSUE 14) instead of N
    flat-named series; other histograms keep the flat form."""
    clock = _FakeClock()
    reg = _registry(clock)
    for _ in range(10):
        reg.observe("serve.stage.queue_wait_s", 0.004)
        reg.observe("serve.stage.dispatch_s", 0.002)
        reg.observe("serve.request_s", 0.01)
    text = monitor.prometheus_text(session=reg)
    lines = text.splitlines()
    assert lines.count("# TYPE photon_serve_stage_seconds summary") == 1
    assert any(l.startswith(
        'photon_serve_stage_seconds{stage="queue_wait",quantile="0.5"}')
        for l in lines)
    assert 'photon_serve_stage_seconds_count{stage="dispatch"} 10' \
        in lines
    # The plain request histogram keeps the flat exposition.
    assert "# TYPE photon_serve_request_s summary" in lines
    assert not any("photon_serve_stage_queue_wait" in l for l in lines)


def test_watch_renders_serve_stage_table(tmp_path, capsys):
    """ISSUE 14 satellite: watching a SERVER run log renders the serve
    stage table (p50/p99 per stage) and the dominant-stage line."""
    path = str(tmp_path / "serve_log.jsonl")
    log = RunLogger(path, run_info={"driver": "serving"})
    log.event("progress", stage="serve", done=100.0, unit="rows",
              stages_ms={
                  "queue_wait": {"count": 40, "p50_ms": 2.1,
                                 "p99_ms": 9.5},
                  "dispatch": {"count": 12, "p50_ms": 3.3,
                               "p99_ms": 6.2},
              })
    log.close()
    rc = telemetry_main(["watch", path, "--once"])
    out = capsys.readouterr().out
    assert rc == 0
    snap = json.loads(out.strip().splitlines()[-1])
    assert snap["serve_stages"]["queue_wait"]["p99_ms"] == 9.5
    assert snap["serve_dominant"] == {"stage": "queue_wait",
                                      "p99_ms": 9.5}
    assert "serve stages (request tracing):" in out
    assert "dominant stage: queue_wait" in out
    # A training log (no serve stage) renders no serve table.
    path2 = str(tmp_path / "train_log.jsonl")
    _write_live_log(path2, done=True)
    rc = telemetry_main(["watch", path2, "--once"])
    out2 = capsys.readouterr().out
    assert rc == 0
    snap2 = json.loads(out2.strip().splitlines()[-1])
    assert snap2["serve_stages"] is None
    assert "serve stages" not in out2
