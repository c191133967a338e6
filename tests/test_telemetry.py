"""Pipeline telemetry (ISSUE 7): span tracer, metrics registry, trace
export, report CLI, and liveness (heartbeat / thread-death) contracts.

The pinned-metric tests are the acceptance check: telemetry's counters
must MATCH the subsystems' own ground truth (the chunk store's
hit/load odometers, the objective's ``sweeps`` odometer, the guards
compile listener) on a real streamed fit — a drifting counter is a
lying dashboard.
"""

from __future__ import annotations

import json
import os
import threading
import time

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
from photon_ml_tpu.optim.base import OptimizerConfig
from photon_ml_tpu.optim.streaming import (
    ChunkedGLMObjective,
    ChunkPrefetcher,
    streaming_lbfgs_solve,
)
from photon_ml_tpu.utils.run_log import RunLogger, read_run_log

pytestmark = pytest.mark.fast

# Unique problem shape (compile-budget hygiene: the fresh-compile leg
# of other tests must not depend on what this module compiled).
D = 83
K = 4
CHUNK_ROWS = 200
N_CHUNKS = 6


@pytest.fixture(autouse=True)
def _no_leaked_session():
    """Every test must leave the module-global session closed."""
    assert telemetry.active() is None
    yield
    t = telemetry.active()
    if t is not None:        # a failing test leaked its session
        t.close()
        raise AssertionError("test leaked an active telemetry session")


def _spilled_objective(tmp_path, seed=7):
    rng = np.random.default_rng(seed)
    n = CHUNK_ROWS * N_CHUNKS
    cols = np.stack([np.sort(rng.choice(D, K, replace=False))
                     for _ in range(n)]).astype(np.int64)
    vals = rng.normal(size=(n, K)).astype(np.float32)
    labels = (rng.uniform(size=n) < 0.5).astype(np.float32)
    rows = SparseRows.from_flat(np.arange(n + 1, dtype=np.int64) * K,
                                cols.reshape(-1), vals.reshape(-1))
    obj = GLMObjective(loss=losses.LOGISTIC,
                       reg=RegularizationContext.l2(1.0),
                       norm=NormalizationContext.identity())
    cb = build_chunked_batch(rows, D, labels, n_chunks=N_CHUNKS,
                             layout="ell",
                             spill_dir=str(tmp_path / "spill"),
                             host_max_resident=2)
    return ChunkedGLMObjective(obj, cb, max_resident=0, prefetch_depth=2)


def _fit(cobj, max_iters=4):
    return streaming_lbfgs_solve(
        cobj.value_and_gradient, jnp.zeros(D, jnp.float32),
        OptimizerConfig(max_iters=max_iters, tolerance=1e-9),
        value_fn=cobj.value)


# ---------------------------------------------------------------------------
# off path
# ---------------------------------------------------------------------------


def test_off_is_noop_and_emits_nothing(tmp_path):
    """The off contract: no session → the module helpers are no-ops,
    instrumented pipelines write ZERO telemetry events."""
    assert telemetry.active() is None
    with telemetry.span("anything", cat="x", k=1) as sp:
        assert sp.__class__.__name__ == "_NullSpan"
    telemetry.count("c", 5)
    telemetry.gauge("g", 1.0)
    telemetry.observe("h", 0.5)
    telemetry.heartbeat("stage")

    log = RunLogger(str(tmp_path / "log.jsonl"))
    cobj = _spilled_objective(tmp_path)
    _fit(cobj, max_iters=2)
    log.close()
    events = read_run_log(str(tmp_path / "log.jsonl"))
    # Only the RunLogger's own schema header — zero telemetry events
    # (no spans, counters, convergence or device records).
    assert [e["event"] for e in events] == ["run_header"]


def test_maybe_session_off_and_nested(tmp_path):
    with telemetry.maybe_session("off") as t:
        assert t is None
    with telemetry.maybe_session(None) as t:
        assert t is None
    with telemetry.maybe_session("metrics", str(tmp_path)) as outer:
        assert telemetry.active() is outer
        # A nested session request no-ops (driver-over-estimator rule).
        with telemetry.maybe_session("trace", str(tmp_path)) as inner:
            assert inner is outer
        assert telemetry.active() is outer
    assert telemetry.active() is None


def test_double_start_rejected(tmp_path):
    t = telemetry.start("metrics")
    try:
        with pytest.raises(RuntimeError, match="already active"):
            telemetry.start("metrics")
    finally:
        t.close()
    assert telemetry.active() is None


def test_config_validation():
    from photon_ml_tpu.config import ScoringConfig

    cfg = ScoringConfig(input_path="x", model_dir="m", telemetry="trace")
    cfg.validate()
    cfg.telemetry = "verbose"
    with pytest.raises(ValueError, match="telemetry"):
        cfg.validate()


# ---------------------------------------------------------------------------
# pinned metrics: telemetry counters == subsystem ground truth
# ---------------------------------------------------------------------------


def test_metrics_match_ground_truth_on_streamed_fit(tmp_path):
    """LRU hit count, sweeps odometer, and compile count all match the
    subsystems' own records on a small spilled streamed fit."""
    cobj = _spilled_objective(tmp_path)
    log = RunLogger(str(tmp_path / "run_log.jsonl"))
    t = telemetry.start("metrics", run_logger=log)
    try:
        with count_compiles() as cc:
            _fit(cobj)
        summary = t.summary()
    finally:
        t.close()
        log.close()
    c = summary["counters"]
    store = cobj.batch.store
    assert c["solver.sweeps"] == cobj.sweeps > 0
    assert c["store.hits"] == store.hits
    assert c["store.loads"] == store.loads > 0
    assert c["jax.compiles"] == cc.count
    assert c["prefetch.chunks_consumed"] == cobj.sweeps * N_CHUNKS
    assert c["prefetch.consumer_wait_s"] >= 0.0
    assert c["solver.iterations"] >= 1
    assert c["solver.ls_trials"] >= c["solver.iterations"]
    # Derived overlap: defined whenever sweeps streamed through the
    # prefetcher.
    d = summary["derived"]
    assert 0.0 <= d["overlap_efficiency"] <= 1.0
    assert 0.0 <= d["consumer_blocked_fraction"] <= 1.0
    # The summary event landed in the run log.
    events = read_run_log(str(tmp_path / "run_log.jsonl"))
    summ = [e for e in events if e["event"] == "telemetry_summary"]
    assert len(summ) == 1
    assert summ[0]["counters"]["solver.sweeps"] == cobj.sweeps
    # metrics mode: aggregated span stats only, no per-span events.
    assert summ[0]["spans"]["sweep"]["count"] == cobj.sweeps
    assert not [e for e in events if e["event"] == "span"]


# ---------------------------------------------------------------------------
# trace export
# ---------------------------------------------------------------------------


def _check_nesting(spans_by_tid):
    """Spans on one thread must be properly nested: a depth-d span lies
    inside the enclosing depth-(d-1) span's interval (small float
    slack)."""
    eps = 5e-3
    for tid, spans in spans_by_tid.items():
        spans = sorted(spans, key=lambda s: (s["ts"], -s["dur"]))
        stack = []
        for s in spans:
            while stack and stack[-1]["depth"] >= s["depth"]:
                stack.pop()
            if s["depth"] > 0:
                assert stack, f"depth-{s['depth']} span with no parent"
                parent = stack[-1]
                assert parent["depth"] == s["depth"] - 1
                assert s["ts"] >= parent["ts"] - eps
                assert (s["ts"] + s["dur"]
                        <= parent["ts"] + parent["dur"] + eps)
            stack.append(s)


def test_trace_export_valid_chrome_json_and_nesting(tmp_path):
    cobj = _spilled_objective(tmp_path)
    log = RunLogger(str(tmp_path / "run_log.jsonl"))
    t = telemetry.start("trace", telemetry_dir=str(tmp_path),
                        run_logger=log)
    try:
        with telemetry.span("fit", cat="phase"):
            _fit(cobj)
    finally:
        t.close()
        log.close()

    # trace.json: valid Chrome trace-event JSON.
    with open(tmp_path / "trace.json") as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    phs = {e["ph"] for e in events}
    assert "X" in phs and "M" in phs
    for e in events:
        assert {"ph", "name", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 1 and e["ts"] >= 0
    # Thread-name metadata names the prefetch thread.
    names = {e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "photon-chunk-prefetch" in names
    assert any("MainThread" in n for n in names)

    # JSONL span events: nested correctly per thread, spans from BOTH
    # threads present.
    evs = read_run_log(str(tmp_path / "run_log.jsonl"))
    spans = [e for e in evs if e["event"] == "span"]
    assert spans
    by_tid: dict = {}
    for s in spans:
        by_tid.setdefault(s["tid"], []).append(s)
    assert len(by_tid) >= 2          # main + prefetch thread
    _check_nesting(by_tid)
    names = {s["name"] for s in spans}
    assert {"fit", "sweep", "chunk_compute", "prefetch_load",
            "prefetch_place"} <= names
    # The prefetch thread's loads/places carry the chunk index arg.
    loads = [s for s in spans if s["name"] == "prefetch_load"]
    assert all("args" in s and "chunk" in s["args"] for s in loads)


def test_report_cli_reconciles_and_reports_overlap(tmp_path, capsys):
    from photon_ml_tpu.telemetry.__main__ import main as telemetry_main

    cobj = _spilled_objective(tmp_path)
    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    t = telemetry.start("trace", telemetry_dir=str(tmp_path),
                        run_logger=log)
    try:
        with log.timed("fit"):
            _fit(cobj)
    finally:
        t.close()
        log.close()

    rc = telemetry_main(["report", log_path])
    out = capsys.readouterr().out
    assert rc == 0
    tail = json.loads(out.strip().splitlines()[-1])
    # The fit phase span covers the solve: stage spans reconcile to
    # >= 90% of the measured wall clock (the ISSUE acceptance bar).
    assert tail["ok"] is True
    assert tail["reconciliation"] >= 0.9
    assert tail["overlap_efficiency"] is not None
    assert 0.0 <= tail["overlap_efficiency"] <= 1.0
    assert tail["phases"]["fit"] > 0
    assert "Reconciliation" in out and "overlap efficiency" in out


def test_report_tolerates_torn_tail(tmp_path, capsys):
    """The report's primary forensic case is a killed run — which can
    leave a partial final JSONL line.  Malformed lines are skipped and
    counted, never fatal (review finding)."""
    from photon_ml_tpu.telemetry.__main__ import main as telemetry_main

    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    t = telemetry.start("metrics", run_logger=log, heartbeat_s=0.01)
    try:
        t.heartbeat("prefetch-producer", chunk=3)
    finally:
        t.close()
        log.close()
    with open(log_path, "a") as f:
        f.write('{"t": 1.0, "event": "hea')     # torn mid-write
    rc = telemetry_main(["report", log_path])
    out = capsys.readouterr().out
    assert rc == 0
    assert "malformed line(s) skipped" in out
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["heartbeats"]["prefetch-producer"] == 1


def test_report_cli_fails_below_threshold(tmp_path, capsys):
    """An uninstrumented gap (idle wall clock between depth-0 spans)
    fails the reconciliation check at rc 1."""
    from photon_ml_tpu.telemetry.__main__ import main as telemetry_main

    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    t = telemetry.start("trace", run_logger=log)
    try:
        with telemetry.span("a", cat="x"):
            time.sleep(0.02)
        time.sleep(0.2)            # unattributed wall clock
        with telemetry.span("b", cat="x"):
            time.sleep(0.02)
    finally:
        t.close()
        log.close()
    rc = telemetry_main(["report", log_path])
    tail = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1 and tail["ok"] is False
    assert tail["reconciliation"] < 0.9


# ---------------------------------------------------------------------------
# liveness: heartbeats + thread death
# ---------------------------------------------------------------------------


def test_prefetcher_death_emits_exception_event(tmp_path):
    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    t = telemetry.start("metrics", run_logger=log, heartbeat_s=0.05)

    boom = RuntimeError("disk on fire")

    def load(i):
        if i >= 2:
            raise boom
        return np.zeros(4)

    pf = ChunkPrefetcher(load, lambda h: h, depth=2)
    pf.start(range(5))
    try:
        with pytest.raises(RuntimeError, match="disk on fire"):
            for i in range(5):
                pf.next(i)
    finally:
        pf.close()
        t.close()
        log.close()
    deaths = [e for e in read_run_log(log_path)
              if e["event"] == "thread_exception"]
    assert len(deaths) == 1
    assert deaths[0]["stage"] == "prefetch-producer"
    assert "disk on fire" in deaths[0]["error"]
    assert deaths[0]["thread"] == "photon-chunk-prefetch"


def test_starved_consumer_emits_heartbeats(tmp_path):
    """A hung producer (slow load) shows as waiting-but-alive consumer
    heartbeats — the which-stage-stopped forensic."""
    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    t = telemetry.start("metrics", run_logger=log, heartbeat_s=0.05)

    def slow_load(i):
        time.sleep(0.4 if i == 1 else 0.0)
        return np.zeros(4)

    pf = ChunkPrefetcher(slow_load, lambda h: h, depth=1)
    pf.start(range(3))
    try:
        for i in range(3):
            pf.next(i)
    finally:
        pf.close()
        t.close()
        log.close()
    beats = [e for e in read_run_log(log_path)
             if e["event"] == "heartbeat"]
    consumer = [e for e in beats if e["stage"] == "prefetch-consumer"]
    assert consumer, beats
    assert consumer[0]["state"] == "queue_empty"
    assert consumer[0]["waiting_s"] > 0


def test_sink_writer_death_emits_exception_event(tmp_path):
    from photon_ml_tpu.estimators.streaming_scorer import _SinkWriter

    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    t = telemetry.start("metrics", run_logger=log, heartbeat_s=0.05)

    class BadSink:
        def write(self, *a, **kw):
            raise IOError("disk full")

    w = _SinkWriter([BadSink()])
    try:
        w.put(0, 4, np.zeros(4), np.zeros(4), np.zeros(4), {})
        with pytest.raises(IOError, match="disk full"):
            w.close()
            # A racing put may surface the error instead of close().
    finally:
        t.close()
        log.close()
    deaths = [e for e in read_run_log(log_path)
              if e["event"] == "thread_exception"]
    assert len(deaths) == 1
    assert deaths[0]["stage"] == "sink-writer"
    assert "disk full" in deaths[0]["error"]
    assert deaths[0]["thread"] == "photon-score-writer"


def test_idle_sink_writer_heartbeats(tmp_path):
    from photon_ml_tpu.estimators.streaming_scorer import _SinkWriter

    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    t = telemetry.start("metrics", run_logger=log, heartbeat_s=0.05)

    class NullSink:
        def write(self, *a, **kw):
            pass

    w = _SinkWriter([NullSink()])
    try:
        time.sleep(0.25)     # starved writer: heartbeats while waiting
        w.close()
    finally:
        t.close()
        log.close()
    beats = [e for e in read_run_log(log_path)
             if e["event"] == "heartbeat"
             and e["stage"] == "sink-writer"]
    assert beats
    assert beats[0]["state"] == "queue_empty"


# ---------------------------------------------------------------------------
# estimator / config wiring
# ---------------------------------------------------------------------------


def test_estimator_fit_honors_telemetry_config(tmp_path):
    """A programmatic fit with telemetry='trace' in the config produces
    run_log.jsonl + trace.json in telemetry_dir with no driver."""
    from photon_ml_tpu.config import (
        CoordinateConfig,
        CoordinateKind,
        OptimizerSettings,
        TrainingConfig,
    )
    from photon_ml_tpu.estimators import GameEstimator
    from photon_ml_tpu.game.dataset import GameDataset
    from photon_ml_tpu.models.glm import TaskType

    rng = np.random.default_rng(11)
    n, d = 400, 13
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    train = GameDataset(labels=y, features={"global": x}, entity_ids={})
    cfg = TrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinates=[CoordinateConfig(
            name="global", kind=CoordinateKind.FIXED_EFFECT,
            feature_shard="global",
            optimizer=OptimizerSettings(max_iters=10))],
        update_sequence=["global"],
        n_iterations=1,
        evaluators=[],
        telemetry="trace",
        telemetry_dir=str(tmp_path / "tel"),
        output_dir=str(tmp_path / "out"),
    )
    GameEstimator(cfg).fit(train)
    assert telemetry.active() is None     # session closed with fit
    tel_dir = tmp_path / "tel"
    assert (tel_dir / "trace.json").exists()
    events = read_run_log(str(tel_dir / "run_log.jsonl"))
    kinds = {e["event"] for e in events}
    assert {"telemetry_start", "telemetry_summary", "span",
            "trace_written"} <= kinds
    spans = [e for e in events if e["event"] == "span"]
    assert any(s["name"] == "estimator_fit" for s in spans)
    assert any(s["name"] == "cd_coordinate" for s in spans)


def test_e2e_streamed_swept_fit_trace_acceptance(tmp_path, capsys):
    """THE ISSUE-7 acceptance run, in miniature: an end-to-end streamed
    swept fit through the training driver with telemetry=trace yields
    run_log.jsonl + trace.json where the report CLI reconciles stage
    spans to >= 90% of measured wall clock and reports prefetcher
    overlap efficiency."""
    from photon_ml_tpu.cli import game_training_driver
    from photon_ml_tpu.io.libsvm import write_libsvm
    from photon_ml_tpu.telemetry.__main__ import main as telemetry_main
    from photon_ml_tpu.utils.synthetic import make_a1a_like

    rows, labels, _ = make_a1a_like(n=1200, seed=5)
    train_path = str(tmp_path / "a1a.libsvm")
    write_libsvm(train_path, rows, np.where(labels > 0, 1, -1))
    out_dir = str(tmp_path / "out")
    config = {
        "task_type": "LOGISTIC_REGRESSION",
        "coordinates": [{
            "name": "global", "kind": "FIXED_EFFECT",
            "feature_shard": "features",
            "optimizer": {"optimizer": "LBFGS", "reg_weight": 1.0,
                          "max_iters": 12},
        }],
        "update_sequence": ["global"],
        "input_path": train_path,
        "validation_fraction": 0.2,
        "output_dir": out_dir,
        "evaluators": ["AUC"],
        "reg_weight_grid": {"global": [3.0, 1.0, 0.3]},
        "chunk_rows": 200,
        "spill_dir": str(tmp_path / "spill"),
        "host_max_resident": 2,
        "telemetry": "trace",
    }
    cfg_path = str(tmp_path / "cfg.json")
    with open(cfg_path, "w") as f:
        json.dump(config, f)
    game_training_driver.main(["--config", cfg_path])
    assert telemetry.active() is None

    log_path = os.path.join(out_dir, "run_log.jsonl")
    assert os.path.exists(os.path.join(out_dir, "trace.json"))
    events = read_run_log(log_path)
    spans = [e for e in events if e["event"] == "span"]
    names = {s["name"] for s in spans}
    # Driver phases AND streaming-tier stages are on the timeline.
    assert {"fit", "sweep", "swept_train", "prefetch_load"} <= names

    rc = telemetry_main(["report", log_path])
    tail = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and tail["ok"] is True
    assert tail["reconciliation"] >= 0.9
    assert tail["overlap_efficiency"] is not None
    assert tail["counters"]["solver.sweeps"] > 0
    assert tail["counters"]["store.loads"] > 0


def test_runlogger_context_manager_and_thread_safety(tmp_path):
    path = str(tmp_path / "log.jsonl")
    with RunLogger(path) as log:
        log.event("hello", x=1)
        assert log._f is not None
    assert log._f is None                # context exit closed the file
    log.close()                          # idempotent (atexit fallback)
    events = read_run_log(path)
    # Schema header first (ISSUE 8 satellite), then the event.
    assert [e["event"] for e in events] == ["run_header", "hello"]
    assert events[0]["schema"] == 1
    assert events[0]["run_id"]
    assert isinstance(events[0]["argv"], list)
    # Cross-thread event writes keep lines whole (the lock contract:
    # heartbeats arrive from pipeline threads).
    with RunLogger(path) as log:
        threads = [threading.Thread(
            target=lambda j=j: [log.event("t", j=j, i=i)
                                for i in range(50)])
            for j in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    events = read_run_log(path)          # every line parses
    assert len(events) == 201            # header + 200 thread events


def test_runlogger_atexit_flush_fallback(tmp_path):
    """An abandoned logger (no close) still lands its events at
    interpreter exit — the file handle no longer leaks buffered
    lines."""
    import subprocess
    import sys

    path = str(tmp_path / "leak.jsonl")
    code = (
        "from photon_ml_tpu.utils.run_log import RunLogger\n"
        f"log = RunLogger({path!r})\n"
        "log.event('abandoned', x=1)\n"
        "# no close(): the atexit fallback must flush+close\n"
    )
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    events = read_run_log(path)
    assert [e["event"] for e in events] == ["run_header", "abandoned"]


# ---------------------------------------------------------------------------
# ISSUE 8: histogram percentiles (bounded-error contract)
# ---------------------------------------------------------------------------


def test_histogram_percentile_bounded_error():
    """The reservoir is a deterministic every-stride-th subsample; its
    quantiles must track the stream's within the documented rank-error
    bound once the stream far exceeds the cap (10000 obs vs cap 1024 →
    reservoir ≥ 512 entries)."""
    n = 10_000
    rng = np.random.default_rng(17)
    shuffled = rng.permutation(n).astype(float)
    t = telemetry.start("metrics")
    try:
        for v in shuffled:
            t.observe("test.shuffled", v)
        for v in range(n):                       # arrival-ordered
            t.observe("test.ordered", float(v))
        for q, truth in ((0.5, 0.5 * (n - 1)), (0.95, 0.95 * (n - 1)),
                         (0.99, 0.99 * (n - 1))):
            # Ordered arrivals: systematic sample → near-exact.
            assert abs(t.percentile("test.ordered", q) - truth) <= 0.01 * n
            # Shuffled arrivals: uniform-ish subsample of ≥512 → a few
            # percentile points of rank error.
            assert abs(t.percentile("test.shuffled", q) - truth) <= 0.05 * n
        assert t.percentile("no.such.metric", 0.5) is None
        with pytest.raises(ValueError, match="quantile"):
            t.percentile("test.ordered", 1.5)
        summ = t.summary()
        h = summ["histograms"]["test.ordered"]
        assert h["p50"] is not None and h["p95"] is not None
        assert h["p50"] <= h["p95"] <= h["p99"] <= h["max"]
    finally:
        t.close()


# ---------------------------------------------------------------------------
# ISSUE 8: device accounting
# ---------------------------------------------------------------------------


def test_device_cost_captured_on_streamed_fit(tmp_path):
    """A metrics-mode streamed fit captures the per-chunk programs' XLA
    cost analyses (FLOPs, bytes, roofline estimate) once per session,
    emits device_cost events, and samples the device-memory gauge at
    phase boundaries (live-buffer census on the CPU backend)."""
    cobj = _spilled_objective(tmp_path)
    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    t = telemetry.start("metrics", run_logger=log)
    try:
        with telemetry.span("fit", cat="phase"):
            _fit(cobj)
        summary = t.summary()
    finally:
        t.close()
        log.close()
    programs = summary["device"]["programs"]
    assert {"chunk_vg", "chunk_value"} <= set(programs)
    for name in ("chunk_vg", "chunk_value"):
        cost = programs[name]
        assert cost["flops"] > 0
        assert cost["bytes_accessed"] > 0
        # The counts stand on any backend; a roofline estimate is made
        # only against a listed accelerator's published peak.
        assert cost["platform"] == "cpu"
        assert "roofline_est_ms" not in cost
        assert cost["span"] == "chunk_compute"
    # Phase boundaries sampled the device-memory gauge (CPU → census).
    mem = summary["device"]["memory"]
    assert mem["source"] == "live_arrays"
    assert mem["samples"] >= 2                   # fit open + close
    assert summary["gauges"]["device.bytes_in_use"]["last"] >= 0
    events = read_run_log(log_path)
    costs = [e for e in events if e["event"] == "device_cost"]
    assert {e["program"] for e in costs} >= {"chunk_vg", "chunk_value"}
    # Each boundary sample lands as a TAGGED event, so a specific
    # boundary's footprint is recoverable from the log.
    mems = [e for e in events if e["event"] == "device_memory"]
    assert mems and all(e["tag"] == "fit" for e in mems)


def test_device_capture_compiles_nothing_new(tmp_path):
    """The capture relowers a warm program: the compile bridge (and the
    guard listener) must see ZERO new compile records — the
    compile-budget contract with telemetry on."""
    cobj = _spilled_objective(tmp_path)
    w0 = jnp.zeros(D, jnp.float32)
    _fit(cobj, max_iters=2)      # everything compiled, no session
    t = telemetry.start("metrics")
    try:
        with count_compiles() as cc:
            cobj.capture_device_cost(w0)
        summary = t.summary()
    finally:
        t.close()
    assert cc.count == 0, cc.programs
    assert summary["counters"].get("jax.compiles", 0) == 0
    assert summary["device"]["programs"]["chunk_vg"]["flops"] > 0


def test_report_shows_device_section(tmp_path, capsys):
    from photon_ml_tpu.telemetry.__main__ import main as telemetry_main

    cobj = _spilled_objective(tmp_path)
    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    t = telemetry.start("trace", telemetry_dir=str(tmp_path),
                        run_logger=log)
    try:
        with log.timed("fit"):
            _fit(cobj)
    finally:
        t.close()
        log.close()
    rc = telemetry_main(["report", log_path])
    out = capsys.readouterr().out
    assert rc == 0
    assert "Device programs (XLA cost analysis):" in out
    tail = json.loads(out.strip().splitlines()[-1])
    dev = tail["device"]["programs"]["chunk_vg"]
    assert dev["bytes_accessed"] > 0
    assert dev["measured_span_ms"] > 0
    # No roofline estimate on the CPU backend, so nothing to join ...
    assert "roofline_fraction" not in dev
    # ... and where a capture carries one (an accelerator run), it is
    # joined against the measured per-dispatch time.
    from photon_ml_tpu.telemetry.report import _device

    joined = _device({
        "device": {"programs": {"chunk_vg": {
            "bytes_accessed": 8.19e8, "roofline_est_ms": 1.0}}},
        "histograms": {"device.dispatch_s.chunk_vg": {
            "count": 4, "mean": 0.004}}})
    assert joined["programs"]["chunk_vg"]["roofline_fraction"] == 0.25


# ---------------------------------------------------------------------------
# ISSUE 8: convergence traces + sweep-odometer reconciliation
# ---------------------------------------------------------------------------


def test_convergence_events_reconcile_with_odometer(tmp_path, capsys):
    """A metrics-mode streamed fit emits one convergence_iter event per
    solver iteration and one convergence_trace per solve; the report's
    sweep-odometer identity (sweeps == solves + ls trials + grad
    recoveries + aux) holds exactly."""
    from photon_ml_tpu.telemetry.__main__ import main as telemetry_main

    cobj = _spilled_objective(tmp_path)
    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    t = telemetry.start("metrics", run_logger=log)
    try:
        _fit(cobj)
        summary = t.summary()
    finally:
        t.close()
        log.close()
    c = summary["counters"]
    events = read_run_log(log_path)
    iters = [e for e in events if e["event"] == "convergence_iter"]
    traces = [e for e in events if e["event"] == "convergence_trace"]
    assert len(iters) == c["solver.iterations"] == c["conv.iterations"]
    assert len(traces) == 1
    tr = traces[0]
    assert tr["solver"] == "streaming_lbfgs"
    assert tr["iterations"] >= 1
    # Tracker planes ride the trace: slot 0 (initial) + one per iter.
    assert len(tr["values"]) == tr["iterations"] + 1
    assert len(tr["step_sizes"]) == tr["iterations"] + 1
    # Per-iteration events carry step size and trial count.
    assert all("step_size" in e and e["ls_trials"] >= 1 for e in iters)
    # The odometer identity, from the raw counters...
    assert c["solver.sweeps"] == (c["solver.streamed_solves"]
                                  + c["solver.ls_trials"]
                                  + c.get("solver.grad_recovery_sweeps", 0)
                                  + c.get("solver.aux_sweeps", 0))
    # ...and through the report (rc 0, convergence ok).
    rc = telemetry_main(["report", log_path])
    out = capsys.readouterr().out
    assert rc == 0
    assert "sweep odometer" in out and "PASS" in out
    tail = json.loads(out.strip().splitlines()[-1])
    conv = tail["convergence"]
    assert conv["ok"] is True
    assert conv["unattributed_sweeps"] == 0
    assert conv["iterations"]["streaming_lbfgs"] == len(iters)


def test_tron_convergence_reconciles_with_hvp_odometer(tmp_path, capsys):
    """ISSUE 17: a streamed TRON fit closes the sweep-odometer identity
    through the new hvp_sweeps term exactly — sweeps == streamed_solves
    (the initial value+gradient) + ls_trials (one trial point per outer
    iteration) + aux_sweeps (the Jacobi diagonal) + hvp_sweeps (the CG
    passes) — and the report renders the trust-region trajectory (the
    per-iteration delta/rho the convergence events carry)."""
    from photon_ml_tpu.optim.streaming import streaming_tron_solve
    from photon_ml_tpu.telemetry.__main__ import main as telemetry_main

    cobj = _spilled_objective(tmp_path)
    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    t = telemetry.start("metrics", run_logger=log)
    try:
        streaming_tron_solve(
            cobj.value_and_gradient, cobj.hvp_pass,
            jnp.zeros(D, jnp.float32),
            OptimizerConfig(max_iters=4, tolerance=1e-9),
            hessian_diag=cobj.hessian_diagonal, label="t")
        summary = t.summary()
    finally:
        t.close()
        log.close()
    c = summary["counters"]
    assert c["solver.hvp_sweeps"] > 0
    assert c["solver.aux_sweeps"] >= 1       # the preconditioner pass
    assert c["solver.sweeps"] == (
        c["solver.streamed_solves"] + c["solver.ls_trials"]
        + c.get("solver.grad_recovery_sweeps", 0)
        + c["solver.aux_sweeps"]
        + c.get("solver.fused_cycle_sweeps", 0)
        + c["solver.hvp_sweeps"])
    events = read_run_log(log_path)
    iters = [e for e in events if e["event"] == "convergence_iter"]
    assert len(iters) == c["solver.iterations"]
    # Every TRON iteration event carries the radius and the ratio.
    assert all(e.get("delta", 0) > 0 for e in iters)
    assert all("rho" in e for e in iters)
    rc = telemetry_main(["report", log_path])
    out = capsys.readouterr().out
    assert rc == 0
    tail = json.loads(out.strip().splitlines()[-1])
    conv = tail["convergence"]
    assert conv["ok"] is True
    assert conv["unattributed_sweeps"] == 0
    assert conv["hvp_sweeps"] == c["solver.hvp_sweeps"]
    assert conv["passes_per_solve"] == c["solver.sweeps"]
    tr = conv["trust_region"]["streaming_tron:t"]
    assert len(tr["delta"]) == len(iters)
    assert tr["delta"][0] > 0
    assert "trust region" in out
    assert "hvp" in out


def test_direct_evaluations_stay_informational(tmp_path, capsys):
    """A direct objective evaluation outside any solve (a final-loss
    log line, a notebook probe) is a legitimate pass no solve claims:
    it must show as POSITIVE unattributed sweeps and keep rc 0 — only
    impossible accounting (negative) fails the gate."""
    from photon_ml_tpu.telemetry.__main__ import main as telemetry_main

    cobj = _spilled_objective(tmp_path)
    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    t = telemetry.start("metrics", run_logger=log)
    try:
        res = _fit(cobj)
        cobj.value(res.w)                      # the unclaimed pass
    finally:
        t.close()
        log.close()
    rc = telemetry_main(["report", log_path])
    tail = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and tail["ok"] is True
    assert tail["convergence"]["ok"] is True
    assert tail["convergence"]["unattributed_sweeps"] == 1


def test_report_fails_on_odometer_drift(tmp_path, capsys):
    """A log whose counters claim more solver evaluations than data
    passes (the drift this check exists to catch) fails the report at
    rc 1 naming the convergence check."""
    from photon_ml_tpu.telemetry.__main__ import main as telemetry_main

    log_path = str(tmp_path / "drift.jsonl")
    events = [
        {"t": 0.0, "event": "run_header", "schema": 1, "run_id": "x"},
        {"t": 1.0, "event": "telemetry_summary", "mode": "metrics",
         "counters": {"solver.sweeps": 3, "solver.streamed_solves": 1,
                      "solver.ls_trials": 4, "solver.iterations": 4},
         "gauges": {}, "histograms": {}, "spans": {}, "derived": {}},
    ]
    with open(log_path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    rc = telemetry_main(["report", log_path])
    out = capsys.readouterr().out
    assert rc == 1
    assert "CONVERGENCE FAIL" in out
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["ok"] is False
    assert tail["convergence"]["ok"] is False
    # 3 sweeps recorded, 1 + 4 = 5 claimed evaluations → 2 passes
    # claimed by nobody's data.
    assert tail["convergence"]["unattributed_sweeps"] == -2


def test_e2e_swept_streamed_fit_metrics_convergence(tmp_path, capsys):
    """THE ISSUE-8 acceptance run: an e2e swept streamed fit through
    the training driver with telemetry=metrics emits convergence traces
    whose per-solver iteration totals reconcile with the solver.sweeps
    odometer in `telemetry report`."""
    from photon_ml_tpu.cli import game_training_driver
    from photon_ml_tpu.io.libsvm import write_libsvm
    from photon_ml_tpu.telemetry.__main__ import main as telemetry_main
    from photon_ml_tpu.utils.synthetic import make_a1a_like

    rows, labels, _ = make_a1a_like(n=1200, seed=5)
    train_path = str(tmp_path / "a1a.libsvm")
    write_libsvm(train_path, rows, np.where(labels > 0, 1, -1))
    out_dir = str(tmp_path / "out")
    config = {
        "task_type": "LOGISTIC_REGRESSION",
        "coordinates": [{
            "name": "global", "kind": "FIXED_EFFECT",
            "feature_shard": "features",
            "optimizer": {"optimizer": "LBFGS", "reg_weight": 1.0,
                          "max_iters": 12},
        }],
        "update_sequence": ["global"],
        "input_path": train_path,
        "validation_fraction": 0.2,
        "output_dir": out_dir,
        "evaluators": ["AUC"],
        "reg_weight_grid": {"global": [3.0, 1.0, 0.3]},
        "chunk_rows": 200,
        "spill_dir": str(tmp_path / "spill"),
        "host_max_resident": 2,
        "telemetry": "metrics",
    }
    cfg_path = str(tmp_path / "cfg.json")
    with open(cfg_path, "w") as f:
        json.dump(config, f)
    game_training_driver.main(["--config", cfg_path])
    assert telemetry.active() is None

    log_path = os.path.join(out_dir, "run_log.jsonl")
    events = read_run_log(log_path)
    # Header first (schema-versioned), convergence events present.
    assert events[0]["event"] == "run_header"
    assert events[0]["schema"] == 1
    assert events[0]["telemetry"] == "metrics"
    iters = [e for e in events if e["event"] == "convergence_iter"]
    assert iters and all(e["solver"] == "streaming_lbfgs_swept"
                         and e["label"] == "global" for e in iters)
    assert all(len(e["values"]) == 3 for e in iters)   # per-lane
    traces = [e for e in events if e["event"] == "convergence_trace"]
    assert len(traces) == 1 and traces[0]["lanes"] == 3

    rc = telemetry_main(["report", log_path])
    out = capsys.readouterr().out
    tail = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and tail["ok"] is True
    conv = tail["convergence"]
    assert conv["ok"] is True
    assert conv["sweeps"] > 0
    assert conv["unattributed_sweeps"] == 0
    assert conv["iterations"]["streaming_lbfgs_swept:global"] == len(iters)
    assert tail["run_id"] == events[0]["run_id"]


def test_scoring_driver_trace_mode_report(tmp_path, capsys):
    """ISSUE 8 satellite: `telemetry report` over a trace-mode log
    produced by the SCORING driver e2e (only the training driver path
    was reconciliation-tested before)."""
    from photon_ml_tpu.cli import game_scoring_driver, game_training_driver
    from photon_ml_tpu.io.libsvm import write_libsvm
    from photon_ml_tpu.telemetry.__main__ import main as telemetry_main
    from photon_ml_tpu.utils.synthetic import make_a1a_like

    rows, labels, _ = make_a1a_like(n=1000, seed=7)
    train_path = str(tmp_path / "a1a.libsvm")
    write_libsvm(train_path, rows, np.where(labels > 0, 1, -1))
    out_dir = str(tmp_path / "out")
    config = {
        "task_type": "LOGISTIC_REGRESSION",
        "coordinates": [{
            "name": "global", "kind": "FIXED_EFFECT",
            "feature_shard": "features",
            "optimizer": {"reg_weight": 1.0, "max_iters": 10},
        }],
        "update_sequence": ["global"],
        "input_path": train_path,
        "output_dir": out_dir,
        "evaluators": [],
    }
    cfg_path = str(tmp_path / "cfg.json")
    with open(cfg_path, "w") as f:
        json.dump(config, f)
    game_training_driver.main(["--config", cfg_path])

    score_dir = tmp_path / "scored"
    sc = {"input_path": train_path,
          "model_dir": os.path.join(out_dir, "model"),
          "output_path": str(score_dir / "scores.npz"),
          "evaluators": ["AUC"],
          "score_chunk_rows": 128,
          "spill_dir": str(tmp_path / "spill_sc"),
          "host_max_resident": 2,
          "telemetry": "trace"}
    sc_path = str(tmp_path / "sc.json")
    with open(sc_path, "w") as f:
        json.dump(sc, f)
    game_scoring_driver.main(["--config", sc_path])
    assert telemetry.active() is None

    log_path = str(score_dir / "scoring_log.jsonl")
    assert os.path.exists(str(score_dir / "trace.json"))
    events = read_run_log(log_path)
    assert events[0]["event"] == "run_header"
    assert events[0]["driver"] == "game_scoring"
    assert events[0]["telemetry"] == "trace"
    spans = [e for e in events if e["event"] == "span"]
    names = {s["name"] for s in spans}
    assert {"transform_streamed", "score_pass", "chunk_compute"} <= names

    rc = telemetry_main(["report", log_path])
    out = capsys.readouterr().out
    tail = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and tail["ok"] is True
    assert tail["reconciliation"] >= 0.9
    assert tail["phases"]["transform_streamed"] > 0
    assert tail["counters"]["score.passes"] == 1


def test_streamed_re_emits_convergence_dynamics(tmp_path):
    """The streamed random-effect coordinate emits one re_convergence
    event per sweep carrying the solved/converged/retired/woken entity
    dynamics (previously judged only by end-state parity)."""
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.game.coordinates import (
        build_streamed_random_effect_coordinate,
    )
    from photon_ml_tpu.game.dataset import GameDataset
    from photon_ml_tpu.optim import OptimizerConfig

    rng = np.random.default_rng(3)
    n, p, E = 600, 4, 24
    ids = rng.integers(0, E, n)
    x = rng.normal(size=(n, p)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    ds = GameDataset(labels=y, features={"re": x},
                     entity_ids={"u": ids})
    obj = GLMObjective(loss=losses.LOGISTIC,
                       reg=RegularizationContext.l2(1.0),
                       norm=NormalizationContext.identity())
    coord = build_streamed_random_effect_coordinate(
        "u", ds, "re", obj,
        config=OptimizerConfig(max_iters=30, tolerance=1e-4),
        spill_dir=str(tmp_path / "spill_re"), chunk_entities=8,
        host_max_resident=2, prefetch_depth=1, retirement=True)

    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    t = telemetry.start("metrics", run_logger=log)
    try:
        off = jnp.zeros(n, jnp.float32)
        w, diag = coord.train(off, None)
        coord.retire_converged()               # sweep 1: no candidates yet
        w, diag = coord.train(off, w)
        coord.retire_converged()               # static offsets → retire
        w, diag = coord.train(off, w)
        summary = t.summary()
    finally:
        t.close()
        log.close()
    assert "entities_woken" in diag
    events = read_run_log(log_path)
    res = [e for e in events if e["event"] == "re_convergence"]
    assert len(res) == 3
    assert res[0]["coordinate"] == "u"
    assert res[0]["entities_solved"] == E
    assert res[2]["entities_retired"] > 0      # third sweep saw frozen
    assert summary["counters"]["conv.re_sweeps"] == 3
    # Device cost of the per-bucket chunk-train program was captured.
    programs = summary.get("device", {}).get("programs", {})
    assert any(k.startswith("re_chunk_train.b") for k in programs)
