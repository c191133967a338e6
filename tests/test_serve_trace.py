"""End-to-end request tracing (ISSUE 14): trace context + header
contract, the tail-sampled ring buffer under concurrency, stage
attribution through the real serving path, Chrome flow-event export,
and the ``serve-report`` cross-process join.

The acceptance checks live here: every request above
the tail threshold is retained (tail sampling is COMPLETE, not
probabilistic), the ring buffer stays bounded under sustained
concurrent load, the exported flow events are valid Chrome JSON whose
``s``/``f`` ids join across process ids, request ids ride EVERY
response (sheds included), and a warm traced server still compiles
nothing (guard-pinned).
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from photon_ml_tpu import telemetry
from photon_ml_tpu.analysis.guards import count_compiles
from photon_ml_tpu.config import ServingConfig
from photon_ml_tpu.io import model_io
from photon_ml_tpu.serving import tracing
from photon_ml_tpu.serving.http import HttpEndpoint, HttpError
from photon_ml_tpu.serving.server import ModelServer
from photon_ml_tpu.telemetry import monitor as _mon
from photon_ml_tpu.telemetry.__main__ import main as telemetry_main
from photon_ml_tpu.telemetry.export import serve_trace_events
from photon_ml_tpu.telemetry.serve_report import (
    analyze,
    load_trace_files,
    run_serve_report,
)
from photon_ml_tpu.utils.run_log import RunLogger, read_run_log

from test_serving import TASK, _serve_cfg, _workload

pytestmark = pytest.mark.fast


@pytest.fixture(autouse=True)
def _no_leaked_sessions():
    """Tracing tests must leave every module-global session closed
    (the test_serving/test_monitor discipline), recorder included."""
    assert tracing.active() is None
    assert telemetry.active() is None and _mon.active() is None
    yield
    leaked = []
    if tracing.active() is not None:
        tracing.active().close()
        leaked.append("tracing")
    if _mon.active() is not None:
        _mon.active().close()
        leaked.append("monitor")
    if telemetry.active() is not None:
        telemetry.active().close()
        leaked.append("telemetry")
    assert not leaked, f"leaked sessions: {leaked}"


# ---------------------------------------------------------------------------
# trace context + header parsing
# ---------------------------------------------------------------------------


def test_trace_context_mint_parse_round_trip():
    ctx = tracing.mint()
    assert len(ctx.trace_id) == 20 and ctx.hop == 0
    assert tracing.mint().trace_id != ctx.trace_id     # unique
    # Per-process random prefix: two processes cannot collide.
    assert ctx.trace_id.startswith(tracing._MINT_PREFIX)
    back = tracing.parse_trace_header(ctx.header_value())
    assert back.trace_id == ctx.trace_id and back.hop == 0
    child = tracing.parse_trace_header(ctx.child_header())
    assert child.trace_id == ctx.trace_id and child.hop == 1


def test_trace_header_parsing_rejects_garbage():
    assert tracing.parse_trace_header(None) is None
    assert tracing.parse_trace_header("") is None
    assert tracing.parse_trace_header("bad id with spaces/1") is None
    assert tracing.parse_trace_header("x" * 100 + "/1") is None
    assert tracing.parse_trace_header("abc/notanint") is None
    # Bare id (no hop) is accepted at hop 0; negative hops clamp.
    assert tracing.parse_trace_header("abc123").hop == 0
    assert tracing.parse_trace_header("abc123/-4").hop == 0


def test_from_headers_adoption_order():
    ctx = tracing.from_headers({"X-Photon-Trace": "cafe01/2"})
    assert ctx.trace_id == "cafe01" and ctx.hop == 2
    # A bare client request id is adopted as the trace id.
    ctx = tracing.from_headers({"X-Photon-Request-Id": "client-7"})
    assert ctx.trace_id == "client-7" and ctx.hop == 0
    # Garbage in either header mints instead of echoing it back.
    ctx = tracing.from_headers({"X-Photon-Request-Id": "bad id!"})
    assert ctx.trace_id != "bad id!" and len(ctx.trace_id) == 20
    assert tracing.from_headers({}).hop == 0


def test_serving_config_trace_validation():
    cfg = ServingConfig(model_dir="m")
    cfg.validate()                    # tracing on by default
    assert cfg.trace == "on"
    for field, bad in (("trace", "maybe"), ("trace_threshold_ms", -1.0),
                       ("trace_sample_every", -1), ("trace_buffer", 0)):
        c = ServingConfig(model_dir="m", **{field: bad})
        with pytest.raises(ValueError):
            c.validate()


# ---------------------------------------------------------------------------
# recorder: tail sampling, floor, ring bounds, batch linking
# ---------------------------------------------------------------------------


def _finish_with_duration(rec, dur_s: float, stages: dict | None = None,
                          batch: int | None = None) -> None:
    """Drive one request through the recorder with a synthetic
    duration (t0 shifted back — no sleeps in tier-1)."""
    rt = rec.begin()
    tracing.take_attached()           # tests finish manually
    rt.t0 -= dur_s
    for k, v in (stages or {}).items():
        rt.stamp(k, v)
    rt.batch = batch
    rec.finish(rt, status=200)


def test_tail_sampling_keeps_every_slow_request(tmp_path):
    """COMPLETE tail capture: every request at/above the threshold is
    retained and exported as a request_trace event; fast requests are
    dropped (histograms aside)."""
    log = RunLogger(str(tmp_path / "log.jsonl"))
    rec = tracing.TraceRecorder(threshold_s=0.010, sample_every=0,
                                cap=64, run_logger=log)
    for i in range(40):
        _finish_with_duration(rec, 0.050 if i % 2 else 0.001)
    rec.close()
    log.close()
    events = read_run_log(str(tmp_path / "log.jsonl"))
    traces = [e for e in events if e["event"] == "request_trace"]
    assert len(traces) == 20                     # every slow one
    assert all(t["sampled"] == "tail" for t in traces)
    assert all(t["total_ms"] >= 10.0 for t in traces)
    summary = [e for e in events
               if e["event"] == "serve_trace_summary"][0]
    assert summary["requests"] == 40
    assert summary["sampled_tail"] == 20


def test_floor_sampling_is_deterministic(tmp_path):
    """With an unreachable threshold the 1-in-N floor still samples —
    deterministically (no RNG in the telemetry path)."""
    log = RunLogger(str(tmp_path / "log.jsonl"))
    rec = tracing.TraceRecorder(threshold_s=10.0, sample_every=10,
                                cap=64, run_logger=log)
    for _ in range(35):
        _finish_with_duration(rec, 0.001)
    snap = rec.snapshot()
    rec.close()
    log.close()
    assert snap["sampled_floor"] == 4            # seq 0, 10, 20, 30
    traces = [e for e in read_run_log(str(tmp_path / "log.jsonl"))
              if e["event"] == "request_trace"]
    assert len(traces) == 4
    assert all(t["sampled"] == "floor" for t in traces)


def test_ring_bounded_under_concurrent_load(tmp_path):
    """8 threads x 100 all-tail requests: the in-memory ring stays at
    its cap, the pending-batch window stays bounded, and EVERY request
    still reached the JSONL export (bounded memory, complete tail)."""
    log = RunLogger(str(tmp_path / "log.jsonl"))
    rec = tracing.TraceRecorder(threshold_s=0.0, sample_every=0,
                                cap=32, run_logger=log)

    def worker(seed: int) -> None:
        for j in range(100):
            bt = rec.begin_batch(bucket=8, rows=4, requests=1)
            bt.stamp("dispatch", 0.002)
            rec.finish_batch(bt)
            _finish_with_duration(rec, 0.005,
                                  stages={"queue_wait": 0.001},
                                  batch=bt.batch_id)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = rec.snapshot()
    assert snap["requests"] == 800
    assert snap["sampled_tail"] == 800
    assert snap["buffered"] <= 32                # ring bounded
    assert len(rec._pending) <= tracing._PENDING_BATCH_CAP
    rec.close()
    log.close()
    events = read_run_log(str(tmp_path / "log.jsonl"))
    traces = [e for e in events if e["event"] == "request_trace"]
    assert len(traces) == 800                    # none lost
    assert len({t["trace"] for t in traces}) == 800


def test_batch_ids_unique_across_recorder_incarnations(tmp_path):
    """Review finding (round 19): a restarted replica appends to the
    SAME log with a fresh recorder whose sequence restarts — bare
    integer batch ids would collide across the stitched segments and
    serve-report would join a pre-kill tail request to a post-restart
    batch's stages.  The per-recorder random prefix makes them
    disjoint, and the attribution picks the RIGHT batch."""
    log_path = tmp_path / "replica.jsonl"
    ids = []
    for incarnation in range(2):
        log = RunLogger(str(log_path),
                        mode=("w" if incarnation == 0 else "a"),
                        header=True)
        rec = tracing.TraceRecorder(threshold_s=0.0, sample_every=0,
                                    cap=16, run_logger=log)
        bt = rec.begin_batch(bucket=8, rows=4, requests=1)
        bt.stamp("dispatch", 0.001 * (incarnation + 1))
        rec.finish_batch(bt)
        ids.append(bt.batch_id)
        _finish_with_duration(rec, 0.020, batch=bt.batch_id)
        rec.close()
        log.close()
    assert ids[0] != ids[1]              # no cross-segment collision
    result = analyze(load_trace_files([str(log_path)]))
    # Each tail request joined ITS OWN batch: the two dispatch stamps
    # (1ms and 2ms) both appear, not one batch claimed twice.
    assert result["stages"]["dispatch"]["count"] == 2
    assert result["tail_requests"] == 2


def test_batch_registered_before_members_can_finish():
    """Review finding (round 19): the dispatcher must register the
    completed batch BEFORE waking member slots — a member's finish()
    races it otherwise and the shared span is silently dropped.  Drive
    the real batcher and assert every retained request's batch was
    emitted exactly once per batch."""
    from test_serving import _FakeEngine

    from photon_ml_tpu.serving.batcher import MicroBatcher

    rec = tracing.start(threshold_s=0.0, sample_every=0, cap=64)
    batcher = None
    try:
        engine = _FakeEngine()
        batcher = MicroBatcher(lambda: engine, [4, 8],
                               deadline_s=0.001)
        rts = []
        for _ in range(6):
            rt = rec.begin()
            tracing.take_attached()
            batcher.submit([1.0, 2.0], trace=rt)
            rec.finish(rt, status=200)
            rts.append(rt)
        assert all(rt.batch is not None for rt in rts)
        with rec._lock:
            emitted = {bt.batch_id for bt in rec._batch_ring}
        # Every request's linked batch made it to the retained set —
        # none lost to the registration race.
        assert {rt.batch for rt in rts} <= emitted
    finally:
        if batcher is not None:
            batcher.close()
        rec.close()


def test_batch_trace_emitted_once_for_shared_batch(tmp_path):
    """The shared micro-batch span is recorded ONCE however many
    member requests are retained — members link it by batch id."""
    log = RunLogger(str(tmp_path / "log.jsonl"))
    rec = tracing.TraceRecorder(threshold_s=0.0, sample_every=0,
                                cap=16, run_logger=log)
    bt = rec.begin_batch(bucket=8, rows=6, requests=3)
    bt.stamp("assemble", 0.001)
    bt.stamp("dispatch", 0.004)
    rec.finish_batch(bt)
    for _ in range(3):
        _finish_with_duration(rec, 0.020, batch=bt.batch_id)
    rec.close()
    log.close()
    events = read_run_log(str(tmp_path / "log.jsonl"))
    batches = [e for e in events if e["event"] == "batch_trace"]
    traces = [e for e in events if e["event"] == "request_trace"]
    assert len(batches) == 1                     # once, not per member
    assert len(traces) == 3
    assert all(t["batch"] == bt.batch_id for t in traces)
    assert batches[0]["requests"] == 3
    assert batches[0]["stages_ms"]["dispatch"] == pytest.approx(4.0)


def test_stage_histograms_fold_for_dropped_requests(tmp_path):
    """Requests below the threshold are dropped from the ring but
    still fold into the serve.stage.* histograms — /metrics sees the
    full stream, not the tail."""
    tel = telemetry.start("metrics")
    try:
        rec = tracing.TraceRecorder(threshold_s=10.0, sample_every=0,
                                    cap=8)
        for _ in range(12):
            _finish_with_duration(rec, 0.001,
                                  stages={"queue_wait": 0.002,
                                          "serialize": 0.0005})
        rec.close()
        assert rec.snapshot()["sampled_tail"] == 0
        summary = tracing.stage_summary()
        assert summary["queue_wait"]["count"] == 12
        assert summary["queue_wait"]["p50_ms"] == pytest.approx(
            2.0, rel=0.01)
        dom = tracing.dominant_stage(summary)
        assert dom[0] == "queue_wait"
        # No per-request counter churn (the p50 budget): the
        # recorder's own tally is the request count of record.
        assert tel.counter("serve.trace.requests") == 0
        assert rec.snapshot()["requests"] == 12
    finally:
        tel.close()


# ---------------------------------------------------------------------------
# HTTP core: request-id echo + context adoption
# ---------------------------------------------------------------------------


def _raw_get(port: int, path: str, headers: dict | None = None):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, dict(r.headers), r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read().decode()


def test_request_id_echoed_on_every_response():
    """ISSUE 14 satellite: EVERY response — 200, 404, HttpError sheds,
    even /healthz — carries X-Photon-Request-Id (a shed is no longer
    anonymous)."""
    def shed(body):
        raise HttpError(503, headers={"Retry-After": "1"},
                        error="overloaded")

    ep = HttpEndpoint({("GET", "/ok"):
                       (lambda b: (200, "ok", "text/plain")),
                       ("GET", "/shed"): shed})
    ep.start()
    try:
        for path, want_code in (("/ok", 200), ("/shed", 503),
                                ("/nope", 404), ("/healthz", 200)):
            code, headers, _ = _raw_get(ep.port, path)
            assert code == want_code
            rid = headers.get("X-Photon-Request-Id")
            assert rid, f"no request id on {path}"
            assert headers.get("X-Photon-Trace", "").startswith(rid)
        # The shed keeps its own headers too.
        _, headers, _ = _raw_get(ep.port, "/shed")
        assert headers.get("Retry-After") == "1"
    finally:
        ep.close()


def test_client_trace_context_adopted_and_visible_to_routes():
    """A client-sent X-Photon-Trace is adopted (echoed back, hop
    preserved) and visible to the route via tracing.context()."""
    seen: list = []

    def probe(body):
        ctx = tracing.context()
        seen.append((ctx.trace_id, ctx.hop))
        return 200, "ok", "text/plain"

    ep = HttpEndpoint({("GET", "/probe"): probe})
    ep.start()
    try:
        _, headers, _ = _raw_get(
            ep.port, "/probe",
            headers={"X-Photon-Trace": "feedface01/3"})
        assert headers["X-Photon-Request-Id"] == "feedface01"
        assert headers["X-Photon-Trace"] == "feedface01/3"
        assert seen == [("feedface01", 3)]
        # A bare client request id is adopted as the trace id.
        _, headers, _ = _raw_get(
            ep.port, "/probe",
            headers={"X-Photon-Request-Id": "my-req-1"})
        assert headers["X-Photon-Request-Id"] == "my-req-1"
    finally:
        ep.close()


# ---------------------------------------------------------------------------
# flow-event export
# ---------------------------------------------------------------------------


def _request_rec(trace, role, wall_t, total_ms, stages=None,
                 batch=None, **extra):
    return {"event": "request_trace", "trace": trace, "hop": 0,
            "role": role, "wall_t": wall_t, "total_ms": total_ms,
            "stages_ms": stages or {}, "sampled": "tail",
            **({"batch": batch} if batch is not None else {}), **extra}


def _batch_rec(batch, wall_t, total_ms, stages=None):
    return {"event": "batch_trace", "batch": batch, "wall_t": wall_t,
            "total_ms": total_ms, "bucket": 8, "rows": 4,
            "requests": 2, "stages_ms": stages or {}}


def _processes():
    """Frontend + one replica sharing two trace ids and one batch."""
    frontend = {
        "name": "frontend", "requests": [
            _request_rec("t1", "frontend", 100.000, 80.0,
                         {"route": 1.0, "forward": 70.0}),
            _request_rec("t2", "frontend", 100.010, 60.0,
                         {"route": 0.5, "retry": 20.0,
                          "forward": 30.0},
                         attempts=[{"replica": 0, "ms": 20.0,
                                    "outcome": "connect_fail:OSError"},
                                   {"replica": 1, "ms": 30.0,
                                    "outcome": 200}]),
        ], "batches": []}
    replica = {
        "name": "replica_0", "requests": [
            _request_rec("t1", "replica", 100.002, 70.0,
                         {"admission": 1.0, "queue_wait": 40.0,
                          "serialize": 0.5, "write": 1.0}, batch=7),
            _request_rec("t2", "replica", 100.032, 28.0,
                         {"admission": 0.5, "queue_wait": 5.0,
                          "serialize": 0.4, "write": 0.8}, batch=7),
        ], "batches": [
            _batch_rec(7, 100.045, 12.0,
                       {"assemble": 1.0, "store_lookup": 2.0,
                        "dispatch": 6.0, "d2h": 3.0}),
        ]}
    return [frontend, replica]


def test_flow_export_valid_chrome_json_with_cross_process_joins(
        tmp_path):
    """The exported events are valid Chrome trace JSON; every flow
    start (ph s) has a matching finish (ph f) under the same id, and
    the request flow crosses PROCESS boundaries (frontend pid →
    replica pid)."""
    events = serve_trace_events(_processes())
    doc = json.loads(json.dumps({"traceEvents": events,
                                 "displayTimeUnit": "ms"}))
    assert doc["traceEvents"]
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "M", "s", "f")
        assert "pid" in ev and "tid" in ev
        if ev["ph"] in ("X", "s", "f"):
            assert "ts" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 1
    starts = {e["id"]: e for e in doc["traceEvents"]
              if e["ph"] == "s"}
    finishes = {e["id"]: e for e in doc["traceEvents"]
                if e["ph"] == "f"}
    assert set(starts) == set(finishes)
    # Request flows join ACROSS pids; batch flows join across tids.
    for trace in ("t1", "t2"):
        assert starts[trace]["pid"] != finishes[trace]["pid"]
        assert finishes[f"{trace}:b7"]["tid"] == 2
    # Binding contract: every flow event's ts coincides with a slice
    # that encloses it on the same pid/tid (Perfetto binds s/f events
    # to enclosing slices).
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    for fl in list(starts.values()) + list(finishes.values()):
        assert any(s["pid"] == fl["pid"] and s["tid"] == fl["tid"]
                   and s["ts"] <= fl["ts"] <= s["ts"] + s["dur"]
                   for s in slices)


# ---------------------------------------------------------------------------
# serve-report
# ---------------------------------------------------------------------------


def _write_log(path, records):
    log = RunLogger(str(path))
    for rec in records:
        kind = rec.pop("event")
        log.event(kind, **rec)
    log.close()


def test_serve_report_joins_and_attributes(tmp_path):
    """The cross-process join: 100% of replica tail records match a
    frontend trace; queue_wait dominates t1 (per-request wait), the
    retry cost is surfaced for t2; ok=True, rc 0."""
    procs = _processes()
    _write_log(tmp_path / "frontend.jsonl",
               [dict(r) for r in procs[0]["requests"]])
    _write_log(tmp_path / "replica.jsonl",
               [dict(r) for r in procs[1]["requests"]]
               + [dict(b) for b in procs[1]["batches"]])
    out_path = tmp_path / "flow.json"
    result = run_serve_report(
        [str(tmp_path / "frontend.jsonl"),
         str(tmp_path / "replica.jsonl")],
        trace_out=str(out_path))
    assert result["ok"] is True
    assert result["join_fraction"] == 1.0
    assert result["tail_requests"] == 2
    assert result["retried_requests"] == 1
    assert result["retry_cost_ms"]["total"] == pytest.approx(20.0)
    assert result["stages"]["queue_wait"]["count"] == 2
    assert result["stages"]["retry"]["count"] == 1
    # t1's tail is queue-wait dominated (40ms of an 80ms request).
    t1 = next(r for r in result["slowest"] if r["trace"] == "t1")
    assert t1["dominant"] == "queue_wait"
    # The retried request's attribution includes the retry cost.
    t2 = next(r for r in result["slowest"] if r["trace"] == "t2")
    assert t2["retry_ms"] == pytest.approx(20.0)
    assert json.load(open(out_path))["traceEvents"]


def test_serve_report_fails_when_join_breaks(tmp_path):
    """Replica tail traces with NO frontend match (propagation broke)
    fail the join check: ok False, CLI rc 1."""
    procs = _processes()
    # Frontend logs different trace ids than the replica's.
    fe = [dict(r, trace=f"other-{i}")
          for i, r in enumerate(procs[0]["requests"])]
    _write_log(tmp_path / "frontend.jsonl", fe)
    _write_log(tmp_path / "replica.jsonl",
               [dict(r) for r in procs[1]["requests"]])
    rc = telemetry_main(["serve-report",
                         str(tmp_path / "frontend.jsonl"),
                         str(tmp_path / "replica.jsonl")])
    assert rc == 1
    # And the pure analyzer agrees.
    result = analyze(load_trace_files(
        [str(tmp_path / "frontend.jsonl"),
         str(tmp_path / "replica.jsonl")]))
    assert result["ok"] is False and result["join_fraction"] == 0.0


def test_serve_report_single_log_mode(tmp_path):
    """One server's log (no frontend records): stage table + tail
    attribution still render, the join check is N/A, rc 0."""
    procs = _processes()
    _write_log(tmp_path / "replica.jsonl",
               [dict(r) for r in procs[1]["requests"]]
               + [dict(b) for b in procs[1]["batches"]])
    rc = telemetry_main(["serve-report",
                         str(tmp_path / "replica.jsonl")])
    assert rc == 0
    result = analyze(load_trace_files([str(tmp_path / "replica.jsonl")]))
    assert result["join_fraction"] is None and result["ok"] is True
    assert result["dominant_stage"] == "queue_wait"


def test_serve_report_empty_logs_fail(tmp_path):
    """No trace records at all (tracing off / wrong file) is rc 1 —
    a forensic tool must not report green on nothing."""
    _write_log(tmp_path / "empty.jsonl", [])
    rc = telemetry_main(["serve-report", str(tmp_path / "empty.jsonl")])
    assert rc == 1


# ---------------------------------------------------------------------------
# end to end through the real server
# ---------------------------------------------------------------------------


def _post_rows(port, rows, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/score",
        data=json.dumps({"rows": rows}).encode(),
        headers={"Content-Type": "application/json",
                 **(headers or {})})
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read()), dict(r.headers)


def test_server_traces_real_requests_end_to_end(tmp_path):
    """Real server, threshold 0 (everything tails): request_trace +
    batch_trace land in the run log with every replica stage, the
    /status stages table materializes, serve-report attributes each
    request, and a client-supplied trace id joins its record."""
    from photon_ml_tpu.serving.engine import dataset_rows

    model, dataset = _workload()
    mdir = str(tmp_path / "model")
    model_io.save_game_model(model, TASK, mdir)
    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    cfg = _serve_cfg(mdir, tmp_path, telemetry="metrics",
                     monitor="off", trace_threshold_ms=0.0)
    srv = ModelServer(cfg, run_logger=log).start()
    try:
        reqs = dataset_rows(dataset, 0, 4)
        _out, headers = _post_rows(
            srv.port, reqs,
            headers={"X-Photon-Trace": "cafebabe12345678/1"})
        assert headers["X-Photon-Request-Id"] == "cafebabe12345678"
        for _ in range(3):
            _post_rows(srv.port, reqs)
        st, _ = _post_rows(srv.port, reqs[:1])
        import urllib.request as _ur

        with _ur.urlopen(f"http://127.0.0.1:{srv.port}/status",
                         timeout=10) as r:
            status = json.loads(r.read())["serving"]
        assert status["tracing"]["requests"] == 5
        assert status["tracing"]["sampled_tail"] == 5
        for stage in ("admission", "queue_wait", "assemble",
                      "store_lookup", "dispatch", "d2h", "serialize",
                      "write"):
            assert stage in status["stages"], stage
    finally:
        srv.stop()
        log.close()
    events = read_run_log(log_path)
    traces = [e for e in events if e["event"] == "request_trace"]
    batches = [e for e in events if e["event"] == "batch_trace"]
    assert len(traces) == 5 and batches
    adopted = [t for t in traces if t["trace"] == "cafebabe12345678"]
    assert len(adopted) == 1 and adopted[0]["hop"] == 1
    assert all(t["role"] == "replica" for t in traces)
    assert all("batch" in t for t in traces)   # every request linked
    result = analyze(load_trace_files([log_path]))
    assert result["ok"] and result["tail_requests"] == 5
    assert result["dominant_stage"] is not None


def test_server_zero_compiles_with_tracing_on(tmp_path):
    """The guard pin: a warm traced server compiles NOTHING in steady
    state — tracing must never perturb the jit cache."""
    from photon_ml_tpu.serving.engine import dataset_rows

    model, dataset = _workload()
    mdir = str(tmp_path / "model")
    model_io.save_game_model(model, TASK, mdir)
    cfg = _serve_cfg(mdir, tmp_path, telemetry="off", monitor="off",
                     trace_threshold_ms=0.0)
    srv = ModelServer(cfg).start()
    try:
        reqs = dataset_rows(dataset, 0, 6)
        _post_rows(srv.port, reqs)          # shapes warm
        with count_compiles() as compiles:
            for _ in range(4):
                _post_rows(srv.port, reqs)
        assert compiles.count == 0
        # a request's trace is finished after its response is written:
        # the fifth may still be on its way when the client returns
        deadline = time.monotonic() + 5.0
        while tracing.active().snapshot()["requests"] < 5 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert tracing.active().snapshot()["requests"] >= 5
    finally:
        srv.stop()


def test_server_trace_off_takes_no_timestamps(tmp_path):
    """trace='off' is the pre-ISSUE-14 path: no recorder, no
    request_trace events, no stages block — the A/B baseline."""
    model, dataset = _workload()
    mdir = str(tmp_path / "model")
    model_io.save_game_model(model, TASK, mdir)
    log_path = str(tmp_path / "run_log.jsonl")
    log = RunLogger(log_path)
    cfg = _serve_cfg(mdir, tmp_path, telemetry="metrics",
                     monitor="off", trace="off")
    srv = ModelServer(cfg, run_logger=log).start()
    try:
        from photon_ml_tpu.serving.engine import dataset_rows

        assert tracing.active() is None
        _post_rows(srv.port, dataset_rows(dataset, 0, 4))
        import urllib.request as _ur

        with _ur.urlopen(f"http://127.0.0.1:{srv.port}/status",
                         timeout=10) as r:
            status = json.loads(r.read())["serving"]
        assert "tracing" not in status
        assert "stages" not in status
    finally:
        srv.stop()
        log.close()
    assert not [e for e in read_run_log(log_path)
                if e["event"] == "request_trace"]


def test_shed_response_carries_request_id_and_trace(tmp_path):
    """ISSUE 14 satellite through the real server: a 503 shed (server
    warming) is no longer anonymous — the client can correlate its
    failure by request id."""
    model, _ = _workload()
    mdir = str(tmp_path / "model")
    model_io.save_game_model(model, TASK, mdir)
    srv = ModelServer(_serve_cfg(mdir, tmp_path, telemetry="off",
                                 monitor="off"))
    try:
        # NOT started: /v1/score sheds 503 "warming".
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/score",
            data=json.dumps({"rows": [{}]}).encode(),
            headers={"X-Photon-Request-Id": "shed-corr-1"})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=10)
        assert err.value.code == 503
        assert err.value.headers["X-Photon-Request-Id"] == "shed-corr-1"
    finally:
        srv.stop()
