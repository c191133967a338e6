"""Bench budget contract: the driver's capture must never again be
``rc: 124 / parsed: null`` (round-5 verdict).  These run the REAL
bench.py as a subprocess on a tiny CPU shape, so a bench that outgrows
its budget or breaks its JSON contract fails here — in the fast tier —
instead of in the driver.
"""

import json
import os
import subprocess
import sys

import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")
_TINY = ["--n", "4096", "--d", "2048", "--k", "4"]


def _run_bench(tmp_path, *args, timeout=300):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, _BENCH, "--cache-dir", str(tmp_path / "cache"),
         *args],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    return proc


@pytest.mark.fast
def test_bench_etl_section_budgeted_json(tmp_path):
    """`bench.py --section etl --budget-s 60` on a tiny shape: rc=0 and
    the last stdout line parses as JSON with the ETL record."""
    proc = _run_bench(tmp_path, "--section", "etl",
                      "--budget-s", "60", *_TINY)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, f"no stdout; stderr: {proc.stderr[-2000:]}"
    rec = json.loads(lines[-1])
    assert rec["section"] == "etl"
    assert rec["etl_grr_s"] is not None
    assert "etl_phases" in rec
    assert rec.get("errors") is None
    assert rec["sections_skipped"] == []


def test_bench_cached_section_records_warm_vs_cold(tmp_path):
    """etl + cached in one run: the cached section records the warm
    load, the cold reference, the speedup ratio, and plan parity."""
    proc = _run_bench(tmp_path, "--section", "etl,cached",
                      "--budget-s", "120", *_TINY)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    cached = rec["cached"]
    assert cached["etl_warm_s"] is not None
    assert cached["etl_cold_s"] == rec["etl_grr_s"]
    assert cached["warm_speedup"] is not None
    assert cached["parity_ok"] is True


@pytest.mark.slow   # 10s+ in tests/tier1_durations.json
def test_bench_sweep_section_contract(tmp_path):
    """`--section sweep` keeps the budget/JSON-last-line contract and
    records the batched-vs-sequential λ-sweep measurement: wall times,
    speedup, coefficient parity, and the phase breakdown showing the
    data-pass amortization (passes per grid step: ~L·x → ~2)."""
    proc = _run_bench(tmp_path, "--section", "sweep",
                      "--budget-s", "240", *_TINY)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert rec["section"] == "sweep"
    assert rec.get("errors") is None
    sweep = rec["sweep"]
    assert sweep["lanes"] >= 4
    assert sweep["batched_s"] > 0 and sweep["sequential_s"] > 0
    assert sweep["speedup"] is not None
    assert sweep["parity_max_dw"] < 1e-3
    ph = sweep["phases"]
    # The tentpole invariant: one shared chunk stream feeds all lanes,
    # so the batched grid pays a small constant number of passes per
    # grid step while sequential pays ~L of them.
    assert ph["batched"]["data_passes"] < ph["sequential"]["data_passes"]
    assert ph["batched"]["passes_per_grid_step"] <= 3.0
    assert sweep["pass_amortization"] >= 2.0


@pytest.mark.slow   # 10s+ in tests/tier1_durations.json
def test_bench_stream_section_contract(tmp_path):
    """`--section stream` keeps the budget/JSON-last-line contract and
    records the out-of-core measurement: per-arm wall-clock and peak
    host RSS (each arm in its own subprocess), the LRU window bound,
    gradient parity across arms, and the per-section peak_rss_mb
    trajectory satellite."""
    proc = _run_bench(tmp_path, "--section", "stream",
                      "--budget-s", "240", "--guards", *_TINY)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert rec["section"] == "stream"
    assert rec.get("errors") is None
    s = rec["stream"]
    assert s["host_max_resident"] == 2
    # --guards (ISSUE 6): the timed sweeps ran under the runtime guard
    # harness and the steady state compiled NOTHING (everything was
    # compiled in the warmup; a per-sweep retrace would count here).
    for arm in ("spilled", "resident"):
        assert s[arm]["guards"]["sweep_compiles"] == 0, \
            s[arm]["guards"]
    # ISSUE 7: each arm's record carries the telemetry summary block;
    # the spilled arm streams through the prefetcher, so the overlap
    # derivation is defined and the pinned counters are live.
    for arm in ("spilled", "resident"):
        assert "telemetry" in s[arm], sorted(s[arm])
    # ISSUE 10: monitoring OFF stays the default — no monitor session
    # (no `progress` block in the arm record, no status thread probe)
    # and zero `progress` events counted over the timed sweeps.
    assert s["monitor"] is False
    for arm in ("spilled", "resident"):
        assert "progress" not in s[arm], sorted(s[arm])
        assert "status_ok" not in s[arm], sorted(s[arm])
        assert s[arm]["telemetry"]["progress_events"] == 0
        assert s[arm]["telemetry"]["alerts"] == 0
    tel = s["spilled"]["telemetry"]
    assert tel["sweeps"] == s["sweeps_timed"]
    assert tel["overlap_efficiency"] is not None
    assert 0.0 <= tel["overlap_efficiency"] <= 1.0
    assert tel["consumer_wait_s"] >= 0.0
    assert tel["store_loads"] + tel["store_hits"] > 0
    # Steady-state sweeps under telemetry still compile nothing (the
    # guard budget and the bridge agree) — including across the ISSUE-8
    # device-cost capture, whose AOT relower must not register.
    assert tel["compiles"] == 0, tel
    # ISSUE 8 acceptance: each arm's JSON carries a device_cost block
    # (FLOPs, bytes accessed) for the per-chunk value+gradient program,
    # and names the device it ran on.  A roofline estimate is a device
    # metric: none is made on the CPU backend.
    for arm in ("spilled", "resident"):
        cost = s[arm]["device_cost"]
        assert cost["flops"] > 0
        assert cost["bytes_accessed"] > 0
        assert cost["platform"] == "cpu"
        assert "roofline_est_ms" not in cost
        assert s[arm]["device"]["platform"] == "cpu"
    assert rec["device"] == s["spilled"]["device"]
    # Chunks must dwarf the window (the RSS-bound claim's precondition)
    assert s["n_chunks"] >= 6 * s["host_max_resident"]
    # LRU bound held during the spilled arm's sweeps.
    assert 1 <= s["spilled"]["peak_live_chunks"] <= 2
    assert s["spilled"]["disk_loads"] > 0
    for arm in ("spilled", "resident"):
        assert s[arm]["pass_ms"] > 0
        assert s[arm]["peak_rss_mb"] > 0
    assert s["grad_parity_max"] < 1e-3
    assert s["pass_time_ratio"] is not None
    # Satellite: every section records the RSS high-water trajectory.
    assert rec["peak_rss_mb"]["stream"] > 0


@pytest.mark.fast
def test_bench_stream_arm_monitor_contract(tmp_path):
    """A monitoring-ON stream arm (ISSUE 10): one `--stream-arm
    spilled --monitor --guards` subprocess embeds a `progress` block
    (stage snapshots from the live monitor), proves its ephemeral
    /status endpoint answered from inside the measured process, and
    STILL compiles nothing over the timed sweeps — the monitor never
    touches jax."""
    proc = _run_bench(tmp_path, "--stream-arm", "spilled",
                      "--monitor", "--guards", *_TINY)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert rec["arm"] == "spilled"
    prog = rec["progress"]
    # The chunk loop reported: the sweep stage has done == total and a
    # rolling rate, and at least one snapshot event was emitted.
    assert prog["snapshots"] >= 1
    sweep = prog["stages"]["train.sweep"]
    assert sweep["done"] == sweep["total"] > 0
    assert sweep["unit"] == "chunks"
    # The status endpoint answered a live GET /status with stages.
    assert rec["status_ok"] is True
    # Monitoring must not break the steady-state compile contract.
    assert rec["guards"]["sweep_compiles"] == 0, rec["guards"]
    # The registry counted exactly the emitted snapshots.
    assert rec["telemetry"]["progress_events"] == prog["snapshots"]


@pytest.mark.slow   # 10s+ in tests/tier1_durations.json
def test_bench_score_section_contract(tmp_path):
    """`--section score` keeps the budget/JSON-last-line contract and
    records the streaming-fused-scoring measurement (ISSUE 4): per-arm
    rows/s and peak host RSS (each arm in its own subprocess),
    streamed-vs-resident margin parity, and the pass-time ratio."""
    proc = _run_bench(tmp_path, "--section", "score",
                      "--budget-s", "240", *_TINY)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert rec["section"] == "score"
    assert rec.get("errors") is None
    s = rec["score"]
    # Chunks must dwarf the streamed arm's host window (the bounded-RSS
    # claim's precondition).
    assert s["n_chunks"] >= 6 * s["host_max_resident"]
    for arm in ("streamed", "resident"):
        assert s[arm]["pass_ms"] > 0
        assert s[arm]["rows_per_sec"] > 0
        assert s[arm]["peak_rss_mb"] > 0
    assert s["streamed"]["chunk_rows"] * s["n_chunks"] >= 4096
    # LRU window bound held during the streamed arm's timed passes.
    assert 1 <= s["streamed"]["peak_live_chunks"] <= 2
    assert s["margin_parity_max"] < 1e-4
    assert s["pass_time_ratio"] is not None
    # Satellite discipline from round 8: every section records the RSS
    # high-water trajectory.
    assert rec["peak_rss_mb"]["score"] > 0


@pytest.mark.slow   # 10s+ in tests/tier1_durations.json
def test_bench_re_section_contract(tmp_path):
    """`--section re` keeps the budget/JSON-last-line contract and
    records the out-of-core random-effect measurement (ISSUE 5):
    per-arm sweep times, rows/s and peak RSS (subprocess isolation),
    the LRU window bound, streamed-vs-resident coefficient/score
    parity, and the converged-entity retirement work-reduction curve
    (per-sweep solved entities monotone non-increasing, with real
    reduction by the last sweep on the converging schedule)."""
    proc = _run_bench(tmp_path, "--section", "re",
                      "--budget-s", "240", *_TINY)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert rec["section"] == "re"
    assert rec.get("errors") is None
    r = rec["re"]
    # Entity chunks must dwarf the streamed arm's host window.
    assert r["n_chunks"] >= 4 * r["host_max_resident"]
    assert 1 <= r["streamed"]["peak_live_chunks"] <= r["host_max_resident"]
    assert r["streamed"]["disk_loads"] > 0
    for arm in ("streamed", "resident"):
        assert r[arm]["sweep_s"] > 0
        assert r[arm]["rows_per_sec"] > 0
        assert r[arm]["peak_rss_mb"] > 0
        assert len(r[arm]["sweep_s_all"]) == r["sweeps"]
    # Retirement work-reduction: monotone non-increasing solved counts,
    # strictly fewer by the end (entities froze), none retired at the
    # resident arm (no retirement support there).
    solved = r["streamed"]["entities_solved_per_sweep"]
    assert all(a >= b for a, b in zip(solved, solved[1:]))
    assert solved[-1] < solved[0]
    retired = r["streamed"]["entities_retired_per_sweep"]
    assert all(a <= b for a, b in zip(retired, retired[1:]))
    assert retired[-1] > 0
    assert r["retirement_work_fraction"] < 1.0
    # ISSUE 7: the streamed arm's telemetry block reports the prefetch
    # overlap story for the entity-chunk pipeline.
    tel = r["streamed"]["telemetry"]
    assert tel["sweeps"] == r["sweeps"] - 1      # sweep 0 untelemetered
    assert tel["overlap_efficiency"] is not None
    assert "telemetry" in r["resident"]
    # Retirement must not move the model beyond solver tolerance.
    assert r["coef_parity_max"] < 1e-2
    assert r["score_parity_max"] < 1e-2
    assert r["sweep_time_ratio"] is not None
    assert rec["peak_rss_mb"]["re"] > 0


@pytest.mark.slow   # two subprocess estimator fits per arm
def test_bench_cd_fused_section_contract(tmp_path):
    """`--section cd_fused` keeps the budget/JSON-last-line contract
    and records the fused-vs-per-coordinate measurement (ISSUE 11):
    per-arm pass counts and pass times (subprocess isolation for
    per-arm peak RSS), the fused arm's passes/cycle ≈ 1 against the
    legacy arm's ~C × solver-iterations, zero compiles in the measured
    (post-warmup) fits, and cross-arm coefficient parity within the
    documented tolerance."""
    proc = _run_bench(tmp_path, "--section", "cd_fused",
                      "--budget-s", "280", *_TINY)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert rec["section"] == "cd_fused"
    assert rec.get("errors") is None
    s = rec["cd_fused"]
    for arm in ("fused", "percoord"):
        a = s[arm]
        assert a["fit_s"] > 0
        assert a["cycles"] > 0 and a["data_passes"] > 0
        assert a["peak_rss_mb"] > 0
        # Zero new compiles across the measured sweeps: the warm-up
        # fit paid every compile (guard-pinned acceptance criterion).
        assert a["telemetry"]["compiles"] == 0, a["telemetry"]
    # THE claim: one pass per cycle (+ the final score pass) fused,
    # C × solver-iterations per cycle legacy.
    assert s["passes_per_cycle_fused"] <= 1.2
    assert s["passes_per_cycle_percoord"] >= 4.0
    assert s["pass_count_ratio"] >= 3.0
    assert s["pass_time_ratio"] is not None
    assert s["coef_parity_max"] < 5e-2
    assert rec["peak_rss_mb"]["cd_fused"] > 0


@pytest.mark.fast
def test_history_spec_watches_cd_fused():
    """The 'gate watches it from round 16 on' satellite: the history
    metric spec carries the cd_fused section's passes/cycle, pass-time
    ratio, and fused throughput."""
    from photon_ml_tpu.telemetry.history import METRICS

    keys = {(s, p) for s, p, _ in METRICS}
    assert ("cd_fused", "cd_fused.passes_per_cycle_fused") in keys
    assert ("cd_fused", "cd_fused.pass_time_ratio") in keys
    assert ("cd_fused", "cd_fused.fused.rows_per_sec") in keys
    directions = {f"{s}:{p}": d for s, p, d in METRICS}
    assert directions["cd_fused:cd_fused.passes_per_cycle_fused"] == "lower"
    assert directions["cd_fused:cd_fused.fused.rows_per_sec"] == "higher"


@pytest.mark.fast
def test_history_spec_watches_serve():
    """ISSUE 12 satellite: the history metric spec carries the serve
    section's p99 latency, sustained rows/s, and batch fill."""
    from photon_ml_tpu.telemetry.history import METRICS

    keys = {(s, p) for s, p, _ in METRICS}
    assert ("serve", "serve.p99_ms") in keys
    assert ("serve", "serve.rows_per_sec") in keys
    assert ("serve", "serve.batch_fill") in keys
    directions = {f"{s}:{p}": d for s, p, d in METRICS}
    assert directions["serve:serve.p99_ms"] == "lower"
    assert directions["serve:serve.rows_per_sec"] == "higher"
    assert directions["serve:serve.batch_fill"] == "higher"


@pytest.mark.fast
def test_history_spec_watches_serve_fleet():
    """ISSUE 13 satellite: the history spec gates the fleet arm's
    claims — failed client requests (the retry-once contract says 0)
    and the killed replica's detect→ready restart latency."""
    from photon_ml_tpu.telemetry.history import METRICS

    keys = {(s, p) for s, p, _ in METRICS}
    assert ("serve", "serve.failed_requests") in keys
    assert ("serve", "serve.restart_s") in keys
    directions = {f"{s}:{p}": d for s, p, d in METRICS}
    assert directions["serve:serve.failed_requests"] == "lower"
    assert directions["serve:serve.restart_s"] == "lower"


@pytest.mark.fast
def test_history_spec_watches_serve_stage_medians():
    """ISSUE 14 satellite: the history spec gates the request-tracing
    stage medians — queue wait creeping up (batcher becoming the
    bottleneck) and dispatch creeping up (device path regressing) are
    history-gated like everything else."""
    from photon_ml_tpu.telemetry.history import METRICS, detect

    keys = {(s, p) for s, p, _ in METRICS}
    assert ("serve", "serve.queue_wait_ms") in keys
    assert ("serve", "serve.dispatch_ms") in keys
    directions = {f"{s}:{p}": d for s, p, d in METRICS}
    assert directions["serve:serve.queue_wait_ms"] == "lower"
    assert directions["serve:serve.dispatch_ms"] == "lower"
    # Contract: an injected 2x queue-wait regression gates (rc-1
    # shape) while a flat trajectory stays clean.
    rounds = [
        {"name": f"r{i}", "rc": 0,
         "record": {"serve": {"queue_wait_ms": 2.0,
                              "dispatch_ms": 3.0}}}
        for i in range(3)
    ]
    assert detect(rounds)["ok"] is True
    rounds.append({"name": "r3", "rc": 0,
                   "record": {"serve": {"queue_wait_ms": 4.0,
                                        "dispatch_ms": 3.0}}})
    result = detect(rounds)
    assert result["ok"] is False
    assert [r["metric"] for r in result["regressions"]] == \
        ["serve:serve.queue_wait_ms"]


@pytest.mark.slow   # server subprocess + client storm
def test_bench_serve_section_contract(tmp_path):
    """`--section serve` keeps the budget/JSON-last-line contract and
    records the serving measurement: client-observed p50/p99 latency
    and rows/s under concurrent open-loop clients, micro-batch fill,
    margin parity vs the batch scorer, the server's own peak RSS, and
    the server subprocess's clean rc."""
    proc = _run_bench(tmp_path, "--section", "serve",
                      "--budget-s", "480", *_TINY, timeout=640)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert rec["section"] == "serve"
    assert rec.get("errors") is None
    s = rec["serve"]
    assert s["clients"] == 4
    assert s["requests"] > 0
    assert s["p50_ms"] > 0 and s["p99_ms"] >= s["p50_ms"]
    assert s["rows_per_sec"] > 0
    assert 0 < s["batch_fill"] <= 1.0
    # Served margins match the batch scorer on identical rows
    # (documented tolerance — same f32 fused program).
    assert s["margin_parity_max"] <= 1e-5
    assert s["server_peak_rss_mb"] > 0
    assert s["server_rc"] == 0
    assert rec["peak_rss_mb"]["serve"] > 0
    # Request tracing (ISSUE 14): stage medians recorded for the
    # history gate, and the paired tracing off/on A/B measured.
    assert s["queue_wait_ms"] is not None and s["queue_wait_ms"] > 0
    assert s["dispatch_ms"] is not None and s["dispatch_ms"] > 0
    ov = s["trace_overhead"]
    assert ov["p50_off_ms"] > 0 and ov["p50_on_ms"] > 0
    assert ov["overhead_frac"] is not None
    # Fleet arm (ISSUE 13): 2 replicas, one SIGKILLed mid-storm —
    # zero failed client requests, the restart latency measured, the
    # shed fraction reported, and a clean frontend exit.
    if "skipped" in s.get("fleet", {}):
        pytest.fail(f"fleet arm skipped: {s['fleet']['skipped']}")
    assert s["failed_requests"] == 0
    assert s["restart_s"] is not None and s["restart_s"] > 0
    assert 0.0 <= s["shed_fraction"] < 1.0
    f = s["fleet"]
    assert f["replicas"] == 2
    assert f["requests"] > 0
    assert f["restarts"] >= 1
    assert f["frontend_rc"] == 0
    # Cross-process trace join (ISSUE 14 acceptance): the frontend's
    # and replicas' trace logs join by trace id at >= 99%, and the
    # SIGKILL guarantees retried requests exercised the retry column.
    tj = s["trace_join"]
    assert tj is not None and "error" not in tj, tj
    assert tj["ok"] is True
    assert tj["join_fraction"] is None or tj["join_fraction"] >= 0.99
    assert tj["retried_requests"] >= 1
    assert tj["dominant_stage"] is not None


def test_bench_history_dir_appends_envelope(tmp_path):
    """`--history-dir` appends the run's JSON record as a
    schema-versioned envelope file that `telemetry history` ingests
    (ISSUE 8 satellite)."""
    hist = tmp_path / "hist"
    proc = _run_bench(tmp_path, "--section", "etl", "--budget-s", "60",
                      "--history-dir", str(hist), *_TINY)
    assert proc.returncode == 0, proc.stderr[-3000:]
    files = sorted(os.listdir(hist))
    assert len(files) == 1 and files[0].endswith(".json")
    with open(hist / files[0]) as f:
        env = json.load(f)
    assert env["schema"] == 1
    assert env["kind"] == "bench_record"
    assert env["rc"] == 0
    assert env["record"]["etl_grr_s"] is not None
    # The gate ingests it cleanly.
    proc = subprocess.run(
        [sys.executable, "-m", "photon_ml_tpu.telemetry", "history",
         str(hist)], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout[-2000:]
    tail = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert tail["ok"] is True and tail["rounds"] == files


def test_bench_history_trajectory_gate(tmp_path):
    """The CI gating contract (ISSUE 8 satellite): `telemetry history`
    over a synthetic two-round trajectory exits rc 0 clean and rc 1
    with an injected 20% rows/s regression, naming the section/metric."""
    hist = tmp_path / "hist"
    hist.mkdir()

    def write_round(name, rows_per_sec):
        with open(hist / name, "w") as f:
            json.dump({"schema": 1, "kind": "bench_record", "rc": 0,
                       "record": {"stream": {
                           "spilled": {"examples_per_sec": rows_per_sec},
                           "pass_time_ratio": 1.02}}}, f)

    def gate():
        proc = subprocess.run(
            [sys.executable, "-m", "photon_ml_tpu.telemetry", "history",
             str(hist)], capture_output=True, text=True, timeout=120)
        tail = json.loads(
            [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
        return proc.returncode, tail

    write_round("r01.json", 1_000_000.0)
    write_round("r02.json", 1_020_000.0)
    rc, tail = gate()
    assert rc == 0 and tail["ok"] is True and tail["regressions"] == []

    write_round("r03.json", 800_000.0)       # injected 20% regression
    rc, tail = gate()
    assert rc == 1 and tail["ok"] is False
    assert tail["regressions"][0]["metric"] == (
        "stream:stream.spilled.examples_per_sec")
    assert tail["regressions"][0]["round"] == "r03.json"


def test_bench_zero_budget_still_emits_json(tmp_path):
    """A hopeless budget skips every section but the process still
    exits 0 with one parseable JSON line recording the skips."""
    proc = _run_bench(tmp_path, "--budget-s", "0", *_TINY)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert set(rec["sections_skipped"]) == {
        "etl", "cached", "grr", "segment_sum", "colmajor"}
    assert rec["value"] is None


@pytest.mark.fast
def test_history_spec_watches_mesh_stream():
    """ISSUE 16 satellite: the history spec gates the multi-host
    section's claims — fleet throughput, the barrier-wait tax, the
    per-host peak-RSS bound, and the replicated odometer's
    passes/cycle."""
    from photon_ml_tpu.telemetry.history import METRICS

    keys = {(s, p) for s, p, _ in METRICS}
    assert ("mesh_stream", "mesh_stream.rows_per_sec") in keys
    assert ("mesh_stream",
            "mesh_stream.barrier_wait_fraction") in keys
    assert ("mesh_stream",
            "mesh_stream.max_host_peak_rss_mb") in keys
    assert ("mesh_stream", "mesh_stream.passes_per_cycle") in keys
    directions = {f"{s}:{p}": d for s, p, d in METRICS}
    assert directions["mesh_stream:mesh_stream.rows_per_sec"] == \
        "higher"
    assert directions[
        "mesh_stream:mesh_stream.barrier_wait_fraction"] == "lower"
    assert directions[
        "mesh_stream:mesh_stream.max_host_peak_rss_mb"] == "lower"
    assert directions["mesh_stream:mesh_stream.passes_per_cycle"] == \
        "lower"


def test_bench_mesh_arm_solo_smoke(tmp_path):
    """The fast mesh smoke: ONE ``--mesh-arm`` worker with no fleet
    environment is a single-host control run — rc 0, one JSON line
    with the arm record (no fleet counters, a live odometer), and the
    per-host ``run_log.jsonl`` the fleet-report join would consume."""
    proc = _run_bench(tmp_path, "--mesh-arm", "solo", *_TINY)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert rec["host"] == 0
    assert rec["transport"] is None
    assert rec["reduces"] == 0 and rec["chunks_streamed"] == 0
    assert rec["cycles"] > 0 and rec["data_passes"] > 0
    assert rec["passes_per_cycle"] is not None
    assert rec["peak_rss_mb"] > 0
    assert os.path.exists(rec["run_log"])
    # Solo run → NOT host-sharded: the log sits at the mesh base dir.
    assert os.path.dirname(rec["run_log"]).endswith("mesh_stream")


@pytest.mark.slow   # MESH_HOSTS concurrent subprocess estimator fits
def test_bench_mesh_stream_section_contract(tmp_path):
    """`--section mesh_stream` keeps the budget/JSON-last-line
    contract and records the multi-host measurement (ISSUE 16): all
    hosts report one reduce count (barrier agreement), the replicated
    odometer agrees with passes/cycle ≈ 1, coefficients are bitwise
    identical across hosts, and the fleet-report join passes."""
    proc = _run_bench(tmp_path, "--section", "mesh_stream",
                      "--budget-s", "400", *_TINY, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert rec["section"] == "mesh_stream"
    assert rec.get("errors") is None, rec["errors"]
    s = rec["mesh_stream"]
    assert s["transport"] in ("psum", "tcp")
    assert len(s["per_host"]) == s["hosts"] == 3
    assert s["barrier_agreement"] is True
    assert s["odometer_agreement"] is True
    assert s["coef_identical_across_hosts"] is True
    assert s["fleet_report_ok"] is True
    assert s["reduces_per_host"] > 0
    assert s["total_chunks_streamed"] > 0
    assert s["rows_per_sec"] > 0
    assert s["max_host_peak_rss_mb"] > 0
    assert s["passes_per_cycle"] <= 1.5    # fused: ~1 (+ score pass)
    for host in s["per_host"]:
        assert host["reduces"] == s["reduces_per_host"]
        assert host["barrier_wait_s"] >= 0


@pytest.mark.fast
def test_history_spec_watches_tron():
    """ISSUE 17 satellite: the history metric spec carries the tron
    section's passes-to-tolerance, streamed throughput, and peak RSS,
    so the pass advantage is gated from this round on."""
    from photon_ml_tpu.telemetry.history import METRICS

    keys = {(s, p) for s, p, _ in METRICS}
    assert ("tron", "tron.passes_to_tol") in keys
    assert ("tron", "tron.rows_per_sec") in keys
    assert ("tron", "tron.peak_rss_mb") in keys
    directions = {f"{s}:{p}": d for s, p, d in METRICS}
    assert directions["tron:tron.passes_to_tol"] == "lower"
    assert directions["tron:tron.rows_per_sec"] == "higher"
    assert directions["tron:tron.peak_rss_mb"] == "lower"


def test_bench_tron_arm_smoke(tmp_path):
    """The fast tron smoke: ONE ``--tron-arm tron`` subprocess on the
    tiny shape — rc 0, one JSON line whose odometer fields close the
    identity (passes == 1 initial vg + hvp passes + trial evals + the
    preconditioner diagonal) and whose throughput/RSS fields are
    live."""
    proc = _run_bench(tmp_path, "--tron-arm", "tron", *_TINY)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert rec["arm"] == "tron"
    assert rec["converged"] is True
    assert rec["iterations"] >= 1
    assert rec["passes_to_tol"] == (1 + rec["hvp_passes"]
                                    + rec["ls_trials"]
                                    + rec["aux_passes"])
    assert rec["hvp_passes"] >= 1
    assert rec["aux_passes"] == 1
    assert rec["rows_per_sec"] > 0
    assert rec["solve_peak_rss_mb"] > 0
    assert rec["telemetry"]["sweeps"] == rec["passes_to_tol"]


@pytest.mark.slow   # two subprocess solve-to-tolerance arms
def test_bench_tron_section_contract(tmp_path):
    """`--section tron` keeps the budget/JSON-last-line contract and
    records the second-order measurement (ISSUE 17): both arms
    converge to the shared tolerance, the TRON arm reaches it in
    FEWER data passes (the pass advantage the section exists to
    claim), per-arm RSS is subprocess-isolated, the measured solves
    compile nothing (--guards), and the arms agree on the
    coefficients.  Runs a step above _TINY: at 4096x2048 the logistic
    fit is easy enough that first-order passes tie second-order ones —
    the pass-advantage claim needs the conditioning to actually
    bite."""
    proc = _run_bench(tmp_path, "--section", "tron", "--budget-s",
                      "240", "--guards",
                      "--n", "60000", "--d", "4000", "--k", "8")
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    assert rec["section"] == "tron"
    assert rec.get("errors") is None, rec.get("errors")
    s = rec["tron"]
    for arm in ("tron", "lbfgs"):
        assert s[arm]["converged"] is True
        assert s[arm]["passes_to_tol"] > 0
        assert s[arm]["solve_peak_rss_mb"] > 0
        assert s[arm]["guards"]["solve_compiles"] == 0, s[arm]["guards"]
        assert "telemetry" in s[arm]
    # The gated numbers ride the section record at the METRICS paths.
    assert s["passes_to_tol"] == s["tron"]["passes_to_tol"]
    assert s["rows_per_sec"] == s["tron"]["rows_per_sec"]
    assert s["peak_rss_mb"] == s["tron"]["solve_peak_rss_mb"]
    # The claim: strictly fewer data passes to the same tolerance.
    assert s["pass_advantage"] is not None
    assert s["pass_advantage"] > 1.0, s
    assert s["coef_parity_max"] < 0.5
    assert rec["peak_rss_mb"]["tron"] > 0


@pytest.mark.fast
def test_bench_failed_section_exits_nonzero(tmp_path, monkeypatch, capsys):
    """A section that raises is recorded in ``errors``, the record is
    still the last stdout line, and the process exits 1 (it used to
    exit 0 with the error buried in the record)."""
    import bench

    def boom(ctx):
        raise RuntimeError("section fell over")

    monkeypatch.setitem(bench.SECTION_FNS, "etl", boom)
    rc = bench.main(["--section", "etl", "--budget-s", "60",
                     "--cache-dir", str(tmp_path / "cache"), *_TINY])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1
    assert rec["errors"] == {"etl": "RuntimeError: section fell over"}
    assert rec["device"]["platform"] == "cpu"


@pytest.mark.fast
def test_bench_refuses_to_mix_spawning_and_in_process_sections(tmp_path):
    """Arm sections run in children that need the device; a parent that
    also computed in-process sections would hold the chip, so the mix
    is refused before anything runs."""
    proc = _run_bench(tmp_path, "--section", "etl,tron", *_TINY)
    assert proc.returncode == 2
    assert "cannot share a run" in proc.stderr
    assert proc.stdout == ""
