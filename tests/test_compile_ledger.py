"""The compile path's ledger (ISSUE 39): ``jax.monitoring``'s trace,
lowering, cache and compile events charged to the stage and the fit
that paid them.  A tiny config-5 fit at shapes of this file's own, so
that its programs are new to the process whichever files the worker ran
before; the cache's events, which a test process with the persistent
cache off never fires, are recorded by hand.  Nothing timed here is a
performance number."""

import json
import logging
import os
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import manifest as manifests  # noqa: E402
from photon_ml_tpu import telemetry  # noqa: E402
from photon_ml_tpu.utils.run_log import read_run_log  # noqa: E402

SECONDS = ("trace_s", "lower_s", "cache_load_s", "compile_s", "saved_s")
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE = "/jax/compilation_cache/"


def _gained(before, after):
    """Rows of ``after`` less those of ``before``, where anything is
    left."""
    out = {}
    for key, row in after.items():
        left = {c: row[c] - before.get(key, {}).get(c, 0) for c in row}
        if any(left.values()):
            out[key] = left
    return out


def _fresh(tag):
    """A jitted function no one has traced: its name is the test's."""
    def body(x):
        return jnp.tanh(x) * 3.0 + 1.0
    body.__name__ = body.__qualname__ = tag
    return jax.jit(body)


@pytest.fixture(scope="module")
def two_fits():
    """(rows gained by a first fit, rows gained by a second of the same
    shapes), of the benchmark's configuration at a rehearsal size no
    other file uses."""
    from photon_ml_tpu.config import training_config_from_json
    from photon_ml_tpu.estimators.game_estimator import GameEstimator

    cell = manifests.resolve(manifests.load_manifest(), "game5-kdd.fit-cold")
    config = cell["config"]
    generator = manifests.load_module(cell["generator_path"])
    train, valid, _truth = generator.make(
        11, **dict(config["generator"]["params"], n=5000, d=2500,
                   nnz_per_row=5, n_users=50, n_items=50))
    training = training_config_from_json(json.dumps(dict(
        config["training_config"], sparse_layout="GRR",
        plan_cache_dir=None)))
    gained = []
    for _ in range(2):
        before = telemetry.compile_ledger()
        GameEstimator(training).fit(train, valid)
        gained.append(_gained(before, telemetry.compile_ledger()))
    return gained + [lambda: GameEstimator(training).fit(train, valid)]


def test_first_fit_is_charged_under_its_number_and_its_stages(two_fits):
    first, _second, _fit_again = two_fits
    fits = {fit for fit, _stage in first}
    assert len(fits) == 1 and min(fits) >= 1
    assert {stage for _fit, stage in first} <= set(telemetry.STAGES)
    assert sum(row["programs"] for row in first.values()) > 0
    # the solves are the program's own programs, and they were traced
    assert first[(min(fits), "coord_train")]["trace_s"] > 0
    for row in first.values():
        assert set(row) == set(telemetry.COMPILE_COUNTERS)
        assert all(amount >= 0 for amount in row.values())


def test_second_fit_of_the_same_shapes_adds_no_row(two_fits):
    assert two_fits[1] == {}


def test_readers_on_a_real_trace_of_a_third_fit(two_fits, tmp_path):
    """The benchmark's six readers as ``run.py`` calls them, on a
    profiler trace of one more fit of the same shapes: everything the
    process compiled lies before the traced fit, nothing in it."""
    import glob

    from benchmark.harness import compile_path, host_spans

    first, _second, fit_again = two_fits
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("fit"):
            fit_again()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host_spans.read_host_lines.cache_clear()
    (interval,) = [(start, start + duration)
                   for line in host_spans.read_host_lines(path)
                   for start, duration, name, _stats in line if name == "fit"]
    ctx = {"trace": {"interval": interval}}
    cell = manifests.resolve(manifests.load_manifest(), "game5-kdd.fit-cold")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(host_spans, "TRACE_DIR", str(tmp_path))
        traced_fit = compile_path.traced_fit(ctx)
        read = {name: manifests.load_module(path).read(ctx)
                for name, path in cell["layer_metric_paths"].items()
                if name.startswith("setup_") or name == "retrace_s.window"}
    host_spans.read_host_lines.cache_clear()
    assert len(read) == 6 and read["retrace_s.window"] == 0.0
    ledger = telemetry.compile_ledger()
    (first_fit,) = {fit for fit, _stage in first}
    assert traced_fit == first_fit + 2
    assert not any(fit == traced_fit for fit, _stage in ledger)
    for name, column in (("setup_programs", "programs"),
                         ("setup_trace_s", "trace_s"),
                         ("setup_lower_s", "lower_s"),
                         ("setup_cache_load_s", "cache_load_s"),
                         ("setup_compile_s", "compile_s")):
        assert read[name] == pytest.approx(
            sum(row[column] for (fit, _stage), row in ledger.items()
                if fit < traced_fit))
    assert read["setup_programs"] >= sum(
        row["programs"] for row in first.values()) > 0


def test_stage_rows_and_program_rows_sum_to_the_same_totals(two_fits):
    by_stage = telemetry.compile_ledger()
    by_program = telemetry.compile_programs()
    for column in telemetry.COMPILE_COUNTERS:
        assert sum(r[column] for r in by_stage.values()) == pytest.approx(
            sum(r[column] for r in by_program.values())), column
    assert all(name.startswith("jit(") for name in by_program)


def test_a_trace_inside_a_trace_is_counted_once_in_the_outer():
    inner = [_fresh(f"ledger_inner_{i}") for i in range(3)]

    @jax.jit
    def ledger_outer(x):
        return inner[0](x) + inner[1](x) * inner[2](x)

    heard = []

    def naive(event, seconds, fun_name="", **_kw):
        if event.endswith("jaxpr_trace_duration"):
            heard.append((fun_name, seconds))

    x = jnp.ones(7)
    jax.monitoring.register_event_duration_secs_listener(naive)
    try:
        with telemetry.stage("to_ell") as stage:
            ledger_outer(x).block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(naive)
    # the event nests: the three inner functions fire it, and so does
    # every jnp function they call
    names = [name for name, _s in heard]
    assert {"ledger_outer"} | {f"ledger_inner_{i}" for i in range(3)} \
        < set(names)
    programs = telemetry.compile_programs()
    assert not any(f"ledger_inner_{i}" in name
                   for name in programs for i in range(3))
    row = programs["jit(ledger_outer)"]
    assert row["trace_s"] == pytest.approx(dict(heard)["ledger_outer"])
    assert row["trace_s"] < sum(s for _n, s in heard)
    assert row["programs"] == 1 and row["lower_s"] > 0
    # the stage's own totals: what it was charged, within its wall
    assert stage.counts["programs"] == 1
    assert stage.counts["trace_s"] == pytest.approx(row["trace_s"])
    assert stage.counts["trace_s"] + stage.counts["lower_s"] \
        + stage.counts["compile_s"] <= stage.duration_s


def test_a_stage_that_was_charged_nothing_sets_nothing():
    warm = _fresh("ledger_warm")
    warm(jnp.ones(3)).block_until_ready()
    with telemetry.stage("to_ell", rows=3) as stage:
        warm(jnp.ones(3)).block_until_ready()
    assert stage.counts == {"rows": 3}


def test_estimator_fit_sets_its_fits_totals_zeros_included():
    with telemetry.stage("estimator_fit", fit=70001, rows=1) as warm_fit:
        pass
    assert {c: warm_fit.counts[c] for c in
            ("programs", "trace_s", "lower_s", "cache_load_s", "compile_s")
            } == {"programs": 0, "trace_s": 0.0, "lower_s": 0.0,
                  "cache_load_s": 0.0, "compile_s": 0.0}
    x = jnp.ones(5)
    with telemetry.stage("estimator_fit", fit=70002, rows=1) as cold_fit:
        with telemetry.stage("coord_train", coordinate="x") as train:
            _fresh("ledger_in_a_fit")(x).block_until_ready()
    # the fit's totals hold its stages', its own row being empty
    assert cold_fit.counts["programs"] == train.counts["programs"] == 1
    assert cold_fit.counts["trace_s"] == train.counts["trace_s"] > 0
    assert (70002, "estimator_fit") not in telemetry.compile_ledger()
    assert telemetry.compile_totals(70002)["programs"] == 1


def _on_a_thread(work):
    errors = []

    def run():
        try:
            work()
        except Exception as e:   # read below: a thread's raise is silent
            errors.append(e)

    thread = threading.Thread(target=run)
    thread.start()
    thread.join(timeout=120)
    assert not thread.is_alive() and not errors, errors


@pytest.mark.parametrize("where", ["pool_stage", "pool_no_stage",
                                   "after_the_fit", "nested_stage"])
def test_an_event_is_charged_to_the_fit_and_stage_open_where_it_fired(where):
    """On a pool thread during fit k, under that thread's stage or
    under none: fit k.  Outside every stage and fit: (0, "").  Inside a
    stage inside the fit, on the fit's own thread: the innermost."""
    fit = {"pool_stage": 71001, "pool_no_stage": 71002,
           "after_the_fit": 71003, "nested_stage": 71004}[where]
    fresh, x = _fresh(f"ledger_{where}"), jnp.ones(9)

    def compile_it():
        fresh(x).block_until_ready()

    def in_a_pool_stage():
        with telemetry.stage("grr_row_part", parent="grr_plan_build"):
            compile_it()

    before = telemetry.compile_ledger()
    with telemetry.stage("estimator_fit", fit=fit, rows=1):
        if where == "pool_stage":
            _on_a_thread(in_a_pool_stage)
        elif where == "pool_no_stage":
            _on_a_thread(compile_it)
        elif where == "nested_stage":
            with telemetry.stage("cd_coordinate", coordinate="x"):
                with telemetry.stage("coord_train", coordinate="x"):
                    compile_it()
    if where == "after_the_fit":
        compile_it()
    gained = _gained(before, telemetry.compile_ledger())
    key = {"pool_stage": (fit, "grr_row_part"), "pool_no_stage": (fit, ""),
           "after_the_fit": (0, ""), "nested_stage": (fit, "coord_train")
           }[where]
    assert set(gained) == {key}
    assert gained[key]["programs"] == 1 and gained[key]["trace_s"] > 0
    assert getattr(telemetry._OPEN, "stack", []) == []
    assert telemetry._OPEN_FIT == 0


def test_a_stage_that_raises_leaves_no_stage_open():
    with pytest.raises(RuntimeError):
        with telemetry.stage("estimator_fit", fit=72001, rows=1):
            with telemetry.stage("to_ell"):
                raise RuntimeError("boom")
    assert telemetry._OPEN.stack == [] and telemetry._OPEN_FIT == 0


# (events of one backend compile as JAX records them, what the row gains)
CACHE_CASES = {
    # a hit: the backend's clock ran over the retrieval, which is taken
    # out of compile_s
    "hit": ([("event", CACHE + "cache_hits"),
             ("duration", CACHE + "compile_time_saved_sec", 2.0),
             ("duration", CACHE + "cache_retrieval_time_sec", 0.25)],
            0.375,
            {"programs": 1, "cache_hits": 1, "cache_load_s": 0.25,
             "saved_s": 2.0, "compile_s": 0.125}),
    # a compile long enough to be written to the cache
    "miss": ([("event", CACHE + "cache_misses")], 0.75,
             {"programs": 1, "cache_misses": 1, "compile_s": 0.75}),
    # a compile under the persistence floor: neither hit nor miss
    "under_the_floor": ([], 0.0625, {"programs": 1, "compile_s": 0.0625}),
}


@pytest.mark.parametrize("case", sorted(CACHE_CASES))
def test_cache_events_go_to_the_program_whose_compile_they_are_in(case):
    events, backend_s, expected = CACHE_CASES[case]
    program = f"jit(ledger_cache_{case})"
    before = telemetry.compile_ledger()
    with telemetry.stage("score_coordinate", coordinate="x") as stage:
        for kind, name, *amount in events:
            if kind == "event":
                jax.monitoring.record_event(name)
            else:
                jax.monitoring.record_event_duration_secs(name, *amount)
        jax.monitoring.record_event_duration_secs(COMPILE, backend_s,
                                                  fun_name=program)
    whole = dict(telemetry._ZERO_ROW, **expected)
    assert telemetry.compile_programs()[program] == whole
    assert _gained(before, telemetry.compile_ledger()) \
        == {(0, "score_coordinate"): whole}
    assert stage.counts == {
        "coordinate": "x",
        **{c: whole[c] for c in telemetry._STAGE_TOTALS}}
    assert not getattr(telemetry._OPEN, "pending", None)


def test_program_rows_are_capped_and_the_rest_is_other():
    ledger = telemetry._CompileLedger(cap=3)
    for i in range(6):
        ledger.charge(1, "coord_train", f"jit(p{i})",
                      {"programs": 1, "compile_s": 0.5})
    ledger.charge(1, "coord_train", "jit(p1)", {"programs": 1})
    programs = ledger.rows("by_program")
    assert sorted(programs) == ["jit(p0)", "jit(p1)", "jit(p2)", "other"]
    assert programs["other"]["programs"] == 3
    assert programs["other"]["compile_s"] == 1.5
    assert programs["jit(p1)"]["programs"] == 2
    assert sum(r["programs"] for r in programs.values()) == 7 \
        == ledger.rows("by_stage")[(1, "coord_train")]["programs"]
    assert telemetry._LEDGER._cap == 4096


@pytest.mark.parametrize("broken", ["_charge", "_pend"])
def test_a_listener_that_fails_does_not_break_the_compile(
        monkeypatch, broken):
    def fail(*_a, **_kw):
        raise RuntimeError("the ledger is broken")

    monkeypatch.setattr(telemetry, broken, fail)
    before = telemetry.compile_programs()
    out = _fresh(f"ledger_broken{broken}")(jnp.zeros(4))
    jax.monitoring.record_event(CACHE + "cache_hits")
    jax.monitoring.record_event_duration_secs(
        CACHE + "cache_retrieval_time_sec", 0.5)
    assert out.tolist() == [1.0] * 4
    if broken == "_charge":
        assert telemetry.compile_programs() == before


def test_listening_is_registered_once():
    for _ in range(3):
        telemetry.listen_to_compiles()
    from jax._src import monitoring

    assert monitoring.get_event_duration_listeners().count(
        telemetry._on_duration) == 1
    assert monitoring.get_event_listeners().count(telemetry._on_event) == 1
    assert monitoring.get_scalar_listeners().count(telemetry._on_start) == 1


def test_enabling_the_compile_cache_starts_the_listening(monkeypatch,
                                                         tmp_path):
    from photon_ml_tpu.cache import compile_cache

    called = []
    monkeypatch.setattr(telemetry, "listen_to_compiles",
                        lambda: called.append(True))
    saved = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        compile_cache.enable_compilation_cache()
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)
    assert called == [True]


def test_a_session_changes_nothing_of_what_the_process_logs(tmp_path):
    jax_logger = logging.getLogger("jax")
    found = (jax.config.jax_log_compiles, jax_logger.level,
             list(jax_logger.handlers))
    session = telemetry.start("trace", str(tmp_path))
    try:
        assert (jax.config.jax_log_compiles, jax_logger.level,
                list(jax_logger.handlers)) == found
        _fresh("ledger_logged")(jnp.ones(2)).block_until_ready()
    finally:
        session.close()
    assert (jax.config.jax_log_compiles, jax_logger.level,
            list(jax_logger.handlers)) == found


def test_a_session_counts_what_the_ledger_gained_and_reports_it(
        tmp_path, capsys):
    from photon_ml_tpu.telemetry.__main__ import main as telemetry_main

    x = jnp.ones(2)
    _fresh("ledger_before_the_session")(x).block_until_ready()
    session = telemetry.start("trace", str(tmp_path))
    try:
        with telemetry.stage("estimator_fit", fit=73001, rows=1):
            with telemetry.stage("coord_train", coordinate="x"):
                _fresh("ledger_in_the_session")(x).block_until_ready()
        summary = session.summary()
    finally:
        session.close()
    (row,) = summary["compile_path"]
    assert (row["fit"], row["stage"], row["programs"]) \
        == (73001, "coord_train", 1)
    counters = summary["counters"]
    assert counters["jax.compiles"] == 1
    for column in ("trace_s", "lower_s", "compile_s"):
        assert counters[f"jax.{column}"] == pytest.approx(row[column],
                                                         abs=1e-5)
        assert row[column] > 0
    with open(tmp_path / "trace.json") as f:
        (instant,) = [e for e in json.load(f)["traceEvents"]
                      if e["name"] == "xla_compile"]
    assert instant["args"]["program"] == "jit(ledger_in_the_session)"
    assert instant["args"]["stage"] == "coord_train"
    assert instant["args"]["seconds"] == pytest.approx(row["compile_s"],
                                                       abs=1e-5)
    (span,) = [e for e in read_run_log(str(tmp_path / "run_log.jsonl"))
               if e["event"] == "span" and e["name"] == "coord_train"]
    assert span["args"]["programs"] == 1
    telemetry_main(["report", str(tmp_path / "run_log.jsonl")])
    printed = capsys.readouterr().out
    assert printed.count("Compile path by stage:") == 1
    (line,) = [ln for ln in printed.splitlines()
               if ln.split()[:2] == ["73001", "coord_train"]]
    assert line.split()[2] == "1"
    assert json.loads(printed.splitlines()[-1])["compile_path"] == [row]


def test_the_stage_core_knows_no_logging_bridge():
    """The module holds no logging handler for compiles, switches
    ``jax.log_compiles`` nowhere and imports nothing of ``analysis``."""
    with open(telemetry.__file__) as f:
        source = f.read()
    assert "logging.Handler" not in source
    assert "log_compiles(" not in source
    assert "photon_ml_tpu.analysis" not in source
