"""``telemetry.stage``: the coarse boundaries of a fit on the profiler's
clock (ISSUE 26).  A tiny config-5 fit (the benchmark configuration at
its ``rehearsal_params``, GRR layout forced since AUTO picks ELL off the
TPU) is traced once under ``jax.profiler``, fitted once with telemetry
``trace`` and once with everything off; every stage of
``telemetry.STAGES`` is looked for where the resident path runs it.
Nothing timed here is a performance number."""

import glob
import json
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import host_spans  # noqa: E402
from benchmark.harness import manifest as manifests  # noqa: E402
from benchmark.harness import trace_reduce  # noqa: E402
from photon_ml_tpu import telemetry  # noqa: E402
from photon_ml_tpu.utils.run_log import RunLogger, read_run_log  # noqa: E402

# stage -> the stage it is nested directly inside, in a resident
# one-sweep GRR fit that validates every sweep (the benchmark's).  The
# three pool-thread stages name their parent among their counts.
PARENT = {
    "estimator_fit": None,
    "prepare_fixed": "estimator_fit",
    "add_intercept_col": "prepare_fixed",
    "to_ell": "prepare_fixed",
    "grr_plan_build": "prepare_fixed",
    "grr_hot_split": "grr_plan_build",
    "grr_plan_ranges": "grr_plan_build",
    "grr_row_part": "grr_plan_build",
    "grr_mid_split": "grr_plan_build",
    "grr_col_build": "grr_plan_build",
    "place_batch": "prepare_fixed",
    "build_coordinates": "estimator_fit",
    "group_entities": "build_coordinates",
    "place_re": "build_coordinates",
    "cd_initial_scores": "estimator_fit",
    "cd_coordinate": "estimator_fit",
    "coord_train": "cd_coordinate",
    "coord_score": "cd_coordinate",
    "cd_validation": "estimator_fit",
    "validation": "cd_validation",
    "transform": "validation",
    "score_coordinate": "transform",
    "export_model": "estimator_fit",
}
POOL_STAGES = ("grr_row_part", "grr_mid_split", "grr_col_build")
# Only a fit with a plan cache directory runs these two.
CACHE_STAGES = ("plan_cache_load", "plan_cache_save")
# Only a routed call of two blocks or more runs this one, on the thread
# that called and so inside that thread's chain stage (ISSUE 29); the
# tiny fit routes no call that large.
ROUTE_STAGE = "grr_routes"
# Only a build whose input has columns too sparse to plan runs this one,
# on a pool thread (ISSUE 30); every column of the tiny fit pays for its
# slots.
TAIL_STAGE = "grr_tail_build"
# Only a direction whose spill is worth a plan of its own runs this one,
# once for every overflow level it starts, on the thread that builds the
# direction and so inside that thread's chain stage and inside the level
# above (ISSUE 33); the tiny fit spills too little.
LEVEL_STAGE = "grr_overflow_level"
# Only a random effect over a sparse shard runs this one, inside its
# ``group_entities`` (ISSUE 35: tests/test_projected_fit.py has such a
# fit); the tiny fit's random effects are dense.
PROJECT_STAGE = "re_project"
# Every direction's first scan of its entries (the level-1 plan, C++ or
# numpy) runs this one, on the thread that builds the direction and so
# directly inside that thread's chain stage (ISSUE 39).
SCAN_STAGE = "grr_plan_scan"
# Only a fit whose random effects take a prior from a warm-start model
# runs the first, inside ``build_coordinates``; only a random effect
# that exports variances the second, inside ``export_model``
# (tests/test_refresh_prior.py has such fits); the tiny fit does neither.
PRIOR_STAGES = ("re_prior_import", "re_variances")
# What a plan's builder says of its fill: its threads and the seconds of
# its two parts.
FILL_COUNTS = {"workers", "count_s", "fill_s"}
LEVEL_COUNTS = {"depth", "entries", "supertiles", "native",
                "kept"} | FILL_COUNTS
SCAN_COUNTS = {"entries", "supertiles", "native"} | FILL_COUNTS
# What a stage that was charged anything by the compile path's listener
# sets when it closes, and ``estimator_fit`` always (ISSUE 39).
COMPILE_COUNTS = {"programs", "trace_s", "lower_s", "cache_load_s",
                  "compile_s"}
CLASS_COUNTS = {"active_columns", "hot_columns", "planned_columns",
                "planned_nnz", "tail_columns", "tail_nnz"}
# Counts every run of the stage must carry (a stage may carry more).
COUNTS = {
    "estimator_fit": {"fit", "rows"} | COMPILE_COUNTS,
    "prepare_fixed": {"coordinate", "rows", "dim"},
    "to_ell": {"rows", "dim", "k", "padded_rows"},
    "grr_plan_build": {"rows", "k", "dim", "nnz", "directions", "spill",
                       "cache_hit", "tail_len"} | CLASS_COUNTS,
    "grr_hot_split": {"hot_columns", "native", "workers", "entries",
                      "count_s", "classify_s", "split_s"},
    "grr_plan_ranges": {"ranges"},
    "grr_row_part": {"parent", "lo", "hi", "cap", "spill"},
    "grr_mid_split": {"parent", "mid_columns", "spill"},
    "grr_col_build": {"parent", "cap", "spill"},
    "place_batch": {"bytes"},
    "build_coordinates": {"coordinates"},
    "group_entities": {"entity_key", "rows", "entities", "buckets",
                       "padded_slots", "real_rows"},
    "place_re": {"entity_key", "bytes"},
    "cd_initial_scores": {"coordinates"},
    "cd_coordinate": {"coordinate", "iteration"},
    "coord_train": {"coordinate"},
    "coord_score": {"coordinate"},
    "export_model": {"coordinates", "bytes_pulled"},
    "validation": {"rows"},
}


def test_stage_table_is_the_whole_of_stages():
    assert set(PARENT) | set(CACHE_STAGES) \
        | {ROUTE_STAGE, TAIL_STAGE, LEVEL_STAGE, PROJECT_STAGE, SCAN_STAGE} \
        | set(PRIOR_STAGES) == set(telemetry.STAGES)
    assert len(set(telemetry.STAGES)) == len(telemetry.STAGES)


@pytest.fixture(scope="module")
def tiny():
    """(training-config fields, train, valid) of the benchmark's
    configuration at its rehearsal size."""
    cell = manifests.resolve(manifests.load_manifest(), "game5-kdd.fit-cold")
    config = cell["config"]
    generator = manifests.load_module(cell["generator_path"])
    train, valid, _truth = generator.make(
        7, **dict(config["generator"]["params"],
                  **config["rehearsal_params"]))
    fields = dict(config["training_config"], sparse_layout="GRR",
                  plan_cache_dir=None)
    return fields, train, valid


def _fit(tiny, run_logger=None, **fields):
    from photon_ml_tpu.config import training_config_from_json
    from photon_ml_tpu.estimators.game_estimator import GameEstimator

    base, train, valid = tiny
    config = training_config_from_json(json.dumps(dict(base, **fields)))
    return GameEstimator(config).fit(train, valid, run_logger=run_logger)[0]


@pytest.fixture(scope="module")
def traced(tiny, tmp_path_factory):
    """One fit under the profiler as ``benchmark/run.py`` traces it
    (Python tracer off, inside a ``fit`` annotation), after a warm-up
    fit; returns what ``host_spans.stages`` makes of it."""
    import jax

    _fit(tiny)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("fit"):
            _fit(tiny)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    lines = host_spans.read_host_lines(path)
    (main,) = [line for line in lines
               if any(name == "fit" for _s, _d, name, _k in line)]
    (lo, length) = [(s, d) for s, d, name, _k in main if name == "fit"][0]
    found = {"interval": (lo, lo + length), "thread": [], "other": [],
             "counts": {}}
    for line in lines:
        for start, duration, name, stats in line:
            if stats is not None:
                event = (start, duration, name)
                found["thread" if line is main else "other"].append(event)
                found["counts"][event] = stats
    return found


def _events(found, stage):
    name = host_spans.PREFIX + stage
    return [e for e in found["thread"] + found["other"] if e[2] == name]


@pytest.mark.parametrize("stage", sorted(PARENT))
def test_traced_fit_has_the_stage_where_the_table_says(traced, stage):
    events = _events(traced, stage)
    assert events, f"no photon/{stage} in the trace"
    parent = PARENT[stage]
    if stage in POOL_STAGES:
        assert all(e in traced["other"] for e in events)
        (ps, pd, _n), = _events(traced, parent)
        for start, duration, _n in events:
            assert traced["counts"][(start, duration, _n)]["parent"] == parent
            assert ps <= start and start + duration <= ps + pd
    elif parent is None:
        lo, hi = traced["interval"]
        assert all(e in traced["thread"] for e in events)
        assert all(lo <= s and s + d <= hi for s, d, _n in events)
    else:
        children = host_spans.direct_children(
            traced["thread"], host_spans.PREFIX + parent)
        assert all(e in children for e in events)
    for event in events:
        assert COUNTS.get(stage, set()) <= set(traced["counts"][event])


def test_traced_fit_counts_say_what_was_done(traced, tiny):
    _fields, train, valid = tiny
    counts = traced["counts"]
    (fit,) = _events(traced, "estimator_fit")
    assert counts[fit]["rows"] == train.n and counts[fit]["fit"] >= 2
    # the warm-up fit compiled every program: this one none, and no
    # stage of it was charged a trace or a lowering
    assert {c: counts[fit][c] for c in COMPILE_COUNTS} \
        == dict.fromkeys(COMPILE_COUNTS, 0)
    assert not any(COMPILE_COUNTS & set(counts[e])
                   for e in counts if e != fit)
    (build,) = _events(traced, "grr_plan_build")
    assert counts[build]["cache_hit"] == 0 and counts[build]["nnz"] > 0
    assert counts[build]["directions"] == len(
        _events(traced, "grr_row_part")) + len(
        _events(traced, "grr_col_build")) + (
        counts[_events(traced, "grr_mid_split")[0]]["mid_columns"] > 0)
    assert [counts[e]["coordinate"]
            for e in sorted(_events(traced, "cd_coordinate"))] \
        == ["global", "per_user", "per_item"]
    trains = sorted(_events(traced, "coord_train"))
    assert counts[trains[0]]["solver_iterations"] == 30
    # the fixed effect's line search walks the margins: one forward
    # contraction an iteration and one at the start, whatever the trials
    assert counts[trains[0]]["forward_passes"] == 31
    assert not any("forward_passes" in counts[e] for e in trains[1:])
    # a random effect's says what shape its solves had (ISSUE 35)
    assert "buckets" not in counts[trains[0]]
    for e in trains[1:]:
        assert counts[e]["chunks"] == counts[e]["buckets"] >= 2
    assert sorted(counts[e]["entity_key"]
                  for e in _events(traced, "group_entities")) \
        == ["itemId", "userId"]
    for e in _events(traced, "group_entities"):
        assert counts[e]["real_rows"] == train.n
        assert counts[e]["padded_slots"] >= train.n
    (validation,) = _events(traced, "validation")
    assert counts[validation]["rows"] == valid.n
    assert len(_events(traced, "place_batch")) == 2   # the fence, the rest


def test_traced_fit_says_who_ran_the_hot_split(traced):
    """``grr_hot_split``: the ELL slots its two passes scanned, whether
    both ran in the native library and on how many threads, and the
    seconds of its three parts (ISSUE 40).  The tiny fit is under a row
    window, so its mid split counts no column and names no counter
    (``test_mid_split_says_who_counted_where_it_counts``)."""
    from photon_ml_tpu import native

    counts = traced["counts"]
    (build,) = _events(traced, "grr_plan_build")
    (hot,) = _events(traced, "grr_hot_split")
    (mid,) = _events(traced, "grr_mid_split")
    entries = counts[build]["rows"] * counts[build]["k"]
    assert counts[hot]["entries"] == entries >= counts[build]["nnz"]
    library = int(native.native_available())
    assert counts[hot]["native"] == library
    assert counts[hot]["workers"] == (
        native._split_workers(entries) if library else 1)
    parts = [counts[hot][part]
             for part in ("count_s", "classify_s", "split_s")]
    assert all(seconds >= 0 for seconds in parts)
    assert sum(parts) <= hot[1] / 1e9 + 1e-3
    assert counts[build]["rows"] < 16384 and "native" not in counts[mid]


def test_traced_fit_scans_each_direction_inside_its_chain_stage(traced):
    """``grr_plan_scan``: one for every direction built, on a pool
    thread, while a chain stage is open (the run log, which knows the
    threads apart, says inside which:
    ``test_run_log_scan_is_directly_inside_its_chain_stage``)."""
    counts = traced["counts"]
    scans = _events(traced, SCAN_STAGE)
    (build,) = _events(traced, "grr_plan_build")
    assert len(scans) == counts[build]["directions"]
    chains = [e for stage in POOL_STAGES for e in _events(traced, stage)]
    for start, duration, name in scans:
        assert (start, duration, name) in traced["other"]
        assert set(counts[(start, duration, name)]) == SCAN_COUNTS
        assert counts[(start, duration, name)]["entries"] > 0
        assert counts[(start, duration, name)]["supertiles"] > 0
        assert any(s <= start and start + duration <= s + d
                   for s, d, _n in chains)


def test_coord_train_counts_of_one_solve():
    """What ``coord_train`` says of a solve: along the margins the
    trials only where the solver tracked its states; with an L1 term
    trials, those walked along the margins and forward passes from the
    carry, tracked or not; nothing for a list of batched results."""
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import make_dense_batch
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.game.coordinate_descent import _solve_counts
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optim import OptimizationProblem, OptimizerConfig

    rng = np.random.default_rng(3)
    x = rng.normal(size=(80, 4))
    batch = make_dense_batch(x, (rng.uniform(size=80) < 0.5).astype(float))
    w0 = jnp.zeros(4, jnp.float32)

    def solve(reg, **config):
        return OptimizationProblem(
            objective=GLMObjective(loss=losses.LOGISTIC, reg=reg,
                                   norm=NormalizationContext.identity()),
            config=OptimizerConfig(max_iters=6, tolerance=0.0, **config),
        ).run(batch, w0)

    tracked = _solve_counts(solve(RegularizationContext.l2(1.0)))
    assert tracked["solver_iterations"] == 6 \
        and tracked["forward_passes"] == 7 and tracked["ls_trials"] >= 6
    assert _solve_counts(solve(RegularizationContext.l2(1.0),
                               track_states=False)) \
        == {"solver_iterations": 6, "forward_passes": 7}
    whole = _solve_counts(solve(RegularizationContext.l1(0.1)))
    # through the split OWL-QN keeps its last trial's margins: nothing
    # is contracted at the accepted point; a trial the orthant projection
    # clips nothing of walks the margins, after one X·d in its search
    # (the first search, from w = 0, walks every trial)
    walked = whole["walked_trials"]
    xd_searches = whole["forward_passes"] - (1 + whole["ls_trials"] - walked)
    assert whole["solver_iterations"] == 6 and walked >= 1 \
        and 1 <= xd_searches <= min(walked, 6)
    assert _solve_counts(solve(RegularizationContext.l1(0.1),
                               track_states=False)) == whole
    assert _solve_counts([solve(RegularizationContext.l2(1.0))]) == {}
    assert _solve_counts({"entities": 3}) == {}


def test_traced_fit_is_divided_and_little_is_unnamed(traced):
    """The stages directly inside ``estimator_fit`` do not overlap, and
    what no stage names is under a tenth of the fit at this size."""
    lo, hi = traced["interval"]
    children = host_spans.direct_children(traced["thread"],
                                          host_spans.TOP_STAGE)
    covered = trace_reduce.busy_time(children)
    assert sum(d for _s, d, _n in children) == pytest.approx(covered)
    assert (hi - lo) - covered < 0.10 * (hi - lo)


def test_untraced_fit_with_telemetry_off_writes_no_event(tiny, tmp_path):
    assert telemetry.active() is None
    log = RunLogger(str(tmp_path / "log.jsonl"))
    _fit(tiny, run_logger=log)
    log.close()
    assert telemetry.active() is None
    assert telemetry.span("anything") is telemetry._NULL_SPAN
    kinds = {e["event"] for e in read_run_log(str(tmp_path / "log.jsonl"))}
    assert kinds == {"run_header", "cd_coordinate", "cd_validation"}
    assert not glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)


@pytest.fixture(scope="module")
def run_log(tiny, tmp_path_factory):
    """The run log of one fit with ``telemetry="trace"`` and no
    profiler."""
    out = tmp_path_factory.mktemp("telemetry")
    log = RunLogger(str(out / "run_log.jsonl"))
    _fit(tiny, run_logger=log, telemetry="trace", telemetry_dir=str(out))
    log.close()
    assert telemetry.active() is None
    return read_run_log(str(out / "run_log.jsonl"))


@pytest.mark.parametrize("stage", sorted(PARENT))
def test_telemetry_trace_run_log_holds_the_same_stage(run_log, stage):
    spans = [e for e in run_log
             if e["event"] == "span" and e["name"] == stage]
    assert spans, f"no span {stage!r} in the run log"
    for span in spans:
        assert span["cat"] == ("phase" if stage == "estimator_fit"
                               else "stage")
        assert COUNTS.get(stage, set()) <= set(span.get("args", {}))
        if stage in POOL_STAGES:
            assert span["args"]["parent"] == PARENT[stage]
            assert span["thread"] != "MainThread"


def test_run_log_duration_is_the_stage_and_holds_its_children(run_log):
    """``cd_coordinate.duration_s`` is the stage's wall: it ends after
    the train and the score have run on the device, not at the
    enqueue."""
    events = [e for e in run_log if e["event"] == "cd_coordinate"]
    spans = {name: [e for e in run_log
                    if e["event"] == "span" and e["name"] == name]
             for name in ("cd_coordinate", "coord_train", "coord_score")}
    assert len(events) == len(spans["cd_coordinate"]) == 3
    for event, whole, train, score in zip(
            events, spans["cd_coordinate"], spans["coord_train"],
            spans["coord_score"]):
        assert event["duration_s"] == pytest.approx(whole["dur"], abs=5e-3)
        assert whole["dur"] >= train["dur"] + score["dur"]
        assert train["depth"] == score["depth"] == whole["depth"] + 1


def test_run_log_scan_is_directly_inside_its_chain_stage(run_log):
    spans = [e for e in run_log if e["event"] == "span"]
    scans = [e for e in spans if e["name"] == SCAN_STAGE]
    assert scans
    for scan in scans:
        (chain,) = _around(spans, scan, POOL_STAGES)
        assert scan["depth"] == chain["depth"] + 1
        assert scan["cat"] == "stage" and set(scan["args"]) == SCAN_COUNTS


@pytest.mark.parametrize("stage", CACHE_STAGES)
def test_plan_cache_branches_have_their_stage(tmp_path, spans_of, stage):
    """A build that saves its plan and one that loads it, as telemetry
    spans (the same objects the profiler would see)."""
    from photon_ml_tpu.data import grr

    rng = np.random.default_rng(3)
    cols = rng.integers(0, 500, size=(256, 4)).astype(np.int32)
    vals = rng.normal(size=(256, 4)).astype(np.float32)

    def build_twice():
        for _ in range(2):
            grr.build_grr_pair(cols, vals, 500,
                               cache_dir=str(tmp_path / "plans"))

    _, spans = spans_of(build_twice)
    # the first build looks (and finds nothing to load), then saves; the
    # second loads
    found = [e for e in spans if e["name"] == stage]
    assert [e["args"]["bytes"] > 0 for e in found] == {
        "plan_cache_load": [False, True], "plan_cache_save": [True]}[stage]
    assert all(e["depth"] == 1 for e in found)
    builds = [e for e in spans if e["name"] == "grr_plan_build"]
    assert [b["args"]["cache_hit"] for b in builds] == [0, 1]


@pytest.fixture(scope="module")
def routed_build(tmp_path_factory):
    """(spans, supertiles of every routed call) of one plan build whose
    larger calls cross the block threshold and whose smaller do not, as
    telemetry spans."""
    import photon_ml_tpu.native as nat
    from photon_ml_tpu.data import grr

    if not nat.native_available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(3)
    cols = rng.integers(0, 5000, size=(20000, 8)).astype(np.int32)
    vals = rng.normal(size=(20000, 8)).astype(np.float32)
    real, routed = nat.grr_routes_native, []

    def recording(dst, hi):
        routed.append(dst.shape[0])
        return real(dst, hi)

    out = tmp_path_factory.mktemp("routed")
    session = telemetry.start("trace", str(out))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(nat, "grr_routes_native", recording)
        try:
            grr.build_grr_pair(cols, vals, 5000)
        finally:
            session.close()
    spans = [e for e in read_run_log(str(out / "run_log.jsonl"))
             if e["event"] == "span"]
    return spans, routed


def test_mid_split_says_who_counted_where_it_counts(routed_build):
    """A batch of a row window or more: ``grr_mid_split`` counts every
    column again and says the library did (``native`` 1), and
    ``grr_hot_split`` ran its passes over two blocks, on two threads
    where the process has them (ISSUE 40)."""
    from photon_ml_tpu.native import _usable_cores

    spans, _routed = routed_build
    (hot,) = [s["args"] for s in spans if s["name"] == "grr_hot_split"]
    (mid,) = [s["args"] for s in spans if s["name"] == "grr_mid_split"]
    assert mid["native"] == 1
    assert (hot["native"], hot["entries"]) == (1, 20000 * 8)
    assert hot["workers"] == min(2, _usable_cores())


def test_grr_routes_stage_only_for_calls_that_went_to_the_threads(
        routed_build):
    from photon_ml_tpu.native import _ROUTE_BLOCK, _usable_cores

    spans, routed = routed_build
    large = sorted(n for n in routed if n > _ROUTE_BLOCK)
    assert large and len(large) < len(routed)   # both sides of the choice
    found = [e["args"] for e in spans if e["name"] == ROUTE_STAGE]
    assert sorted(a["supertiles"] for a in found) == large
    for args in found:
        assert set(args) == {"supertiles", "blocks", "workers"}
        assert args["blocks"] == -(-args["supertiles"] // _ROUTE_BLOCK)
        assert args["workers"] == min(_usable_cores(), args["blocks"])


def _around(spans, inner, names):
    """The spans named in ``names`` on ``inner``'s thread that hold it."""
    return [e for e in spans if e["name"] in names and e is not inner
            and e["tid"] == inner["tid"] and e["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= e["ts"] + e["dur"]]


def test_grr_routes_stage_nests_inside_its_chain_stage(routed_build):
    """Directly inside the chain's stage, or inside the overflow levels
    between the two where the routed plan is a level's."""
    spans, _routed = routed_build
    for route in (e for e in spans if e["name"] == ROUTE_STAGE):
        (chain,) = _around(spans, route, POOL_STAGES)
        levels = _around(spans, route, (LEVEL_STAGE,))
        assert route["depth"] == chain["depth"] + 1 + len(levels)
        assert route["cat"] == "stage" and "parent" not in route["args"]


@pytest.fixture(scope="module")
def levelled_build(tmp_path_factory):
    """(spans, the pair) of one plan build whose column direction spills
    through two overflow levels or more, as telemetry spans."""
    from photon_ml_tpu.data import grr

    rng = np.random.default_rng(5)
    n, k, dim = 20000, 6, 3000
    cols = (dim * rng.random((n, k)) ** 3.0).astype(np.int32)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    out = tmp_path_factory.mktemp("levelled")
    session = telemetry.start("trace", str(out))
    try:
        pair = grr.build_grr_pair(cols, vals, dim, cap=4,
                                  hot_threshold=10**9, mid_threshold=10**9,
                                  overflow_threshold=500)
    finally:
        session.close()
    spans = [e for e in read_run_log(str(out / "run_log.jsonl"))
             if e["event"] == "span"]
    return spans, pair


def _chain_of(direction):
    out = []
    while direction is not None:
        out.append(direction)
        direction = direction.overflow
    return out


def test_grr_overflow_level_stage_once_a_level_with_its_counts(
        levelled_build):
    import photon_ml_tpu.native as nat

    spans, pair = levelled_build
    found = [e for e in spans if e["name"] == LEVEL_STAGE]
    col_levels = _chain_of(pair.col_dir)[1:]
    assert len(col_levels) >= 2
    (col_build,) = [e for e in spans if e["name"] == "grr_col_build"]
    of_col = sorted((e for e in found if e["tid"] == col_build["tid"]),
                    key=lambda e: e["args"]["depth"])
    assert [e["args"]["depth"] for e in of_col] \
        == list(range(2, 2 + len(of_col)))
    kept = [e for e in of_col if e["args"]["kept"]]
    assert [e["args"]["supertiles"] for e in kept] \
        == [d.n_supertiles for d in col_levels]
    for event in found:
        assert set(event["args"]) == LEVEL_COUNTS
        assert event["args"]["native"] == int(nat.native_available())
        assert event["args"]["entries"] > 500
        assert event["cat"] == "stage"


def test_grr_overflow_level_stage_nests_in_its_chain_and_the_level_above(
        levelled_build):
    spans, _pair = levelled_build
    found = [e for e in spans if e["name"] == LEVEL_STAGE]
    assert any(e["args"]["depth"] > 2 for e in found)
    for event in found:
        (chain,) = _around(spans, event, POOL_STAGES)
        above = _around(spans, event, (LEVEL_STAGE,))
        assert sorted(e["args"]["depth"] for e in above) \
            == list(range(2, event["args"]["depth"]))
        assert event["depth"] == chain["depth"] + 1 + len(above)


def test_grr_tail_build_stage_only_where_the_input_has_a_tail(
        tmp_path, monkeypatch):
    """Twelve dense columns and a few thousand of one entry, under an
    economy bound that one entry does not meet: the tail's sort and
    placement is a pool-thread stage that names its parent, and the
    plan build's counts say what went where."""
    from photon_ml_tpu.data import grr

    rng = np.random.default_rng(4)
    n = 4000
    cols = np.stack([rng.integers(0, 12, n),
                     12 + rng.permutation(5000)[:n]], axis=1).astype(np.int32)
    vals = np.ones((n, 2), np.float32)
    monkeypatch.setattr(grr, "ECONOMY_SLOTS_PER_ENTRY", 2)
    session = telemetry.start("trace", str(tmp_path))
    try:
        pair = grr.build_grr_pair(cols, vals, 6000)
    finally:
        session.close()
    spans = [e for e in read_run_log(str(tmp_path / "run_log.jsonl"))
             if e["event"] == "span"]
    (tail,) = [e for e in spans if e["name"] == TAIL_STAGE]
    assert tail["args"]["parent"] == "grr_plan_build"
    assert tail["args"]["tail_nnz"] == pair.tail.nnz == n
    assert tail["args"]["bytes"] == 6 * 4 * n
    (build,) = [e for e in spans if e["name"] == "grr_plan_build"]
    assert tail["tid"] != build["tid"]
    assert build["ts"] <= tail["ts"] \
        and tail["ts"] + tail["dur"] <= build["ts"] + build["dur"]
    counts = {k: build["args"][k] for k in CLASS_COUNTS}
    assert build["args"]["tail_len"] == pair.tail.row_val.shape[0] == n
    assert counts["tail_columns"] == counts["tail_nnz"] == n
    assert counts["active_columns"] == n + 12
    assert counts["hot_columns"] + counts["planned_columns"] == 12
    assert counts["planned_nnz"] + counts["tail_nnz"] <= build["args"]["nnz"]


# -- the stage object ---------------------------------------------------------

def test_stage_refuses_a_name_outside_stages():
    with pytest.raises(ValueError, match="telemetry.STAGES"):
        telemetry.stage("no_such_stage")


def test_stage_off_keeps_its_duration_and_counts_and_no_session():
    assert telemetry.active() is None
    with telemetry.stage("to_ell", rows=3) as stage:
        stage.set(k=4)
    assert stage.counts == {"rows": 3, "k": 4}
    assert stage.duration_s >= 0
    assert telemetry.active() is None


def test_stage_under_a_session_is_a_span_with_its_counts(tmp_path):
    session = telemetry.start("trace", str(tmp_path))
    try:
        with telemetry.stage("to_ell", rows=3) as stage:
            stage.set(k=4)
        with pytest.raises(RuntimeError):
            with telemetry.stage("place_re", bytes=1):
                raise RuntimeError("boom")
    finally:
        session.close()
    spans = [e for e in read_run_log(str(tmp_path / "run_log.jsonl"))
             if e["event"] == "span"]
    assert [(s["name"], s["cat"], s["args"]) for s in spans] == [
        ("to_ell", "stage", {"rows": 3, "k": 4}),
        ("place_re", "stage", {"bytes": 1})]
    assert spans[1]["failed"] is True and "failed" not in spans[0]


# -- the call sites -----------------------------------------------------------

STAGE_CALL = re.compile(r'telemetry\.stage\(\s*"([^"]+)"')


def _package_sources():
    for root, _dirs, files in os.walk(os.path.join(REPO, "photon_ml_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    yield os.path.relpath(path, REPO), f.read()


@pytest.fixture(scope="module")
def call_sites():
    sites = {}
    for path, text in _package_sources():
        for name in STAGE_CALL.findall(text):
            sites.setdefault(name, []).append(path)
    return sites


@pytest.mark.parametrize("stage", telemetry.STAGES)
def test_every_stage_has_a_call_site(call_sites, stage):
    assert call_sites.get(stage), f"{stage!r} is in STAGES and nowhere else"


def test_every_call_site_names_a_stage_and_only_the_core_annotates(
        call_sites):
    assert set(call_sites) <= set(telemetry.STAGES)
    annotating = [path for path, text in _package_sources()
                  if "TraceAnnotation" in text]
    assert annotating == [os.path.join("photon_ml_tpu", "telemetry",
                                       "__init__.py")]
