"""The program's stages read back from a profiler trace: a hand-written
XSpace in the profiler's own file format, holding a traced ``fit``, its
nested ``photon/`` stages, two pool-thread stages on another line, a JAX
event that is no stage and a second, shorter fit that is not the traced
one.  Every number below is worked out by hand from the text."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import host_spans  # noqa: E402
from benchmark.harness import manifest as manifests  # noqa: E402
from benchmark.harness import trace_reduce  # noqa: E402

# (name, start_ns, duration_ns) on the traced thread; indentation shows
# the nesting.
MAIN_LINE = [
    ("fit", 0, 100000),
    ("photon/estimator_fit", 1000, 98000),
    ("photon/prepare_fixed", 2000, 40000),
    ("photon/to_ell", 2500, 2000),
    ("photon/grr_plan_build", 5000, 30000),
    ("photon/grr_hot_split", 5500, 1000),
    ("photon/place_batch", 35000, 3000),
    ("photon/place_batch", 38500, 1000),
    ("photon/build_coordinates", 42000, 14000),
    ("photon/group_entities", 43000, 5000),
    ("photon/place_re", 48000, 1000),
    ("photon/group_entities", 50000, 4000),
    ("photon/place_re", 54000, 1000),
    ("photon/cd_initial_scores", 56000, 4000),
    ("photon/cd_coordinate", 60000, 15000),
    ("photon/coord_train", 60500, 11500),
    ("PjitFunction(solve)", 61000, 1000),       # JAX's own, not a stage
    ("photon/coord_score", 72000, 2500),
    ("photon/cd_coordinate", 75000, 10000),
    ("photon/cd_validation", 85000, 8000),
    ("photon/validation", 86000, 6000),
    ("photon/export_model", 93000, 2000),
    # another, shorter fit of the same process: not the traced one
    ("fit", 190000, 50000),
    ("photon/estimator_fit", 191000, 48000),
    ("photon/grr_plan_build", 200000, 30000),
]
POOL_LINE = [
    ("photon/grr_row_part", 7000, 23000),
    ("photon/grr_col_build", 7000, 27000),
]
# Device operations of the first chip: in the plan build, in the initial
# scores, in the first coordinate, across the boundary of the two
# coordinates, and in the validation.
DEVICE_OPS = [(10000, 1000), (57000, 2000), (61000, 10000), (74000, 2000),
              (87000, 1000)]

EXPECTED = {
    "plan_build_s": 30000e-9,
    "entity_grouping_s": (5000 + 4000) * 1e-9,
    "placement_s": (3000 + 1000 + 1000 + 1000) * 1e-9,
    # [56000, 85000) is 29000 ns of initial scores and coordinates; the
    # device ran 2000 + 10000 + 2000 of them; the operations in the plan
    # build and in the validation lie outside and are not subtracted
    "cd_host_s": (29000 - 14000) * 1e-9,
    "validation_s": 6000e-9,
    # the fit is 100000 ns; directly inside estimator_fit: 40000 + 14000
    # + 4000 + 15000 + 10000 + 8000 + 2000
    "host_unnamed_s": (100000 - 93000) * 1e-9,
}


def _xspace(main_line, pool_line=(), device_ops=DEVICE_OPS):
    names = sorted({name for name, _s, _d in list(main_line)
                    + list(pool_line)})
    ids = {name: i + 1 for i, name in enumerate(names)}

    def events(line, stats=""):
        return "\n".join(
            f"events {{ metadata_id: {ids[name]} offset_ps: {start * 1000} "
            f"duration_ps: {duration * 1000} "
            + (stats if name.startswith("photon/") else "") + "}"
            for name, start, duration in line)

    metadata = "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" }} }}'
        for name, i in ids.items())
    ops = "\n".join(
        f"events {{ metadata_id: 1 offset_ps: {start * 1000} "
        f"duration_ps: {duration * 1000} }}" for start, duration in device_ops)
    return f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {ops} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[8] fusion(%a), kind=kLoop" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 5 name: "python" timestamp_ns: 0
    {events(main_line, "stats { metadata_id: 1 int64_value: 7 } "
                       "stats { metadata_id: 2 str_value: 'global' }")} }}
  lines {{ id: 6 name: "python" timestamp_ns: 0
    {events(pool_line, "stats { metadata_id: 3 str_value: 'grr_plan_build' }")} }}
  {metadata}
  stat_metadata {{ key: 1 value {{ id: 1 name: "rows" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "coordinate" }} }}
  stat_metadata {{ key: 3 value {{ id: 3 name: "parent" }} }}
}}
'''


def _traced(tmp_path, monkeypatch, text):
    """The text as the newest ``.xplane.pb`` of the benchmark's trace
    directory, and the ``ctx`` the harness hands a reader for it."""
    from jax.profiler import ProfileData

    trace_dir = tmp_path / "trace"
    path = trace_dir / "cell-1" / "plugins" / "profile" / "t" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(host_spans, "TRACE_DIR", str(trace_dir))
    trace = trace_reduce.summarize(trace_reduce.read_xplane(str(path)),
                                   "fit", chips=1, k=10)
    return {"trace": trace, "chips": 1}


def _reader(name):
    return manifests.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py")).read


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    return _traced(tmp_path, monkeypatch, _xspace(MAIN_LINE, POOL_LINE))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_number_worked_out_by_hand(ctx, name):
    assert ctx["trace"]["interval"] == (0.0, 100000.0)
    assert _reader(name)(ctx) == pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_none_where_the_program_has_no_stage(
        tmp_path, monkeypatch, name):
    """The parent commit's trace: a ``fit`` and JAX's own events."""
    bare = [e for e in MAIN_LINE if not e[0].startswith("photon/")]
    ctx = _traced(tmp_path, monkeypatch, _xspace(bare))
    assert host_spans.stages(ctx) is None
    assert _reader(name)(ctx) is None
    assert _reader(name)({}) is None and _reader(name)({"trace": None}) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_none_when_the_file_is_not_this_runs(ctx, name):
    """No host event starts and ends where the traced operation did:
    the newest file is another run's, and nothing is read from it."""
    lo, hi = ctx["trace"]["interval"]
    other = {"trace": dict(ctx["trace"], interval=(lo, hi + 1)), "chips": 1}
    assert _reader(name)(other) is None


def test_reader_gives_none_without_a_trace_file(tmp_path, monkeypatch, ctx):
    monkeypatch.setattr(host_spans, "TRACE_DIR", str(tmp_path / "empty"))
    assert host_spans.newest_xplane() is None
    assert host_spans.stages(ctx) is None


def test_only_the_stages_a_reader_names_are_missing(tmp_path, monkeypatch):
    """A fit with no random effect has no grouping and no placement of
    entity blocks: those readers leave their metric out, the others
    read on."""
    line = [e for e in MAIN_LINE
            if e[0] not in ("photon/group_entities", "photon/place_re")]
    ctx = _traced(tmp_path, monkeypatch, _xspace(line, POOL_LINE))
    assert _reader("entity_grouping_s")(ctx) is None
    assert _reader("placement_s")(ctx) == pytest.approx(4000e-9)
    assert _reader("plan_build_s")(ctx) == pytest.approx(30000e-9)


def test_stages_keeps_threads_apart_and_the_counts(ctx):
    found = host_spans.stages(ctx)
    assert found["interval"] == (0.0, 100000.0)
    # the second fit's stages and JAX's own event are not there
    assert len(found["thread"]) == 20
    assert all(name.startswith("photon/") for _s, _d, name in found["thread"])
    assert sorted(found["other"]) == [
        (7000.0, 23000.0, "photon/grr_row_part"),
        (7000.0, 27000.0, "photon/grr_col_build")]
    assert found["counts"][(5000.0, 30000.0, "photon/grr_plan_build")] \
        == {"rows": 7, "coordinate": "global"}
    assert found["counts"][(7000.0, 23000.0, "photon/grr_row_part")] \
        == {"parent": "grr_plan_build"}
    # trace_reduce applies as it is: self time by name adds up to the
    # traced thread's staged time
    by_name = trace_reduce.sum_by_name(found["thread"])
    assert by_name["photon/estimator_fit"] == 98000 - 93000
    assert sum(by_name.values()) == trace_reduce.busy_time(found["thread"])


def test_the_fit_is_divided_and_not_counted_twice(ctx):
    found = host_spans.stages(ctx)
    children = host_spans.direct_children(found["thread"],
                                          host_spans.TOP_STAGE)
    assert [name for _s, _d, name in children] == [
        "photon/prepare_fixed", "photon/build_coordinates",
        "photon/cd_initial_scores", "photon/cd_coordinate",
        "photon/cd_coordinate", "photon/cd_validation",
        "photon/export_model"]
    assert sum(d for _s, d, _n in children) \
        == trace_reduce.busy_time(children) == 93000
    assert host_spans.direct_children(found["thread"], "photon/nothing") == []


def test_idle_gaps_take_the_innermost_stage_as_their_label(ctx):
    """What ``run.py`` prints as the breakdown: a gap is named after the
    shortest host span that holds its middle, now a stage."""
    gaps = dict(ctx["trace"]["idle_gaps"])
    assert gaps["photon/grr_plan_build@0.000s"] == 10000 / 1e9
    labels = [label.split("@")[0] for label, _s in ctx["trace"]["idle_gaps"]]
    assert "fit" not in labels[:3]


def test_cli_prints_one_json_line_a_stage(ctx, capsys):
    assert host_spans.main([host_spans.newest_xplane()]) == 0
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert len(rows) == 24                  # both fits', both threads'
    assert rows[0] == {"stage": "estimator_fit", "line": 0, "at_s": 0.0,
                       "seconds": 98000 / 1e9, "rows": 7,
                       "coordinate": "global"}
    assert {r["stage"] for r in rows if r["line"] == 1} \
        == {"grr_row_part", "grr_col_build"}


def test_manifest_names_a_reader_for_each_span_metric():
    """Every ``program_span`` metric of the manifest, whatever their
    number: a reader of its own name, seconds, lower is better, and an
    end-to-end metric of each of its cells that it moves.  The six
    worked out by hand above are among them."""
    manifest = manifests.load_manifest()
    spans = [m for m in manifest["per_layer"]
             if m["source"] == "program_span"]
    assert set(EXPECTED) <= {m["name"] for m in spans}
    for m in spans:
        assert (m["unit"], m["better"]) == ("s", "lower")
        assert callable(_reader(m["name"]))
        for cell in m.get("workloads",
                          [w["name"] for w in manifest["workloads"]]):
            assert m["moves"] in {e["name"] for e in manifests.metrics_of(
                manifest, "end_to_end", cell)}
