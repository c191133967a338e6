"""The reader of ``fe_forward_passes`` (ISSUE 31) on a hand-written
trace: a traced ``fit`` whose three ``photon/coord_train`` stages are
recorded as the program publishes them, the fixed effect's with the
counts of its one solve, the random effects' (lists of batched
results) with the coordinate's name alone."""

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

NAME = "fe_forward_passes"
# the fixed effect's solve along the margins: 30 iterations, 55 trials,
# 31 forward contractions; the same solve by whole evaluations: 86
ALONG = {"solver_iterations": 30, "ls_trials": 55, "forward_passes": 31}
WHOLE = {"solver_iterations": 30, "ls_trials": 55}
# (coordinate, start_ns, duration_ns)
TRAINS = [("global", 60500, 11500), ("per_user", 75500, 3000),
          ("per_item", 80500, 500)]


def _xspace(fixed_counts, trains=TRAINS):
    keys = sorted(ALONG)
    stat_ids = {key: i + 2 for i, key in enumerate(keys)}

    def stats(coordinate, counts):
        return f"stats {{ metadata_id: 1 str_value: '{coordinate}' }} " \
            + " ".join(f"stats {{ metadata_id: {stat_ids[key]} "
                       f"int64_value: {value} }}"
                       for key, value in counts.items())

    events = "\n".join(
        f"events {{ metadata_id: 3 offset_ps: {start * 1000} "
        f"duration_ps: {duration * 1000} "
        f"{stats(coordinate, fixed_counts[coordinate])} }}"
        for coordinate, start, duration in trains)
    stat_metadata = "\n".join(
        f'stat_metadata {{ key: {i} value {{ id: {i} name: "{key}" }} }}'
        for key, i in stat_ids.items())
    return f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 61000000 duration_ps: 10000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[8] fusion(%a), kind=kLoop" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 5 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }}
    events {{ metadata_id: 2 offset_ps: 1000000 duration_ps: 98000000 }}
    {events} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fit" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "photon/estimator_fit" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "photon/coord_train" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "coordinate" }} }}
  {stat_metadata}
}}
'''


def _traced(tmp_path, monkeypatch, text):
    from jax.profiler import ProfileData

    trace_dir = tmp_path / "trace"
    path = trace_dir / "cell-1" / "plugins" / "profile" / "t" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(host_spans, "TRACE_DIR", str(trace_dir))
    host_spans.read_host_lines.cache_clear()
    trace = trace_reduce.summarize(trace_reduce.read_xplane(str(path)),
                                   "fit", chips=1, k=10)
    return {"trace": trace, "chips": 1}


def _read(ctx):
    return manifests.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", NAME + ".py")).read(ctx)


def test_the_stages_that_carry_the_count_are_summed(tmp_path, monkeypatch):
    counts = {"global": ALONG, "per_user": {}, "per_item": {}}
    ctx = _traced(tmp_path, monkeypatch, _xspace(counts))
    assert len(host_spans.named(host_spans.stages(ctx)["thread"],
                                "coord_train")) == 3
    assert _read(ctx) == 31.0
    # a second sweep's fixed-effect solve adds its own
    again = TRAINS + [("global", 85000, 9000)]
    ctx = _traced(tmp_path / "two", monkeypatch, _xspace(counts, again))
    assert _read(ctx) == 62.0


def test_a_program_that_does_not_count_them_leaves_the_metric_out(
        tmp_path, monkeypatch):
    """The parent commit's stages: iterations, and no forward passes."""
    parent = {"global": {"solver_iterations": 30}, "per_user": {},
              "per_item": {}}
    ctx = _traced(tmp_path, monkeypatch, _xspace(parent))
    assert host_spans.stages(ctx) is not None
    assert _read(ctx) is None
    # nor does a solve by whole evaluations (OWL-QN): its two other
    # counts say what it made
    ctx = _traced(tmp_path / "whole", monkeypatch,
                  _xspace(dict(parent, **{"global": WHOLE})))
    assert _read(ctx) is None


def test_nothing_to_read_without_a_trace():
    assert _read({}) is None
    assert _read({"trace": None, "chips": 1}) is None


def test_the_manifest_gives_the_count_to_the_cells_that_count():
    """Both cells' fixed effect is an L-BFGS solve with no L1 term.  The
    entry lists them: a later cell whose fixed effect runs TRON or
    OWL-QN has no count to report."""
    manifest = manifests.load_manifest()
    (metric,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    cells = ["game5-kdd.fit-cold", "game5-kdd12.fit-cold"]
    assert metric == {"name": NAME, "unit": "count", "better": "lower",
                      "source": "program_counter",
                      "layer": "objective + solvers", "moves": "fit_s",
                      "workloads": cells}
    for cell in cells:
        assert NAME in manifests.resolve(
            manifest, cell)["layer_metric_paths"]
