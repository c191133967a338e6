"""The readers of the tail's four metrics (ISSUE 30; the roofline by
direction since ISSUE 34) on a hand-written
trace: a traced ``fit`` whose ``photon/grr_plan_build`` stage carries the
column classes' counts, and device operations named, as a v5e names
them, by their HLO text without metadata, some of them on vectors as
long as the tail.  Every number below is worked out by hand from the
text."""

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

NAMES = ("fe_tail_ms", "fe_tail_dot_roofline", "fe_tail_tdot_roofline",
         "tail_nnz_share")
COUNTS = {"rows": 1000, "dim": 50000, "nnz": 11000, "tail_nnz": 4400}


def _hlo(name, result, *operands):
    """A device operation as a v5e's trace names it: its HLO text, with
    no metadata."""
    return (f"%{name} = {result}{{0:T(1024)}} fusion("
            + ", ".join(f"{shape}{{0:T(1024)}} %p{i}"
                        for i, shape in enumerate(operands))
            + "), kind=kCustom, calls=%fused_computation")


# X.w: a gather from the [dim] table into [tail_nnz] (its indices padded
# by an operation of its own), a multiplication, a segment-sum into
# [rows].  X^T r: a gather from [rows], a segment-sum into [dim].
PAD = _hlo("pad_clamp_fusion.3", "s32[5120]", "s32[4400]")
GATHER_W = _hlo("fusion.1", "f32[4400]", "f32[50000]", "s32[5120]")
SUM_ROWS = _hlo("fusion.2", "f32[1000]", "s32[4400]", "f32[4400]", "f32[]")
GATHER_R = _hlo("fusion.3", "f32[4400]", "f32[1000]", "s32[5120]")
SUM_COLS = _hlo("fusion.4", "f32[50000]", "f32[50000]", "s32[4400]",
                "f32[4400]")
# not the tail's: the planned class's scatter into the same width, and
# an operation on another [rows] vector
OTHER = _hlo("fusion.5", "f32[50000]", "f32[50000]", "s32[191]", "f32[191]")
ROWS_OP = _hlo("fusion.6", "f32[1000]", "f32[1000]", "f32[1000]")
# the solver's loop carries the tail's arrays and encloses their
# operations: its own length is not the tail's
LOOP = ("%while.130 = (f32[50000]{0:T(1024)}, s32[4400]{0:T(1024)}, "
        "f32[4400]{0:T(1024)}) while((f32[50000]{0:T(1024)}, "
        "s32[4400]{0:T(1024)}, f32[4400]{0:T(1024)}) %tuple.200), "
        "condition=%region_42, body=%region_12")
# (name, start_ns, duration_ns): three calls of X.w and two of X^T r in
# the traced fit, one of X^T r after it.
DEVICE_OPS = [
    (LOOP, 8000, 62500),
    (PAD, 9000, 1000),
    (GATHER_W, 10000, 2000), (SUM_ROWS, 13000, 5000),
    (PAD, 29000, 1000),
    (GATHER_W, 30000, 2000), (SUM_ROWS, 33000, 5000),
    (PAD, 49000, 1000),
    (GATHER_W, 50000, 2000), (SUM_ROWS, 53000, 5000),
    (GATHER_R, 20000, 2000), (SUM_COLS, 22000, 6000),
    (GATHER_R, 40000, 2000), (SUM_COLS, 42000, 6000),
    (OTHER, 60000, 9000), (ROWS_OP, 70000, 500),
    (SUM_COLS, 150000, 6000),
]
DOT_NS = 3 * (1000 + 2000 + 5000)
TDOT_NS = 2 * (2000 + 6000)
TAIL_NS = DOT_NS + TDOT_NS
# three calls read 4,400 entries of 16 B and write 1,000 rows of 4 B;
# two read the same entries and write 50,000 columns of 4 B
DOT_BYTES = 3 * (4400 * 16 + 4 * 1000)
TDOT_BYTES = 2 * (4400 * 16 + 4 * 50000)


def _share(least_bytes, ns):
    return 100.0 * least_bytes / (ns / 1e9) / 819e9


def _xspace(counts=COUNTS, device_ops=DEVICE_OPS):
    names = sorted({name for name, _s, _d in device_ops})
    ids = {name: i + 1 for i, name in enumerate(names)}
    ops = "\n".join(
        f"events {{ metadata_id: {ids[name]} offset_ps: {start * 1000} "
        f"duration_ps: {duration * 1000} }}"
        for name, start, duration in device_ops)
    op_metadata = "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" }} }}'
        for name, i in ids.items())
    stat_names = sorted(counts)
    stats = " ".join(
        f"stats {{ metadata_id: {i + 1} int64_value: {counts[key]} }}"
        for i, key in enumerate(stat_names))
    stat_metadata = "\n".join(
        f'stat_metadata {{ key: {i + 1} value {{ id: {i + 1} '
        f'name: "{key}" }} }}' for i, key in enumerate(stat_names))
    return f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {ops} }}
  {op_metadata}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 5 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }}
    events {{ metadata_id: 2 offset_ps: 1000000 duration_ps: 98000000 }}
    events {{ metadata_id: 3 offset_ps: 2000000 duration_ps: 6000000
              {stats} }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fit" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "photon/estimator_fit" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "photon/grr_plan_build" }} }}
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
    return {"trace": trace, "chips": 1, "device_kind": "TPU v5 lite"}


def _reader(name):
    return manifests.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    return _traced(tmp_path, monkeypatch, _xspace())


def test_readers_give_the_numbers_worked_out_by_hand(ctx):
    assert ctx["trace"]["interval"] == (0.0, 100000.0)
    assert _reader("fe_tail_ms").read(ctx) == pytest.approx(TAIL_NS / 1e6)
    assert _reader("tail_nnz_share").read(ctx) == pytest.approx(40.0)
    dot = _reader("fe_tail_dot_roofline").read(ctx)
    tdot = _reader("fe_tail_tdot_roofline").read(ctx)
    assert dot == pytest.approx(_share(DOT_BYTES, DOT_NS), rel=1e-12)
    assert tdot == pytest.approx(_share(TDOT_BYTES, TDOT_NS), rel=1e-12)
    assert 0 < dot < 100 and 0 < tdot < 100
    roofline = _reader("fe_tail_dot_roofline")
    assert roofline.least_bytes(4400, 1000, 3) == DOT_BYTES
    assert roofline.least_bytes(4400, 50000, 2) == TDOT_BYTES
    assert roofline.least_bytes(4400, 50000, 0) == 0


def test_the_calls_are_counted_by_what_each_contraction_writes(ctx):
    counts, found = _reader("fe_tail_ms").tail_events(ctx)
    assert counts["tail_nnz"] == 4400
    (events,) = found.values()
    assert len(events) == 13                # the late one is outside
    roofline = _reader("fe_tail_dot_roofline")
    assert roofline.by_direction(counts, found) == {
        "dot": [3, DOT_NS], "tdot": [2, TDOT_NS]}
    # an operation that only writes [dim] or [rows] is not the tail's
    assert not any("%fusion.5 " in name or "%fusion.6 " in name
                   for _s, _d, name in events)


def test_a_direction_that_was_not_traced_is_left_out(tmp_path, monkeypatch):
    """Only X.w calls in the traced fit: the other direction's reader
    gives nothing, never a share of 0; and what follows the last
    segment-sum (a call the trace cut off) is nobody's time."""
    only_dot = [op for op in DEVICE_OPS
                if op[0] not in (GATHER_R, SUM_COLS)] + [(PAD, 90000, 1000)]
    ctx = _traced(tmp_path, monkeypatch, _xspace(COUNTS, only_dot))
    assert _reader("fe_tail_dot_roofline").read(ctx) == pytest.approx(
        _share(DOT_BYTES, DOT_NS), rel=1e-12)
    assert _reader("fe_tail_tdot_roofline").read(ctx) is None
    assert _reader("fe_tail_ms").read(ctx) == pytest.approx(
        (DOT_NS + 1000) / 1e6)


def test_the_operations_are_known_by_the_length_the_program_publishes(
        tmp_path, monkeypatch):
    """A program that pads the tail's arrays says so in ``tail_len``:
    the operations are found by that length, the bytes still counted
    from the entries."""
    padded = [(name.replace("[4400]", "[4480]"), start, duration)
              for name, start, duration in DEVICE_OPS]
    ctx = _traced(tmp_path, monkeypatch,
                  _xspace(dict(COUNTS, tail_len=4480), padded))
    assert _reader("fe_tail_ms").read(ctx) == pytest.approx(TAIL_NS / 1e6)
    assert _reader("fe_tail_dot_roofline").read(ctx) == pytest.approx(
        _share(DOT_BYTES, DOT_NS), rel=1e-12)
    assert _reader("fe_tail_tdot_roofline").read(ctx) == pytest.approx(
        _share(TDOT_BYTES, TDOT_NS), rel=1e-12)
    # and nothing is found under the entries' count
    ctx = _traced(tmp_path / "unsaid", monkeypatch, _xspace(COUNTS, padded))
    assert _reader("fe_tail_ms").read(ctx) is None


@pytest.mark.parametrize("name", NAMES)
def test_readers_give_none_without_a_trace(name):
    assert _reader(name).read({}) is None
    assert _reader(name).read({"trace": None, "chips": 1}) is None


def test_a_program_without_the_tail_class_leaves_them_all_out(
        tmp_path, monkeypatch):
    """The parent commit's trace: a plan build whose stage carries no
    class counts, and no device operation as long as a tail."""
    plain = [(OTHER, 60000, 9000), (ROWS_OP, 70000, 500)]
    ctx = _traced(tmp_path, monkeypatch,
                  _xspace({"rows": 1000, "dim": 50000, "nnz": 11000}, plain))
    assert host_spans.stages(ctx) is not None
    for name in NAMES:
        assert _reader(name).read(ctx) is None


def test_an_input_without_a_tail_reports_a_share_of_zero_and_no_time(
        tmp_path, monkeypatch):
    plain = [(OTHER, 60000, 9000), (ROWS_OP, 70000, 500)]
    ctx = _traced(tmp_path, monkeypatch,
                  _xspace(dict(COUNTS, tail_nnz=0), plain))
    assert _reader("tail_nnz_share").read(ctx) == 0.0
    for name in NAMES[:3]:
        assert _reader(name).read(ctx) is None


def test_the_manifest_gives_them_to_the_cells_with_a_tail_alone():
    manifest = manifests.load_manifest()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert "fe_tail_hbm_roofline" not in by_name
    for name in NAMES:
        assert by_name[name]["workloads"] == ["game5-kdd12.fit-cold"]
        assert by_name[name]["moves"] == "fit_s"
    for name in NAMES[1:3]:
        assert (by_name[name]["unit"], by_name[name]["better"]) == (
            "%", "higher")
    cell = manifests.resolve(manifest, "game5-kdd12.fit-cold")
    assert set(NAMES) <= set(cell["layer_metric_paths"])
    old = manifests.resolve(manifest, "game5-kdd.fit-cold")
    assert not set(NAMES) & set(old["layer_metric_paths"])
