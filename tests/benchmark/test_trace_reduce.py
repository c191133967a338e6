"""The trace reduction on hand-made (start, duration, name) events: the
busy-interval union, the sum by name, the idle gaps and their labels."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import manifest as manifests  # noqa: E402
from benchmark.harness import trace_reduce  # noqa: E402

# One device line: a while loop [10, 60) holding two fusions and a
# kernel, then a kernel that overlaps the next fusion without nesting.
EVENTS = [
    (10, 50, "while"),
    (12, 10, "fusion.1"),
    (25, 20, "kernel"),
    (30, 5, "inner"),       # nested in the kernel, two levels down
    (50, 5, "fusion.1"),
    (100, 20, "kernel"),
    (110, 30, "fusion.2"),  # overlaps the kernel, ends after it
]


@pytest.mark.parametrize("events, lo, hi, expected", [
    ([], None, None, []),
    (EVENTS, None, None, [(10, 60), (100, 140)]),
    (EVENTS, 20, 120, [(20, 60), (100, 120)]),
    ([(5, 5, "a"), (10, 5, "b")], None, None, [(5, 15)]),   # touching
    ([(0, 10, "a"), (0, 10, "a")], None, None, [(0, 10)]),  # duplicates
    ([(0, 10, "a")], 20, 30, []),                            # outside
])
def test_busy_intervals(events, lo, hi, expected):
    assert trace_reduce.busy_intervals(events, lo, hi) == expected


@pytest.mark.parametrize("events, lo, hi, expected", [
    ([], 0, 100, 0),
    (EVENTS, None, None, 90),
    (EVENTS, 0, 200, 90),
    (EVENTS, 20, 120, 60),
    (list(reversed(EVENTS)), None, None, 90),
])
def test_busy_time_counts_overlap_and_nesting_once(events, lo, hi, expected):
    assert trace_reduce.busy_time(events, lo, hi) == expected


def test_sum_by_name_is_self_time_for_nested_events():
    totals = trace_reduce.sum_by_name(EVENTS)
    assert totals == {
        "while": 50 - 10 - 20 - 5,    # less its three direct children
        "fusion.1": 10 + 5,
        "kernel": (20 - 5) + 20,      # the first less its child
        "inner": 5,
        "fusion.2": 30,               # overlapping, not nested: whole
    }
    # nested events add up to the union of their line
    nested = EVENTS[:5]
    assert sum(trace_reduce.sum_by_name(nested).values()) \
        == trace_reduce.busy_time(nested)


def test_sum_by_name_of_nothing_and_order_independence():
    assert trace_reduce.sum_by_name([]) == {}
    assert trace_reduce.sum_by_name(list(reversed(EVENTS))) \
        == trace_reduce.sum_by_name(EVENTS)


def test_top_orders_by_value_then_name():
    assert trace_reduce.top({"b": 2, "a": 2, "c": 5, "d": 1}, 3) \
        == [["c", 5], ["a", 2], ["b", 2]]
    assert trace_reduce.top({}, 3) == []


KERNEL_HLO = (
    '%body.190 = f32[10140,16,128]{2,1,0:T(8,128)S(1)} custom-call('
    's32[2535]{0:T(1024)S(1)} %copy-done.85, f32[195,128,128]{2,1,0:T(8,128)'
    'S(1)} %bitcast.186), custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={s32[2535]{0}, f32[195,128,128]{2,1,0}}')


@pytest.mark.parametrize("name, expected", [
    # the names a v5e trace gave (my chip run, PR 25), cut for the test
    (KERNEL_HLO, "%body.190 custom-call:tpu_custom_call "
                 "f32[10140,16,128]{2,1,0:T(8,128)S(1)}"),
    ("%fusion.555 = f32[82911]{0:T(1024)S(1)} fusion(f32[82911,10]{0,1:T(8,"
     "128)S(1)} %get-tuple-element.12750), kind=kCustom, calls=%fused.9",
     "%fusion.555 fusion:kCustom f32[82911]{0:T(1024)S(1)}"),
    # a tuple result: the opcode is not taken from inside the shape
    ("%while.130 = (f32[100001]{0:T(1024)}, f32[]{:T(128)}) while((f32[100001]"
     "{0:T(1024)}, f32[]{:T(128)}) %tuple.7), condition=%c, body=%b",
     "%while.130 while (f32[100001]{0:T(1024)}"),
    ("kernel", "kernel"),
    ("x" * 300, "x" * trace_reduce.NAME_CHARS),
], ids=["mosaic", "fusion", "tuple", "plain", "long"])
def test_short_name_keeps_instruction_opcode_and_shape(name, expected):
    assert trace_reduce.short_name(name) == expected
    assert len(trace_reduce.short_name(name)) <= trace_reduce.NAME_CHARS


def test_idle_gaps_longest_first_with_the_innermost_host_span():
    host = [(0, 200, "fit"), (60, 45, "plan_build"), (62, 10, "short"),
            (300, 10, "elsewhere")]
    gaps = trace_reduce.idle_gaps(EVENTS, 0, 200, host)
    assert gaps == [
        (140, 60, "fit"),          # after the last event, to hi
        (60, 40, "plan_build"),    # middle 80: fit and plan_build hold it
        (0, 10, "fit"),
    ]
    assert sum(g[1] for g in gaps) + trace_reduce.busy_time(EVENTS, 0, 200) \
        == 200
    assert trace_reduce.idle_gaps(EVENTS, 0, 200, host, k=2) == gaps[:2]
    # no host span holds the gap: no label
    assert trace_reduce.idle_gaps([], 0, 10) == [(0, 10, None)]
    # an empty line inside a window is one gap, the whole window
    assert trace_reduce.idle_gaps([], 5, 25, [(0, 100, "fit")]) \
        == [(5, 20, "fit")]


def test_summarize_takes_the_annotated_interval_and_averages_over_chips():
    planes = {
        "device": {"/device:TPU:0": EVENTS + [(500, 10, "after")],
                   "/device:TPU:1": [(100, 40, "kernel")]},
        "host": {"python#0": [(0, 200, "fit"), (60, 45, "plan_build"),
                              (400, 5, "fit")],
                 "worker#1": [(0, 1000, "noise")]},
    }
    out = trace_reduce.summarize(planes, "fit", chips=2, k=2)
    assert out["interval"] == (0, 200) and out["window_ns"] == 200
    assert out["busy_ns"] == (90 + 40) / 2
    assert out["n_device_events"] == len(EVENTS) + 1  # "after" is outside
    assert out["device_ops"] == [["kernel", (35 + 40) / 2 / 1e9],
                                 ["fusion.2", 30 / 2 / 1e9]]
    assert out["idle_gaps"] == [["fit@0.000s", 60 / 1e9],
                                ["plan_build@0.000s", 40 / 1e9]]
    with pytest.raises(RuntimeError, match="annotation"):
        trace_reduce.summarize(planes, "score", 1, 2)
    with pytest.raises(RuntimeError, match="no device operation"):
        trace_reduce.summarize({"device": {}, "host": planes["host"]},
                               "fit", 1, 2)


def _reader(name):
    return manifests.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py")).read


SORT_HLO = ('%custom-call.3 = (f32[8]{0}, s32[8]{0}) custom-call(f32[8]{0} '
            '%a), custom_call_target="Sort"')


def test_kernel_time_counts_only_the_mosaic_custom_calls():
    planes = {
        "device": {"/device:TPU:0": [(0, 30, KERNEL_HLO),
                                     (40, 10, SORT_HLO),
                                     (60, 20, KERNEL_HLO)],
                   "/device:TPU:1": [(5, 10, KERNEL_HLO)]},
        "host": {"python#0": [(0, 100, "fit")]},
    }
    trace = trace_reduce.summarize(planes, "fit", chips=2, k=3)
    read = _reader("grr_kernel_ms")
    # 50 ns on the first chip, 10 on the second, averaged over two
    assert read({"trace": trace, "chips": 2}) == 60 / 2 / 1e6
    assert read({}) is None
    # no Mosaic kernel ran: nothing to read, the line leaves the metric out
    planes["device"] = {"/device:TPU:0": [(40, 10, SORT_HLO)]}
    assert read({"trace": trace_reduce.summarize(planes, "fit", 2, 3),
                 "chips": 2}) is None


XSPACE = r'''
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000 duration_ps: 5000000
             stats { metadata_id: 2 int64_value: 7 } }
    events { metadata_id: 2 offset_ps: 9000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 19000000 duration_ps: 2000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 30000000 } }
  event_metadata { key: 1 value { id: 1 name:
    "%body.7 = f32[1] custom-call(%a), custom_call_target=\"tpu_custom_call\"" } }
  event_metadata { key: 2 value { id: 2 name:
    "%custom-call.1 = f32[8] custom-call(%b), custom_call_target=\"Sort\"" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[8] fusion(%c), kind=kLoop" } }
  stat_metadata { key: 2 value { id: 2 name: "run_id" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 5 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 90000000 } }
  event_metadata { key: 1 value { id: 1 name: "fit" } } }
'''


def test_read_xplane_takes_the_xla_ops_line_and_the_host_lines(tmp_path):
    """A hand-written XSpace in the profiler's own file format, its
    operations named as a v5e names them: by their HLO text."""
    from jax.profiler import ProfileData

    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    planes = trace_reduce.read_xplane(str(path))
    assert [(s, d) for s, d, _ in planes["device"]["/device:TPU:0"]] == [
        (1001.0, 5000.0), (10000.0, 1000.0), (20000.0, 2000.0)]
    assert list(planes["device"]) == ["/device:TPU:0"]
    assert planes["host"] == {"python#0": [(0.0, 90000.0, "fit")]}
    trace = trace_reduce.summarize(planes, "fit", chips=1, k=3)
    assert trace["busy_ns"] == 8000.0 and trace["window_ns"] == 90000.0
    assert trace["device_ops"][0] == [
        "%body.7 custom-call:tpu_custom_call f32[1]", 5000.0 / 1e9]
    assert _reader("grr_kernel_ms")({"trace": trace, "chips": 1}) \
        == 5000.0 / 1e6


@pytest.mark.parametrize("name,ctx,expected", [
    ("device_busy_ms", {"trace": {"busy_ns": 3e6, "window_ns": 4e6}}, 3.0),
    ("device_idle_share", {"trace": {"busy_ns": 3e6, "window_ns": 4e6}},
     25.0),
    ("peak_hbm_gb", {"memory_peak_bytes": 5e9}, 5.0),
    ("compile_s.window", {"attempted": 2, "compile_s_window": 1.0}, 0.5),
    ("device_busy_ms", {}, None), ("device_idle_share", {}, None),
    ("peak_hbm_gb", {}, None), ("compile_s.window", {}, None),
])
def test_layer_metric_readers(name, ctx, expected):
    assert _reader(name)(ctx) == expected
