"""The six readers of the compile ledger (ISSUE 39) on a hand-written
trace and a hand-made ledger: a traced ``fit`` whose
``photon/estimator_fit`` says it is the process's second, and rows of
what ran outside a fit, of the warm-up fit, of the traced fit and of the
fit after it."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import compile_path  # noqa: E402
from benchmark.harness import host_spans  # noqa: E402
from benchmark.harness import manifest as manifests  # noqa: E402
from benchmark.harness import trace_reduce  # noqa: E402
from photon_ml_tpu import telemetry  # noqa: E402


def _row(programs, trace_s, lower_s, cache_load_s, compile_s):
    return {"programs": programs, "trace_s": trace_s, "lower_s": lower_s,
            "cache_load_s": cache_load_s, "compile_s": compile_s,
            "cache_hits": 0, "cache_misses": 0, "saved_s": 0.0}


LEDGER = {
    (0, ""): _row(3, 0.25, 0.5, 1.0, 2.0),           # prepare, placement
    (1, "coord_train"): _row(4, 4.0, 8.0, 16.0, 32.0),   # the warm-up fit
    (1, "validation"): _row(30, 64.0, 128.0, 0.0, 256.0),
    (2, "coord_train"): _row(1, 0.125, 0.0625, 0.03125, 7.0),  # traced
    (3, "score_coordinate"): _row(5, 512.0, 512.0, 512.0, 512.0),  # after
}
# name -> what the reader makes of LEDGER with fit 2 traced
EXPECTED = {
    "setup_programs": 37.0,
    "setup_trace_s": 68.25,
    "setup_lower_s": 136.5,
    "setup_cache_load_s": 17.0,
    "setup_compile_s": 290.0,
    "retrace_s.window": 0.21875,
}


def _xspace(fit_stat):
    return f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 61000000 duration_ps: 10000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[8] fusion(%a), kind=kLoop" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 5 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }}
    events {{ metadata_id: 2 offset_ps: 1000000 duration_ps: 98000000
              {fit_stat} }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fit" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "photon/estimator_fit" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "fit" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "rows" }} }}
}}
'''


FIT_TWO = "stats { metadata_id: 1 int64_value: 2 }"


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


def _reader(name):
    cell = manifests.resolve(manifests.load_manifest(),
                             "game5-kdd.fit-cold")
    return manifests.load_module(cell["layer_metric_paths"][name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_sums_the_rows_on_its_side_of_the_traced_fit(
        tmp_path, monkeypatch, name):
    ctx = _traced(tmp_path, monkeypatch, _xspace(FIT_TWO))
    monkeypatch.setattr(telemetry, "compile_ledger", lambda: dict(LEDGER))
    assert compile_path.traced_fit(ctx) == 2
    assert _reader(name).read(ctx) == EXPECTED[name]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_zero_where_nothing_was_charged(
        tmp_path, monkeypatch, name):
    ctx = _traced(tmp_path, monkeypatch, _xspace(FIT_TWO))
    monkeypatch.setattr(telemetry, "compile_ledger",
                        lambda: {(3, "validation"): LEDGER[(0, "")]})
    assert _reader(name).read(ctx) == 0.0


@pytest.mark.parametrize("name", sorted(EXPECTED))
@pytest.mark.parametrize("missing", ["trace", "stage", "fit_count", "ledger"])
def test_reader_has_nothing_to_read(tmp_path, monkeypatch, name, missing):
    """No trace; a trace of a program with no stages; an
    ``estimator_fit`` that carries no ``fit``; a program that keeps no
    ledger (the parent of ISSUE 39)."""
    if missing == "trace":
        ctx = {"trace": None, "chips": 1}
    elif missing == "stage":
        ctx = _traced(tmp_path, monkeypatch, _xspace(FIT_TWO).replace(
            "photon/estimator_fit", "something_else"))
    elif missing == "fit_count":
        ctx = _traced(tmp_path, monkeypatch, _xspace(
            "stats { metadata_id: 2 int64_value: 9 }"))
    else:
        ctx = _traced(tmp_path, monkeypatch, _xspace(FIT_TWO))
        monkeypatch.delattr(telemetry, "compile_ledger")
    if missing != "ledger":
        monkeypatch.setattr(telemetry, "compile_ledger",
                            lambda: dict(LEDGER))
    assert _reader(name).read(ctx) is None


def test_every_setup_metric_is_reported_in_every_cell():
    manifest = manifests.load_manifest()
    for cell in manifest["workloads"]:
        reported = {m["name"] for m in manifests.metrics_of(
            manifest, "per_layer", cell["name"])}
        assert set(EXPECTED) <= reported, cell["name"]
