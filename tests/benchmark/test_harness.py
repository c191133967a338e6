"""The benchmark harness on the CPU: every manifest entry resolves by
name, the result line has the contract's keys, run.py refuses to run
without a TPU, the generators are deterministic in the seed, and every
cell's operation, rehearsed at a tiny size, is ``correct`` whole and
not ``correct`` damaged or cut short.

These are the CPU rehearsals of benchmark/run.py: they call its pieces
at a tiny size.  Nothing here is a performance number.

What is checked of a cell comes from the manifest, the cell's mix and
its operation (``benchmark/README.md`` has the operation's interface):
this file names no mix, no operation and no limit of ``correct``, so a
cell of another operation is rehearsed and schema-checked by the same
code with no edit here.  The last test proves that on a manifest of its
own under ``tmp_path``.
"""

import json
import os
import re
import shutil
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import manifest as manifests  # noqa: E402
from benchmark.harness.peaks import peaks  # noqa: E402
from benchmark.harness.report import result_line  # noqa: E402

MANIFEST = manifests.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
CONFIGS = [c["name"] for c in MANIFEST["configs"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

# Each configuration file brings its own ``rehearsal_params``: tiny
# values of its generator's parameters (widths included: this is a CPU
# unit test, the cells keep theirs), and each operation says what limits
# a tiny run is held to (``rehearsal_config``), so a new configuration
# or operation edits no test.
OPERATION_INTERFACE = (
    "prepare", "one", "ok", "not_ok", "summary", "end_to_end",
    "reference_check", "limit_problems", "rehearsal_config", "damaged",
    "cut_short")


# -- (1) the manifest resolves by name ---------------------------------------

def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert MANIFEST["command"][-1] == "benchmark/run.py"
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)


def check_cell_resolves(manifest, root, cell_name):
    cell = manifests.resolve(manifest, cell_name, root)
    assert set(cell["cell"]) == {"name", "config", "traffic", "chips", "why"}
    assert cell["cell"]["chips"] in (1, 4)
    assert len(cell["cell"]["why"]) <= 200
    for key in ("config_path", "traffic_path", "generator_path",
                "operation_path"):
        assert os.path.isfile(cell[key]), cell[key]
    for name, path in cell["layer_metric_paths"].items():
        assert os.path.isfile(path), path
        assert callable(manifests.load_module(path).read), name
    operation = manifests.load_module(cell["operation_path"])
    for function in OPERATION_INTERFACE:
        assert callable(getattr(operation, function)), function
    assert operation.LIMIT_KEYS
    for name in getattr(operation, "CONTROLS", ()):
        assert callable(operation.control), name
    assert callable(manifests.load_module(cell["generator_path"]).make)
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer metric
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell["per_layer"]
    # what the operation measures is what the manifest asks of the cell
    measured = operation.end_to_end([3.0, 1.0, 2.0], 6.0)
    assert set(measured) | {"setup_s"} >= e2e
    assert all(value > 0 for value in measured.values())


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_resolves_to_existing_files(cell_name):
    check_cell_resolves(MANIFEST, REPO, cell_name)


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        manifests.resolve(MANIFEST, "no-such.cell")


def check_configuration(manifest, root, config_name):
    entry = {c["name"]: c for c in manifest["configs"]}[config_name]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert any(entry["file"].startswith(p + "/") for p in manifest["paths"])
    assert 1 <= len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    cells = [w["name"] for w in manifest["workloads"]
             if w["config"] == config_name]
    assert cells
    config = manifests.load_json(os.path.join(root, entry["file"]))
    assert config["name"] == config_name
    assert len(config["source"]) <= 200
    # every reduced key is a key of the file, with its reason there
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    run_keys = set(config["generator"]["params"]) | set(
        config.get("training_config", ()))
    for key in entry["reduced"]:
        assert NAME.match(key) and key in run_keys
        assert not re.search(r"(_dim|_rank)$", key)
    assert set(config["generator"]["params"]) >= set(
        config["rehearsal_params"])
    # the limits of ``correct`` are those that the operations running
    # this configuration declare, each with its derivation, and sound
    # by the operation's own account
    for cell_name in cells:
        operation = manifests.load_module(manifests.resolve(
            manifest, cell_name, root)["operation_path"])
        for key in operation.LIMIT_KEYS:
            assert key in config, (cell_name, key)
            assert config[key + "_derivation"], (cell_name, key)
        assert operation.limit_problems(config) == []
        for key in operation.LIMIT_KEYS:
            without = {k: v for k, v in config.items() if k != key}
            assert operation.limit_problems(without), (cell_name, key)


@pytest.mark.parametrize("config_name", CONFIGS)
def test_configuration_file_says_what_the_manifest_says(config_name):
    check_configuration(MANIFEST, REPO, config_name)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_is_well_formed(metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    per_layer = metric in MANIFEST["per_layer"]
    allowed |= {"layer", "moves"} if per_layer else {"bound"}
    assert set(metric) <= allowed and set(metric) >= allowed - {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = metric.get("workloads", CELLS)
    assert set(cells) <= set(CELLS)
    if per_layer:
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
        for cell_name in cells:
            moved = {m["name"] for m in
                     manifests.metrics_of(MANIFEST, "end_to_end", cell_name)}
            assert metric["moves"] in moved, (metric["name"], cell_name)


def test_names_are_unique_and_in_the_allowed_characters():
    for group in (METRICS, MANIFEST["workloads"], MANIFEST["configs"]):
        names = [x["name"] for x in group]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)


def _benchmark_sources():
    """(file name, text) of every Python file under benchmark/."""
    for folder, _dirs, files in os.walk(os.path.join(REPO, "benchmark")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    yield name, f.read()


def test_benchmark_imports_nothing_of_bench_smoke_or_examples():
    for name, src in _benchmark_sources():
        assert not re.search(
            r"^\s*(import|from)\s+(bench|chip_smoke|kdd_scale|make_data|"
            r"examples)\b", src, re.M), name


def test_peaks_table_refuses_an_unlisted_device():
    assert peaks("TPU v5 lite")["hbm_gb_per_s"] == 819.0
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


# -- (2) the result line, and no TPU no run ----------------------------------

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 5_000_000_000}
COMPARED = {"a_gap": {"value": 0.25, "limit": 0.5},
            "a_floor": {"value": 0.75, "at_least": 0.5}}


def test_result_line_has_exactly_the_contract_keys():
    line = result_line(
        correct=True, attempted=2, failed=0,
        metrics={"fit_s": 31.25, "setup_s": 60.5},
        units={"fit_s": "s", "setup_s": "s"}, device=dict(DEVICE),
        compared=COMPARED)
    assert "\n" not in line
    record = json.loads(line)
    assert set(record) == {"correct", "attempted", "failed", "metrics",
                           "device", "compared"}
    assert list(record)[-1] == "compared" and record["compared"] == COMPARED
    assert record["metrics"] == {"fit_s": {"value": 31.25, "unit": "s"},
                                 "setup_s": {"value": 60.5, "unit": "s"}}
    assert record["device"] == DEVICE
    assert record["correct"] is True and record["failed"] == 0


def test_traced_result_line_adds_the_breakdown():
    device = dict(DEVICE, busy_s=1.5, window_s=30.0)
    breakdown = {"device_ops": [["kernel", 0.5]],
                 "idle_gaps": [["fit@0.000s", 17.0]]}
    record = json.loads(result_line(
        correct=False, attempted=1, failed=1,
        metrics={"device_idle_share": 95.0}, units={"device_idle_share": "%"},
        device=device, breakdown=breakdown, compared=COMPARED))
    assert set(record) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown", "compared"}
    assert list(record)[-1] == "compared"
    assert record["breakdown"] == breakdown and record["correct"] is False
    assert set(record["device"]) == set(DEVICE) | {"busy_s", "window_s"}


@pytest.mark.parametrize("cell_name", CELLS)
def test_without_a_chip_run_py_exits_nonzero_with_no_result(cell_name,
                                                           tmp_path):
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", cell_name, "--seed", str(2**31 + 7),
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="anything"))
    assert proc.returncode != 0
    assert time.time() - t0 < 60
    assert proc.stdout == ""
    said = [json.loads(ln) for ln in proc.stderr.splitlines()
            if ln.startswith("{")]
    assert said[0]["platform"] == "cpu"
    assert any("no TPU" in r.get("error", "") for r in said)
    assert os.listdir(tmp_path) == []


def test_source_never_steers_the_platform_or_reads_bench_run():
    for name, src in _benchmark_sources():
        for word in ("JAX_PLATFORMS", "jax_platforms", "BENCH_RUN"):
            assert word not in src, (name, word)


# -- (3) generators, and every cell's operation rehearsed --------------------

def _arrays(dataset):
    out = [dataset.labels]
    for name in sorted(dataset.features):
        f = dataset.features[name]
        out += [f] if isinstance(f, np.ndarray) else [f.indptr, f.cols,
                                                      f.vals]
    out += [dataset.entity_ids[k] for k in sorted(dataset.entity_ids)]
    return out


@pytest.mark.parametrize("config_name", CONFIGS)
def test_generator_is_deterministic_in_the_seed(config_name):
    entry = {c["name"]: c for c in MANIFEST["configs"]}[config_name]
    config = manifests.load_json(os.path.join(REPO, entry["file"]))
    params = dict(config["generator"]["params"], **config["rehearsal_params"])
    generator = manifests.load_module(os.path.join(
        REPO, "benchmark", "generators", config["generator"]["name"] + ".py"))
    big_seed = 2**31 + 11  # the driver's seeds do not fit 32 signed bits
    a = generator.make(big_seed, **params)
    b = generator.make(big_seed, **params)
    c = generator.make(big_seed + 1, **params)
    for part_a, part_b in zip(a[:2], b[:2]):
        for x, y in zip(_arrays(part_a), _arrays(part_b)):
            np.testing.assert_array_equal(x, y)
    for key in ("train_margins", "valid_margins"):
        np.testing.assert_array_equal(a[2][key], b[2][key])
    assert len(a[2]["train_margins"]) == a[0].n
    assert not np.array_equal(a[0].labels, c[0].labels)
    # another seed, the same shapes and the same sparsity pattern: the
    # same programs and the same host work, on other numbers
    for x, y in zip(_arrays(a[0]), _arrays(c[0])):
        assert x.shape == y.shape
    for name, shard in a[0].features.items():
        if not isinstance(shard, np.ndarray):
            np.testing.assert_array_equal(shard.cols,
                                          c[0].features[name].cols)
    for key in a[0].entity_ids:
        np.testing.assert_array_equal(a[0].entity_ids[key],
                                      c[0].entity_ids[key])
    assert a[0].n + a[1].n == params["n"]
    assert a[1].n == int(params["n"] * params["valid_fraction"])


def rehearse(manifest, root, cell_name):
    """(operation, state, outcome) of one run of the cell's operation
    at its configuration's rehearsal size, under the cell's own mix."""
    cell = manifests.resolve(manifest, cell_name, root)
    operation = manifests.load_module(cell["operation_path"])
    config = operation.rehearsal_config(cell["config"])
    assert all(config["generator"]["params"][key] == value
               for key, value in cell["config"]["rehearsal_params"].items())
    data = manifests.load_module(cell["generator_path"]).make(
        3, **config["generator"]["params"])
    state = operation.prepare(config, cell["traffic"], data)
    return operation, state, operation.one(state)


def check_whole(rehearsal):
    operation, state, outcome = rehearsal
    check = operation.reference_check(state, outcome)
    assert check["correct"], check
    assert all(check["conditions"].values())
    # every number compared is there beside its limit
    for name, entry in check["compared"].items():
        assert NAME.match(name)
        assert set(entry) in ({"value", "limit"}, {"value", "at_least"})
    json.dumps(check["compared"])
    assert operation.ok(outcome, outcome)
    spoiled = operation.not_ok(outcome)
    assert spoiled and not any(operation.ok(bad, outcome) for bad in spoiled)
    assert isinstance(operation.summary(outcome), dict)


def check_damaged(rehearsal):
    operation, state, outcome = rehearsal
    variants = operation.damaged(state, outcome)
    assert variants
    for what, bad, failing in variants:
        check = operation.reference_check(state, bad)
        assert not check["correct"], (what, check)
        assert failing and not any(check["conditions"][c] for c in failing), (
            what, check)


def check_cut_short(rehearsal):
    """Left-out work shows: with the limits set just above what the
    whole run reaches, the same run cut short is not ``correct``."""
    operation, state, outcome = rehearsal
    tight, short, holding, one_fails = operation.cut_short(state, outcome)
    check = operation.reference_check(short, operation.one(short))
    assert not check["correct"], check
    assert all(check["conditions"][c] for c in holding), check
    assert not all(check["conditions"][c] for c in one_fails), check
    assert operation.reference_check(tight, outcome)["correct"]


@pytest.fixture(scope="module", params=CELLS)
def rehearsal(request):
    return rehearse(MANIFEST, REPO, request.param)


def test_tiny_fit_passes_the_plain_reference(rehearsal):
    check_whole(rehearsal)


def test_a_model_with_one_block_zeroed_fails_the_reference(rehearsal):
    check_damaged(rehearsal)


def test_a_solve_cut_short_fails_the_reference(rehearsal):
    check_cut_short(rehearsal)


# -- (4) the room is real: a cell of another operation, as files alone --------

STUB_FILES = {
    "configs/seven-steps.json": json.dumps({
        "name": "seven-steps", "source": "a deployment of another task",
        "generator": {"name": "nothing", "params": {"n": 1000}},
        "rehearsal_params": {"n": 10},
        "reduced": {"n": "a thousand of a million, for the test"},
        "answer_atol": 0.5,
        "answer_atol_derivation": "the answer is exact; half a step"}),
    "traffic/count.json": json.dumps({"operation": "answer", "steps": 7}),
    "generators/nothing.py": """
        def make(seed, *, n):
            return {"seed": seed, "n": n}
        """,
    "reference/sixes.py": """
        def expected(steps):
            return 6.0 * steps
        """,
    "layer_metrics/answer_reads.py": """
        def read(ctx):
            return None
        """,
    "operations/answer.py": """
        import copy
        import os

        from benchmark.harness import manifest as manifests

        HERE = os.path.dirname(os.path.abspath(__file__))
        LIMIT_KEYS = ("answer_atol",)

        def limit_problems(config):
            return [] if 0 < config.get("answer_atol", 0) < 1 else ["no"]

        def rehearsal_config(config):
            config = copy.deepcopy(config)
            config["generator"]["params"].update(config["rehearsal_params"])
            return config

        def prepare(config, traffic, data):
            return {"config": config, "steps": traffic["steps"]}

        def one(state):
            return {"answer": 42.0 * state["steps"] / 7}

        def ok(outcome, warm):
            return outcome["answer"] == warm["answer"]

        def not_ok(outcome):
            return [{"answer": outcome["answer"] + 1}]

        def summary(outcome):
            return dict(outcome)

        def end_to_end(durations, window_s):
            return {"answer_s": sum(durations) / len(durations)}

        def reference_check(state, outcome):
            sixes = manifests.load_module(os.path.join(
                HERE, "..", "reference", "sixes.py"))
            compared = {"answer": {
                "value": abs(outcome["answer"] - sixes.expected(7)),
                "limit": state["config"]["answer_atol"]}}
            close = compared["answer"]["value"] <= compared["answer"]["limit"]
            return {"correct": close, "conditions": {"answer_close": close},
                    "compared": compared}

        def damaged(state, outcome):
            return [("one added", {"answer": outcome["answer"] + 1},
                     ["answer_close"])]

        def cut_short(state, outcome):
            return state, dict(state, steps=2), [], ["answer_close"]
        """,
}


def test_a_cell_of_another_operation_is_taken_as_files_alone(tmp_path):
    """A manifest under ``tmp_path`` that adds, to a copy of the
    benchmark, one configuration, one mix, one operation with limit
    keys of its own, its reference and a metric: the cell resolves, is
    schema-checked and rehearsed by the code above, and no file of
    ``benchmark/`` or ``tests/benchmark/`` was edited for it."""
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    for name, text in STUB_FILES.items():
        path = tmp_path / "benchmark" / name
        assert not path.exists()
        path.write_text(textwrap.dedent(text))
    theirs = [w["name"] for w in MANIFEST["workloads"]]
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append({
        "name": "seven-steps", "source": "a deployment of another task",
        "file": "benchmark/configs/seven-steps.json", "reduced": ["n"],
        "why": "a stub"})
    manifest["workloads"].append({
        "name": "seven-steps.count", "config": "seven-steps",
        "traffic": "count", "chips": 1, "why": "a stub"})
    # a metric of the other operation's cells lists them, so the new
    # cell reports neither it nor the layer metrics that move it
    for metric in manifest["end_to_end"]:
        if metric["name"] != "setup_s":
            metric.setdefault("workloads", theirs)
    manifest["end_to_end"].append({
        "name": "answer_s", "unit": "s", "better": "lower", "bound": 0.05,
        "source": "host_clock", "workloads": ["seven-steps.count"]})
    manifest["per_layer"].append({
        "name": "answer_reads", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "entry", "moves": "answer_s",
        "workloads": ["seven-steps.count"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    manifest = manifests.load_manifest(str(tmp_path))

    cell = manifests.resolve(manifest, "seven-steps.count", str(tmp_path))
    assert cell["operation_path"].startswith(str(tmp_path))
    assert [m["name"] for m in cell["per_layer"]] == ["answer_reads"]
    assert {m["name"] for m in cell["end_to_end"]} == {"answer_s", "setup_s"}
    check_cell_resolves(manifest, str(tmp_path), "seven-steps.count")
    check_configuration(manifest, str(tmp_path), "seven-steps")
    stub = rehearse(manifest, str(tmp_path), "seven-steps.count")
    check_whole(stub)
    check_damaged(stub)
    check_cut_short(stub)
    # and the cells that were there still resolve beside it, unchanged
    for cell_name in theirs:
        check_cell_resolves(manifest, str(tmp_path), cell_name)
        assert ([m["name"] for m in manifests.resolve(
            manifest, cell_name, str(tmp_path))["per_layer"]]
            == [m["name"] for m in manifests.resolve(
                MANIFEST, cell_name)["per_layer"]])
