"""The benchmark harness on the CPU: every manifest entry resolves by
name, the result line has the contract's keys, run.py refuses to run
without a TPU, the generators are deterministic in the seed, and the
plain reference accepts a tiny fit and rejects a damaged model.

These are the CPU rehearsals of benchmark/run.py: they call its pieces
at a tiny size.  Nothing here is a performance number.
"""

import copy
import dataclasses
import json
import os
import re
import subprocess
import sys
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
# unit test, the cells keep theirs), so a new configuration edits no
# test.


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


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_resolves_to_existing_files(cell_name):
    cell = manifests.resolve(MANIFEST, cell_name)
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
    for function in ("prepare", "one", "ok", "summary", "end_to_end",
                     "reference_check"):
        assert callable(getattr(operation, function)), function
    assert callable(manifests.load_module(cell["generator_path"]).make)
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer metric
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell["per_layer"]
    # what the operation measures is what the manifest asks of the cell
    assert set(operation.end_to_end([1.0, 2.0], 3.0)) | {"setup_s"} >= e2e


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        manifests.resolve(MANIFEST, "no-such.cell")


@pytest.mark.parametrize("config_name", CONFIGS)
def test_configuration_file_says_what_the_manifest_says(config_name):
    entry = {c["name"]: c for c in MANIFEST["configs"]}[config_name]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert any(entry["file"].startswith(p + "/") for p in MANIFEST["paths"])
    assert 1 <= len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert any(w["config"] == config_name for w in MANIFEST["workloads"])
    config = manifests.load_json(os.path.join(REPO, entry["file"]))
    assert config["name"] == config_name
    assert len(config["source"]) <= 200
    # every reduced key is a key of the file, with its reason there
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    run_keys = set(config["generator"]["params"]) | set(
        config["training_config"])
    for key in entry["reduced"]:
        assert NAME.match(key) and key in run_keys
        assert not re.search(r"(_dim|_rank)$", key)
    assert 0.5 < config["auc_floor"] < 1.0
    assert config["auc_floor_derivation"]
    assert sorted(config["gradient_rtol"]) == sorted(
        c["name"] for c in config["training_config"]["coordinates"])
    assert all(0 < v < 0.1 for v in config["gradient_rtol"].values())
    assert abs(config["objective_gap"]) < 0.1
    assert config["objective_gap_derivation"]
    assert config["gradient_rtol_derivation"]
    assert set(config["generator"]["params"]) >= set(
        config["rehearsal_params"])


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


def test_result_line_has_exactly_the_contract_keys():
    line = result_line(
        correct=True, attempted=2, failed=0,
        metrics={"fit_s": 31.25, "setup_s": 60.5},
        units={"fit_s": "s", "setup_s": "s"}, device=dict(DEVICE))
    assert "\n" not in line
    record = json.loads(line)
    assert set(record) == {"correct", "attempted", "failed", "metrics",
                           "device"}
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
        device=device, breakdown=breakdown))
    assert set(record) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
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


# -- (3) generators, and the plain reference on a tiny fit -------------------

def _tiny(config_name):
    """(config as the file has it but tiny, generator module)."""
    entry = {c["name"]: c for c in MANIFEST["configs"]}[config_name]
    config = manifests.load_json(os.path.join(REPO, entry["file"]))
    generator = config["generator"]
    generator["params"].update(config["rehearsal_params"])
    # a tiny problem learns less than the cell's: the floor here only
    # has to tell a model from a coin
    config["auc_floor"] = 0.55
    # and is fitted looser: the cell's own limits are for its own size
    config["objective_gap"] = 0.5
    config["gradient_rtol"] = {c["name"]: 0.5 for c in
                               config["training_config"]["coordinates"]}
    return config, manifests.load_module(os.path.join(
        REPO, "benchmark", "generators", generator["name"] + ".py"))


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
    config, generator = _tiny(config_name)
    params = config["generator"]["params"]
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
    shard = a[0].features["global"]
    np.testing.assert_array_equal(shard.cols, c[0].features["global"].cols)
    for key in a[0].entity_ids:
        np.testing.assert_array_equal(a[0].entity_ids[key],
                                      c[0].entity_ids[key])
    assert a[0].n + a[1].n == params["n"]
    assert a[1].n == int(params["n"] * params["valid_fraction"])


@pytest.fixture(scope="module", params=CONFIGS)
def tiny_fit(request):
    config, generator = _tiny(request.param)
    traffic = manifests.load_json(os.path.join(
        REPO, "benchmark", "traffic", "fit-cold.json"))
    operation = manifests.load_module(os.path.join(
        REPO, "benchmark", "operations", traffic["operation"] + ".py"))
    data = generator.make(3, **config["generator"]["params"])
    state = operation.prepare(config, traffic, data)
    return operation, state, operation.one(state)


def test_tiny_fit_passes_the_plain_reference(tiny_fit):
    operation, state, outcome = tiny_fit
    check = operation.reference_check(state, outcome)
    assert check["correct"], check
    assert abs(check["plain_auc"] - outcome["auc"]) < 1e-5
    assert operation.ok(outcome, outcome)
    assert not operation.ok({"auc": float("nan")}, outcome)
    assert not operation.ok({"auc": outcome["auc"] + 0.01}, outcome)
    assert operation.end_to_end([3.0, 1.0, 2.0], 6.0) == {"fit_s": 2.0}
    assert state["training_config"].plan_cache_dir is None


def test_a_model_with_one_block_zeroed_fails_the_reference(tiny_fit):
    import jax.numpy as jnp

    operation, state, outcome = tiny_fit
    model = copy.copy(outcome["model"])
    model.models = dict(model.models)
    name = state["training_config"].coordinates[-1].name
    part = model.models[name]
    if hasattr(part, "coefficient_blocks"):
        damaged = dataclasses.replace(part, coefficient_blocks=[
            jnp.zeros_like(b) for b in part.coefficient_blocks])
    else:
        damaged = dataclasses.replace(
            part, coefficients=dataclasses.replace(
                part.coefficients,
                means=jnp.zeros_like(part.coefficients.means)))
    model.models[name] = damaged
    check = operation.reference_check(
        state, {"model": model, "auc": outcome["auc"]})
    assert not check["correct"], check
    assert not check["auc_agrees"]


def test_a_solve_cut_short_fails_the_reference(tiny_fit):
    """Left-out work shows: with the limits set just above what the
    whole solve reaches, the same solve stopped after two iterations is
    not ``correct`` (its scoring still agrees)."""
    operation, state, outcome = tiny_fit
    whole = operation.reference_check(state, outcome)
    config = copy.deepcopy(state["config"])
    config["objective_gap"] = whole["objective_gap"] + 1e-3
    config["gradient_rtol"] = {name: 2 * value for name, value
                               in whole["gradient_rel"].items()}
    for coordinate in config["training_config"]["coordinates"]:
        coordinate["optimizer"]["max_iters"] = 2
    traffic = manifests.load_json(os.path.join(
        REPO, "benchmark", "traffic", "fit-cold.json"))
    short_state = operation.prepare(
        config, traffic, (state["train"], state["valid"], state["truth"]))
    check = operation.reference_check(short_state,
                                      operation.one(short_state))
    assert check["auc_agrees"], check
    assert not check["objective_reached"] or not check["gradient_small"]
    assert not check["correct"]
    tight = dict(state, config=dict(config))
    assert operation.reference_check(tight, outcome)["correct"]
