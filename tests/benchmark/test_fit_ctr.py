"""The cell ``ls-tron-kdd12.fit-cold-ctr``'s own files, on the CPU at
rehearsal size: the generator ``kdd12_ctr``, the plain reference
``reference/least_squares.py``, the operation ``fit_ctr`` (its limits'
soundness, its three controls, each failing by the condition it is
named for) and the reader ``tron_hvp_passes`` on a hand-written trace.
``test_harness.py`` resolves, schema-checks and rehearses the cell
itself (whole, damaged, cut short).  Nothing timed here is a
performance number."""

import copy
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import host_spans  # noqa: E402
from benchmark.harness import manifest as manifests  # noqa: E402
from benchmark.harness import trace_reduce  # noqa: E402
from benchmark.reference import least_squares, plain  # noqa: E402

MANIFEST = manifests.load_manifest()
CELL = "ls-tron-kdd12.fit-cold-ctr"
COUNTS_CELL = manifests.resolve(MANIFEST,
                                "poisson-enet-kdd12.fit-cold-exposure")


@pytest.fixture(scope="module")
def cell():
    return manifests.resolve(MANIFEST, CELL)


@pytest.fixture(scope="module")
def operation(cell):
    return manifests.load_module(cell["operation_path"])


def _rehearsal_params(resolved):
    config = resolved["config"]
    return dict(config["generator"]["params"], **config["rehearsal_params"])


# -- the generator -------------------------------------------------------------

@pytest.fixture(scope="module")
def generated(cell):
    generator = manifests.load_module(cell["generator_path"])
    return generator.make(2**31 + 5, **_rehearsal_params(cell))


def test_rows_keys_and_impressions_are_the_counts_cell_s(cell, generated):
    """``kdd12_counts``' pattern and impressions from the same constant
    in the same order: the rows, columns, users, items and impressions
    ``poisson-enet-kdd12`` runs on (its exposures, here the weights)."""
    counts = manifests.load_module(COUNTS_CELL["generator_path"]).make(
        2**31 + 5, **_rehearsal_params(COUNTS_CELL))
    for mine, theirs in zip(generated[:2], counts[:2]):
        for shard in ("global", "item_re"):
            a, b = mine.features[shard], theirs.features[shard]
            if isinstance(a, np.ndarray):
                np.testing.assert_array_equal(a, b)
            else:
                for part in ("indptr", "cols", "vals"):
                    np.testing.assert_array_equal(getattr(a, part),
                                                  getattr(b, part))
        for key in ("userId", "itemId"):
            np.testing.assert_array_equal(mine.entity_ids[key],
                                          theirs.entity_ids[key])
        assert mine.offsets is None
    for part in ("train", "valid"):
        np.testing.assert_array_equal(generated[2][part + "_weights"],
                                      counts[2][part + "_exposure"])
    assert cell["config"]["generator"]["params"]["fields"] \
        == COUNTS_CELL["config"]["generator"]["params"]["fields"]
    assert cell["config"]["rehearsal_params"] \
        == COUNTS_CELL["config"]["rehearsal_params"]


def test_labels_are_rates_and_weights_the_impressions(generated):
    train, valid, truth = generated
    for data, impressions in ((train, truth["train_weights"]),
                              (valid, truth["valid_weights"])):
        assert data.weights.dtype == data.labels.dtype == np.float32
        np.testing.assert_array_equal(data.weights, impressions)
        assert impressions.min() >= 1
        assert np.all(impressions == np.round(impressions))
        clicks = data.labels.astype(np.float64) * impressions
        np.testing.assert_allclose(clicks, np.round(clicks), atol=1e-3)
        assert 0.0 <= data.labels.min() and data.labels.max() <= 1.0
    assert 1.3 < truth["train_weights"].mean() < 1.9
    assert truth["train_weights"].max() > 10      # heavy-tailed
    rates = np.concatenate([truth["train_margins"], truth["valid_margins"]])
    assert rates.min() >= 0.001 and rates.max() <= 0.5


def test_clicks_are_the_stated_share_of_the_impressions(cell):
    """The expected clicks are ``click_share`` of the impressions by
    construction (the bias is found by halving); the drawn ones come
    within their binomial noise."""
    generator = manifests.load_module(cell["generator_path"])
    params = _rehearsal_params(cell)
    shares = []
    for seed in (1, 2, 3, 4, 5, 6):
        train, valid, truth = generator.make(seed, **params)
        impressions = np.concatenate([truth["train_weights"],
                                      truth["valid_weights"]])
        rates = np.concatenate([truth["train_margins"],
                                truth["valid_margins"]])
        assert abs(np.dot(impressions, rates) / impressions.sum()
                   - params["click_share"]) < 1e-9
        clicks = (np.dot(train.labels, train.weights)
                  + np.dot(valid.labels, valid.weights))
        shares.append(clicks / impressions.sum())
    assert abs(np.mean(shares) - params["click_share"]) < 0.005


def test_the_truth_is_the_constant_s_and_the_clicks_the_seed_s(cell):
    generator = manifests.load_module(cell["generator_path"])
    params = _rehearsal_params(cell)
    a = generator.make(1, **params)
    b = generator.make(2, **params)
    for key in ("train_margins", "valid_margins", "train_weights"):
        np.testing.assert_array_equal(a[2][key], b[2][key])
    assert not np.array_equal(a[0].labels, b[0].labels)
    assert not np.array_equal(a[0].features["user_re"],
                              b[0].features["user_re"])


def test_the_bias_halving_finds_the_share():
    generator = manifests.load_module(os.path.join(
        REPO, "benchmark", "generators", "kdd12_ctr.py"))
    rng = np.random.default_rng(0)
    score = rng.normal(0, 0.05, 1000)
    impressions = rng.integers(1, 9, 1000).astype(float)
    b = generator.clicked_share_bias(score, impressions, 0.0349, 0.001, 0.5)
    rates = np.clip(b + score, 0.001, 0.5)
    assert abs(np.dot(impressions, rates) / impressions.sum() - 0.0349) \
        < 1e-12
    assert rates.min() == 0.001              # the clip is met and kept


def test_the_generator_refuses_a_program_without_cg_settings(cell,
                                                             monkeypatch):
    """Before any data is made, as the cell must on the parent commit."""
    from photon_ml_tpu import config as program_config

    @dataclasses.dataclass
    class OldSettings:
        max_iters: int = 100

    monkeypatch.setattr(program_config, "OptimizerSettings", OldSettings)
    generator = manifests.load_module(cell["generator_path"])
    with pytest.raises(RuntimeError, match="cannot cap TRON's inner loop"):
        generator.make(1, **dict(_rehearsal_params(cell), n=10**12))


# -- the plain reference ---------------------------------------------------------

def test_the_blocked_contractions_are_the_plain_ones(generated, monkeypatch):
    train = generated[0]
    rows = train.features["global"]
    rng = np.random.default_rng(1)
    d = 199584
    w = rng.normal(size=d)
    r = rng.normal(size=train.n)
    monkeypatch.setattr(least_squares, "BLOCK_ROWS", 1000)  # several blocks
    np.testing.assert_allclose(
        least_squares.csr_dot(rows.indptr, rows.cols, rows.vals, w),
        plain.csr_dot(rows.indptr, rows.cols, rows.vals, w), rtol=1e-12,
        atol=1e-12)
    np.testing.assert_allclose(
        least_squares.csr_t_dot(rows.indptr, rows.cols, rows.vals, r, d),
        plain.csr_t_dot(rows.indptr, rows.cols, rows.vals, r, d),
        rtol=1e-12, atol=1e-12)


def test_the_reference_gradient_is_its_objective_s(generated):
    """Central differences of the reference's own objective, weights
    in, on a few coordinates: its gradient belongs to the objective it
    states, the intercept's entry unpenalised."""
    train, _valid, truth = generated
    rows = train.features["global"]
    rng = np.random.default_rng(0)
    d = 199584
    w = np.zeros(d + 1)
    touched = np.unique(rows.cols)[:400]
    w[touched] = rng.normal(0, 0.01, len(touched))
    w[-1] = 0.03
    seen = rng.normal(0, 0.01, train.n)
    labels = train.labels.astype(np.float64)
    weights = truth["train_weights"]
    lam = 7.0

    def objective(v):
        z = seen + least_squares.fixed_scores((rows.indptr, rows.cols,
                                               rows.vals, v, lam))
        return (least_squares.weighted_loss(z, labels, weights)
                + 0.5 * lam * np.sum(v[:-1] ** 2))

    block = (rows.indptr, rows.cols, rows.vals, w, lam)
    own = least_squares.fixed_scores(block)
    g = least_squares.fixed_effect_gradient(block, seen + own, labels,
                                            weights)
    for j in list(touched[:3]) + [d]:
        step = np.zeros_like(w)
        step[j] = 1e-4
        numeric = (objective(w + step) - objective(w - step)) / 2e-4
        assert abs(numeric - g[j]) < 1e-6 * max(1.0, abs(g[j]))
    value, norm, at_zero = least_squares.fixed_effect_end(
        block, own, seen, labels, weights)
    assert abs(value - objective(w)) < 1e-9 * abs(value)
    assert norm == pytest.approx(np.linalg.norm(g))
    assert at_zero > norm


# -- the operation's limits ------------------------------------------------------

def _config_with(cell, **changes):
    config = copy.deepcopy(cell["config"])
    for key, value in changes.items():
        config[key] = value
    return config


@pytest.mark.parametrize("changes,word", [
    ({"rmse_gain_floor": None}, "rmse_gain_floor"),
    ({"rmse_gain_floor": -0.01}, "rmse_gain_floor"),
    ({"objective_gap": 0.01}, "objective_gap"),
    ({"gradient_rtol": {"global": 0.1}}, "one limit a coordinate"),
    ({"gradient_rtol": {"global": 0.6, "per_user": 0.01,
                        "per_item": 0.01}}, "gradient_rtol"),
    ({"fixed_effect_rtol": {"scores": 1e-3}}, "2**-11"),
    ({"fixed_effect_rtol": {"value": 1e-6}}, "nothing else"),
    ({"objective_gap_derivation": ""}, "objective_gap"),
], ids=lambda value: None if isinstance(value, dict) else value)
def test_unsound_limits_are_named(cell, operation, changes, word):
    assert operation.limit_problems(cell["config"]) == []
    problems = operation.limit_problems(_config_with(cell, **changes))
    assert problems and any(word in problem for problem in problems)


def test_every_coordinate_is_a_capped_tron_solve(cell):
    """The configuration is what the cell stands for: the squared loss,
    RMSE, and TRON with its inner cap stated on every coordinate."""
    from photon_ml_tpu.config import training_config_from_json

    config = cell["config"]
    fields = config["training_config"]
    assert fields["task_type"] == "LINEAR_REGRESSION"
    assert fields["evaluators"] == ["RMSE"]
    program = training_config_from_json(json.dumps(fields))
    for stated, coordinate in zip(fields["coordinates"],
                                  program.coordinates):
        assert coordinate.optimizer.optimizer.value == "TRON"
        assert coordinate.optimizer.cg_max_iters \
            == stated["optimizer"]["cg_max_iters"] < 50
        assert coordinate.optimizer.max_iters \
            == stated["optimizer"]["max_iters"]
    assert "coordinates" in config["reduced"]     # the caps, with reasons
    assert cell["traffic"]["operation"] == "fit_ctr"
    assert cell["traffic"]["plan_cache"] is False
    assert config["fixed_effect_rtol"]["scores"] < 2.0 ** -11


# -- the fit and its three controls -------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(cell, operation):
    config = operation.rehearsal_config(cell["config"])
    data = manifests.load_module(cell["generator_path"]).make(
        5, **config["generator"]["params"])
    state = operation.prepare(config, cell["traffic"], data)
    outcome = operation.one(state)
    return state, outcome, operation.reference_check(state, outcome)


def test_the_sound_fit_is_correct_and_says_what_it_paid(operation,
                                                        rehearsal):
    _state, outcome, check = rehearsal
    assert check["correct"], check["compared"]
    assert set(check["conditions"]) == {
        "rmse_agrees", "objective_reached", "optimal", "beats_baseline",
        "fixed_effect_exact"}
    said = operation.summary(outcome)
    assert said["hvp_passes"] == said["cg_steps"] \
        + said["solver_iterations"] > 0
    # the squared loss's products ask for X.w and X.v, as any loss's
    assert said["forward_passes"] == 1 + said["solver_iterations"] \
        + 2 * said["hvp_passes"]
    assert check["compared"]["fixed_effect.scores"]["value"] < 1e-6


@pytest.mark.parametrize("name,failing", [
    ("bfloat16", ["fixed_effect_exact"]),
    ("weights_dropped", ["optimal"]),
    ("one_newton_step", ["optimal"]),
])
def test_a_control_is_not_correct_by_the_condition_it_is_named_for(
        operation, rehearsal, name, failing):
    state, _outcome, sound = rehearsal
    assert name in operation.CONTROLS
    with operation.control(name, state) as controlled:
        outcome = operation.one(controlled)
        check = operation.reference_check(controlled, outcome)
    assert not check["correct"]
    assert not any(check["conditions"][c] for c in failing), check
    assert check["conditions"]["rmse_agrees"]
    if name == "weights_dropped":       # by a wide margin
        assert check["gradient_rel"]["per_user"] \
            > 100 * sound["gradient_rel"]["per_user"]
    if name == "one_newton_step":
        assert operation.summary(outcome)["solver_iterations"] == 1
    if name == "bfloat16":
        assert check["compared"]["fixed_effect.scores"]["value"] > 1e-5


def test_an_unknown_control_is_an_error(operation, rehearsal):
    with pytest.raises(KeyError, match="no control"):
        with operation.control("halved", rehearsal[0]):
            pass


# -- the reader ------------------------------------------------------------------

# the fixed effect's TRON solve: 5 outer iterations, 33 CG steps, 38
# products, 1 + 5 + 2 x 38 forward contractions
TRON = {"solver_iterations": 5, "cg_steps": 33, "hvp_passes": 38,
        "forward_passes": 82}
RANDOM = {"buckets": 4, "chunks": 6, "lane_cg_steps": 900,
          "lane_hvp_passes": 1000, "lane_forward_passes": 2100}
# (coordinate, counts, start_ns, duration_ns)
TRAINS = [("global", TRON, 30000, 40000), ("per_user", RANDOM, 72000, 3000),
          ("per_item", RANDOM, 76000, 500)]


def _xspace(trains):
    keys = sorted({key for _c, counts, _s, _d in trains for key in counts})
    stat_ids = {key: i + 2 for i, key in enumerate(keys)}

    def stats(coordinate, counts):
        return f"stats {{ metadata_id: 1 str_value: '{coordinate}' }} " \
            + " ".join(f"stats {{ metadata_id: {stat_ids[key]} "
                       f"int64_value: {value} }}"
                       for key, value in counts.items())

    events = "\n".join(
        f"events {{ metadata_id: 3 offset_ps: {start * 1000} "
        f"duration_ps: {duration * 1000} {stats(coordinate, counts)} }}"
        for coordinate, counts, start, duration in trains)
    stat_metadata = "\n".join(
        f'stat_metadata {{ key: {i} value {{ id: {i} name: "{key}" }} }}'
        for key, i in stat_ids.items())
    return f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 31000000 duration_ps: 10000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[8] fusion(%a), kind=kLoop" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 5 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }}
    events {{ metadata_id: 2 offset_ps: 1000000 duration_ps: 98000000 }}
    {events} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fit_ctr" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "photon/estimator_fit" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "photon/coord_train" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "coordinate" }} }}
  {stat_metadata}
}}
'''


def _traced(tmp_path, monkeypatch, trains):
    from jax.profiler import ProfileData

    trace_dir = tmp_path / "trace"
    path = trace_dir / "cell-1" / "plugins" / "profile" / "t" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        _xspace(trains)))
    monkeypatch.setattr(host_spans, "TRACE_DIR", str(trace_dir))
    host_spans.read_host_lines.cache_clear()
    trace = trace_reduce.summarize(trace_reduce.read_xplane(str(path)),
                                   "fit_ctr", chips=1, k=10)
    return {"trace": trace, "chips": 1}


def _read(name, ctx):
    return manifests.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py")).read(ctx)


def test_the_reader_reads_the_fixed_effect_s_products(tmp_path, monkeypatch):
    """The random effects' lanes are summed under their own names and
    are not the fixed effect's; ``fe_forward_passes`` and ``fe_solve_s``
    read the same stage."""
    ctx = _traced(tmp_path, monkeypatch, TRAINS)
    assert _read("tron_hvp_passes", ctx) == 38.0
    assert _read("fe_forward_passes", ctx) == 82.0
    assert _read("fe_solve_s", ctx) == pytest.approx(40e-6)
    # a second sweep's solve adds its own
    again = TRAINS + [("global", TRON, 80000, 9000)]
    ctx = _traced(tmp_path / "two", monkeypatch, again)
    assert _read("tron_hvp_passes", ctx) == 76.0


def test_a_quasi_newton_solve_has_no_products(tmp_path, monkeypatch):
    """The other cells' fixed effect, and the parent's TRON: the stage
    carries iterations (and forward passes) and no products."""
    along = {"solver_iterations": 30, "forward_passes": 31}
    ctx = _traced(tmp_path, monkeypatch,
                  [("global", along, 30000, 20000)]
                  + [(c, {"buckets": 4, "chunks": 6}, s, d)
                     for c, _counts, s, d in TRAINS[1:]])
    assert _read("tron_hvp_passes", ctx) is None


def test_nothing_to_read_without_a_trace():
    assert _read("tron_hvp_passes", {}) is None
    assert _read("tron_hvp_passes", {"trace": None, "chips": 1}) is None


def test_the_manifest_gives_the_metric_to_the_new_cell_alone():
    (metric,) = [m for m in MANIFEST["per_layer"]
                 if m["name"] == "tron_hvp_passes"]
    assert metric == {"name": "tron_hvp_passes", "unit": "count",
                      "better": "lower", "source": "program_counter",
                      "layer": "objective + solvers", "moves": "fit_s",
                      "workloads": [CELL]}
    assert "tron_hvp_passes" in manifests.resolve(
        MANIFEST, CELL)["layer_metric_paths"]
