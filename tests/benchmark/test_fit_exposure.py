"""What ISSUE 37 added to the benchmark, on the CPU at rehearsal size:
the generator ``kdd12_counts``, the plain reference
``reference/poisson_enet.py``, the operation ``fit_exposure`` (its
limits' soundness, its four controls, each failing by the condition it
is named for) and the three readers ``fe_solve_s``,
``owlqn_forward_passes`` and ``owlqn_ls_trials`` on a hand-written
trace.  ``test_harness.py`` resolves, schema-checks and rehearses the
cell itself (whole, damaged, cut short).  Nothing timed here is a
performance number."""

import copy
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
from benchmark.reference import plain, poisson_enet  # noqa: E402

MANIFEST = manifests.load_manifest()
CELL = "poisson-enet-kdd12.fit-cold-exposure"
WIDE = manifests.resolve(MANIFEST, "game5-kdd12.fit-cold")


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


def test_rows_and_keys_are_the_wide_cell_s(cell, generated):
    """``kdd12_fields``' pattern from the same constant in the same
    order: the rows, columns, users and items ``game5-kdd12`` runs on."""
    theirs = manifests.load_module(WIDE["generator_path"]).make(
        2**31 + 5, **_rehearsal_params(WIDE))
    for mine, wide in zip(generated[:2], theirs[:2]):
        for shard in ("global", "item_re"):
            a, b = mine.features[shard], wide.features[shard]
            if isinstance(a, np.ndarray):
                np.testing.assert_array_equal(a, b)
            else:
                for part in ("indptr", "cols", "vals"):
                    np.testing.assert_array_equal(getattr(a, part),
                                                  getattr(b, part))
        for key in ("userId", "itemId"):
            np.testing.assert_array_equal(mine.entity_ids[key],
                                          wide.entity_ids[key])
        assert wide.offsets is None and mine.offsets is not None
    assert cell["config"]["generator"]["params"]["fields"] \
        == WIDE["config"]["generator"]["params"]["fields"]
    assert cell["config"]["rehearsal_params"] \
        == WIDE["config"]["rehearsal_params"]


def test_impressions_are_at_least_one_and_the_offsets_their_log(generated):
    train, valid, truth = generated
    for data, exposure in ((train, truth["train_exposure"]),
                           (valid, truth["valid_exposure"])):
        assert exposure.min() >= 1
        assert np.all(exposure == np.round(exposure))
        np.testing.assert_allclose(data.offsets, np.log(exposure),
                                   rtol=1e-6)
        assert data.offsets.dtype == np.float32
        assert data.weights is None
        assert data.labels.dtype == np.float32
        assert np.all(data.labels == np.round(data.labels))
    assert 1.3 < truth["train_exposure"].mean() < 1.9
    assert truth["train_exposure"].max() > 10      # heavy-tailed
    assert train.labels.max() > 1          # counts, not a binary label


def test_clicks_are_the_stated_share_of_the_impressions(cell):
    """The expected clicks are ``click_share`` of the impressions by
    construction; the drawn ones come within their Poisson noise (6,000
    rows: a few hundred clicks)."""
    generator = manifests.load_module(cell["generator_path"])
    params = _rehearsal_params(cell)
    shares = []
    for seed in (1, 2, 3, 4, 5, 6, 7, 8):
        train, valid, truth = generator.make(seed, **params)
        exposure = np.concatenate([truth["train_exposure"],
                                   truth["valid_exposure"]])
        rate = np.exp(np.concatenate([truth["train_margins"],
                                      truth["valid_margins"]]))
        assert abs((exposure * rate).sum() / exposure.sum()
                   - params["click_share"]) < 1e-9
        shares.append((train.labels.sum() + valid.labels.sum())
                      / exposure.sum())
    assert abs(np.mean(shares) - params["click_share"]) < 0.01


def test_the_truth_is_the_constant_s_and_the_counts_the_seed_s(cell):
    generator = manifests.load_module(cell["generator_path"])
    params = _rehearsal_params(cell)
    a = generator.make(1, **params)
    b = generator.make(2, **params)
    for key in ("train_margins", "valid_margins", "train_exposure"):
        np.testing.assert_array_equal(a[2][key], b[2][key])
    assert not np.array_equal(a[0].labels, b[0].labels)
    assert not np.array_equal(a[0].features["user_re"],
                              b[0].features["user_re"])


def test_the_generator_refuses_a_training_that_drops_offsets(cell,
                                                            monkeypatch):
    """Before any data is made, as the cell must on the parent commit."""
    from photon_ml_tpu.game import coordinate_descent

    def old_signature(coordinates, update_sequence, n_iterations):
        raise AssertionError("never called")

    monkeypatch.setattr(coordinate_descent, "run_coordinate_descent",
                        old_signature)
    generator = manifests.load_module(cell["generator_path"])
    with pytest.raises(RuntimeError, match="drops a dataset's offsets"):
        generator.make(1, **dict(_rehearsal_params(cell), n=10**12))


# -- the plain reference ---------------------------------------------------------

def test_kkt_residual_by_hand():
    g = np.array([0.2, -0.2, 0.9, -0.9, 0.3, -0.3, 2.0])
    w = np.array([0.0, 0.0, 0.0, 0.0, 1.5, -1.5, 0.0])
    l1 = np.array([0.5, 0.5, 0.5, 0.5, 0.5, 0.5, 0.0])
    np.testing.assert_allclose(
        poisson_enet.kkt_residual(g, w, l1),
        [0.0, 0.0, 0.4, -0.4, 0.8, -0.8, 2.0])


def test_the_reference_gradient_is_its_objective_s(generated):
    """Central differences of the reference's own objective, exposure
    in, on a few coordinates: its gradient, and so its KKT residual,
    belongs to the objective it states."""
    train, _valid, truth = generated
    rows = train.features["global"]
    rng = np.random.default_rng(0)
    d = 199584
    w = np.zeros(d + 1)
    touched = np.unique(rows.cols)[:400]
    w[touched] = rng.normal(0, 0.3, len(touched))
    w[-1] = -2.0
    seen = np.log(truth["train_exposure"])
    labels = train.labels.astype(np.float64)
    l1, l2 = poisson_enet.split_weights(1.0, 0.5)

    def smooth(v):
        z = seen + plain.margins((rows.indptr, rows.cols, rows.vals, v), [])
        return (np.sum(np.exp(z) - labels * z)
                + 0.5 * l2 * np.sum(v[:-1] ** 2))

    block = (rows.indptr, rows.cols, rows.vals, w, 1.0)
    z = seen + plain.margins(block[:4], [])
    g = poisson_enet.fixed_effect_gradient(block, z, labels, l2)
    for j in list(touched[:3]) + [d]:
        step = np.zeros_like(w)
        step[j] = 1e-5
        numeric = (smooth(w + step) - smooth(w - step)) / 2e-5
        assert abs(numeric - g[j]) < 1e-5 * max(1.0, abs(g[j]))
    end = poisson_enet.fixed_effect_end(block, z - seen, seen, labels, 0.5)
    assert abs(end["value"] - (smooth(w) + l1 * np.sum(np.abs(w[:-1])))) \
        < 1e-9 * abs(end["value"])
    assert not end["inside"][-1]           # the intercept is unpenalised


# -- the operation's limits ------------------------------------------------------

def _config_with(cell, **changes):
    config = copy.deepcopy(cell["config"])
    for key, value in changes.items():
        config[key] = value
    return config


@pytest.mark.parametrize("changes,word", [
    ({"loss_gain_floor": None}, "loss_gain_floor"),
    ({"loss_gain_floor": -0.01}, "loss_gain_floor"),
    ({"objective_gap": 0.2}, "objective_gap"),
    ({"optimality_rtol": {"global": 0.1}}, "one limit a coordinate"),
    ({"optimality_rtol": {"global": 0.6, "per_user": 0.01,
                          "per_item": 0.01}}, "optimality_rtol"),
    ({"fixed_effect_rtol": {"scores": 1e-3}}, "2**-11"),
    ({"fixed_effect_rtol": {"value": 1e-6}}, "nothing else"),
    ({"zero_rtol": 0.2}, "zero_rtol"),
    ({"zero_rtol_derivation": ""}, "zero_rtol"),
], ids=lambda value: None if isinstance(value, dict) else value)
def test_unsound_limits_are_named(cell, operation, changes, word):
    assert operation.limit_problems(cell["config"]) == []
    problems = operation.limit_problems(_config_with(cell, **changes))
    assert problems and any(word in problem for problem in problems)


def test_the_cell_s_limits_stand_in_the_order_the_operation_needs(cell):
    """The gain is a few hundredths of Poisson loss a row, the scores'
    limit of precision the wide cell's."""
    config = cell["config"]
    assert 0 < config["loss_gain_floor"] < 0.05
    assert config["fixed_effect_rtol"]["scores"] \
        == WIDE["config"]["fixed_effect_rtol"]["scores"]
    assert set(config["fixed_effect_rtol"]) == {"scores", "gradient_norm"}
    assert config["training_config"]["task_type"] == "POISSON_REGRESSION"
    assert config["training_config"]["evaluators"] == ["POISSON_LOSS"]
    assert cell["traffic"]["operation"] == "fit_exposure"
    assert cell["traffic"]["plan_cache"] is False


# -- the four controls -----------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(cell, operation):
    config = operation.rehearsal_config(cell["config"])
    data = manifests.load_module(cell["generator_path"]).make(
        5, **config["generator"]["params"])
    state = operation.prepare(config, cell["traffic"], data)
    outcome = operation.one(state)
    return state, outcome, operation.reference_check(state, outcome)


def test_the_sound_fit_is_correct_and_says_what_it_read(rehearsal):
    _state, outcome, check = rehearsal
    assert check["correct"], check["compared"]
    assert set(check["conditions"]) == {
        "loss_agrees", "objective_reached", "optimal_with_exposure",
        "beats_baseline", "fixed_effect_exact", "zeros_exact"}
    assert check["zeros"]["counted"] == check["zeros"]["exported"] > 0
    assert check["compared"]["fixed_effect.scores"]["value"] < 4e-6
    assert outcome["descent"]["last"]["global"]["solver_iterations"] == 30


@pytest.mark.parametrize("name,failing", [
    ("offsets_dropped", ["optimal_with_exposure"]),
    ("l1_dropped", ["zeros_exact"]),
    ("bfloat16", ["fixed_effect_exact"]),
    ("two_iterations", ["optimal_with_exposure"]),
])
def test_a_control_is_not_correct_by_the_condition_it_is_named_for(
        operation, rehearsal, name, failing):
    """``l1_dropped`` is caught by its zeros (and, at the cell's own
    limit, by the objective's gap: the configuration's
    ``objective_gap_derivation``); the fixed effect's residual does not
    see it, at either size."""
    state, _outcome, sound = rehearsal
    assert name in operation.CONTROLS
    with operation.control(name, state) as controlled:
        check = operation.reference_check(controlled,
                                          operation.one(controlled))
    assert not check["correct"]
    assert not any(check["conditions"][c] for c in failing), check
    if name == "offsets_dropped":       # by a wide margin
        assert check["optimality_rel"]["global"] \
            > 20 * sound["optimality_rel"]["global"]
        assert check["conditions"]["loss_agrees"]
    if name == "l1_dropped":
        assert check["zeros"]["counted"] is None
        assert check["zeros"]["not_zero"] > 10 * sound["zeros"]["not_zero"]
    if name == "bfloat16":
        assert check["compared"]["fixed_effect.scores"]["value"] > 2.0 ** -11


def test_an_unknown_control_is_an_error(operation, rehearsal):
    with pytest.raises(KeyError, match="no control"):
        with operation.control("halved", rehearsal[0]):
            pass


# -- the three readers -----------------------------------------------------------

# the fixed effect's OWL-QN solve: 30 iterations, 88 trials, 1 + 88 + 30
OWLQN = {"solver_iterations": 30, "ls_trials": 88, "forward_passes": 119,
         "nonzero_coefficients": 41234}
RANDOM = {"buckets": 4, "chunks": 6}
# (coordinate, counts, start_ns, duration_ns)
TRAINS = [("global", OWLQN, 30000, 55000), ("per_user", RANDOM, 87000, 3000),
          ("per_item", RANDOM, 92000, 500)]


def _xspace(trains):
    keys = sorted(set(OWLQN) | set(RANDOM))
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
  event_metadata {{ key: 1 value {{ id: 1 name: "fit_exposure" }} }}
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
                                   "fit_exposure", chips=1, k=10)
    return {"trace": trace, "chips": 1}


def _read(name, ctx):
    return manifests.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py")).read(ctx)


READERS = ("fe_solve_s", "owlqn_forward_passes", "owlqn_ls_trials")


def test_the_readers_read_the_l1_coordinate_s_stage(tmp_path, monkeypatch):
    ctx = _traced(tmp_path, monkeypatch, TRAINS)
    assert _read("fe_solve_s", ctx) == pytest.approx(55e-6)
    assert _read("owlqn_forward_passes", ctx) == 119.0
    assert _read("owlqn_ls_trials", ctx) == 88.0
    # a second sweep's solve adds its own
    again = TRAINS + [("global", OWLQN, 94000, 4000)]
    ctx = _traced(tmp_path / "two", monkeypatch, again)
    assert _read("fe_solve_s", ctx) == pytest.approx(59e-6)
    assert _read("owlqn_forward_passes", ctx) == 238.0
    assert _read("owlqn_ls_trials", ctx) == 176.0


def test_a_solve_along_the_margins_is_no_owlqn_solve(tmp_path, monkeypatch):
    """The older cells' fixed effect (and the parent's stages): its
    ``coord_train`` carries iterations and forward passes and no
    ``nonzero_coefficients``: its seconds are read, the two OWL-QN
    counts find nothing and say nothing."""
    along = {"solver_iterations": 30, "forward_passes": 31}
    ctx = _traced(tmp_path, monkeypatch,
                  [("global", along, 30000, 20000)] + TRAINS[1:])
    assert _read("fe_solve_s", ctx) == pytest.approx(20e-6)
    assert _read("owlqn_forward_passes", ctx) is None
    assert _read("owlqn_ls_trials", ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_without_a_trace(name):
    assert _read(name, {}) is None
    assert _read(name, {"trace": None, "chips": 1}) is None


@pytest.mark.parametrize("name", READERS)
def test_the_manifest_gives_the_metric_to_the_new_cell_alone(name):
    (metric,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert metric["workloads"] == [CELL] and metric["moves"] == "fit_s"
    assert name in manifests.resolve(MANIFEST, CELL)["layer_metric_paths"]
    assert metric["source"] == ("program_span" if name == "fe_solve_s"
                                else "program_counter")
