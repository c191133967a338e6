"""Out-of-core random-effect training (ISSUE 5): streamed entity-bucket
solves must match the resident path to float tolerance on coefficients,
scores, and variances for every bucket mix × chunk grid; the chunk
store's LRU window must bound host residency; spilled chunks must be a
warm artifact across builds and survive corruption via lineage rebuild;
converged-entity retirement must shrink per-sweep work monotonically on
a converging fit without moving the final model beyond solver
tolerance; and the entity-sharded mesh variant must stream per-shard.
"""

import glob
import os

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.normalization import NormalizationContext
from photon_ml_tpu.game.coordinates import (
    build_random_effect_coordinate,
    build_random_effect_coordinate_sparse,
    build_streamed_random_effect_coordinate,
)
from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optim import OptimizerConfig


def _objective(l2=0.5):
    return GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l2(l2),
        norm=NormalizationContext.identity(),
    )


def _ids(rng, mix: str, n: int) -> np.ndarray:
    if mix == "skewed":
        # Long tail of small entities + a head of heavy ones: several
        # size buckets, uneven fill.
        return np.concatenate([
            rng.integers(0, 30, (2 * n) // 3),
            rng.integers(100, 106, n - (2 * n) // 3),
        ])
    return rng.integers(0, 25, n)


def _dataset(rng, n=420, p=3, mix="skewed"):
    x = rng.normal(0, 1, (n, p)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    w = rng.uniform(0.5, 1.5, n).astype(np.float32)
    return GameDataset(labels=y, features={"re": x},
                       entity_ids={"u": _ids(rng, mix, n)},
                       weights=w)


CFG = OptimizerConfig(max_iters=50, tolerance=1e-7)

# How far two float32 solves of one entity's problem may end apart.  The
# solver stops where float32 can no longer resolve a decrease of the
# objective f; at an L2 weight λ that leaves ½·λ·δ² ≲ eps·|f| of slack,
# so δ ≲ sqrt(2·2⁻²⁴·|f|/λ) ≈ 1.2e-3 for these problems (|f| ≲ 7,
# λ = 0.5).  A streamed chunk and a resident bucket batch the same
# entity with different neighbours, XLA vectorizes the two shapes
# differently, and a last-bit difference decides where in that slack a
# lane stops: most entities agree bitwise, a few land this far apart.
F32_SOLVE_ATOL = 2e-3


def _assert_blocks_close(a, b, atol=1e-6):
    assert len(a) == len(b)
    for ba, bb in zip(a, b):
        np.testing.assert_allclose(np.asarray(ba), np.asarray(bb),
                                   atol=atol, rtol=0)


@pytest.mark.parametrize("mix", ["skewed", "uniform"])
@pytest.mark.parametrize("chunk_entities", [1, 7, 512])
def test_streamed_matches_resident(rng, tmp_path, mix, chunk_entities):
    """Coefficients, scores, AND variances: streamed ≡ resident across
    bucket mixes × chunk grids (chunk 1 = one entity per chunk; 512 =
    one chunk per bucket).  Tolerance note: a different vmap lane count
    compiles a different f32 summation order, so the two solvers walk
    slightly different trajectories to the same optimum — both below
    the 1e-7 gradient tolerance; coefficients agree to the
    tolerance/curvature scale, not bitwise.

    The coefficients' bound, 1.2e-3, is what ``F32_SOLVE_ATOL``'s
    derivation gives, held against readings: the largest difference
    over the six cases, by dataset seed 42 (this test's), 0, 1, 2, 3,
    is 6.1e-4, 9.2e-4, 6.1e-4, 9.4e-4, 1.04e-3 with the line search by
    whole evaluations and 1.05e-3, 9.2e-4, 7.6e-4, 7.4e-4, 5.2e-4 with
    the search along the margins (ISSUE 31).  One distribution, and
    the former bound of 1e-3 sat on its edge: the search by whole
    evaluations is over it at seed 3 as the search along the margins
    is at 42.  Under either search a lane's rounding depends on its
    neighbours (1- and 7-lane chunks of a 29-lane bucket differ from
    it by an ulp after 2 iterations, 4-lane chunks do not), and the
    solver's slack turns that ulp into 1e-3."""
    ds = _dataset(rng, mix=mix)
    offsets = jnp.asarray(rng.normal(0, 0.3, ds.n).astype(np.float32))
    res = build_random_effect_coordinate("u", ds, "re", _objective(),
                                         config=CFG)
    st = build_streamed_random_effect_coordinate(
        "u", ds, "re", _objective(), spill_dir=str(tmp_path),
        chunk_entities=chunk_entities, config=CFG, host_max_resident=2)
    w_r, _ = res.train(offsets)
    w_s, diag = st.train(offsets)
    assert diag["entities_solved"] == st.grouping.n_total_entities
    _assert_blocks_close(w_r, w_s, atol=1.2e-3)
    np.testing.assert_allclose(np.asarray(res.score(w_r)),
                               np.asarray(st.score(w_s)), atol=2e-3)
    _assert_blocks_close(res.compute_variance_blocks(w_r, offsets),
                         st.compute_variance_blocks(w_s, offsets),
                         atol=1e-3)


def test_streamed_sparse_projected_matches_resident(rng, tmp_path):
    """Sparse (subspace-projected) shards stream too: the projection
    blocks spill chunk-wise and the solve matches the resident
    projected coordinate."""
    n, d_re = 300, 12
    ids = _ids(rng, "skewed", n)
    rows = []
    for _ in range(n):
        k = rng.integers(1, 4)
        cols = rng.choice(d_re, size=k, replace=False).astype(np.int32)
        rows.append((cols, rng.normal(0, 1, k).astype(np.float32)))
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    ds = GameDataset(labels=y, features={"re": rows},
                     entity_ids={"u": ids}, feature_dims={"re": d_re})
    offsets = jnp.asarray(rng.normal(0, 0.3, n).astype(np.float32))
    res = build_random_effect_coordinate_sparse(
        "u", ds, "re", _objective(), global_dim=d_re, config=CFG)
    st = build_streamed_random_effect_coordinate(
        "u", ds, "re", _objective(), spill_dir=str(tmp_path),
        chunk_entities=5, config=CFG)
    assert st.projection is not None
    w_r, _ = res.train(offsets)
    w_s, _ = st.train(offsets)
    _assert_blocks_close(w_r, w_s)
    np.testing.assert_allclose(np.asarray(res.score(w_r)),
                               np.asarray(st.score(w_s)), atol=1e-6)


def test_lru_window_bound_and_sequential_order(rng, tmp_path):
    """At most host_max_resident decoded chunks live through build AND
    every training/scoring sweep; the sweep's store access is the
    deterministic ascending order."""
    ds = _dataset(rng)
    st = build_streamed_random_effect_coordinate(
        "u", ds, "re", _objective(), spill_dir=str(tmp_path),
        chunk_entities=4, config=CFG, host_max_resident=2)
    total = st.store.n_chunks
    assert total >= 6  # the bound must be a real claim
    offsets = jnp.asarray(rng.normal(0, 0.3, ds.n).astype(np.float32))
    w, _ = st.train(offsets)
    st.compute_variance_blocks(w, offsets)
    assert st.store.peak_resident <= 2
    st.store.assert_quiesced()
    # Each full pass visits chunks in ascending global order.
    log = st.store.access_log
    per_pass = [log[i:i + total] for i in range(0, len(log), total)]
    for chunk_ids in per_pass:
        assert chunk_ids == sorted(chunk_ids)


def test_warm_store_reuse_across_builds(rng, tmp_path):
    """Same data + config ⇒ the second build reuses every chunk file
    (spills == 0) and trains to the identical result."""
    ds = _dataset(rng)
    offsets = jnp.asarray(rng.normal(0, 0.3, ds.n).astype(np.float32))
    st1 = build_streamed_random_effect_coordinate(
        "u", ds, "re", _objective(), spill_dir=str(tmp_path),
        chunk_entities=6, config=CFG)
    assert st1.store.spills == st1.store.n_chunks
    w1, _ = st1.train(offsets)
    st2 = build_streamed_random_effect_coordinate(
        "u", ds, "re", _objective(), spill_dir=str(tmp_path),
        chunk_entities=6, config=CFG)
    assert st2.store.spills == 0
    w2, _ = st2.train(offsets)
    _assert_blocks_close(w1, w2)
    # Different data ⇒ different content key, no false sharing.
    ds2 = _dataset(np.random.default_rng(7))
    st3 = build_streamed_random_effect_coordinate(
        "u", ds2, "re", _objective(), spill_dir=str(tmp_path),
        chunk_entities=6, config=CFG)
    assert st3.store.key != st2.store.key


def test_corrupt_and_missing_chunks_rebuild_from_lineage(rng, tmp_path):
    """A deleted chunk file and a truncated one both rebuild from the
    original rows mid-sweep — the store can never fail a run — and a
    rebuilt chunk is the spilled chunk to the last bit: the solve after
    the damage is the same coordinate's solve before it (one program on
    one chunk grid, so no other rounding enters)."""
    ds = _dataset(rng)
    offsets = jnp.asarray(rng.normal(0, 0.3, ds.n).astype(np.float32))
    st = build_streamed_random_effect_coordinate(
        "u", ds, "re", _objective(), spill_dir=str(tmp_path),
        chunk_entities=4, config=CFG, host_max_resident=1)
    files = sorted(glob.glob(os.path.join(str(tmp_path), "chunks",
                                          f"{st.store.key}-*.npz")))
    assert len(files) == st.store.n_chunks >= 4
    w_sound = [np.array(block) for block in st.train(offsets)[0]]  # copies
    assert st.store.rebuilds == 0
    # not the chunk the first sweep left resident (the last it loaded)
    os.remove(files[0])
    with open(files[2], "r+b") as f:
        f.truncate(10)
    # from the first sweep's start, zeros, not from where it ended
    w_s, _ = st.train(offsets, warm_start=[
        np.zeros_like(block) for block in w_sound])
    assert st.store.rebuilds >= 2
    for sound, rebuilt in zip(w_sound, w_s, strict=True):
        np.testing.assert_array_equal(np.asarray(sound),
                                      np.asarray(rebuilt))


def _cd_sweeps(coord, offsets_schedule, use_hook=True):
    """Emulated CD sweeps: train → (hook) per offsets step."""
    w = None
    solved = []
    for off in offsets_schedule:
        w, diag = coord.train(jnp.asarray(off), w)
        solved.append(diag.get("entities_solved")
                      if isinstance(diag, dict) else None)
        if use_hook and hasattr(coord, "retire_converged"):
            coord.retire_converged()
    return w, solved


def test_retirement_monotone_and_model_equivalent(rng, tmp_path):
    """On a converging fit (offsets frozen after the first sweep), the
    retired set grows monotonically — per-sweep solved entities shrink
    — and the final model matches retirement-off within solver
    tolerance.  Offset drift past the tolerance wakes entities."""
    ds = _dataset(rng)
    base = rng.normal(0, 0.3, ds.n).astype(np.float32)
    schedule = [base] * 4
    cfg = OptimizerConfig(max_iters=50, tolerance=1e-6)
    on = build_streamed_random_effect_coordinate(
        "u", ds, "re", _objective(), spill_dir=str(tmp_path / "on"),
        chunk_entities=6, config=cfg, retirement=True)
    off_ = build_streamed_random_effect_coordinate(
        "u", ds, "re", _objective(), spill_dir=str(tmp_path / "off"),
        chunk_entities=6, config=cfg, retirement=False)
    w_on, solved_on = _cd_sweeps(on, schedule)
    w_off, solved_off = _cd_sweeps(off_, schedule)
    E = on.grouping.n_total_entities
    assert solved_off == [E] * 4
    assert solved_on[0] == E
    # Monotone non-increasing, with real reduction by the last sweep.
    assert all(a >= b for a, b in zip(solved_on, solved_on[1:]))
    assert solved_on[-1] < E
    assert on.entities_retired > 0
    for ba, bb in zip(w_on, w_off):
        assert float(jnp.max(jnp.abs(ba - bb))) < 1e-5
    # Offsets drift wakes every retired entity.
    w_on, diag = on.train(jnp.asarray(base + 0.5), w_on)
    assert diag["entities_solved"] == E


def test_streamed_cd_loop_matches_resident(rng, tmp_path):
    """Full run_coordinate_descent (fixed + streamed RE, retirement ON
    via the CD hook) vs the all-resident loop: final coefficients and
    total scores agree within solver tolerance."""
    from photon_ml_tpu.data.batch import make_dense_batch
    from photon_ml_tpu.game.coordinate_descent import (
        run_coordinate_descent,
    )
    from photon_ml_tpu.game.coordinates import FixedEffectCoordinate
    from photon_ml_tpu.optim import OptimizationProblem

    ds = _dataset(rng)
    xg = rng.normal(0, 1, (ds.n, 5)).astype(np.float32)
    batch = make_dense_batch(xg, ds.labels, weights=ds.weight_array())
    fixed = FixedEffectCoordinate(
        name="fixed", batch=batch,
        problem=OptimizationProblem(objective=_objective(1.0),
                                    config=CFG))

    def run(re_coord):
        return run_coordinate_descent(
            coordinates={"fixed": fixed, "u": re_coord},
            update_sequence=["fixed", "u"], n_iterations=4)

    cd_r = run(build_random_effect_coordinate(
        "u", ds, "re", _objective(), config=CFG))
    cd_s = run(build_streamed_random_effect_coordinate(
        "u", ds, "re", _objective(), spill_dir=str(tmp_path),
        chunk_entities=6, config=CFG, retirement=True))
    # Scores are x·w over p = 3 unit-normal features: 3 × the
    # coefficient slack.
    np.testing.assert_allclose(np.asarray(cd_s.total_scores),
                               np.asarray(cd_r.total_scores),
                               atol=3 * F32_SOLVE_ATOL)
    np.testing.assert_allclose(np.asarray(cd_s.coefficients["fixed"]),
                               np.asarray(cd_r.coefficients["fixed"]),
                               atol=F32_SOLVE_ATOL)
    _assert_blocks_close(cd_r.coefficients["u"], cd_s.coefficients["u"],
                         atol=F32_SOLVE_ATOL)


def test_mesh_streamed_matches_single_device(rng, tmp_path):
    """Entity-sharded streamed variant: chunk size rounds up to the
    mesh grid, every chunk entity-shards, results match the
    single-device streamed and resident paths."""
    from photon_ml_tpu.parallel.mesh import entity_mesh

    ds = _dataset(rng)
    offsets = jnp.asarray(rng.normal(0, 0.3, ds.n).astype(np.float32))
    res = build_random_effect_coordinate("u", ds, "re", _objective(),
                                         config=CFG)
    w_r, _ = res.train(offsets)
    mesh = entity_mesh(4)
    st = build_streamed_random_effect_coordinate(
        "u", ds, "re", _objective(), spill_dir=str(tmp_path),
        chunk_entities=6, config=CFG, mesh=mesh)
    assert st.chunk_entities % 4 == 0
    w_s, _ = st.train(offsets)
    _assert_blocks_close(w_r, w_s, atol=F32_SOLVE_ATOL)
    # The two scoring paths agree on the SAME coefficients.
    np.testing.assert_allclose(np.asarray(res.score(w_s)),
                               np.asarray(st.score(w_s)), atol=1e-6)


def test_score_external_blocks_and_zero_shortcut(rng, tmp_path):
    """score() on blocks the coordinate did not train (warm-start /
    locked-coordinate scoring) streams a pass that matches the resident
    score; all-zero blocks short-circuit without touching the store."""
    ds = _dataset(rng)
    res = build_random_effect_coordinate("u", ds, "re", _objective(),
                                         config=CFG)
    st = build_streamed_random_effect_coordinate(
        "u", ds, "re", _objective(), spill_dir=str(tmp_path),
        chunk_entities=5, config=CFG)
    blocks = [jnp.asarray(rng.normal(0, 0.2, (e, p)).astype(np.float32))
              for (e, p) in st.coefficient_shapes]
    np.testing.assert_allclose(np.asarray(st.score(blocks)),
                               np.asarray(res.score(blocks)), atol=1e-6)
    loads_before = st.store.loads + st.store.hits
    zeros = st.initial_coefficients()
    assert not np.any(np.asarray(st.score(zeros)))
    assert st.store.loads + st.store.hits == loads_before


def test_external_warm_start_adopted(rng, tmp_path):
    """An externally supplied warm start (model import / checkpoint
    resume) is adopted — the solve continues from it exactly as the
    resident path does."""
    ds = _dataset(rng)
    offsets = jnp.asarray(rng.normal(0, 0.3, ds.n).astype(np.float32))
    cfg = OptimizerConfig(max_iters=3, tolerance=1e-7)
    res = build_random_effect_coordinate("u", ds, "re", _objective(),
                                         config=cfg)
    st = build_streamed_random_effect_coordinate(
        "u", ds, "re", _objective(), spill_dir=str(tmp_path),
        chunk_entities=6, config=cfg)
    warm = [jnp.asarray(rng.normal(0, 0.1, (e, p)).astype(np.float32))
            for (e, p) in st.coefficient_shapes]
    w_r, _ = res.train(offsets, [jnp.asarray(w) for w in warm])
    w_s, _ = st.train(offsets, warm)
    _assert_blocks_close(w_r, w_s)


def test_estimator_streamed_fit_matches_resident(rng, tmp_path):
    """GameEstimator end to end: re_chunk_entities produces the same
    model (coefficients + variances) as the resident fit, including
    the warm chunk-store second fit."""
    from photon_ml_tpu.config import (
        CoordinateConfig,
        CoordinateKind,
        OptimizerSettings,
        TrainingConfig,
    )
    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.models.glm import TaskType

    n = 300
    ds = GameDataset(
        labels=(rng.uniform(size=n) < 0.5).astype(np.float32),
        features={"g": rng.normal(0, 1, (n, 6)).astype(np.float32),
                  "re": rng.normal(0, 1, (n, 3)).astype(np.float32)},
        entity_ids={"u": _ids(rng, "skewed", n)})

    def cfg(re_chunk, spill):
        return TrainingConfig(
            task_type=TaskType.LOGISTIC_REGRESSION,
            coordinates=[
                CoordinateConfig(name="fixed",
                                 kind=CoordinateKind.FIXED_EFFECT,
                                 feature_shard="g",
                                 optimizer=OptimizerSettings(
                                     max_iters=25)),
                CoordinateConfig(name="per_u",
                                 kind=CoordinateKind.RANDOM_EFFECT,
                                 feature_shard="re", entity_key="u",
                                 optimizer=OptimizerSettings(
                                     max_iters=25,
                                     variance_type="SIMPLE")),
            ],
            update_sequence=["fixed", "per_u"], n_iterations=2,
            evaluators=[], re_chunk_entities=re_chunk, spill_dir=spill)

    m_r = GameEstimator(cfg(None, None)).fit(ds)[0].model.models
    m_s = GameEstimator(cfg(5, str(tmp_path))).fit(ds)[0].model.models
    np.testing.assert_allclose(
        np.asarray(m_s["fixed"].coefficients.means),
        np.asarray(m_r["fixed"].coefficients.means), atol=F32_SOLVE_ATOL)
    _assert_blocks_close(m_r["per_u"].coefficient_blocks,
                         m_s["per_u"].coefficient_blocks,
                         atol=F32_SOLVE_ATOL)
    _assert_blocks_close(m_r["per_u"].variance_blocks,
                         m_s["per_u"].variance_blocks,
                         atol=F32_SOLVE_ATOL)


def test_config_validation_re_knobs(tmp_path):
    """re_chunk_entities is validated and wired: positivity, the
    spill-dir requirement (env fallback honored), spill_dir accepted
    for streamed REs without chunk_rows, JSON round trip."""
    from photon_ml_tpu.config import (
        CoordinateConfig,
        CoordinateKind,
        TrainingConfig,
        config_to_json,
        training_config_from_json,
    )
    from photon_ml_tpu.models.glm import TaskType

    def cfg(**kw):
        return TrainingConfig(
            task_type=TaskType.LOGISTIC_REGRESSION,
            coordinates=[CoordinateConfig(
                name="per_u", kind=CoordinateKind.RANDOM_EFFECT,
                feature_shard="re", entity_key="u")],
            update_sequence=["per_u"], **kw)

    with pytest.raises(ValueError, match="re_chunk_entities"):
        cfg(re_chunk_entities=0, spill_dir=str(tmp_path)).validate()
    with pytest.raises(ValueError, match="spill_dir"):
        cfg(re_chunk_entities=4).validate()
    env = os.environ.pop("PHOTON_ML_TPU_SPILL_DIR", None)
    try:
        os.environ["PHOTON_ML_TPU_SPILL_DIR"] = str(tmp_path)
        cfg(re_chunk_entities=4).validate()   # env fallback OK
    finally:
        os.environ.pop("PHOTON_ML_TPU_SPILL_DIR", None)
        if env is not None:
            os.environ["PHOTON_ML_TPU_SPILL_DIR"] = env
    # spill_dir legal with streamed REs and no chunked fixed effect.
    c = cfg(re_chunk_entities=4, spill_dir=str(tmp_path),
            re_retirement=False)
    c.validate()
    c2 = training_config_from_json(config_to_json(c))
    assert c2.re_chunk_entities == 4 and c2.re_retirement is False


def test_bucket_occupancy_stats(rng):
    """Occupancy satellite: fill fractions and padded-slot ratio are
    exact for a hand-checkable grouping."""
    from photon_ml_tpu.game.dataset import (
        bucket_occupancy,
        group_by_entity,
    )

    # 4 entities with 2 examples (cap 4), 1 entity with 16 (cap 16).
    ids = np.concatenate([np.repeat(np.arange(4), 2),
                          np.full(16, 99)])
    occ = bucket_occupancy(group_by_entity(ids, bucket_base=4))
    assert occ["entities"] == 5 and occ["examples"] == 24
    by_cap = {b["capacity"]: b for b in occ["buckets"]}
    assert by_cap[4]["entities"] == 4
    assert by_cap[4]["fill_fraction"] == pytest.approx(8 / 16)
    assert by_cap[16]["fill_fraction"] == pytest.approx(1.0)
    assert occ["total_slots"] == 32
    assert occ["padded_slot_ratio"] == pytest.approx(8 / 32)


def test_diag_fields_batched_reduce_and_dict(rng):
    """_diag_fields satellite: the batched-RE aggregation is one jitted
    reduction with the same numbers as the old per-bucket loop, and
    streamed-RE dict diagnostics pass through as-is."""
    from photon_ml_tpu.game.coordinate_descent import _diag_fields

    ds = _dataset(rng)
    coord = build_random_effect_coordinate("u", ds, "re", _objective(),
                                           config=CFG)
    offsets = jnp.asarray(rng.normal(0, 0.3, ds.n).astype(np.float32))
    _, results = coord.train(offsets)
    fields = _diag_fields(results)
    assert fields["entities"] == coord.grouping.n_total_entities
    assert fields["entities_converged"] == sum(
        int(jnp.sum(r.converged)) for r in results)
    assert fields["max_solver_iterations"] == max(
        int(jnp.max(r.iterations)) for r in results)
    d = {"entities": 5, "entities_solved": 3}
    assert _diag_fields(d) == d
