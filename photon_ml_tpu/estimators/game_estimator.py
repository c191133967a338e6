"""GameEstimator: configs + data → trained, evaluated GAME models.

Reference counterpart: ``GameEstimator``
(photon-api ``com.linkedin.photon.ml.estimators.GameEstimator``
[expected path, mount unavailable — see SURVEY.md §2.6/§3.1]): build
datasets/coordinates from configuration, run coordinate descent once per
optimization configuration in the hyperparameter grid, evaluate each on
validation, return (model, evaluations, config) triples.

TPU translation notes:

- dataset/coordinate construction is the host ETL (entity grouping,
  intercept column, normalization stats, down-sampling), done ONCE and
  reused across the λ grid — only objectives change per grid point
  (the reference likewise persists datasets across the grid);
- per-iteration validation uses the trained-so-far model via
  ``GameTransformer`` on the validation set;
- normalization with shifts folds the margin correction into the
  intercept coefficient at export, so saved models score raw features
  directly (see ``_export_fixed``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import logging

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.config import (
    CoordinateConfig,
    CoordinateKind,
    OptimizerSettings,
    TrainingConfig,
)
from photon_ml_tpu.data.batch import make_dense_batch, make_sparse_batch
from photon_ml_tpu.data.normalization import (
    NormalizationContext,
    NormalizationType,
    compute_normalization,
)
from photon_ml_tpu.data.statistics import compute_statistics
from photon_ml_tpu.estimators.game_transformer import GameTransformer
from photon_ml_tpu.evaluation import evaluate, better_than
from photon_ml_tpu.game.coordinates import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
    build_random_effect_coordinate,
    build_random_effect_coordinate_sparse,
)
from photon_ml_tpu.game.coordinate_descent import (
    CoordinateDescentResult,
    run_coordinate_descent,
)
from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.game.sampling import binary_classification_down_sample
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.models.glm import TaskType
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.prior import MIN_VARIANCE, EntityPriors, GaussianPrior
from photon_ml_tpu.ops.regularization import (
    RegularizationContext,
    RegularizationType,
    SweptRegularization,
)
from photon_ml_tpu.optim import OptimizationProblem, OptimizerConfig
from photon_ml_tpu.optim.variance import VarianceComputationType
from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry import monitor as _mon

logger = logging.getLogger(__name__)

# ``estimator_fit``'s ``fit`` count: which fit of this process a stage
# belongs to (every other stage lies inside its fit's interval).
_FIT_NUMBER = itertools.count(1)


@dataclasses.dataclass
class FitResult:
    """(model, evaluations, grid point) — the reference's result triple."""

    model: GameModel
    evaluations: dict            # EvaluatorType → float (validation)
    reg_weights: dict            # coordinate name → λ used
    # Per-CD-iteration validation metrics (reference: CoordinateDescent
    # logs every evaluator each sweep); empty without validation data.
    validation_history: list = dataclasses.field(default_factory=list)
    # What ``run_coordinate_descent`` returned for this point (its
    # coefficients, each coordinate's final training scores, the
    # solvers' records), as it stands; None where a point is not the
    # product of one descent (the swept lanes).
    descent: CoordinateDescentResult | None = None


def _reg_context(settings: OptimizerSettings, weight: float, dim: int,
                 intercept_index: int | None) -> RegularizationContext:
    from photon_ml_tpu.ops.regularization import exclude_intercept_mask

    mask = exclude_intercept_mask(dim, intercept_index)
    if settings.regularization == RegularizationType.NONE or weight == 0.0:
        return RegularizationContext.none()
    if settings.regularization == RegularizationType.L2:
        return RegularizationContext.l2(weight, mask)
    if settings.regularization == RegularizationType.L1:
        return RegularizationContext.l1(weight, mask)
    return RegularizationContext.elastic_net(
        weight, settings.elastic_net_alpha, mask
    )


def _optimizer_config(settings: OptimizerSettings) -> OptimizerConfig:
    return OptimizerConfig(
        max_iters=settings.max_iters,
        tolerance=settings.tolerance,
        track_states=settings.track_states,
        cg_max_iters=settings.cg_max_iters,
    )


def _training_offsets(train: GameDataset):
    """The dataset's offsets as training takes them ([n] float32), or
    None where it brought none: nothing is then added to any margin."""
    if train.offsets is None:
        return None
    return jnp.asarray(train.offset_array())


def _join_random(comp: RandomEffectModel, coord, planes: list,
                 fills: list) -> list:
    """Each plane of a saved random effect (a list of per-bucket blocks
    shaped as its ``coefficient_blocks``: the coefficients, the
    variances) mapped onto ``coord``'s grouping and subspaces, as host
    float32 blocks shaped as ``coord``'s coefficients; ``fills[i]``
    where plane i has nothing (an entity or a column the saved model
    does not have).

    Fully vectorized (SURVEY §7 entity-ETL scale): one sorted join of
    new vs saved entity ids, then per-(new bucket, old bucket) block
    gathers — the bucket grid is O(log² max-count), each cell one
    fancy-indexed copy of every plane; between two projected models
    one sorted join on (entity, global column) keys a cell, every
    plane's values carried through it together."""
    out = [[np.full(shape, fill, np.float32)
            for shape in coord.coefficient_shapes] for fill in fills]
    g = coord.grouping
    gs = comp.grouping
    if g.n_total_entities == 0 or gs.n_total_entities == 0:
        return out

    # Sorted join on entity id (both sides are np.unique output =
    # sorted; saved models preserve that order through I/O).
    saved_pos = gs.join_ids(np.asarray(g.entity_ids))
    found = saved_pos >= 0
    pos_c = np.maximum(saved_pos, 0)
    old_bucket = np.asarray(gs.entity_bucket)[pos_c]
    old_slot = np.asarray(gs.entity_slot)[pos_c]
    new_bucket = np.asarray(g.entity_bucket)
    new_slot = np.asarray(g.entity_slot)

    old_planes = [[np.asarray(blk) for blk in plane] for plane in planes]
    for b in range(len(out[0])):
        width = out[0][b].shape[1]
        for ob in range(len(old_planes[0])):
            sel = found & (new_bucket == b) & (old_bucket == ob)
            if not sel.any():
                continue
            ns, os_ = new_slot[sel], old_slot[sel]
            olds = [plane[ob][os_] for plane in old_planes]  # [m, p_old]
            if coord.projection is None and comp.projection is None:
                if olds[0].shape[1] != width:
                    continue  # width mismatch: entity starts at 0
                for new, old in zip(out, olds):
                    new[b][ns] = old
            elif coord.projection is None:
                # Saved model projected, target dense: scatter each
                # entity's local coefs to its global columns.
                if comp.projection.global_dim != width:
                    continue
                fids = comp.projection.feature_ids[ob][os_]
                rr, cc = np.nonzero(fids >= 0)
                for new, old in zip(out, olds):
                    new[b][ns[rr], fids[rr, cc]] = old[rr, cc]
            elif comp.projection is None:
                # Saved dense, target projected: gather the target's
                # subspace columns out of the saved global rows.
                fids = coord.projection.feature_ids[b][ns]  # [m, p]
                valid = fids >= 0
                valid &= fids < olds[0].shape[1]
                rr, cc = np.nonzero(valid)
                for new, old in zip(out, olds):
                    new[b][ns[rr], cc] = old[rr, fids[rr, cc]]
            else:
                # Both projected: sparse merge-join on (entity,
                # global col) keys.
                from photon_ml_tpu.game.dataset import sorted_key_join

                G = np.int64(comp.projection.global_dim)
                f_old = comp.projection.feature_ids[ob][os_]
                ro, co = np.nonzero(f_old >= 0)
                if len(ro) == 0:
                    continue
                key_old = ro.astype(np.int64) * G + f_old[ro, co]
                f_new = coord.projection.feature_ids[b][ns]
                rn, cn = np.nonzero((f_new >= 0) & (f_new < G))
                key_new = rn.astype(np.int64) * G + f_new[rn, cn]
                # a subspace lists its columns ascending, so the keys
                # come sorted and the join's argsort is not needed
                at, hit = sorted_key_join(
                    key_old, np.stack([old[ro, co] for old in olds], -1),
                    key_new, presorted=bool(np.all(key_old[1:]
                                                   > key_old[:-1])))
                for i, new in enumerate(out):
                    new[b][ns[rn[hit]], cn[hit]] = at[hit, i]
    return out


def _real_coefficients(coord) -> int:
    """A random effect's coefficients that belong to a column its
    entities saw: every local column of a projected one (padding
    left out), entities x width of a dense one."""
    if coord.projection is not None:
        return int(sum((f >= 0).sum() for f in coord.projection.feature_ids))
    return int(coord.grouping.n_total_entities) * int(
        coord.coefficient_shapes[0][1] if coord.coefficient_shapes else 0)


def _resolve_layout(requested: str, off_tpu: str) -> str:
    """``AUTO`` → the GRR compiled plan on a TPU (the only backend the
    Mosaic kernel runs on), ``off_tpu`` elsewhere; anything else as
    requested."""
    if requested != "AUTO":
        return requested
    import jax

    return "GRR" if jax.default_backend() == "tpu" else off_tpu


class GameEstimator:
    """Build coordinates once; fit once per λ-grid point."""

    def __init__(self, config: TrainingConfig):
        config.validate()
        self.config = config
        self.task = config.task_type
        self.loss = self.task.loss
        self._mesh_cache = None
        self._entity_mesh_cache = None
        self._warm_model = None
        if config.warm_start_model_dir:
            from photon_ml_tpu.io.model_io import load_game_model

            self._warm_model, warm_task = load_game_model(
                config.warm_start_model_dir)
            if warm_task != self.task:
                raise ValueError(
                    f"warm-start model task {warm_task} != {self.task}")

    # -- dataset preparation (once) ----------------------------------------

    def _prepare(self, train: GameDataset):
        cfg = self.config
        if cfg.cd_fused and train.offsets is not None:
            # before any chunk is built: the fused sweep composes its
            # margins from coefficients (game/fused_sweep._fused_chunk)
            raise ValueError(
                "cd_fused does not carry a dataset's offsets: the fused "
                "sweep composes its margins from coefficients; fit with "
                "cd_fused=false")
        prep = {}
        for coord_cfg in cfg.coordinates:
            if coord_cfg.kind == CoordinateKind.FIXED_EFFECT:
                with telemetry.stage("prepare_fixed",
                                     coordinate=coord_cfg.name,
                                     rows=int(train.n)) as stage:
                    prep[coord_cfg.name] = self._prepare_fixed(train,
                                                               coord_cfg)
                    stage.set(dim=int(prep[coord_cfg.name]["dim"]))
        return prep

    def _mesh(self):
        if self.config.n_devices is None:
            return None
        if self._mesh_cache is None:
            from photon_ml_tpu.parallel import data_parallel_mesh

            self._mesh_cache = data_parallel_mesh(self.config.n_devices)
        return self._mesh_cache

    def _entity_mesh(self):
        if self.config.n_devices is None:
            return None
        if self._entity_mesh_cache is None:
            from photon_ml_tpu.parallel.mesh import entity_mesh

            self._entity_mesh_cache = entity_mesh(self.config.n_devices)
        return self._entity_mesh_cache

    def _prepare_fixed(self, train: GameDataset, coord_cfg: CoordinateConfig):
        cfg = self.config
        feats = train.features[coord_cfg.feature_shard]
        labels = train.labels.astype(np.float32)
        weights = train.weight_array()
        mesh = self._mesh()

        intercept_index = None
        if isinstance(feats, np.ndarray):
            if cfg.chunk_rows is not None:
                raise ValueError(
                    "chunk_rows supports sparse feature shards only; "
                    f"fixed-effect shard '{coord_cfg.feature_shard}' is "
                    "a dense array (a resident DenseBatch would defeat "
                    "the beyond-HBM purpose of chunking)")
            x = np.asarray(feats, np.float32)
            if cfg.intercept:
                x = np.concatenate([x, np.ones((len(x), 1), np.float32)], 1)
                intercept_index = x.shape[1] - 1
            if mesh is not None:
                from photon_ml_tpu.parallel import padded_rows, shard_batch

                batch = make_dense_batch(
                    x, labels, weights=weights,
                    pad_to=padded_rows(len(x), mesh.devices.size),
                )
                batch = shard_batch(batch, mesh)
            else:
                batch = make_dense_batch(x, labels, weights=weights)
            dim = x.shape[1]
        else:  # sparse rows
            dim = train.feature_dim(coord_cfg.feature_shard)
            rows = feats
            if cfg.intercept:
                from photon_ml_tpu.data.sparse_rows import SparseRows

                with telemetry.stage("add_intercept_col",
                                     rows=int(train.n), dim=int(dim)):
                    if isinstance(rows, SparseRows):
                        rows = rows.with_constant_col(dim)
                    else:
                        rows = [
                            (np.append(c, dim).astype(np.int32),
                             np.append(v, 1.0).astype(np.float32))
                            for c, v in rows
                        ]
                intercept_index = dim
                dim += 1
            if cfg.chunk_rows is not None:
                # Chunk-accumulated path (beyond-HBM residency; SURVEY
                # §1 L1): K congruent host chunk batches streamed per
                # objective evaluation.  Composes with the mesh
                # (chunks × shards).
                from photon_ml_tpu.data.chunked_batch import (
                    build_chunked_batch,
                )

                layout = _resolve_layout(cfg.chunk_layout, "ELL")
                from photon_ml_tpu.data.chunk_store import (
                    resolve_spill_dir,
                )

                chunked = build_chunked_batch(
                    rows, dim, labels, weights=weights,
                    chunk_rows=cfg.chunk_rows, layout=layout.lower(),
                    mesh=mesh,
                    cache_dir=cfg.plan_cache_dir,
                    # Env default ($PHOTON_ML_TPU_SPILL_DIR) applies at
                    # THIS layer only; the library builder stays
                    # explicit so resident baselines can't be flipped
                    # by ambient environment.
                    spill_dir=resolve_spill_dir(cfg.spill_dir),
                    host_max_resident=cfg.host_max_resident,
                )
                return {
                    "chunked": chunked, "batch": None,
                    "norm": NormalizationContext.identity(), "dim": dim,
                    "intercept_index": intercept_index,
                    "train_idx": None, "train_weights": None,
                    "mesh": mesh, "n_examples": train.n,
                }
            if mesh is not None:
                # Mesh path: per-shard layouts (each device indexes its
                # own rows; SURVEY §5.8's one-time "shuffle").  AUTO
                # picks the sharded GRR compiled plans on TPU — the fast
                # path IS the distributed path — and colmajor elsewhere.
                from photon_ml_tpu.parallel import shard_sparse_batch

                layout = _resolve_layout(cfg.sparse_layout, "COLMAJOR")
                batch = shard_sparse_batch(
                    rows, dim, labels, mesh, weights=weights,
                    layout=layout.lower(),
                    cache_dir=cfg.plan_cache_dir,
                )
            else:
                # Layout: the GRR compiled plan is the fast TPU path
                # (the intercept column lands on its dense MXU side);
                # plain ELL elsewhere (see data/grr.py).
                layout = _resolve_layout(cfg.sparse_layout, "ELL")
                # Device ELL is only consumed by normalization stats
                # and the down-sampled view; a GRR batch that needs
                # neither skips the 8-bytes/nnz HBM copy.
                keep_ell = (
                    cfg.normalization != NormalizationType.NONE
                    or coord_cfg.down_sampling_rate is not None
                )
                batch = make_sparse_batch(
                    rows, dim, labels, weights=weights,
                    grr=(layout == "GRR"),
                    col_major=(layout == "COLMAJOR"),
                    keep_ell=keep_ell,
                    cache_dir=cfg.plan_cache_dir,
                )

        norm = NormalizationContext.identity()
        if cfg.normalization != NormalizationType.NONE:
            stats = compute_statistics(batch)
            if (cfg.normalization == NormalizationType.STANDARDIZATION
                    and intercept_index is None):
                raise ValueError(
                    "STANDARDIZATION requires intercept=True (the margin "
                    "shift folds into the intercept at export)"
                )
            norm = compute_normalization(
                stats.mean, stats.std, stats.max_abs, cfg.normalization,
                intercept_index=intercept_index,
            )

        train_idx = train_weights = None
        if coord_cfg.down_sampling_rate is not None:
            idx, new_w = binary_classification_down_sample(
                labels, weights, coord_cfg.down_sampling_rate, seed=cfg.seed
            )
            train_idx = jnp.asarray(idx.astype(np.int32))
            train_weights = jnp.asarray(new_w)

        return {
            "batch": batch, "norm": norm, "dim": dim,
            "intercept_index": intercept_index,
            "train_idx": train_idx, "train_weights": train_weights,
            "mesh": mesh, "n_examples": train.n,
        }

    # -- warm-start import (saved raw-space model → training space) --------

    def _import_fixed(self, comp: FixedEffectModel, p: dict):
        """Invert ``_export_fixed``: raw-space means (+variances) →
        model-space (means, variances)."""
        w_raw = np.asarray(comp.coefficients.means, np.float64)
        dim, ii = p["dim"], p["intercept_index"]
        if len(w_raw) != dim:
            raise ValueError(
                f"warm-start fixed-effect dim {len(w_raw)} != {dim} "
                "(feature space changed; rebuild index maps)")
        norm = p["norm"]
        f = (np.asarray(norm.factors, np.float64)
             if norm.factors is not None else np.ones(dim))
        wm = w_raw / f
        if norm.shifts is not None and ii is not None:
            # Undo the margin-correction fold into the intercept; the
            # correction only involves non-intercept coords (shift=0 at
            # the intercept), all already final in wm.
            s = np.asarray(norm.shifts, np.float64)
            wm[ii] = w_raw[ii] + float(np.dot(s * f, wm))
        variances = None
        if comp.coefficients.variances is not None:
            # var scales as the square of the linear reparameterization
            # (intercept cross-terms under shifts ignored — documented).
            variances = np.asarray(comp.coefficients.variances,
                                   np.float64) / (f * f)
        return (jnp.asarray(wm.astype(np.float32)),
                None if variances is None
                else jnp.asarray(variances.astype(np.float32)))

    def _import_random(self, comp: RandomEffectModel, coord):
        """Map a saved RandomEffectModel onto a (possibly different)
        training-run grouping by entity id; unseen entities start at 0."""
        (w0s,) = _join_random(comp, coord, [comp.coefficient_blocks], [0.0])
        return [jnp.asarray(w) for w in w0s]

    def _random_prior(self, name: str, comp: RandomEffectModel, coord):
        """The warm model's means and SIMPLE variances of a random
        effect as each entity's Gaussian prior (``EntityPriors``), both
        mapped onto this run's entities and subspaces by the one join
        of ``_join_random``: a coefficient the saved model has keeps
        its mean and gets the precision 1/σ²; an entity or a column it
        has not gets precision 0.  None where the saved model has no
        variances.  Returns (priors, entities that have one)."""
        if comp.variance_blocks is None:
            return None, 0
        if not isinstance(coord, RandomEffectCoordinate):
            raise ValueError(
                f"use_warm_start_as_prior: random effect '{name}' is "
                "streamed (re_chunk_entities), and a streamed random "
                "effect takes no prior; fit it resident or set "
                "use_warm_start_as_prior=false")
        with telemetry.stage("re_prior_import", coordinate=name) as stage:
            # a saved variance is never NaN: NaN marks "none saved"
            means, variances = _join_random(
                comp, coord, [comp.coefficient_blocks, comp.variance_blocks],
                [0.0, np.nan])
            known = [~np.isnan(v) for v in variances]
            precisions = [
                np.where(k, 1.0 / np.maximum(np.where(k, v, 1.0),
                                             MIN_VARIANCE), 0.0)
                .astype(np.float32)
                for k, v in zip(known, variances)]
            with_prior = int(sum(k.any(axis=1).sum() for k in known))
            stage.set(entities=int(coord.grouping.n_total_entities),
                      coefficients=_real_coefficients(coord),
                      entities_with_prior=with_prior,
                      coefficients_with_prior=int(sum(k.sum()
                                                      for k in known)))
            priors = EntityPriors(
                means=[jnp.asarray(m) for m in means],
                precisions=[jnp.asarray(p) for p in precisions],
                weight=jnp.asarray(self.config.prior_weight, jnp.float32))
        return priors, with_prior

    def _warm_coefficients(self, coords: dict, prep: dict) -> dict:
        """Per-coordinate starting coefficients from the warm model."""
        out = {}
        if self._warm_model is None:
            return out
        by_name = {c.name: c for c in self.config.coordinates}
        for name, comp in self._warm_model.models.items():
            if name not in coords:
                continue
            if by_name[name].kind == CoordinateKind.FIXED_EFFECT:
                out[name], _ = self._import_fixed(comp, prep[name])
            elif getattr(coords[name], "prior", None) is not None:
                # the prior's means are the imported coefficients; the
                # start is a copy, since training donates its buffers
                out[name] = [jnp.copy(m) for m in coords[name].prior.means]
            else:
                out[name] = self._import_random(comp, coords[name])
        return out

    # -- coordinate construction (per grid point) --------------------------

    def _build_coordinates(self, train: GameDataset, prep: dict,
                           reg_weights: dict):
        cfg = self.config
        coords = {}
        with telemetry.stage("build_coordinates",
                             coordinates=len(cfg.coordinates)):
            for coord_cfg in cfg.coordinates:
                weight = reg_weights.get(coord_cfg.name,
                                         coord_cfg.optimizer.reg_weight)
                ocfg = _optimizer_config(coord_cfg.optimizer)
                if coord_cfg.kind == CoordinateKind.FIXED_EFFECT:
                    p = prep[coord_cfg.name]
                    prior = None
                    if (cfg.use_warm_start_as_prior
                            and self._warm_model is not None
                            and coord_cfg.name in self._warm_model.models):
                        comp = self._warm_model.models[coord_cfg.name]
                        means, variances = self._import_fixed(comp, p)
                        if variances is not None:
                            prior = GaussianPrior.from_model(
                                means, variances, cfg.prior_weight)
                    objective = GLMObjective(
                        loss=self.loss,
                        reg=_reg_context(coord_cfg.optimizer, weight, p["dim"],
                                         p["intercept_index"]),
                        norm=p["norm"],
                        prior=prior,
                    )
                    if p.get("chunked") is not None:
                        from photon_ml_tpu.game.coordinates import (
                            ChunkedFixedEffectCoordinate,
                        )

                        coords[coord_cfg.name] = ChunkedFixedEffectCoordinate(
                            name=coord_cfg.name,
                            chunked=p["chunked"],
                            objective=objective,
                            optimizer=coord_cfg.optimizer.optimizer,
                            config=ocfg,
                            max_resident=cfg.chunk_max_resident,
                            prefetch_depth=cfg.prefetch_depth,
                        )
                        continue
                    distributed = None
                    if p["mesh"] is not None:
                        from photon_ml_tpu.parallel import (
                            DistributedGLMObjective,
                        )

                        distributed = DistributedGLMObjective(
                            objective=objective, mesh=p["mesh"])
                    coords[coord_cfg.name] = FixedEffectCoordinate(
                        name=coord_cfg.name,
                        batch=p["batch"],
                        problem=OptimizationProblem(
                            objective=objective,
                            optimizer=coord_cfg.optimizer.optimizer,
                            config=ocfg,
                        ),
                        distributed=distributed,
                        train_idx=p["train_idx"],
                        train_weights=p["train_weights"],
                        n_examples=p["n_examples"],
                    )
                else:
                    feats = train.features[coord_cfg.feature_shard]
                    objective = GLMObjective(
                        loss=self.loss,
                        reg=_reg_context(coord_cfg.optimizer, weight, 1, None),
                        norm=NormalizationContext.identity(),
                    )
                    e_mesh = self._entity_mesh()
                    if cfg.re_chunk_entities is not None:
                        # Out-of-core streamed RE training (ISSUE 5): the
                        # builder handles dense and sparse shards; env
                        # default for spill_dir applies at THIS layer only
                        # (library builders stay explicit — same rule as
                        # the chunked fixed-effect path).
                        from photon_ml_tpu.data.chunk_store import (
                            resolve_spill_dir,
                        )
                        from photon_ml_tpu.game.coordinates import (
                            build_streamed_random_effect_coordinate,
                        )

                        spill = resolve_spill_dir(cfg.spill_dir)
                        if spill is None:
                            raise ValueError(
                                "re_chunk_entities requires spill_dir (or "
                                "$PHOTON_ML_TPU_SPILL_DIR)")
                        coords[coord_cfg.name] = (
                            build_streamed_random_effect_coordinate(
                                coord_cfg.entity_key, train,
                                coord_cfg.feature_shard, objective,
                                spill_dir=spill,
                                chunk_entities=cfg.re_chunk_entities,
                                config=ocfg,
                                optimizer=coord_cfg.optimizer.optimizer,
                                host_max_resident=cfg.host_max_resident,
                                prefetch_depth=cfg.prefetch_depth,
                                retirement=cfg.re_retirement,
                                mesh=e_mesh,
                            )
                        )
                    elif isinstance(feats, np.ndarray):
                        coords[coord_cfg.name] = (
                            build_random_effect_coordinate(
                                coord_cfg.entity_key, train,
                                coord_cfg.feature_shard, objective,
                                config=ocfg,
                                optimizer=coord_cfg.optimizer.optimizer,
                                mesh=e_mesh,
                            )
                        )
                    else:
                        coords[coord_cfg.name] = (
                            build_random_effect_coordinate_sparse(
                                coord_cfg.entity_key, train,
                                coord_cfg.feature_shard, objective,
                                global_dim=train.feature_dim(
                                    coord_cfg.feature_shard),
                                config=ocfg,
                                optimizer=coord_cfg.optimizer.optimizer,
                                mesh=e_mesh,
                            )
                        )
                    # Coordinate was registered under entity_key by the
                    # builder; expose it under the coordinate name.
                    coords[coord_cfg.name].name = coord_cfg.name
                    if (cfg.use_warm_start_as_prior
                            and self._warm_model is not None
                            and coord_cfg.name in self._warm_model.models
                            and coord_cfg.name
                            not in cfg.locked_coordinates):
                        coord = coords[coord_cfg.name]
                        prior, entities = self._random_prior(
                            coord_cfg.name,
                            self._warm_model.models[coord_cfg.name], coord)
                        if prior is not None:
                            coord.prior, coord.prior_entities = (prior,
                                                                 entities)
            self._share_chunk_window(coords)
        return coords

    def _share_chunk_window(self, coords: dict) -> None:
        """One LRU residency budget across every store-backed
        coordinate (ISSUE 11 satellite): the legacy per-coordinate CD
        cycle streams the fixed effect's store and each streamed RE's
        store in turn, and per-store windows pinned
        (host_max_resident × stores) chunks — each coordinate's sweep
        thrashing the others' budget expectation.  Grouping makes
        ``host_max_resident`` the TOTAL decoded-chunk bound for the
        whole descent; the active coordinate's sweep naturally fills
        the window and the previous coordinate's stale chunks evict
        first."""
        self._chunk_window_group = None
        stores = []
        for coord in coords.values():
            chunked = getattr(coord, "chunked", None)
            if chunked is not None and getattr(chunked, "store",
                                               None) is not None:
                stores.append(chunked.store)
            store = getattr(coord, "store", None)
            if store is not None:
                stores.append(store)
        if len(stores) < 2:
            return
        from photon_ml_tpu.data.chunk_store import SharedChunkWindow

        group = SharedChunkWindow(self.config.host_max_resident)
        for store in stores:
            store.join_window_group(group)
        self._chunk_window_group = group

    # -- model export ------------------------------------------------------

    def _export_fixed(self, coord: FixedEffectCoordinate, w,
                      coord_cfg: CoordinateConfig,
                      variances=None) -> FixedEffectModel:
        """Export in RAW feature space: scale by normalization factors and
        fold the margin shift-correction into the intercept (its presence
        under shifts is validated in _prepare_fixed), so saved models
        score raw features with a plain dot product."""
        norm = coord.problem.objective.norm
        w_raw = np.asarray(norm.model_to_raw(w)).copy()
        if norm.shifts is not None:
            w_raw[-1] -= float(norm.margin_correction(w))
        var_raw = None
        if variances is not None:
            # Variances scale with the square of the reparameterization.
            f = (np.asarray(norm.factors)
                 if norm.factors is not None
                 else np.ones_like(w_raw))
            var_raw = jnp.asarray(np.asarray(variances) * f * f)
        return FixedEffectModel(
            coefficients=Coefficients(means=jnp.asarray(w_raw),
                                      variances=var_raw),
            feature_shard=coord_cfg.feature_shard,
            intercept=self.config.intercept,
        )

    def _model_snapshot(self, coords, coefficients: dict) -> GameModel:
        """Current-coefficients model, no variances — the cheap export
        used for per-iteration validation scoring."""
        models = {}
        by_name = {c.name: c for c in self.config.coordinates}
        for name, w in coefficients.items():
            coord_cfg = by_name[name]
            coord = coords[name]
            if coord_cfg.kind == CoordinateKind.FIXED_EFFECT:
                models[name] = self._export_fixed(coord, w, coord_cfg, None)
            else:
                models[name] = coord.as_model(w)
                models[name].feature_shard = coord_cfg.feature_shard
                models[name].entity_key = coord_cfg.entity_key
        return GameModel(models=models)

    def _to_game_model(self, coords, cd) -> GameModel:
        models = {}
        by_name = {c.name: c for c in self.config.coordinates}
        with telemetry.stage("export_model",
                             coordinates=len(cd.coefficients)) as stage:
            pulled = 0
            sparsity = {}
            for name, w in cd.coefficients.items():
                coord_cfg = by_name[name]
                coord = coords[name]
                vtype = coord_cfg.optimizer.variance_type
                offsets = cd.total_scores - cd.scores[name]
                if coord_cfg.kind == CoordinateKind.FIXED_EFFECT:
                    variances = None
                    if vtype != VarianceComputationType.NONE:
                        variances = coord.compute_variances(
                            w, offsets, vtype)
                    models[name] = self._export_fixed(
                        coord, w, coord_cfg, variances)
                    # The fixed effect's export is the one host pull
                    # here; a random effect's blocks stay on the device.
                    pulled += int(w.nbytes)
                    if coord.problem.has_l1():
                        sparsity["nonzero_coefficients"] = (
                            sparsity.get("nonzero_coefficients", 0)
                            + int(np.count_nonzero(np.asarray(
                                models[name].coefficients.means))))
                else:
                    models[name] = coord.as_model(w)
                    if vtype != VarianceComputationType.NONE:
                        # Per-entity variances are SIMPLE by design (a
                        # FULL inverse per entity is neither needed nor
                        # tractable).
                        with telemetry.stage(
                                "re_variances", coordinate=name,
                                entities=int(
                                    coord.grouping.n_total_entities),
                                coefficients=_real_coefficients(coord)):
                            models[name].variance_blocks = (
                                jax.block_until_ready(
                                    coord.compute_variance_blocks(
                                        w, offsets)))
                    models[name].feature_shard = coord_cfg.feature_shard
                    models[name].entity_key = coord_cfg.entity_key
            stage.set(bytes_pulled=pulled, **sparsity)
        return GameModel(models=models)

    # -- batched λ-sweep (one data stream for the whole grid) --------------

    def _swept_coordinate_name(self) -> str | None:
        """The single trainable fixed-effect coordinate eligible for
        batched λ-sweep training, or None.

        Eligibility: exactly one trainable (non-locked) coordinate in
        the update sequence, FIXED_EFFECT, LBFGS/OWL-QN (TRON per-point
        fits stay sequential), and no locked coordinate requesting
        variances (those need per-coordinate score bookkeeping the
        swept path doesn't carry).  Locked coordinates are fine
        otherwise — their scores fold into the (lane-shared) offsets.
        """
        cfg = self.config
        trainable = [n for n in dict.fromkeys(cfg.update_sequence)
                     if n not in cfg.locked_coordinates]
        if len(trainable) != 1:
            return None
        name = trainable[0]
        by_name = {c.name: c for c in cfg.coordinates}
        cc = by_name.get(name)
        if cc is None or cc.kind != CoordinateKind.FIXED_EFFECT:
            return None
        from photon_ml_tpu.optim.base import OptimizerType

        if cc.optimizer.optimizer == OptimizerType.TRON:
            return None
        for c in cfg.coordinates:
            if (c.name in cfg.locked_coordinates
                    and c.optimizer.variance_type
                    != VarianceComputationType.NONE):
                return None
        return name

    def _locked_offsets(self, coords, locked: dict, train: GameDataset):
        """Offsets the trainable coordinate sees = the dataset's own +
        Σ locked scores (CD semantics with one trainable coordinate:
        total − own-scores, and own scores cancel)."""
        total = _training_offsets(train)
        if total is None:
            total = jnp.zeros((train.n,), jnp.float32)
        for ln, lw in locked.items():
            total = total + coords[ln].score(lw)
        return total

    def _lane_coordinate(self, coord, coord_cfg: CoordinateConfig,
                         lam: float):
        """Clone of a fixed-effect coordinate with one lane's λ
        installed — for per-lane variance computation (the Hessian
        includes λ₂)."""
        from photon_ml_tpu.game.coordinates import (
            ChunkedFixedEffectCoordinate,
        )

        reg1 = SweptRegularization.from_grid(
            coord_cfg.optimizer.regularization, [lam],
            coord_cfg.optimizer.elastic_net_alpha)
        if isinstance(coord, ChunkedFixedEffectCoordinate):
            base = coord.objective
            obj_l = base.replace(reg=base.reg.replace(
                l1_weight=reg1.l1_weights[0],
                l2_weight=reg1.l2_weights[0]))
            return ChunkedFixedEffectCoordinate(
                name=coord.name, chunked=coord.chunked, objective=obj_l,
                optimizer=coord.optimizer, config=coord.config,
                max_resident=coord.max_resident,
                prefetch_depth=coord.prefetch_depth)
        base = coord.problem.objective
        obj_l = base.replace(reg=base.reg.replace(
            l1_weight=reg1.l1_weights[0], l2_weight=reg1.l2_weights[0]))
        dist_l = (None if coord.distributed is None
                  else coord.distributed.replace(objective=obj_l))
        return dataclasses.replace(
            coord, problem=coord.problem.replace(objective=obj_l),
            distributed=dist_l)

    def _swept_lane_model(self, coords, name: str, w_j, locked: dict,
                          offsets, lam: float,
                          with_variances: bool = True) -> GameModel:
        """One lane's GameModel: the snapshot export (fixed effect at
        this λ plus the locked coordinates), with the trainable entry
        re-exported variance-bearing when requested (variances need
        the LANE's reg context — the Hessian includes λ₂)."""
        model = self._model_snapshot(coords, {**locked, name: w_j})
        by_name = {c.name: c for c in self.config.coordinates}
        cc = by_name[name]
        vtype = cc.optimizer.variance_type
        if with_variances and vtype != VarianceComputationType.NONE:
            variances = self._lane_coordinate(
                coords[name], cc, lam).compute_variances(
                    w_j, offsets, vtype)
            model.models[name] = self._export_fixed(
                coords[name], w_j, cc, variances)
        return model

    def _train_swept_lanes(self, coords, name: str, lams, offsets,
                          locked: dict, validation, run_logger,
                          warm_W=None, base_w0=None, checkpointer=None,
                          resume: bool = False, stage: str = "swept"):
        """Train λ lanes as ONE batched sweep; returns (FitResults in
        the order of ``lams``, W [L, dim] in that order).

        Lanes run λ-DESCENDING inside the solve (continuation order:
        strongly regularized lanes converge first and coast under the
        masked while_loop while weakly regularized stragglers keep
        refining); results are mapped back to the caller's order.

        With a ``checkpointer`` (ISSUE 9) the lane matrix, sweep index,
        and per-lane validation history snapshot to stage ``stage``
        after every sweep, the swept solver checkpoints mid-solve under
        a per-sweep scope, and ``resume`` restores — so a SIGKILL mid
        swept fit resumes at its exact (sweep, solver iteration).
        """
        import time as _time

        from photon_ml_tpu.game.coordinate_descent import (
            _revive_validation,
            _serialize_validation,
        )
        from photon_ml_tpu.reliability import checkpoint as _ckpt

        cfg = self.config
        by_name = {c.name: c for c in cfg.coordinates}
        cc = by_name[name]
        coord = coords[name]
        lams_arr = np.asarray(lams, np.float32)
        order = np.argsort(-lams_arr, kind="stable")
        inv = np.empty_like(order)
        inv[order] = np.arange(len(order))
        reg = SweptRegularization.from_grid(
            cc.optimizer.regularization, lams_arr[order],
            cc.optimizer.elastic_net_alpha)
        L = len(lams)
        if warm_W is not None:
            W = jnp.asarray(warm_W)[jnp.asarray(order)]
        elif base_w0 is not None:
            W = jnp.tile(jnp.asarray(base_w0)[None, :], (L, 1))
        else:
            W = None
        from photon_ml_tpu import telemetry

        t0 = _time.perf_counter()
        res = None
        res_summary: dict | None = None
        inv_idx = jnp.asarray(inv)
        # Per-sweep validation mirrors _fit_point's validator (the
        # reference scores validation data every CD iteration): one
        # snapshot evaluation per lane per sweep — the same L·n_iter
        # transforms the sequential grid pays.
        validate = (validation is not None and cfg.validate_per_iteration)
        lane_history: list[list] = [[] for _ in range(L)]
        start_sweep = 0
        if checkpointer is not None and resume:
            st = checkpointer.load_stage(stage)
            if (st is not None
                    and [float(x) for x in st["lams"]]
                    == [float(x) for x in lams]):
                start_sweep = int(st["sweep"])
                if st.get("W") is not None:
                    W = jnp.asarray(st["W"], jnp.float32)
                lane_history = [_revive_validation(h)
                                for h in st.get("lane_history") or []]
                while len(lane_history) < L:
                    lane_history.append([])
                res_summary = st.get("res_summary")
                logger.info("swept fit '%s': resumed at sweep %d/%d",
                            name, start_sweep, cfg.n_iterations)
        with _ckpt.session(checkpointer):
            for i in range(start_sweep, cfg.n_iterations):
                scope = (checkpointer.scope(f"{stage}_s{i + 1}")
                         if checkpointer is not None
                         else contextlib.nullcontext())
                with scope, telemetry.span("swept_train", cat="train",
                                           coordinate=name, lanes=L):
                    W, res = coord.train_swept(offsets, reg, warm_start=W)
                # Live swept-sweep progress (ISSUE 10): the swept grid
                # bypasses the CD loop, so it reports its own
                # sweep-level trajectory for watch/ETA.
                _mon.progress("swept", i + 1, cfg.n_iterations,
                              unit="sweeps", coordinate=name, lanes=L,
                              lanes_done=int(jnp.sum(res.converged)))
                if validate:
                    with telemetry.span("swept_validation", cat="train",
                                        coordinate=name, lanes=L):
                        W_now = W[inv_idx]
                        for j in range(L):
                            snap = self._swept_lane_model(
                                coords, name, W_now[j], locked, offsets,
                                float(lams[j]), with_variances=False)
                            lane_history[j].append(
                                self._evaluate(snap, validation))
                # Sweep-boundary lane snapshots honor the same
                # ``checkpoint_every_sweeps`` cadence as maybe_save_cd —
                # the [L, dim] lane matrix is the expensive part of the
                # payload, and the final sweep always saves.
                if checkpointer is not None and (
                        (i + 1) == cfg.n_iterations
                        or (i + 1) % checkpointer.every_sweeps == 0):
                    res_summary = {
                        "lanes_converged": int(jnp.sum(res.converged)),
                        "max_solver_iterations": int(
                            jnp.max(res.iterations))}
                    checkpointer.save_stage(stage, {
                        "lams": [float(x) for x in lams],
                        "sweep": i + 1,
                        "W": W,   # internal λ-descending lane order
                        "lane_history": [
                            _serialize_validation(h)
                            for h in lane_history],
                        "res_summary": res_summary,
                    })
        elapsed = _time.perf_counter() - t0
        logger.info("swept fit: %d λ-lanes of '%s' in %.2fs", L, name,
                    elapsed)
        if res is not None:
            res_summary = {
                "lanes_converged": int(jnp.sum(res.converged)),
                "max_solver_iterations": int(jnp.max(res.iterations))}
        if run_logger is not None:
            run_logger.event(
                "swept_fit", coordinate=name, lanes=L,
                duration_s=round(elapsed, 4), **(res_summary or {}),
            )
        W_out = W[inv_idx]
        results = []
        for j in range(L):
            # The caller's λ, not the float32 round-trip (reg_weights
            # in the FitResult must equal the grid/proposal values).
            lam = float(lams[j])
            model = self._swept_lane_model(coords, name, W_out[j],
                                           locked, offsets, lam)
            if lane_history[j]:
                # The last sweep's snapshot IS the final model
                # (variances don't affect scoring) — _fit_point rule.
                evals = dict(lane_history[j][-1])
            else:
                evals = (self._evaluate(model, validation)
                         if validation is not None else {})
            results.append(FitResult(
                model=model, evaluations=evals,
                reg_weights={c.name: (lam if c.name == name
                                      else c.optimizer.reg_weight)
                             for c in cfg.coordinates},
                validation_history=lane_history[j],
            ))
        return results, W_out

    def _swept_setup(self, train: GameDataset, prep: dict, name: str,
                     lam_build: float):
        """Shared swept-fit preamble: coordinates built once (at the
        largest λ, so the reg context carries the intercept mask), warm
        coefficients, locked-coordinate filter, lane-shared offsets.

        Returns (coords, locked, offsets, base_w0)."""
        cfg = self.config
        coords = self._build_coordinates(train, prep, {name: lam_build})
        warm = self._warm_coefficients(coords, prep)
        locked = {n: warm[n] for n in cfg.locked_coordinates if n in warm}
        missing = set(cfg.locked_coordinates) - set(locked)
        if missing:
            raise ValueError(
                f"locked coordinates {sorted(missing)} absent from "
                "the warm-start model")
        offsets = self._locked_offsets(coords, locked, train)
        return coords, locked, offsets, warm.get(name)

    def _fit_grid_swept(self, train: GameDataset, prep: dict, name: str,
                        grid_points: list[dict], validation,
                        run_logger) -> list[FitResult]:
        """The whole ``reg_weight_grid`` as ONE batched sweep: L
        coefficient lanes share every objective evaluation (data
        stream) instead of paying one full fit per grid point.
        Returns results in grid order (the ``fit`` contract), with
        per-sweep ``validation_history`` per lane when
        ``validate_per_iteration`` is on — the same record the
        per-point path produces."""
        lams = [gp[name] for gp in grid_points]
        coords, locked, offsets, base_w0 = self._swept_setup(
            train, prep, name, max(lams))
        logger.info("fit: swept λ grid over '%s' (%d lanes)", name,
                    len(lams))
        results, _ = self._train_swept_lanes(
            coords, name, lams, offsets, locked, validation, run_logger,
            base_w0=base_w0,
            checkpointer=self._checkpointer(self.config.checkpoint_dir,
                                            run_logger),
            resume=self.config.resume)
        return results

    # -- fit ---------------------------------------------------------------

    def _checkpointer(self, ckpt_dir: str | None, run_logger):
        """Config-cadenced ``reliability.checkpoint.RunCheckpointer``
        for ``ckpt_dir`` (None when checkpointing is off).

        Under an active fleet context the directory is sharded per
        host (``host_NNN/`` subdir): every host snapshots its own
        replicated solver state plus its private fleet reduce
        sequence, so a killed host resumes from its OWN manifest
        without restarting — or reading the state of — its peers."""
        if not ckpt_dir:
            return None
        from photon_ml_tpu.parallel import fleet
        from photon_ml_tpu.reliability.checkpoint import RunCheckpointer

        ckpt_dir = fleet.host_dir(ckpt_dir, fleet.active())
        cfg = self.config
        return RunCheckpointer(
            ckpt_dir, every_sweeps=cfg.checkpoint_every_sweeps,
            every_solver_iters=cfg.checkpoint_every_solver_iters,
            run_logger=run_logger, resume=cfg.resume)

    def _grid_points(self) -> list[dict]:
        grid = self.config.reg_weight_grid
        if not grid:
            return [{}]
        names = sorted(grid)
        return [dict(zip(names, vals))
                for vals in itertools.product(*(grid[n] for n in names))]

    def _evaluate(self, model: GameModel, validation: GameDataset) -> dict:
        out = {}
        with telemetry.stage("validation", rows=int(validation.n)):
            transformer = GameTransformer(model=model, task=self.task)
            margins = jnp.asarray(transformer.transform(validation))
            labels = jnp.asarray(validation.labels.astype(np.float32))
            weights = jnp.asarray(validation.weight_array())
            for ev in self.config.evaluators:
                # RMSE/squared-loss evaluate mean-space, others
                # margin-space (reference per-evaluator score
                # conventions).
                scores = margins
                if ev.value in ("RMSE", "SQUARED_LOSS"):
                    scores = self.task.loss.mean(margins)
                out[ev] = float(evaluate(ev, scores, labels, weights))
        return out

    def _fit_point(self, train: GameDataset, prep: dict, reg_weights: dict,
                   validation: GameDataset | None, run_logger,
                   ckpt_tag: str | None = None,
                   checkpointing: bool = True) -> FitResult:
        """One full coordinate-descent fit at fixed λ per coordinate.

        ``checkpointing=False`` runs the point without checkpoint/
        resume machinery even when the config carries a checkpoint_dir
        — the non-swept tuned path, where per-trial fits sharing one
        directory would overwrite (and cross-resume) each other."""
        cfg = self.config
        coords = self._build_coordinates(train, prep, reg_weights)
        logger.info("fit: point %s", reg_weights or "(default)")

        warm = self._warm_coefficients(coords, prep)
        locked = {name: warm[name] for name in cfg.locked_coordinates
                  if name in warm}
        missing = set(cfg.locked_coordinates) - set(locked)
        if missing:
            raise ValueError(
                f"locked coordinates {sorted(missing)} absent from "
                "the warm-start model")
        initial = {n: w for n, w in warm.items() if n not in locked}

        ckpt_dir = cfg.checkpoint_dir if checkpointing else None
        if ckpt_dir and ckpt_tag:
            ckpt_dir = f"{ckpt_dir}/{ckpt_tag}"
        checkpointer = self._checkpointer(ckpt_dir, run_logger)
        validator = None
        if validation is not None and cfg.validate_per_iteration:
            # The reference's CoordinateDescent scores validation data
            # and logs every evaluator each sweep (SURVEY §2.3/§3.1):
            # snapshot the current coefficients into a (variance-free)
            # model and evaluate it.
            def validator(coefficients, _total_scores):
                snap = self._model_snapshot(coords, coefficients)
                return self._evaluate(snap, validation)

        fused = None
        if cfg.cd_fused:
            # Fused CD super-sweep (ISSUE 11): one streamed store pass
            # per cycle accumulates every coordinate's statistics.
            # Config.validate() already enforced the structural
            # requirements (chunk_rows, one fixed effect, smooth reg,
            # no locked coordinates, single device).
            from photon_ml_tpu.data.chunk_store import (
                SharedChunkWindow,
                resolve_spill_dir,
            )
            from photon_ml_tpu.game.fused_sweep import (
                build_fused_cycle_engine,
            )

            spill = resolve_spill_dir(cfg.spill_dir)
            group = getattr(self, "_chunk_window_group", None)
            if group is None and spill is not None:
                # The fused pass consumes FE chunk i AND sidecar chunk
                # i together every step; without a shared group each
                # spilled store pins its own host_max_resident window —
                # 2× the documented budget in the COMMON fused shape
                # (one spilled FE store, resident REs, so
                # _share_chunk_window saw < 2 stores).
                fe_store = next(
                    (c.chunked.store for c in coords.values()
                     if getattr(c, "chunked", None) is not None
                     and getattr(c.chunked, "store", None) is not None),
                    None)
                if fe_store is not None:
                    group = SharedChunkWindow(cfg.host_max_resident)
                    fe_store.join_window_group(group)
                    self._chunk_window_group = group
            fused = build_fused_cycle_engine(
                train, coords, cfg.update_sequence,
                re_shards={c.name: c.feature_shard
                           for c in cfg.coordinates},
                spill_dir=spill,
                host_max_resident=cfg.host_max_resident,
                prefetch_depth=cfg.prefetch_depth,
                retirement=cfg.re_retirement,
                window_group=group,
            )
        cd = run_coordinate_descent(
            coordinates=coords,
            update_sequence=cfg.update_sequence,
            n_iterations=cfg.n_iterations,
            validator=validator,
            locked_coordinates=locked,
            initial_coefficients=initial,
            checkpoint_dir=ckpt_dir,
            resume=cfg.resume and checkpointing,
            run_logger=run_logger,
            checkpointer=checkpointer,
            fused_engine=fused,
            offsets=_training_offsets(train),
        )
        model = self._to_game_model(coords, cd)
        if cd.validation_history:
            # The last sweep's snapshot IS the final model (variances
            # don't affect scoring) — no second validation pass needed.
            evals = dict(cd.validation_history[-1])
        else:
            evals = (self._evaluate(model, validation)
                     if validation is not None else {})
        return FitResult(
            model=model, evaluations=evals,
            reg_weights={c.name: reg_weights.get(
                c.name, c.optimizer.reg_weight)
                for c in cfg.coordinates},
            validation_history=cd.validation_history,
            descent=cd,
        )

    def fit(self, train: GameDataset,
            validation: GameDataset | None = None,
            run_logger=None) -> list[FitResult]:
        """Train the λ grid; returns results in grid order.

        An eligible fixed-effect grid (see ``_swept_coordinate_name``)
        trains as ONE batched sweep — every grid point shares each
        objective evaluation's data stream instead of paying its own
        full fit; other shapes fit once per grid point."""
        # Programmatic callers (no driver) get the warm compile path
        # too.
        from photon_ml_tpu.cache import enable_compilation_cache

        enable_compilation_cache()
        # Telemetry honors the config knob for programmatic callers too
        # (a driver-configured session takes precedence — maybe_session
        # is a no-op when one is already active).  The whole grid fit
        # is one top-level span so the report's reconciliation has a
        # wall-clock anchor on the main thread.
        # "estimator_fit", not "fit": the driver's timed fit phase is
        # already a span of that name, and a same-name nested span
        # double-counts in the report's stage table.
        with telemetry.maybe_session(
                self.config.telemetry,
                self.config.telemetry_dir or self.config.output_dir,
                run_logger=run_logger), \
                _mon.maybe_monitor(
                    self.config.monitor == "on", run_logger=run_logger,
                    status_port=self.config.status_port,
                    every_s=self.config.monitor_every_s), \
                telemetry.stage("estimator_fit", cat="phase",
                                fit=next(_FIT_NUMBER), rows=int(train.n)):
            prep = self._prepare(train)
            # Device-memory data point right after dataset placement
            # (ISSUE 8): the residency the HBM scale math sizes is the
            # post-ETL, pre-solve footprint — phase boundaries alone
            # would fold it into the fit-span sample.
            telemetry.device_memory("datasets_placed")
            grid_points = self._grid_points()
            name = self._swept_coordinate_name()
            if (len(grid_points) > 1 and name is not None
                    and set(self.config.reg_weight_grid) == {name}
                    and not self.config.cd_fused):
                # cd_fused trains grid points as separate fused fits —
                # the swept lane machinery solves per-coordinate.
                # Checkpointing no longer forces the sequential path
                # (ISSUE 9): the swept fit snapshots its lane state per
                # sweep and its solver state per iteration.
                return self._fit_grid_swept(train, prep, name,
                                            grid_points, validation,
                                            run_logger)
            return [
                self._fit_point(
                    train, prep, reg_weights, validation, run_logger,
                    ckpt_tag=(f"grid_{gi}" if len(grid_points) > 1
                              else None),
                )
                for gi, reg_weights in enumerate(grid_points)
            ]

    def fit_tuned(self, train: GameDataset, validation: GameDataset,
                  run_logger=None) -> list[FitResult]:
        """Bayesian/random tuning of per-coordinate reg weights
        (reference HyperparameterTuner wrapping GameEstimator.fit,
        SURVEY §3.5).  Returns one FitResult per trial, in trial order."""
        cfg = self.config
        tuning = cfg.tuning
        if tuning is None:
            raise ValueError("fit_tuned requires config.tuning")
        if not cfg.evaluators:
            raise ValueError("tuning needs at least one evaluator")
        ev = cfg.evaluators[0]
        with contextlib.ExitStack() as stack:
            stack.enter_context(telemetry.maybe_session(
                cfg.telemetry, cfg.telemetry_dir or cfg.output_dir,
                run_logger=run_logger))
            stack.enter_context(_mon.maybe_monitor(
                cfg.monitor == "on", run_logger=run_logger,
                status_port=cfg.status_port,
                every_s=cfg.monitor_every_s))
            stack.enter_context(telemetry.span("fit_tuned", cat="phase"))
            return self._fit_tuned_inner(train, validation, run_logger,
                                         ev, tuning)

    def _fit_tuned_inner(self, train, validation, run_logger, ev,
                         tuning) -> list[FitResult]:
        from photon_ml_tpu.hyperparameter import (
            HyperparameterTuner,
            ParamRange,
            ParamScale,
            SearchSpace,
            TunerMode,
        )

        cfg = self.config

        space = SearchSpace([
            ParamRange(name, r["low"], r["high"],
                       ParamScale(r.get("scale", "LOG")))
            for name, r in sorted(tuning.reg_weight_ranges.items())
        ])
        prep = self._prepare(train)
        tuner = HyperparameterTuner(
            space,
            mode=TunerMode(tuning.mode),
            larger_is_better=ev.larger_is_better,
            seed=tuning.seed,
        )

        swept_name = self._swept_coordinate_name()
        if (swept_name is not None
                and set(tuning.reg_weight_ranges) == {swept_name}):
            return self._fit_tuned_swept(train, prep, swept_name, tuner,
                                         validation, run_logger, ev)

        if cfg.checkpoint_dir:
            # Documented limit: tuner checkpointing rides the swept
            # batched evaluator (round-granular lane state); per-point
            # tuned fits run without checkpoints rather than dying.
            logger.warning(
                "checkpoint_dir is set but this tuning shape is not "
                "swept-eligible; running WITHOUT tuner checkpoints")

        def evaluate_fn(point: dict):
            result = self._fit_point(
                train, prep, dict(point), validation, run_logger,
                ckpt_tag=None, checkpointing=False)
            return result.evaluations[ev], result

        trials = tuner.run(evaluate_fn, tuning.n_trials,
                           run_logger=run_logger)
        return [t.payload for t in trials]

    def _fit_tuned_swept(self, train: GameDataset, prep: dict, name: str,
                         tuner, validation: GameDataset, run_logger,
                         ev) -> list[FitResult]:
        """Batched trial evaluation: each tuner round proposes a BATCH
        of λ points (``propose_batch`` — one GP fit per round) and the
        whole batch trains as one swept solve, so a round of q trials
        pays ~one fit's worth of data streams instead of q.

        Warm-start continuation across rounds: each new lane starts
        from the previous round's nearest-log-λ solution (lanes
        ordered λ-descending inside each solve)."""
        from photon_ml_tpu.game.coordinate_descent import (
            _revive_validation,
            _serialize_validation,
        )

        cfg = self.config
        tuning = cfg.tuning
        hi = float(tuning.reg_weight_ranges[name]["high"])
        coords, locked, offsets, base_w0 = self._swept_setup(
            train, prep, name, hi)
        prev: dict = {"lams": None, "W": None}
        ck = self._checkpointer(cfg.checkpoint_dir, run_logger)
        rounds: list = []
        restored: list = []
        if ck is not None and cfg.resume:
            # One stage file PER round (``tuner_hist_<r>``): each round
            # writes only its own lane matrix — a cumulative snapshot
            # would re-serialize every prior round's [L, d] matrix each
            # round (O(R²) checkpoint I/O over the search).
            while True:
                st = ck.load_stage(f"tuner_hist_{len(rounds)}")
                if st is None:
                    break
                rounds.append(st)
            # Restored tuner history (ISSUE 9): completed rounds feed
            # the search as observations, and their FitResults
            # materialize straight from the checkpointed lane matrix —
            # model export + saved metrics, NO re-training.
            for r in rounds:
                W_r = jnp.asarray(r["W"], jnp.float32)
                hists = r.get("histories") or []
                for j, lam in enumerate(r["lams"]):
                    lam = float(lam)
                    model = self._swept_lane_model(
                        coords, name, W_r[j], locked, offsets, lam)
                    evals = _revive_validation([r["evals"][j]])[0]
                    fr = FitResult(
                        model=model, evaluations=evals,
                        reg_weights={c.name: (lam if c.name == name
                                              else c.optimizer.reg_weight)
                                     for c in cfg.coordinates},
                        validation_history=_revive_validation(
                            hists[j] if j < len(hists) else []))
                    restored.append(({name: lam},
                                     float(r["values"][j]), fr))
                prev["lams"] = [float(x) for x in r["lams"]]
                prev["W"] = W_r
            if rounds:
                logger.info("tuned fit: restored %d trials from %d "
                            "checkpointed rounds", len(restored),
                            len(rounds))

        def evaluate_batch(configs: list[dict]):
            lams = [float(c[name]) for c in configs]
            warm_W = None
            if prev["W"] is not None:
                log_prev = np.log(np.maximum(
                    np.asarray(prev["lams"], np.float64), 1e-30))
                idx = [int(np.argmin(np.abs(
                    np.log(max(lam, 1e-30)) - log_prev)))
                    for lam in lams]
                warm_W = jnp.stack([prev["W"][i] for i in idx])
            results, W_out = self._train_swept_lanes(
                coords, name, lams, offsets, locked, validation,
                run_logger, warm_W=warm_W, base_w0=base_w0,
                checkpointer=ck, resume=cfg.resume,
                stage=f"tuner_round_{len(rounds)}")
            prev["lams"], prev["W"] = lams, W_out
            if ck is not None:
                rd = {
                    "lams": lams,
                    "values": [float(r.evaluations[ev])
                               for r in results],
                    "W": W_out,
                    "evals": _serialize_validation(
                        [r.evaluations for r in results]),
                    # Per-sweep validation trace per trial, so a
                    # restored round's FitResults keep the
                    # validation_history an uninterrupted run carries.
                    "histories": [_serialize_validation(
                        r.validation_history) for r in results],
                }
                rounds.append(rd)
                ck.save_stage(f"tuner_hist_{len(rounds) - 1}", rd)
            return [(r.evaluations[ev], r) for r in results]

        trials = tuner.run_batched(
            evaluate_batch, tuning.n_trials,
            batch_size=tuning.trial_batch, run_logger=run_logger,
            restored=restored)
        return [t.payload for t in trials]

    def best(self, results: list[FitResult]) -> FitResult:
        """Model selection by the first evaluator (reference rule)."""
        if not self.config.evaluators or not results[0].evaluations:
            return results[0]
        ev = self.config.evaluators[0]
        best = results[0]
        for r in results[1:]:
            if bool(better_than(ev, r.evaluations[ev], best.evaluations[ev])):
                best = r
        return best
