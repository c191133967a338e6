"""Typed configuration: the rebuild of the reference's Param plumbing.

Reference counterparts: the spark.ml ``Param``/``ParamMap`` objects +
Scopt CLI parsers on each driver (``GameTrainingDriver`` ~40 params,
``ScoptGameTrainingParametersParser``, coordinate-configuration strings
— photon-client ``com.linkedin.photon.ml.cli.game`` [expected paths,
mount unavailable — see SURVEY.md §2.8/§5.6]).

Design: one validated dataclass per concern, JSON in/out (the reference
passes coordinate configs as structured CLI strings; JSON is the honest
modern equivalent).  Validation happens in ``__post_init__``/
``validate`` — the reference's ``ParamValidators`` role.
"""

from __future__ import annotations

import dataclasses
import enum
import json
import os
from typing import Any

from photon_ml_tpu.data.normalization import NormalizationType
from photon_ml_tpu.evaluation.evaluators import EvaluatorType
from photon_ml_tpu.models.glm import TaskType
from photon_ml_tpu.ops.regularization import RegularizationType
from photon_ml_tpu.optim.base import OptimizerType
from photon_ml_tpu.optim.variance import VarianceComputationType


# ---------------------------------------------------------------------------
# Sanctioned environment fallbacks.  Every env knob the package reads is
# registered HERE with its meaning, and read through ``read_env`` —
# scattered raw ``os.environ`` reads are invisible configuration, and
# the photon-lint ``env-read`` rule rejects them anywhere else.
# ---------------------------------------------------------------------------

SANCTIONED_ENV = {
    "PHOTON_ML_TPU_PLAN_CACHE": (
        "default on-disk GRR plan cache dir (data.grr cache_dir=None)"),
    "PHOTON_ML_TPU_SPILL_DIR": (
        "default chunk-store spill dir (data.chunk_store"
        ".resolve_spill_dir)"),
    "PHOTON_ML_TPU_NATIVE": (
        "'0' forces the numpy ETL fallbacks (native bindings disabled)"),
    "PHOTON_ML_TPU_GRR": (
        "'0' forces the XLA fallback contraction off the Pallas kernel"),
    "JAX_COORDINATOR_ADDRESS": (
        "jax.distributed coordinator (multi-host init, training driver)"),
    "JAX_NUM_PROCESSES": "jax.distributed process count",
    "JAX_PROCESS_ID": "jax.distributed process id",
    "PHOTON_FLEET_NUM_HOSTS": (
        "local-fleet host count (parallel.fleet tcp transport — the "
        "fallback when jaxlib has no multiprocess CPU collectives)"),
    "PHOTON_FLEET_HOST_ID": "local-fleet host id (parallel.fleet)",
    "PHOTON_FLEET_COORDINATOR": (
        "local-fleet reduce coordinator host:port (parallel.fleet)"),
}


def read_env(name: str, default: str | None = None) -> str | None:
    """The one sanctioned ``os.environ`` read.

    Raises ``KeyError`` for an unregistered name — adding an env knob
    means registering it in ``SANCTIONED_ENV`` (with its meaning), so
    ``python -m photon_ml_tpu.analysis`` plus this registry is a
    complete inventory of the package's environment surface."""
    if name not in SANCTIONED_ENV:
        raise KeyError(
            f"env var {name!r} is not in config.SANCTIONED_ENV; "
            "register it (with a description) before reading it")
    return os.environ.get(name, default)


def _validate_monitor(cfg) -> None:
    """Shared live-monitoring knob validation (ISSUE 10) — both run
    configs carry the same monitor/monitor_every_s/status_port trio."""
    if cfg.monitor not in ("off", "on"):
        raise ValueError("monitor must be off|on")
    if cfg.monitor_every_s <= 0:
        raise ValueError("monitor_every_s must be positive")
    if cfg.status_port is not None and not (
            0 <= cfg.status_port <= 65535):
        raise ValueError("status_port must be in [0, 65535] "
                         "(0 = ephemeral)")


class CoordinateKind(str, enum.Enum):
    FIXED_EFFECT = "FIXED_EFFECT"
    RANDOM_EFFECT = "RANDOM_EFFECT"


@dataclasses.dataclass
class OptimizerSettings:
    """Per-coordinate optimizer configuration (reference
    ``FixedEffectOptimizationConfiguration`` /
    ``RandomEffectOptimizationConfiguration``)."""

    optimizer: OptimizerType = OptimizerType.LBFGS
    max_iters: int = 100
    tolerance: float = 1e-6
    regularization: RegularizationType = RegularizationType.L2
    reg_weight: float = 1.0
    elastic_net_alpha: float = 0.5  # only for ELASTIC_NET
    variance_type: VarianceComputationType = VarianceComputationType.NONE
    # Record per-solver-iteration (value, ‖g‖) history (reference
    # OptimizationStatesTracker, SURVEY §2.1/§5.5); the trace lands in
    # the run log's cd_coordinate events.  Costs two [max_iters+1]
    # arrays per solve.
    track_states: bool = False
    # TRON's inner conjugate-gradient loop: at most ``cg_max_iters``
    # Hessian-vector products an outer iteration (ignored by L-BFGS /
    # OWL-QN).
    cg_max_iters: int = 50

    def validate(self) -> None:
        # Coerce a raw-string variance_type to the enum ONCE, loudly
        # rejecting typos — downstream checks (chunked FULL-variance
        # guard, compute_variances dispatch) then compare enums, and an
        # unknown string can't silently fall through to full_variances
        # (review finding).
        if not isinstance(self.variance_type, VarianceComputationType):
            self.variance_type = VarianceComputationType(
                str(self.variance_type).upper())
        if self.max_iters <= 0:
            raise ValueError("max_iters must be positive")
        if self.tolerance <= 0:
            raise ValueError("tolerance must be positive")
        if (isinstance(self.cg_max_iters, bool)
                or not isinstance(self.cg_max_iters, int)
                or self.cg_max_iters <= 0):
            raise ValueError("cg_max_iters must be a positive integer")
        if self.reg_weight < 0:
            raise ValueError("reg_weight must be non-negative")
        if not 0.0 <= self.elastic_net_alpha <= 1.0:
            raise ValueError("elastic_net_alpha must be in [0, 1]")
        if (self.optimizer == OptimizerType.TRON
                and self.regularization in (RegularizationType.L1,
                                            RegularizationType.ELASTIC_NET)):
            raise ValueError("TRON cannot handle L1/elastic-net; use LBFGS")


@dataclasses.dataclass
class CoordinateConfig:
    """One GAME coordinate (reference coordinate-configuration params)."""

    name: str
    kind: CoordinateKind
    feature_shard: str
    entity_key: str | None = None          # RANDOM_EFFECT only
    optimizer: OptimizerSettings = dataclasses.field(
        default_factory=OptimizerSettings
    )
    down_sampling_rate: float | None = None  # FIXED_EFFECT only

    def validate(self) -> None:
        self.optimizer.validate()
        if self.kind == CoordinateKind.RANDOM_EFFECT and not self.entity_key:
            raise ValueError(
                f"random-effect coordinate '{self.name}' needs entity_key"
            )
        if self.down_sampling_rate is not None:
            if self.kind != CoordinateKind.FIXED_EFFECT:
                raise ValueError("down-sampling applies to fixed effects")
            if not 0.0 < self.down_sampling_rate <= 1.0:
                raise ValueError("down_sampling_rate must be in (0, 1]")


@dataclasses.dataclass
class TuningConfig:
    """Hyperparameter-tuning run settings (reference tuning params +
    search-space JSON, SURVEY §2.7)."""

    n_trials: int = 10
    mode: str = "BAYESIAN"                 # BAYESIAN | RANDOM
    # coordinate name → {"low": float, "high": float, "scale": "LOG"|"LINEAR"}
    reg_weight_ranges: dict[str, dict] = dataclasses.field(
        default_factory=dict
    )
    seed: int = 0
    # Trials proposed (and, when the workload is swept-eligible,
    # TRAINED) per batch: the batched λ-sweep evaluates a whole
    # proposal round as one fit, amortizing the data stream across the
    # round's lanes.  None = strategy default (RANDOM: 16 at a time —
    # swept solver state is O(m·L·dim), so lanes stay bounded;
    # BAYESIAN: small rounds so later proposals condition on earlier
    # observations).
    trial_batch: int | None = None

    def validate(self) -> None:
        if self.n_trials <= 0:
            raise ValueError("n_trials must be positive")
        if self.mode not in ("BAYESIAN", "RANDOM"):
            raise ValueError("tuning mode must be BAYESIAN or RANDOM")
        if self.trial_batch is not None and self.trial_batch <= 0:
            raise ValueError("trial_batch must be positive when set")
        if not self.reg_weight_ranges:
            raise ValueError("tuning needs reg_weight_ranges")
        for name, r in self.reg_weight_ranges.items():
            if "low" not in r or "high" not in r:
                raise ValueError(f"range for '{name}' needs low and high")


@dataclasses.dataclass
class TrainingConfig:
    """Full training-run configuration (reference ``GameTrainingDriver``
    params; SURVEY §2.8)."""

    task_type: TaskType
    coordinates: list[CoordinateConfig]
    update_sequence: list[str]
    input_path: str = ""
    input_format: str = "auto"             # auto | jsonl | libsvm
    validation_path: str | None = None
    validation_fraction: float = 0.0       # split from input if no file
    output_dir: str = "output"
    index_dir: str | None = None           # prebuilt index maps (else scan)
    dense_feature_shards: list[str] = dataclasses.field(default_factory=list)
    n_iterations: int = 1
    normalization: NormalizationType = NormalizationType.NONE
    evaluators: list[EvaluatorType] = dataclasses.field(
        default_factory=lambda: [EvaluatorType.AUC]
    )
    # Hyperparameter grid: per-coordinate reg-weight lists, cartesian over
    # coordinates (reference GameOptimizationConfiguration grid).
    reg_weight_grid: dict[str, list[float]] = dataclasses.field(
        default_factory=dict
    )
    # Bayesian/random tuning over reg weights (replaces the grid when set).
    tuning: TuningConfig | None = None
    model_output_mode: str = "BEST"        # ALL | BEST | EXPLICIT
    warm_start_model_dir: str | None = None
    locked_coordinates: list[str] = dataclasses.field(default_factory=list)
    # Incremental training: regularize toward the warm-start model's
    # coefficients with strength prior_weight/σ² when it has variances
    # (reference PriorDistribution semantics), added to the L2 term.
    # It reaches every coordinate the warm model has variances for:
    # the fixed effect (its variances), and each resident random
    # effect entity by entity (its SIMPLE ``variance_blocks``, mapped
    # onto this fit's entities and subspaces; an entity or a column the
    # warm model lacks has no prior).  Streamed random effects refuse
    # it; locked coordinates are not trained.
    use_warm_start_as_prior: bool = False
    prior_weight: float = 1.0
    checkpoint_dir: str | None = None      # per-CD-iteration checkpoints
    resume: bool = False                   # resume from latest checkpoint
    # Checkpoint cadence (reliability.checkpoint, ISSUE 9):
    # checkpoint_every_sweeps gates the CD sweep-boundary snapshot
    # (coefficients + score planes + streamed-RE retirement state; the
    # final sweep always snapshots).  checkpoint_every_solver_iters > 0
    # additionally snapshots the streaming L-BFGS/OWL-QN loop state
    # (coefficients, (s,y,ρ) memory, swept lane buffers) every N solver
    # iterations AND the CD position at every coordinate boundary, so a
    # SIGKILL mid-solve resumes mid-solve; 0 keeps sweep-boundary-only
    # checkpoints (the pre-round-14 behavior).
    checkpoint_every_sweeps: int = 1
    checkpoint_every_solver_iters: int = 0
    intercept: bool = True
    seed: int = 0
    # Score the validation set with every evaluator after each CD sweep
    # (reference CoordinateDescent behavior, SURVEY §3.1); the trace
    # lands in FitResult.validation_history + run-log cd_validation
    # events.  Costs one validation transform per sweep.
    validate_per_iteration: bool = True
    # Sparse fixed-effect batch layout: AUTO picks the GRR compiled plan
    # (data/grr.py — the fast TPU path) on TPU backends and plain ELL
    # elsewhere; GRR/COLMAJOR/ELL force a specific layout.
    sparse_layout: str = "AUTO"
    # Device-mesh training (reference: the Spark cluster; SURVEY §3.1):
    # when set, fixed-effect batches are example-sharded over an
    # n_devices data mesh with the psum-reduced objective, and
    # random-effect bucket blocks are entity-sharded (strategy #2).
    # None = single device.
    n_devices: int | None = None
    # Chunk-accumulated (beyond-HBM-residency) fixed-effect training
    # (reference: Spark streams splits through executors, SURVEY §1
    # L1/§5.8; see data/chunked_batch.py): when set, sparse fixed
    # effects are compiled into ceil(n/chunk_rows) congruent chunk
    # batches streamed through HBM per objective evaluation, solved by
    # the host-driven streaming L-BFGS.  Composes with n_devices
    # (chunks × shards).  chunk_layout picks the per-chunk layout: AUTO
    # = GRR on TPU (kernel-speed steps, ~1.6 GB/10⁶ examples streamed)
    # else ELL (8 bytes/nnz — when transfer dominates).
    # chunk_max_resident chunks stay live in HBM across evaluations
    # (set ≥ n_chunks when the dataset fits; transfer then happens
    # once).
    chunk_rows: int | None = None
    chunk_layout: str = "AUTO"
    chunk_max_resident: int = 1
    # Out-of-core chunk store (data/chunk_store.py): spill_dir (default
    # $PHOTON_ML_TPU_SPILL_DIR; None = chunks stay host-resident)
    # activates the disk tier — chunk batches spill to atomic
    # content-keyed .npz files at build time, at most host_max_resident
    # decoded chunks stay live in host RAM (memory-mapped, LRU), and a
    # background prefetch thread overlaps disk read → host staging →
    # async device_put of chunks i+1..i+prefetch_depth under chunk i's
    # device compute.  Host RSS is then bounded by the WINDOW and the
    # trainable size by disk; spilled files double as a persistent
    # warm-ETL artifact (same data + config ⇒ the chunk compile is
    # skipped on the next run).  prefetch_depth=0 disables the thread
    # (chunks load synchronously from the store).
    spill_dir: str | None = None
    host_max_resident: int = 2
    prefetch_depth: int = 2
    # Out-of-core random-effect training (game/coordinates.py
    # StreamedRandomEffectCoordinate, ISSUE 5): when set, every
    # random-effect coordinate's entity blocks are split into
    # fixed-shape chunks of re_chunk_entities entities per size bucket,
    # spilled through the chunk store (same spill_dir /
    # host_max_resident window / prefetch_depth pipeline as chunked
    # fixed effects), and solved chunk-by-chunk by the vmapped masked
    # while_loop — HBM/host residency is bounded by the window instead
    # of the entity count.  Requires spill_dir (or
    # $PHOTON_ML_TPU_SPILL_DIR).  With a mesh (n_devices) the chunk
    # size rounds up to the device grid and every chunk entity-shards.
    re_chunk_entities: int | None = None
    # Converged-entity retirement (streamed REs only): between CD
    # sweeps, entities whose coefficients AND offsets moved less than
    # the solver tolerance are frozen (scores stay folded into totals)
    # and later sweeps solve only the active set; a retired entity
    # wakes if its offsets drift past the tolerance, so the final model
    # stays within solver tolerance of the retirement-off fit.
    re_retirement: bool = True
    # Fused CD super-sweep (game/fused_sweep.py, ISSUE 11): when true,
    # each coordinate-descent cycle is ONE streamed store pass that
    # accumulates the fixed effect's loss/grad/Hessian-diagonal
    # partials AND every random effect's per-entity statistics, then
    # solves all coordinates against cycle-START offsets (Jacobi
    # staleness) — ~1 data pass per cycle instead of C coordinates ×
    # solver iterations.  Per-cycle progress is one damped Newton step
    # per coordinate, so fused runs want MORE (cheap) cycles
    # (n_iterations) than per-coordinate runs; both converge to the
    # same block-stationary point.  Requires chunk_rows (the fixed
    # effect's chunk grid is the master cycle grid), exactly one
    # fixed-effect coordinate, smooth regularization (NONE/L2) on every
    # coordinate, no locked coordinates, and single-device execution.
    cd_fused: bool = False
    # Warm-path plan cache (photon_ml_tpu.cache): plan_cache_dir
    # persists compiled GRR plans keyed by dataset fingerprint ×
    # plan-config × planner version, so the second run of a workload
    # skips the plan ETL.  May also be set via PHOTON_ML_TPU_PLAN_CACHE.
    # (The XLA compilation cache is not a config field: its place is
    # cache.compile_cache's decision alone.)
    plan_cache_dir: str | None = None
    # When set, the driver's fit phase runs under jax.profiler.trace
    # and a TensorBoard/XProf device trace is written here (SURVEY §5.1).
    profile_dir: str | None = None
    # Pipeline telemetry (photon_ml_tpu.telemetry, ISSUE 7):
    # "off" (default) = the no-op singleton — zero events, zero extra
    # compiles, no measurable pass-time overhead; "metrics" = counters/
    # gauges/histograms + per-name span duration stats (the
    # telemetry_summary event); "trace" = metrics plus full span
    # retention, per-span run-log events, and a Chrome trace-event
    # trace.json (Perfetto-loadable) in telemetry_dir.  telemetry_dir
    # defaults to output_dir.  Analyze with
    # `python -m photon_ml_tpu.telemetry report <run_log.jsonl>`.
    telemetry: str = "off"
    telemetry_dir: str | None = None
    # Live run monitoring (photon_ml_tpu.telemetry.monitor, ISSUE 10):
    # "on" emits cadence-throttled `progress` events (phase, units
    # done/total, rolling throughput, ETA) from the CD loop, streaming
    # solvers, streamed-RE sweeps, and the tuner into the run log, and
    # evaluates the online anomaly rules (diverging loss, throughput
    # collapse, retry storms, ...) at the same cadence, emitting
    # structured `alert` events.  Follow live with
    # `python -m photon_ml_tpu.telemetry watch <run_log.jsonl>`.
    # "off" (default) is the no-op singleton: zero events, zero extra
    # compiles, no status thread.  monitor_every_s is the snapshot
    # cadence; status_port (0 = ephemeral) additionally serves
    # GET /status (JSON) and GET /metrics (Prometheus text) from a
    # localhost stdlib http.server thread — setting it implies
    # monitor="on".
    monitor: str = "off"
    monitor_every_s: float = 2.0
    status_port: int | None = None
    # Multi-host scale-out (SURVEY §5.8/§7 stage 9): when true, the
    # training driver calls jax.distributed.initialize() before any
    # backend use (coordinator/process env read from the standard JAX
    # env vars or cluster auto-detection).  The mesh then spans every
    # process's local devices, with XLA collectives riding ICI within a
    # slice and DCN across slices.  Single-process runs leave it false.
    distributed_init: bool = False

    def validate(self) -> None:
        names = [c.name for c in self.coordinates]
        if len(set(names)) != len(names):
            raise ValueError("duplicate coordinate names")
        for c in self.coordinates:
            c.validate()
        for s in self.update_sequence:
            if s not in names:
                raise ValueError(f"update_sequence entry '{s}' unknown")
        for s in self.locked_coordinates:
            if s not in names:
                raise ValueError(f"locked coordinate '{s}' unknown")
        if self.locked_coordinates and not self.warm_start_model_dir:
            raise ValueError(
                "locked_coordinates require warm_start_model_dir (locked "
                "coefficients come from the previous model)"
            )
        if self.use_warm_start_as_prior and not self.warm_start_model_dir:
            raise ValueError(
                "use_warm_start_as_prior requires warm_start_model_dir"
            )
        if self.resume and not self.checkpoint_dir:
            raise ValueError("resume requires checkpoint_dir")
        if self.checkpoint_every_sweeps < 1:
            raise ValueError("checkpoint_every_sweeps must be >= 1")
        if self.checkpoint_every_solver_iters < 0:
            raise ValueError(
                "checkpoint_every_solver_iters must be >= 0")
        if not 0.0 <= self.validation_fraction < 1.0:
            raise ValueError("validation_fraction must be in [0, 1)")
        if self.n_iterations <= 0:
            raise ValueError("n_iterations must be positive")
        if self.model_output_mode not in ("ALL", "BEST", "EXPLICIT"):
            raise ValueError("model_output_mode must be ALL|BEST|EXPLICIT")
        if self.sparse_layout not in ("AUTO", "GRR", "COLMAJOR", "ELL"):
            raise ValueError("sparse_layout must be AUTO|GRR|COLMAJOR|ELL")
        if self.telemetry not in ("off", "metrics", "trace"):
            raise ValueError("telemetry must be off|metrics|trace")
        _validate_monitor(self)
        if self.chunk_layout not in ("AUTO", "GRR", "ELL"):
            raise ValueError("chunk_layout must be AUTO|GRR|ELL")
        if self.host_max_resident < 1:
            raise ValueError("host_max_resident must be >= 1")
        if self.prefetch_depth < 0:
            raise ValueError("prefetch_depth must be >= 0")
        if (self.spill_dir is not None and self.chunk_rows is None
                and self.re_chunk_entities is None):
            raise ValueError(
                "spill_dir requires chunked training (chunk_rows) or "
                "streamed random effects (re_chunk_entities): only "
                "chunk batches spill to the disk tier")
        if self.re_chunk_entities is not None:
            if self.re_chunk_entities <= 0:
                raise ValueError("re_chunk_entities must be positive")
            from photon_ml_tpu.data.chunk_store import resolve_spill_dir

            if resolve_spill_dir(self.spill_dir) is None:
                raise ValueError(
                    "re_chunk_entities requires spill_dir (or "
                    "$PHOTON_ML_TPU_SPILL_DIR): streamed random-effect "
                    "training is store-backed")
        if self.chunk_rows is not None:
            if self.chunk_rows <= 0:
                raise ValueError("chunk_rows must be positive")
            if self.chunk_max_resident < 0:
                raise ValueError("chunk_max_resident must be >= 0")
            for c in self.coordinates:
                if (c.kind == CoordinateKind.FIXED_EFFECT
                        and c.down_sampling_rate is not None):
                    raise ValueError(
                        "down-sampling is not supported with chunked "
                        "training (chunk_rows)")
                if (c.kind == CoordinateKind.FIXED_EFFECT
                        and c.optimizer.variance_type
                        == VarianceComputationType.FULL):
                    raise ValueError(
                        "FULL variances materialize a [d, d] Hessian — "
                        "not supported with chunked training "
                        "(chunk_rows); use SIMPLE")
            if self.normalization != NormalizationType.NONE:
                raise ValueError(
                    "normalization requires resident feature statistics; "
                    "not supported with chunked training (chunk_rows)")
        if self.cd_fused:
            if self.chunk_rows is None:
                raise ValueError(
                    "cd_fused requires chunked training (chunk_rows): "
                    "the fixed effect's chunk grid is the fused cycle's "
                    "master grid")
            if self.locked_coordinates:
                raise ValueError(
                    "cd_fused does not support locked_coordinates (the "
                    "fused pass composes every coordinate's margins "
                    "from live coefficients)")
            if self.n_devices is not None:
                raise ValueError(
                    "cd_fused is single-device (the fused per-chunk "
                    "program is not mesh-sharded); drop n_devices")
            fixed = [c for c in self.coordinates
                     if c.name in self.update_sequence
                     and c.kind == CoordinateKind.FIXED_EFFECT]
            if len(fixed) != 1:
                raise ValueError(
                    "cd_fused requires exactly one fixed-effect "
                    f"coordinate in update_sequence (got {len(fixed)})")
            for c in self.coordinates:
                if (c.name in self.update_sequence
                        and c.optimizer.regularization
                        not in (RegularizationType.NONE,
                                RegularizationType.L2)):
                    raise ValueError(
                        "cd_fused requires smooth regularization "
                        "(NONE or L2) on every coordinate; "
                        f"'{c.name}' uses "
                        f"{c.optimizer.regularization.value} — the "
                        "Jacobi Newton solves have no proximal step")
        if self.n_devices is not None:
            if self.n_devices <= 0:
                raise ValueError("n_devices must be positive")
            for c in self.coordinates:
                if c.down_sampling_rate is not None:
                    raise ValueError(
                        "down-sampling is not supported with mesh "
                        "training (n_devices); the row subset would "
                        "cross shard boundaries"
                    )
        for name, grid in self.reg_weight_grid.items():
            if name not in names:
                raise ValueError(f"grid entry '{name}' unknown")
            if not grid:
                raise ValueError(f"empty grid for '{name}'")
        if self.tuning is not None:
            self.tuning.validate()
            if self.reg_weight_grid:
                raise ValueError("tuning and reg_weight_grid are exclusive")
            if not self.evaluators:
                raise ValueError("tuning needs at least one evaluator")
            for name in self.tuning.reg_weight_ranges:
                if name not in names:
                    raise ValueError(f"tuning range '{name}' unknown")


@dataclasses.dataclass
class ScoringConfig:
    """Scoring-run configuration (reference ``GameScoringDriver``)."""

    input_path: str
    model_dir: str
    output_path: str = "scores.npz"
    input_format: str = "auto"             # auto | jsonl | libsvm
    index_dir: str | None = None           # default: <model_dir>/../index_maps
    dense_feature_shards: list[str] = dataclasses.field(default_factory=list)
    evaluators: list[EvaluatorType] = dataclasses.field(default_factory=list)
    # Streaming fused scoring (estimators.streaming_scorer, ISSUE 4):
    # score_chunk_rows activates the one-pass chunked pipeline — every
    # coordinate scored by ONE fused device program per fixed-shape
    # chunk, output sinks and evaluators fed chunk-wise (streaming
    # accumulators), so peak memory is bounded by the chunk window, not
    # the dataset.  None keeps the per-coordinate resident transform.
    # spill_dir (default $PHOTON_ML_TPU_SPILL_DIR, same env as
    # training) spills prepared score chunks to content-keyed .npz
    # files (memory-mapped back, LRU host_max_resident window; spilled
    # chunks double as a warm-scoring artifact across runs);
    # prefetch_depth runs the background disk→host→device prefetch
    # thread (0 = synchronous).
    score_chunk_rows: int | None = None
    spill_dir: str | None = None
    host_max_resident: int = 2
    prefetch_depth: int = 2
    # Pipeline telemetry (see TrainingConfig.telemetry): off | metrics
    # | trace; telemetry_dir defaults to the output file's directory.
    telemetry: str = "off"
    telemetry_dir: str | None = None
    # Live run monitoring (see TrainingConfig.monitor): progress/ETA
    # snapshots + online alerts from the streaming scorer; status_port
    # serves /status + /metrics (implies monitor="on").
    monitor: str = "off"
    monitor_every_s: float = 2.0
    status_port: int | None = None

    def validate(self) -> None:
        if self.score_chunk_rows is not None and self.score_chunk_rows <= 0:
            raise ValueError("score_chunk_rows must be positive")
        if self.telemetry not in ("off", "metrics", "trace"):
            raise ValueError("telemetry must be off|metrics|trace")
        _validate_monitor(self)
        if self.host_max_resident < 1:
            raise ValueError("host_max_resident must be >= 1")
        if self.prefetch_depth < 0:
            raise ValueError("prefetch_depth must be >= 0")
        if self.spill_dir is not None and self.score_chunk_rows is None:
            raise ValueError(
                "spill_dir requires streamed scoring (score_chunk_rows):"
                " only score chunks spill to the disk tier")


@dataclasses.dataclass
class ServingConfig:
    """Model-server configuration (ISSUE 12): the persistent online
    scoring process — ``python -m photon_ml_tpu.serving``."""

    # Model source: a checkpoint-manifest directory (model_manifest.npz
    # — the hot-swap unit) or a legacy metadata.json model dir; both go
    # through io.model_io.load_game_model (the shared loading path).
    model_dir: str
    # HTTP bind (127.0.0.1 only — front a proxy for external traffic);
    # port 0 asks the kernel for an ephemeral port (the bound port is
    # in ModelServer.port and the --info-file).
    host: str = "127.0.0.1"
    port: int = 0
    # Micro-batching: concurrent requests coalesce for up to
    # batch_deadline_ms, then dispatch as ONE fused device program call
    # padded to the smallest bucket ≥ the batch's row count.  Buckets
    # are the CLOSED shape set (default: powers of two up to
    # batch_rows) — every bucket is compiled at warm-up, so the steady
    # state pays zero compiles (guard-pinned).  Oversized requests
    # split across buckets.
    batch_rows: int = 64
    batch_buckets: list[int] | None = None
    batch_deadline_ms: float = 2.0
    max_queue: int = 1024
    request_timeout_s: float = 30.0
    # Sparse fixed-effect request rows densify to ELL at this per-row
    # capacity (part of the closed shape set); a request row with more
    # non-zeros answers 400 naming this knob.
    ell_row_capacity: int = 64
    # Feature shards served as dense vectors (same knob as
    # ScoringConfig); non-projected random-effect shards are dense
    # automatically — the model knows which those are.
    dense_feature_shards: list[str] = dataclasses.field(
        default_factory=list)
    # Random-effect coefficient store (serving.entity_store): with a
    # spill dir (default $PHOTON_ML_TPU_SPILL_DIR) coefficients live in
    # content-keyed chunked .npz files of entity_chunk entities,
    # memory-mapped back through an LRU host_max_resident window, with
    # a persistent entity-id → (chunk, row) index — host RSS is bounded
    # by the window, not the entity count, and a restart with the same
    # model reuses the files.  None keeps coefficients host-resident.
    spill_dir: str | None = None
    entity_chunk: int = 4096
    host_max_resident: int = 4
    # Hot model swap: poll the model dir's manifest at this cadence and
    # atomically switch to a newly published manifest between batches
    # (zero dropped requests; a corrupt manifest keeps the previous
    # good model).  0 disables the watcher.
    hot_swap_poll_s: float = 2.0
    # Telemetry/monitoring: the request path is instrumented (latency
    # histograms, queue-depth gauge, batch-fill counters) through a
    # telemetry session and the live monitor's alert rules (incl.
    # serve_tail_latency) — both ON by default: a server without
    # metrics is blind.  /status + /metrics ride the serving port.
    telemetry: str = "metrics"
    monitor: str = "on"
    monitor_every_s: float = 2.0
    status_port: int | None = None   # unused: /status rides the port
    log_path: str | None = None      # run-log JSONL (default: stderr)
    # --- Resilient fleet (ISSUE 13) ------------------------------------
    # replicas > 1 runs the supervised fleet: N replica ModelServer
    # subprocesses behind one health-routed frontend (serving.fleet /
    # serving.frontend).  The frontend binds `port`; replicas take
    # ephemeral ports and are restarted on crash or wedge.
    replicas: int = 1
    # Health probing: each replica's /healthz is polled every
    # probe_every_s with probe_timeout_s per probe; unhealthy_after
    # consecutive failed probes on a live process mark it wedged (it is
    # killed and restarted like a crash).
    probe_every_s: float = 0.5
    probe_timeout_s: float = 2.0
    unhealthy_after: int = 3
    # Restart policy: bounded exponential backoff between restarts of
    # the same replica (base doubling, capped), and a circuit breaker —
    # breaker_threshold restarts inside breaker_window_s opens the
    # breaker for breaker_reset_s (no restarts), after which ONE
    # half-open attempt either closes it (replica reaches ready) or
    # re-opens it.
    restart_backoff_s: float = 0.5
    restart_backoff_max_s: float = 10.0
    breaker_threshold: int = 5
    breaker_window_s: float = 30.0
    breaker_reset_s: float = 30.0
    # A (re)spawned replica that has not reached ready within this
    # budget counts as a failed start (killed, backoff applies).
    replica_ready_timeout_s: float = 300.0
    # Per-connection socket timeout on the HTTP cores (frontend and
    # replicas): a stalled client is disconnected instead of pinning a
    # handler thread forever.
    http_timeout_s: float = 30.0
    # --- Request tracing (ISSUE 14) ------------------------------------
    # End-to-end request tracing: per-request stage timestamps (+ the
    # shared micro-batch span), trace-id propagation across the fleet,
    # and tail-based sampling into a bounded ring buffer + request_trace
    # JSONL events.  "on" costs ≤2% on p50 (guard-pinned A/B, PERF.md
    # round 19); "off" is the pre-tracing request path bit for bit.
    trace: str = "on"
    # Tail threshold: a request slower than this is retained (sampled
    # as "tail"); every trace_sample_every-th request is retained
    # regardless (the deterministic floor; 0 disables the floor).
    trace_threshold_ms: float = 50.0
    trace_sample_every: int = 100
    # Retained traces kept in process memory (the /status view); every
    # retained trace is also a request_trace event on the run log.
    trace_buffer: int = 512

    def validate(self) -> None:
        if not self.model_dir:
            raise ValueError("serving needs model_dir")
        if not 0 <= self.port <= 65535:
            raise ValueError("port must be in [0, 65535] (0 = ephemeral)")
        if self.batch_rows <= 0:
            raise ValueError("batch_rows must be positive")
        if self.batch_buckets is not None:
            b = list(self.batch_buckets)
            if not b or any(int(x) <= 0 for x in b):
                raise ValueError("batch_buckets must be positive")
            if sorted(set(int(x) for x in b)) != [int(x) for x in b]:
                raise ValueError(
                    "batch_buckets must be strictly ascending")
            if int(b[-1]) != self.batch_rows:
                raise ValueError(
                    "batch_buckets must end at batch_rows (the largest "
                    "bucket IS the max micro-batch)")
        if self.batch_deadline_ms < 0:
            raise ValueError("batch_deadline_ms must be >= 0")
        if self.max_queue <= 0:
            raise ValueError("max_queue must be positive")
        if self.request_timeout_s <= 0:
            raise ValueError("request_timeout_s must be positive")
        if self.ell_row_capacity <= 0:
            raise ValueError("ell_row_capacity must be positive")
        if self.entity_chunk <= 0:
            raise ValueError("entity_chunk must be positive")
        if self.host_max_resident < 1:
            raise ValueError("host_max_resident must be >= 1")
        if self.hot_swap_poll_s < 0:
            raise ValueError("hot_swap_poll_s must be >= 0 (0 = off)")
        if self.telemetry not in ("off", "metrics", "trace"):
            raise ValueError("telemetry must be off|metrics|trace")
        _validate_monitor(self)
        if self.replicas < 1:
            raise ValueError("replicas must be >= 1")
        if self.probe_every_s <= 0:
            raise ValueError("probe_every_s must be positive")
        if self.probe_timeout_s <= 0:
            raise ValueError("probe_timeout_s must be positive")
        if self.unhealthy_after < 1:
            raise ValueError("unhealthy_after must be >= 1")
        if self.restart_backoff_s < 0:
            raise ValueError("restart_backoff_s must be >= 0")
        if self.restart_backoff_max_s < self.restart_backoff_s:
            raise ValueError(
                "restart_backoff_max_s must be >= restart_backoff_s")
        if self.breaker_threshold < 1:
            raise ValueError("breaker_threshold must be >= 1")
        if self.breaker_window_s <= 0:
            raise ValueError("breaker_window_s must be positive")
        if self.breaker_reset_s <= 0:
            raise ValueError("breaker_reset_s must be positive")
        if self.replica_ready_timeout_s <= 0:
            raise ValueError("replica_ready_timeout_s must be positive")
        if self.http_timeout_s <= 0:
            raise ValueError("http_timeout_s must be positive")
        if self.trace not in ("on", "off"):
            raise ValueError("trace must be on|off")
        if self.trace_threshold_ms < 0:
            raise ValueError("trace_threshold_ms must be >= 0")
        if self.trace_sample_every < 0:
            raise ValueError(
                "trace_sample_every must be >= 0 (0 = no floor)")
        if self.trace_buffer < 1:
            raise ValueError("trace_buffer must be >= 1")

    def buckets(self) -> list[int]:
        """The closed micro-batch shape set, smallest first."""
        if self.batch_buckets is not None:
            return [int(b) for b in self.batch_buckets]
        out, b = [], 1
        while b < self.batch_rows:
            out.append(b)
            b *= 2
        out.append(self.batch_rows)
        return out


# ---------------------------------------------------------------------------
# JSON (de)serialization.  Enums serialize by value; nested dataclasses by
# field name — forgiving on input (unknown keys rejected, enums by name or
# value).
# ---------------------------------------------------------------------------

def _to_jsonable(obj: Any) -> Any:
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {f.name: _to_jsonable(getattr(obj, f.name))
                for f in dataclasses.fields(obj)}
    if isinstance(obj, enum.Enum):
        return obj.value
    if isinstance(obj, dict):
        return {k: _to_jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_to_jsonable(v) for v in obj]
    return obj


def config_to_json(config) -> str:
    return json.dumps(_to_jsonable(config), indent=2)


def _build(cls, data: Any):
    if dataclasses.is_dataclass(cls) and isinstance(data, dict):
        fields = {f.name: f for f in dataclasses.fields(cls)}
        unknown = set(data) - set(fields)
        if unknown:
            raise ValueError(f"unknown config keys for {cls.__name__}: "
                             f"{sorted(unknown)}")
        kwargs = {}
        for k, v in data.items():
            kwargs[k] = _coerce(fields[k].type, v)
        return cls(**kwargs)
    return data


_ENUMS = {
    "TaskType": TaskType,
    "CoordinateKind": CoordinateKind,
    "OptimizerType": OptimizerType,
    "RegularizationType": RegularizationType,
    "NormalizationType": NormalizationType,
    "EvaluatorType": EvaluatorType,
    "VarianceComputationType": VarianceComputationType,
}


def _coerce(type_str, v):
    """Best-effort typed coercion from annotation strings (PEP 563)."""
    t = type_str if isinstance(type_str, str) else getattr(
        type_str, "__name__", str(type_str))
    if isinstance(v, list):
        if "CoordinateConfig" in t:
            return [_build(CoordinateConfig, c) for c in v]
        for name, enum_cls in _ENUMS.items():
            if name in t:
                return [enum_cls(e) if isinstance(e, str) else e for e in v]
        return v
    if isinstance(v, str):
        for name, enum_cls in _ENUMS.items():
            if name in t:
                try:
                    return enum_cls(v)
                except ValueError:
                    return enum_cls[v]
    if "OptimizerSettings" in t and isinstance(v, dict):
        return _build(OptimizerSettings, v)
    if "TuningConfig" in t and isinstance(v, dict):
        return _build(TuningConfig, v)
    return v


def training_config_from_json(text: str) -> TrainingConfig:
    cfg = _build(TrainingConfig, json.loads(text))
    cfg.validate()
    return cfg


def scoring_config_from_json(text: str) -> ScoringConfig:
    cfg = _build(ScoringConfig, json.loads(text))
    cfg.validate()
    return cfg


def serving_config_from_json(text: str) -> ServingConfig:
    cfg = _build(ServingConfig, json.loads(text))
    cfg.validate()
    return cfg


def load_training_config(path: str) -> TrainingConfig:
    with open(path) as f:
        return training_config_from_json(f.read())


def load_scoring_config(path: str) -> ScoringConfig:
    with open(path) as f:
        return scoring_config_from_json(f.read())


def load_serving_config(path: str) -> ServingConfig:
    with open(path) as f:
        return serving_config_from_json(f.read())
