"""Coordinate descent: the GAME outer loop.

Reference counterpart: ``CoordinateDescent``
(photon-api ``com.linkedin.photon.ml.algorithm.CoordinateDescent``
[expected path, mount unavailable — see SURVEY.md §2.3/§3.1]).

Semantics mirror the reference exactly:

    for iteration 1..N:
      for coordinate in update_sequence:
        offsets   = total_scores − coordinate_scores[coordinate]
        model     = coordinate.train(offsets, warm start = prior coefs)
        scores    = coordinate.score(model)
        total     = total − old_scores + new_scores
      (validation metrics once per iteration)

The loop itself is host-level Python — like the reference's driver loop
— but every ``train``/``score`` inside it is a single jitted device
program, so per-coordinate work is one dispatch, and scores/offsets
live on device for the whole descent (no host round-trips between
coordinates).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.reliability import checkpoint as _ckpt
from photon_ml_tpu.telemetry import convergence as _conv
from photon_ml_tpu.telemetry import monitor as _mon
from photon_ml_tpu.game.coordinates import Coordinate
from photon_ml_tpu.optim.base import OptimizationResult

logger = logging.getLogger(__name__)


def _serialize_history(history: list) -> list:
    """Per-iteration diagnostics → checkpoint-tree form (raw
    OptimizationResult diagnostics reduce through ``_diag_fields``;
    already-serialized entries — a resumed run's restored prefix —
    pass through)."""
    out = []
    for iter_diag in history:
        out.append({name: (diag if isinstance(diag, dict)
                           else _diag_fields(diag))
                    for name, diag in iter_diag.items()})
    return out


def _serialize_validation(entries: list) -> list:
    out = []
    for e in entries:
        if isinstance(e, dict):
            out.append({str(getattr(k, "value", k)): float(v)
                        for k, v in e.items()})
        else:
            out.append(float(e))
    return out


def _revive_validation(entries: list) -> list:
    """Inverse of ``_serialize_validation``: dict keys come back as
    ``EvaluatorType`` where they parse (downstream model selection
    indexes evaluations by the enum), else stay strings."""
    from photon_ml_tpu.evaluation.evaluators import EvaluatorType

    out = []
    for e in entries or []:
        if isinstance(e, dict):
            revived = {}
            for k, v in e.items():
                try:
                    revived[EvaluatorType(k)] = v
                except ValueError:
                    revived[k] = v
            out.append(revived)
        else:
            out.append(e)
    return out


@jax.jit
def _re_diag_reduce(diag):
    """Batched-RE convergence aggregation as ONE device program: the
    per-bucket Python loop of ``jnp.sum``/``jnp.max`` calls performed
    one blocking host sync per bucket per stat (ISSUE 5 satellite);
    this folds every bucket's reduction into a single dispatch whose
    result is fetched with one bulk device→host copy per sweep."""
    conv = sum(jnp.sum(r.converged.astype(jnp.int32)) for r in diag)
    iters = jnp.max(jnp.stack([jnp.max(r.iterations) for r in diag]))
    return conv, iters


# TRON's counts (``optim.base.OptimizationResult``), where it made them
_TRON_COUNTS = ("cg_steps", "hvp_passes", "forward_passes")


def _solve_counts(diag) -> dict:
    """What one solve did, for the ``coord_train`` stage (a scalar
    ``OptimizationResult``): its iterations, the line-search trials they
    paid (the solve's own count where a trial is a contraction, else
    the tracker's plane, where states are tracked) and the forward
    contractions X·v, where the solver counts them: ``iterations + 1``
    along the margins; for an L1 coordinate ``1 + ls_trials −
    walked_trials`` and one X·d for each search that walked, with
    ``walked_trials`` (the trials the orthant projection clipped nothing
    of) beside them; TRON's CG steps and Hessian-vector products.  A random
    effect's per-bucket list of batched TRON results gives each of
    TRON's counts summed over its lanes, as ``lane_<count>``: no stage
    of a random effect carries a key that the fixed effect's readers
    sum.  One device→host copy for all of them."""
    if isinstance(diag, (list, tuple)) and diag \
            and isinstance(diag[0], OptimizationResult) \
            and diag[0].hvp_passes is not None:
        lanes = jax.device_get([[getattr(r, key) for key in _TRON_COUNTS]
                                for r in diag])
        return {"lane_" + key: int(sum(np.sum(bucket[i], dtype=np.int64)
                                       for bucket in lanes))
                for i, key in enumerate(_TRON_COUNTS)}
    if not isinstance(diag, OptimizationResult) \
            or jnp.ndim(diag.iterations) != 0:
        return {}
    iterations, passes, counted, tracked, trials, cg, hvp, walked = \
        jax.device_get((diag.iterations, diag.forward_passes,
                        diag.ls_trials, diag.tracker.count,
                        diag.tracker.ls_trials, diag.cg_steps,
                        diag.hvp_passes, diag.walked_trials))
    out = {"solver_iterations": int(iterations)}
    if counted is not None:              # the solve's own count, untracked
        out["ls_trials"] = int(counted)
    elif tracked and trials is not None:  # hand-built trackers have no plane
        out["ls_trials"] = int(np.nansum(trials))
    if passes is not None:
        out["forward_passes"] = int(passes)
    if walked is not None:
        out["walked_trials"] = int(walked)
    if hvp is not None:
        out["cg_steps"] = int(cg)
        out["hvp_passes"] = int(hvp)
    return out


def _nonzero_counts(coord, w) -> dict:
    """``nonzero_coefficients`` of a coordinate whose objective has an L1
    term (an array, or a random effect's list of blocks): the sparsity
    its solve ended with.  Nothing, and no device work, without one."""
    problem = getattr(coord, "problem", None)
    if problem is None or not problem.has_l1():
        return {}
    return {"nonzero_coefficients": int(sum(
        jnp.count_nonzero(leaf) for leaf in jax.tree.leaves(w)))}


def _diag_fields(diag) -> dict:
    """Scalar convergence fields from a coordinate's train diagnostics
    (an ``OptimizationResult`` for fixed effects; a per-bucket list of
    batched results for random effects; a plain dict — already host
    scalars — for the streamed random-effect coordinate)."""
    if isinstance(diag, dict):
        return dict(diag)
    if hasattr(diag, "value") and jnp.ndim(diag.value) == 0:
        out = {
            "value": float(diag.value),
            "grad_norm": float(diag.grad_norm),
            "solver_iterations": int(diag.iterations),
            "converged": bool(diag.converged),
        }
        # what a solve whose trials are contractions paid, where it counts
        if getattr(diag, "ls_trials", None) is not None:
            out["ls_trials"] = int(diag.ls_trials)
            out["forward_passes"] = int(diag.forward_passes)
        if getattr(diag, "walked_trials", None) is not None:
            out["walked_trials"] = int(diag.walked_trials)
        if getattr(diag, "hvp_passes", None) is not None:
            out.update({key: int(getattr(diag, key))
                        for key in _TRON_COUNTS})
        tracker = getattr(diag, "tracker", None)
        if tracker is not None and int(tracker.count) > 0:
            # Per-solver-iteration convergence trace (reference
            # OptimizationStatesTracker; slot 0 = initial point).
            # Bulk device→host copies, not one sync per element.
            c = int(tracker.count)
            out["states"] = {
                "values": np.round(
                    np.asarray(tracker.values[:c], np.float64), 8).tolist(),
                "grad_norms": np.round(
                    np.asarray(tracker.grad_norms[:c], np.float64),
                    8).tolist(),
            }
        return out
    if isinstance(diag, (list, tuple)) and diag and hasattr(diag[0], "value"):
        # Batched per-entity results: one jitted reduction, one bulk
        # device→host copy (not one sync per bucket per stat).
        n = sum(int(r.value.shape[0]) for r in diag)
        conv, iters = jax.device_get(_re_diag_reduce(list(diag)))
        return {"entities": n, "entities_converged": int(conv),
                "max_solver_iterations": int(iters)}
    return {}


def _call_validator(validator, coefs, total):
    """Call a per-sweep validator, accepting both the current two-arg
    ``(coefficients, total_scores)`` signature and the pre-round-4
    one-arg ``(total_scores)`` form (advisor finding: the signature
    changed with no shim, so an external caller's old validator would
    TypeError mid-training).  Arity is inspected up front — catching
    TypeError around the call would mask genuine TypeErrors raised
    *inside* the validator.  The rule is TOTAL positional count
    (advisor finding: counting only REQUIRED positionals misclassified
    a current-API ``(coefficients, total_scores=None)`` validator as
    legacy and silently bound its coefficients to the scores slot):
    a callable with two or more positional parameters is new-style
    regardless of defaults; only an exactly-one-positional callable is
    the legacy ``(total_scores)`` form."""
    import inspect

    try:
        params = list(inspect.signature(validator).parameters.values())
    except (TypeError, ValueError):  # builtins / C callables: assume new
        return validator(coefs, total)
    positional = [
        p for p in params
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
    ]
    var_pos = any(p.kind is p.VAR_POSITIONAL for p in params)
    if len(positional) == 1 and not var_pos:
        return validator(total)
    return validator(coefs, total)


def _record_validation(validator, coefs, total, it, validation_history,
                       run_logger) -> None:
    """One per-sweep validation: evaluate, append to the history, log
    (shared by the per-coordinate and fused loops — one place for the
    metric-to-fields conversion)."""
    with telemetry.stage("cd_validation", iteration=it + 1):
        metric = _call_validator(validator, coefs, total)
    validation_history.append(metric)
    if isinstance(metric, dict):
        fields = {str(getattr(k, "value", k)): float(v)
                  for k, v in metric.items()}
    else:
        fields = {"metric": float(metric)}
    logger.info("CD iter %d validation %s", it + 1, fields)
    if run_logger is not None:
        run_logger.event("cd_validation", iteration=it + 1, **fields)


@dataclasses.dataclass
class CoordinateDescentResult:
    """Trained coefficients per coordinate + per-iteration history."""

    coefficients: dict          # name → coordinate-specific coefficients
    scores: dict                # name → final per-example scores [n]
    # [n] the margins training ended with: the dataset's offsets, where
    # it brought any, plus every coordinate's scores.  ``total_scores −
    # scores[c]`` is what coordinate c's last solve saw.
    total_scores: jnp.ndarray
    history: list               # per iteration: {coordinate: scalar
                                # diagnostic fields (plain dict — the
                                # checkpoint-serializable form, uniform
                                # across fresh and resumed runs)}
    validation_history: list    # per iteration: metric value (if validator)


def run_coordinate_descent(
    coordinates: dict[str, Coordinate],
    update_sequence: list[str],
    n_iterations: int,
    validator=None,
    locked_coordinates: dict | None = None,
    initial_coefficients: dict | None = None,
    checkpoint_dir: str | None = None,
    resume: bool = False,
    run_logger=None,
    checkpointer=None,
    fused_engine=None,
    offsets=None,
) -> CoordinateDescentResult:
    """Run GAME coordinate descent.

    Args:
      coordinates: name → Coordinate (trainable units).
      update_sequence: coordinate update order (reference
        ``updateSequence`` param).
      n_iterations: full sweeps over the sequence (reference
        ``coordinateDescentIterations``).
      validator: optional callable ``(coefficients: dict, total_scores)
        → float | dict`` run once per full sweep (the reference's
        per-iteration validation: CoordinateDescent scores the
        validation set and logs every evaluator each iteration, SURVEY
        §2.3/§3.1).  ``coefficients`` are the current per-coordinate
        values (for scoring held-out data); ``total_scores`` the
        current train-set score sum (for cheap train-side metrics).
        A dict return (evaluator → value) is recorded as-is in
        ``validation_history`` and the run log.
      locked_coordinates: name → pre-trained coefficients for partial
        retraining (reference ``partialRetrainLockedCoordinates``):
        locked coordinates contribute scores but are never retrained.
      initial_coefficients: name → starting coefficients (warm start
        from a previous model, reference ``modelInputDir`` semantics):
        the coordinate starts scored at these values instead of zero.
      checkpoint_dir: if set, snapshot run state after every completed
        sweep via ``reliability.checkpoint.RunCheckpointer`` (format is
        a superset of the legacy ``utils.checkpoint`` files).
      resume: resume from the most advanced checkpoint in
        ``checkpoint_dir`` (overrides ``initial_coefficients`` for
        checkpointed names; restores mid-sweep position and streamed-RE
        retirement state when present).
      run_logger: optional ``photon_ml_tpu.utils.run_log.RunLogger`` for
        structured per-iteration events.
      checkpointer: pre-configured ``RunCheckpointer`` (cadence knobs
        from ``TrainingConfig``); built from ``checkpoint_dir`` with
        defaults when omitted.  While the loop runs it is also the
        ACTIVE checkpoint session, so the streaming solvers snapshot
        mid-solve state under the loop's (iteration, coordinate) scope.
      fused_engine: optional ``game.fused_sweep.FusedCycleEngine``
        (ISSUE 11): each CD iteration becomes ONE fused streamed pass
        that accumulates every coordinate's statistics, followed by the
        Jacobi solves — ~1 store pass per cycle instead of C ×
        solver-iterations.  All coordinate updates within a cycle are
        computed against cycle-START offsets (Jacobi staleness — the
        ``validator``'s ``total_scores`` are therefore the cycle-start
        scores).  Locked coordinates are not supported on this path.
      offsets: the dataset's per-example offsets [n] (``GameDataset
        .offsets``: a Poisson model's log exposure, a prior model's
        margins), or None.  They are part of every training margin:
        the total starts at them, so each coordinate trains against
        ``offsets + Σ other coordinates' scores``, the margins the
        scored model is validated at (``GameTransformer`` adds the
        same).  None starts the total at the scores alone: no zeros
        are added for it.  The fused path composes its margins from
        coefficients and refuses them.
    """
    if fused_engine is not None and locked_coordinates:
        raise ValueError("fused CD does not support locked coordinates")
    if fused_engine is not None and offsets is not None:
        raise ValueError(
            "fused CD (cd_fused) composes its margins from coefficients "
            "and does not carry a dataset's offsets; fit with "
            "cd_fused=false")
    locked_coordinates = locked_coordinates or {}
    initial_coefficients = dict(initial_coefficients or {})
    for name in update_sequence:
        if name not in coordinates and name not in locked_coordinates:
            raise ValueError(f"coordinate '{name}' has no trainable unit "
                             "and is not locked")

    if checkpointer is None and checkpoint_dir:
        checkpointer = _ckpt.RunCheckpointer(checkpoint_dir,
                                             run_logger=run_logger,
                                             resume=resume)
    start_iteration = 0
    start_pos = 0
    ckpt_scores: dict = {}
    restored_extra: dict = {}
    fused_state: dict | None = None
    if resume:
        if checkpointer is None:
            raise ValueError("resume=True requires checkpoint_dir")
        loaded = checkpointer.load_latest_cd()
        if loaded is not None:
            start_iteration = loaded["iteration"]
            start_pos = loaded["coord_pos"]
            initial_coefficients.update(loaded["coefs"])
            restored_extra = loaded["extra"]
            # Fleet resume: restore the reduce counter recorded at this
            # checkpoint boundary so the host replays its reduce
            # sequence (cache-answered) back to the live barrier.
            from photon_ml_tpu.optim.streaming import _restore_fleet_seq

            _restore_fleet_seq(restored_extra.get("fleet_seq"))
            # Fused-cycle engine state rides re_state under a reserved
            # key (ISSUE 11); it is restored by the fused branch below
            # and the per-coordinate loop skips it (no such coordinate).
            fused_state = (loaded["re_state"] or {}).get("__cd_fused__")
            if fused_state is not None and fused_engine is None:
                # A fused checkpoint pairs post-Jacobi-step coefficients
                # with cycle-START score planes (the fused loop never
                # reads the scores back — it composes margins from
                # coefficients).  The per-coordinate loop DOES read
                # them as a consistent pair, so adopting this snapshot
                # would train every coordinate against offsets one
                # Jacobi step stale.  Refuse rather than drift.
                raise ValueError(
                    "checkpoint was written by a fused run (cd_fused); "
                    "resume with cd_fused=true or start a fresh "
                    "checkpoint_dir")
            if fused_state is None and fused_engine is not None:
                # Symmetric refusal: a legacy checkpoint's iteration
                # count budgets FULL inner solves — adopting it as a
                # fused start (start_iteration of n_iterations damped
                # Jacobi cycles, mid-sweep position dropped, engine
                # state fresh) would "complete" severely
                # under-converged with no error.
                raise ValueError(
                    "checkpoint was written by a per-coordinate run; "
                    "resume with cd_fused=false or start a fresh "
                    "checkpoint_dir")
            if fused_engine is None:
                # Device placement of the restored score planes is the
                # per-coordinate path's business only — the fused loop
                # recomputes scores from coefficients and would drop
                # these [n] planes unread (wasted H2D at scale).
                ckpt_scores = {k: jnp.asarray(v)
                               for k, v in loaded["scores"].items()}
            # Streamed-RE runtime state (retirement masks, solved
            # offsets, resident coefficient blocks): the coordinate's
            # canonical blocks become the warm start, so its own
            # warm-start identity check sees ITS arrays and keeps the
            # restored retirement bookkeeping intact.
            for name, st in (loaded["re_state"] or {}).items():
                coord = coordinates.get(name)
                if coord is not None and hasattr(coord,
                                                 "restore_runtime_state"):
                    blocks, cached_scores = coord.restore_runtime_state(st)
                    initial_coefficients[name] = blocks
                    if name not in ckpt_scores:
                        ckpt_scores[name] = cached_scores
            if run_logger is not None:
                run_logger.event("cd_resume", iteration=start_iteration,
                                 coord_pos=start_pos)

    if fused_engine is not None:
        # Fused super-sweep (ISSUE 11): every iteration is ONE streamed
        # pass + Jacobi solves; no per-coordinate score planes are
        # carried as training state, so the per-coordinate preamble
        # below (which would stream a scoring pass per warm start) is
        # bypassed entirely.
        return _run_fused_cycles(
            fused_engine, coordinates, update_sequence, n_iterations,
            validator, initial_coefficients, checkpointer, run_logger,
            start_iteration, restored_extra, fused_state)

    coefs: dict = {}
    scores: dict = {}

    # Fenced: the stage ends when the scores are on the device, so
    # what is left of the data's host-to-device transfers (placement
    # only enqueues them) shows here and not in the first train.
    with telemetry.stage("cd_initial_scores",
                         coordinates=len(update_sequence),
                         **({} if offsets is None else {"offsets": 1})):
        # Locked coordinates score once, up front, and never move.
        for name, locked_coefs in locked_coordinates.items():
            coefs[name] = locked_coefs
            scores[name] = coordinates[name].score(locked_coefs)

        # Trainable coordinates start at their warm-start coefficients
        # (scored in) or contribute zero until first trained.
        for name in update_sequence:
            if name in locked_coordinates:
                continue
            if name in ckpt_scores and name in initial_coefficients:
                # Restored score state: bitwise-identical to what the
                # uninterrupted loop carried at this point.
                coefs[name] = initial_coefficients[name]
                scores[name] = ckpt_scores[name]
            elif name in initial_coefficients:
                coefs[name] = initial_coefficients[name]
                scores[name] = coordinates[name].score(coefs[name])
            else:
                s = coordinates[name].score(
                    coordinates[name].initial_coefficients())
                scores[name] = jnp.zeros_like(s)

        if "__cd_total__" in ckpt_scores:
            # the running total as it was saved, offsets and all
            total = ckpt_scores["__cd_total__"]
        else:
            total = (None if offsets is None
                     else jnp.asarray(offsets, jnp.float32))
            for s in scores.values():
                total = s if total is None else total + s
        jax.block_until_ready(total)

    history = _serialize_history(restored_extra.get("history") or [])
    validation_history = _revive_validation(
        restored_extra.get("validation_history"))
    # Per-coordinate objective trajectory across sweeps (ISSUE 8): the
    # delta between consecutive sweeps' final objective values is the
    # CD-level convergence signal the reference logs per iteration.
    prev_values: dict = dict(restored_extra.get("prev_values") or {})

    def _re_states() -> dict:
        return {name: coord.runtime_state()
                for name, coord in coordinates.items()
                if hasattr(coord, "runtime_state")
                and name not in locked_coordinates}

    def _extra() -> dict:
        from photon_ml_tpu.optim.streaming import _fleet_seq

        return {"history": _serialize_history(history),
                "validation_history": _serialize_validation(
                    validation_history),
                "prev_values": dict(prev_values),
                "fleet_seq": _fleet_seq()}

    # A mid-sweep resume re-enters a PARTIAL sweep: the coordinates it
    # skips already trained before the kill, and their diagnostics ride
    # in the partial snapshot — seed them back so the resumed sweep's
    # history entry matches the uninterrupted run's record.
    partial_diag = dict(restored_extra.get("partial_iter_diag") or {})

    ckpt_session = (_ckpt.session(checkpointer) if checkpointer is not None
                    else contextlib.nullcontext())
    with ckpt_session:
        for it in range(start_iteration, n_iterations):
            total, iter_diag = _run_sweep(
                coordinates, update_sequence, locked_coordinates, coefs,
                scores, it, start_iteration, start_pos, checkpointer,
                run_logger, prev_values, total, _extra, _re_states,
                n_iterations,
                seed_diag=(partial_diag if it == start_iteration
                           else None))
            # Completed CD cycle: the denominator of the report's
            # passes-per-cycle metric (ISSUE 11 — sweep odometer ÷
            # cycles is how the C → ~1 fused drop is measured).
            telemetry.count("cd.cycles")
            # Normalized to the serialized (plain-dict) diagnostic form
            # so ``CoordinateDescentResult.history`` is uniformly typed
            # whether or not the run was resumed (the restored prefix
            # arrives serialized from the checkpoint).
            history.append(_serialize_history([iter_diag])[0])
            if validator is not None:
                _record_validation(validator, coefs, total, it,
                                   validation_history, run_logger)
            if checkpointer is not None:
                checkpointer.maybe_save_cd(
                    it + 1, coefs,
                    scores={**scores, "__cd_total__": total},
                    re_state=_re_states(), extra=_extra(),
                    final=(it + 1 == n_iterations))

    return CoordinateDescentResult(
        coefficients=coefs,
        scores=scores,
        total_scores=total,
        history=history,
        validation_history=validation_history,
    )


def _run_fused_cycles(engine, coordinates, update_sequence,
                      n_iterations, validator, initial_coefficients,
                      checkpointer, run_logger, start_iteration,
                      restored_extra, fused_state):
    """The fused-CD loop (ISSUE 11): one streamed super-sweep per
    iteration, harvested statistics solved once per cycle, offsets
    updated once per cycle (Jacobi).  Checkpoints land at cycle
    boundaries — the engine's retirement/step-scale state rides
    ``re_state["__cd_fused__"]`` so a resumed run steps identically."""
    engine.restore_runtime_state(fused_state)
    trainable = [n for n in dict.fromkeys(update_sequence)]
    coefs: dict = {}
    for name in trainable:
        if name in initial_coefficients:
            coefs[name] = initial_coefficients[name]
        else:
            coefs[name] = coordinates[name].initial_coefficients()

    history = _serialize_history(restored_extra.get("history") or [])
    validation_history = _revive_validation(
        restored_extra.get("validation_history"))

    def _extra() -> dict:
        from photon_ml_tpu.optim.streaming import _fleet_seq

        return {"history": _serialize_history(history),
                "validation_history": _serialize_validation(
                    validation_history),
                "fleet_seq": _fleet_seq()}

    scores: dict = {}
    total = None
    ckpt_session = (_ckpt.session(checkpointer) if checkpointer is not None
                    else contextlib.nullcontext())
    with ckpt_session:
        for it in range(start_iteration, n_iterations):
            t0 = time.perf_counter()
            with telemetry.span("cd_fused_cycle", cat="cd",
                                iteration=it + 1):
                coefs, scores, total, iter_diag = engine.run_cycle(coefs)
            elapsed = time.perf_counter() - t0
            telemetry.count("cd.cycles")
            telemetry.count("cd.coordinate_updates", len(trainable))
            history.append(_serialize_history([iter_diag])[0])
            # Cycle-level progress (the fused analog of the legacy
            # loop's per-coordinate updates; per-CHUNK progress comes
            # from the engine's train.cd_fused stage).
            _mon.progress("cd", it + 1, n_iterations, unit="cycles",
                          iteration=it + 1)
            fe_diag = iter_diag.get(engine.fe_name, {})
            logger.info(
                "CD fused cycle %d in %.2fs (value %s, alpha %s)",
                it + 1, elapsed, fe_diag.get("value"),
                fe_diag.get("alpha"))
            if run_logger is not None:
                retired = sum(d.get("entities_retired", 0)
                              for d in iter_diag.values()
                              if isinstance(d, dict))
                run_logger.event(
                    "cd_fused_cycle", iteration=it + 1,
                    duration_s=round(elapsed, 4),
                    value=fe_diag.get("value"),
                    grad_norm=fe_diag.get("grad_norm"),
                    alpha=fe_diag.get("alpha"),
                    entities_retired=retired)
            if validator is not None:
                # ``total`` holds the CYCLE-START scores (Jacobi
                # staleness — documented in run_coordinate_descent);
                # snapshot scoring of held-out data uses the fresh
                # coefficients either way.
                _record_validation(validator, coefs, total, it,
                                   validation_history, run_logger)
            if checkpointer is not None:
                checkpointer.maybe_save_cd(
                    it + 1, coefs,
                    scores={**scores, "__cd_total__": total},
                    re_state={"__cd_fused__": engine.runtime_state()},
                    extra=_extra(),
                    final=(it + 1 == n_iterations))

    # One final pass brings the score planes to the FINAL coefficients
    # (each cycle's planes are at its start) — counted as an auxiliary
    # sweep, so passes/cycle stays (N+1)/N ≈ 1.
    scores, total = engine.score_pass(coefs)
    return CoordinateDescentResult(
        coefficients=coefs,
        scores=scores,
        total_scores=total,
        history=history,
        validation_history=validation_history,
    )


def _run_sweep(coordinates, update_sequence, locked_coordinates, coefs,
               scores, it, start_iteration, start_pos, checkpointer,
               run_logger, prev_values, total, extra_fn, re_states_fn,
               n_iterations, seed_diag=None):
    """One CD sweep over the update sequence (split out so the resume
    position logic stays readable).  Mutates ``coefs``/``scores``/
    ``prev_values`` in place; returns (total, iteration diagnostics).
    ``extra_fn``/``re_states_fn`` supply the parent loop's history and
    streamed-RE state snapshots for mid-sweep partial checkpoints (one
    collection rule for partial AND boundary snapshots); ``seed_diag``
    pre-fills the skipped coordinates' diagnostics when re-entering a
    partial sweep after a resume."""
    iter_diag = dict(seed_diag or {})
    for pos, name in enumerate(update_sequence):
        if name in locked_coordinates:
            continue
        if it == start_iteration and pos < start_pos:
            # Mid-sweep resume: this coordinate already trained in the
            # interrupted sweep — its coefficients/scores came back
            # with the partial snapshot.
            continue
        coord = coordinates[name]
        scope = (checkpointer.scope(f"it{it + 1}", name)
                 if checkpointer is not None
                 else contextlib.nullcontext())
        # Per-coordinate stage (ISSUE 7, ISSUE 26): one CD sweep's
        # train+score for this coordinate is one block on the
        # timeline, the unit the report's stage table attributes
        # time to.  Its two children block on what they enqueued, so
        # the stage's wall is the device's work and not the enqueue.
        with scope, telemetry.stage("cd_coordinate", coordinate=name,
                                    iteration=it + 1) as coordinate_stage:
            offsets = total - scores[name]
            with telemetry.stage("coord_train",
                                 coordinate=name) as train_stage:
                # The warm-start buffer is rebound to the result right
                # below, so let XLA write the new coefficients into the
                # old buffer (donation; SURVEY §5.2).  NOTE: on the
                # first sweep this consumes the caller's
                # initial_coefficients / checkpoint-restored arrays —
                # any later read of those buffers would hit a
                # deleted-buffer error; nothing in this loop re-reads
                # them (coefs[name] is rebound below).
                w, diag = jax.block_until_ready(
                    coord.train(offsets, coefs.get(name),
                                donate_warm_start=True))
                sparsity = _nonzero_counts(coord, w)
                train_stage.set(**_solve_counts(diag), **sparsity,
                                **coord.train_counts())
            with telemetry.stage("coord_score", coordinate=name):
                new_scores = jax.block_until_ready(coord.score(w))
        # ``offsets`` already holds total − old scores; reusing it
        # saves one [n]-vector op per coordinate per sweep (and
        # matches the reference's residual algebra exactly).
        total = offsets + new_scores
        scores[name] = new_scores
        coefs[name] = w
        # an L1 coordinate's record says how sparse its solve ended
        iter_diag[name] = ({**_diag_fields(diag), **sparsity} if sparsity
                           else diag)
        elapsed = coordinate_stage.duration_s
        # Retirement hook (streamed random effects, ISSUE 5): the
        # coordinate stashed this sweep's converged-entity
        # candidates during train; committing them HERE — after the
        # scores are folded into the totals — freezes their
        # coefficients so the next sweep re-packs only the active
        # entities into chunks.  Part of the Coordinate contract:
        # the base returns None (no retirement protocol).
        newly_retired = coord.retire_converged()
        if newly_retired:
            telemetry.count("cd.entities_retired", newly_retired)
        # Live CD progress (ISSUE 10): coordinate updates completed
        # against the whole descent's plan — the top-level ETA the
        # watch view leads with.
        _mon.progress("cd", it * len(update_sequence) + pos + 1,
                      n_iterations * len(update_sequence),
                      unit="updates", iteration=it + 1,
                      coordinate=name)
        extra = dict(sparsity)
        if newly_retired is not None:
            extra["entities_newly_retired"] = newly_retired
        telemetry.count("cd.coordinate_updates")
        # Objective delta vs this coordinate's previous sweep, and
        # a convergence trace for resident solves (streaming
        # coordinates emit their own — traces_convergence).
        if hasattr(diag, "value") and jnp.ndim(diag.value) == 0:
            value = float(diag.value)
            if name in prev_values:
                delta = prev_values[name] - value
                extra["value_delta"] = round(delta, 8)
                telemetry.observe("cd.objective_delta", delta)
            prev_values[name] = value
            if not getattr(coord, "traces_convergence", False):
                _conv.solve_trace("resident", name, diag)
        logger.info(
            "CD iter %d coordinate %s trained in %.2fs",
            it + 1, name, elapsed,
        )
        if run_logger is not None:
            run_logger.event(
                "cd_coordinate", iteration=it + 1, coordinate=name,
                duration_s=round(elapsed, 4), **_diag_fields(diag),
                **extra,
            )
        if checkpointer is not None and checkpointer.mid_sweep_enabled:
            # Mid-sweep position snapshot (ISSUE 9): ``pos + 1``
            # update-sequence entries of sweep ``it + 1`` are done, so
            # a kill during the NEXT coordinate's solve resumes here
            # (plus whatever mid-solve state that solve checkpointed).
            checkpointer.save_cd_partial(
                it, pos + 1, coefs,
                scores={**scores, "__cd_total__": total},
                re_state=re_states_fn(),
                extra={**extra_fn(),
                       "partial_iter_diag":
                           _serialize_history([iter_diag])[0]})
    return total, iter_diag
