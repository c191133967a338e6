"""Chip smoke: the GAME training path, end to end, on one TPU chip.

    python chip_smoke.py              # one chip; what the driver runs
    python chip_smoke.py --chips 4    # the mesh path and its one-device
                                      # comparison, nothing else

One process, which holds the chip from its first ``jax.devices()`` to
its exit.  Phases (each a function of its sizes, so the tests run them
tiny on the CPU; only ``main`` looks at the device):

1. *drivers*: ``examples/make_data.py`` → the training driver on configs
   1 and 4 → the scoring driver: files → ETL → fit → model on disk →
   read back → scores.
2. *full width*: BASELINE config 5 through ``GameEstimator.fit`` as
   ``examples/kdd_scale.py`` builds it, at published widths (d = 10⁵,
   ~10 nnz/row + intercept, 10⁵ + 10⁵ power-law entities); only the
   number of rows is cut.
3. *kernel*: the fixed effect's value+gradient program holds the Mosaic
   kernel (``tpu_custom_call``) and agrees with the plain ELL path at
   the trained coefficients.

Everything but the result goes to stderr, one JSON object per line.
The last (and only) line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failure exits non-zero with the reason on stderr and no such line.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "examples"))  # make_data, kdd_scale

# Published widths of BASELINE config 5 (KDD Cup 2012 track 2 class):
# never cut.  N_ROWS is the depth: KDD2012 has ~1.5e8 rows, the smoke
# takes 1e6 so that a cold run, compiles included, fits its time limit.
DIM, NNZ_PER_ROW, ENTITIES = 100_000, 10, 100_000
N_ROWS = 1_000_000
# Validation AUC of this fit at N_ROWS on the CPU backend (ELL layout,
# seed 0) is 0.7211; the chip differs by float32 summation order only.
AUC_FLOOR = 0.71
# GRR vs ELL at the trained coefficients, float32: |Δvalue| / |value|
# and max|Δgrad| / max|grad|.  Both paths sum ~1e6 float32 terms in
# different orders; sqrt(n)·eps ≈ 6e-5.
VALUE_RTOL, GRAD_RTOL = 1e-4, 1e-3
# One-device vs four-device validation AUC.  Same data and solver, but
# the fixed effect's L-BFGS is cut at 30 iterations, short of
# convergence, and the psum reorders its float32 sums: on the CPU the
# one-device (ELL) and four-device (colmajor) fits differ by 1.2e-3.
MESH_AUC_ATOL = 5e-3


def say(**record) -> None:
    print(json.dumps(record), file=sys.stderr, flush=True)


class SmokeFailure(Exception):
    """A check of the smoke did not hold; the message says which."""


def require(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


def last_line(device, count: int) -> str:
    """The contract's result line: exactly these keys."""
    return json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": count}})


class CompileClock:
    """Seconds JAX spent in backend compilation (a persistent-cache hit
    counts only its retrieval), summed from JAX's own duration events
    while the clock is registered."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += duration


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, **fields):
    """Wall and compile seconds of one phase, said when it ends."""
    t0, c0 = time.perf_counter(), clock.seconds
    out: dict = {}
    yield out
    say(phase=name, seconds=round(time.perf_counter() - t0, 2),
        compile_seconds=round(clock.seconds - c0, 2), **fields, **out)


# -- phase 1: the drivers ---------------------------------------------------

def run_drivers(root: str = REPO) -> dict:
    """Generate the example data, train configs 1 and 4, score config 1's
    model.  ``root`` is where ``examples/data`` and ``examples/out`` go
    (the configs' own relative paths, resolved against it)."""
    import make_data

    from photon_ml_tpu.cli import game_scoring_driver, game_training_driver
    from photon_ml_tpu.config import (
        load_scoring_config,
        load_training_config,
    )

    def at_root(cfg, *fields):
        for f in fields:
            setattr(cfg, f, os.path.join(root, getattr(cfg, f)))
        return cfg

    make_data.main(os.path.join(root, "examples", "data"))
    out = {}
    for name in ("config1_libsvm", "config4_game"):
        cfg = at_root(
            load_training_config(
                os.path.join(REPO, "examples", f"{name}.json")),
            "input_path", "validation_path", "output_dir")
        summary = game_training_driver.run(cfg)
        best = summary["models"][summary["best_index"]]
        out[name] = {"auc": best["evaluations"]["AUC"]}
    scoring = at_root(
        load_scoring_config(os.path.join(REPO, "examples", "scoring.json")),
        "input_path", "model_dir", "output_path")
    scored = game_scoring_driver.run(scoring)
    out["scoring"] = {"n": scored["n"],
                      "auc": scored["evaluation"]["AUC"],
                      "output_path": scored["output_path"]}
    return out


def check_drivers(out: dict) -> None:
    import numpy as np

    for name in ("config1_libsvm", "config4_game"):
        require(out[name]["auc"] > 0.7, f"{name}: AUC {out[name]['auc']}")
    # scoring.json scores config 1's validation file with config 1's
    # saved model: the model read back must reproduce the trainer's AUC.
    require(abs(out["scoring"]["auc"] - out["config1_libsvm"]["auc"])
            <= 1e-4, f"scoring driver AUC differs: {out}")
    with np.load(out["scoring"]["output_path"]) as z:
        scores = z["scores"]
    require(scores.shape == (out["scoring"]["n"],)
            and bool(np.isfinite(scores).all()),
            f"scores: shape {scores.shape}, or not finite")


# -- phase 2: config 5 at full width ----------------------------------------

def make_config5_data(n: int, d: int, k: int, entities: int, seed: int):
    import kdd_scale

    return kdd_scale.split(
        kdd_scale.synthesize(n, d, k, entities, entities, seed=seed))


def fit_config5(train, valid, **overrides) -> dict:
    """One config-5 fit; returns the estimator, the model and its
    validation AUC."""
    import kdd_scale

    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.evaluation import EvaluatorType

    est = GameEstimator(kdd_scale.training_config(**overrides))
    result = est.fit(train, valid)[0]
    return {"estimator": est, "model": result.model,
            "auc": float(result.evaluations[EvaluatorType.AUC])}


# -- phase 3: the kernel really ran, and agrees -----------------------------

def _layout(batch) -> str:
    return ("GRR" if batch.grr is not None else
            "COLMAJOR" if batch.colmajor is not None else "ELL")


def check_kernel(estimator, train, w) -> dict:
    """Value+gradient at ``w`` over the fixed-effect batch as the
    estimator lays it out, against the plain ELL path.

    Returns the layout AUTO resolved to, whether the compiled program
    holds the Mosaic kernel, and the two relative differences; raises
    if they exceed the stated tolerances."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu.data.batch import make_sparse_batch
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.regularization import (
        RegularizationContext,
        exclude_intercept_mask,
    )

    coord = estimator.config.coordinates[0]
    # The estimator keeps no handle on the batches it trained on: lay
    # the fixed effect out again, by the same code (a second plan build).
    prep = estimator._prepare_fixed(train, coord)
    batch, dim = prep["batch"], prep["dim"]
    objective = GLMObjective(
        loss=estimator.loss,
        reg=RegularizationContext.l2(
            coord.optimizer.reg_weight,
            exclude_intercept_mask(dim, prep["intercept_index"])),
        norm=NormalizationContext.identity())
    w = jnp.asarray(w, jnp.float32)

    value_and_gradient = jax.jit(
        lambda obj, w, b: obj.value_and_gradient(w, b))
    compiled = value_and_gradient.lower(objective, w, batch).compile()
    v, g = compiled(objective, w, batch)

    rows = train.features[coord.feature_shard]
    if prep["intercept_index"] is not None:
        rows = rows.with_constant_col(prep["intercept_index"])
    ell = make_sparse_batch(rows, dim, train.labels.astype(np.float32),
                            weights=train.weight_array(),
                            pad_to=batch.n_padded)
    v_ell, g_ell = value_and_gradient(objective, w, ell)

    v, g, v_ell, g_ell = (np.asarray(a, np.float64)
                          for a in (v, g, v_ell, g_ell))
    out = {
        "layout": _layout(batch),
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        "value": float(v),
        "value_rel_diff": float(abs(v - v_ell) / abs(v_ell)),
        "grad_rel_diff": float(
            np.max(np.abs(g - g_ell)) / np.max(np.abs(g_ell))),
    }
    require(bool(np.isfinite(v) and np.isfinite(g).all())
            and g.shape == (dim,), f"value/gradient not finite: {out}")
    require(out["value_rel_diff"] <= VALUE_RTOL
            and out["grad_rel_diff"] <= GRAD_RTOL,
            f"GRR and ELL disagree: {out}")
    return out


# -- the mesh path (--chips 4) ----------------------------------------------

def check_spread(estimator, train, n_devices: int) -> dict:
    """The example-sharded batch as the estimator lays it out on the
    mesh: its shards sit on ``n_devices`` distinct devices, and each
    device has at least its shard's bytes in use."""
    import jax

    prep = estimator._prepare_fixed(train, estimator.config.coordinates[0])
    shard_bytes: dict = {}
    for leaf in jax.tree_util.tree_leaves(prep["batch"]):
        for s in leaf.addressable_shards:
            shard_bytes[s.device] = shard_bytes.get(s.device, 0) \
                + s.data.nbytes
    require(len(shard_bytes) == n_devices,
            f"batch shards on {len(shard_bytes)} devices, not {n_devices}")
    out = []
    for dev, nbytes in sorted(shard_bytes.items(), key=lambda kv: kv[0].id):
        stats = dev.memory_stats()
        in_use = None if stats is None else int(stats["bytes_in_use"])
        out.append({"device": dev.id, "shard_bytes": nbytes,
                    "bytes_in_use": in_use})
        require(in_use is None or in_use >= nbytes,
                f"device {dev.id} holds {in_use} bytes, less than its "
                f"shard's {nbytes}")
    return {"layout": _layout(prep["batch"]), "devices": out}


# -- main --------------------------------------------------------------------

def run_one_chip(clock: CompileClock, sizes: dict) -> None:
    with phase("drivers", clock) as out:
        out.update(run_drivers())
        check_drivers(out)
    with phase("synthesize", clock):
        train, valid = make_config5_data(
            N_ROWS, DIM, NNZ_PER_ROW, ENTITIES, sizes["seed"])
    with phase("fit_config5", clock, **sizes) as out:
        fit = fit_config5(train, valid)
        out["auc"] = fit["auc"]
    require(fit["auc"] > AUC_FLOOR,
            f"AUC {fit['auc']} not above {AUC_FLOOR}")
    with phase("kernel", clock) as out:
        w = fit["model"].models["global"].coefficients.means
        out.update(check_kernel(fit["estimator"], train, w))
    require(out["layout"] == "GRR" and out["tpu_custom_call"],
            "the fixed effect did not run the GRR kernel")


def run_mesh(clock: CompileClock, sizes: dict, n_devices: int) -> None:
    with phase("synthesize", clock):
        train, valid = make_config5_data(
            N_ROWS, DIM, NNZ_PER_ROW, ENTITIES, sizes["seed"])
    with phase("fit_one_device", clock, **sizes) as out:
        one_auc = out["auc"] = fit_config5(train, valid)["auc"]
    with phase("fit_mesh", clock, n_devices=n_devices, **sizes) as out:
        mesh = fit_config5(train, valid, n_devices=n_devices)
        out["auc"] = mesh["auc"]
    with phase("spread", clock) as out:
        out.update(check_spread(mesh["estimator"], train, n_devices))
    require(out["layout"] == "GRR",
            "the mesh fit did not use the sharded GRR plans")
    require(mesh["auc"] > AUC_FLOOR
            and abs(mesh["auc"] - one_auc) <= MESH_AUC_ATOL,
            f"mesh AUC {mesh['auc']} vs one device {one_auc}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-device mesh fit and the "
                         "one-device fit it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic config-5 data")
    args = ap.parse_args(argv)

    # Only the final line may reach stdout: send everything else that
    # any layer (a log handler, a C++ runtime, an exit hook) writes to
    # file descriptor 1 to stderr, and keep the real stdout aside.
    sys.stdout.flush()
    result_out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)

    import jax

    devices = jax.devices()
    say(phase="device", platform=devices[0].platform,
        device_kind=devices[0].device_kind, count=len(devices),
        jax=jax.__version__)
    if devices[0].platform != "tpu":
        say(error="no TPU: this is a chip check and does not fall back")
        return 2
    if len(devices) < args.chips:
        say(error=f"--chips {args.chips} needs {args.chips} devices")
        return 2

    from photon_ml_tpu import native
    from photon_ml_tpu.cache import (
        cache_entry_count,
        enable_compilation_cache,
    )

    cache_dir = enable_compilation_cache()
    entries_before = cache_entry_count(cache_dir)
    say(phase="compile_cache", dir=cache_dir, entries=entries_before,
        from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))
    clock = CompileClock()
    sizes = dict(n=N_ROWS, d=DIM, nnz_per_row=NNZ_PER_ROW,
                 entities_per_effect=ENTITIES, seed=args.seed)
    say(phase="sizes", **sizes, reduced=[
        f"rows: {N_ROWS:.0e} of KDD Cup 2012 track 2's ~1.5e8 (one chip, "
        "one cold run inside the time limit)",
        "CD sweeps: 1, as examples/kdd_scale.py"])
    try:
        require(native.lib() is not None,
                "native ETL library unavailable (its reason is above)")
        if args.chips == 1:
            run_one_chip(clock, sizes)
        else:
            run_mesh(clock, sizes, args.chips)
    except SmokeFailure as e:
        say(error=str(e))
        return 1

    say(phase="done",
        peak_bytes_in_use=[(d.memory_stats() or {}).get("peak_bytes_in_use")
                           for d in devices],
        compile_cache_entries_before=entries_before,
        compile_cache_entries_after=cache_entry_count(cache_dir),
        compile_seconds_total=round(clock.seconds, 2))
    result_out.write(last_line(devices[0], len(devices)) + "\n")
    result_out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
