"""KDD2012-shaped synthetic scale run: the reference's north-star class.

Shape (SURVEY.md §6/§7 "entity-grouping ETL at KDD2012 scale"):
  - n = 10^7 examples (KDD2012 CTR has ~1.5x10^8; one v5e chip's HBM
    comfortably holds 10^7 with the sparse fixed effect below),
  - sparse global fixed effect, d = 10^5, ~10 nnz/example,
  - TWO random effects with 10^5 entities each (user: 2 features,
    item: per-entity intercept), power-law entity skew,
  - one full GAME coordinate-descent sweep on one chip.

Prints ONE JSON line with phase timings, peak host RSS, and validation
AUC.  Everything host-side is the vectorized SparseRows/grouping ETL —
no per-example Python anywhere.

Usage::

    python examples/kdd_scale.py            # full size (TPU, ~minutes)
    python examples/kdd_scale.py --small    # 10^5-example smoke run
"""

import argparse
import json
import os
import resource
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from photon_ml_tpu.config import (  # noqa: E402
    CoordinateConfig,
    CoordinateKind,
    OptimizerSettings,
    TrainingConfig,
)
from photon_ml_tpu.data.sparse_rows import SparseRows  # noqa: E402
from photon_ml_tpu.estimators.game_estimator import GameEstimator  # noqa: E402
from photon_ml_tpu.evaluation import EvaluatorType  # noqa: E402
from photon_ml_tpu.game.dataset import GameDataset  # noqa: E402
from photon_ml_tpu.models.glm import TaskType  # noqa: E402
from photon_ml_tpu.utils.run_log import RunLogger  # noqa: E402


def max_rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def synthesize(n: int, d: int, k: int, n_users: int, n_items: int,
               seed: int = 0):
    """Vectorized KDD-shaped generator (no per-example Python)."""
    rng = np.random.default_rng(seed)
    # Skewed column popularity (power-law, like hashed CTR features),
    # made strictly increasing within each row so the CSR is canonical
    # by construction — no 10⁸-element sort needed to build it.
    cols_mat = np.sort(
        ((d - k) * rng.random((n, k)) ** 2.2).astype(np.int64), axis=1)
    for j in range(1, k):
        bump = cols_mat[:, j] <= cols_mat[:, j - 1]
        cols_mat[bump, j] = cols_mat[bump, j - 1] + 1
    indptr = np.arange(n + 1, dtype=np.int64) * k
    fixed = SparseRows.from_flat(indptr, cols_mat.reshape(-1),
                                 np.ones(n * k, np.float32))

    # Power-law entity popularity for both random effects.
    user = (n_users * rng.random(n) ** 1.8).astype(np.int64)
    item = (n_items * rng.random(n) ** 1.8).astype(np.int64)

    # Ground truth: sparse global weights + per-entity offsets.
    w_true = np.zeros(d)
    n_active = max(d // 20, 200)
    active = rng.choice(d, size=n_active, replace=False)
    w_true[active] = rng.normal(0, 1.2, n_active)
    u_eff = rng.normal(0, 1.2, n_users)
    i_eff = rng.normal(0, 0.8, n_items)
    x_user = np.concatenate(
        [np.ones((n, 1), np.float32),
         rng.normal(size=(n, 1)).astype(np.float32)], axis=1)
    margins = (fixed.dot_dense(w_true).astype(np.float64)
               + u_eff[user] + i_eff[item] - 1.0)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-margins))).astype(np.float32)

    return GameDataset(
        labels=y,
        features={
            "global": fixed,
            "user_re": x_user,
            "item_re": np.ones((n, 1), np.float32),
        },
        entity_ids={"userId": user, "itemId": item},
        feature_dims={"global": d},
    )


def training_config(**overrides) -> TrainingConfig:
    """BASELINE config 5: logistic GAME, sparse fixed effect (L-BFGS)
    plus per-user and per-item random effects, one CD sweep, AUC."""
    return TrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinates=[
            CoordinateConfig(
                name="global", kind=CoordinateKind.FIXED_EFFECT,
                feature_shard="global",
                optimizer=OptimizerSettings(reg_weight=1.0, max_iters=30)),
            CoordinateConfig(
                name="per_user", kind=CoordinateKind.RANDOM_EFFECT,
                feature_shard="user_re", entity_key="userId",
                optimizer=OptimizerSettings(reg_weight=1.0, max_iters=10)),
            CoordinateConfig(
                name="per_item", kind=CoordinateKind.RANDOM_EFFECT,
                feature_shard="item_re", entity_key="itemId",
                optimizer=OptimizerSettings(reg_weight=1.0, max_iters=10)),
        ],
        update_sequence=["global", "per_user", "per_item"],
        n_iterations=1,
        evaluators=[EvaluatorType.AUC],
        intercept=True,
        **overrides,
    )


def split(data: GameDataset) -> tuple[GameDataset, GameDataset]:
    """(train, validation): validation is the last 2 % of the rows, at
    most 200,000."""
    n = data.n
    n_valid = min(n // 50, 200_000)
    return (data.take(np.arange(n - n_valid)),
            data.take(np.arange(n - n_valid, n)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="10^5-example smoke run (CPU-friendly)")
    ap.add_argument("--n", type=int, default=None,
                    help="example count (default 10^7; ~9x10^6 is the "
                         "largest class whose GRR plans fit one v5e's "
                         "16 GB HBM resident — beyond that, use "
                         "--chunked or shard over a mesh)")
    ap.add_argument("--chunked", type=int, default=None, metavar="ROWS",
                    help="chunk-accumulated fixed-effect training "
                         "(data/chunked_batch.py): examples per chunk; "
                         "breaks the HBM residency wall")
    ap.add_argument("--chunk-layout", default="AUTO",
                    choices=["AUTO", "GRR", "ELL"],
                    help="per-chunk layout: GRR = kernel-speed steps, "
                         "~1.6 GB/1e6 examples streamed per pass (PCIe-"
                         "class hosts); ELL = 8 B/nnz, ~20x smaller "
                         "stream (transfer-bound links)")
    ap.add_argument("--chunk-resident", type=int, default=1,
                    help="chunks kept live in HBM across passes (set "
                         ">= n/chunk_rows when the compact layout fits "
                         "— transfer then happens once)")
    ap.add_argument("--spill-dir", default=None,
                    help="out-of-core chunk store (data/chunk_store.py):"
                         " chunk batches spill to disk here and only "
                         "--host-resident decoded chunks stay in host "
                         "RAM — breaks the host-RAM wall the same way "
                         "--chunked breaks HBM's (default also "
                         "$PHOTON_ML_TPU_SPILL_DIR)")
    ap.add_argument("--host-resident", type=int, default=2,
                    help="decoded chunks kept live in host RAM when "
                         "spilling (the LRU window)")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="chunks prefetched disk->host->device ahead "
                         "of compute when spilling (0 = synchronous)")
    ap.add_argument("--out", default=None, help="also write the JSON here")
    args = ap.parse_args(argv)

    if args.small:
        n, d, k, ents = 100_000, 10_000, 10, 1_000
    else:
        n, d, k, ents = 10_000_000, 100_000, 10, 100_000
    if args.n is not None:
        n = args.n

    import tempfile

    log_path = os.path.join(tempfile.mkdtemp(prefix="kdd_scale_"),
                            "run_log.jsonl")
    log = RunLogger(path=log_path)
    t0 = time.time()
    with log.timed("synthesize"):
        data = synthesize(n, d, k, n_users=ents, n_items=ents)
    with log.timed("split"):
        train, valid = split(data)

    cfg = training_config(
        chunk_rows=args.chunked,
        chunk_layout=args.chunk_layout,
        chunk_max_resident=args.chunk_resident,
        spill_dir=args.spill_dir,
        host_max_resident=args.host_resident,
        prefetch_depth=args.prefetch_depth,
    )
    est = GameEstimator(cfg)
    with log.timed("fit"):
        results = est.fit(train, valid, run_logger=log)
    auc = results[0].evaluations[EvaluatorType.AUC]

    from photon_ml_tpu.utils.run_log import read_run_log

    log.close()
    phases = {e["phase"]: round(e["duration_s"], 2)
              for e in read_run_log(log_path)
              if e.get("event") == "phase_end"}
    out = {
        "metric": "kdd_scale_wall_seconds",
        "value": round(time.time() - t0, 2),
        "unit": "s",
        "n_examples": n,
        "fixed_dim": d,
        "entities_per_re": ents,
        "n_random_effects": 2,
        "validation_auc": round(float(auc), 4),
        "peak_host_rss_gb": round(max_rss_gb(), 2),
        "phases": phases,
        "chunked": (None if args.chunked is None else {
            "chunk_rows": args.chunked,
            "layout": args.chunk_layout,
            "max_resident": args.chunk_resident,
            "spill_dir": args.spill_dir,
            "host_max_resident": args.host_resident,
            "prefetch_depth": args.prefetch_depth,
        }),
    }
    line = json.dumps(out)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    assert auc > 0.70, f"scale-run AUC gate failed: {auc}"


if __name__ == "__main__":
    main()
