"""Benchmark: fused GLM value+gradient pass at realistic sparse scale.

Measures the framework's hot loop — one fused (value, gradient)
evaluation of the logistic objective, the unit of work per optimizer
iteration (the reference's ``ValueAndGradientAggregator`` +
``treeAggregate`` round, SURVEY.md §2.2) — on whatever accelerator jax
provides (the driver runs this on one real TPU v5e chip).

Workload: n=1,000,000 examples, d=100,000 features, k=30 nnz/row
(KDD-2012-class sparsity).  THREE sparse layouts are timed on identical
data (round-2 verdict item: report them all, honestly):

- ``segment_sum``: plain ELL — XLA's scalar gather + scatter lowering
  (what a straight port produces; round 2's shipped path);
- ``colmajor``: transposed-ELL — scatter-free but still on XLA's scalar
  gather;
- ``grr``: the compiled gather-route-reduce plan executed by the Mosaic
  kernel (``data/grr.py`` + ``ops/grr_kernel.py``) — the production
  path (``TrainingConfig.sparse_layout`` AUTO on TPU).

Timing runs the step inside one jitted ``lax.scan`` (mirroring the
production solvers, where the whole optimize loop is a single device
program), so per-call dispatch overhead is not counted against a
millisecond-scale kernel.

``vs_baseline``: the reference publishes no numbers (BASELINE.md), so
the ratio is best-XLA-layout time / GRR time — the speedup of the
framework's compiled plan over the best formulation XLA alone can run.
``roofline_fraction`` is achieved HBM traffic (counting every byte the
GRR plan actually streams, padding and index planes included) against
the device's published peak, looked up by ``device_kind``
(``telemetry.device.DEVICE_PEAK_GBPS``; an unlisted TPU is an error, and
off a TPU no roofline is reported).

Budgeted-section contract (round-5 verdict: the bench outgrew the
driver's capture window and the round had NO perf number of record —
``rc: 124 / parsed: null`` must never happen again):

- ``--section A[,B...]`` runs only those sections; default is
  ``etl,cached,grr,segment_sum,colmajor`` (``powerlaw`` and ``chunked``
  are opt-in extras).
- ``--budget-s N`` (default 840) is a wall-clock budget: before each
  section its cost is estimated (scaled to the shape) and sections
  that do not fit are SKIPPED and recorded, so the process always
  ends in budget with the measurements it did make.
- The LAST stdout line is always one machine-parseable JSON object
  (progress goes to stderr).  A section that raises is recorded in
  ``"errors"``, the remaining sections still run, and the process
  then exits 1.
- ``cached`` measures the warm path: loading the GRR plan from the
  on-disk plan cache (``photon_ml_tpu.cache``) vs the cold build the
  ``etl`` section always performs (the etl number stays honest — it
  never reads the cache).  The persistent XLA compilation cache is on,
  wherever ``cache.enable_compilation_cache`` puts it, so a second run
  also skips the scan compiles.
- One process per chip.  The arm sections (``stream``, ``score``,
  ``re``, ``cd_fused``, ``tron``) run each arm in a child process that
  needs the device, so this parent never initialises JAX for them: the
  record's ``device`` is what the arms report.  They cannot share a run
  with the sections that compute in this process.  ``serve`` (its
  parent computes the reference margins with JAX while two servers
  run) and ``mesh_stream`` (several hosts at once) need more than one
  process on the device at a time and so cannot run on one chip.
- ``--n/--d/--k`` shrink the shape (CI runs a tiny-shape ``etl``
  section as a fast-tier test so budget regressions fail in tests, not
  in the driver).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from contextlib import ExitStack

import numpy as np

# Single source of truth for the device peaks (ISSUE 8): the telemetry
# device-accounting table and this bench must emit the SAME roofline
# basis or one record would carry two disagreeing estimates.
from photon_ml_tpu.telemetry.device import peak_gbps

DEFAULT_SECTIONS = ("etl", "cached", "grr", "segment_sum", "colmajor")
# Sections whose work runs in child processes that need the device.
SPAWNING_SECTIONS = ("stream", "score", "re", "cd_fused", "serve",
                     "mesh_stream", "tron")
ALL_SECTIONS = DEFAULT_SECTIONS + ("powerlaw", "chunked", "sweep",
                                   "stream", "score", "re", "cd_fused",
                                   "serve", "mesh_stream", "tron")
# Default artifact directory (plans, spill stores, arm outputs): one
# fixed, git-ignored place in the checkout.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".bench_cache")
DEFAULT_BUDGET_S = 840.0
DEFAULT_N, DEFAULT_D, DEFAULT_K = 1_000_000, 100_000, 30

# Out-of-core stream section shape: the chunk total must dwarf the
# host window (≥ 6×; 24/2 = 12×) for the RSS bound to be a real claim
# — and finer chunks tighten the spilled arm's floor (window, prefetch
# queue, and in-flight temporaries all scale with CHUNK size, the
# resident arm with the DATASET).
STREAM_CHUNKS = 24
STREAM_WINDOW = 2
STREAM_DEPTH = 2
STREAM_SWEEPS = 5

# Scoring section shape (ISSUE 4): same window-vs-dataset discipline as
# the stream section — the streamed arm's score chunks must dwarf the
# LRU host window for the bounded-RSS claim to mean anything.
SCORE_CHUNKS = 16
SCORE_WINDOW = 2
SCORE_DEPTH = 2
SCORE_PASSES = 3
SCORE_D_RE = 4

# Streamed-RE section shape (ISSUE 5): entity chunks must dwarf the LRU
# host window (same discipline as the stream/score sections), and the
# per-entity offset schedule decays at entity-specific rates so the
# converged-entity retirement curve is GRADUAL — entities cross the
# movement tolerance on different sweeps, the shape a converging CD
# endgame actually produces.
RE_CHUNKS = 24          # target entity chunks (window 2 → 12×)
RE_WINDOW = 2
RE_DEPTH = 2
RE_SWEEPS = 8
RE_D = 8                # dense RE feature width
RE_TOL = 1e-4           # solver tolerance = retirement threshold

# λ-sweep section shape: lanes × solver-iteration cap (kept static so
# the batched and sequential arms solve the identical problem set).
SWEEP_LANES = 6
SWEEP_MAX_ITERS = 12

# Fused-CD section shape (ISSUE 11): the SAME fixed-effect + random-
# effect workload trained twice — per-coordinate (C streamed passes per
# CD cycle: solver iterations × line-search trials per coordinate) and
# fused (ONE pass per cycle, Jacobi solves).  The fused arm runs more
# (cheap) cycles — its per-cycle step is one damped Newton update, not
# a full inner solve — so the section's claims are pass COUNT per
# cycle, per-pass time, peak RSS, and cross-arm coefficient parity at
# convergence, not equal-cycle wall clock.
CDF_CHUNKS = 8
CDF_WINDOW = 2
CDF_DEPTH = 2
CDF_FUSED_CYCLES = 40
CDF_LEGACY_ITERS = 4
CDF_LEGACY_MAX_ITERS = 15
CDF_D_RE = 4

# Multi-host mesh-stream section shape (ISSUE 16): MESH_HOSTS worker
# processes chunk-synchronized over one shared chunk grid — each host
# streams only its contiguous shard (4 of 12 chunks) from a per-host
# spill subdir and the per-chunk partials cross hosts once per chunk
# step.  The shard must still dwarf the host window (4/2 = 2× per
# host, 12/2 = 6× fleet-wide) so per-host RSS stays a real claim, and
# the fused cycle count is small: the section measures the fleet
# schedule (barrier wait, reduces, replicated odometer), not
# convergence endurance.
MESH_HOSTS = 3
MESH_CHUNKS = 12
MESH_WINDOW = 2
MESH_DEPTH = 2
MESH_CYCLES = 10

# Streaming TRON section shape (ISSUE 17): the SAME ill-conditioned
# chunked logistic problem solved twice to the SAME relative gradient
# tolerance — streamed trust-region Newton (chunk-accumulated HVPs,
# Jacobi-preconditioned Steihaug-CG) vs streamed L-BFGS — each arm in
# its own subprocess for honest per-arm RSS.  Ill-conditioning comes
# from power-law per-column feature scales spanning TRON_SCALE_DECADES
# decades: the Hessian diagonal then spans ~2×decades decades, which a
# diagonal-preconditioned Newton absorbs into its change of variables
# while limited-memory quasi-Newton pays for it in data passes — the
# pass-count gap IS the section's claim.  The chunk grid keeps the
# store-bounded discipline of the stream section (chunks ≥ 4× the host
# window) so the HVP pass's RSS story is a real out-of-core claim.
TRON_CHUNKS = 8
TRON_WINDOW = 2
TRON_DEPTH = 2
TRON_SCALE_DECADES = 2.5   # per-column scale span 10^0 .. 10^-2.5
TRON_L2 = 0.1              # small enough that the scale span survives
TRON_TOL = 1e-5            # shared relative gradient tolerance
TRON_MAX_ITERS = 500       # generous cap: L-BFGS must REACH tol

# Serve section shape (ISSUE 12): a subprocess-isolated model server
# (honest per-process RSS, real socket path) under SERVE_CLIENTS
# concurrent OPEN-LOOP clients — each fires on its own fixed schedule
# regardless of completions, so queueing delay lands IN the measured
# latency instead of throttling the offered load (the closed-loop
# trap).  The request pool replays real dataset rows with every 7th
# entity id remapped to an unseen one (the fixed-effect fallback path
# stays on the measured path).
SERVE_CLIENTS = 4
SERVE_ROWS_PER_REQ = 8
SERVE_REQS_PER_CLIENT = 100      # measured requests per client
SERVE_WARM_REQS = 8              # per client, before the clock starts
SERVE_INTERVAL_S = 0.010         # open-loop firing cadence per client
SERVE_POOL = 512                 # distinct request rows replayed
SERVE_BATCH_ROWS = 64            # largest micro-batch bucket
# Request tracing (ISSUE 14): the ON arm's tail threshold — low enough
# that the storm's queueing tail samples richly, high enough that the
# steady-state p50 request is dropped after its histogram folds.
SERVE_TRACE_THRESHOLD_MS = 25.0
# Closed-loop PAIRS for the tracing-overhead A/B: ONE request in
# flight alternating between the live off/on servers, so p50 is the
# request SERVICE time and each pair shares one instant of box state.
# The open-loop storm offers more load than a 2-core box sustains —
# its p50 is queue depth, which amplifies any delta and measures
# nothing about tracing.
SERVE_CLOSED_REQS = 600

# Fleet arm (ISSUE 13): supervisor + 2 replicas behind the frontend,
# one replica SIGKILLed mid-storm.  Claims under test: zero failed
# client requests (the frontend's bounded retry-once), the killed
# replica restarted + re-warmed + back in rotation with the
# supervisor-measured restart latency, and overload sheds (if any)
# reported as a fraction, not hidden.
SERVE_FLEET_REPLICAS = 2
SERVE_FLEET_REQS_PER_CLIENT = 300
SERVE_FLEET_INTERVAL_S = 0.020   # open-loop cadence (storm ~6 s)
SERVE_FLEET_KILL_FRACTION = 0.33  # SIGKILL one replica this far in

# Per-section wall-clock estimates at the FULL bench shape, from a July
# capture since deleted (etl 123 s, grr measure 346 s, colmajor 305 s,
# segment_sum 35 s; powerlaw/chunked from PERF.md's round-5 record),
# linearly scaled by nnz for smaller shapes.  Pessimistic on purpose:
# a skipped section costs one number, a blown budget costs the whole
# record.
SECTION_EST_S = {
    "etl": 160.0,
    "cached": 45.0,
    "grr": 370.0,
    "segment_sum": 50.0,
    "colmajor": 330.0,
    "powerlaw": 500.0,
    "chunked": 300.0,
    # L+1 streamed solves over 4 ELL chunks (~(L·⌀16 + ~25) passes at
    # ~1.5 s/pass at the full shape) + chunk ETL.
    "sweep": 420.0,
    # Two chunk ETLs (one spilling to disk) + 2×(1 warm + STREAM_SWEEPS
    # timed) full-data passes.
    "stream": 420.0,
    # Two subprocess arms × (score-chunk ETL + 1 warm + SCORE_PASSES
    # timed one-pass scores).
    "score": 300.0,
    # Two subprocess arms × (entity-chunk ETL + RE_SWEEPS vmapped
    # bucket solves over the full dataset).
    "re": 420.0,
    # Two subprocess arms × (chunk ETL + a warm-up fit + the measured
    # fit: CDF_FUSED_CYCLES+1 passes fused, ~C×iters passes legacy).
    "cd_fused": 480.0,
    # TWO server subprocess arms (tracing off/on A/B — model load +
    # bucket warm-up each) + the open-loop client storm per arm
    # (~CLIENTS × REQS × INTERVAL of wall) + the parent's parity pass
    # over the request pool, then the fleet arm: 2 replica warm-ups, a
    # ~6 s storm with a mid-run SIGKILL, the restart-latency wait, and
    # the serve-report cross-process trace join.
    "serve": 480.0,
    # MESH_HOSTS concurrent worker subprocesses on a small box: each
    # pays the jax import + fused-program compile + full-dataset build,
    # then MESH_CYCLES chunk-synchronized fused passes over 1/HOSTS of
    # the chunks (the passes themselves are ~1/HOSTS of a cd_fused
    # pass, but the fixed per-worker costs dominate at bench shapes).
    "mesh_stream": 480.0,
    # Two subprocess arms × (chunk ETL + a short warm solve + the
    # measured solve-to-tolerance: tens of streamed passes TRON,
    # potentially hundreds L-BFGS on the ill-conditioned shape).
    "tron": 480.0,
}


def _peak_rss_mb() -> float:
    """Process high-water RSS (ru_maxrss is KB on Linux, bytes on mac)."""
    import resource

    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    if sys.platform == "darwin":
        peak //= 1024
    return peak / 1024.0


def _current_rss_mb(field: str = "VmRSS") -> float | None:
    """Instantaneous RSS from /proc (Linux); None elsewhere.
    ``field="RssAnon"`` reads the anonymous-only portion — the
    spilled chunk window and its device aliases are FILE-backed
    (memory-mapped, reclaimable under pressure), so anon RSS is the
    honest can-this-OOM number for the out-of-core arm."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith(field + ":"):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    return None


class _RssSampler:
    """Peak CURRENT RSS over a window, sampled at ~50 Hz — unlike
    ru_maxrss (a process-lifetime high-water mark) this attributes a
    peak to ONE bench arm, which is what the spilled-vs-resident
    comparison needs.  Falls back to ru_maxrss when /proc is absent."""

    def __init__(self):
        import threading

        self._stop = threading.Event()
        self._peak = 0.0
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            cur = _current_rss_mb()
            if cur is not None:
                self._peak = max(self._peak, cur)
            self._stop.wait(0.02)

    def __enter__(self):
        cur = _current_rss_mb()
        if cur is not None:
            self._peak = cur
            self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join()
        return False

    @property
    def peak_mb(self) -> float:
        return self._peak if self._peak else _peak_rss_mb()


def _make_ell(n: int, d: int, k: int, seed: int = 0):
    """Vectorized synthetic ELL batch: unique col ids per row by
    stratified sampling (one column per d/k-wide block)."""
    rng = np.random.default_rng(seed)
    block = max(d // k, 1)
    cols = (np.arange(k, dtype=np.int64) * block)[None, :] + rng.integers(
        0, block, (n, k)
    )
    cols = np.minimum(cols, d - 1)
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    labels = (rng.uniform(size=n) < 0.5).astype(np.float32)
    return cols.astype(np.int32), vals, labels


def _grr_stream_bytes(pair) -> int:
    """Bytes the GRR plan actually moves per fused value+gradient step:
    both directions' (vals f32 + 3 route planes i8) streams — including
    each direction's second-level overflow plan — spill COO, table
    windows, and the dense hot side."""

    def direction_bytes(d_) -> int:
        from photon_ml_tpu.data.grr import GrrRangeSplit

        if isinstance(d_, GrrRangeSplit):
            return sum(direction_bytes(p) for p in d_.parts)
        slots = d_.n_supertiles * 16384
        b = slots * (4 + 3)                           # vals + g1/g2/g3
        b += d_.n_spill * 12                          # spill idx/seg/val
        if d_.dense_grid:
            # gw-major grid: the window block index only changes between
            # gw runs, so each [128,128] window streams ONCE per run;
            # the per-tile partials are written then re-read by the
            # reshape-sum reduction.
            b += d_.n_gw * 16384 * 4
            b += 2 * d_.n_supertiles * (16384 // d_.cap) * 4
        else:
            # Legacy order: one window is (re)streamed per supertile.
            b += d_.n_supertiles * 16384 * 4
        if d_.overflow is not None:
            b += direction_bytes(d_.overflow)
        return b

    total = direction_bytes(pair.row_dir) + direction_bytes(pair.col_dir)
    if pair.col_mid is not None:
        total += direction_bytes(pair.col_mid)
    total += int(np.prod(pair.x_hot.shape)) * 4 * 2   # dense side, 2 dirs
    return total


def _device_record() -> dict:
    """Where THIS process's programs run, as JAX reports it.  Every
    record a process prints names its device."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def _run_arm(ctx: "BenchContext", flag: str, arm: str, *flags) -> dict:
    """Run one arm of a section in its own process and return its JSON
    record.  A process of its own gives the arm an honest peak RSS —
    and, on a chip, makes it the one process that holds the device:
    this parent never initialises JAX for a spawning section."""
    import subprocess

    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), flag, arm,
         "--n", str(ctx.n), "--d", str(ctx.d), "--k", str(ctx.k),
         "--cache-dir", ctx.cache_dir, *flags],
        capture_output=True, text=True,
        timeout=max(60.0, ctx.remaining()),
    )
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        raise RuntimeError(f"{flag} {arm!r} failed "
                           f"(rc={proc.returncode}): "
                           f"{proc.stderr[-500:]}")
    rec = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.strip()][-1])
    rec["arm_wall_s"] = round(time.time() - t0, 1)
    ctx.note_device(rec["device"])
    return rec


class BenchContext:
    """Shared state across sections: data, plans, step fn, budget."""

    def __init__(self, args):
        self.n, self.d, self.k = args.n, args.d, args.k
        self.cache_dir = args.cache_dir
        self.guards = args.guards
        self.monitor = getattr(args, "monitor", False)
        self.deadline = time.time() + args.budget_s
        self.budget_s = args.budget_s
        self.record: dict = {}
        self.errors: dict = {}
        self.skipped: list = []
        # Where the arms ran, as they report it (spawning sections).
        self.device: dict | None = None
        self.step_times: dict = {}
        self._data = None
        self._plan_path = None
        self._pair = None
        self._cm = None
        self._step = None
        self._w0 = None
        self.scale = (self.n * self.k) / (DEFAULT_N * DEFAULT_K)

    def remaining(self) -> float:
        return self.deadline - time.time()

    def note_device(self, device: dict) -> None:
        """Record where an arm ran; every arm of a run must agree."""
        if self.device is not None and self.device != device:
            raise RuntimeError(f"arms ran on different devices: "
                               f"{self.device} vs {device}")
        self.device = device

    def estimate(self, section: str) -> float:
        est = SECTION_EST_S[section] * self.scale
        if section in ("stream", "score", "re"):
            # Two subprocess arms pay a fixed jax-import + compile cost
            # each, regardless of shape.
            est += 60.0
        elif section == "mesh_stream":
            # MESH_HOSTS concurrent workers each pay the fixed
            # jax-import + compile cost (concurrent, but the box is
            # small — charge them near-serially).
            est += 40.0 * MESH_HOSTS
        # Sections that need the GRR plan pay a COLD build first when
        # neither a resident pair nor a cache file exists (e.g. etl was
        # skipped or never ran) — charge it, or a section admitted
        # under its own estimate blows the budget on the hidden build.
        if section == "cached" and not os.path.exists(self.plan_path()):
            est += SECTION_EST_S["etl"] * self.scale
        elif (section == "grr" and self._pair is None
                and not os.path.exists(self.plan_path())):
            est += SECTION_EST_S["etl"] * self.scale
        return max(3.0, est)

    # -- lazy shared pieces -------------------------------------------------

    def data(self):
        if self._data is None:
            self._data = _make_ell(self.n, self.d, self.k)
        return self._data

    def plan_path(self) -> str:
        # Defaults resolved from build_grr_pair's own signature — the
        # bench never holds a copy of them that could drift.  Memoized:
        # the fingerprint hashes the full dataset, and estimate()/
        # pair()/section_cached all ask for the same immutable answer.
        if self._plan_path is None:
            from photon_ml_tpu.data.grr import pair_cache_path_for

            cols, vals, _ = self.data()
            self._plan_path = pair_cache_path_for(
                cols, vals, self.d, self.cache_dir)
        return self._plan_path

    def pair(self):
        """The GRR plan — through the production warm path
        (``build_grr_pair`` with ``cache_dir``) when a cache file
        exists, else a cold build (recorded so later sections aren't
        double-charged)."""
        if self._pair is None:
            if os.path.exists(self.plan_path()):
                from photon_ml_tpu.data.grr import build_grr_pair

                cols, vals, _ = self.data()
                self._pair = build_grr_pair(cols, vals, self.d,
                                            cache_dir=self.cache_dir)
            else:
                self._pair = self._cold_build()
        return self._pair

    def _cold_build(self):
        """Cold plan build: never READS the cache (the ETL number of
        record stays honest) but saves the host plan for ``cached``
        (the save is timed inside ``build_grr_pair``'s phases)."""
        from photon_ml_tpu.data.grr import build_grr_pair

        cols, vals, _ = self.data()
        t0 = time.time()
        pair = build_grr_pair(cols, vals, self.d,
                              cache_dir=self.cache_dir,
                              cache_rebuild=True)
        self.record.setdefault("etl_grr_s", round(time.time() - t0, 1))
        self._pair = pair
        return pair

    def mk_batch(self, colmajor=None, grr=None):
        import jax.numpy as jnp

        from photon_ml_tpu.data.batch import SparseBatch

        cols, vals, labels = self.data()
        n = self.n
        return SparseBatch(
            values=jnp.asarray(vals), col_ids=jnp.asarray(cols),
            labels=jnp.asarray(labels),
            weights=jnp.ones((n,), jnp.float32),
            offsets=jnp.zeros((n,), jnp.float32),
            mask=jnp.ones((n,), jnp.float32),
            dim=self.d, colmajor=colmajor, grr=grr,
        )

    def step_fn(self):
        if self._step is None:
            import jax.numpy as jnp

            from photon_ml_tpu.data.normalization import (
                NormalizationContext,
            )
            from photon_ml_tpu.ops import losses
            from photon_ml_tpu.ops.objective import GLMObjective
            from photon_ml_tpu.ops.regularization import (
                RegularizationContext,
            )

            obj = GLMObjective(
                loss=losses.LOGISTIC,
                reg=RegularizationContext.l2(1.0),
                norm=NormalizationContext.identity(),
            )

            def step(w, batch):
                _, g = obj.value_and_gradient(w, batch)
                return w - 1e-6 * g

            self._step = step
            self._w0 = jnp.asarray(
                np.random.default_rng(1).normal(0, 0.1, self.d),
                jnp.float32)
        return self._step, self._w0

    def measure_variant(self, name: str, batch, length: int, iters: int):
        from photon_ml_tpu.utils.timing import measure_scanned

        step, w0 = self.step_fn()
        t0 = time.time()
        s = measure_scanned(step, w0, batch, length=length, iters=iters)
        self.step_times[name] = s
        print(f"{name}: {s*1e3:.2f} ms/step "
              f"(measured in {time.time()-t0:.0f}s)", file=sys.stderr)
        return s


# ---------------------------------------------------------------------------
# Sections.  Each mutates ctx.record; scan lengths amortize per-dispatch
# overhead to <~2% of step time for EVERY variant (advisor finding:
# unequal amortization biased the cross-variant ratio): the production
# solvers run the WHOLE optimize loop as one device program
# (lbfgs/tron while_loop), so per-call dispatch/fence is measurement
# artifact, not production cost.  GRR at ~5 ms/step needs length 250;
# colmajor/segment_sum at ~500 ms/step reach the same <~1% bias at
# length 20.
# ---------------------------------------------------------------------------


def section_etl(ctx: BenchContext) -> None:
    """Cold plan ETL (never reads the cache — the number of record) +
    the colmajor build, with the plan persisted for ``cached``."""
    from photon_ml_tpu.data import grr as grr_mod
    from photon_ml_tpu.data.colmajor import build_colmajor

    ctx.record.pop("etl_grr_s", None)  # force a fresh cold measurement
    ctx._pair = None
    ctx._cold_build()
    ctx.record["etl_phases"] = {
        k_: round(v, 2) for k_, v in grr_mod.last_build_phases.items()}
    cols, vals, _ = ctx.data()
    t0 = time.time()
    ctx._cm = build_colmajor(cols, vals, ctx.d)
    ctx.record["etl_colmajor_s"] = round(time.time() - t0, 1)
    print(f"ETL: grr={ctx.record['etl_grr_s']}s "
          f"(phases {ctx.record['etl_phases']}) "
          f"colmajor={ctx.record['etl_colmajor_s']}s", file=sys.stderr)


def section_cached(ctx: BenchContext) -> None:
    """Warm-path ETL: plan-cache load + device transfer vs cold build.

    The cold reference comes from this process's ``etl`` section when
    it ran; otherwise one cold build is performed here (and saved), so
    the section is self-contained.  The warm number drives the REAL
    production path — ``build_grr_pair`` with ``cache_dir`` — and
    reads the load/transfer split from its own phase timings, so the
    bench can never measure a different warm protocol than runs take."""
    from photon_ml_tpu.data import grr

    path = ctx.plan_path()
    if not os.path.exists(path):
        ctx._cold_build()
    cold_s = ctx.record.get("etl_grr_s")

    cols, vals, _ = ctx.data()
    t0 = time.time()
    warm_pair = grr.build_grr_pair(cols, vals, ctx.d,
                                   cache_dir=ctx.cache_dir)
    warm_s = time.time() - t0
    ph = dict(grr.last_build_phases)
    if ph.get("cache_hit") != 1.0:
        raise RuntimeError(f"plan cache entry unreadable: {path}")
    load_s = ph.get("cache_load_s", 0.0)
    transfer_s = ph.get("transfer_fence_s", 0.0)

    parity = None
    if ctx._pair is not None:
        # Cheap correctness cross-check when both plans are resident:
        # one contraction each direction must agree to float tolerance.
        import jax

        w = jax.numpy.asarray(
            np.random.default_rng(7).normal(0, 1, ctx.d), np.float32)
        a = np.asarray(ctx._pair.dot(w))
        b = np.asarray(warm_pair.dot(w))
        parity = bool(np.allclose(a, b, rtol=1e-5, atol=1e-5))
    ctx._pair = warm_pair

    ctx.record["cached"] = {
        "etl_warm_s": round(warm_s, 2),
        "load_s": round(load_s, 2),
        "transfer_s": round(transfer_s, 2),
        "etl_cold_s": cold_s,
        "warm_speedup": (round(cold_s / warm_s, 1)
                         if cold_s and warm_s > 0 else None),
        "parity_ok": parity,
        "plan_file_mb": round(os.path.getsize(path) / 1e6, 1),
    }
    print(f"cached: warm ETL {warm_s:.2f}s (load {load_s:.2f} + "
          f"transfer {transfer_s:.2f}) vs cold {cold_s}s "
          f"-> {ctx.record['cached']['warm_speedup']}x", file=sys.stderr)


def section_grr(ctx: BenchContext) -> None:
    ctx.measure_variant("grr", ctx.mk_batch(grr=ctx.pair()), 250, 2)


def section_colmajor(ctx: BenchContext) -> None:
    if ctx._cm is None:
        from photon_ml_tpu.data.colmajor import build_colmajor

        cols, vals, _ = ctx.data()
        t0 = time.time()
        ctx._cm = build_colmajor(cols, vals, ctx.d)
        ctx.record.setdefault("etl_colmajor_s",
                              round(time.time() - t0, 1))
    ctx.measure_variant("colmajor", ctx.mk_batch(colmajor=ctx._cm), 20, 2)


def section_segment_sum(ctx: BenchContext) -> None:
    ctx.measure_variant("segment_sum", ctx.mk_batch(), 20, 2)


def section_powerlaw(ctx: BenchContext) -> None:
    """Power-law-columns variant (round-4 verdict item #1: the uniform
    bench hides exactly the skew defect the column-range split fixes).
    Reciprocal popularity P(col) ∝ 1/(col+x0) puts ~45% of entries in
    table window 0 at this shape — the KDD/CTR profile."""
    from photon_ml_tpu.data.grr import build_grr_pair

    n, d, k = ctx.n, ctx.d, ctx.k
    _, vals, _ = ctx.data()
    rng = np.random.default_rng(3)
    x0 = float(d) / 14.0
    u = rng.uniform(size=(n, k))
    cols_p = np.minimum(x0 * np.exp(u * np.log((d + x0) / x0)) - x0,
                        d - 1).astype(np.int32)
    t0 = time.time()
    pair_p = build_grr_pair(cols_p, vals, d)
    etl_s = time.time() - t0
    row_stats = pair_p.row_dir.plan_stats()
    t0 = time.time()
    t_grr_p = ctx.measure_variant("grr_powerlaw",
                                  ctx.mk_batch(grr=pair_p), 250, 2)
    print(f"grr powerlaw: row spill_frac={row_stats['spill_frac']:.4f} "
          f"coo_frac={row_stats['coo_frac']:.5f} "
          f"caps={row_stats['cap']}", file=sys.stderr)
    ctx.record["powerlaw"] = {
        "step_ms_grr": round(t_grr_p * 1e3, 3),
        "etl_grr_s": round(etl_s, 1),
        "row_spill_frac": round(row_stats["spill_frac"], 4),
        "row_coo_frac": round(row_stats["coo_frac"], 5),
        "row_caps": row_stats["cap"],
        "range_bounds": row_stats.get("bounds"),
    }


def section_chunked(ctx: BenchContext) -> None:
    """Chunked (beyond-HBM) regime: one full-dataset value+gradient pass
    through resident ELL chunks (data/chunked_batch.py +
    optim/streaming.py) — the class that trains 3x10^7 examples on
    one chip (PERF.md).  Timed EAGERLY including per-chunk dispatch,
    because that IS this class's production cost (the streaming
    solver cannot fuse the pass into one device program)."""
    import jax

    from photon_ml_tpu.data.chunked_batch import build_chunked_batch
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.data.sparse_rows import SparseRows
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optim.streaming import ChunkedGLMObjective

    cols, vals, labels = ctx.data()
    n, d, k = ctx.n, ctx.d, ctx.k
    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l2(1.0),
        norm=NormalizationContext.identity(),
    )
    _, w0 = ctx.step_fn()
    t0 = time.time()
    rows_sp = SparseRows.from_flat(
        np.arange(n + 1, dtype=np.int64) * k,
        cols.reshape(-1).astype(np.int64), vals.reshape(-1))
    cobj = ChunkedGLMObjective(
        obj, build_chunked_batch(rows_sp, d, labels, n_chunks=4,
                                 layout="ell"),
        max_resident=4)
    etl_chunked_s = time.time() - t0
    jax.block_until_ready(cobj.value_and_gradient(w0)[1])  # compile+place
    t0 = time.time()
    chunk_iters = 5
    for _ in range(chunk_iters):
        # Fence EVERY pass: the streaming solver syncs after each
        # evaluation (the line search reads the value on host), so a
        # per-pass fence is production cost, not artifact.
        jax.block_until_ready(cobj.value_and_gradient(w0)[1])
    t_pass = (time.time() - t0) / chunk_iters
    print(f"chunked (4 ELL chunks, fully resident): {t_pass*1e3:.1f} "
          f"ms/pass (etl {etl_chunked_s:.0f}s)", file=sys.stderr)
    ctx.record["chunked"] = {
        "pass_ms": round(t_pass * 1e3, 1),
        "examples_per_sec": round(n / t_pass, 1),
        "n_chunks": 4,
        # All chunks held in HBM across passes — the resident end of
        # the chunked regime (no per-pass transfer timed); streaming
        # re-placement costs are link-dependent (PERF.md).
        "max_resident": 4,
        "regime": "resident",
        "layout": "ell",
        "etl_s": round(etl_chunked_s, 1),
    }


def section_sweep(ctx: BenchContext) -> None:
    """Batched λ-sweep vs L× sequential fits (ISSUE 2 tentpole
    measurement): the same L-point L2 grid over the chunked objective,
    trained once as ONE swept masked-lane solve (one chunk stream feeds
    all L coefficient lanes per evaluation) and once as L sequential
    streaming fits.  Records wall time, data passes (full chunk
    sweeps), and passes per grid step — the L → 1 amortization —
    plus a batched-vs-sequential coefficient parity check."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.chunked_batch import build_chunked_batch
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.data.sparse_rows import SparseRows
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.regularization import (
        RegularizationContext,
        RegularizationType,
        SweptRegularization,
    )
    from photon_ml_tpu.optim import OptimizerConfig
    from photon_ml_tpu.optim.streaming import (
        ChunkedGLMObjective,
        streaming_lbfgs_solve,
        streaming_lbfgs_solve_swept,
    )

    cols, vals, labels = ctx.data()
    n, d, k = ctx.n, ctx.d, ctx.k
    L = SWEEP_LANES
    lams = [float(10.0 ** e) for e in np.linspace(1.0, -2.0, L)]
    cfg = OptimizerConfig(max_iters=SWEEP_MAX_ITERS, tolerance=1e-6)

    t0 = time.time()
    rows_sp = SparseRows.from_flat(
        np.arange(n + 1, dtype=np.int64) * k,
        cols.reshape(-1).astype(np.int64), vals.reshape(-1))
    cb = build_chunked_batch(rows_sp, d, labels, n_chunks=4,
                             layout="ell")
    etl_s = time.time() - t0

    def mk_obj(lam):
        return GLMObjective(
            loss=losses.LOGISTIC,
            reg=RegularizationContext.l2(lam),
            norm=NormalizationContext.identity(),
        )

    w0 = jnp.zeros((d,), jnp.float32)

    # --- batched: one swept solve, all L lanes per data pass ---------
    reg = SweptRegularization.from_grid(RegularizationType.L2, lams)
    cobj_b = ChunkedGLMObjective(mk_obj(1.0), cb, max_resident=4)
    W0 = jnp.zeros((L, d), jnp.float32)
    # Warm both arms' compiles before timing (one max_iters=1 solve
    # each) — the bench convention everywhere: compiles are one-time
    # (and cached persistently), not per-grid cost.
    t0 = time.time()
    warm_cfg = OptimizerConfig(max_iters=1, tolerance=1e-6)
    streaming_lbfgs_solve_swept(
        lambda W: cobj_b.value_and_gradient_swept(W, reg),
        lambda W: cobj_b.value_swept(W, reg),
        W0, warm_cfg)
    # The 1-iteration warm solve only exercises the value-only program
    # if it happens to backtrack — compile it explicitly so a timed
    # iteration's first backtrack can't pay the XLA compile.
    cobj_b.value_swept(W0, reg)
    co_w = ChunkedGLMObjective(mk_obj(1.0), cb, max_resident=4)
    streaming_lbfgs_solve(co_w.value_and_gradient, w0, warm_cfg,
                          value_fn=co_w.value)
    co_w.value(w0)
    compile_s = time.time() - t0
    cobj_b.sweeps = 0
    t0 = time.time()
    res_b = streaming_lbfgs_solve_swept(
        lambda W: cobj_b.value_and_gradient_swept(W, reg),
        lambda W: cobj_b.value_swept(W, reg),
        W0, cfg)
    jax.block_until_ready(res_b.w)
    batched_s = time.time() - t0
    passes_b = cobj_b.sweeps
    iters_b = int(jnp.max(res_b.iterations))          # grid steps
    lane_iters_b = int(jnp.sum(res_b.iterations))
    print(f"sweep batched: {batched_s:.1f}s, {passes_b} data passes, "
          f"{iters_b} grid steps ({lane_iters_b} lane-iterations)",
          file=sys.stderr)

    # --- sequential: L independent streaming fits --------------------
    seq_s = 0.0
    passes_s = 0
    iters_s = 0
    W_seq = []
    for lam in lams:
        co = ChunkedGLMObjective(mk_obj(lam), cb, max_resident=4)
        t0 = time.time()
        r = streaming_lbfgs_solve(co.value_and_gradient, w0, cfg,
                                  value_fn=co.value)
        jax.block_until_ready(r.w)
        seq_s += time.time() - t0
        passes_s += co.sweeps
        iters_s += int(r.iterations)
        W_seq.append(np.asarray(r.w))
    print(f"sweep sequential: {seq_s:.1f}s, {passes_s} data passes, "
          f"{iters_s} lane-iterations", file=sys.stderr)

    parity = float(np.max(np.abs(np.asarray(res_b.w) - np.stack(W_seq))))
    # Passes per grid step (one iteration of EVERY lane): sequential
    # pays ~L fits' worth; batched pays ~1-2 shared sweeps.
    per_step_b = passes_b / max(iters_b, 1)
    per_step_s = (passes_s / max(iters_s, 1)) * L
    ctx.record["sweep"] = {
        "lanes": L,
        "max_iters": SWEEP_MAX_ITERS,
        "batched_s": round(batched_s, 2),
        "sequential_s": round(seq_s, 2),
        "speedup": (round(seq_s / batched_s, 2) if batched_s > 0
                    else None),
        "etl_chunked_s": round(etl_s, 1),
        "compile_s": round(compile_s, 1),
        "parity_max_dw": parity,
        "phases": {
            "batched": {
                "data_passes": passes_b,
                "grid_steps": iters_b,
                "lane_iterations": lane_iters_b,
                "passes_per_grid_step": round(per_step_b, 2),
            },
            "sequential": {
                "data_passes": passes_s,
                "lane_iterations": iters_s,
                "passes_per_grid_step": round(per_step_s, 2),
            },
        },
        "pass_amortization": (round(per_step_s / per_step_b, 2)
                              if per_step_b > 0 else None),
    }
    print(f"sweep: batched {batched_s:.1f}s vs sequential {seq_s:.1f}s "
          f"-> {ctx.record['sweep']['speedup']}x; passes/grid-step "
          f"{per_step_s:.1f} -> {per_step_b:.1f}", file=sys.stderr)


def _telemetry_block(summary: dict, sweeps_key: str = "solver.sweeps") -> dict:
    """The bench-facing slice of a telemetry summary (ISSUE 7): the
    overlap/stall derivations plus the pinned counters, embedded in
    each arm's JSON record so a section's last line carries the
    pipeline story alongside the wall-clock one."""
    c = summary.get("counters", {})
    d = summary.get("derived", {})
    return {
        "overlap_efficiency": d.get("overlap_efficiency"),
        "consumer_blocked_fraction": d.get("consumer_blocked_fraction"),
        "producer_stall_fraction": d.get("producer_stall_fraction"),
        "consumer_wait_s": round(c.get("prefetch.consumer_wait_s", 0.0), 3),
        "producer_stall_s": round(c.get("prefetch.producer_stall_s", 0.0), 3),
        "pass_span_total_s": d.get("pass_span_total_s"),
        "sweeps": c.get(sweeps_key, 0),
        "store_hits": c.get("store.hits", 0),
        "store_loads": c.get("store.loads", 0),
        "compiles": c.get("jax.compiles", 0),
        # Live-monitor event counters (ISSUE 10): the monitoring-off
        # default must read 0 here — the contract test pins it.
        "progress_events": c.get("monitor.progress_events", 0),
        "alerts": c.get("monitor.alerts", 0),
        # Captured XLA program costs (ISSUE 8): whatever the arm's
        # instrumented paths resolved during the telemetry window.
        "device_cost": summary.get("device", {}).get("programs") or None,
    }


def stream_arm_main(args) -> int:
    """One arm of the ``stream`` section, run in its OWN process
    (``bench.py --stream-arm spilled|resident``): a shared process
    would let the first arm's freed glibc arenas absorb the second
    arm's allocations and understate its RSS — per-arm ``ru_maxrss``
    is the honest high-water mark.  Emits one JSON line (the section
    contract, one level down) and writes the final gradient next to
    the cache dir for the parent's cross-arm parity check."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.chunked_batch import build_chunked_batch
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.data.sparse_rows import SparseRows
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optim.streaming import ChunkedGLMObjective

    arm = args.stream_arm
    n, d, k = args.n, args.d, args.k
    cols, vals, labels = _make_ell(n, d, k)
    rows_sp = SparseRows.from_flat(
        np.arange(n + 1, dtype=np.int64) * k,
        cols.reshape(-1).astype(np.int64), vals.reshape(-1))
    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l2(1.0),
        norm=NormalizationContext.identity(),
    )
    w0 = jnp.asarray(
        np.random.default_rng(1).normal(0, 0.1, d), jnp.float32)
    base_mb = _current_rss_mb()   # raw data + runtime, pre-chunk-ETL
    base_anon_mb = _current_rss_mb("RssAnon")

    t0 = time.time()
    if arm == "spilled":
        cb = build_chunked_batch(
            rows_sp, d, labels, n_chunks=STREAM_CHUNKS, layout="ell",
            spill_dir=os.path.join(args.cache_dir, "spill"),
            host_max_resident=STREAM_WINDOW)
        cobj = ChunkedGLMObjective(obj, cb, max_resident=0,
                                   prefetch_depth=STREAM_DEPTH)
    else:
        cb = build_chunked_batch(rows_sp, d, labels,
                                 n_chunks=STREAM_CHUNKS, layout="ell")
        cobj = ChunkedGLMObjective(obj, cb,
                                   max_resident=STREAM_CHUNKS)
    etl_s = time.time() - t0
    jax.block_until_ready(cobj.value_and_gradient(w0)[1])   # compile
    times = []
    # Steady-state RSS is sampled over the TIMED sweeps only:
    # ru_maxrss spans the whole arm and the one-time XLA compile spike
    # can set both arms' high-water, masking the training-regime
    # difference the section exists to measure.
    g = None
    # --guards: the timed sweeps run under the runtime guard harness —
    # the steady-state contract is ZERO compiles (everything compiled
    # in the warmup above; a nonzero count means a per-sweep retrace)
    # and no implicit host<->device transfers in the per-chunk dispatch
    # loop (transfer_guard 'log': reported, not fatal — on the CPU
    # backend the guard is structurally silent, host == device).
    # Telemetry over the TIMED sweeps only (metrics mode): the arm's
    # JSON gains the prefetcher overlap-efficiency block — how much of
    # the disk+staging tier the pipeline hid under device compute.
    # Started BEFORE the guard contexts and closed after they exit, so
    # the two jax.log_compiles scopes nest properly.
    from photon_ml_tpu import telemetry

    tel = telemetry.start("metrics")
    # --monitor (ISSUE 10): the live monitor — snapshot throttling,
    # online alert evaluation, AND the ephemeral /status endpoint
    # thread — spans the timed sweeps, so the pass_ms delta vs an
    # unmonitored arm IS the monitoring overhead the ≤2% acceptance
    # budget gates.  Off stays the default: no monitor session, no
    # status thread, zero `progress` events (the contract test pins
    # both states).
    mon = None
    if args.monitor:
        from photon_ml_tpu.telemetry import monitor as _mon

        mon = _mon.start(status_port=0)
    # Device cost (ISSUE 8) rides the IN-SWEEP capture on the first
    # timed pass: it reuses the chunk that pass already loaded (an
    # explicit pre-capture here would bump store.hits/loads with an
    # access the timed sweeps never made), emits no "Compiling" record
    # (lowering cache → the --guards zero-compile contract holds), and
    # its one-time AOT relower lands in a single pass that the
    # median-of-5 timing excludes.
    guard_stack = ExitStack()
    compile_log = None
    if args.guards:
        from photon_ml_tpu.analysis.guards import (
            count_compiles,
            no_implicit_transfers,
        )

        compile_log = guard_stack.enter_context(count_compiles())
        guard_stack.enter_context(no_implicit_transfers("log"))
    with guard_stack, _RssSampler() as rss:
        for _ in range(STREAM_SWEEPS):
            # Fence every pass — the streaming solver syncs per
            # evaluation (the line search reads the value on host).
            t0 = time.time()
            g = cobj.value_and_gradient(w0)[1]
            jax.block_until_ready(g)
            times.append(time.time() - t0)
    progress_block = None
    status_ok = None
    if mon is not None:
        # Prove the endpoint is live from inside the measured arm: one
        # localhost GET against the ephemeral port, parsed as JSON.
        import urllib.request

        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{mon.status_port}/status",
                    timeout=5) as resp:
                status_ok = bool(json.load(resp).get("stages"))
        except OSError as e:
            status_ok = False
            print(f"status endpoint probe failed: {e}", file=sys.stderr)
        progress_block = mon.summary()
        mon.close()
    tel_summary = tel.summary()
    tel.close()
    # Median, not mean: single passes on a small shared host jitter
    # ±20% and one descheduled pass would swing the cross-arm ratio.
    pass_s = float(np.median(times))
    # The last timed sweep's gradient IS the parity artifact — no
    # extra data pass to capture it.
    g = np.asarray(g)
    np.save(os.path.join(args.cache_dir, f"stream_grad_{arm}.npy"), g)

    peak = _peak_rss_mb()
    sweep_peak = rss.peak_mb
    anon = _current_rss_mb("RssAnon")   # steady state; None pre-4.5
    rec = {
        "arm": arm,
        "etl_s": round(etl_s, 1),
        "pass_ms": round(pass_s * 1e3, 1),
        "pass_ms_all": [round(t * 1e3, 1) for t in times],
        "examples_per_sec": round(n / pass_s, 1),
        "peak_rss_mb": round(peak, 1),
        "sweep_peak_rss_mb": round(sweep_peak, 1),
        # RSS attributable to the chunk tier at steady state: the
        # sweep-window peak minus the raw-data baseline snapshotted
        # before the chunk build.
        "rss_delta_mb": (round(sweep_peak - base_mb, 1)
                         if base_mb is not None else None),
        # Anonymous-only growth (kernel >= 4.5): the spilled arm's
        # window and device aliases are file-backed (reclaimable), so
        # this is the can-this-OOM working set.
        "anon_delta_mb": (round(anon - base_anon_mb, 1)
                          if anon is not None
                          and base_anon_mb is not None else None),
        "telemetry": _telemetry_block(tel_summary),
        # The per-chunk value+gradient program's XLA cost analysis +
        # roofline estimate (ISSUE 8 acceptance: FLOPs, bytes, and the
        # analytic time floor ride the arm's JSON).
        "device_cost": tel_summary.get("device", {}).get(
            "programs", {}).get("chunk_vg"),
    }
    if progress_block is not None:
        # The monitoring-on contract: stage snapshots + alerts + the
        # endpoint probe ride the arm's JSON.
        rec["progress"] = progress_block
        rec["status_ok"] = status_ok
    if compile_log is not None:
        rec["guards"] = {
            # Steady-state sweeps must compile nothing; a retrace here
            # is exactly the regression the budget tests pin.
            "sweep_compiles": compile_log.count,
            "sweep_compile_programs": sorted(set(compile_log.programs)),
            "transfer_guard": "log",
        }
    if arm == "spilled":
        store = cb.store
        rec.update({
            "peak_live_chunks": store.peak_resident,
            "disk_loads": store.loads,
            "window_hits": store.hits,
            "spill_files_mb": round(sum(
                os.path.getsize(store.path(i))
                for i in range(STREAM_CHUNKS) if store.has(i)) / 1e6, 1),
        })
    rec["device"] = _device_record()
    print(json.dumps(rec))
    return 0


def section_stream(ctx: BenchContext) -> None:
    """Out-of-core streaming regime (ISSUE 3 tentpole measurement):
    the SAME full-data value+gradient sweeps run twice — once with the
    disk-backed chunk store (``spill_dir``, host window
    ``STREAM_WINDOW`` of ``STREAM_CHUNKS`` chunks, async
    disk→host→device prefetch) and once all-resident — each arm in
    its own subprocess so peak host RSS is measured per arm
    (``ru_maxrss``; one shared process would hide the second arm's
    growth in the first arm's freed allocator arenas).  The claims
    under test: host RSS bounded by the window (chunks total 6× the
    window at this section's shape) while wall-clock per sweep stays
    within ~1.3× of all-resident (prefetch hides the disk tier)."""
    import shutil

    spill_dir = os.path.join(ctx.cache_dir, "spill")
    shutil.rmtree(spill_dir, ignore_errors=True)  # honest cold spill ETL

    flags = ((["--guards"] if ctx.guards else [])
             + (["--monitor"] if ctx.monitor else []))
    spilled = _run_arm(ctx, "--stream-arm", "spilled", *flags)
    resident = _run_arm(ctx, "--stream-arm", "resident", *flags)
    g_s = np.load(os.path.join(ctx.cache_dir, "stream_grad_spilled.npy"))
    g_r = np.load(os.path.join(ctx.cache_dir,
                               "stream_grad_resident.npy"))
    parity = float(np.max(np.abs(g_s - g_r)))

    def ratio(a, b):
        # Explicit None/zero-divisor guard: a legitimate 0.0 numerator
        # (a flat arm) must report 0.0, not null.
        if a is None or b is None or b == 0:
            return None
        return round(a / b, 2)

    ctx.record["stream"] = {
        "n_chunks": STREAM_CHUNKS,
        "host_max_resident": STREAM_WINDOW,
        "prefetch_depth": STREAM_DEPTH,
        "sweeps_timed": STREAM_SWEEPS,
        "layout": "ell",
        "monitor": ctx.monitor,
        "spilled": spilled,
        "resident": resident,
        # The two acceptance numbers: how much smaller the spilled
        # arm's training working set is (chunk-tier RSS growth over
        # the shared raw-data baseline), and the wall-clock cost of
        # streaming from disk.
        "rss_delta_ratio": ratio(resident["rss_delta_mb"],
                                 spilled["rss_delta_mb"]),
        "anon_delta_ratio": ratio(resident["anon_delta_mb"],
                                  spilled["anon_delta_mb"]),
        "peak_rss_ratio": ratio(resident["peak_rss_mb"],
                                spilled["peak_rss_mb"]),
        "pass_time_ratio": ratio(spilled["pass_ms"],
                                 resident["pass_ms"]),
        "grad_parity_max": parity,
    }
    s = ctx.record["stream"]
    print(f"stream: spilled {spilled['pass_ms']} ms/pass (peak RSS "
          f"{spilled['peak_rss_mb']} MB, Δ{spilled['rss_delta_mb']} MB,"
          f" window {spilled['peak_live_chunks']}/{STREAM_CHUNKS} "
          f"chunks) vs resident {resident['pass_ms']} ms/pass (peak "
          f"{resident['peak_rss_mb']} MB, Δ{resident['rss_delta_mb']} "
          f"MB); time ratio {s['pass_time_ratio']}x, RSS-delta ratio "
          f"{s['rss_delta_ratio']}x", file=sys.stderr)


def _make_score_workload(n: int, d: int, k: int):
    """Synthetic GAME scoring workload: sparse fixed-effect shard +
    one dense non-projected random effect — the coordinate mix the
    fused chunk program must cover — with a model of matching shape."""
    import jax.numpy as jnp

    from photon_ml_tpu.data.sparse_rows import SparseRows
    from photon_ml_tpu.game.dataset import GameDataset, group_by_entity
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_ml_tpu.models.glm import TaskType

    cols, vals, labels = _make_ell(n, d, k)
    rows = SparseRows.from_flat(
        np.arange(n + 1, dtype=np.int64) * k,
        cols.reshape(-1).astype(np.int64), vals.reshape(-1))
    rng = np.random.default_rng(5)
    E = max(32, n // 100)
    ids = rng.integers(0, E, n)
    x_re = rng.normal(0, 1, (n, SCORE_D_RE)).astype(np.float32)
    grouping = group_by_entity(ids)
    blocks = [jnp.asarray(rng.normal(0, 0.1, (ne, SCORE_D_RE))
                          .astype(np.float32))
              for ne in grouping.n_entities]
    model = GameModel(models={
        "global": FixedEffectModel(
            coefficients=Coefficients(means=jnp.asarray(
                rng.normal(0, 0.1, d).astype(np.float32))),
            feature_shard="global"),
        "per_user": RandomEffectModel(
            coefficient_blocks=blocks, grouping=grouping,
            feature_shard="re", entity_key="userId"),
    })
    dataset = GameDataset(labels=labels,
                          features={"global": rows, "re": x_re},
                          entity_ids={"userId": ids})
    return model, TaskType.LOGISTIC_REGRESSION, dataset


def score_arm_main(args) -> int:
    """One arm of the ``score`` section in its OWN process (same
    rationale as ``stream_arm_main``: per-arm ``ru_maxrss`` is the
    honest high-water mark).  ``streamed`` runs the fused one-pass
    chunk pipeline with the disk tier; ``resident`` the per-coordinate
    ``GameTransformer.transform``.  Emits one JSON line and saves the
    margins for the parent's cross-arm parity check."""
    from photon_ml_tpu.estimators.game_transformer import GameTransformer

    arm = args.score_arm
    n, d, k = args.n, args.d, args.k
    model, task, dataset = _make_score_workload(n, d, k)
    transformer = GameTransformer(model=model, task=task)
    chunk_rows = -(-n // SCORE_CHUNKS)
    base_mb = _current_rss_mb()
    base_anon_mb = _current_rss_mb("RssAnon")

    scorer = None
    if arm == "streamed":
        from photon_ml_tpu.estimators.streaming_scorer import (
            StreamingGameScorer,
        )

        # ONE scorer across passes: the plan (device tables + the spill
        # store's content key) is per-dataset state, derived once — a
        # production scoring run pays it once per run.
        scorer = StreamingGameScorer(
            model=model, task=task, chunk_rows=chunk_rows,
            spill_dir=os.path.join(args.cache_dir, "spill_score"),
            host_max_resident=SCORE_WINDOW,
            prefetch_depth=SCORE_DEPTH)

    last_result = {}

    def one_pass():
        if arm == "streamed":
            last_result.clear()
            last_result.update(scorer.score(dataset, keep_margins=True))
            return last_result["margins"]
        return transformer.transform(dataset)

    t0 = time.time()
    margins = one_pass()             # warm: compile + (streamed) spill
    etl_s = time.time() - t0
    times = []
    # Telemetry (metrics) over the timed passes: the streamed arm's
    # JSON gains the prefetcher overlap block (ISSUE 7).
    from photon_ml_tpu import telemetry

    tel = telemetry.start("metrics")
    with _RssSampler() as rss:
        for _ in range(SCORE_PASSES):
            t0 = time.time()
            margins = one_pass()
            times.append(time.time() - t0)
    tel_summary = tel.summary()
    tel.close()
    pass_s = float(np.median(times))
    np.save(os.path.join(args.cache_dir, f"score_margins_{arm}.npy"),
            np.asarray(margins))

    peak = _peak_rss_mb()
    anon = _current_rss_mb("RssAnon")
    rec = {
        "arm": arm,
        "warm_s": round(etl_s, 1),
        "pass_ms": round(pass_s * 1e3, 1),
        "pass_ms_all": [round(t * 1e3, 1) for t in times],
        "rows_per_sec": round(n / pass_s, 1),
        "peak_rss_mb": round(peak, 1),
        "sweep_peak_rss_mb": round(rss.peak_mb, 1),
        "rss_delta_mb": (round(rss.peak_mb - base_mb, 1)
                         if base_mb is not None else None),
        "anon_delta_mb": (round(anon - base_anon_mb, 1)
                          if anon is not None
                          and base_anon_mb is not None else None),
        "telemetry": _telemetry_block(tel_summary,
                                      sweeps_key="score.passes"),
    }
    if arm == "streamed":
        # The ACTUAL chunk count from the scorer (ceil rounding can
        # land below the SCORE_CHUNKS target at tiny n) — the
        # window-vs-chunks evidence must not overstate itself.
        rec.update({"n_chunks": last_result.get("n_chunks"),
                    "chunk_rows": chunk_rows,
                    "host_max_resident": SCORE_WINDOW,
                    "prefetch_depth": SCORE_DEPTH,
                    # Window-bound evidence: live decoded chunks during
                    # the last timed pass never exceeded the LRU window.
                    "peak_live_chunks": last_result.get(
                        "store", {}).get("peak_resident")})
    rec["device"] = _device_record()
    print(json.dumps(rec))
    return 0


def section_score(ctx: BenchContext) -> None:
    """Streaming fused scoring vs per-coordinate resident scoring
    (ISSUE 4 tentpole measurement): the SAME model × dataset scored by
    both paths, each arm in its own subprocess (honest per-arm peak
    RSS).  Claims under test: margins identical to float tolerance,
    streamed peak RSS bounded by the chunk window (chunks total
    SCORE_CHUNKS/SCORE_WINDOW = 8× the window), pass time within ~1.1×
    of resident."""
    import shutil

    shutil.rmtree(os.path.join(ctx.cache_dir, "spill_score"),
                  ignore_errors=True)   # honest cold spill ETL

    streamed = _run_arm(ctx, "--score-arm", "streamed")
    resident = _run_arm(ctx, "--score-arm", "resident")
    m_s = np.load(os.path.join(ctx.cache_dir,
                               "score_margins_streamed.npy"))
    m_r = np.load(os.path.join(ctx.cache_dir,
                               "score_margins_resident.npy"))
    parity = float(np.max(np.abs(m_s - m_r))) if len(m_s) else 0.0

    def ratio(a, b):
        if a is None or b is None or b == 0:
            return None
        return round(a / b, 2)

    ctx.record["score"] = {
        "n_chunks": streamed.get("n_chunks", SCORE_CHUNKS),
        "host_max_resident": SCORE_WINDOW,
        "prefetch_depth": SCORE_DEPTH,
        "passes_timed": SCORE_PASSES,
        "streamed": streamed,
        "resident": resident,
        "margin_parity_max": parity,
        "pass_time_ratio": ratio(streamed["pass_ms"],
                                 resident["pass_ms"]),
        "peak_rss_ratio": ratio(resident["peak_rss_mb"],
                                streamed["peak_rss_mb"]),
        "rss_delta_ratio": ratio(resident["rss_delta_mb"],
                                 streamed["rss_delta_mb"]),
    }
    s = ctx.record["score"]
    print(f"score: streamed {streamed['pass_ms']} ms/pass "
          f"({streamed['rows_per_sec']} rows/s, peak RSS "
          f"{streamed['peak_rss_mb']} MB) vs resident "
          f"{resident['pass_ms']} ms/pass ({resident['rows_per_sec']} "
          f"rows/s, peak {resident['peak_rss_mb']} MB); time ratio "
          f"{s['pass_time_ratio']}x, parity {parity:.2e}",
          file=sys.stderr)


def _make_re_workload(n: int, seed: int = 9):
    """Synthetic random-effect workload with power-law-ish entity skew
    (a long tail of small entities + a head of heavy ones → several
    size buckets) and per-entity offset decay rates for the retirement
    curve.  Returns (dataset, entity decay rates, base offset noise)."""
    from photon_ml_tpu.game.dataset import GameDataset

    rng = np.random.default_rng(seed)
    e_small = max(8, n // 64)
    e_big = max(2, e_small // 16)
    n_small = (3 * n) // 4
    ids = np.concatenate([
        rng.integers(0, e_small, n_small),
        rng.integers(e_small, e_small + e_big, n - n_small),
    ]).astype(np.int64)
    E = e_small + e_big
    x = rng.normal(0, 1, (n, RE_D)).astype(np.float32)
    w_true = rng.normal(0, 0.5, (E, RE_D)).astype(np.float32)
    margins = np.einsum("np,np->n", x, w_true[ids])
    labels = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margins)))
    dataset = GameDataset(labels=labels.astype(np.float32),
                          features={"re": x}, entity_ids={"u": ids})
    decay = rng.uniform(0.05, 0.6, E).astype(np.float32)
    base = rng.normal(0, 0.3, n).astype(np.float32)
    return dataset, ids, decay, base


def re_arm_main(args) -> int:
    """One arm of the ``re`` section in its OWN process (per-arm
    ``ru_maxrss`` honesty, as in ``stream_arm_main``): RE_SWEEPS
    emulated CD sweeps — per-entity offsets decay at entity-specific
    rates toward a fixed point, the converging-endgame shape — over
    the streamed (chunk store + prefetch + retirement) or resident
    random-effect coordinate.  Emits one JSON line; saves the final
    coefficients and scores for the parent's cross-arm parity check."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.game.coordinates import (
        build_random_effect_coordinate,
        build_streamed_random_effect_coordinate,
    )
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optim import OptimizerConfig

    arm = args.re_arm
    n = args.n
    dataset, ids, decay, base = _make_re_workload(n)
    E = len(decay)
    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l2(1.0),
        norm=NormalizationContext.identity(),
    )
    cfg = OptimizerConfig(max_iters=60, tolerance=RE_TOL)
    base_mb = _current_rss_mb()
    base_anon_mb = _current_rss_mb("RssAnon")

    t0 = time.time()
    if arm == "streamed":
        chunk_entities = max(1, -(-E // RE_CHUNKS))
        coord = build_streamed_random_effect_coordinate(
            "u", dataset, "re", obj, config=cfg,
            spill_dir=os.path.join(args.cache_dir, "spill_re"),
            chunk_entities=chunk_entities,
            host_max_resident=RE_WINDOW, prefetch_depth=RE_DEPTH,
            retirement=True)
    else:
        coord = build_random_effect_coordinate(
            "u", dataset, "re", obj, config=cfg)
    etl_s = time.time() - t0

    per_ex_decay = decay[ids]
    times, solved, retired = [], [], []
    w = None
    scores = None

    def sweep(s):
        nonlocal w, scores
        # Squared exponent: per-entity offset deltas cross the
        # retirement tolerance on DIFFERENT sweeps (fast-decay
        # entities around sweep 3, slow ones near the end) — the
        # gradual work-reduction curve of a real CD endgame.
        off = jnp.asarray(base * (per_ex_decay ** (2 * s)))
        t0 = time.time()
        w, diag = coord.train(off, w)
        scores = coord.score(w)
        jax.block_until_ready(scores)
        times.append(time.time() - t0)
        if isinstance(diag, dict):               # streamed coordinate
            solved.append(int(diag["entities_solved"]))
            retired.append(int(diag["entities_retired"]))
            coord.retire_converged()             # the CD hook
        else:
            solved.append(E)
            retired.append(0)

    # Sweep 0 runs OUTSIDE the RSS sampler: it pays the one-time
    # per-bucket XLA compiles, whose allocator spike would set BOTH
    # arms' high-water and mask the training-regime residency
    # difference this section exists to measure (the round-8 stream
    # section's rule).  It also runs outside the telemetry window, so
    # the overlap numbers describe the steady state, not the compile
    # sweep.
    sweep(0)
    from photon_ml_tpu import telemetry

    tel = telemetry.start("metrics")
    with _RssSampler() as rss:
        for s in range(1, RE_SWEEPS):
            sweep(s)
    tel_summary = tel.summary()
    tel.close()
    # Sweep 0 pays the per-bucket XLA compiles; the steady-state number
    # is the median of the remaining sweeps.
    sweep_s = float(np.median(times[1:])) if len(times) > 1 else times[0]
    np.save(os.path.join(args.cache_dir, f"re_coefs_{arm}.npy"),
            np.concatenate([np.asarray(b).ravel() for b in w]))
    np.save(os.path.join(args.cache_dir, f"re_scores_{arm}.npy"),
            np.asarray(scores))

    peak = _peak_rss_mb()
    anon = _current_rss_mb("RssAnon")
    rec = {
        "arm": arm,
        "etl_s": round(etl_s, 1),
        "entities": E,
        "sweeps": RE_SWEEPS,
        "sweep_s": round(sweep_s, 3),
        "sweep_s_all": [round(t, 3) for t in times],
        "rows_per_sec": round(n / sweep_s, 1),
        "entities_per_sec": round(E / sweep_s, 1),
        "entities_solved_per_sweep": solved,
        "entities_retired_per_sweep": retired,
        "peak_rss_mb": round(peak, 1),
        "sweep_peak_rss_mb": round(rss.peak_mb, 1),
        "rss_delta_mb": (round(rss.peak_mb - base_mb, 1)
                         if base_mb is not None else None),
        "anon_delta_mb": (round(anon - base_anon_mb, 1)
                          if anon is not None
                          and base_anon_mb is not None else None),
        "telemetry": _telemetry_block(tel_summary,
                                      sweeps_key="re.sweeps"),
    }
    if arm == "streamed":
        store = coord.store
        rec.update({
            "n_chunks": store.n_chunks,
            "chunk_entities": coord.chunk_entities,
            "host_max_resident": RE_WINDOW,
            "prefetch_depth": RE_DEPTH,
            "peak_live_chunks": store.peak_resident,
            "disk_loads": store.loads,
            "window_hits": store.hits,
            "spill_files_mb": round(sum(
                os.path.getsize(store.path(i))
                for i in range(store.n_chunks) if store.has(i)) / 1e6, 1),
        })
    rec["device"] = _device_record()
    print(json.dumps(rec))
    return 0


def section_re(ctx: BenchContext) -> None:
    """Out-of-core random-effect training (ISSUE 5 tentpole
    measurement): the SAME emulated converging CD sweeps run twice —
    streamed (disk-backed entity chunks, LRU window, prefetch,
    converged-entity retirement) and resident — each arm in its own
    subprocess for honest per-arm peak RSS.  Claims under test: final
    coefficients/scores match to float tolerance despite retirement,
    live window ≤ host_max_resident, retirement reduces per-sweep
    solved entities monotonically on the converging schedule."""
    import shutil

    shutil.rmtree(os.path.join(ctx.cache_dir, "spill_re"),
                  ignore_errors=True)   # honest cold spill ETL

    streamed = _run_arm(ctx, "--re-arm", "streamed")
    resident = _run_arm(ctx, "--re-arm", "resident")
    c_s = np.load(os.path.join(ctx.cache_dir, "re_coefs_streamed.npy"))
    c_r = np.load(os.path.join(ctx.cache_dir, "re_coefs_resident.npy"))
    s_s = np.load(os.path.join(ctx.cache_dir, "re_scores_streamed.npy"))
    s_r = np.load(os.path.join(ctx.cache_dir, "re_scores_resident.npy"))
    coef_parity = float(np.max(np.abs(c_s - c_r))) if len(c_s) else 0.0
    score_parity = float(np.max(np.abs(s_s - s_r))) if len(s_s) else 0.0

    def ratio(a, b):
        if a is None or b is None or b == 0:
            return None
        return round(a / b, 2)

    solved = streamed["entities_solved_per_sweep"]
    ctx.record["re"] = {
        "n_chunks": streamed.get("n_chunks"),
        "host_max_resident": RE_WINDOW,
        "prefetch_depth": RE_DEPTH,
        "sweeps": RE_SWEEPS,
        "streamed": streamed,
        "resident": resident,
        "coef_parity_max": coef_parity,
        "score_parity_max": score_parity,
        # Retirement work reduction: solved entities on the last sweep
        # as a fraction of the first (monotone ↓ on this schedule).
        "retirement_work_fraction": (round(solved[-1] / solved[0], 4)
                                     if solved and solved[0] else None),
        "sweep_time_ratio": ratio(streamed["sweep_s"],
                                  resident["sweep_s"]),
        "peak_rss_ratio": ratio(resident["peak_rss_mb"],
                                streamed["peak_rss_mb"]),
        "rss_delta_ratio": ratio(resident["rss_delta_mb"],
                                 streamed["rss_delta_mb"]),
    }
    r = ctx.record["re"]
    print(f"re: streamed {streamed['sweep_s']}s/sweep "
          f"({streamed['rows_per_sec']} rows/s, peak RSS "
          f"{streamed['peak_rss_mb']} MB, window "
          f"{streamed['peak_live_chunks']}/{streamed.get('n_chunks')} "
          f"chunks) vs resident {resident['sweep_s']}s/sweep (peak "
          f"{resident['peak_rss_mb']} MB); solved/sweep {solved}; "
          f"coef parity {coef_parity:.2e}", file=sys.stderr)


def _make_cd_fused_workload(n: int, d: int, k: int, seed: int = 11):
    """Synthetic GAME workload for the fused-CD section: a sparse
    fixed-effect shard (the chunked master grid) + a dense random
    effect with skewed entity sizes (several buckets, like the re
    section), labels from both planes so neither coordinate is
    decorative."""
    from photon_ml_tpu.game.dataset import GameDataset

    rng = np.random.default_rng(seed)
    cols, vals, _ = _make_ell(n, d, k, seed=seed)
    e_small = max(8, n // 256)
    e_big = max(2, e_small // 16)
    n_small = (3 * n) // 4
    ids = np.concatenate([
        rng.integers(0, e_small, n_small),
        rng.integers(e_small, e_small + e_big, n - n_small),
    ]).astype(np.int64)
    E = e_small + e_big
    x_re = rng.normal(0, 1, (n, CDF_D_RE)).astype(np.float32)
    w_fe = rng.normal(0, 1, d).astype(np.float32)
    w_re = rng.normal(0, 0.5, (E, CDF_D_RE)).astype(np.float32)
    margins = (np.einsum("nk,nk->n", vals, w_fe[cols])
               + np.einsum("np,np->n", x_re, w_re[ids]))
    labels = (rng.uniform(size=n)
              < 1.0 / (1.0 + np.exp(-margins))).astype(np.float32)
    rows = [(cols[i], vals[i]) for i in range(n)]
    return GameDataset(labels=labels,
                       features={"fe": rows, "re": x_re},
                       entity_ids={"u": ids},
                       feature_dims={"fe": d})


def cd_fused_arm_main(args) -> int:
    """One arm of the ``cd_fused`` section in its OWN process (per-arm
    ``ru_maxrss`` honesty): the same chunked FE + dense-RE workload
    trained with ``cd_fused`` on (``fused``) or off (``percoord``).
    A 1-cycle warm-up fit pays the XLA compiles and spills the chunk
    stores; the MEASURED fit then runs with a warm everything — its
    ``compiles`` count is the zero-new-compiles-after-warmup claim.
    Emits one JSON line; saves final coefficients for the parent's
    cross-arm parity check."""
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.config import (
        CoordinateConfig,
        CoordinateKind,
        OptimizerSettings,
        TrainingConfig,
    )
    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.models.glm import TaskType

    arm = args.cd_fused_arm
    n = args.n
    fused = arm == "fused"
    ds = _make_cd_fused_workload(n, args.d, args.k)
    chunk_rows = -(-n // CDF_CHUNKS)

    def cfg(iters):
        return TrainingConfig(
            task_type=TaskType.LOGISTIC_REGRESSION,
            coordinates=[
                CoordinateConfig(
                    name="global", kind=CoordinateKind.FIXED_EFFECT,
                    feature_shard="fe",
                    optimizer=OptimizerSettings(
                        max_iters=CDF_LEGACY_MAX_ITERS, reg_weight=1.0)),
                CoordinateConfig(
                    name="per_u", kind=CoordinateKind.RANDOM_EFFECT,
                    feature_shard="re", entity_key="u",
                    optimizer=OptimizerSettings(
                        max_iters=CDF_LEGACY_MAX_ITERS, reg_weight=2.0)),
            ],
            update_sequence=["global", "per_u"], n_iterations=iters,
            validation_fraction=0.0, validate_per_iteration=False,
            intercept=False, chunk_rows=chunk_rows, chunk_layout="ELL",
            cd_fused=fused,
            spill_dir=os.path.join(args.cache_dir, f"spill_cdf_{arm}"),
            host_max_resident=CDF_WINDOW, prefetch_depth=CDF_DEPTH)

    base_mb = _current_rss_mb()
    # Warm-up: compiles + chunk/sidecar spill (content-keyed — the
    # measured fit reuses the files).  Runs OUTSIDE the telemetry
    # window and the RSS sampler, the other sections' rule.
    t0 = time.time()
    warm_cfg = cfg(1)
    warm_cfg.validate()
    GameEstimator(warm_cfg).fit(ds)
    warmup_s = time.time() - t0

    iters = CDF_FUSED_CYCLES if fused else CDF_LEGACY_ITERS
    run_cfg = cfg(iters)
    run_cfg.validate()
    tel = telemetry.start("metrics")
    t0 = time.time()
    with _RssSampler() as rss:
        fit = GameEstimator(run_cfg).fit(ds)[0]
    fit_s = time.time() - t0
    tel_summary = tel.summary()
    tel.close()

    c = tel_summary.get("counters", {})
    d_ = tel_summary.get("derived", {})
    sweeps = c.get("solver.sweeps", 0)
    cycles = c.get("cd.cycles", 0)
    pass_total_s = d_.get("pass_span_total_s") or None
    pass_s = (pass_total_s / sweeps if pass_total_s and sweeps else None)
    models = fit.model.models
    np.save(os.path.join(args.cache_dir, f"cdf_fe_{arm}.npy"),
            np.asarray(models["global"].coefficients.means))
    np.save(os.path.join(args.cache_dir, f"cdf_re_{arm}.npy"),
            np.concatenate([np.asarray(b).ravel()
                            for b in models["per_u"].coefficient_blocks]))

    peak = _peak_rss_mb()
    rec = {
        "arm": arm,
        "warmup_s": round(warmup_s, 1),
        "fit_s": round(fit_s, 2),
        "cycles": cycles,
        "data_passes": sweeps,
        "passes_per_cycle": (round(sweeps / cycles, 3) if cycles
                             else None),
        "pass_s": round(pass_s, 3) if pass_s else None,
        "rows_per_sec": (round(n * sweeps / pass_total_s, 1)
                         if pass_total_s else None),
        "chunk_rows": chunk_rows,
        "n_chunks": CDF_CHUNKS,
        "peak_rss_mb": round(peak, 1),
        "fit_peak_rss_mb": round(rss.peak_mb, 1),
        "rss_delta_mb": (round(rss.peak_mb - base_mb, 1)
                         if base_mb is not None else None),
        "telemetry": _telemetry_block(tel_summary),
    }
    rec["device"] = _device_record()
    print(json.dumps(rec))
    return 0


def section_cd_fused(ctx: BenchContext) -> None:
    """Fused CD super-sweep vs per-coordinate training (ISSUE 11
    tentpole measurement): the same workload in two subprocess arms.
    Claims under test: the fused arm's passes/cycle ≈ 1 (vs ~C ×
    solver-iterations per cycle legacy), its per-pass time stays within
    a small factor of the legacy pass (it computes every coordinate's
    statistics per chunk), zero compiles in the measured (warm) fit,
    and the two arms' final coefficients agree at convergence."""
    import shutil

    for arm in ("fused", "percoord"):
        shutil.rmtree(os.path.join(ctx.cache_dir, f"spill_cdf_{arm}"),
                      ignore_errors=True)   # honest cold spill ETL

    fused = _run_arm(ctx, "--cd-fused-arm", "fused")
    percoord = _run_arm(ctx, "--cd-fused-arm", "percoord")
    fe_f = np.load(os.path.join(ctx.cache_dir, "cdf_fe_fused.npy"))
    fe_p = np.load(os.path.join(ctx.cache_dir, "cdf_fe_percoord.npy"))
    re_f = np.load(os.path.join(ctx.cache_dir, "cdf_re_fused.npy"))
    re_p = np.load(os.path.join(ctx.cache_dir, "cdf_re_percoord.npy"))
    coef_parity = float(max(np.max(np.abs(fe_f - fe_p)),
                            np.max(np.abs(re_f - re_p))
                            if len(re_f) else 0.0))

    def ratio(a, b):
        if a is None or b is None or b == 0:
            return None
        return round(a / b, 3)

    ctx.record["cd_fused"] = {
        "n_chunks": CDF_CHUNKS,
        "host_max_resident": CDF_WINDOW,
        "prefetch_depth": CDF_DEPTH,
        "fused": fused,
        "percoord": percoord,
        "passes_per_cycle_fused": fused["passes_per_cycle"],
        "passes_per_cycle_percoord": percoord["passes_per_cycle"],
        "pass_count_ratio": ratio(percoord["passes_per_cycle"],
                                  fused["passes_per_cycle"]),
        # The fused pass computes every coordinate's statistics, so it
        # is allowed to cost more than one legacy (FE-only) pass — the
        # win is needing ~C× fewer of them per cycle.
        "pass_time_ratio": ratio(fused["pass_s"], percoord["pass_s"]),
        "coef_parity_max": coef_parity,
    }
    s = ctx.record["cd_fused"]
    print(f"cd_fused: fused {fused['passes_per_cycle']} passes/cycle "
          f"({fused['pass_s']}s/pass, {fused['cycles']} cycles, peak "
          f"RSS {fused['peak_rss_mb']} MB, compiles "
          f"{fused['telemetry']['compiles']}) vs per-coordinate "
          f"{percoord['passes_per_cycle']} passes/cycle "
          f"({percoord['pass_s']}s/pass); pass-count ratio "
          f"{s['pass_count_ratio']}x, pass-time ratio "
          f"{s['pass_time_ratio']}x, coef parity {coef_parity:.2e}",
          file=sys.stderr)


def mesh_arm_main(args) -> int:
    """One HOST of the ``mesh_stream`` section in its own process:
    joins the fleet named by the environment (the ``jax.distributed``
    env trio → psum transport; the ``PHOTON_FLEET_*`` trio → tcp
    transport; neither → a single-host control run), trains the shared
    fused-CD workload over ITS chunk shard with a per-host spill
    subdir, and writes the per-host ``run_log.jsonl`` the parent's
    fleet-report join consumes.  Emits one JSON line; saves final
    coefficients for the parent's cross-host bitwise-identity check."""
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.config import (
        CoordinateConfig,
        CoordinateKind,
        OptimizerSettings,
        TrainingConfig,
        read_env,
    )
    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.models.glm import TaskType
    from photon_ml_tpu.parallel import fleet
    from photon_ml_tpu.utils.run_log import RunLogger

    if read_env("JAX_COORDINATOR_ADDRESS"):
        from photon_ml_tpu.cli.game_training_driver import (
            distributed_init_from_env,
        )

        distributed_init_from_env()
    fctx = fleet.initialize_from_env()
    is_fleet = fctx is not None and fctx.is_fleet
    host = fctx.host_id if is_fleet else 0
    mesh_dir = os.path.join(args.cache_dir, "mesh_stream")
    out_dir = fleet.host_dir(mesh_dir, fctx)
    os.makedirs(out_dir, exist_ok=True)

    n = args.n
    ds = _make_cd_fused_workload(n, args.d, args.k)
    chunk_rows = -(-n // MESH_CHUNKS)
    cfg = TrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinates=[
            CoordinateConfig(
                name="global", kind=CoordinateKind.FIXED_EFFECT,
                feature_shard="fe",
                optimizer=OptimizerSettings(
                    max_iters=CDF_LEGACY_MAX_ITERS, reg_weight=1.0)),
            CoordinateConfig(
                name="per_u", kind=CoordinateKind.RANDOM_EFFECT,
                feature_shard="re", entity_key="u",
                optimizer=OptimizerSettings(
                    max_iters=CDF_LEGACY_MAX_ITERS, reg_weight=2.0)),
        ],
        update_sequence=["global", "per_u"], n_iterations=MESH_CYCLES,
        validation_fraction=0.0, validate_per_iteration=False,
        intercept=False, chunk_rows=chunk_rows, chunk_layout="ELL",
        cd_fused=True,
        # Shared base on purpose: the chunk builder host-shards it
        # (``fleet.host_dir``) exactly as a production config would.
        spill_dir=os.path.join(mesh_dir, "spill"),
        host_max_resident=MESH_WINDOW, prefetch_depth=MESH_DEPTH)
    cfg.validate()

    run_info = {"telemetry": "metrics"}
    if is_fleet:
        run_info.update(fleet_host=fctx.host_id,
                        fleet_hosts=fctx.n_hosts,
                        fleet_transport=fctx.transport)
    run_log_path = os.path.join(out_dir, "run_log.jsonl")
    rl = RunLogger(run_log_path, run_info=run_info)
    tel = telemetry.start("metrics", run_logger=rl)
    t0 = time.time()
    fit = GameEstimator(cfg).fit(ds)[0]
    fit_s = time.time() - t0
    tel_summary = tel.summary()
    tel.close()
    rl.close()

    c = tel_summary.get("counters", {})
    sweeps = c.get("solver.sweeps", 0)
    cycles = c.get("cd.cycles", 0)
    pass_total_s = tel_summary.get("derived", {}).get(
        "pass_span_total_s") or None
    models = fit.model.models
    tag = f"h{host}" if is_fleet else "solo"
    np.save(os.path.join(mesh_dir, f"mesh_fe_{tag}.npy"),
            np.asarray(models["global"].coefficients.means))
    np.save(os.path.join(mesh_dir, f"mesh_re_{tag}.npy"),
            np.concatenate([np.asarray(b).ravel()
                            for b in models["per_u"].coefficient_blocks]))
    rec = {
        "host": host,
        "transport": fctx.transport if is_fleet else None,
        "fit_s": round(fit_s, 2),
        "cycles": cycles,
        "data_passes": sweeps,
        "passes_per_cycle": (round(sweeps / cycles, 3) if cycles
                             else None),
        "pass_span_total_s": pass_total_s,
        "chunks_streamed": c.get("fleet.chunks_streamed", 0),
        "reduces": c.get("fleet.psums", 0),
        "barrier_wait_s": round(c.get("fleet.barrier_wait_s", 0.0), 3),
        "chunk_rows": chunk_rows,
        "n_chunks": MESH_CHUNKS,
        "peak_rss_mb": round(_peak_rss_mb(), 1),
        "run_log": run_log_path,
        "telemetry": _telemetry_block(tel_summary),
    }
    rec["device"] = _device_record()
    print(json.dumps(rec))
    return 0


def section_mesh_stream(ctx: BenchContext) -> None:
    """Multi-host out-of-core training (ISSUE 16 tentpole
    measurement): MESH_HOSTS worker processes train the SAME fused-CD
    workload as one chunk-synchronized fleet — each host spills +
    streams only its shard of the MESH_CHUNKS grid and the per-chunk
    partials cross hosts once per chunk step.  Transport is probed:
    real ``jax.distributed`` psum where this box supports multi-process
    CPU collectives, the local-fleet tcp coordinator otherwise (the
    same solver/schedule code either way).  Claims under test: every
    host reports the SAME reduce count (the sentinel-padded schedule's
    no-deadlock invariant), the replicated solver odometer agrees
    host-to-host with passes/cycle ≈ 1, final coefficients are bitwise
    identical across hosts, per-host peak RSS is bounded by
    shard+window (not the full grid), and the barrier-wait fraction
    stays a small tax.  The per-host run logs are joined by the SAME
    ``telemetry fleet-report`` analyzer an operator would use."""
    import shutil
    import subprocess

    from photon_ml_tpu.parallel import fleet
    from photon_ml_tpu.telemetry import fleet_report

    mesh_dir = os.path.join(ctx.cache_dir, "mesh_stream")
    shutil.rmtree(mesh_dir, ignore_errors=True)  # honest cold spill ETL
    os.makedirs(mesh_dir, exist_ok=True)

    use_psum = fleet.probe_cpu_multiprocess_collectives()
    coord = None
    envs = []
    if use_psum:
        import socket

        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        envs = [{"JAX_COORDINATOR_ADDRESS": f"localhost:{port}",
                 "JAX_NUM_PROCESSES": str(MESH_HOSTS),
                 "JAX_PROCESS_ID": str(h)} for h in range(MESH_HOSTS)]
    else:
        print("mesh_stream: multi-process CPU collectives unsupported "
              "here; using the local-fleet tcp transport",
              file=sys.stderr)
        coord = fleet.ReduceCoordinator(MESH_HOSTS)
        envs = [{"PHOTON_FLEET_NUM_HOSTS": str(MESH_HOSTS),
                 "PHOTON_FLEET_HOST_ID": str(h),
                 "PHOTON_FLEET_COORDINATOR": coord.address}
                for h in range(MESH_HOSTS)]

    def spawn(extra_env):
        env = dict(os.environ)
        env.update(extra_env)
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--mesh-arm", "fleet", "--n", str(ctx.n), "--d",
             str(ctx.d), "--k", str(ctx.k),
             "--cache-dir", ctx.cache_dir],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)

    # All hosts MUST run concurrently (they barrier at every chunk
    # step); the fleet wall-clock is the slowest host's, measured by
    # the parent around the whole fan-out.
    t0 = time.time()
    procs = [spawn(e) for e in envs]
    recs = []
    try:
        for h, proc in enumerate(procs):
            out, err = proc.communicate(
                timeout=max(120.0, ctx.remaining()))
            sys.stderr.write(err)
            if proc.returncode != 0:
                raise RuntimeError(f"mesh host {h} failed "
                                   f"(rc={proc.returncode}): "
                                   f"{err[-500:]}")
            recs.append(json.loads(
                [ln for ln in out.splitlines() if ln.strip()][-1]))
            ctx.note_device(recs[-1]["device"])
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        if coord is not None:
            coord.close()
    fleet_wall_s = time.time() - t0

    fe = [np.load(os.path.join(mesh_dir, f"mesh_fe_h{h}.npy"))
          for h in range(MESH_HOSTS)]
    re_ = [np.load(os.path.join(mesh_dir, f"mesh_re_h{h}.npy"))
           for h in range(MESH_HOSTS)]
    coef_cross = float(max(
        max(np.max(np.abs(fe[0] - fe[h]))
            for h in range(1, MESH_HOSTS)),
        max(np.max(np.abs(re_[0] - re_[h]))
            for h in range(1, MESH_HOSTS))))

    # The operator-facing join over the per-host logs IS the section's
    # analysis layer — the bench exercises it instead of reimplementing
    # the invariants.
    fr = fleet_report.analyze(
        fleet_report.load_host_logs([r["run_log"] for r in recs]))

    spans = [r["pass_span_total_s"] for r in recs]
    span = max([s for s in spans if s], default=None)
    sweeps = fr["fleet_sweeps"] or max(
        (r["data_passes"] for r in recs), default=0)
    ctx.record["mesh_stream"] = {
        "hosts": MESH_HOSTS,
        "transport": recs[0]["transport"],
        "n_chunks": MESH_CHUNKS,
        "chunks_per_host": -(-MESH_CHUNKS // MESH_HOSTS),
        "host_max_resident": MESH_WINDOW,
        "prefetch_depth": MESH_DEPTH,
        "cycles": MESH_CYCLES,
        "fleet_wall_s": round(fleet_wall_s, 1),
        # Fleet throughput: each chunk-synchronized sweep covers the
        # full n rows ACROSS hosts, paced by the slowest host's
        # in-pass time.
        "rows_per_sec": (round(ctx.n * sweeps / span, 1)
                         if span and sweeps else None),
        "passes_per_cycle": fr["passes_per_cycle"],
        "barrier_wait_fraction": fr["max_barrier_wait_fraction"],
        "max_host_peak_rss_mb": (fr["max_peak_rss_mb"]
                                 or max(r["peak_rss_mb"]
                                        for r in recs)),
        "reduces_per_host": fr["reduces"],
        "total_chunks_streamed": fr["total_chunks_streamed"],
        "barrier_agreement": fr["barrier_agreement"],
        "odometer_agreement": fr["odometer_agreement"],
        "coef_cross_host_max": coef_cross,
        "coef_identical_across_hosts": coef_cross == 0.0,
        "fleet_report_ok": fr["ok"],
        "per_host": recs,
    }
    s = ctx.record["mesh_stream"]
    print(f"mesh_stream: {MESH_HOSTS} hosts ({s['transport']}), "
          f"reduce counts {fr['reduce_counts']}, passes/cycle "
          f"{s['passes_per_cycle']}, max barrier-wait fraction "
          f"{s['barrier_wait_fraction']:.1%}, max host peak RSS "
          f"{s['max_host_peak_rss_mb']} MB, {s['rows_per_sec']} rows/s "
          f"fleet-wide, cross-host coef delta {coef_cross:.1e}, "
          f"fleet-report {'PASS' if fr['ok'] else 'FAIL'}",
          file=sys.stderr)


class _ServeServer:
    """One subprocess-isolated model server for the serve section:
    spawn with a config dict, poll ready, post, stop.  Two of these
    run SIMULTANEOUSLY for the tracing A/B (ISSUE 14) so alternating
    probe requests hit both arms under the identical instantaneous box
    state — sequential arms on a shared 2-core box measured ±15%
    drift, an order of magnitude above the effect."""

    def __init__(self, ctx: BenchContext, cfg: dict, arm: str):
        import subprocess

        self.arm = arm
        self.cfg_path = os.path.join(ctx.cache_dir,
                                     f"serve_config_{arm}.json")
        self._info_path = os.path.join(ctx.cache_dir,
                                       f"serve_info_{arm}.json")
        if os.path.exists(self._info_path):
            os.remove(self._info_path)
        with open(self.cfg_path, "w") as f:
            json.dump(cfg, f)
        self.t_start = time.time()
        self.url: str | None = None
        self.warm_wait_s: float | None = None
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "photon_ml_tpu.serving",
             "--config", self.cfg_path, "--info-file", self._info_path],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)))

    def _startup_fail(self, msg: str):
        # Kill BEFORE reading stderr: read() on a live child's pipe
        # blocks until an EOF that never comes (the startup-timeout
        # branch reaches here with the server still running).
        if self.proc.poll() is None:
            self.proc.kill()
        _out, err = self.proc.communicate()
        return RuntimeError(
            f"serve[{self.arm}]: {msg}: {(err or '')[-500:]}")

    def wait_ready(self, deadline: float) -> None:
        import urllib.request

        while not os.path.exists(self._info_path):
            if self.proc.poll() is not None or time.time() > deadline:
                raise self._startup_fail(
                    "server never wrote its info file")
            time.sleep(0.05)
        with open(self._info_path) as f:
            self.url = json.load(f)["url"]
        while True:          # poll /healthz: warming → ready
            if self.proc.poll() is not None or time.time() > deadline:
                raise self._startup_fail("server never became ready")
            try:
                with urllib.request.urlopen(self.url + "/healthz",
                                            timeout=2) as r:
                    if json.loads(r.read())["state"] == "ready":
                        break
            except OSError:
                pass
            time.sleep(0.1)
        self.warm_wait_s = time.time() - self.t_start

    def post(self, body: bytes) -> dict:
        import urllib.request

        req = urllib.request.Request(
            self.url + "/v1/score", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    def status(self) -> dict:
        import urllib.request

        with urllib.request.urlopen(self.url + "/status",
                                    timeout=10) as r:
            return json.loads(r.read())["serving"]

    def stop(self) -> dict | None:
        """SIGTERM, drain, return the CLI's final JSON line (or None
        if the exit was unclean — the caller raises)."""
        import signal
        import subprocess

        self.proc.send_signal(signal.SIGTERM)
        try:
            stdout, stderr = self.proc.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            stdout, stderr = self.proc.communicate()
        sys.stderr.write(stderr[-2000:] if stderr else "")
        if self.proc.returncode != 0:
            raise RuntimeError(f"serve[{self.arm}]: server exited rc="
                               f"{self.proc.returncode}")
        return json.loads(
            [ln for ln in stdout.splitlines() if ln.strip()][-1])


def _serve_storm(srv: _ServeServer, bodies: list) -> tuple:
    """The open-loop client storm against one server: SERVE_CLIENTS
    threads each firing on a fixed schedule (queue delay lands IN the
    measured latency) — a warm storm first, then the measured one.
    → (sorted latencies, measured wall seconds)."""
    import threading

    latencies: list[list[float]] = [[] for _ in range(SERVE_CLIENTS)]
    errors: list = []

    def client(c: int, measured: bool) -> None:
        reqs_n = (SERVE_REQS_PER_CLIENT if measured
                  else SERVE_WARM_REQS)
        t0 = time.perf_counter()
        for j in range(reqs_n):
            target = t0 + j * SERVE_INTERVAL_S
            lag = target - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            body = bodies[(c * 31 + j) % len(bodies)]
            t1 = time.perf_counter()
            try:
                srv.post(body)
            except Exception as e:  # noqa: BLE001 - recorded
                errors.append(f"{type(e).__name__}: {e}")
                continue
            if measured:
                latencies[c].append(time.perf_counter() - t1)

    for measured in (False, True):     # warm storm, then the clock
        t0 = time.time()
        threads = [threading.Thread(target=client, args=(c, measured))
                   for c in range(SERVE_CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall_s = time.time() - t0
    lat = np.asarray(sorted(x for c in latencies for x in c))
    if errors or not len(lat):
        raise RuntimeError(f"serve: {len(errors)} client error(s): "
                           f"{errors[:3]}")
    return lat, wall_s


def _serve_paired_closed_loop(off: _ServeServer, on: _ServeServer,
                              bodies: list) -> dict:
    """The tracing-overhead A/B (ISSUE 14): one request in flight,
    ALTERNATING between the live off/on servers — each pair runs under
    the same instantaneous box state, so the median pairwise delta is
    the tracing cost, not queue depth (open-loop storms here run past
    a 2-core box's capacity) and not inter-arm drift (sequential arms
    measured ±15% on the shared build box)."""
    off_lat, on_lat, deltas = [], [], []
    for j in range(SERVE_CLOSED_REQS):
        body = bodies[j % len(bodies)]
        # Alternate which arm goes first inside the pair so per-pair
        # cache/scheduler asymmetry cancels too.
        order = (off, on) if j % 2 == 0 else (on, off)
        pair = {}
        for srv in order:
            t1 = time.perf_counter()
            srv.post(body)
            pair[srv.arm] = time.perf_counter() - t1
        off_lat.append(pair["off"])
        on_lat.append(pair["on"])
        deltas.append(pair["on"] - pair["off"])
    p50_off = float(np.percentile(off_lat, 50)) * 1e3
    p50_on = float(np.percentile(on_lat, 50)) * 1e3
    delta_ms = float(np.percentile(deltas, 50)) * 1e3
    return {
        "p50_off_ms": round(p50_off, 3),
        "p50_on_ms": round(p50_on, 3),
        # The claim of record: the MEDIAN PAIRWISE delta over the off
        # p50 — each pair shares one instant of box state, so marginal
        # p50 jitter (±4% observed on the build box) cancels and the
        # per-request tracing cost survives.
        "overhead_frac": (round(delta_ms / p50_off, 4)
                          if p50_off > 0 else None),
        "median_pair_delta_ms": round(delta_ms, 4),
        "closed_reqs": SERVE_CLOSED_REQS,
    }


def section_serve(ctx: BenchContext) -> None:
    """Online serving (ISSUE 12 tentpole measurement + ISSUE 14
    tracing A/B): TWO simultaneous subprocess-isolated model servers —
    tracing off and tracing on — with the open-loop client storm on
    the ON arm (the production-shape numbers) and an alternating
    one-in-flight closed loop across BOTH arms measuring the tracing
    overhead against its ≤2% budget under identical box state.
    Claims under test: served margins match the batch scorer on the
    identical rows, client-observed p50/p99 latency and sustained
    rows/s under concurrency, micro-batch fill, the tracing stage
    medians (queue-wait / dispatch), and the server's own peak RSS —
    all from the real socket path."""
    import shutil

    from photon_ml_tpu.estimators.streaming_scorer import (
        StreamingGameScorer,
    )
    from photon_ml_tpu.io.model_io import save_game_model
    from photon_ml_tpu.serving.engine import dataset_rows

    n, d, k = ctx.n, ctx.d, ctx.k
    model, task, dataset = _make_score_workload(n, d, k)
    model_dir = os.path.join(ctx.cache_dir, "serve_model")
    shutil.rmtree(model_dir, ignore_errors=True)
    save_game_model(model, task, model_dir)

    # Request pool: real dataset rows, every 7th entity id remapped to
    # an unseen one (the fixed-effect-fallback path stays measured).
    pool_n = min(SERVE_POOL, n)
    sub = dataset.take(slice(0, pool_n))
    ids = np.array(sub.entity_ids["userId"], copy=True)
    ids[::7] = 10 ** 9 + np.arange(len(ids[::7]))
    sub.entity_ids = dict(sub.entity_ids)
    sub.entity_ids["userId"] = ids
    reqs = dataset_rows(sub, 0, pool_n)
    bodies = [json.dumps({"rows": reqs[lo: lo + SERVE_ROWS_PER_REQ]})
              .encode()
              for lo in range(0, pool_n - SERVE_ROWS_PER_REQ + 1,
                              SERVE_ROWS_PER_REQ)]

    base_cfg = {
        "model_dir": model_dir,
        "batch_rows": SERVE_BATCH_ROWS,
        "batch_deadline_ms": 2.0,
        "ell_row_capacity": max(k, 8),
        "spill_dir": os.path.join(ctx.cache_dir, "spill_serve"),
        "hot_swap_poll_s": 0.0,
    }
    servers: dict = {}
    try:
        on_cfg = dict(base_cfg, trace="on",
                      trace_threshold_ms=SERVE_TRACE_THRESHOLD_MS,
                      log_path=os.path.join(ctx.cache_dir,
                                            "serve_on_log.jsonl"))
        servers["on"] = on = _ServeServer(ctx, on_cfg, "on")
        servers["off"] = off = _ServeServer(
            ctx, dict(base_cfg, trace="off"), "off")
        deadline = time.time() + max(60.0, ctx.remaining())
        for srv in (on, off):
            srv.wait_ready(deadline)

        # Paired A/B FIRST, both servers equally fresh (an arm that
        # just absorbed the storm measures slower for non-tracing
        # reasons — heap/allocator history — and poisons the delta).
        overhead = _serve_paired_closed_loop(off, on, bodies)
        final = {"off": off.stop()}
        del servers["off"]

        # The open-loop storm runs on the ON arm ALONE (tracing is the
        # new default — these are the production-shape numbers of
        # record, comparable to prior rounds; the OFF arm is gone so
        # its residency cannot perturb them).
        lat, wall_s = _serve_storm(on, bodies)
        parity_out = on.post(bodies[0])
        status = on.status()
        final["on"] = on.stop()
        del servers["on"]
    except BaseException:
        # Kill AND reap any still-live server, surfacing its stderr —
        # the root cause of a serve-section failure usually lives
        # there, and an unreaped child leaks a zombie + pipe FDs for
        # the rest of the bench run.
        for srv in servers.values():
            if srv.proc.poll() is None:
                srv.proc.kill()
            try:
                _out, err = srv.proc.communicate(timeout=10)
                sys.stderr.write((err or "")[-2000:])
            except Exception:  # photon-lint: disable=swallowed-exception (best-effort teardown forensics: the original section failure is already propagating and must not be masked by a reap error)
                pass
        raise
    rows_total = len(lat) * SERVE_ROWS_PER_REQ

    # Parity: one ON-arm response vs the batch path's margins on the
    # identical rows.
    ref = StreamingGameScorer(
        model=model, task=task, chunk_rows=pool_n).score(
        sub, keep_margins=True)
    parity = float(np.max(np.abs(
        np.asarray(parity_out["margins"], np.float32)
        - ref["margins"][:SERVE_ROWS_PER_REQ])))

    stages = status.get("stages") or {}
    overhead["sampled"] = (
        (status.get("tracing") or {}).get("sampled_tail", 0)
        + (status.get("tracing") or {}).get("sampled_floor", 0))

    def _stage_p50(name: str):
        return (stages.get(name) or {}).get("p50_ms")

    ctx.record["serve"] = {
        "clients": SERVE_CLIENTS,
        "rows_per_request": SERVE_ROWS_PER_REQ,
        "requests": int(len(lat)),
        "interval_ms": SERVE_INTERVAL_S * 1e3,
        "batch_rows": SERVE_BATCH_ROWS,
        "warm_wait_s": round(on.warm_wait_s, 2),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
        "rows_per_sec": round(rows_total / wall_s, 1),
        "wall_s": round(wall_s, 2),
        "batch_fill": status["batcher"]["batch_fill"],
        "batches": status["batcher"]["batches"],
        "margin_parity_max": parity,
        "server_peak_rss_mb": status["peak_rss_mb"],
        "server_rc": final["on"]["rc"],
        # ISSUE 14: history-gated stage medians + the paired tracing
        # overhead A/B (alternating closed loop across both live arms).
        "queue_wait_ms": _stage_p50("queue_wait"),
        "dispatch_ms": _stage_p50("dispatch"),
        "trace_overhead": overhead,
    }
    s = ctx.record["serve"]
    print(f"serve: {SERVE_CLIENTS} clients x "
          f"{SERVE_REQS_PER_CLIENT} reqs x {SERVE_ROWS_PER_REQ} rows: "
          f"p50 {s['p50_ms']} ms, p99 {s['p99_ms']} ms, "
          f"{s['rows_per_sec']} rows/s, batch fill {s['batch_fill']}, "
          f"parity {parity:.2e}, server peak RSS "
          f"{s['server_peak_rss_mb']} MB; stage medians queue_wait "
          f"{s['queue_wait_ms']} ms / dispatch {s['dispatch_ms']} ms; "
          f"tracing overhead p50 {overhead['p50_off_ms']} → "
          f"{overhead['p50_on_ms']} ms ({overhead['overhead_frac']}, "
          f"median pair delta {overhead['median_pair_delta_ms']} ms)",
          file=sys.stderr)
    _serve_fleet_arm(ctx, on.cfg_path, bodies)


def _serve_fleet_arm(ctx: BenchContext, base_cfg_path: str,
                     bodies: list) -> None:
    """Fleet arm (ISSUE 13): supervisor + SERVE_FLEET_REPLICAS replica
    subprocesses behind the frontend; one replica SIGKILLed mid-storm.
    Reports failed-request count (the retry-once contract says 0),
    supervisor-measured restart latency, and the shed fraction."""
    import shutil
    import signal
    import subprocess
    import threading
    import urllib.error
    import urllib.request

    budget = ctx.remaining()
    if budget < 90.0:
        # No silent caps: a skipped arm is recorded as skipped, not
        # absent-and-assumed-green.
        ctx.record["serve"]["fleet"] = {
            "skipped": f"budget ({budget:.0f}s remaining < 90s)"}
        print("serve: fleet arm SKIPPED (budget)", file=sys.stderr)
        return

    with open(base_cfg_path) as f:
        cfg = json.load(f)
    frontend_log = os.path.join(ctx.cache_dir, "fleet_frontend.jsonl")
    cfg.update({
        "replicas": SERVE_FLEET_REPLICAS,
        # Tight detection/restart knobs: the measured restart latency
        # should be dominated by the replica's model load + warm-up,
        # not the probe cadence.
        "probe_every_s": 0.25,
        "probe_timeout_s": 2.0,
        "restart_backoff_s": 0.25,
        # Request tracing across the fleet (ISSUE 14): the frontend
        # writes its trace log here; replicas write theirs under the
        # fleet workdir — serve-report joins them by trace id below.
        "trace": "on",
        "trace_threshold_ms": SERVE_TRACE_THRESHOLD_MS,
        "log_path": frontend_log,
    })
    fleet_cfg_path = os.path.join(ctx.cache_dir, "serve_fleet.json")
    with open(fleet_cfg_path, "w") as f:
        json.dump(cfg, f)
    fleet_dir = os.path.join(ctx.cache_dir, "fleet")
    shutil.rmtree(fleet_dir, ignore_errors=True)
    info_path = os.path.join(ctx.cache_dir, "fleet_info.json")
    if os.path.exists(info_path):
        os.remove(info_path)
    t_start = time.time()
    proc = subprocess.Popen(
        [sys.executable, "-m", "photon_ml_tpu.serving",
         "--config", fleet_cfg_path, "--info-file", info_path,
         "--fleet-dir", fleet_dir],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=os.path.dirname(os.path.abspath(__file__)))

    def _fail(msg: str):
        if proc.poll() is None:
            proc.kill()
        _out, err = proc.communicate()
        return RuntimeError(f"serve fleet: {msg}: {(err or '')[-500:]}")

    def get_json(url_: str) -> dict:
        with urllib.request.urlopen(url_, timeout=10) as r:
            return json.loads(r.read())

    try:
        deadline = time.time() + max(60.0, min(budget, 240.0))
        while not os.path.exists(info_path):
            if proc.poll() is not None or time.time() > deadline:
                raise _fail("frontend never wrote its info file")
            time.sleep(0.05)
        with open(info_path) as f:
            url = json.load(f)["url"]
        while True:     # BOTH replicas warm before the storm
            if proc.poll() is not None or time.time() > deadline:
                raise _fail("fleet never became fully ready")
            try:
                st = get_json(url + "/status")
                if st["fleet"]["ready"] == SERVE_FLEET_REPLICAS:
                    break
            except OSError:
                pass
            time.sleep(0.2)
        warm_wait_s = time.time() - t_start

        def post(body: bytes) -> dict:
            req = urllib.request.Request(
                url + "/v1/score", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                return json.loads(r.read())

        latencies: list = []
        errors: list = []
        client_sheds = [0]
        lat_lock = threading.Lock()

        def client(c: int) -> None:
            t0 = time.perf_counter()
            for j in range(SERVE_FLEET_REQS_PER_CLIENT):
                target = t0 + j * SERVE_FLEET_INTERVAL_S
                lag = target - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                body = bodies[(c * 37 + j) % len(bodies)]
                t1 = time.perf_counter()
                try:
                    post(body)
                except urllib.error.HTTPError as e:
                    # A 429/503 shed is the DESIGNED overload answer
                    # (Retry-After), not a failed request — it rides
                    # the shed fraction, never failed_requests.
                    with lat_lock:
                        if e.code in (429, 503):
                            client_sheds[0] += 1
                        else:
                            errors.append(f"HTTP {e.code}")
                    e.read()
                    continue
                except Exception as e:  # noqa: BLE001 - recorded
                    with lat_lock:
                        errors.append(f"{type(e).__name__}: {e}")
                    continue
                with lat_lock:
                    latencies.append(time.perf_counter() - t1)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(SERVE_CLIENTS)]
        storm_s = SERVE_FLEET_REQS_PER_CLIENT * SERVE_FLEET_INTERVAL_S
        for t in threads:
            t.start()
        # SIGKILL one READY replica mid-storm — the fault the fleet
        # exists to survive.
        time.sleep(storm_s * SERVE_FLEET_KILL_FRACTION)
        st = get_json(url + "/status")
        victim = next((r for r in st["fleet"]["replicas"]
                       if r["state"] == "ready" and r["pid"]), None)
        if victim is None:
            raise _fail(f"no ready replica to SIGKILL "
                        f"(fleet: {st['fleet']['replicas']})")
        os.kill(victim["pid"], signal.SIGKILL)
        t_kill = time.time()
        for t in threads:
            t.join()
        # The replica must come back: restarted, re-warmed, in
        # rotation.
        restart_deadline = time.time() + 120.0
        while True:
            st = get_json(url + "/status")
            if (st["fleet"]["restarts"] >= 1
                    and st["fleet"]["ready"] == SERVE_FLEET_REPLICAS):
                break
            if time.time() > restart_deadline:
                raise _fail("killed replica never rejoined the fleet")
            time.sleep(0.2)
        recovery_wall_s = time.time() - t_kill
        fe = st["frontend"]
        shed_total = fe["shed"]
        served = fe["requests"]
        shed_fraction = (shed_total / (shed_total + served)
                         if (shed_total + served) else 0.0)
        lat = np.asarray(sorted(latencies))
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            stdout, stderr = proc.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            stdout, stderr = proc.communicate()
        sys.stderr.write(stderr[-2000:] if stderr else "")
    if proc.returncode != 0:
        raise RuntimeError(f"serve fleet: frontend exited rc="
                           f"{proc.returncode}")
    final = json.loads(
        [ln for ln in stdout.splitlines() if ln.strip()][-1])

    # Cross-process trace join (ISSUE 14 acceptance): serve-report
    # over the frontend's and every replica's trace logs — the SIGKILL
    # storm guarantees retried requests, so the retry-cost column is
    # exercised, and ≥99% of replica-side tail requests must join a
    # frontend trace by trace id.
    trace_join = None
    try:
        import glob as _glob
        import io as _io

        from photon_ml_tpu.telemetry.serve_report import (
            run_serve_report,
        )

        replica_logs = sorted(_glob.glob(
            os.path.join(fleet_dir, "replica_*.jsonl")))
        if os.path.exists(frontend_log) and replica_logs:
            buf = _io.StringIO()
            rep = run_serve_report([frontend_log] + replica_logs,
                                   out=buf)
            trace_join = {
                "ok": rep["ok"],
                "join_fraction": rep["join_fraction"],
                "tail_requests": rep["tail_requests"],
                "retried_requests": rep["retried_requests"],
                "retry_cost_ms": rep["retry_cost_ms"]["total"],
                "dominant_stage": rep["dominant_stage"],
            }
            print(f"serve fleet trace join: "
                  f"{rep['joined']}/{rep['tail_requests']} tail "
                  f"requests joined "
                  f"({rep['join_fraction']}), dominant stage "
                  f"{rep['dominant_stage']}, "
                  f"{rep['retried_requests']} retried",
                  file=sys.stderr)
    except Exception as e:  # noqa: BLE001 - recorded, never fatal
        trace_join = {"error": f"{type(e).__name__}: {e}"}
        print(f"serve fleet trace join FAILED: {e}", file=sys.stderr)

    s = ctx.record["serve"]
    # History-gated claims ride at the serve.* top level.
    s["failed_requests"] = len(errors)
    s["restart_s"] = st["fleet"]["last_restart_s"]
    s["shed_fraction"] = round(shed_fraction, 4)
    s["trace_join"] = trace_join
    s["fleet"] = {
        "replicas": SERVE_FLEET_REPLICAS,
        "requests": int(len(lat)),
        "client_sheds": client_sheds[0],
        "errors": errors[:5],
        "warm_wait_s": round(warm_wait_s, 2),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
        "retries": fe["retries"],
        "shed": shed_total,
        "restarts": st["fleet"]["restarts"],
        "recovery_wall_s": round(recovery_wall_s, 2),
        "frontend_rc": final["rc"],
    }
    print(f"serve fleet: {SERVE_FLEET_REPLICAS} replicas, SIGKILL at "
          f"{SERVE_FLEET_KILL_FRACTION:.0%}: failed "
          f"{s['failed_requests']}, retries {fe['retries']}, restart "
          f"{s['restart_s']}s (recovery wall {recovery_wall_s:.1f}s), "
          f"shed fraction {s['shed_fraction']}, p99 "
          f"{s['fleet']['p99_ms']} ms", file=sys.stderr)


def _make_tron_problem(n: int, d: int, k: int):
    """Ill-conditioned sparse logistic problem: the ``_make_ell``
    structure with per-column power-law scales (10^0 down to
    10^-TRON_SCALE_DECADES across the column range) folded into the
    values, and labels drawn from a realizable margin whose true
    coefficients are inversely scaled — every scale decade carries
    signal, so the fit must travel a real distance in the flat
    directions, exactly where limited-memory quasi-Newton pays."""
    rng = np.random.default_rng(17)
    cols, vals, _ = _make_ell(n, d, k, seed=17)
    expo = -TRON_SCALE_DECADES / max(d - 1, 1)
    vals = vals * np.power(10.0, expo * cols).astype(np.float32)
    w_true = (rng.normal(0, 1.0, d)
              / np.power(10.0, expo * np.arange(d))).astype(np.float32)
    m = np.einsum("nk,nk->n", vals, w_true[cols])
    labels = (rng.uniform(size=n)
              < 1.0 / (1.0 + np.exp(-np.clip(m, -30, 30))))
    return cols, vals, labels.astype(np.float32)


def tron_arm_main(args) -> int:
    """One arm of the ``tron`` section in its OWN process (per-arm
    ``ru_maxrss`` honesty, as in ``stream_arm_main``): the same
    ill-conditioned chunked logistic problem solved to the same
    relative gradient tolerance by the streamed TRON
    (chunk-accumulated HVPs) or the streamed L-BFGS.  A short warm
    solve pays every XLA compile — the per-chunk value+gradient / HVP
    / Hessian-diag programs and the host loop's scalar helpers —
    outside the telemetry window and the RSS sampler (the warm solve is
    the identical solve: host loops compile lazily along the
    trajectory, so only a same-trajectory warm covers every program),
    and the measured solve's ``compiles`` is the
    zero-new-compiles-after-warm-up claim.
    Passes-to-tolerance is the ``solver.sweeps`` odometer over the
    measured solve — the number every acceptance claim rides on.
    Emits one JSON line; saves final weights for the parent's
    cross-arm parity check."""
    import jax.numpy as jnp

    from photon_ml_tpu import telemetry
    from photon_ml_tpu.data.chunked_batch import build_chunked_batch
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.data.sparse_rows import SparseRows
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optim.base import OptimizerConfig
    from photon_ml_tpu.optim.streaming import (
        ChunkedGLMObjective,
        streaming_lbfgs_solve,
        streaming_tron_solve,
    )

    arm = args.tron_arm
    n, d, k = args.n, args.d, args.k
    cols, vals, labels = _make_tron_problem(n, d, k)
    rows_sp = SparseRows.from_flat(
        np.arange(n + 1, dtype=np.int64) * k,
        cols.reshape(-1).astype(np.int64), vals.reshape(-1))
    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l2(TRON_L2),
        norm=NormalizationContext.identity(),
    )
    base_mb = _current_rss_mb()
    t0 = time.time()
    cb = build_chunked_batch(
        rows_sp, d, labels, n_chunks=TRON_CHUNKS, layout="ell",
        spill_dir=os.path.join(args.cache_dir, f"spill_tron_{arm}"),
        host_max_resident=TRON_WINDOW)
    cobj = ChunkedGLMObjective(obj, cb, max_resident=0,
                               prefetch_depth=TRON_DEPTH)
    etl_s = time.time() - t0
    w0 = jnp.zeros(d, jnp.float32)
    cfg = OptimizerConfig(max_iters=TRON_MAX_ITERS, tolerance=TRON_TOL)

    def solve(c):
        if arm == "tron":
            return streaming_tron_solve(
                cobj.value_and_gradient, cobj.hvp_pass, w0, c,
                hessian_diag=cobj.hessian_diagonal)
        return streaming_lbfgs_solve(cobj.value_and_gradient, w0, c)

    # Warm-up is the IDENTICAL solve (same config, same w0): both host
    # loops compile programs lazily along the trajectory — TRON's
    # boundary-exit helper only on the first trust-region wall hit,
    # L-BFGS's two-loop scalars only once curvature history exists — so
    # a cheaper warm (loose tolerance, short cap) leaves late-iteration
    # programs to register against the measured solve's zero-compile
    # claim.  First run pays every compile; second run is measured.
    t0 = time.time()
    solve(cfg)
    warmup_s = time.time() - t0

    tel = telemetry.start("metrics")
    guard_stack = ExitStack()
    compile_log = None
    if args.guards:
        from photon_ml_tpu.analysis.guards import (
            count_compiles,
            no_implicit_transfers,
        )

        compile_log = guard_stack.enter_context(count_compiles())
        guard_stack.enter_context(no_implicit_transfers("log"))
    t0 = time.time()
    with guard_stack, _RssSampler() as rss:
        res = solve(cfg)
    solve_s = time.time() - t0
    tel_summary = tel.summary()
    tel.close()

    c = tel_summary.get("counters", {})
    d_ = tel_summary.get("derived", {})
    passes = c.get("solver.sweeps", 0)
    pass_total_s = d_.get("pass_span_total_s") or None
    np.save(os.path.join(args.cache_dir, f"tron_w_{arm}.npy"),
            np.asarray(res.w))

    rec = {
        "arm": arm,
        "etl_s": round(etl_s, 1),
        "warmup_s": round(warmup_s, 1),
        "solve_s": round(solve_s, 2),
        "iterations": int(res.iterations),
        "converged": bool(res.converged),
        "grad_norm": float(res.grad_norm),
        "final_value": round(float(res.value), 6),
        "passes_to_tol": passes,
        "hvp_passes": c.get("solver.hvp_sweeps", 0),
        "ls_trials": c.get("solver.ls_trials", 0),
        "aux_passes": c.get("solver.aux_sweeps", 0),
        "pass_s": (round(pass_total_s / passes, 3)
                   if pass_total_s and passes else None),
        # Rows streamed through the device per second of pass span —
        # the streamed-throughput number the history gate watches.
        "rows_per_sec": (round(n * passes / pass_total_s, 1)
                         if pass_total_s else None),
        "n_chunks": TRON_CHUNKS,
        "peak_rss_mb": round(_peak_rss_mb(), 1),
        "solve_peak_rss_mb": round(rss.peak_mb, 1),
        "rss_delta_mb": (round(rss.peak_mb - base_mb, 1)
                         if base_mb is not None else None),
        "telemetry": _telemetry_block(tel_summary),
    }
    if compile_log is not None:
        rec["guards"] = {
            "solve_compiles": compile_log.count,
            "solve_compile_programs": sorted(set(compile_log.programs)),
            "transfer_guard": "log",
        }
    rec["device"] = _device_record()
    print(json.dumps(rec))
    return 0


def section_tron(ctx: BenchContext) -> None:
    """Streaming TRON vs streaming L-BFGS (ISSUE 17 tentpole
    measurement): the same ill-conditioned out-of-core logistic problem
    solved to the same relative gradient tolerance in two subprocess
    arms.  Claims under test: total data passes to tolerance
    measurably below the L-BFGS arm's (the second-order pass
    advantage), streamed throughput in the same regime as the L-BFGS
    passes (the HVP pass is one more store-bounded sweep, not a new
    memory tier), per-arm peak RSS bounded by the chunk window, and
    cross-arm coefficient parity at convergence."""
    import shutil

    for arm in ("tron", "lbfgs"):
        shutil.rmtree(os.path.join(ctx.cache_dir, f"spill_tron_{arm}"),
                      ignore_errors=True)   # honest cold spill ETL

    flags = ["--guards"] if ctx.guards else []
    tron = _run_arm(ctx, "--tron-arm", "tron", *flags)
    lbfgs = _run_arm(ctx, "--tron-arm", "lbfgs", *flags)
    w_t = np.load(os.path.join(ctx.cache_dir, "tron_w_tron.npy"))
    w_l = np.load(os.path.join(ctx.cache_dir, "tron_w_lbfgs.npy"))
    parity = float(np.max(np.abs(w_t - w_l)))

    def ratio(a, b):
        if a is None or b is None or b == 0:
            return None
        return round(a / b, 3)

    ctx.record["tron"] = {
        "n_chunks": TRON_CHUNKS,
        "host_max_resident": TRON_WINDOW,
        "prefetch_depth": TRON_DEPTH,
        "scale_decades": TRON_SCALE_DECADES,
        "tolerance": TRON_TOL,
        "tron": tron,
        "lbfgs": lbfgs,
        # The three gated numbers (history METRICS): the TRON arm's
        # own trajectory — its pass advantage is gated via the ratio.
        "passes_to_tol": tron["passes_to_tol"],
        "rows_per_sec": tron["rows_per_sec"],
        "peak_rss_mb": tron["solve_peak_rss_mb"],
        # >1 means TRON reached the tolerance in fewer data passes.
        "pass_advantage": ratio(lbfgs["passes_to_tol"],
                                tron["passes_to_tol"]),
        "pass_time_ratio": ratio(tron["pass_s"], lbfgs["pass_s"]),
        "coef_parity_max": parity,
    }
    s = ctx.record["tron"]
    print(f"tron: {tron['passes_to_tol']} passes to tol "
          f"({tron['iterations']} iters, conv {tron['converged']}, "
          f"{tron['pass_s']}s/pass, peak RSS "
          f"{tron['solve_peak_rss_mb']} MB) vs lbfgs "
          f"{lbfgs['passes_to_tol']} passes ({lbfgs['iterations']} "
          f"iters, conv {lbfgs['converged']}); pass advantage "
          f"{s['pass_advantage']}x, pass-time ratio "
          f"{s['pass_time_ratio']}x, coef parity {parity:.2e}",
          file=sys.stderr)


SECTION_FNS = {
    "etl": section_etl,
    "cached": section_cached,
    "grr": section_grr,
    "colmajor": section_colmajor,
    "segment_sum": section_segment_sum,
    "powerlaw": section_powerlaw,
    "chunked": section_chunked,
    "sweep": section_sweep,
    "stream": section_stream,
    "score": section_score,
    "re": section_re,
    "cd_fused": section_cd_fused,
    "serve": section_serve,
    "mesh_stream": section_mesh_stream,
    "tron": section_tron,
}


def _finalize(ctx: BenchContext) -> dict:
    """Compose the record from whatever ran (missing pieces → null)."""
    rec = dict(ctx.record)
    # The arms' own report where sections spawned; this process's
    # otherwise (every child has exited by now).
    device = ctx.device or _device_record()
    platform = device["platform"]
    t_grr = ctx.step_times.get("grr")
    xla = [ctx.step_times[v] for v in ("colmajor", "segment_sum")
           if v in ctx.step_times]
    t_best_xla = min(xla) if xla else None
    out = {
        "metric": "fused sparse GLM value+gradient throughput "
                  f"(n={ctx.n:.0e},d={ctx.d:.0e},k={ctx.k},{platform},"
                  "GRR layout)".replace("e+0", "e"),
        "value": (round(ctx.n / t_grr, 1) if t_grr else None),
        "unit": "examples/sec",
        "vs_baseline": (round(t_best_xla / t_grr, 3)
                        if t_grr and t_best_xla else None),
        "step_ms_grr": (round(t_grr * 1e3, 3) if t_grr else None),
        "step_ms_colmajor": (
            round(ctx.step_times["colmajor"] * 1e3, 3)
            if "colmajor" in ctx.step_times else None),
        "step_ms_segment_sum": (
            round(ctx.step_times["segment_sum"] * 1e3, 3)
            if "segment_sum" in ctx.step_times else None),
        "baseline_note": "vs_baseline = best XLA layout (colmajor or "
                         "segment_sum) over the GRR compiled plan; "
                         "reference publishes no numbers",
    }
    out["achieved_hbm_gbps"] = None
    out["roofline_fraction"] = None
    if t_grr and ctx._pair is not None:
        grr_bytes = (_grr_stream_bytes(ctx._pair)
                     + 6 * ctx.n * 4 + 4 * ctx.d * 4)
        # Emitted device-cost block for the GRR step (ISSUE 8): the
        # Mosaic kernel is opaque to XLA cost_analysis (a custom call),
        # so its bytes come from the PLAN — the analytic stream count
        # _grr_stream_bytes already audits.  The bytes are a count and
        # stand on any backend; a bandwidth and a roofline share are
        # device metrics and are reported on a TPU only, against the
        # published peak of that device_kind.
        cost = {
            "bytes_accessed": int(grr_bytes),
            "bytes_source": "analytic plan stream count",
            "measured_step_ms": round(t_grr * 1e3, 3),
        }
        if platform == "tpu":
            peak, source = peak_gbps(device["device_kind"])
            achieved = grr_bytes / t_grr / 1e9
            out["achieved_hbm_gbps"] = round(achieved, 1)
            out["roofline_fraction"] = round(achieved / peak, 4)
            cost.update(
                peak_gbps=peak, peak_source=source,
                roofline_est_ms=round(grr_bytes / (peak * 1e9) * 1e3, 3),
                roofline_fraction=out["roofline_fraction"])
        out["device_cost"] = {"grr_step": cost}
    out.update(rec)
    out["device"] = device
    out["sections_skipped"] = ctx.skipped
    if ctx.errors:
        out["errors"] = ctx.errors
    out["budget_s"] = ctx.budget_s
    return out


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--section", default=None,
                   help="comma-separated sections to run "
                        f"({'|'.join(ALL_SECTIONS)}); default "
                        f"{','.join(DEFAULT_SECTIONS)}")
    p.add_argument("--budget-s", type=float, default=DEFAULT_BUDGET_S)
    p.add_argument("--n", type=int, default=DEFAULT_N)
    p.add_argument("--d", type=int, default=DEFAULT_D)
    p.add_argument("--k", type=int, default=DEFAULT_K)
    p.add_argument("--cache-dir", default=None,
                   help="artifact dir (plans, spill stores, arm "
                        "outputs); default $PHOTON_ML_TPU_BENCH_CACHE "
                        f"or {DEFAULT_CACHE_DIR}")
    p.add_argument("--history-dir", default=None,
                   help="append this run's JSON record (as a "
                        "schema-versioned envelope file) into the "
                        "directory; gate the trajectory with "
                        "python -m photon_ml_tpu.telemetry history")
    p.add_argument("--guards", action="store_true",
                   help="run guard-instrumented sections (currently "
                        "stream) under photon_ml_tpu.analysis.guards: "
                        "compile counting over the timed sweeps "
                        "(steady state must compile nothing) and "
                        "jax.transfer_guard('log') over the per-chunk "
                        "dispatch loop; results land in the section "
                        "record under 'guards'")
    p.add_argument("--monitor", action="store_true",
                   help="run the stream arms with the live monitor on "
                        "(ISSUE 10): progress snapshots + online alert "
                        "evaluation + an ephemeral /status endpoint "
                        "span the timed sweeps, and each arm's JSON "
                        "embeds its 'progress' block — the knob the "
                        "monitoring-overhead measurement flips")
    p.add_argument("--stream-arm", choices=("spilled", "resident"),
                   default=None,
                   help="internal: run ONE arm of the stream section "
                        "in this process (per-arm peak-RSS isolation)")
    p.add_argument("--score-arm", choices=("streamed", "resident"),
                   default=None,
                   help="internal: run ONE arm of the score section "
                        "in this process (per-arm peak-RSS isolation)")
    p.add_argument("--cd-fused-arm", choices=("fused", "percoord"),
                   default=None,
                   help="internal: run ONE cd_fused-section arm in this "
                        "process and emit its JSON line")
    p.add_argument("--re-arm", choices=("streamed", "resident"),
                   default=None,
                   help="internal: run ONE arm of the re section "
                        "in this process (per-arm peak-RSS isolation)")
    p.add_argument("--mesh-arm", choices=("fleet", "solo"),
                   default=None,
                   help="internal: run ONE host of the mesh_stream "
                        "section in this process (fleet identity comes "
                        "from the environment; without fleet env vars "
                        "this is a single-host control run)")
    p.add_argument("--tron-arm", choices=("tron", "lbfgs"),
                   default=None,
                   help="internal: run ONE arm of the tron section "
                        "in this process (per-arm peak-RSS isolation)")
    args = p.parse_args(argv)
    if args.cache_dir is None:
        args.cache_dir = os.environ.get("PHOTON_ML_TPU_BENCH_CACHE",
                                        DEFAULT_CACHE_DIR)

    sections = (tuple(s for s in args.section.split(",") if s)
                if args.section else DEFAULT_SECTIONS)
    unknown = [s for s in sections if s not in SECTION_FNS]
    if unknown:
        p.error(f"unknown sections {unknown}; pick from {ALL_SECTIONS}")
    spawning = [s for s in sections if s in SPAWNING_SECTIONS]
    if spawning and len(spawning) != len(sections):
        p.error(f"sections {spawning} run in child processes that need "
                "the device and cannot share a run with sections that "
                "compute in this process, which would hold the chip; "
                "run them separately")

    # Configuration only (no backend is initialised): every process of
    # a run, parent and arms, caches where this function says.
    from photon_ml_tpu.cache import enable_compilation_cache

    enable_compilation_cache()

    if args.stream_arm:
        return stream_arm_main(args)
    if args.score_arm:
        return score_arm_main(args)
    if args.re_arm:
        return re_arm_main(args)
    if args.cd_fused_arm:
        return cd_fused_arm_main(args)
    if args.mesh_arm:
        return mesh_arm_main(args)
    if args.tron_arm:
        return tron_arm_main(args)

    ctx = BenchContext(args)
    print(f"n={ctx.n} d={ctx.d} k={ctx.k} "
          f"budget={args.budget_s:.0f}s sections={','.join(sections)}",
          file=sys.stderr)

    for s in sections:
        est = ctx.estimate(s)
        if ctx.remaining() < est:
            ctx.skipped.append(s)
            print(f"SKIP {s}: {ctx.remaining():.0f}s left < ~{est:.0f}s "
                  "estimated", file=sys.stderr)
            continue
        try:
            SECTION_FNS[s](ctx)
        except Exception as e:  # record, run the rest, exit 1 below
            traceback.print_exc()
            ctx.errors[s] = f"{type(e).__name__}: {e}"
        finally:
            # Memory trajectory alongside wall-clock: the process
            # high-water RSS after each section (monotone — a jump
            # names the section that caused it).
            ctx.record.setdefault("peak_rss_mb", {})[s] = round(
                _peak_rss_mb(), 1)

    out = _finalize(ctx)
    rc = 1 if ctx.errors else 0
    if args.section and len(sections) == 1:
        # Single-section invocation: emit just that section's slice
        # (still one JSON object on the last line).
        out["section"] = sections[0]
    if args.history_dir:
        # One envelope file per run (ISSUE 8 trajectory gating): the
        # record the last stdout line carries, plus the schema/argv
        # header `telemetry history` consumes.  Filename sorts by
        # wall-clock so directory order is round order.
        os.makedirs(args.history_dir, exist_ok=True)
        stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
        path = os.path.join(args.history_dir,
                            f"bench_{stamp}_{os.getpid()}.json")
        with open(path, "w") as f:
            json.dump({"schema": 1, "kind": "bench_record",
                       "ts": time.time(), "argv": sys.argv[1:],
                       "rc": rc, "record": out}, f)
        print(f"history record appended: {path}", file=sys.stderr)
    print(json.dumps(out))
    return rc


if __name__ == "__main__":
    sys.exit(main())
