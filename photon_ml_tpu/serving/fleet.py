"""Supervised replica fleet (ISSUE 13 tentpole).

PR 12's ``ModelServer`` is one process: one wedged handler, one corrupt
mmap, or one OOM takes the whole scoring path down.  This module is the
Snap ML cluster→node hierarchy one level up — a fleet of replica
``ModelServer`` subprocesses, each wrapping the already-warmed fused
engine, behind one supervised frontend (``serving.frontend``):

- **Spawn**: ``FleetSupervisor`` launches ``config.replicas`` replica
  processes (``python -m photon_ml_tpu.serving``) on ephemeral ports,
  discovered through the existing ``--info-file`` contract.  Replicas
  run with their own hot-swap watcher OFF — the supervisor owns swap
  coordination (rolling, below).
- **Probe**: each replica's ``/healthz`` is polled every
  ``probe_every_s`` (the ``serve.replica_healthz`` fault seam).  A
  crashed process, or a live one failing ``unhealthy_after``
  consecutive probes (wedged), is killed and restarted.
- **Restart policy**: bounded exponential backoff per replica
  (``restart_backoff_s`` doubling to ``restart_backoff_max_s``), and a
  circuit breaker — ``breaker_threshold`` restarts inside
  ``breaker_window_s`` opens the breaker for ``breaker_reset_s``
  (state ``broken``, no restarts), then ONE half-open attempt either
  closes it (ready) or re-opens it.  A flapping replica cannot consume
  the host in a restart storm.
- **Rolling hot swap**: a newly published model manifest recycles
  replicas ONE at a time — cordon (the frontend stops routing), drain
  outstanding requests, SIGTERM, respawn against the new manifest,
  wait ready — and the next recycle only starts when every other
  replica is ready, so the fleet never dips below N−1 ready.  A
  replica that cannot come up on the new manifest (corrupt publish)
  aborts the swap: the remaining replicas keep serving the previous
  good model.

Everything observable rides the existing tiers: ``fleet.*`` telemetry
counters/gauges (``fleet.replica_restarts`` is the monitor's
``replica_restarts`` alert rule input), ``fleet_*`` run-log events, and
the aggregated ``/status`` fleet view served by the frontend.

Testability: replica processes hide behind the ``launch()`` seam — the
tier-1 fault matrix drives the supervisor against in-process stub
replicas with a fake clock (no subprocess, no sleeps), while the
slow-marked e2e uses the real
``SubprocessReplicaLauncher``.
"""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

from photon_ml_tpu import telemetry
from photon_ml_tpu.config import ServingConfig, config_to_json
from photon_ml_tpu.reliability import faults
from photon_ml_tpu.serving import tracing as _tracing
from photon_ml_tpu.serving.server import _manifest_signature

logger = logging.getLogger(__name__)

# Replica lifecycle states (frontend routes only READY replicas).
STARTING = "starting"     # spawned, warming (or info file pending)
READY = "ready"           # probed healthy; in rotation
DRAINING = "draining"     # cordoned for rolling swap
DOWN = "down"             # dead/wedged; restart scheduled (backoff)
BROKEN = "broken"         # circuit breaker open; no restarts

# Rolling-swap drain/exit budgets (seconds on the supervisor clock).
DRAIN_TIMEOUT_S = 30.0
EXIT_TIMEOUT_S = 10.0


class Replica:
    """One replica's supervised record.  All mutable fields are
    guarded by the supervisor's lock; the control thread is the only
    state writer, the frontend only bumps ``outstanding``."""

    def __init__(self, idx: int):
        self.idx = idx
        self.handle: "ReplicaHandle | None" = None
        self.state = DOWN
        self.url: str | None = None
        self.outstanding = 0          # in-flight frontend requests
        self.served = 0               # total requests routed here
        self.restarts = 0             # restarts after a failure
        self.probe_failures = 0       # consecutive
        self.restart_times: list[float] = []   # breaker window
        self.backoff_s = 0.0          # next restart delay
        self.restart_at: float | None = None   # scheduled restart time
        self.breaker_open_until: float | None = None
        self.half_open = False
        self.recycling = False        # down for a rolling swap, not a
        self.down_since: float | None = None     # ...crash
        self.spawned_at: float | None = None
        self.last_restart_s: float | None = None
        self.last_error: str | None = None

    def snapshot(self) -> dict:
        return {
            "idx": self.idx,
            "state": self.state,
            "url": self.url,
            "pid": self.handle.pid() if self.handle else None,
            "outstanding": self.outstanding,
            "served": self.served,
            "restarts": self.restarts,
            "probe_failures": self.probe_failures,
            "last_restart_s": self.last_restart_s,
            **({"last_error": self.last_error}
               if self.last_error else {}),
        }


class ReplicaHandle:
    """The process seam: what the supervisor needs from a replica
    process.  ``SubprocessReplicaHandle`` is the real one; tests stub
    it with in-process endpoints."""

    def poll(self) -> int | None:          # None = alive
        raise NotImplementedError

    def url(self) -> str | None:           # None until discovered
        raise NotImplementedError

    def pid(self) -> int | None:
        return None

    def terminate(self) -> None:           # graceful (SIGTERM)
        raise NotImplementedError

    def kill(self) -> None:                # hard (SIGKILL)
        raise NotImplementedError

    def wait(self, timeout_s: float) -> int | None:
        raise NotImplementedError


class SubprocessReplicaHandle(ReplicaHandle):
    def __init__(self, proc: subprocess.Popen, info_path: str):
        self._proc = proc
        self._info_path = info_path
        self._url: str | None = None

    def poll(self) -> int | None:
        return self._proc.poll()

    def url(self) -> str | None:
        if self._url is None:
            try:
                with open(self._info_path) as f:
                    self._url = json.load(f)["url"]
            except (OSError, ValueError, KeyError):  # photon-lint: disable=swallowed-exception (the info file simply has not been written yet; the caller treats None as still-starting)
                return None
        return self._url

    def pid(self) -> int | None:
        return self._proc.pid

    def terminate(self) -> None:
        if self._proc.poll() is None:
            self._proc.terminate()

    def kill(self) -> None:
        if self._proc.poll() is None:
            self._proc.kill()

    def wait(self, timeout_s: float) -> int | None:
        try:
            return self._proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:  # photon-lint: disable=swallowed-exception (the timeout IS the result: None tells the caller the process is still alive and escalation — SIGKILL — is its decision)
            return None


class SubprocessReplicaLauncher:
    """Launches real replica processes: one derived single-replica
    config each (ephemeral port, supervisor-owned swap), stdout/stderr
    to per-replica files under the fleet workdir, port discovery via
    ``--info-file``."""

    def __init__(self, config: ServingConfig, workdir: str):
        self.config = config
        self.workdir = workdir
        os.makedirs(workdir, exist_ok=True)

    def _replica_config_path(self, idx: int) -> str:
        import dataclasses

        cfg = dataclasses.replace(
            self.config, replicas=1, port=0, hot_swap_poll_s=0.0,
            log_path=os.path.join(self.workdir,
                                  f"replica_{idx}.jsonl"))
        path = os.path.join(self.workdir, f"replica_{idx}.json")
        with open(path, "w") as f:
            f.write(config_to_json(cfg))
        return path

    def launch(self, idx: int) -> ReplicaHandle:
        cfg_path = self._replica_config_path(idx)
        info_path = os.path.join(self.workdir, f"replica_{idx}.info")
        # A stale info file from the previous incarnation would hand
        # the supervisor a dead port; remove before spawn.
        if os.path.exists(info_path):
            os.remove(info_path)
        out = open(os.path.join(self.workdir, f"replica_{idx}.out"),
                   "ab")
        err = open(os.path.join(self.workdir, f"replica_{idx}.err"),
                   "ab")
        try:
            # The replica inherits the supervisor's environment as it
            # is.  The supervisor itself never initialises a JAX
            # backend: a chip belongs to one process, and it is the
            # replica that needs it.
            proc = subprocess.Popen(
                [sys.executable, "-m", "photon_ml_tpu.serving",
                 "--config", cfg_path, "--info-file", info_path],
                stdout=out, stderr=err)
        finally:
            out.close()
            err.close()
        logger.info("fleet: launched replica %d (pid %d)", idx,
                    proc.pid)
        return SubprocessReplicaHandle(proc, info_path)


class FleetSupervisor:
    """The control loop: spawn, probe, restart, breaker, rolling swap.

    The thread started by ``start()`` calls ``_step()`` every
    ``probe_every_s``; tests drive ``_step()`` directly with a fake
    clock and a stub launcher.  One lock guards every replica record;
    network probes run outside it.
    """

    def __init__(self, config: ServingConfig, launcher=None,
                 run_logger=None, workdir: str | None = None,
                 clock=time.monotonic, watch_manifest: bool = True):
        config.validate()
        self.config = config
        self.workdir = workdir or tempfile.mkdtemp(
            prefix="photon-fleet-")
        self.launcher = launcher if launcher is not None else \
            SubprocessReplicaLauncher(config, self.workdir)
        self._log = run_logger
        self._clock = clock
        self._lock = threading.Lock()
        self.replicas = [Replica(i) for i in range(config.replicas)]
        self._watch_manifest = watch_manifest
        self._last_sig: tuple | None = None
        self._pending_sig: tuple | None = None
        self._swap: dict | None = None
        self.swaps = 0
        self.swap_aborts = 0
        self.last_swap_error: str | None = None
        self._frontend = None
        self._stop_evt = threading.Event()
        self._stopped = False
        self._thread: threading.Thread | None = None

    # -- wiring --------------------------------------------------------------

    def attach_frontend(self, frontend) -> None:
        """The frontend's readiness follows the fleet's ready count
        (updated at the end of every step)."""
        with self._lock:
            self._frontend = frontend

    def _event(self, kind: str, **fields) -> None:
        if self._log is not None:
            self._log.event(kind, **fields)

    # -- lifecycle -----------------------------------------------------------

    def spawn_all(self) -> None:
        if self._watch_manifest:
            sig = _manifest_signature(self.config.model_dir)
            with self._lock:
                self._last_sig = sig
        now = self._clock()
        for r in self.replicas:
            self._spawn(r, now)

    def start(self) -> "FleetSupervisor":
        self.spawn_all()
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name="photon-fleet-supervisor")
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop_evt.wait(self.config.probe_every_s):
            try:
                self._step()
            except Exception as e:
                # The control loop must survive its own bugs: a failed
                # step is logged and the next tick retries.
                telemetry.count("fleet.supervisor_errors")
                logger.exception("fleet supervisor step failed: %r", e)

    def stop(self) -> None:
        """Terminate every replica (SIGTERM, grace, SIGKILL) and stop
        the control loop.  Idempotent."""
        with self._lock:
            if self._stopped:
                return
            self._stopped = True
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
        with self._lock:
            handles = [r.handle for r in self.replicas
                       if r.handle is not None]
            for r in self.replicas:
                r.state = DOWN
        for h in handles:
            h.terminate()
        deadline = time.monotonic() + 15.0
        for h in handles:
            if h.wait(max(0.1, deadline - time.monotonic())) is None:
                h.kill()
                h.wait(5.0)
        self._event("fleet_stopped",
                    restarts=sum(r.restarts for r in self.replicas),
                    swaps=self.swaps)

    # -- spawn / restart policy ----------------------------------------------

    def _spawn(self, r: Replica, now: float) -> None:
        try:
            handle = self.launcher.launch(r.idx)
        except Exception as e:
            # A failed exec is a failed start: schedule the next
            # attempt through the same backoff/breaker policy.
            with self._lock:
                r.last_error = f"launch failed: {type(e).__name__}: {e}"
            logger.warning("fleet: replica %d launch failed (%r)",
                           r.idx, e)
            self._schedule_restart(r, now, reason="launch failed")
            return
        with self._lock:
            r.handle = handle
            r.state = STARTING
            r.url = None
            r.probe_failures = 0
            r.spawned_at = now
            r.restart_at = None
        self._event("fleet_replica_spawned", replica=r.idx,
                    pid=handle.pid())

    def _schedule_restart(self, r: Replica, now: float,
                          reason: str) -> None:
        """A replica failed (crash, wedge, failed start): kill what is
        left, open the breaker if it is flapping, else schedule the
        restart after the current backoff."""
        if r.handle is not None:
            r.handle.kill()
        with self._lock:
            r.state = DOWN
            r.url = None
            if r.down_since is None:
                r.down_since = now
            r.last_error = reason
            # Breaker bookkeeping: restarts inside the rolling window.
            window = self.config.breaker_window_s
            r.restart_times = [t for t in r.restart_times
                               if now - t <= window]
            r.restart_times.append(now)
            flapping = len(r.restart_times) >= \
                self.config.breaker_threshold
            if r.half_open or flapping:
                # A failed half-open attempt re-opens; a flapping
                # replica opens.  Either way: no restarts until the
                # reset window passes.
                r.state = BROKEN
                r.breaker_open_until = now + self.config.breaker_reset_s
                r.half_open = False
                r.restart_at = None
                opened = True
            else:
                r.backoff_s = min(
                    max(self.config.restart_backoff_s, r.backoff_s * 2),
                    self.config.restart_backoff_max_s)
                r.restart_at = now + r.backoff_s
                opened = False
        telemetry.count("fleet.replica_failures")
        if opened:
            telemetry.count("fleet.breaker_opened")
            self._event("fleet_breaker_opened", replica=r.idx,
                        reason=reason,
                        reset_s=self.config.breaker_reset_s)
            logger.warning("fleet: replica %d circuit breaker OPEN "
                           "(%s); no restarts for %.1fs", r.idx,
                           reason, self.config.breaker_reset_s)
        else:
            self._event("fleet_replica_down", replica=r.idx,
                        reason=reason, restart_in_s=round(r.backoff_s, 3))
            logger.warning("fleet: replica %d down (%s); restart in "
                           "%.2fs", r.idx, reason, r.backoff_s)

    def _mark_ready(self, r: Replica, now: float) -> None:
        with self._lock:
            was_down = r.down_since is not None
            recycled = r.recycling
            restart_s = (now - r.down_since) if was_down else None
            r.state = READY
            r.probe_failures = 0
            r.backoff_s = 0.0
            r.down_since = None
            r.recycling = False
            r.last_error = None
            if r.half_open:
                r.half_open = False
                r.restart_times = []
                closed = True
            else:
                closed = False
            if was_down:
                # A rolling-swap recycle is a DELIBERATE bounce: its
                # latency is recorded, but it is not a crash restart —
                # the replica_restarts alert must not fire on deploys.
                if not recycled:
                    r.restarts += 1
                r.last_restart_s = round(restart_s, 3)
        if closed:
            self._event("fleet_breaker_closed", replica=r.idx)
            logger.info("fleet: replica %d circuit breaker closed",
                        r.idx)
        if was_down:
            telemetry.count("fleet.replica_recycles" if recycled
                            else "fleet.replica_restarts")
            telemetry.observe("fleet.restart_s", restart_s)
            self._event("fleet_replica_ready", replica=r.idx,
                        restart_s=round(restart_s, 3),
                        recycled=recycled)
            logger.info("fleet: replica %d %s and ready in %.2fs",
                        r.idx, "recycled" if recycled else "restarted",
                        restart_s)
        else:
            self._event("fleet_replica_ready", replica=r.idx)

    # -- probing -------------------------------------------------------------

    def _probe(self, r: Replica) -> str:
        """One /healthz probe → "ready" | "warming" | "error" (the
        ``serve.replica_healthz`` fault seam fires per probe)."""
        url = r.url
        if url is None:
            return "warming"      # info file not discovered yet
        try:
            faults.fire("serve.replica_healthz", replica=r.idx)
            req = url + "/healthz"
            with urllib.request.urlopen(
                    req, timeout=self.config.probe_timeout_s) as resp:
                state = json.loads(resp.read()).get("state")
                return "ready" if state == "ready" else "warming"
        except urllib.error.HTTPError as e:
            try:
                state = json.loads(e.read()).get("state")
            except Exception:  # photon-lint: disable=swallowed-exception (a non-JSON 5xx body is simply an unhealthy probe; the caller counts it)
                state = None
            return "warming" if state == "warming" else "error"
        except Exception:  # photon-lint: disable=swallowed-exception (any transport failure IS the probe result; the caller counts consecutive failures toward the wedge threshold)
            return "error"

    def note_failure(self, idx: int) -> None:
        """Frontend feedback: a connection-level failure against a
        replica counts like a failed probe, so a wedged replica is
        detected at request rate, not just probe cadence."""
        r = self.replicas[idx]
        with self._lock:
            r.probe_failures += 1

    # -- the control step ----------------------------------------------------

    def _step(self) -> None:
        now = self._clock()
        self._step_swap_detect()
        with self._lock:
            swap = self._swap
            frontend = self._frontend
        swap_active, swap_phase = None, None
        if swap is not None:
            # The swap dict is only ever mutated by this (control)
            # thread; the lock above guards the reference hand-off.
            swap_active = swap.get("active")
            swap_phase = swap.get("phase")
        for r in self.replicas:
            if r.idx == swap_active and swap_phase in ("drain", "exit"):
                continue   # the swap machinery owns this replica
            self._step_replica(r, now)
        self._step_swap(now)
        ready = self.ready_count()
        telemetry.gauge("fleet.ready_replicas", ready)
        if frontend is not None:
            frontend.update_readiness(ready)

    def _step_replica(self, r: Replica, now: float) -> None:
        with self._lock:
            state = r.state
            handle = r.handle
        if state == BROKEN:
            if now >= (r.breaker_open_until or 0.0):
                with self._lock:
                    r.half_open = True
                self._event("fleet_breaker_half_open", replica=r.idx)
                self._spawn(r, now)
            return
        if state == DOWN:
            if r.restart_at is not None and now >= r.restart_at:
                self._spawn(r, now)
            return
        if handle is None:
            return
        rc = handle.poll()
        if rc is not None:
            self._schedule_restart(r, now, reason=f"exited rc={rc}")
            return
        if r.url is None:
            url = handle.url()
            if url is not None:
                with self._lock:
                    r.url = url
        result = self._probe(r)
        if result == "ready":
            if state in (STARTING, READY):
                if state == STARTING or r.down_since is not None:
                    self._mark_ready(r, now)
                else:
                    with self._lock:
                        r.probe_failures = 0
            return
        if state == STARTING:
            # Warming (or failing while warming): only the ready
            # timeout kills a starting replica — compiles can be slow.
            if (r.spawned_at is not None
                    and now - r.spawned_at
                    > self.config.replica_ready_timeout_s):
                self._schedule_restart(
                    r, now, reason="never became ready "
                    f"(> {self.config.replica_ready_timeout_s:g}s)")
            return
        if state == READY:
            # Any non-ready answer from an in-rotation replica —
            # transport error, 5xx, or a bogus "warming" regression —
            # counts toward the wedge threshold.
            with self._lock:
                r.probe_failures += 1
                failures = r.probe_failures
            if failures >= self.config.unhealthy_after:
                telemetry.count("fleet.replica_wedged")
                self._event("fleet_replica_wedged", replica=r.idx,
                            probe_failures=failures)
                self._schedule_restart(
                    r, now, reason=f"wedged ({failures} consecutive "
                    "failed probes)")

    # -- rolling swap --------------------------------------------------------

    def _step_swap_detect(self) -> None:
        with self._lock:
            watching = self._watch_manifest and self._swap is None
            last = self._last_sig
        if not watching:
            return
        sig = _manifest_signature(self.config.model_dir)
        if sig is None or sig == last:
            return
        with self._lock:
            self._pending_sig = sig
            self._swap = {"queue": [r.idx for r in self.replicas],
                          "active": None, "phase": None}
        self._event("fleet_swap_started", signature=list(sig))
        logger.info("fleet: new manifest detected; rolling swap over "
                    "%d replica(s)", len(self.replicas))

    def _swap_abort(self, reason: str) -> None:
        with self._lock:
            self.swap_aborts += 1
            self.last_swap_error = reason
            for r in self.replicas:
                # Whatever happens to the failed replica from here on
                # is crash-restart territory, not a deploy bounce.
                r.recycling = False
            # Adopt the signature anyway: a corrupt publish must not
            # re-trigger the same doomed swap every step — the NEXT
            # publish (new signature) swaps normally, and the failed
            # replica stays with the normal restart/breaker machinery.
            self._last_sig = self._pending_sig
            self._swap = None
        telemetry.count("fleet.swap_aborts")
        self._event("fleet_swap_aborted", reason=reason)
        logger.warning("fleet: rolling swap ABORTED (%s); remaining "
                       "replicas keep the previous model", reason)

    def _step_swap(self, now: float) -> None:
        with self._lock:
            s = self._swap
        if s is None:
            return
        if s["active"] is None:
            if not s["queue"]:
                with self._lock:
                    self._last_sig = self._pending_sig
                    self._swap = None
                    self.swaps += 1
                telemetry.count("fleet.swaps")
                self._event("fleet_swap_done")
                logger.info("fleet: rolling swap complete")
                return
            nxt = self.replicas[s["queue"][0]]
            with self._lock:
                others_ready = all(
                    x.state == READY for x in self.replicas
                    if x.idx != nxt.idx)
                if others_ready:
                    # Cordon: the frontend stops routing here; the
                    # fleet stays at N−1 ready throughout the recycle.
                    s["queue"].pop(0)
                    s["active"] = nxt.idx
                    s["phase"] = "drain"
                    s["deadline"] = now + DRAIN_TIMEOUT_S
                    nxt.state = DRAINING
            if s["active"] is not None:
                self._event("fleet_swap_recycling", replica=s["active"])
            return
        r = self.replicas[s["active"]]
        if s["phase"] == "drain":
            with self._lock:
                drained = r.outstanding == 0
            if drained or now > s["deadline"]:
                if r.handle is not None:
                    r.handle.terminate()
                s["phase"] = "exit"
                s["deadline"] = now + EXIT_TIMEOUT_S
            return
        if s["phase"] == "exit":
            if r.handle is None or r.handle.poll() is not None:
                with self._lock:
                    r.down_since = now     # restart latency = recycle
                    r.recycling = True
                self._spawn(r, now)
                s["phase"] = "warm"
                s["deadline"] = now + self.config.replica_ready_timeout_s
            elif now > s["deadline"]:
                r.handle.kill()
            return
        if s["phase"] == "warm":
            # The normal probe/restart machinery owns the replica
            # here; the swap just watches the outcome.
            with self._lock:
                state = r.state
            if state == READY:
                s["active"] = None
                s["phase"] = None
                return
            if state == BROKEN or now > s["deadline"]:
                self._swap_abort(
                    f"replica {r.idx} failed to come up on the new "
                    f"manifest (state {state})")

    # -- frontend-facing reads ------------------------------------------------

    def ready_count(self) -> int:
        with self._lock:
            return sum(1 for r in self.replicas if r.state == READY)

    def acquire_replica(self, exclude: set[int] = frozenset()
                        ) -> Replica | None:
        """Least-outstanding ready replica (outside ``exclude``), with
        its outstanding count bumped — call ``release`` when done."""
        with self._lock:
            ready = [r for r in self.replicas
                     if r.state == READY and r.idx not in exclude
                     and r.url is not None]
            if not ready:
                return None
            # Least-outstanding, ties broken by fewest-served: under
            # sequential load (everything at 0 outstanding) requests
            # still spread instead of pinning the first replica.
            r = min(ready, key=lambda x: (x.outstanding, x.served,
                                          x.idx))
            r.outstanding += 1
            r.served += 1
            return r

    def release_replica(self, r: Replica) -> None:
        with self._lock:
            r.outstanding = max(0, r.outstanding - 1)

    def wait_ready(self, count: int | None = None,
                   timeout_s: float = 300.0) -> bool:
        """Block (wall clock) until ``count`` replicas are ready
        (default: the whole fleet).  Driven by the control thread —
        only meaningful after ``start()``."""
        want = count if count is not None else len(self.replicas)
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.ready_count() >= want:
                return True
            time.sleep(0.05)
        return False

    def status(self) -> dict:
        with self._lock:
            replicas = [r.snapshot() for r in self.replicas]
            swaps, aborts = self.swaps, self.swap_aborts
            swapping = self._swap is not None
            swap_err = self.last_swap_error
        restarts = sum(r["restarts"] for r in replicas)
        last_restart = max(
            (r["last_restart_s"] for r in replicas
             if r["last_restart_s"] is not None), default=None)
        return {
            "replicas": replicas,
            "ready": sum(1 for r in replicas if r["state"] == READY),
            "size": len(replicas),
            "restarts": restarts,
            "last_restart_s": last_restart,
            "swaps": swaps,
            "swap_aborts": aborts,
            "swap_in_progress": swapping,
            **({"last_swap_error": swap_err} if swap_err else {}),
        }


class FleetServer:
    """The CLI composition: supervisor + frontend + telemetry/monitor
    sessions, with the single-server lifecycle shape (``start()``,
    ``serve_forever()``, ``stop()``) so ``__main__`` treats
    ``replicas > 1`` as a drop-in."""

    def __init__(self, config: ServingConfig, run_logger=None,
                 launcher=None, workdir: str | None = None):
        from photon_ml_tpu.serving.frontend import FleetFrontend

        config.validate()
        self.config = config
        self._log = run_logger
        self._monitor = None
        self._telemetry = None
        self._tracer = None
        self._stop_evt = threading.Event()
        self._stop_lock = threading.Lock()
        self._stopped = False
        self.supervisor = FleetSupervisor(
            config, launcher=launcher, run_logger=run_logger,
            workdir=workdir)
        # Bind-first, like ModelServer: probes get an honest 503
        # ``warming`` from the frontend while replicas come up.
        self.frontend = FleetFrontend(config, self.supervisor,
                                      run_logger=run_logger)
        self.frontend.start()
        self.port = self.frontend.port

    def start(self) -> "FleetServer":
        from photon_ml_tpu.telemetry import monitor as _mon

        cfg = self.config
        if cfg.telemetry != "off" and telemetry.active() is None:
            self._telemetry = telemetry.start(
                cfg.telemetry, run_logger=self._log)
        if cfg.monitor == "on" and _mon.active() is None:
            self._monitor = _mon.start(
                run_logger=self._log, every_s=cfg.monitor_every_s)
        if cfg.trace == "on" and _tracing.active() is None:
            # The frontend-side recorder (ISSUE 14): frontend traces
            # carry routing/forward/retry stages and join the replica
            # processes' records by trace id in serve-report.
            self._tracer = _tracing.start(
                role="frontend",
                threshold_s=cfg.trace_threshold_ms / 1e3,
                sample_every=cfg.trace_sample_every,
                cap=cfg.trace_buffer, run_logger=self._log)
        self.supervisor.start()
        if self._log is not None:
            self._log.event("fleet_started", port=self.port,
                            replicas=cfg.replicas)
        logger.info("fleet frontend bound on http://%s:%d "
                    "(%d replicas warming)", cfg.host, self.port,
                    cfg.replicas)
        return self

    def serve_forever(self) -> None:
        # photon-lint: disable=eternal-wait (the main thread parks until stop() or the CLI signal handler sets the event; there is nothing to time out toward)
        self._stop_evt.wait()

    def stop(self) -> None:
        with self._stop_lock:
            if self._stopped:
                return
            self._stopped = True
        self._stop_evt.set()
        self.supervisor.stop()
        self.frontend.close()
        if self._tracer is not None:
            self._tracer.close()
        if self._monitor is not None:
            self._monitor.close()
        if self._telemetry is not None:
            self._telemetry.close()

    def serving_status(self) -> dict:
        return {
            "state": self.frontend.readiness.state,
            "frontend": self.frontend.stats(),
            "fleet": self.supervisor.status(),
        }
