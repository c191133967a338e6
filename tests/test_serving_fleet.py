"""Resilient serving fleet (ISSUE 13): supervisor, frontend, and the
serving fault matrix.

The tier-1 matrix drives ``FleetSupervisor._step()`` directly against
IN-PROCESS stub replicas on a fake clock — no subprocesses, no sleeps
— so restart backoff, the circuit breaker, wedge detection, and the
rolling swap are deterministic.  The slow-marked e2e at the bottom
runs the real thing: two replica subprocesses, one SIGKILLed
mid-traffic, zero client-visible failures.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from photon_ml_tpu import telemetry
from photon_ml_tpu.config import ServingConfig
from photon_ml_tpu.reliability.faults import (
    Fault,
    FaultInjector,
    injected,
)
from photon_ml_tpu.serving.fleet import (
    BROKEN,
    DOWN,
    DRAINING,
    READY,
    STARTING,
    FleetSupervisor,
    ReplicaHandle,
)
from photon_ml_tpu.serving.frontend import FleetFrontend
from photon_ml_tpu.serving.http import HttpEndpoint, Readiness
from photon_ml_tpu.telemetry import monitor as _mon

pytestmark = pytest.mark.fast


@pytest.fixture(autouse=True)
def _no_leaked_sessions():
    assert _mon.active() is None and telemetry.active() is None
    yield
    leaked = []
    if _mon.active() is not None:
        _mon.active().close()
        leaked.append("monitor")
    if telemetry.active() is not None:
        telemetry.active().close()
        leaked.append("telemetry")
    assert not leaked, f"leaked sessions: {leaked}"


class _FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


class StubReplica:
    """In-process fake replica: the real HTTP core (healthz + score
    echo), controllable readiness, killable."""

    def __init__(self, version: str = "v1"):
        self.version = version
        self.readiness = Readiness(READY)
        self.rc: int | None = None
        self.scored = 0
        self._ep = HttpEndpoint(
            {("POST", "/v1/score"): self._score},
            readiness=self.readiness, port=0)
        self._ep.start()
        self.url = f"http://127.0.0.1:{self._ep.port}"

    def _score(self, body: bytes):
        rows = json.loads(body)["rows"]
        self.scored += 1
        return 200, json.dumps({
            "margins": [float(r) for r in rows],
            "predictions": [2.0 * float(r) for r in rows],
            "model_version": self.version,
            "n": len(rows),
        }), "application/json"

    def kill(self, rc: int = -9) -> None:
        if self.rc is None:
            self.rc = rc
            self._ep.close()


class StubHandle(ReplicaHandle):
    def __init__(self, replica: StubReplica | None, rc: int = 1):
        self.replica = replica       # None = born dead (failed start)
        self._dead_rc = rc

    def poll(self):
        return self._dead_rc if self.replica is None \
            else self.replica.rc

    def url(self):
        return self.replica.url if self.replica is not None else None

    def terminate(self):
        self.kill()

    def kill(self):
        if self.replica is not None:
            self.replica.kill()

    def wait(self, timeout_s):
        return self.poll()


class StubLauncher:
    def __init__(self):
        self.launches: list[tuple[int, StubHandle]] = []
        self.dead_launches: dict[int, int] = {}   # idx -> born-dead n
        self.version = "v1"

    def launch(self, idx: int) -> StubHandle:
        if self.dead_launches.get(idx, 0) > 0:
            self.dead_launches[idx] -= 1
            h = StubHandle(None)
        else:
            h = StubHandle(StubReplica(self.version))
        self.launches.append((idx, h))
        return h

    def stub(self, idx: int) -> StubReplica:
        """Latest LIVE stub launched for replica ``idx``."""
        for i, h in reversed(self.launches):
            if i == idx and h.replica is not None:
                return h.replica
        raise AssertionError(f"no live stub for replica {idx}")

    def launch_count(self, idx: int | None = None) -> int:
        return len([1 for i, _h in self.launches
                    if idx is None or i == idx])

    def close(self):
        for _i, h in self.launches:
            h.kill()


def _cfg(tmp_path, **kw):
    kw.setdefault("replicas", 2)
    kw.setdefault("probe_every_s", 0.05)
    kw.setdefault("probe_timeout_s", 1.0)
    kw.setdefault("unhealthy_after", 3)
    kw.setdefault("restart_backoff_s", 1.0)
    kw.setdefault("restart_backoff_max_s", 8.0)
    kw.setdefault("breaker_threshold", 3)
    kw.setdefault("breaker_window_s", 100.0)
    kw.setdefault("breaker_reset_s", 50.0)
    kw.setdefault("replica_ready_timeout_s", 30.0)
    kw.setdefault("request_timeout_s", 10.0)
    kw.setdefault("telemetry", "off")
    kw.setdefault("monitor", "off")
    return ServingConfig(model_dir=str(tmp_path / "mdl"), port=0, **kw)


def _fleet(tmp_path, watch_manifest=False, **kw):
    cfg = _cfg(tmp_path, **kw)
    launcher = StubLauncher()
    clock = _FakeClock()
    sup = FleetSupervisor(cfg, launcher=launcher, clock=clock,
                          workdir=str(tmp_path / "fleet"),
                          watch_manifest=watch_manifest)
    return sup, launcher, clock


def _states(sup):
    return [r.state for r in sup.replicas]


# ---------------------------------------------------------------------------
# supervisor: spawn / probe / restart / breaker
# ---------------------------------------------------------------------------


def test_supervisor_spawns_probes_and_reports_ready(tmp_path):
    sup, launcher, _clock = _fleet(tmp_path)
    try:
        sup.spawn_all()
        assert _states(sup) == [STARTING, STARTING]
        sup._step()
        assert _states(sup) == [READY, READY]
        st = sup.status()
        assert st["ready"] == 2 and st["size"] == 2
        assert st["restarts"] == 0
        assert all(r["url"] for r in st["replicas"])
    finally:
        sup.stop()
        launcher.close()


def test_supervisor_restarts_crashed_replica_with_backoff(tmp_path):
    """Crash → DOWN with the backoff delay, restarted after it, back
    READY with restart latency recorded and the counter pinned."""
    sup, launcher, clock = _fleet(tmp_path)
    tel = telemetry.start("metrics")
    try:
        sup.spawn_all()
        sup._step()
        launcher.stub(0).kill()          # crash replica 0
        sup._step()                      # death detected
        assert sup.replicas[0].state == DOWN
        assert sup.ready_count() == 1
        sup._step()                      # backoff (1 s) not elapsed
        assert sup.replicas[0].state == DOWN
        assert launcher.launch_count(0) == 1
        clock.tick(1.1)
        sup._step()                      # respawn
        assert sup.replicas[0].state == STARTING
        sup._step()                      # probe → ready
        assert sup.replicas[0].state == READY
        assert sup.replicas[0].restarts == 1
        # Detect→ready on the fake clock: the 1.1 s backoff window.
        assert sup.replicas[0].last_restart_s == pytest.approx(
            1.1, abs=0.01)
        assert tel.counter("fleet.replica_restarts") == 1
        assert sup.status()["last_restart_s"] == pytest.approx(
            1.1, abs=0.01)
    finally:
        sup.stop()
        launcher.close()
        tel.close()


def test_supervisor_backoff_doubles_until_ready_resets(tmp_path):
    """Consecutive failed starts double the backoff (bounded); a
    successful ready resets it."""
    sup, launcher, clock = _fleet(tmp_path, replicas=1,
                                  breaker_threshold=100)
    try:
        sup.spawn_all()
        sup._step()
        backoffs = []
        launcher.dead_launches[0] = 2    # next two launches born dead
        launcher.stub(0).kill()
        sup._step()                      # death → backoff 1
        backoffs.append(sup.replicas[0].backoff_s)
        clock.tick(sup.replicas[0].backoff_s + 0.01)
        sup._step()                      # respawn (born dead)
        sup._step()                      # death → backoff 2
        backoffs.append(sup.replicas[0].backoff_s)
        clock.tick(sup.replicas[0].backoff_s + 0.01)
        sup._step()
        sup._step()                      # death → backoff 4
        backoffs.append(sup.replicas[0].backoff_s)
        assert backoffs == [1.0, 2.0, 4.0]
        clock.tick(sup.replicas[0].backoff_s + 0.01)
        sup._step()                      # respawn (live now)
        sup._step()                      # ready
        assert sup.replicas[0].state == READY
        assert sup.replicas[0].backoff_s == 0.0
    finally:
        sup.stop()
        launcher.close()


def test_supervisor_wedge_via_healthz_fault_seam(tmp_path):
    """The serve.replica_healthz fault seam: unhealthy_after
    consecutive failed probes on a LIVE process kill and restart it
    (the wedged-replica path), with the wedge counter pinned."""
    sup, launcher, clock = _fleet(tmp_path, replicas=1)
    tel = telemetry.start("metrics")
    try:
        sup.spawn_all()
        sup._step()
        assert sup.replicas[0].state == READY
        inj = FaultInjector([Fault(site="serve.replica_healthz",
                                   kind="error", at=0, count=3)])
        with injected(inj):
            sup._step()                  # occurrence 0: fail 1
            sup._step()                  # fail 2
            assert sup.replicas[0].state == READY   # below threshold
            sup._step()                  # fail 3 → wedged
        assert sup.replicas[0].state == DOWN
        assert tel.counter("fleet.replica_wedged") == 1
        assert "wedged" in sup.replicas[0].last_error
        clock.tick(1.1)
        sup._step()                      # respawn
        sup._step()
        assert sup.replicas[0].state == READY
        assert sup.replicas[0].restarts == 1
    finally:
        sup.stop()
        launcher.close()
        tel.close()


def test_circuit_breaker_opens_then_half_open_closes(tmp_path):
    """breaker_threshold rapid failures open the breaker (no restarts
    for breaker_reset_s); the half-open attempt closes it when the
    replica comes back healthy."""
    sup, launcher, clock = _fleet(tmp_path, replicas=1,
                                  restart_backoff_s=0.0,
                                  restart_backoff_max_s=0.0,
                                  breaker_threshold=3,
                                  breaker_reset_s=50.0)
    tel = telemetry.start("metrics")
    try:
        sup.spawn_all()
        sup._step()
        launcher.dead_launches[0] = 99   # everything born dead now
        launcher.stub(0).kill()
        # Failure 1 (crash), then born-dead spawn/death cycles; the
        # third failure inside the window opens the breaker.
        for _ in range(8):
            clock.tick(0.01)
            sup._step()
            if sup.replicas[0].state == BROKEN:
                break
        assert sup.replicas[0].state == BROKEN
        assert tel.counter("fleet.breaker_opened") == 1
        spawns_at_open = launcher.launch_count(0)
        # Open breaker: NO restarts while the reset window runs.
        for _ in range(5):
            clock.tick(5.0)
            if clock.t - 1000.0 > 45.0:
                break
            sup._step()
            assert launcher.launch_count(0) == spawns_at_open
        # Past the reset: ONE half-open attempt.
        launcher.dead_launches[0] = 0    # healthy again
        clock.tick(60.0)
        sup._step()                      # half-open spawn
        assert launcher.launch_count(0) == spawns_at_open + 1
        sup._step()                      # probe → ready, breaker closes
        assert sup.replicas[0].state == READY
        assert sup.replicas[0].restart_times == []
        assert not sup.replicas[0].half_open
    finally:
        sup.stop()
        launcher.close()
        tel.close()


def test_circuit_breaker_failed_half_open_reopens(tmp_path):
    sup, launcher, clock = _fleet(tmp_path, replicas=1,
                                  restart_backoff_s=0.0,
                                  restart_backoff_max_s=0.0,
                                  breaker_threshold=2,
                                  breaker_reset_s=10.0)
    tel = telemetry.start("metrics")
    try:
        sup.spawn_all()
        sup._step()
        launcher.dead_launches[0] = 99
        launcher.stub(0).kill()
        for _ in range(6):
            clock.tick(0.01)
            sup._step()
            if sup.replicas[0].state == BROKEN:
                break
        assert sup.replicas[0].state == BROKEN
        clock.tick(11.0)
        sup._step()                      # half-open spawn (born dead)
        sup._step()                      # death → re-open
        assert sup.replicas[0].state == BROKEN
        assert tel.counter("fleet.breaker_opened") == 2
    finally:
        sup.stop()
        launcher.close()
        tel.close()


# ---------------------------------------------------------------------------
# frontend: routing, retry-once, shedding
# ---------------------------------------------------------------------------


def _frontend(tmp_path, **kw):
    sup, launcher, clock = _fleet(tmp_path, **kw)
    fe = FleetFrontend(sup.config, sup)
    fe.start()
    sup.spawn_all()
    sup._step()
    return sup, launcher, clock, fe


def _post(port, rows, timeout=10):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/score",
        data=json.dumps({"rows": rows}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def test_frontend_routes_and_balances(tmp_path):
    sup, launcher, _clock, fe = _frontend(tmp_path)
    try:
        for i in range(8):
            out = _post(fe.port, [float(i)])
            assert out["margins"] == [float(i)]
        # Least-outstanding with fewest-served tie-break: sequential
        # load spreads across both replicas.
        assert launcher.stub(0).scored == 4
        assert launcher.stub(1).scored == 4
        assert fe.stats()["requests"] == 8
        assert fe.stats()["retries"] == 0
    finally:
        fe.close()
        sup.stop()
        launcher.close()


def test_frontend_healthz_follows_fleet(tmp_path):
    sup, launcher, _clock, fe = _frontend(tmp_path)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{fe.port}/healthz", timeout=10) as r:
            assert json.loads(r.read())["state"] == "ready"
        launcher.stub(0).kill()
        launcher.stub(1).kill()
        sup._step()                      # both dead → 0 ready
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{fe.port}/healthz", timeout=10)
        assert err.value.code == 503
    finally:
        fe.close()
        sup.stop()
        launcher.close()


def test_frontend_retries_exactly_once_on_dead_replica(tmp_path):
    """THE retry contract: a connection failure retries ONCE on a
    different replica; the client sees one success, the frontend
    counts one retry, and the dead replica's failure feedback lands in
    the supervisor."""
    sup, launcher, _clock, fe = _frontend(tmp_path)
    tel = telemetry.start("metrics")
    try:
        # Kill replica 0's socket WITHOUT telling the supervisor (no
        # _step): the frontend discovers it the hard way.
        launcher.stub(0).kill()
        out = _post(fe.port, [7.0])
        assert out["margins"] == [7.0]
        st = fe.stats()
        assert st["requests"] == 1
        assert st["retries"] == 1
        assert st["failed"] == 0
        assert tel.counter("serve.frontend_retries") == 1
        assert sup.replicas[0].probe_failures >= 1   # note_failure
    finally:
        fe.close()
        sup.stop()
        launcher.close()
        tel.close()


def test_frontend_sheds_503_with_retry_after_when_fleet_down(tmp_path):
    sup, launcher, _clock, fe = _frontend(tmp_path)
    tel = telemetry.start("metrics")
    try:
        launcher.stub(0).kill()
        launcher.stub(1).kill()
        sup._step()
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(fe.port, [1.0])
        assert err.value.code == 503
        assert err.value.headers.get("Retry-After") == "1"
        assert "no ready replica" in \
            json.loads(err.value.read().decode())["error"]
        assert fe.stats()["shed"] == 1
        assert tel.counter("serve.shed") == 1
    finally:
        fe.close()
        sup.stop()
        launcher.close()
        tel.close()


def test_frontend_retry_exhausted_is_502_not_hang(tmp_path):
    """Both replicas' sockets dead but the supervisor has not noticed
    yet: first attempt + one retry both fail → an answered 502."""
    sup, launcher, _clock, fe = _frontend(tmp_path)
    try:
        launcher.stub(0).kill()
        launcher.stub(1).kill()          # no _step: both look READY
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(fe.port, [1.0])
        assert err.value.code in (502, 503)
        st = fe.stats()
        assert st["retries"] == 1        # exactly one retry, no more
        assert st["failed"] + st["shed"] >= 1
    finally:
        fe.close()
        sup.stop()
        launcher.close()


def test_frontend_forwards_replica_sheds_verbatim(tmp_path):
    """A replica's 429/503 (admission shed) is the replica's verdict:
    forwarded with its Retry-After, counted as fleet-level shed, and
    NEVER retried on another replica."""
    sup, launcher, _clock, fe = _frontend(tmp_path, replicas=1)
    tel = telemetry.start("metrics")
    try:
        stub = launcher.stub(0)

        def shedding(body):
            from photon_ml_tpu.serving.http import HttpError

            raise HttpError(503, headers={"Retry-After": "9"},
                            error="estimated queue wait exceeds "
                                  "deadline")

        stub._ep.routes[("POST", "/v1/score")] = shedding
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(fe.port, [1.0])
        assert err.value.code == 503
        assert err.value.headers.get("Retry-After") == "9"
        st = fe.stats()
        assert st["shed"] == 1 and st["retries"] == 0
        assert tel.counter("serve.shed_replica") == 1
    finally:
        fe.close()
        sup.stop()
        launcher.close()
        tel.close()


def test_frontend_status_aggregates_fleet_view(tmp_path):
    sup, launcher, _clock, fe = _frontend(tmp_path)
    try:
        _post(fe.port, [1.0])
        with urllib.request.urlopen(
                f"http://127.0.0.1:{fe.port}/status", timeout=10) as r:
            st = json.loads(r.read())
        assert st["state"] == "ready"
        assert st["fleet"]["ready"] == 2
        assert len(st["fleet"]["replicas"]) == 2
        assert st["frontend"]["requests"] == 1
        with urllib.request.urlopen(
                f"http://127.0.0.1:{fe.port}/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "photon_fleet_ready_replicas 2" in text
        assert "photon_frontend_requests_total 1" in text
    finally:
        fe.close()
        sup.stop()
        launcher.close()


# ---------------------------------------------------------------------------
# rolling hot swap
# ---------------------------------------------------------------------------


def _publish(tmp_path, content: str) -> None:
    mdl = tmp_path / "mdl"
    mdl.mkdir(exist_ok=True)
    (mdl / "metadata.json").write_text(content)


def test_rolling_swap_recycles_one_replica_at_a_time(tmp_path):
    """A new manifest rolls the fleet: cordon → drain → recycle →
    ready, one replica at a time — the fleet NEVER dips below N−1
    ready, and both replicas end on fresh processes."""
    _publish(tmp_path, "model-v1")
    sup, launcher, clock = _fleet(tmp_path, watch_manifest=True)
    try:
        sup.spawn_all()
        sup._step()
        assert sup.ready_count() == 2
        launcher.version = "v2"
        _publish(tmp_path, "model-v2-longer")   # signature changes
        min_ready = 2
        for _ in range(20):
            clock.tick(0.1)
            sup._step()
            min_ready = min(min_ready, sup.ready_count())
            if sup.swaps == 1:
                break
        assert sup.swaps == 1
        assert sup.swap_aborts == 0
        assert min_ready == 1               # never below N−1
        assert sup.ready_count() == 2
        # Four launches total: 2 initial + 2 recycles; recycles did
        # not count as crash restarts (the replica_restarts alert must
        # not fire on a deploy).
        assert launcher.launch_count() == 4
        assert sup.status()["restarts"] == 0
        # Recycle latency recorded (the restart-latency plumbing).
        assert all(r.last_restart_s is not None for r in sup.replicas)
    finally:
        sup.stop()
        launcher.close()


def test_rolling_swap_waits_for_draining_requests(tmp_path):
    _publish(tmp_path, "model-v1")
    sup, launcher, clock = _fleet(tmp_path, watch_manifest=True)
    try:
        sup.spawn_all()
        sup._step()
        # Pin an outstanding request on replica 0.
        r0 = sup.acquire_replica()
        assert r0.idx == 0
        _publish(tmp_path, "model-v2-longer")
        clock.tick(0.1)
        sup._step()                      # swap starts, cordons 0
        assert sup.replicas[0].state == DRAINING
        clock.tick(0.1)
        sup._step()                      # outstanding=1 → still waiting
        assert sup.replicas[0].state == DRAINING
        assert launcher.stub(0).rc is None      # NOT killed yet
        sup.release_replica(r0)
        clock.tick(0.1)
        sup._step()                      # drained → terminate
        assert launcher.launches[0][1].replica.rc is not None
        for _ in range(10):
            clock.tick(0.1)
            sup._step()
            if sup.swaps == 1:
                break
        assert sup.swaps == 1
    finally:
        sup.stop()
        launcher.close()


def test_rolling_swap_aborts_on_corrupt_publish_under_load(tmp_path):
    """The corrupt-swap matrix case: the first recycled replica cannot
    come up on the new manifest → the swap ABORTS, the other replica
    keeps serving the previous model, and clients see zero failures."""
    _publish(tmp_path, "model-v1")
    sup, launcher, clock = _fleet(tmp_path, watch_manifest=True,
                                  restart_backoff_s=0.0,
                                  restart_backoff_max_s=0.0,
                                  breaker_threshold=3)
    fe = FleetFrontend(sup.config, sup)
    fe.start()
    try:
        sup.spawn_all()
        sup._step()
        launcher.dead_launches[0] = 99   # replica 0 reborn dead forever
        _publish(tmp_path, "model-v2-corrupt")
        for _ in range(30):
            clock.tick(0.1)
            sup._step()
            # Under load THROUGHOUT the doomed swap: every request
            # must still succeed via the surviving replica.
            out = _post(fe.port, [3.0])
            assert out["margins"] == [3.0]
            if sup.swap_aborts == 1:
                break
        assert sup.swap_aborts == 1
        assert sup.last_swap_error is not None
        assert sup.replicas[1].state == READY    # old model serving
        assert fe.stats()["failed"] == 0
        # The aborted signature is adopted: no swap-retry storm.
        clock.tick(0.5)
        sup._step()
        assert sup.status()["swap_in_progress"] is False
    finally:
        fe.close()
        sup.stop()
        launcher.close()


def test_dead_replica_during_rolling_swap_pauses_then_completes(
        tmp_path):
    """The OTHER replica dying mid-swap pauses the roll (cordoning
    would drop the fleet to zero); the normal restart machinery
    revives it, then the swap resumes and completes."""
    _publish(tmp_path, "model-v1")
    sup, launcher, clock = _fleet(tmp_path, watch_manifest=True,
                                  restart_backoff_s=1.0)
    try:
        sup.spawn_all()
        sup._step()
        _publish(tmp_path, "model-v2-longer")
        # Kill replica 1 in the same instant the swap begins.
        launcher.stub(1).kill()
        clock.tick(0.1)
        sup._step()      # swap detected; replica 1 death detected
        # Replica 1 down → the swap must NOT cordon replica 0.
        assert sup.replicas[0].state == READY
        assert sup.status()["swap_in_progress"] is True
        clock.tick(0.1)
        sup._step()
        assert sup.replicas[0].state == READY    # still paused
        clock.tick(1.1)                          # backoff elapses
        for _ in range(20):
            clock.tick(0.1)
            sup._step()
            if sup.swaps == 1:
                break
        assert sup.swaps == 1
        assert sup.ready_count() == 2
        assert sup.replicas[1].restarts == 1     # the crash restart
    finally:
        sup.stop()
        launcher.close()


# ---------------------------------------------------------------------------
# e2e: real subprocess fleet, SIGKILL mid-traffic
# ---------------------------------------------------------------------------


@pytest.mark.slow   # two replica subprocesses + warm-up + kill/restart
def test_fleet_sigkill_e2e_zero_client_failures(tmp_path):
    """THE acceptance criterion: SIGKILL one of two replicas under
    sustained client traffic → zero failed client requests (affected
    requests succeed via the single bounded retry), the replica is
    restarted, re-warmed, and back in rotation, and the fleet reports
    the restart."""
    import os
    import signal

    from photon_ml_tpu.io import model_io
    from photon_ml_tpu.models.glm import TaskType
    from photon_ml_tpu.serving.engine import dataset_rows
    from photon_ml_tpu.serving.fleet import FleetServer
    from tests.test_serving import _workload

    model, dataset = _workload()
    mdir = str(tmp_path / "model")
    model_io.save_game_model(model, TaskType.LOGISTIC_REGRESSION, mdir)
    cfg = ServingConfig(
        model_dir=mdir, port=0, replicas=2, batch_rows=8,
        batch_deadline_ms=1.0, ell_row_capacity=8,
        spill_dir=str(tmp_path / "spill"), entity_chunk=4,
        probe_every_s=0.2, probe_timeout_s=2.0,
        restart_backoff_s=0.2, telemetry="off", monitor="off")
    server = FleetServer(cfg, workdir=str(tmp_path / "fleet"))
    reqs = dataset_rows(dataset, 0, 8)
    try:
        server.start()
        assert server.supervisor.wait_ready(2, timeout_s=240.0), \
            server.supervisor.status()
        stop = threading.Event()
        errors: list = []
        ok = [0]
        lock = threading.Lock()

        def hammer():
            while not stop.is_set():
                try:
                    out = _post(server.port, reqs, timeout=30)
                    assert len(out["margins"]) == 8
                    with lock:
                        ok[0] += 1
                except Exception as e:   # noqa: BLE001 - collected
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for th in threads:
            th.start()
        time.sleep(1.0)
        victim = next(r for r in server.supervisor.status()["replicas"]
                      if r["state"] == "ready" and r["pid"])
        os.kill(victim["pid"], signal.SIGKILL)
        # Keep the traffic up across detection + restart + re-warm.
        deadline = time.time() + 180.0
        while time.time() < deadline:
            st = server.supervisor.status()
            if st["restarts"] >= 1 and st["ready"] == 2:
                break
            time.sleep(0.3)
        time.sleep(1.0)
        stop.set()
        for th in threads:
            th.join(timeout=60)
        st = server.supervisor.status()
        assert not errors, errors[:5]            # ZERO client failures
        assert ok[0] > 50
        assert st["restarts"] >= 1               # replica came back
        assert st["ready"] == 2
        assert st["last_restart_s"] is not None
        assert st["last_restart_s"] > 0
        # Post-recovery requests still score correctly.
        out = _post(server.port, reqs, timeout=30)
        assert len(out["margins"]) == 8
        fe = server.frontend.stats()
        assert fe["failed"] == 0
    finally:
        server.stop()
