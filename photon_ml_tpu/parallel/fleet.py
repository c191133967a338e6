"""Multi-host chunk-synchronized fleet streaming (ISSUE 16).

The two scale mechanisms that bound the KDD2012-scale run — the chunk
store/prefetch pipeline (host RSS) and the mesh-sharded GRR path (HBM)
— previously only composed inside ONE process.  This module is the
cross-process layer: the chunk store's chunk sequence is partitioned
across processes ("hosts"), each host opens/spills/prefetches only its
shard from a per-host spill directory, and the streaming objectives
reduce their per-chunk partials across the fleet on a
chunk-synchronized schedule.  Snap ML's hierarchical parallelism
(cluster → node → accelerator, pipelined loading at every level) is
the blueprint (PAPERS.md).

Pieces:

- ``FleetContext`` — (host_id, n_hosts, transport) for this process.
  ``initialize_from_env`` builds it from ``jax.distributed`` state
  (``transport="psum"``) or from the ``PHOTON_FLEET_*`` env trio
  (``transport="tcp"`` — the local-fleet fallback for CPU backends
  whose jaxlib has no multiprocess collectives, see
  ``MULTIPROC_UNSUPPORTED_MARKER``).
- ``shard_chunk_ids`` — contiguous per-host chunk shard, padded with
  ``EMPTY_CHUNK`` sentinels to a COMMON step count, so every host
  issues the same number of per-chunk reductions and collectives never
  deadlock on ragged shards (sentinel steps contribute exact zeros).
- ``FleetReducer`` — the per-chunk allreduce.  ``psum`` transport runs
  one cached jitted ``shard_map``/``lax.psum`` program over a
  one-device-per-process mesh (the small partial pytree is the ONLY
  thing that crosses hosts — chunk programs stay process-local, so the
  GRR/pallas per-chunk pipeline needs no sharding).  ``tcp`` transport
  is a star allreduce through a ``ReduceCoordinator`` (run by the
  launcher), summing contributions in host-id order — deterministic,
  so killed-host replay is bitwise-stable.
- ``ReduceCoordinator`` — the launcher-side reduction server.  Results
  are cached per sequence number: a host killed mid-sweep resumes from
  its per-host checkpoint, replays its reduce sequence, and fast-
  forwards through cached totals until it rejoins the live barrier —
  the rest of the fleet just waits at the chunk barrier, it is never
  restarted.

Thread contract (photon-lint ``unlocked-shared-write``): coordinator
state mutates under one condition-variable lock; client sockets are
owned by the calling (driver) thread.  All waits are bounded
(``stall_timeout_s``) — a torn fleet ends in ONE actionable error,
never a hang.
"""

from __future__ import annotations

import contextlib
import dataclasses
import io
import json
import logging
import os
import socket
import threading
import time

import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.config import read_env
from photon_ml_tpu.reliability import faults

logger = logging.getLogger(__name__)

# The sentinel chunk id padding ragged shards to a common step count.
# A sentinel step computes no chunk — it contributes an exact-zero
# partial so the fleet's per-chunk reduction count stays identical on
# every host.
EMPTY_CHUNK = -1

# Mesh axis name for the cross-process partial reduction (distinct from
# the intra-process DATA_AXIS/ENTITY_AXIS meshes — the reduce mesh has
# exactly one device per process).
HOSTS_AXIS = "hosts"

# The jaxlib CPU backend's "no multiprocess collectives" marker: the
# single capability probe every 2-process CPU test and a launcher's
# transport selection key off (ISSUE 16 satellite — previously an
# ad-hoc string scattered through the mesh tests).
MULTIPROC_UNSUPPORTED_MARKER = "Multiprocess computations aren't implemented"

# Default bound on any fleet barrier wait: a killed host stalls its
# peers AT the barrier (that is the protocol — the fleet is never
# restarted), but a fleet that lost a host forever must end in one
# actionable error, never a hang.
DEFAULT_STALL_TIMEOUT_S = 600.0

# Reduce-result cache depth on the coordinator: a replaying host can
# fast-forward at most this many sequence numbers past its checkpoint.
# Solver/CD checkpoints land every iteration (a handful of sweeps ×
# chunks apart), so 4096 covers multiple checkpoint intervals at any
# realistic chunk grid.
_RESULT_CACHE_CAP = 4096


class FleetBarrierError(RuntimeError):
    """A fleet reduction could not complete (torn fleet, dead
    coordinator, stalled peer past the timeout)."""


@dataclasses.dataclass(frozen=True)
class FleetContext:
    """This process's position in the training fleet.

    ``transport``: ``"psum"`` (jax.distributed collectives) or
    ``"tcp"`` (the local-fleet star allreduce via ``coordinator``,
    ``host:port``)."""

    host_id: int
    n_hosts: int
    transport: str = "psum"
    coordinator: str | None = None

    def __post_init__(self):
        if self.n_hosts < 1:
            raise ValueError("n_hosts must be >= 1")
        if not 0 <= self.host_id < self.n_hosts:
            raise ValueError(
                f"host_id {self.host_id} not in [0, {self.n_hosts})")
        if self.transport not in ("psum", "tcp"):
            raise ValueError("transport must be psum|tcp")
        if self.transport == "tcp" and self.n_hosts > 1 \
                and not self.coordinator:
            raise ValueError("tcp transport needs coordinator host:port")

    @property
    def is_fleet(self) -> bool:
        return self.n_hosts > 1


def shard_chunk_ids(n_chunks: int, host_id: int, n_hosts: int
                    ) -> tuple[list[int], list[int]]:
    """Contiguous chunk shard for one host + its padded schedule.

    Returns ``(local_ids, schedule)``: ``local_ids`` are the real chunk
    ids this host owns; ``schedule`` is ``local_ids`` followed by
    ``EMPTY_CHUNK`` sentinels up to the COMMON per-host step count
    ``ceil(n_chunks / n_hosts)``.  Real chunks come FIRST so the
    prefetch pipeline never idles behind a sentinel; a host past the
    end of a ragged grid gets an all-sentinel schedule (its partials
    are exact zeros every step)."""
    if n_chunks < 0:
        raise ValueError("n_chunks must be >= 0")
    if not 0 <= host_id < n_hosts:
        raise ValueError(f"host_id {host_id} not in [0, {n_hosts})")
    steps = -(-n_chunks // n_hosts) if n_chunks else 0
    lo = min(host_id * steps, n_chunks)
    hi = min(lo + steps, n_chunks)
    local = list(range(lo, hi))
    return local, local + [EMPTY_CHUNK] * (steps - len(local))


def host_dir(base: str, ctx: "FleetContext | None") -> str:
    """Per-host subdirectory of ``base`` (spill/checkpoint/output
    sharding by process id); ``base`` unchanged outside a fleet."""
    if ctx is None or not ctx.is_fleet:
        return base
    return os.path.join(base, f"host_{ctx.host_id:03d}")


# ---------------------------------------------------------------------------
# Active-context plumbing (the telemetry/checkpoint pattern: deep library
# code — chunk builders, streaming sweeps — cannot thread a handle
# through every call).
# ---------------------------------------------------------------------------

_ACTIVE: FleetContext | None = None
_REDUCER: "FleetReducer | None" = None
_ACTIVE_LOCK = threading.Lock()


def active() -> FleetContext | None:
    """The active fleet context, or None (single-host run)."""
    return _ACTIVE


def reducer() -> "FleetReducer | None":
    """The process-wide reducer for the active context (lazily built),
    or None outside a fleet."""
    global _REDUCER
    ctx = _ACTIVE
    if ctx is None or not ctx.is_fleet:
        return None
    with _ACTIVE_LOCK:
        if _REDUCER is None or _REDUCER.ctx is not ctx:
            _REDUCER = FleetReducer(ctx)
        return _REDUCER


@contextlib.contextmanager
def session(ctx: FleetContext | None):
    """Expose ``ctx`` as the active fleet for the block (tests'
    workers); None yields a no-op."""
    global _ACTIVE, _REDUCER
    if ctx is None:
        yield None
        return
    with _ACTIVE_LOCK:
        if _ACTIVE is not None:
            raise RuntimeError("a fleet session is already active")
        _ACTIVE = ctx
    try:
        yield ctx
    finally:
        with _ACTIVE_LOCK:
            red, _REDUCER = _REDUCER, None
            _ACTIVE = None
        if red is not None:
            red.close()


def initialize_from_env() -> FleetContext | None:
    """Build + activate the fleet context for this process, or None.

    Order: an initialized ``jax.distributed`` multi-process runtime
    wins (``transport="psum"`` — the production path); otherwise the
    ``PHOTON_FLEET_NUM_HOSTS`` / ``PHOTON_FLEET_HOST_ID`` /
    ``PHOTON_FLEET_COORDINATOR`` env trio selects the local-fleet tcp
    transport.  Idempotent: an already-active context is returned
    as-is."""
    global _ACTIVE
    if _ACTIVE is not None:
        return _ACTIVE
    ctx = None
    try:
        import jax

        if jax.process_count() > 1:
            ctx = FleetContext(host_id=jax.process_index(),
                               n_hosts=jax.process_count(),
                               transport="psum")
    except Exception as e:  # pragma: no cover - jax-less hosts
        logger.info("fleet: jax process probe unavailable (%r)", e)
    if ctx is None:
        n = read_env("PHOTON_FLEET_NUM_HOSTS")
        if n is None or int(n) <= 1:
            return None
        ctx = FleetContext(
            host_id=int(read_env("PHOTON_FLEET_HOST_ID", "0") or 0),
            n_hosts=int(n),
            transport="tcp",
            coordinator=read_env("PHOTON_FLEET_COORDINATOR"),
        )
    with _ACTIVE_LOCK:
        if _ACTIVE is None:
            _ACTIVE = ctx
        ctx = _ACTIVE
    logger.info("fleet: host %d of %d (transport=%s)",
                ctx.host_id, ctx.n_hosts, ctx.transport)
    return ctx


# ---------------------------------------------------------------------------
# Wire codec (tcp transport): one JSON header line + one npz payload.
# Pickle-free by design — the coordinator ingests bytes from N worker
# processes; npz with allow_pickle=False bounds the parse surface.
# ---------------------------------------------------------------------------


def _encode_leaves(leaves: list[np.ndarray]) -> bytes:
    bio = io.BytesIO()
    np.savez(bio, *[np.asarray(lf) for lf in leaves])
    return bio.getvalue()


def _decode_leaves(payload: bytes) -> list[np.ndarray]:
    with np.load(io.BytesIO(payload), allow_pickle=False) as z:
        return [np.asarray(z[f"arr_{i}"]) for i in range(len(z.files))]


def _send_msg(sock: socket.socket, header: dict, payload: bytes) -> None:
    head = json.dumps({**header, "nbytes": len(payload)}).encode() + b"\n"
    sock.sendall(head + payload)


def _recv_exact(fh, n: int) -> bytes:
    buf = fh.read(n)
    if len(buf) != n:
        raise FleetBarrierError(
            f"fleet connection closed mid-message ({len(buf)}/{n} bytes)")
    return buf


def _recv_msg(fh) -> tuple[dict, bytes]:
    line = fh.readline()
    if not line:
        raise EOFError("fleet connection closed")
    header = json.loads(line.decode())
    return header, _recv_exact(fh, int(header.get("nbytes", 0)))


# ---------------------------------------------------------------------------
# ReduceCoordinator: the launcher-side star-allreduce server.
# ---------------------------------------------------------------------------


class ReduceCoordinator:
    """Star allreduce for the tcp local-fleet transport.

    Runs in the LAUNCHER (a test harness / a dedicated
    supervisor) — deliberately outside any worker, so killing a worker
    host never takes the reduction plane with it.  Each reduce sequence
    number completes when all ``n_hosts`` contributions arrive; the
    total (summed in host-id order — deterministic float order) is
    broadcast to every waiter and cached, so a restarted host replaying
    from its per-host checkpoint fast-forwards through cached totals
    (duplicate contributions for a completed seq are answered from
    cache, never re-summed)."""

    def __init__(self, n_hosts: int, host: str = "127.0.0.1",
                 port: int = 0,
                 stall_timeout_s: float = DEFAULT_STALL_TIMEOUT_S):
        if n_hosts < 1:
            raise ValueError("n_hosts must be >= 1")
        self.n_hosts = int(n_hosts)
        self.stall_timeout_s = float(stall_timeout_s)
        self._cond = threading.Condition()
        self._pending: dict[int, dict[int, list[np.ndarray]]] = {}
        self._done: dict[int, list[np.ndarray]] = {}
        self._done_order: list[int] = []
        self._closed = False
        self.reduces = 0          # completed sequence numbers
        self.replays = 0          # cache-answered duplicate requests
        self._srv = socket.create_server((host, port))
        self._srv.settimeout(0.5)
        self.port = self._srv.getsockname()[1]
        self.address = f"{host}:{self.port}"
        self._threads: list[threading.Thread] = []
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True,
            name="photon-fleet-coordinator")
        self._accept_thread.start()

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                conn, _addr = self._srv.accept()
            except socket.timeout:  # photon-lint: disable=swallowed-exception (the accept poll tick; loop re-checks _closed)
                continue
            except OSError:  # photon-lint: disable=swallowed-exception (server socket closed under us: the shutdown path)
                break
            t = threading.Thread(target=self._serve, args=(conn,),
                                 daemon=True, name="photon-fleet-conn")
            t.start()
            with self._cond:
                self._threads.append(t)

    def _serve(self, conn: socket.socket) -> None:
        try:
            with conn, conn.makefile("rb") as fh:
                while True:
                    try:
                        header, payload = _recv_msg(fh)
                    # photon-lint: disable=swallowed-exception (worker hung up, possibly SIGKILLed; its restart replays the seq)
                    except (EOFError, FleetBarrierError, ValueError,
                            OSError):
                        return
                    total = self._reduce_one(int(header["host"]),
                                             int(header["seq"]),
                                             _decode_leaves(payload))
                    if total is None:
                        return  # coordinator closed / barrier torn
                    _send_msg(conn, {"seq": int(header["seq"])},
                              _encode_leaves(total))
        except OSError:  # photon-lint: disable=swallowed-exception (peer death mid-reply; the worker side raises its own barrier error)
            return

    def _reduce_one(self, host: int, seq: int,
                    leaves: list[np.ndarray]) -> list[np.ndarray] | None:
        deadline = time.monotonic() + self.stall_timeout_s
        with self._cond:
            if seq in self._done:
                self.replays += 1
                return self._done[seq]
            # Overwrite semantics per (seq, host): a replaying host's
            # duplicate contribution for a still-pending seq replaces
            # (never double-counts) its earlier one — the values are
            # bitwise-identical by determinism anyway.
            self._pending.setdefault(seq, {})[host] = leaves
            if len(self._pending[seq]) == self.n_hosts:
                contrib = self._pending.pop(seq)
                total = contrib[0]
                for h in range(1, self.n_hosts):
                    total = [np.add(a, b) for a, b in
                             zip(total, contrib[h])]
                self._done[seq] = total
                self._done_order.append(seq)
                self.reduces += 1
                if len(self._done_order) > _RESULT_CACHE_CAP:
                    self._done.pop(self._done_order.pop(0), None)
                self._cond.notify_all()
                return total
            while seq not in self._done and not self._closed:
                if not self._cond.wait(
                        timeout=min(1.0, self.stall_timeout_s)):
                    if time.monotonic() > deadline:
                        return None
            return self._done.get(seq)

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
            threads = list(self._threads)
        try:
            self._srv.close()
        except OSError:  # photon-lint: disable=swallowed-exception (already closed)
            pass
        self._accept_thread.join(timeout=5.0)
        for t in threads:
            t.join(timeout=5.0)


# ---------------------------------------------------------------------------
# FleetReducer: the per-chunk allreduce, both transports.
# ---------------------------------------------------------------------------


class FleetReducer:
    """Per-chunk partial-pytree allreduce for one fleet process.

    ``reduce(tree)`` returns the fleet-wide sum with the SAME tree
    structure; every host must call it in the same order (the
    chunk-synchronized schedule guarantees the alignment).  ``seq`` is
    the monotonically increasing reduction counter — it rides in the
    per-host solver checkpoints so a resumed host replays the exact
    sequence (tcp transport replay is answered from the coordinator's
    result cache).

    Wall time spent inside ``reduce`` (transfer + peer wait) is the
    chunk-barrier cost; it accumulates in ``barrier_wait_s`` and the
    ``fleet.barrier_wait_s`` telemetry counter.
    """

    def __init__(self, ctx: FleetContext,
                 stall_timeout_s: float = DEFAULT_STALL_TIMEOUT_S):
        self.ctx = ctx
        self.seq = 0
        self.barrier_wait_s = 0.0
        self.stall_timeout_s = float(stall_timeout_s)
        self._sock: socket.socket | None = None
        self._fh = None
        self._psum_cache: dict = {}
        self._mesh = None

    # -- psum transport ------------------------------------------------------

    def _hosts_mesh(self):
        """1-D mesh with exactly ONE device per process — the partial
        pytree's reduction plane.  Chunk programs never touch it."""
        if self._mesh is None:
            import jax
            from jax.sharding import Mesh

            by_proc: dict[int, object] = {}
            for d in jax.devices():
                by_proc.setdefault(d.process_index, d)
            if len(by_proc) != self.ctx.n_hosts:
                raise FleetBarrierError(
                    f"jax reports {len(by_proc)} processes, fleet "
                    f"context says {self.ctx.n_hosts}")
            devs = [by_proc[p] for p in sorted(by_proc)]
            self._mesh = Mesh(np.asarray(devs), (HOSTS_AXIS,))
        return self._mesh

    def _psum_program(self, key, n_leaves: int):
        prog = self._psum_cache.get(key)
        if prog is None:
            import jax
            import jax.numpy as jnp
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            mesh = self._hosts_mesh()

            def red(*xs):
                return tuple(jax.lax.psum(jnp.squeeze(x, 0), HOSTS_AXIS)
                             for x in xs)

            # photon-lint: disable=jit-in-function (memoized in self._psum_cache keyed on leaf shapes/dtypes; one compile per pytree signature)
            prog = jax.jit(shard_map(
                red, mesh=mesh,
                in_specs=(P(HOSTS_AXIS),) * n_leaves,
                out_specs=(P(),) * n_leaves))
            self._psum_cache[key] = prog
        return prog

    def _psum_reduce(self, leaves: list):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._hosts_mesh()
        dev0 = mesh.devices.flat[self.ctx.host_id]
        placed = []
        shapes = []
        for lf in leaves:
            lf = jnp.asarray(lf)
            shapes.append((lf.shape, lf.dtype.name))
            local = jax.device_put(lf[None], dev0)
            placed.append(jax.make_array_from_single_device_arrays(
                (self.ctx.n_hosts, *lf.shape),
                NamedSharding(mesh, P(HOSTS_AXIS)), [local]))
        prog = self._psum_program(tuple(shapes), len(leaves))
        out = prog(*placed)
        jax.block_until_ready(out)
        # Replicated outputs → this process's local single-device view,
        # so downstream per-chunk programs stay process-local.
        return [r.addressable_data(0) for r in out]

    # -- tcp transport -------------------------------------------------------

    def _connect(self) -> None:
        host, port = self.ctx.coordinator.rsplit(":", 1)
        deadline = time.monotonic() + self.stall_timeout_s
        delay = 0.05
        while True:
            try:
                self._sock = socket.create_connection(
                    (host, int(port)), timeout=self.stall_timeout_s)
                self._sock.setsockopt(socket.IPPROTO_TCP,
                                      socket.TCP_NODELAY, 1)
                self._fh = self._sock.makefile("rb")
                return
            except OSError as e:
                if time.monotonic() > deadline:
                    raise FleetBarrierError(
                        f"fleet coordinator {self.ctx.coordinator} "
                        f"unreachable: {e}") from e
                time.sleep(delay)
                delay = min(delay * 2, 1.0)

    def _tcp_reduce(self, leaves: list) -> list[np.ndarray]:
        if self._sock is None:
            self._connect()
        try:
            _send_msg(self._sock,
                      {"host": self.ctx.host_id, "seq": self.seq},
                      _encode_leaves([np.asarray(lf) for lf in leaves]))
            header, payload = _recv_msg(self._fh)
        except (OSError, EOFError) as e:
            raise FleetBarrierError(
                f"fleet reduce seq={self.seq} failed (coordinator "
                f"{self.ctx.coordinator}): {e}") from e
        if int(header.get("seq", -1)) != self.seq:
            raise FleetBarrierError(
                f"fleet reduce got seq {header.get('seq')} for "
                f"request seq {self.seq} (protocol skew)")
        return _decode_leaves(payload)

    # -- the public reduce ---------------------------------------------------

    def reduce(self, tree):
        """Fleet-wide sum of ``tree`` (any pytree of arrays/scalars).
        Single-host contexts return the tree unchanged (and count
        nothing) — callers never branch on fleet-ness."""
        if not self.ctx.is_fleet:
            return tree
        import jax

        faults.fire("fleet.reduce", seq=self.seq)
        leaves, treedef = jax.tree.flatten(tree)
        t0 = time.perf_counter()
        if self.ctx.transport == "psum":
            out = self._psum_reduce(leaves)
        else:
            out = self._tcp_reduce(leaves)
        dt = time.perf_counter() - t0
        self.seq += 1
        self.barrier_wait_s += dt
        telemetry.count("fleet.psums")
        telemetry.count("fleet.barrier_wait_s", dt)
        return jax.tree.unflatten(treedef, out)

    def close(self) -> None:
        if self._fh is not None:
            with contextlib.suppress(OSError):
                self._fh.close()
            self._fh = None
        if self._sock is not None:
            with contextlib.suppress(OSError):
                self._sock.close()
            self._sock = None


# ---------------------------------------------------------------------------
# Capability probe: can THIS jaxlib run real 2-process CPU collectives?
# ---------------------------------------------------------------------------

_PROBE_WORKER = r'''
import os, sys
os.environ.pop("JAX_PLATFORMS", None)
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
    num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
    process_id=int(os.environ["JAX_PROCESS_ID"]))
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map
mesh = Mesh(np.asarray(jax.devices()[:jax.process_count()]), ("hosts",))
arr = jax.make_array_from_single_device_arrays(
    (jax.process_count(),), NamedSharding(mesh, P("hosts")),
    [jax.device_put(jnp.ones((1,)), jax.local_devices()[0])])
out = jax.jit(shard_map(lambda x: jax.lax.psum(x[0], "hosts"),
                        mesh=mesh, in_specs=P("hosts"),
                        out_specs=P(), check_vma=False))(arr)
assert float(np.asarray(out.addressable_data(0))) == jax.process_count()
print("FLEET_PROBE_OK", flush=True)
'''

_PROBE_RESULT: bool | None = None


def probe_cpu_multiprocess_collectives(timeout_s: float = 120.0) -> bool:
    """Whether this environment can run REAL 2-process CPU collectives
    (jax.distributed + cross-process psum).  Spawns two tiny probe
    workers once per process and caches the verdict — a launcher's
    transport selection and the 2-process tests' skip guard share this
    single probe instead of ad-hoc marker scans."""
    global _PROBE_RESULT
    if _PROBE_RESULT is not None:
        return _PROBE_RESULT
    import subprocess
    import sys
    import tempfile

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    with tempfile.TemporaryDirectory(prefix="fleet_probe_") as tmp:
        script = os.path.join(tmp, "probe_worker.py")
        with open(script, "w") as f:
            f.write(_PROBE_WORKER)
        procs = []
        for pid in range(2):
            env = dict(os.environ)  # photon-lint: disable=env-read (whole-environment copy for a subprocess, not a config knob read)
            env.pop("JAX_PLATFORMS", None)
            env.update({
                "JAX_COORDINATOR_ADDRESS": f"localhost:{port}",
                "JAX_NUM_PROCESSES": "2",
                "JAX_PROCESS_ID": str(pid),
                "XLA_FLAGS": "",
            })
            procs.append(subprocess.Popen(
                [sys.executable, script], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
            outs.append(out or "")
    ok = (all(p.returncode == 0 for p in procs)
          and all("FLEET_PROBE_OK" in o for o in outs)
          and not any(MULTIPROC_UNSUPPORTED_MARKER in o for o in outs))
    if not ok:
        logger.info("fleet probe: 2-process CPU collectives unavailable "
                    "(rc=%s)", [p.returncode for p in procs])
    _PROBE_RESULT = ok
    return ok
