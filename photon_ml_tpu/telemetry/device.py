"""Device accounting: XLA program costs, device memory, rooflines (ISSUE 8).

The ROADMAP's scale math runs on two numbers that were previously
hand-reconstructed: device-memory residency ("~1.6 GB HBM per 1e6
examples" — the KDD sizing for the ~16-chip mesh) and per-program
bytes/FLOPs (PERF.md's roofline fractions).  This module turns both
into emitted data riding the telemetry session:

- **Program costs**: per-jitted-program XLA ``cost_analysis()`` (FLOPs,
  bytes accessed) + ``memory_analysis()`` (argument/output/temp bytes),
  captured once per session per program name at its first instrumented
  dispatch (``maybe_capture``).  The capture AOT-relowers the
  just-executed program — the pjit lowering cache means NO new
  "Compiling" record is emitted, so the compile-budget counters and
  guard tests are untouched (verified: ``jax.compiles`` stays 0 across
  a capture of a warm program).
- **Roofline estimate**: bytes-accessed over the device's published
  peak memory bandwidth — the analytic time floor the report compares
  against the measured per-chunk span.  Peaks are a small static table
  keyed by ``device_kind``, each with its source; an accelerator that
  is not in it is an error, never a default.  The CPU backend is the
  host, not a device with a roofline: there the counts (FLOPs, bytes)
  are captured and no estimate is made.
- **Device memory**: ``Device.memory_stats()`` where the backend
  provides it (TPU/GPU), a ``jax.live_arrays()`` nbytes census as the
  CPU fallback — sampled at phase boundaries (every cat="phase" span
  open/close) into ``device.bytes_in_use`` gauges and a (ts, bytes)
  series for the trace counter track.

Everything is best-effort and session-gated: with telemetry off these
helpers cost one global read; capture/sampling failures degrade to a
missing block, never a broken run (the guard discipline).
"""

from __future__ import annotations

import logging
import sys

logger = logging.getLogger(__name__)

# Published peak HBM bandwidth per chip, GB/s, by jax ``device_kind``.
DEVICE_PEAK_GBPS = {
    "TPU v5 lite": (819.0, 'Google Cloud documentation, "TPU v5e": '
                           "16 GB of HBM at 819 GB/s per chip"),
}


def peak_gbps(device_kind: str) -> tuple[float, str]:
    """(GB/s, source) for ``device_kind``; an unlisted device raises."""
    try:
        return DEVICE_PEAK_GBPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}: add it "
            "to telemetry.device.DEVICE_PEAK_GBPS with its source"
        ) from None


def _jax():
    """The jax module if (and only if) something already imported it —
    device accounting must never force a backend into a host-only
    driver."""
    return sys.modules.get("jax")


def program_cost(fn, args) -> dict | None:
    """FLOPs / bytes / memory for jitted ``fn`` at ``args`` via AOT
    ``lower().compile()``, plus, on an accelerator, the roofline
    estimate against its ``device_kind``'s published peak.

    Call AFTER the program has executed once with congruent arguments:
    the lowering cache then serves the trace, no "Compiling" record is
    logged (compile budgets unaffected), and the XLA backend compile is
    a cache hit wherever the persistent compilation cache is wired.
    Returns None (logged at info) on any failure."""
    try:
        compiled = fn.lower(*args).compile()
        ca = compiled.cost_analysis()
    except Exception as e:       # pragma: no cover - backend-specific
        logger.info("device cost capture failed: %r", e)
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    out = {
        "flops": flops,
        "bytes_accessed": byts,
        "bytes_per_flop": (round(byts / flops, 4) if flops > 0 else None),
    }
    try:
        mem = compiled.memory_analysis()
        out["argument_bytes"] = int(mem.argument_size_in_bytes)
        out["output_bytes"] = int(mem.output_size_in_bytes)
        out["temp_bytes"] = int(mem.temp_size_in_bytes)
    except Exception:  # pragma: no cover - backend-specific  # photon-lint: disable=swallowed-exception (memory_analysis is optional per backend; cost rows just omit it)
        pass
    import jax

    device = jax.devices()[0]   # fn just ran: the backend is up
    out["platform"] = device.platform
    out["device_kind"] = device.device_kind
    if device.platform != "cpu" and byts > 0:
        gbps, source = peak_gbps(device.device_kind)
        out["peak_gbps"] = gbps
        out["peak_source"] = source
        out["roofline_est_ms"] = round(byts / (gbps * 1e9) * 1e3, 6)
    return out


def maybe_capture(name: str, fn, args, span: str | None = None) -> bool:
    """Session-scoped, once-per-name program-cost capture.

    Instrumentation sites call this right after a program's first
    dispatch in a sweep; the compile bridge's counter proves the
    capture itself compiled nothing new.  ``span`` names the stage span
    whose measured duration the report compares the roofline estimate
    against (e.g. ``chunk_compute``).  Returns True when THIS call
    performed the capture (callers exclude that dispatch from their
    per-program timing measures — it paid the XLA compile)."""
    from photon_ml_tpu import telemetry

    t = telemetry.active()
    if t is None:
        return False
    with t._lock:
        if name in t._device_programs:
            return False
        t._device_programs[name] = None   # reserve: capture once, ever
    cost = program_cost(fn, args)
    if cost is None:
        return True
    if span is not None:
        cost["span"] = span
    with t._lock:
        t._device_programs[name] = cost
    t._log.event("device_cost", program=name, **cost)
    return True


def memory_snapshot() -> dict | None:
    """Current device-memory occupancy: backend ``memory_stats()``
    summed over local devices, or a live-buffer nbytes census on
    backends (CPU) that expose none.  None when jax is absent or the
    backend is not initialized."""
    jax = _jax()
    if jax is None:
        return None
    try:
        devices = jax.local_devices()
    except Exception:  # photon-lint: disable=swallowed-exception (no initialized backend: the memory gauge simply has no source)
        return None
    in_use = peak = 0
    have_stats = False
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if ms:
            have_stats = True
            in_use += int(ms.get("bytes_in_use", 0))
            peak += int(ms.get("peak_bytes_in_use", 0))
    if have_stats:
        return {"source": "memory_stats", "bytes_in_use": in_use,
                "peak_bytes_in_use": peak, "devices": len(devices)}
    try:
        live = jax.live_arrays()
        return {"source": "live_arrays",
                "bytes_in_use": int(sum(int(getattr(a, "nbytes", 0))
                                        for a in live)),
                "buffers": len(live)}
    except Exception:  # pragma: no cover - jax-version edge  # photon-lint: disable=swallowed-exception (live_arrays census is best-effort; gauge degrades to absent)
        return None
