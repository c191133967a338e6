"""One run of one cell of BENCHMARK.json, on the chips of this machine.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process.  Set-up (everything before the window, all of it
``setup_s``): find the cell's files by name, make its data from the
seed, and run the cell's operation once to compile or load every
program.  Window: start operations while less than ``--seconds`` have
passed and always finish the one in flight.  With ``--trace 1`` the window's first
operation runs under the JAX profiler and the per-layer metrics are
read; with ``--trace 0`` the end-to-end metrics are.  Once the window
has closed and the device's peak has been read, the window's last
result is checked against the plain reference: that decides
``correct``, and its seconds are nobody's metric.

Everything but the result goes to stderr, one JSON object per line,
the numbers that were compared, each beside its limit, last.
The only line on stdout is the contract's result.  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints
no result.  See README.md beside this file.
"""

import time

T_START = time.time()  # the process's start, as near as Python can read it

import argparse  # noqa: E402
import glob  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark.harness import manifest as manifests  # noqa: E402
from benchmark.harness import trace_reduce  # noqa: E402
from benchmark.harness.peaks import peaks  # noqa: E402
from benchmark.harness.report import CompileClock, result_line, say  # noqa: E402

OUT_DIR = os.path.join(BENCH_DIR, "out")
BREAKDOWN_ENTRIES = 10


def traced(operation_name, trace_dir, call):
    """``call()`` under the JAX profiler, inside a TraceAnnotation named
    after the operation; returns (its result, its seconds, the path of
    the ``.xplane.pb``).  The Python tracer is off: an operation is
    tens of seconds of host Python, and per-call events of it would
    swamp the trace and slow what is measured."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(operation_name):
            t0 = time.perf_counter()
            result = call()
            seconds = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return result, seconds, found[0]


def run_cell(cell, seed, seconds, trace_on, devices):
    """Set-up, window and reduction of one run of ``cell`` (as
    ``manifest.resolve`` gives it) on ``devices``; returns the keyword
    arguments of ``result_line``, or None (the reason said) when no
    result can be given."""
    from photon_ml_tpu import native
    from photon_ml_tpu.cache import (
        cache_entry_count,
        enable_compilation_cache,
    )

    name, chips = cell["cell"]["name"], cell["cell"]["chips"]
    cache_dir = enable_compilation_cache()
    entries_before = cache_entry_count(cache_dir)
    say(phase="compile_cache", dir=cache_dir, entries=entries_before,
        from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))
    clock = CompileClock()
    if native.lib() is None:
        say(error="native ETL library unavailable (its reason is above)")
        return None

    # -- set-up -------------------------------------------------------------
    t0 = time.perf_counter()
    generator = manifests.load_module(cell["generator_path"])
    data = generator.make(seed, **cell["config"]["generator"]["params"])
    say(phase="generate", seconds=time.perf_counter() - t0,
        generator=cell["config"]["generator"]["name"], seed=seed)

    operation_name = cell["traffic"]["operation"]
    operation = manifests.load_module(cell["operation_path"])
    state = operation.prepare(cell["config"], cell["traffic"], data)
    t0, c0 = time.perf_counter(), clock.seconds
    warm = operation.one(state)
    say(phase="warm_up", seconds=time.perf_counter() - t0,
        compile_seconds=clock.seconds - c0, compiles=clock.count,
        **operation.summary(warm))

    # -- window -------------------------------------------------------------
    durations = []
    attempted = failed = 0
    trace = last = None
    c0, n0 = clock.seconds, clock.count
    setup_s = time.time() - T_START
    w0 = time.perf_counter()
    while time.perf_counter() - w0 < seconds:
        attempted += 1
        try:
            if trace_on and trace is None:
                outcome, took, xplane = traced(
                    operation_name,
                    os.path.join(OUT_DIR, "trace", f"{name}-{seed}"),
                    lambda: operation.one(state))
                trace = trace_reduce.summarize(
                    trace_reduce.read_xplane(xplane), operation_name,
                    chips, BREAKDOWN_ENTRIES)
                say(phase="trace", xplane=xplane,
                    **{k: v for k, v in trace.items()
                       if k != "device_events"})
            else:
                t0 = time.perf_counter()
                outcome = operation.one(state)
                took = time.perf_counter() - t0
        except Exception:
            failed += 1
            say(phase=operation_name, error=traceback.format_exc())
            continue
        if operation.ok(outcome, warm):
            durations.append(took)
            last = outcome
        else:
            failed += 1
        say(phase=operation_name, seconds=took, **operation.summary(outcome))
    window_s = time.perf_counter() - w0
    if not durations:
        say(error="no operation of the window completed")
        return None

    # -- result -------------------------------------------------------------
    peak = max(int(d.memory_stats()["peak_bytes_in_use"])
               for d in devices[:chips])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    breakdown = None
    if trace_on:
        group = cell["per_layer"]
        ctx = {
            "config": cell["config"], "chips": chips,
            "attempted": attempted,
            "compile_s_window": clock.seconds - c0,
            "memory_peak_bytes": peak, "trace": trace,
        }
        metrics = {}
        for m in group:
            value = manifests.load_module(
                cell["layer_metric_paths"][m["name"]]).read(ctx)
            if value is not None:
                metrics[m["name"]] = value
        device["busy_s"] = trace["busy_ns"] / 1e9
        device["window_s"] = trace["window_ns"] / 1e9
        breakdown = {"device_ops": trace["device_ops"],
                     "idle_gaps": trace["idle_gaps"]}
    else:
        group = cell["end_to_end"]
        measured = dict(operation.end_to_end(durations, window_s),
                        setup_s=setup_s)
        metrics = {m["name"]: measured[m["name"]] for m in group}
    say(phase="done", memory_peak_bytes=peak,
        memory_bytes_limit=devices[0].memory_stats().get("bytes_limit"),
        window_seconds=window_s, attempted=attempted,
        failed=failed, durations=durations, setup_s=setup_s,
        compile_seconds_window=clock.seconds - c0,
        compiles_window=clock.count - n0,
        compile_cache_entries_before=entries_before,
        compile_cache_entries_after=cache_entry_count(cache_dir),
        compile_seconds_total=clock.seconds)

    # -- correct: what the timed path produced, against the reference ------
    t0 = time.perf_counter()
    check = operation.reference_check(state, last)
    say(phase="reference_check", seconds=time.perf_counter() - t0,
        **{k: v for k, v in check.items() if k != "compared"})
    say(correct=check["correct"], compared=check["compared"])
    return dict(correct=check["correct"], attempted=attempted, failed=failed,
                metrics=metrics, units={m["name"]: m["unit"] for m in group},
                device=device, breakdown=breakdown,
                compared=check["compared"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    # Only the result line may reach stdout: whatever any layer (a log
    # handler, a C++ runtime, an exit hook) writes to file descriptor 1
    # goes to stderr, and the real stdout is kept aside.
    sys.stdout.flush()
    result_out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)

    cell = manifests.resolve(manifests.load_manifest(), args.workload)
    chips = cell["cell"]["chips"]

    import jax

    devices = jax.devices()
    say(phase="device", platform=devices[0].platform,
        device_kind=devices[0].device_kind, count=len(devices),
        jax=jax.__version__)
    if devices[0].platform != "tpu":
        say(error="no TPU: the benchmark measures the chip and does not "
                  "fall back")
        return 2
    if len(devices) < chips:
        say(error=f"{args.workload} needs {chips} chips, this machine has "
                  f"{len(devices)}")
        return 2
    peaks(devices[0].device_kind)  # an unlisted device is an error

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    if result is None:
        return 1
    result_out.write(result_line(**result) + "\n")
    result_out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
