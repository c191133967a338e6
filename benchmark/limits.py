"""The readings that a cell's limits of ``correct`` are set from: many
seeds of one cell in ONE process, each fitted once and held against the
plain reference, then the operation's controls (the fits that must not
be ``correct``) on some of them.

    python3 benchmark/limits.py --workload <name> --seeds 1,2,3 \
        [--control <name> ...] [--control-seeds 1,2] [--out <file>]

One JSON line a fit on stdout (and in ``--out``): the seed, the control
if any, the operation's seconds (the first of a kind compiles), and
``reference_check``'s verdicts with every number compared beside its
limit, and the device the fit ran on (``platform``, ``device_kind``):
a file of readings says itself where they are from.  With ``--damaged``
every sound fit is followed by a line for each of the operation's
damaged results (``control`` says ``damaged: <what>``; no fit is made
for it).  A control patches the fitting process and clears the jit
caches, so the sound seeds all come first.  It is no measurement of
speed: a ``run.py`` run costs three minutes of set-up a seed, this a
fit and its check.  A cell's limits are about the chip: without a TPU
it exits 2 with no record, as ``run.py`` does, unless ``--rehearsal``
takes the configuration at its rehearsal size (the CPU's; the records
then say ``rehearsal: true`` beside the device).
"""

import argparse
import contextlib
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark.harness import manifest as manifests  # noqa: E402


class NoChip(RuntimeError):
    """No TPU, and the cell's own size was asked for."""


def readings(cell, seeds, controls, control_seeds, rehearsal=False,
             damaged=False):
    """Yields one record a fit: ``seeds`` as the configuration states
    them (each followed, with ``damaged``, by one record a damaged
    result of it), then every control of ``controls`` on
    ``control_seeds``."""
    import jax
    from photon_ml_tpu.cache import enable_compilation_cache

    device = jax.devices()[0]
    if device.platform != "tpu" and not rehearsal:
        raise NoChip(f"{device.platform}: a cell's limits are read on the "
                     "chip (--rehearsal for the rehearsal size here)")
    enable_compilation_cache()
    generator = manifests.load_module(cell["generator_path"])
    operation = manifests.load_module(cell["operation_path"])
    config = (operation.rehearsal_config(cell["config"]) if rehearsal
              else cell["config"])

    def record(seed, control, seconds, outcome, check):
        return {"cell": cell["cell"]["name"], "seed": seed,
                "control": control, "seconds": seconds,
                "platform": device.platform,
                "device_kind": device.device_kind, "rehearsal": rehearsal,
                **operation.summary(outcome), "correct": check["correct"],
                "conditions": check["conditions"],
                "compared": check["compared"]}

    def fit(seed, control):
        data = generator.make(seed, **config["generator"]["params"])
        state = operation.prepare(config, cell["traffic"], data)
        with (operation.control(control, state) if control
              else contextlib.nullcontext(state)) as state:
            t0 = time.perf_counter()
            outcome = operation.one(state)
            seconds = time.perf_counter() - t0
            yield record(seed, control, seconds, outcome,
                         operation.reference_check(state, outcome))
        if damaged and not control:
            for what, bad, _failing in operation.damaged(state, outcome):
                yield record(seed, "damaged: " + what, 0.0, bad,
                             operation.reference_check(state, bad))

    for seed in seeds:
        yield from fit(seed, None)
    for control in controls:
        for seed in control_seeds:
            yield from fit(seed, control)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", action="append", default=[])
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--damaged", action="store_true")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    def numbers(text):
        return [int(s) for s in text.split(",") if s]

    cell = manifests.resolve(manifests.load_manifest(), args.workload)
    out = None
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        out = open(args.out, "a")
    try:
        for record in readings(cell, numbers(args.seeds), args.control,
                               numbers(args.control_seeds), args.rehearsal,
                               args.damaged):
            line = json.dumps(record)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    except NoChip as refusal:
        print(f"limits.py: {refusal}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
