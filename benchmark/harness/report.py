"""What a run says: JSON lines on stderr while it works, and the
contract's one line on stdout when it is done."""

import json
import sys


def say(**record):
    print(json.dumps(record), file=sys.stderr, flush=True)


class CompileClock:
    """Seconds JAX spent in backend compilation (a persistent-cache hit
    counts only its retrieval), summed from JAX's own duration events
    while the clock is registered, and the number of such events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def result_line(*, correct, attempted, failed, metrics, units, device,
                compared, breakdown=None):
    """The contract's result line.  ``metrics`` is name -> number and
    ``units`` name -> unit (from the manifest); ``device`` already has
    the contract's keys; ``breakdown`` is given only by a traced run;
    ``compared`` is every number ``correct`` rests on beside its limit,
    by short name, and comes last."""
    record = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {name: {"value": value, "unit": units[name]}
                    for name, value in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        record["breakdown"] = breakdown
    record["compared"] = compared
    return json.dumps(record)
