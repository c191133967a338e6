"""The program's compile ledger, split at the traced fit.

``photon_ml_tpu.telemetry.compile_ledger()`` holds, for the whole
process, what every (fit, stage) traced, lowered, loaded from the
persistent cache and compiled (``fit``: the ``estimator_fit`` that was
open, 0 outside any).  The traced fit's number is the ``fit`` count of
its ``photon/estimator_fit`` event; rows with a lower number are what
ran before the window (the warm-up fit, and whatever ran outside a fit
until the metrics are read), the rows with that number are the traced
fit's own.  This reads the process's ledger and not the trace: the
trace only says which fit was traced.  Nothing to read (None) without a
trace, without the stage, or in a program that keeps no ledger.
"""

from benchmark.harness import host_spans


def traced_fit(ctx):
    """The ``fit`` count of the traced operation's first
    ``photon/estimator_fit``, or None."""
    found = host_spans.stages(ctx)
    events = host_spans.named(found["thread"], "estimator_fit") \
        if found else []
    numbers = [found["counts"][e]["fit"] for e in sorted(events)
               if "fit" in found["counts"][e]]
    return int(numbers[0]) if numbers else None


def ledger():
    """The program's (fit, stage) rows, or None where it keeps none."""
    from photon_ml_tpu import telemetry

    read = getattr(telemetry, "compile_ledger", None)
    return None if read is None else read()


def _total(ctx, columns, wanted):
    fit, rows = traced_fit(ctx), ledger()
    if fit is None or rows is None:
        return None
    return float(sum(row[column] for (row_fit, _stage), row in rows.items()
                     if wanted(row_fit, fit) for column in columns))


def before_window(ctx, *columns):
    """``columns`` summed over the rows of every fit before the traced
    one, fit 0 among them."""
    return _total(ctx, columns, lambda row_fit, fit: row_fit < fit)


def traced_window(ctx, *columns):
    """``columns`` summed over the traced fit's rows."""
    return _total(ctx, columns, lambda row_fit, fit: row_fit == fit)
