"""GB of random-effect blocks (features, labels, weights, masks and
index maps) handed to the device: ``bytes`` summed over the traced
fit's ``photon/place_re`` stages."""

from benchmark.harness import host_spans


def read(ctx):
    found = host_spans.stages(ctx)
    events = host_spans.named(found["thread"], "place_re") if found else []
    if not events:
        return None
    return sum(found["counts"][e]["bytes"] for e in events) / 1e9
