"""Runtime layer: mean duration of JaxRTS's ``carrier.dispatch`` spans
(planning, stacking and placing a carrier's inputs, and enqueueing its
program), in ms."""

from bench.layers import span_durations_ns


def read(window):
    durations = span_durations_ns(window, "carrier.dispatch")
    if not durations:
        return None
    return sum(durations) / len(durations) / 1e6
