"""Device: the share of the measured window in which no operation ran,
averaged over the chips the run used (profiler trace), in %."""

from bench.layers import busy_ns


def read(window):
    busy = busy_ns(window)
    if busy is None:
        return None
    lo, hi = window.bounds
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
