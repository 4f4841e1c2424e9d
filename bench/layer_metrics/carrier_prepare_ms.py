"""Fusion engine: mean self time of a carrier's input preparation (the
program's ``fusion.prepare`` span: members' kwargs resolved, stacked and
placed on the carrier's devices), in ms."""

from bench.layers import self_ns, span_durations_ns


def read(window):
    carriers = len(span_durations_ns(window, "fusion.prepare"))
    if not carriers:
        return None
    return self_ns(window, ("fusion.prepare",)) / 1e6 / carriers
