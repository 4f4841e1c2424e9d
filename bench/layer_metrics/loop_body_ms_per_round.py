"""API hooks: self time of the adaptive loops' round builders (the
program's ``api.loop.body`` spans: round 0 at compile time, later rounds in
the WFProcessor's dequeue batch) in ms per campaign round."""

from bench.layers import self_ns


def read(window):
    ns = self_ns(window, ("api.loop.body",))
    if ns is None or not window.rounds:
        return None
    return ns / 1e6 / window.rounds
