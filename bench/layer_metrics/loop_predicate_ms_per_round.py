"""API hooks: self time of the adaptive loops' predicates (the program's
``api.loop.predicate`` spans, which the WFProcessor's dequeue batch runs)
in ms per campaign round."""

from bench.layers import self_ns


def read(window):
    ns = self_ns(window, ("api.loop.predicate",))
    if ns is None or not window.rounds:
        return None
    return ns / 1e6 / window.rounds
