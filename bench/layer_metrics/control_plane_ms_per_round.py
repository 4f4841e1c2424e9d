"""Core layer: self time of the WFProcessor's enqueue and dequeue batches
and of Emgr's submits (program spans), in ms per campaign round."""

from bench.layers import self_ns

NAMES = ("wfp.enqueue_batch", "wfp.dequeue_batch", "emgr.submit")


def read(window):
    ns = self_ns(window, NAMES)
    if ns is None or not window.rounds:
        return None
    return ns / 1e6 / window.rounds
