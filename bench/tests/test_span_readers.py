"""The readers of the program-span metrics of the api hooks and the fusion
engine, on synthetic span lists: self time less direct children, per round
and per carrier, and nothing read where the span is absent."""

import pytest

from bench import layers


def span(name, ts, dur, depth, tid=1, **attrs):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "depth": depth,
            "tid": tid, "attrs": attrs}


def window(spans, rounds=4):
    return layers.Window(config={}, traffic={}, setup_s=0, seconds=1,
                         campaigns=1, rounds=rounds, compiles=0, spans=spans)


def read(name, w):
    return layers.read("per_layer", name, w)


HOOKS = [
    # round 0's body at compile time, on the caller's thread
    span("api.loop.body", 0, 3_000_000, 0, tid=7, loop="s", round=0),
    # later rounds inside the dequeue batch, whose own self time is apart
    span("wfp.dequeue_batch", 10_000_000, 9_000_000, 0),
    span("api.loop.predicate", 10_500_000, 2_000_000, 1, loop="s", round=0),
    span("api.loop.body", 12_600_000, 5_000_000, 1, loop="s", round=1),
    # a child of the body (one level deeper) leaves its self time
    span("fusion.prepare", 13_000_000, 1_000_000, 2),
    span("wfp.dequeue_batch", 30_000_000, 3_000_000, 0),
    span("api.loop.predicate", 30_100_000, 2_000_000, 1, loop="s", round=1),
]


def test_loop_hooks_read_self_time_per_round():
    w = window(HOOKS, rounds=4)
    assert read("loop_predicate_ms_per_round", w) == pytest.approx(1.0)
    assert read("loop_body_ms_per_round", w) == pytest.approx(
        (3.0 + 5.0 - 1.0) / 4)
    # the hooks are direct children: the control plane no longer holds them
    assert layers.self_ns(w, ["wfp.dequeue_batch"]) == (
        9_000_000 - 2_000_000 - 5_000_000 + 3_000_000 - 2_000_000)


def test_carrier_prepare_is_self_time_per_carrier():
    spans = [span("carrier.dispatch", 0, 10_000_000, 0, tid=3),
             span("fusion.prepare", 100, 4_000_000, 1, tid=3,
                  placed_bytes=3 << 20, rows=128),
             span("carrier.dispatch", 20_000_000, 10_000_000, 0, tid=4),
             span("fusion.prepare", 20_000_100, 6_000_000, 1, tid=4,
                  placed_bytes=1 << 20, rows=128),
             span("inner", 20_001_000, 2_000_000, 2, tid=4)]
    w = window(spans)
    assert read("carrier_prepare_ms", w) == pytest.approx((4 + 6 - 2) / 2)
    assert read("carrier_placed_mib", w) == pytest.approx(2.0)


@pytest.mark.parametrize("name", ["loop_predicate_ms_per_round",
                                  "loop_body_ms_per_round",
                                  "carrier_prepare_ms",
                                  "carrier_placed_mib"])
def test_nothing_read_without_the_span(name):
    parent = [span("wfp.dequeue_batch", 0, 9_000_000, 0),
              span("carrier.dispatch", 0, 1_000_000, 0, tid=2)]
    assert read(name, window(parent)) is None
    assert read(name, window([])) is None


def test_per_round_readers_need_rounds():
    assert read("loop_predicate_ms_per_round", window(HOOKS, rounds=0)) \
        is None
    assert read("loop_body_ms_per_round", window(HOOKS, rounds=0)) is None
