"""The reduction from a profiler trace to device intervals and metrics.

The synthetic cases pin the interval arithmetic; the recorded case reduces
``data/small.xplane.pb``, a trace recorded on a TPU v5e by
``record_trace.py``: a window of two campaigns, each one jitted program
and one Pallas AnEn distance call with host sleeps in between.
"""

from pathlib import Path

import pytest

from bench import layers
from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_union_merges_overlaps_and_clips_to_the_window():
    spans = [(5, 10), (0, 3), (8, 12), (20, 30), (2, 4)]
    assert tr.union(spans, 1, 25) == [(1, 4), (5, 12), (20, 25)]
    assert tr.union([], 0, 10) == []
    assert tr.union([(0, 5)], 5, 10) == []


def test_gaps_are_the_complement_of_busy_time():
    busy = [(1, 4), (5, 12), (20, 25)]
    assert tr.gaps(busy, 0, 30) == [(0, 1), (4, 5), (12, 20), (25, 30)]
    assert tr.gaps([], 0, 7) == [(0, 7)]


def test_time_ns_sums_matching_events_inside_the_window():
    events = [(0, 10, "anen_distance.1"), (10, 30, "fusion.2"),
              (40, 60, "anen_distance.1")]
    match = lambda n: n.startswith("anen_distance")  # noqa: E731
    assert tr.time_ns(events, match, 0, 100) == 30
    assert tr.time_ns(events, match, 5, 50) == 15


def test_enclosing_lists_outermost_first():
    events = [(0, 100, "bench.window"), (10, 50, "bench.campaign"),
              (60, 90, "bench.campaign")]
    assert tr.enclosing(events, 20) == ["bench.window", "bench.campaign"]
    assert tr.enclosing(events, 55) == ["bench.window"]
    assert tr.enclosing(events, 100) == []


def test_self_time_subtracts_direct_children_only():
    def span(name, ts, dur, depth, tid=1):
        return {"ph": "X", "name": name, "ts": ts, "dur": dur,
                "depth": depth, "tid": tid}

    window = layers.Window(config={}, traffic={}, setup_s=0, seconds=1,
                           campaigns=1, rounds=2, compiles=0,
                           spans=[span("emgr.submit", 0, 100, 0),
                                  span("carrier.dispatch", 10, 30, 1),
                                  span("carrier.drain", 15, 5, 2),
                                  span("emgr.submit", 0, 40, 0, tid=2)])
    assert layers.self_ns(window, ["emgr.submit"]) == 70 + 40
    assert layers.self_ns(window, ["wfp.enqueue_batch"]) is None
    assert layers.span_durations_ns(window, "carrier.dispatch") == [30]


@pytest.fixture(scope="module")
def recorded():
    trace = tr.load(str(DATA))
    config = {"n_hist": 365, "n_vars": 3, "max_iters": 1, "per_iter": 1024}
    return layers.Window(config=config, traffic={}, setup_s=0.0,
                         seconds=0.3, campaigns=2, rounds=2,
                         compiles=0, trace=trace, devices=[0],
                         peak={"flops_per_s": 197e12,
                               "hbm_bytes_per_s": 819e9})


def test_recorded_trace_has_the_device_and_the_annotations(recorded):
    trace = recorded.trace
    assert trace.devices == [0]
    assert [e[2] for e in trace.annotations] == [
        "bench.window", "bench.campaign", "bench.campaign"]
    ops = [e[2] for e in trace.ops[0]]
    assert ops.count("anen_distance.1") == 2       # the Pallas custom call
    assert all(" = " not in name for name in ops)
    assert [m[2].split("(")[0] for m in trace.modules[0]] == [
        "jit__lambda", "jit_anen_distance"] * 2
    lo, hi = recorded.bounds
    assert hi - lo == 305_799_955                  # two 50 ms + two 100 ms sleeps


def test_recorded_busy_time_and_idle_share(recorded):
    assert layers.busy_ns(recorded) == [67_819]
    idle = layers.read("per_layer", "device_idle_share.adaptive", recorded)
    assert idle == pytest.approx(100 * (1 - 67_819 / 305_799_955))


def test_recorded_kernel_roofline(recorded):
    # two calls at (H, V, N) = (365, 3, 1024): 5,992,448 B each at
    # 819 GB/s is 7.317 µs. The kernel ops took 16,802 ns, reading what
    # the copy and pad before them staged on chip; the two programs that
    # ran them took 25,076 + 25,073 ns
    kernel = lambda n: n.startswith("anen_distance")  # noqa: E731
    assert layers.op_ns(recorded, kernel) == 16_802
    assert layers.program_ns(recorded, kernel) == 50_149
    share = layers.read("per_layer", "anen_distance_roofline", recorded)
    assert share == pytest.approx(100 * 2 * 5_992_448 / 819e9 / 50_149e-9)
    assert share < 100


def test_recorded_gaps_are_labelled_by_the_benchmarks_annotations(recorded):
    found = dict(layers.breakdown(recorded, mono_at_lo=0)["idle_gaps"])
    # the host slept 100 ms after each campaign and 50 ms inside each
    assert found["between campaigns"] == pytest.approx(0.2035, abs=0.003)
    assert found["campaign, outside program spans"] == pytest.approx(
        0.1022, abs=0.003)
    ops = dict(layers.breakdown(recorded, mono_at_lo=0)["device_ops"])
    assert ops["jit_anen_distance/anen_distance.1"] == pytest.approx(
        16_802e-9)
