"""The seismic sweep's per-layer readers on a synthetic window, and the
leapfrog's counted work against shapes worked by hand."""

import pytest

from bench import layers, work, work_seismic
from bench import trace as tr

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the Marmousi grid with its 50-cell pad on every side
POINTS = 2401 * 851


def sweep_event(ts, shots=8, steps=5800, points=POINTS):
    return {"ph": "i", "name": "seismic.sweep", "ts": ts, "dur": 0,
            "depth": 0, "tid": 1,
            "attrs": {"shots": shots, "steps": steps, "grid_points": points,
                      "record_samples": steps // 8,
                      "observed_bytes": shots * 725 * 96 * 4}}


def window(spans, modules, campaigns=2):
    trace = tr.Trace(
        ops={0: [(s, e, "fusion") for s, e, _ in modules]},
        modules={0: modules},
        annotations=[(0, 100_000_000_000, "bench.window")])
    return layers.Window(config={}, traffic={}, setup_s=0, seconds=1,
                         campaigns=campaigns, rounds=campaigns, compiles=0,
                         spans=spans, trace=trace, devices=[0], peak=V5E)


def read(name, w):
    return layers.read("per_layer", name, w)


# two campaigns, each one sweep program of 12 s, and a 1 s program that is
# not the sweep's
MODULES = [(1_000_000_000, 13_000_000_000, "jit_seg(17)"),
           (14_000_000_000, 15_000_000_000, "jit_records(3)"),
           (20_000_000_000, 32_000_000_000, "jit_seg(17)")]
SPANS = [sweep_event(500_000_000), sweep_event(19_000_000_000),
         {"ph": "X", "name": "carrier.dispatch", "ts": 0, "dur": 5,
          "depth": 0, "tid": 2, "attrs": {}}]


def test_leapfrog_counts_by_hand():
    # 8 shots, one step on 10 points: 8 × 10 × 10 flops; 4 B × 10 points
    # × (8 shots × 3 wavefields + 2 coefficient fields)
    assert work_seismic.leapfrog(8, 1, 10) == (800, 4 * 10 * 26)
    flops, nbytes = work_seismic.leapfrog(8, 5800, POINTS)
    assert nbytes == 4 * POINTS * 5800 * 26
    # the stencil is byte-bound: 1.505 s of HBM traffic a campaign
    least = work.least_seconds(flops, nbytes, V5E)
    assert least == pytest.approx(nbytes / 819e9)
    assert least == pytest.approx(1.50487, rel=1e-5)


def test_forward_ms_per_shot_reads_the_sweep_programs_over_the_shots():
    w = window(SPANS, MODULES)
    assert read("seismic_forward_ms_per_shot", w) == pytest.approx(
        24_000 / 16)


def test_stencil_roofline_is_least_time_over_sweep_device_time():
    w = window(SPANS, MODULES)
    least = 2 * 4 * POINTS * 5800 * 26 / 819e9
    assert read("seismic_stencil_roofline", w) == pytest.approx(
        100 * least / 24.0)
    assert read("seismic_stencil_roofline", w) < 100


@pytest.mark.parametrize("name", ["seismic_forward_ms_per_shot",
                                  "seismic_stencil_roofline"])
def test_nothing_read_without_the_events_or_the_programs(name):
    # the parent's program emits no seismic.sweep event
    assert read(name, window(SPANS[2:], MODULES)) is None
    # an AnEn window: no sweep program ran
    assert read(name, window(SPANS, [(0, 5, "jit_idw_interpolate(2)")])) \
        is None
    # no trace at all (an untraced run)
    untraced = layers.Window(config={}, traffic={}, setup_s=0, seconds=1,
                             campaigns=1, rounds=1, compiles=0, spans=SPANS,
                             peak=V5E)
    assert read(name, untraced) is None
