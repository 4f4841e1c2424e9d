"""The seismic sweep through ``api.run`` against the plain reference, at
the configuration's rehearsal sizes on the CPU, and the reference's own
parts against plain forms of the configuration's definitions."""

import math

import numpy as np
import pytest

from bench.reference import seismic as ref
from bench.run import load_cell

CELL = "seismic_sweep.1chip"


def _rehearsal():
    run = load_cell(CELL)
    run["config"].update(run["config"]["rehearsal"])
    return run


@pytest.mark.parametrize("seed", [7, 2_147_483_659])
def test_the_sweep_agrees_with_the_reference_and_is_not_vacuous(seed):
    import jax
    from bench.apps import seismic as app

    run = _rehearsal()
    campaigns = app.Campaigns(run["config"], run["traffic"], seed,
                              jax.devices()[:1])
    out = campaigns.run()
    numbers = campaigns.check([out])
    expected = campaigns.reference(np.float32)
    campaigns.release()
    assert out["faults"] == 0
    assert all(numbers[k] <= app.LIMITS[k] for k in app.LIMITS), numbers
    assert len(out["misfits"]) == run["config"]["shots_per_campaign"]
    # every shot's misfit is at least 1 % of its recorded energy: a
    # program returning 0, or the true model's misfit, cannot pass
    assert np.all(expected["misfits"] >= 0.01 * expected["energies"])
    assert out["total"] == pytest.approx(expected["total"], rel=1e-3)


def test_campaign_shots_are_every_stride_th_from_the_seed():
    from bench.apps.seismic import campaign_shots

    config = load_cell(CELL)["config"]
    assert 2_147_483_659 % 30 == 19
    assert campaign_shots(config, 2_147_483_659) == [
        19 + 30 * k for k in range(8)]
    assert campaign_shots(config, 30) == [30 * k for k in range(8)]


def test_positions_round_halves_up_and_move_with_the_shot():
    c = load_cell(CELL)["config"]
    source, receivers = ref.positions(c, 0)
    assert source == 750 and receivers[0] == 700 and receivers[-1] == 106
    # 3025 m / 4 m = 756.25; 3050 m / 4 m = 762.5, rounded up
    assert ref.positions(c, 1)[0] == 756 and ref.positions(c, 2)[0] == 763
    assert np.array_equal(ref.positions(c, 5)[1][1:],
                          ref.positions(c, 4)[1][:-1])


def test_smoothing_is_the_plain_gaussian_sum():
    rng = np.random.default_rng(3)
    model = rng.uniform(1500.0, 5500.0, (9, 13))
    model[0] = ref.WATER_VELOCITY
    dx = 125.0                      # σ = 2 cells, cut at 6
    got = ref.smooth(model, dx)
    offsets = np.arange(-6, 7)
    w = np.exp(-0.5 * (offsets / 2.0) ** 2)
    w /= w.sum()
    want = np.zeros_like(model)
    for z in range(9):
        for x in range(13):
            want[z, x] = sum(
                w[i] * w[j] * model[min(max(z + a, 0), 8),
                                    min(max(x + b, 0), 12)]
                for i, a in enumerate(offsets) for j, b in enumerate(offsets))
    want[0] = ref.WATER_VELOCITY
    np.testing.assert_allclose(got, want, rtol=1e-12)


def test_damping_is_zero_inside_and_strongest_at_the_edge():
    c = dict(load_cell(CELL)["config"], nz=20, nx=30, pad=4,
             damp_strength=0.1)
    d = ref.damping(c)
    assert d.shape == (28, 38)
    assert d[0, 10] == pytest.approx(0.1) and d[3, 10] == pytest.approx(
        0.1 / 16)
    assert np.all(d[4:-4, 4:-4] == 0.0)


def test_the_wavelet_peaks_at_its_delay():
    c = load_cell(CELL)["config"]
    w = ref.ricker(c)
    assert len(w) == c["nt"]
    assert int(np.argmax(w)) == round(1.2 / c["f0"] / c["dt"])
    assert w.max() == pytest.approx(1.0, abs=1e-6)
    # 0.1 s before its peak the 12 Hz wavelet has all but died out
    assert math.isclose(w[0], 0.0, abs_tol=1e-4)
