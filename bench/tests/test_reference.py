"""The AnEn reference's own parts against plain, slow forms of the same
definitions, on small grids."""

import numpy as np
import pytest

from bench.reference import anen as ref


def _greedy_whole_grid(est, points, n):
    """Greedy placement recomputing every cell after each pick."""
    e = np.asarray(est, np.float32)
    ny, nx = e.shape
    grad = (np.abs(np.roll(e, -1, 0) - e)
            + np.abs(np.roll(e, -1, 1) - e)).astype(np.float64)
    grad = grad + 0.5 * (np.roll(grad, 1, 0) + np.roll(grad, -1, 0)
                         + np.roll(grad, 1, 1) + np.roll(grad, -1, 1))
    yy, xx = np.mgrid[0:ny, 0:nx]
    d2 = np.full((ny, nx), np.inf)
    for py, px in points:
        d2 = np.minimum(d2, (yy - py) ** 2 + (xx - px) ** 2)
    picks = []
    for _ in range(n):
        py, px = divmod(int(np.argmax(grad * d2)), nx)
        picks.append([py, px])
        d2 = np.minimum(d2, (yy - py) ** 2 + (xx - px) ** 2)
    return picks


def _points(rng, ny, nx, n):
    flat = rng.choice(ny * nx, size=n, replace=False)
    return np.stack([flat // nx, flat % nx], axis=1)


@pytest.mark.parametrize("seed,ny,nx,samples,picks", [
    (0, 24, 32, 20, 60), (1, 37, 53, 5, 120), (2, 64, 48, 200, 40)])
def test_greedy_picks_match_the_whole_grid_form(seed, ny, nx, samples,
                                                picks):
    rng = np.random.default_rng(seed)
    est = rng.standard_normal((ny, nx)).astype(np.float32)
    points = _points(rng, ny, nx, samples)
    assert ref.greedy_picks(est, points, picks) == _greedy_whole_grid(
        est, points, picks)


@pytest.mark.parametrize("ny,nx,samples", [(17, 29, 1), (40, 40, 30)])
def test_nearest_d2_is_the_exact_squared_distance(ny, nx, samples):
    points = _points(np.random.default_rng(ny), ny, nx, samples)
    yy, xx = np.mgrid[0:ny, 0:nx]
    brute = np.min([(yy - py) ** 2 + (xx - px) ** 2 for py, px in points],
                   axis=0)
    assert np.array_equal(ref.nearest_d2(points, ny, nx), brute)


def test_random_rounds_draw_distinct_untaken_cells_from_the_seed():
    kw = dict(method="random", seed=2_147_483_659, ny=12, nx=10,
              per_iter=30, rounds=3, idw={}, dtype=None)
    placed = np.zeros((0, 2), np.int64)
    for r in range(3):      # each round given the rounds before it
        due = ref.placements(placed, [], **dict(kw, rounds=r + 1))[r]
        placed = np.concatenate([placed, due])
    again = ref.placements(placed, [], **kw)
    assert np.array_equal(np.concatenate(again), placed)
    assert len({tuple(p) for p in placed.tolist()}) == 90
    assert placed[:, 0].max() < 12 and placed[:, 1].max() < 10


def test_the_threaded_dataset_is_the_days_one_by_one():
    data = ref.dataset(5, 20, 28, 6, workers=3)
    days = ref.Days(5, 20, 28)
    for t in (0, 5):
        obs = days.observation(t)
        assert np.array_equal(data["hist_obs"][t], obs.astype(np.float32))
        assert np.array_equal(data["hist_forecast"][t],
                              days.forecasts(t, obs).astype(np.float32))
    assert np.array_equal(data["truth"],
                          days.observation(6 + ref.NOW_OFFSET)
                          .astype(np.float32))
