"""Plain reference of the AnEn campaign (paper §III-B).

Independent of the package under test: it imports nothing of it and reads
nothing that it built. Four parts:

* the synthetic NAM-like data, day by day from the seed, generated here in
  full for every check;
* the analog search: for each location the ``k`` historical forecasts most
  similar to the current one (squared distance summed over the variables),
  and the mean of their verified observations;
* the k-nearest inverse-distance interpolation of the analog values onto
  the whole grid, and its RMSE against the verification field;
* the placement of each round: uniform draws from the seed's stream, and
  for AUA rounds after the first a quarter drawn so and the rest picked
  greedily by gradient × squared distance to the nearest sample.

Device work runs in ``dtype`` (float32 at ``highest`` matmul precision for
the reference; bfloat16 for the control) and in blocks of grid points, so
the (grid × locations) distances never exist whole.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np


# --------------------------------------------------------------------------
# the data, from the seed


def _smooth_noise(rng, shape, scale: int) -> np.ndarray:
    """Upsampled coarse white noise, box-blurred twice (periodic)."""
    coarse = rng.standard_normal((shape[0] // scale + 2,
                                  shape[1] // scale + 2))
    out = np.kron(coarse, np.ones((scale, scale)))[:shape[0], :shape[1]]
    for _ in range(2):
        out = 0.25 * (np.roll(out, 1, 0) + np.roll(out, -1, 0)
                      + np.roll(out, 1, 1) + np.roll(out, -1, 1))
    return out


def _truth(ny: int, nx: int) -> np.ndarray:
    """Smooth waves plus two sharp fronts (the field AUA refines)."""
    yy, xx = np.mgrid[0:ny, 0:nx] / max(ny, nx)
    base = (np.sin(2.5 * np.pi * xx) * np.cos(1.5 * np.pi * yy)
            + 0.5 * np.sin(4 * np.pi * (xx + yy)))
    front = (np.tanh(18 * (yy - 0.45 - 0.18 * np.sin(3 * np.pi * xx)))
             + 0.7 * np.tanh(24 * (xx - 0.7 + 0.1 * np.cos(2 * np.pi * yy))))
    return 0.35 * base + 2.2 * front


class Days:
    """Any one day's observation and forecasts, without the others."""

    def __init__(self, seed: int, ny: int, nx: int) -> None:
        self.seed, self.ny, self.nx = seed, ny, nx
        self.truth = _truth(ny, nx)
        # the stationary forecast bias: the first draw of the seed's stream
        self.bias = _smooth_noise(np.random.default_rng(seed), (ny, nx),
                                  16) * 0.5

    def observation(self, t: int) -> np.ndarray:
        season = 0.6 * np.sin(2 * np.pi * t / 73.0)
        wobble = _smooth_noise(np.random.default_rng(self.seed + 100 + t),
                               (self.ny, self.nx), 8) * 0.35
        return self.truth + season + wobble

    def forecasts(self, t: int, obs: np.ndarray) -> np.ndarray:
        """The three predictor variables of day ``t`` from its observation
        ``obs``, (3, ny, nx)."""
        shape = (self.ny, self.nx)
        r = np.random.default_rng(self.seed + 500 + t)
        f0 = obs + (self.bias + _smooth_noise(r, shape, 8) * 0.3)
        f1 = 0.8 * obs + 0.3 + _smooth_noise(r, shape, 8) * 0.25
        f2 = np.roll(obs, 2, axis=1) + _smooth_noise(r, shape, 8) * 0.3
        return np.stack([f0, f1, f2])


# the verification day lies 13 days past the history
NOW_OFFSET = 13


def dataset(seed: int, ny: int, nx: int, n_hist: int,
            workers: int = 8) -> Dict[str, np.ndarray]:
    """The whole campaign's data in float32 on the host: the history of
    ``n_hist`` days, the verification day's forecasts and its observation
    (``truth``). Days are independent, so threads build them side by side."""
    days = Days(seed, ny, nx)
    hist_obs = np.empty((n_hist, ny, nx), np.float32)
    hist_forecast = np.empty((n_hist, 3, ny, nx), np.float32)

    def fill(t: int) -> None:
        obs = days.observation(t)
        hist_obs[t] = obs
        hist_forecast[t] = days.forecasts(t, obs)

    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(fill, range(n_hist)))
    now = n_hist + NOW_OFFSET
    obs_now = days.observation(now)
    return {"truth": obs_now.astype(np.float32),
            "forecast_now": days.forecasts(now, obs_now).astype(np.float32),
            "hist_forecast": hist_forecast, "hist_obs": hist_obs}


# --------------------------------------------------------------------------
# the analog search and the interpolation


# Two distances count as tied when they differ by less than this share of
# the k-th smallest: a few float32 ulps of a sum of three squares, which
# two correct implementations may round differently.
TIE_RTOL = 1e-6


def analog_values(hist_forecast, hist_obs, forecast_now, locations,
                  k: int, dtype):
    """Mean verified observation of the ``k`` most similar historical
    forecasts at each (y, x) in ``locations`` (n, 2).

    Returns the means (float32) and, per location, the lowest and highest
    mean that any resolution of a tie at the k-th distance gives (float64;
    both equal the mean where the k-th distance has no tie).
    """
    import jax
    import jax.numpy as jnp

    locs = np.asarray(locations, np.int32)
    ys, xs = locs[:, 0], locs[:, 1]
    with jax.default_matmul_precision("highest"):
        f_hist = jnp.asarray(hist_forecast[:, :, ys, xs]).astype(dtype)
        f_now = jnp.asarray(forecast_now[:, ys, xs]).astype(dtype)
        obs = jnp.asarray(hist_obs[:, ys, xs]).astype(dtype)    # (H, n)
        d2 = jnp.sum((f_hist - f_now[None]) ** 2, axis=1)       # (H, n)
        _, idx = jax.lax.top_k(-d2.T, k)                         # (n, k)
        mean = jnp.take_along_axis(obs.T, idx, axis=1).mean(axis=1)
    lo, hi = _tie_bounds(np.asarray(d2.astype(jnp.float32)).T,
                         np.asarray(obs.astype(jnp.float32)).T, k)
    return np.asarray(mean.astype(jnp.float32)), lo, hi


def _tie_bounds(d2: np.ndarray, obs: np.ndarray, k: int):
    """For (n, H) distances and observations: the range of the mean of
    ``k`` observations over every choice of analogs that is nearest up to
    a tie at the k-th distance."""
    order = np.argsort(d2, axis=1, kind="stable")
    d = np.take_along_axis(d2, order, axis=1).astype(np.float64)
    o = np.take_along_axis(obs, order, axis=1).astype(np.float64)
    dk = d[:, k - 1:k]
    tol = TIE_RTOL * np.abs(dk)
    sure = d < dk - tol                        # nearer than any tie
    tied = np.abs(d - dk) <= tol
    free = k - sure.sum(axis=1)                # analogs the tie supplies
    sure_sum = np.where(sure, o, 0.0).sum(axis=1)
    low = np.sort(np.where(tied, o, np.inf), axis=1)
    high = -np.sort(np.where(tied, -o, np.inf), axis=1)
    rows = np.arange(len(d))
    pick = np.cumsum(np.where(np.isfinite(low), low, 0.0), axis=1)
    pick_hi = np.cumsum(np.where(np.isfinite(high), high, 0.0), axis=1)
    lo = (sure_sum + pick[rows, free - 1]) / k
    hi = (sure_sum + pick_hi[rows, free - 1]) / k
    return lo, hi


def idw_field(locations, values, ny: int, nx: int, *, k_nearest: int,
              power: float, eps: float, dtype,
              block: int = 8192) -> np.ndarray:
    """The k-nearest inverse-distance interpolation of ``values`` at
    ``locations`` over the whole (ny, nx) grid, float32."""
    import jax
    import jax.numpy as jnp

    block = min(block, ny * nx)
    locs = np.asarray(locations, np.float32)
    ly = jnp.asarray(locs[:, 0]).astype(dtype)
    lx = jnp.asarray(locs[:, 1]).astype(dtype)
    vals = jnp.asarray(np.asarray(values, np.float32)).astype(dtype)
    k = min(k_nearest, len(locs))

    @jax.jit
    def estimate(lo, ly, lx, vals):
        # grid points lo .. lo + block, row-major (the last block runs past
        # the grid; its tail is cut off below)
        flat = lo + jnp.arange(block)
        gy = (flat // nx).astype(dtype)
        gx = (flat % nx).astype(dtype)
        d2 = (gy[:, None] - ly[None]) ** 2 + (gx[:, None] - lx[None]) ** 2
        neg, idx = jax.lax.top_k(-d2, k)
        w = 1.0 / ((-neg) ** (power / 2) + eps)
        est = (w * vals[idx]).sum(axis=1) / w.sum(axis=1)
        return est.astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        parts = [np.asarray(estimate(jnp.int32(lo), ly, lx, vals))
                 for lo in range(0, ny * nx, block)]
    return np.concatenate(parts)[:ny * nx].reshape(ny, nx)


def rmse(field: np.ndarray, truth: np.ndarray) -> float:
    err = np.asarray(field, np.float64) - np.asarray(truth, np.float64)
    return float(np.sqrt(np.mean(err ** 2)))


# --------------------------------------------------------------------------
# where each round places its locations


# Each method draws from its own stream of the seed.
STREAM = {"aua": 0, "random": 10_000}


def _draw(rng, n: int, taken: set, ny: int, nx: int) -> List[List[int]]:
    """``n`` uniform locations not yet taken, a row then a column each."""
    out: List[List[int]] = []
    while len(out) < n:
        y = int(rng.integers(0, ny))
        x = int(rng.integers(0, nx))
        if (y, x) not in taken:
            taken.add((y, x))
            out.append([y, x])
    return out


def nearest_d2(points, ny: int, nx: int) -> np.ndarray:
    """Squared distance from each grid cell to the nearest of ``points``,
    exact (float64 of an integer)."""
    from scipy import ndimage

    empty = np.ones((ny, nx), bool)
    pts = np.asarray(points, np.int64).reshape(-1, 2)
    empty[pts[:, 0], pts[:, 1]] = False
    iy, ix = ndimage.distance_transform_edt(empty, return_distances=False,
                                            return_indices=True)
    yy, xx = np.mgrid[0:ny, 0:nx]
    return ((yy - iy) ** 2 + (xx - ix) ** 2).astype(np.float64)


def greedy_picks(est: np.ndarray, points, n: int) -> List[List[int]]:
    """``n`` cells picked one by one where |∇ estimate| (smeared one cell)
    × squared distance to the nearest sample is largest, each pick a
    sample for the next; the first such cell in row-major order on a tie."""
    e = np.asarray(est, np.float32)
    ny, nx = e.shape
    grad = (np.abs(np.roll(e, -1, 0) - e)
            + np.abs(np.roll(e, -1, 1) - e)).astype(np.float64)
    grad = grad + 0.5 * (np.roll(grad, 1, 0) + np.roll(grad, -1, 0)
                         + np.roll(grad, 1, 1) + np.roll(grad, -1, 1))
    d2 = nearest_d2(points, ny, nx)
    pri = grad * d2
    # no distance grows, so a pick changes no cell farther than this
    reach = int(np.ceil(np.sqrt(d2.max())))
    yy, xx = np.mgrid[0:ny, 0:nx]
    picks: List[List[int]] = []
    for _ in range(n):
        py, px = divmod(int(np.argmax(pri)), nx)
        picks.append([py, px])
        box = (slice(max(0, py - reach), py + reach + 1),
               slice(max(0, px - reach), px + reach + 1))
        d2[box] = np.minimum(d2[box], (yy[box] - py) ** 2
                             + (xx[box] - px) ** 2)
        pri[box] = grad[box] * d2[box]
    return picks


def placements(locations, values, *, method: str, seed: int, ny: int,
               nx: int, per_iter: int, rounds: int, idw: Dict[str, float],
               dtype) -> List[np.ndarray]:
    """The locations each round should place, given the ones the campaign's
    earlier rounds placed (``locations``, round after round, with the
    reference's ``values`` there): round 1 and every random round draw
    uniformly from the seed's stream; a later AUA round draws a quarter so
    and picks the rest greedily from the interpolated estimate."""
    rng = np.random.default_rng(seed + STREAM[method])
    locs = np.asarray(locations, np.int64).reshape(-1, 2)
    out = []
    for r in range(rounds):
        before = locs[:r * per_iter]
        taken = set(map(tuple, before.tolist()))
        if method == "aua" and r > 0:
            n_explore = max(1, per_iter // 4)
            explore = _draw(rng, n_explore, taken, ny, nx)
            est = idw_field(before, values[:r * per_iter], ny, nx,
                            dtype=dtype, **idw)
            picks = greedy_picks(est, np.concatenate([before, explore]),
                                 per_iter - n_explore)
            out.append(np.asarray(explore + picks, np.int64))
        else:
            out.append(np.asarray(_draw(rng, per_iter, taken, ny, nx),
                                  np.int64))
    return out


def campaign_answers(data: Dict[str, np.ndarray], locations, *, k: int,
                     idw: Dict[str, float], method: str, seed: int,
                     per_iter: int, rounds: int, dtype) -> Dict[str, object]:
    """What a campaign that placed these locations answers: the analog
    values (with their tie bounds), the RMSE of their interpolation, and
    where each round should have placed its locations."""
    values, lo, hi = analog_values(data["hist_forecast"], data["hist_obs"],
                                   data["forecast_now"], locations, k, dtype)
    ny, nx = data["truth"].shape
    est = idw_field(locations, values, ny, nx, dtype=dtype, **idw)
    rounds_due = placements(locations, values, method=method, seed=seed,
                            ny=ny, nx=nx, per_iter=per_iter, rounds=rounds,
                            idw=idw, dtype=dtype)
    return {"values": values, "lo": lo, "hi": hi,
            "final_rmse": rmse(est, data["truth"]),
            "placements": rounds_due}
