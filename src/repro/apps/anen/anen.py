"""Analog Ensemble (AnEn) numerics in JAX.

Monache-style analog forecasting: for a target time and location, find the
``k`` historical forecasts most similar to the current forecast (similarity
over a short time window and multiple variables) and average their verified
observations. The paper's AUA contribution is *where* to compute analogs:
adaptively concentrating locations where the field has sharp gradients
instead of sampling uniformly (§III-B, Fig. 11).

Synthetic NAM-like dataset: a truth field with smooth structure plus sharp
fronts; historical forecast/observation pairs share a stationary,
spatially-correlated error process, so analog search is genuinely
informative (forecasts with similar values have similar errors).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class AnEnConfig:
    ny: int = 64
    nx: int = 64
    n_hist: int = 200        # historical forecast/observation pairs
    n_vars: int = 3          # forecast variables entering the similarity
    k: int = 12              # analogs averaged
    seed: int = 0


class AnEnData(NamedTuple):
    truth: jnp.ndarray          # (ny, nx) — verification field O_now
    forecast_now: jnp.ndarray   # (n_vars, ny, nx)
    hist_forecast: jnp.ndarray  # (n_hist, n_vars, ny, nx)
    hist_obs: jnp.ndarray       # (n_hist, ny, nx)


def _smooth_noise(rng, shape, scale: int) -> np.ndarray:
    """Cheap spatially-correlated noise: upsampled coarse white noise."""
    coarse = rng.standard_normal((shape[0] // scale + 2,
                                  shape[1] // scale + 2))
    up = np.kron(coarse, np.ones((scale, scale)))
    out = up[:shape[0], :shape[1]]
    # light box blur
    for _ in range(2):
        out = 0.25 * (np.roll(out, 1, 0) + np.roll(out, -1, 0)
                      + np.roll(out, 1, 1) + np.roll(out, -1, 1))
    return out


def make_dataset(cfg: AnEnConfig) -> AnEnData:
    rng = np.random.default_rng(cfg.seed)
    ny, nx = cfg.ny, cfg.nx
    yy, xx = np.mgrid[0:ny, 0:nx] / max(ny, nx)
    # truth: smooth waves + two sharp fronts (the AUA refinement targets)
    base = (np.sin(2.5 * np.pi * xx) * np.cos(1.5 * np.pi * yy)
            + 0.5 * np.sin(4 * np.pi * (xx + yy)))
    front = (np.tanh(18 * (yy - 0.45 - 0.18 * np.sin(3 * np.pi * xx)))
             + 0.7 * np.tanh(24 * (xx - 0.7 + 0.1 * np.cos(2 * np.pi * yy))))
    # front-dominated, as in the paper's temperature maps: "the highest
    # resolution of the analogs is required only at specific regions,
    # where drastic gradient changes occur"
    truth = 0.35 * base + 2.2 * front

    def day_field(t: int) -> np.ndarray:
        season = 0.6 * np.sin(2 * np.pi * t / 73.0)
        wobble = _smooth_noise(np.random.default_rng(cfg.seed + 100 + t),
                               (ny, nx), 8) * 0.35
        return truth + season + wobble

    hist_obs = np.stack([day_field(t) for t in range(cfg.n_hist)])
    # forecast error process: stationary spatially-correlated bias + noise
    bias = _smooth_noise(rng, (ny, nx), 16) * 0.5
    def forecast_of(obs, t):
        r = np.random.default_rng(cfg.seed + 500 + t)
        err = bias + _smooth_noise(r, (ny, nx), 8) * 0.3
        f0 = obs + err
        # extra predictor variables: shifted/scaled views with their own noise
        f1 = 0.8 * obs + 0.3 + _smooth_noise(r, (ny, nx), 8) * 0.25
        f2 = np.roll(obs, 2, axis=1) + _smooth_noise(r, (ny, nx), 8) * 0.3
        return np.stack([f0, f1, f2][:3])

    hist_forecast = np.stack(
        [forecast_of(hist_obs[t], t) for t in range(cfg.n_hist)])
    obs_now = day_field(cfg.n_hist + 13)
    forecast_now = forecast_of(obs_now, cfg.n_hist + 13)
    return AnEnData(
        truth=jnp.asarray(obs_now, jnp.float32),
        forecast_now=jnp.asarray(forecast_now, jnp.float32),
        hist_forecast=jnp.asarray(hist_forecast, jnp.float32),
        hist_obs=jnp.asarray(hist_obs, jnp.float32),
    )


def compute_analogs(data: AnEnData, locations: jnp.ndarray, k: int
                    ) -> jnp.ndarray:
    """AnEn prediction at ``locations`` (n, 2) int32 (y, x) indices.

    similarity(h, p) = Σ_vars w_v · (F_now[v,p] − F_hist[h,v,p])²  (lower
    is more similar); prediction = mean of the k most similar historical
    observations at p.
    """
    ys, xs = locations[:, 0], locations[:, 1]
    f_now = data.forecast_now[:, ys, xs]            # (V, n)
    f_h = data.hist_forecast[:, :, ys, xs]          # (H, V, n)
    o_h = data.hist_obs[:, ys, xs]                  # (H, n)
    d2 = jnp.sum((f_h - f_now[None]) ** 2, axis=1)  # (H, n)
    _, idx = jax.lax.top_k(-d2.T, k)                # (n, k) most similar
    picked = jnp.take_along_axis(o_h.T, idx, axis=1)
    return picked.mean(axis=1)                      # (n,)


def _grid_nearest(locations: jnp.ndarray, values: jnp.ndarray, ny: int,
                  nx: int, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Each grid cell's ``k`` nearest samples (fewer if there are fewer
    samples), nearest first and the lower sample index first on a tie, as
    ``lax.top_k`` orders them: their squared distances and values, both
    (ny·nx, k). The distances are integers below 2²⁴, so exact in float32."""
    yy, xx = jnp.mgrid[0:ny, 0:nx]
    gy = yy.reshape(-1).astype(jnp.float32)
    gx = xx.reshape(-1).astype(jnp.float32)
    ly = locations[:, 0].astype(jnp.float32)
    lx = locations[:, 1].astype(jnp.float32)
    d2 = ((gy[:, None] - ly[None]) ** 2
          + (gx[:, None] - lx[None]) ** 2)          # (G, n)
    neg_d2, idx = jax.lax.top_k(-d2, min(k, d2.shape[1]))
    return -neg_d2, values[idx]


def _weighted(d2: jnp.ndarray, vals: jnp.ndarray, ny: int, nx: int,
              power: float, eps: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The inverse-distance estimate over each cell's nearest samples, and
    its squared distance to the nearest, both (ny, nx). A +inf distance
    weighs exactly 0."""
    w = 1.0 / (d2 ** (power / 2) + eps)
    est = (w * vals).sum(axis=1) / w.sum(axis=1)
    # the top-1 as a min over the k columns: a slice of its own would keep
    # XLA from rewriting the sort under ``top_k`` into a partial TopK
    return est.reshape(ny, nx), d2.min(axis=1).reshape(ny, nx)


@functools.partial(jax.jit, static_argnames=("ny", "nx", "power",
                                             "k_nearest", "eps"))
def idw_interpolate_nearest(locations: jnp.ndarray, values: jnp.ndarray,
                            ny: int, nx: int, power: float = 2.0,
                            k_nearest: int = 8, eps: float = 1e-6
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """k-nearest inverse-distance interpolation onto the full grid, and
    each cell's squared distance to its nearest sample, both (ny, nx).

    Restricting to the nearest ``k`` samples (the unstructured-grid
    behaviour of the paper's implementation) is what makes *local*
    refinement effective: far-away samples cannot wash out a freshly
    refined front. Jitted so the (grid × samples) distance matrix is built
    once inside one program: op by op, its temporaries would hold three
    copies of it at once, over 12 GB on the NAM grid at 4096 samples.
    """
    return _weighted(*_grid_nearest(locations, values, ny, nx, k_nearest),
                     ny, nx, power, eps)


def idw_empty(ny: int, nx: int, k_nearest: int = 8
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The state of :func:`idw_interpolate_merge` before any sample: every
    cell's k nearest at +inf distance with value 0."""
    return (jnp.full((ny * nx, k_nearest), jnp.inf, jnp.float32),
            jnp.zeros((ny * nx, k_nearest), jnp.float32))


@functools.partial(jax.jit, static_argnames=("ny", "nx", "power", "eps"),
                   donate_argnames=("kept",))
def idw_interpolate_merge(kept: Tuple[jnp.ndarray, jnp.ndarray],
                          locations: jnp.ndarray, values: jnp.ndarray,
                          ny: int, nx: int, power: float = 2.0,
                          eps: float = 1e-6):
    """:func:`idw_interpolate_nearest` made incremental: fold new samples
    into ``kept``, each cell's k nearest earlier samples (squared distances
    and values, both (ny·nx, k), nearest first; :func:`idw_empty` before
    the first), and return the estimate, the nearest distances and the new
    state, whose buffers replace ``kept``'s.

    The k nearest of (earlier ∪ new) are the k nearest of (kept ∪ the new
    samples' k nearest), so the grid is scored against the new samples
    alone. A stable sort of those 2k candidates, kept first, settles a tie
    as ``top_k`` over every sample would: earlier samples have the lower
    indices, and each half is already in order. Same k nearest in the same
    order, so the same estimate as the whole-grid program over all the
    samples.
    """
    k = kept[0].shape[1]
    d2, vals = _grid_nearest(locations, values, ny, nx, k)
    d2 = jnp.concatenate([kept[0], d2], axis=1)
    vals = jnp.concatenate([kept[1], vals], axis=1)
    d2, vals = jax.lax.sort((d2, vals), dimension=1, is_stable=True,
                            num_keys=1)
    d2, vals = d2[:, :k], vals[:, :k]
    return (*_weighted(d2, vals, ny, nx, power, eps), (d2, vals))


def idw_interpolate(locations: jnp.ndarray, values: jnp.ndarray,
                    ny: int, nx: int, power: float = 2.0,
                    k_nearest: int = 8, eps: float = 1e-6) -> jnp.ndarray:
    """The estimate of :func:`idw_interpolate_nearest` alone."""
    return idw_interpolate_nearest(locations, values, ny, nx, power,
                                   k_nearest, eps)[0]


def rmse(a: jnp.ndarray, b: jnp.ndarray) -> float:
    return float(jnp.sqrt(jnp.mean((a - b) ** 2)))


def gradient_magnitude(field: jnp.ndarray) -> jnp.ndarray:
    gy = jnp.abs(jnp.roll(field, -1, 0) - field)
    gx = jnp.abs(jnp.roll(field, -1, 1) - field)
    return gy + gx
