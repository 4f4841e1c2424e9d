"""Plain reference of the seismic misfit sweep (paper §III-A).

Independent of the package under test: it imports nothing of it and reads
nothing that it built. From the configuration's numbers and the seed it
builds the Marmousi-like true model and the smoothed starting model, the
wavelet, the damping and each shot's positions, simulates each shot's
recorded data on the true model and its synthetic seismogram on the
starting model, one shot at a time, by the equations the configuration
file writes out, and returns each shot's misfit and their sum.

Models are built in float64 on the host; the simulation runs in ``dtype``
(float32 for the reference, bfloat16 for the control); misfits and
energies are summed in float64.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

WATER_DEPTH_M = 36.0
WATER_VELOCITY = 1500.0
HARD_VELOCITY = 5500.0
INTERFACES = 16
FAULTS = 3
SIGMA_M = 250.0


# --------------------------------------------------------------------------
# the models, from the seed


def true_model(nz: int, nx: int, dx: float, seed: int) -> np.ndarray:
    """Water over dipping layers, one of them hard, cut by normal faults.

    Draws, in order, from ``default_rng(seed)``: the faults' surface
    positions (0.2-0.8 of the width), tilts (0.3-0.8 m across per m down)
    and throws (0.02-0.06 of the depth); the interfaces' dips (±0.15);
    each layer's velocity jitter (±200 m/s on 1700-4700 m/s); the hard
    layer's index (8-16)."""
    rng = np.random.default_rng(seed)
    width, depth = nx * dx, nz * dx
    fault_x = rng.uniform(0.2, 0.8, FAULTS) * width
    fault_tilt = rng.uniform(0.3, 0.8, FAULTS)
    fault_throw = rng.uniform(0.02, 0.06, FAULTS) * depth
    dips = rng.uniform(-0.15, 0.15, INTERFACES)
    velocity = np.linspace(1700.0, 4700.0, INTERFACES + 1) + rng.uniform(
        -200.0, 200.0, INTERFACES + 1)
    velocity[rng.integers(INTERFACES // 2, INTERFACES + 1)] = HARD_VELOCITY

    model = np.empty((nz, nx))
    x = np.arange(nx) * dx
    for row in range(nz):
        z = row * dx
        if z < WATER_DEPTH_M:
            model[row] = WATER_VELOCITY
            continue
        # the depth this point had before the faults threw it down
        z0 = z - sum(np.where(x > fx + z * ft, th, 0.0)
                     for fx, ft, th in zip(fault_x, fault_tilt, fault_throw))
        layer = np.zeros(nx, np.int64)
        for i in range(INTERFACES):
            top = WATER_DEPTH_M + (depth - WATER_DEPTH_M) * (
                (i + 1) / (INTERFACES + 1))
            layer += z0 > top + dips[i] * (x - width / 2)
        model[row] = velocity[layer]
    return model


def smooth(model: np.ndarray, dx: float) -> np.ndarray:
    """Gaussian smoothing with σ = 250 m along each axis, the kernel cut at
    3σ and normalised, the edge value repeated beyond the grid; then the
    water layer put back."""
    sigma = SIGMA_M / dx
    radius = math.ceil(3 * sigma)
    taps = np.exp(-0.5 * (np.arange(-radius, radius + 1) / sigma) ** 2)
    taps /= taps.sum()
    out = model
    for axis in (0, 1):
        n = out.shape[axis]
        padded = np.take(out, np.clip(np.arange(-radius, n + radius), 0,
                                      n - 1), axis=axis)
        out = np.apply_along_axis(
            lambda line: np.convolve(line, taps, mode="valid"), axis,
            padded)
    out = out.copy()
    out[np.arange(model.shape[0]) * dx < WATER_DEPTH_M] = WATER_VELOCITY
    return out


# --------------------------------------------------------------------------
# the acquisition


def column(x_m, dx: float):
    """The grid column of x metres: the nearest, halves rounded up."""
    return np.floor(np.asarray(x_m, np.float64) / dx + 0.5).astype(np.int64)


def positions(c: Dict[str, Any], shot: int) -> Tuple[int, np.ndarray]:
    """Shot ``shot``'s source column and its receivers' columns."""
    x = c["first_shot_m"] + shot * c["shot_spacing_m"]
    offsets = c["near_offset_m"] + c["receiver_spacing_m"] * np.arange(
        c["n_receivers"])
    return int(column(x, c["dx"])), column(x - offsets, c["dx"])


def ricker(c: Dict[str, Any]) -> np.ndarray:
    t = np.arange(c["nt"]) * c["dt"] - 1.2 / c["f0"]
    a = (np.pi * c["f0"] * t) ** 2
    return (1 - 2 * a) * np.exp(-a)


def damping(c: Dict[str, Any]) -> np.ndarray:
    p = c["pad"]
    nz, nx = c["nz"] + 2 * p, c["nx"] + 2 * p
    z, x = np.arange(nz)[:, None], np.arange(nx)[None, :]
    edge = np.minimum(np.minimum(z, nz - 1 - z), np.minimum(x, nx - 1 - x))
    return np.where(edge < p, c["damp_strength"] * ((p - edge) / p) ** 2,
                    0.0)


# --------------------------------------------------------------------------
# the simulation


@partial(jax.jit, static_argnames=("rows", "keep", "dtype"))
def _simulate(coef, damp, wavelet, source, receivers, *, rows, keep,
              dtype):
    """Every step of the leapfrog on the padded grid; the record of each
    step at the receivers, every ``keep``-th kept."""
    src_row, rec_row = rows
    d = damp.astype(dtype)

    def step(carry, w_t):
        u_prev, u = carry
        lap = (-4 * u + jnp.roll(u, 1, 0) + jnp.roll(u, -1, 0)
               + jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1))
        u_next = (2 - d) * u - (1 - d) * u_prev + coef * lap
        u_next = u_next.at[src_row, source].add(w_t)
        return (u, u_next), u_next[rec_row, receivers]

    zero = jnp.zeros(coef.shape, dtype)
    _, record = jax.lax.scan(step, (zero, zero), wavelet.astype(dtype))
    return record[::keep]


def seismogram(model: np.ndarray, shot: int, c: Dict[str, Any], dtype
               ) -> np.ndarray:
    """One shot's record (nt / record_every, n_receivers) on ``model``."""
    p = c["pad"]
    v = np.pad(model, p, mode="edge")
    coef = (jnp.asarray(v, dtype) * c["dt"]) ** 2 / c["dx"] ** 2
    source, receivers = positions(c, shot)
    rows = (int(column(c["source_depth_m"], c["dx"])) + p,
            int(column(c["receiver_depth_m"], c["dx"])) + p)
    record = _simulate(coef, jnp.asarray(damping(c), jnp.float32),
                       jnp.asarray(ricker(c), jnp.float32), source + p,
                       jnp.asarray(receivers + p), rows=rows,
                       keep=c["record_every"], dtype=dtype)
    return np.asarray(record.astype(jnp.float32), np.float64)


def sweep(c: Dict[str, Any], seed: int, shots: Sequence[int], dtype
          ) -> Dict[str, Any]:
    """Each shot's misfit of the starting model against the true model's
    record, their sum, and each record's energy (½ Σ record²)."""
    true = true_model(c["nz"], c["nx"], c["dx"], seed)
    start = smooth(true, c["dx"])
    misfits: List[float] = []
    energies: List[float] = []
    for shot in shots:
        recorded = seismogram(true, shot, c, dtype)
        synthetic = seismogram(start, shot, c, dtype)
        misfits.append(0.5 * float(np.sum((synthetic - recorded) ** 2)))
        energies.append(0.5 * float(np.sum(recorded ** 2)))
    return {"misfits": np.asarray(misfits), "total": math.fsum(misfits),
            "energies": np.asarray(energies)}
