"""2-D acoustic full-waveform forward/adjoint solver in JAX.

Stands in for SPECFEM in the paper's tomography workflow (§III-A): the
physics is reduced (2-D acoustic, second-order FD leapfrog, absorbing-ish
damped boundaries) but the *workflow shape* is identical — per-earthquake
forward simulations producing seismograms at receiver arrays, a misfit
against observed data, and the adjoint gradient (here via ``jax.grad``
through the ``lax.scan`` time loop, which is exactly adjoint-state in
reverse-mode form) feeding an iterative velocity-model update.

A :class:`SeismicConfig` also carries an acquisition: a survey of shots at
a fixed spacing, each with a receiver spread that moves with it, recorded
every ``record_every``-th step, on a grid with an absorbing pad outside it.
The defaults are the small fixed-spread setting of the Fig.-10 sweeps; the
Marmousi survey (Versteeg 1994) fills in the published sizes.

Every function is jittable; forward simulations are the EnTK tasks of the
Fig.-10 scale experiment.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SeismicConfig:
    nx: int = 128
    nz: int = 128
    nt: int = 400
    dx: float = 10.0          # m
    dt: float = 1e-3          # s  (CFL: c_max·dt/dx ≤ 1/√2, checked)
    f0: float = 12.0          # Ricker peak frequency, Hz
    n_receivers: int = 32
    damp_width: int = 12
    damp_strength: float = 0.015
    # acquisition: the seismogram keeps every ``record_every``-th step;
    # source and receivers sit ``source_z`` / ``receiver_z`` rows down
    record_every: int = 1
    source_z: int = 2
    receiver_z: int = 2
    # absorbing cells added outside the nz × nx grid on every side (the
    # model edge-replicated into them); with 0 the damping lies inside
    pad: int = 0
    c_max: float = 5500.0     # the fastest velocity a model may hold, m/s
    model: str = "blobs"      # the velocity generator: "blobs" or "marmousi"
    # the survey, in metres along the surface: shot j at first_shot_m +
    # j·shot_spacing_m, its receivers at the shot's x + each offset. With
    # no offsets the spread is fixed: n_receivers evenly over the grid
    first_shot_m: float = 0.0
    shot_spacing_m: float = 0.0
    receiver_offsets_m: Tuple[float, ...] = ()

    def __post_init__(self) -> None:
        courant = self.c_max * self.dt / self.dx
        if courant > 1 / math.sqrt(2):
            raise ValueError(
                f"CFL: c_max·dt/dx = {courant:.4f} > 1/√2 — the leapfrog "
                f"is unstable at dt = {self.dt} s, dx = {self.dx} m")
        if self.nt % self.record_every:
            raise ValueError(f"nt = {self.nt} is not a multiple of "
                             f"record_every = {self.record_every}")
        if self.receiver_offsets_m and len(
                self.receiver_offsets_m) != self.n_receivers:
            raise ValueError("one receiver offset per receiver")

    @property
    def padded_shape(self) -> Tuple[int, int]:
        return self.nz + 2 * self.pad, self.nx + 2 * self.pad

    @property
    def record_samples(self) -> int:
        return self.nt // self.record_every


def grid_index(x_m, dx: float) -> np.ndarray:
    """Nearest grid point of a position in metres, halves rounded up."""
    return np.floor(np.asarray(x_m, np.float64) / dx + 0.5).astype(np.int32)


def shot_geometry(cfg: SeismicConfig, shot: int) -> Tuple[int, np.ndarray]:
    """Shot ``shot``'s source column and its receivers' columns on the
    nz × nx grid: the spread moves with the shot."""
    x_s = cfg.first_shot_m + shot * cfg.shot_spacing_m
    source = int(grid_index(x_s, cfg.dx))
    receivers = grid_index(x_s + np.asarray(cfg.receiver_offsets_m), cfg.dx)
    if (min(source, receivers.min()) < 0
            or max(source, receivers.max()) >= cfg.nx):
        raise ValueError(f"shot {shot} reaches outside the grid")
    return source, receivers


def make_velocity_model(cfg: SeismicConfig, kind: str = "true",
                        seed: int = 0) -> jnp.ndarray:
    """The ``cfg.model`` generator's model: "true", else the starting
    (background) model."""
    if cfg.model == "marmousi":
        true = marmousi_like(cfg.nz, cfg.nx, cfg.dx, seed)
        model = true if kind == "true" else starting_model(true, cfg.dx)
        if model.max() > cfg.c_max:
            raise ValueError("the model is faster than c_max")
        return jnp.asarray(model, jnp.float32)
    # layered background + (for 'true') ellipsoidal anomalies
    z = np.linspace(0, 1, cfg.nz)[:, None]
    c = 1500.0 + 1200.0 * z + 0.0 * np.zeros((cfg.nz, cfg.nx))
    if kind == "true":
        rng = np.random.default_rng(seed)
        zz, xx = np.mgrid[0:cfg.nz, 0:cfg.nx]
        for _ in range(3):
            cz, cx = rng.uniform(0.3, 0.8) * cfg.nz, rng.uniform(
                0.2, 0.8) * cfg.nx
            rz, rx = rng.uniform(6, 14), rng.uniform(8, 20)
            blob = np.exp(-(((zz - cz) / rz) ** 2 + ((xx - cx) / rx) ** 2))
            c += rng.choice([-1, 1]) * 180.0 * blob
    return jnp.asarray(c, jnp.float32)


# ---- the Marmousi-like models -------------------------------------------- #

WATER_DEPTH_M = 36.0
WATER_VELOCITY = 1500.0
HARD_VELOCITY = 5500.0
N_INTERFACES = 16
N_FAULTS = 3
SMOOTHING_M = 250.0


def marmousi_like(nz: int, nx: int, dx: float, seed: int) -> np.ndarray:
    """A seeded model in the Marmousi's manner, in m/s (float64): a water
    layer over dipping layers of rising velocity, one of them hard
    (5500 m/s), cut by dipping normal faults."""
    rng = np.random.default_rng(seed)
    z = np.arange(nz)[:, None] * dx
    x = np.arange(nx)[None, :] * dx
    width, depth = nx * dx, nz * dx
    # each fault: a line x = x_f + z·tilt_f, the ground beyond it thrown down
    x_f = rng.uniform(0.2, 0.8, N_FAULTS) * width
    tilt = rng.uniform(0.3, 0.8, N_FAULTS)
    throw = rng.uniform(0.02, 0.06, N_FAULTS) * depth
    unfaulted = z - sum(t * (x > xf + z * tl)
                        for xf, tl, t in zip(x_f, tilt, throw))
    # interfaces spread evenly under the water, each at its own dip
    base = WATER_DEPTH_M + (depth - WATER_DEPTH_M) * (
        np.arange(1, N_INTERFACES + 1) / (N_INTERFACES + 1))
    dip = rng.uniform(-0.15, 0.15, N_INTERFACES)
    layer = sum((unfaulted > b + d * (x - width / 2)).astype(np.int64)
                for b, d in zip(base, dip))
    speeds = np.linspace(1700.0, 4700.0, N_INTERFACES + 1) + rng.uniform(
        -200.0, 200.0, N_INTERFACES + 1)
    speeds[rng.integers(N_INTERFACES // 2, N_INTERFACES + 1)] = HARD_VELOCITY
    model = speeds[layer]
    model[np.broadcast_to(z < WATER_DEPTH_M, model.shape)] = WATER_VELOCITY
    return model


def _gaussian_matrix(n: int, sigma: float) -> np.ndarray:
    """Row i: Gaussian weights (σ cells, cut at 3σ, summing to 1) over the
    cells of an axis of ``n``, the edge cell repeated beyond the ends."""
    radius = int(math.ceil(3 * sigma))
    offsets = np.arange(-radius, radius + 1)
    weights = np.exp(-0.5 * (offsets / sigma) ** 2)
    weights /= weights.sum()
    matrix = np.zeros((n, n))
    cols = np.clip(np.arange(n)[:, None] + offsets[None, :], 0, n - 1)
    np.add.at(matrix, (np.repeat(np.arange(n), len(offsets)),
                       cols.ravel()), np.tile(weights, n))
    return matrix


def starting_model(true: np.ndarray, dx: float) -> np.ndarray:
    """The true model Gaussian-smoothed (σ = 250 m), the water layer kept."""
    nz, nx = true.shape
    sigma = SMOOTHING_M / dx
    smooth = _gaussian_matrix(nz, sigma) @ true @ _gaussian_matrix(
        nx, sigma).T
    water = np.arange(nz) * dx < WATER_DEPTH_M
    smooth[water, :] = WATER_VELOCITY
    return smooth


# ---- the forward simulation ---------------------------------------------- #

def _ricker(cfg: SeismicConfig) -> jnp.ndarray:
    t = jnp.arange(cfg.nt) * cfg.dt - 1.2 / cfg.f0
    a = (jnp.pi * cfg.f0 * t) ** 2
    return (1 - 2 * a) * jnp.exp(-a)


def _damping(cfg: SeismicConfig) -> jnp.ndarray:
    """strength·((w − i)/w)² at i cells from the padded grid's nearest edge,
    for i < w = damp_width, else 0: the profile's w + 1 values indexed by
    the distance, so the grid-sized field is built in the program and is
    no constant of it."""
    nzp, nxp = cfg.padded_shape
    w = cfg.damp_width
    profile = np.zeros(w + 1)
    profile[:w] = cfg.damp_strength * ((w - np.arange(w)) / w) ** 2
    zz = jax.lax.broadcasted_iota(jnp.int32, (nzp, nxp), 0)
    xx = jax.lax.broadcasted_iota(jnp.int32, (nzp, nxp), 1)
    edge = jnp.minimum(jnp.minimum(zz, nzp - 1 - zz),
                       jnp.minimum(xx, nxp - 1 - xx))
    return jnp.asarray(profile, jnp.float32)[jnp.minimum(edge, w)]


def forward_simulation(velocity: jnp.ndarray, source_x: int,
                       cfg: SeismicConfig, receiver_x=None) -> jnp.ndarray:
    """One shot: source at (``cfg.source_z``, ``source_x``). Returns the
    seismogram (nt / record_every, n_receivers) recorded at row
    ``cfg.receiver_z`` of columns ``receiver_x`` (default: the fixed
    spread); sample k is the wavefield after step k·record_every. Positions
    are on the nz × nx grid; the velocity model is nz × nx."""
    with jax.named_scope("seismic.forward"):
        p = cfg.pad
        if p:
            velocity = jnp.pad(velocity, p, mode="edge")
        src = _ricker(cfg)
        damp = _damping(cfg)
        c2dt2 = (velocity * cfg.dt) ** 2 / (cfg.dx ** 2)
        if receiver_x is None:   # the fixed spread, evenly over the grid
            receiver_x = jnp.linspace(4, cfg.nx - 5, cfg.n_receivers)
        rec_x = jnp.asarray(receiver_x).astype(jnp.int32) + p
        sz, rz, sx = cfg.source_z + p, cfg.receiver_z + p, source_x + p

        def laplacian(u):
            return (-4.0 * u
                    + jnp.roll(u, 1, 0) + jnp.roll(u, -1, 0)
                    + jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1))

        def step(carry, s_t):
            u_prev, u = carry
            u_next = ((2.0 - damp) * u - (1.0 - damp) * u_prev
                      + c2dt2 * laplacian(u))
            return (u, u_next.at[sz, sx].add(s_t)), None

        def recorded_step(carry, s_block):
            # a sample after the block's first step, then the rest unseen
            carry, _ = step(carry, s_block[0])
            rec = carry[1][rz, rec_x]
            carry, _ = jax.lax.scan(step, carry, s_block[1:])
            return carry, rec

        zeros = jnp.zeros(cfg.padded_shape, jnp.float32)
        _, seis = jax.lax.scan(recorded_step, (zeros, zeros),
                               src.reshape(-1, cfg.record_every))
        return seis


def misfit(velocity: jnp.ndarray, observed: jnp.ndarray, source_x: int,
           cfg: SeismicConfig) -> jnp.ndarray:
    """L2 waveform misfit for one source."""
    synth = forward_simulation(velocity, source_x, cfg)
    return 0.5 * jnp.sum((synth - observed) ** 2)


def misfit_and_grad(velocity: jnp.ndarray, observed: jnp.ndarray,
                    source_x: int, cfg: SeismicConfig
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Adjoint gradient via reverse-mode through the time loop."""
    return jax.value_and_grad(misfit)(velocity, observed, source_x, cfg)
