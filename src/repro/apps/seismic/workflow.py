"""Seismic forward-ensemble workflow under EnTK (paper §IV-C.1, Fig. 10).

Each task forward-simulates one earthquake (one source position) on the
current velocity model. The scale experiment varies the *concurrency*
(pilot slots) for a fixed ensemble and injects failures at high concurrency
— reproducing the paper's observation that reducing concurrency eliminated
failures while EnTK's resubmission transparently completed the failed tasks
(157 attempted for 128 nominal at 2⁵ concurrency in the paper).

The misfit sweep of an inversion iteration (§III-A) is :func:`run_sweep`:
forward → misfit → Σ over a survey's shots, each shot's recorded data one
of its member's inputs, the trial model an operand of the fused program.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ... import api
from ... import telemetry as tel
from ...core import AppManager, Pipeline, Stage, Task, register_executable
from ...fusion import fusable, fusable_reduction
from ...rts.base import ResourceDescription
from ...rts.jax_rts import JaxRTS
from ...rts.local import LocalRTS
from .solver import SeismicConfig, forward_simulation, make_velocity_model, misfit

_CACHE: Dict[object, object] = {}

#: one shot: its source column and its receivers' columns
Shot = Tuple[int, np.ndarray]


def _forward_jit():
    if "fwd" not in _CACHE:
        _CACHE["fwd"] = jax.jit(forward_simulation,
                                static_argnames=("source_x", "cfg"))
    return _CACHE["fwd"]


def _velocity(kind: str, cfg: SeismicConfig, seed: int):
    key = ("vel", kind, cfg, seed)
    if key not in _CACHE:
        vel = make_velocity_model(cfg, kind, seed=seed)
        if isinstance(vel, jax.core.Tracer):
            # first call happened inside a trace (a fused vmap of
            # eval_misfit): the value is a traced constant — valid for
            # this trace, but caching it would leak the tracer into every
            # later scalar call
            return vel
        _CACHE[key] = vel
    return _CACHE[key]


def simulate_earthquake(source_x: int, nx: int = 96, nz: int = 96,
                        nt: int = 220, seed: int = 0) -> Dict[str, float]:
    """EnTK task executable: one forward simulation; returns summary stats
    (the seismogram itself would be staged out in production)."""
    cfg = SeismicConfig(nx=nx, nz=nz, nt=nt)
    vel = make_velocity_model(cfg, "true", seed=seed)
    seis = _forward_jit()(vel, source_x, cfg)
    seis.block_until_ready()
    return {"source_x": int(source_x),
            "energy": float((np.asarray(seis) ** 2).sum())}


register_executable("simulate_earthquake", simulate_earthquake)


@fusable(static_argnames=("nx", "nz", "nt", "seed", "dv"))
def eval_misfit(source_x: int, nx: int = 64, nz: int = 64, nt: int = 120,
                seed: int = 0, dv: float = 0.0):
    """EnTK task: the misfit of a trial (smooth background + ``dv``)
    velocity model against the true model's data for one earthquake — the
    fused seismic member kernel of the tomography workflow's evaluation
    sweep. ``source_x`` varies per member, so a fused micro-batch runs the
    whole source ensemble (observed-data forward + trial forward + misfit)
    as one batched scan over (B, nz, nx) wavefields.
    """
    import jax.numpy as jnp
    cfg = SeismicConfig(nx=nx, nz=nz, nt=nt)
    vel_true = _velocity("true", cfg, seed)
    vel_trial = _velocity("init", cfg, seed) + jnp.float32(dv)
    observed = forward_simulation(vel_true, source_x, cfg)
    return misfit(vel_trial, observed, source_x, cfg)


register_executable("eval_misfit", eval_misfit)


@fusable(static_argnames=("nx", "nz", "nt", "seed", "dv"))
def forward_trial(source_x: int, nx: int = 64, nz: int = 64, nt: int = 120,
                  seed: int = 0, dv: float = 0.0):
    """Chain link 1: the trial model's synthetic seismogram for one source.

    Split out of :func:`eval_misfit` so the evaluation sweep becomes an
    elementwise forward→misfit *chain*: per member the forward wavefield
    (the expensive link) hands its ``(nt, n_receivers)`` seismogram to the
    misfit link device-resident — under chain fusion the whole sweep runs
    both links as composed batched dispatches on one lease.
    """
    import jax.numpy as jnp
    cfg = SeismicConfig(nx=nx, nz=nz, nt=nt)
    vel_trial = _velocity("init", cfg, seed) + jnp.float32(dv)
    return forward_simulation(vel_trial, source_x, cfg)


register_executable("forward_trial", forward_trial)


@fusable(static_argnames=("nx", "nz", "nt", "seed"))
def trial_misfit(synthetic, source_x: int = 0, nx: int = 64, nz: int = 64,
                 nt: int = 120, seed: int = 0):
    """Chain link 2: L2 misfit of a trial seismogram against the observed
    data for its source (the observed forward is recomputed from the true
    model, exactly as :func:`eval_misfit` does — the two-link chain's
    values match the single-kernel sweep to float precision; the Marmousi
    sweep, :func:`run_sweep`, takes the recorded data as an input)."""
    import jax.numpy as jnp
    cfg = SeismicConfig(nx=nx, nz=nz, nt=nt)
    vel_true = _velocity("true", cfg, seed)
    observed = forward_simulation(vel_true, source_x, cfg)
    return 0.5 * jnp.sum((jnp.asarray(synthetic) - observed) ** 2)


register_executable("trial_misfit", trial_misfit)


def build_misfit_ensemble(n_events: int, *, nx: int = 64, nz: int = 64,
                          nt: int = 120, seed: int = 0, dv: float = 0.0,
                          max_retries: int = 0, fuse: bool = True
                          ) -> api.Ensemble:
    """The misfit-evaluation sweep as a declarative (fusible) ensemble."""
    xs = np.linspace(8, nx - 9, n_events).astype(int)
    return api.ensemble(
        eval_misfit,
        over=[{"source_x": int(sx), "nx": nx, "nz": nz, "nt": nt,
               "seed": seed, "dv": dv} for sx in xs],
        name=f"misfit-{seed}", max_retries=max_retries, fuse=fuse)


def build_misfit_chain(n_events: int, *, nx: int = 64, nz: int = 64,
                       nt: int = 120, seed: int = 0, dv: float = 0.0,
                       max_retries: int = 0, fuse: bool = True
                       ) -> api.Ensemble:
    """The misfit sweep as a 2-link forward→misfit chain (one member per
    earthquake source): ``api.compile`` detects the elementwise link and a
    chain-capable RTS executes each micro-batch through BOTH links as one
    composed dispatch, the per-source seismograms never touching the host."""
    xs = np.linspace(8, nx - 9, n_events).astype(int)
    forward = api.ensemble(
        forward_trial,
        over=[{"source_x": int(sx), "nx": nx, "nz": nz, "nt": nt,
               "seed": seed, "dv": dv} for sx in xs],
        name=f"forward-{seed}", max_retries=max_retries, fuse=fuse)
    return forward.then(
        trial_misfit,
        over=[{"source_x": int(sx), "nx": nx, "nz": nz, "nt": nt,
               "seed": seed} for sx in xs],
        name=f"misfit-chain-{seed}", max_retries=max_retries, fuse=fuse)


def run_misfit_chain(n_events: int, slots: int = 4, *, nx: int = 64,
                     nt: int = 120, seed: int = 0, dv: float = 0.0,
                     fuse: bool = True, chain: bool = True,
                     shard: bool = True, timeout: float = 600.0) -> Dict:
    """Evaluate the forward→misfit chain on the JaxRTS data plane.

    ``chain=False`` runs the identical 2-stage description per-stage-fused;
    ``fuse=False`` runs it member-per-task — the parity baselines. On a
    multi-device pool a wide event ensemble shards its chain across the
    whole mesh; ``shard=False`` pins it to per-device micro-batches. The
    result carries the runtime that ran the sweep (``rts``)."""
    ens = build_misfit_chain(n_events, nx=nx, nz=nx, nt=nt, seed=seed,
                             dv=dv, fuse=fuse)
    return _run_objective(ens, f"total-chain-{seed}", slots=slots,
                          devices=None, fuse=fuse, chain=chain, shard=shard,
                          timeout=timeout)


@fusable_reduction(kind="sum")
def total_misfit(values: List) -> float:
    """Gather: the ensemble objective Σ_sources misfit(source).

    ``@fusable_reduction(kind="sum")`` lets ``api.compile`` fold this
    fan-in into the sweep's ``_fusion_dag`` plan: the whole
    forward → misfit → Σ aggregation becomes one device-side dispatch
    (sharded sweeps reduce via ``psum`` across the mesh), while the scalar
    body keeps running unchanged everywhere fusion is off."""
    return float(np.sum([np.asarray(v) for v in values]))


# ---- the misfit sweep: forward → misfit → Σ over a survey's shots -------- #

def _model_operands(*, cfg: SeismicConfig, seed: int) -> Dict[str, object]:
    """The starting model, handed to the fusion engine as an operand: the
    sweep's program takes it as an argument, not as a constant of the
    compiled program (6.9 MB at the Marmousi's widths)."""
    return {"velocity": _velocity("init", cfg, seed)}


@fusable(static_argnames=("cfg", "seed"), operands=_model_operands)
def shot_synthetic(source_x: int, receiver_x, cfg: SeismicConfig,
                   seed: int = 0, velocity=None):
    """Chain link 1 of the sweep: the starting model's seismogram for one
    shot, gathered at the shot's own receivers. The fused sweep passes the
    model as ``velocity``; a scalar call looks it up."""
    if velocity is None:
        velocity = _velocity("init", cfg, seed)
    return forward_simulation(velocity, source_x, cfg, receiver_x)


register_executable("shot_synthetic", shot_synthetic)


@fusable
def shot_misfit(synthetic, observed):
    """Chain link 2 of the sweep: the L2 misfit of a shot's synthetic
    seismogram against its recorded data."""
    import jax.numpy as jnp
    return 0.5 * jnp.sum((jnp.asarray(synthetic) - observed) ** 2)


register_executable("shot_misfit", shot_misfit)


def _records(velocity, sources, receivers, cfg: SeismicConfig):
    return jax.vmap(lambda s, r: forward_simulation(velocity, s, cfg, r))(
        sources, receivers)


def record_survey(cfg: SeismicConfig, geometry: Sequence[Shot],
                  seed: int = 0) -> np.ndarray:
    """The true model's seismograms of the shots, (shots, nt / record_every,
    n_receivers) float32, in one batched program: the recorded data that a
    sweep compares against."""
    import jax.numpy as jnp
    if "rec" not in _CACHE:
        _CACHE["rec"] = jax.jit(_records, static_argnames=("cfg",))
    sources = jnp.asarray([sx for sx, _ in geometry], jnp.int32)
    receivers = jnp.asarray(np.stack([rx for _, rx in geometry]), jnp.int32)
    return np.asarray(_CACHE["rec"](_velocity("true", cfg, seed), sources,
                                    receivers, cfg))


def run_sweep(cfg: SeismicConfig, geometry: Sequence[Shot],
              observed: np.ndarray, *, seed: int = 0, slots: int = 4,
              devices=None, timeout: float = 3600.0) -> Dict:
    """The misfit objective of the starting model: Σ over the shots
    (``geometry``, each a source and its receivers, from
    :func:`~repro.apps.seismic.solver.shot_geometry`) of each shot's misfit
    against its recorded data (``observed``, one record per shot), on the
    JaxRTS data plane. The forward → misfit chain and the Σ run as one
    fused ``dag`` carrier; ``devices`` pins the runtime's pool. The result
    carries the runtime that ran the sweep (``rts``)."""
    observed = np.asarray(observed, np.float32)
    if observed.shape != (len(geometry), cfg.record_samples,
                          cfg.n_receivers):
        raise ValueError(f"recorded data of shape {observed.shape} for "
                         f"{len(geometry)} shots of {cfg.record_samples} "
                         f"samples x {cfg.n_receivers} receivers")
    if tel.enabled():
        nzp, nxp = cfg.padded_shape
        tel.event("seismic.sweep", "seismic", shots=len(geometry),
                  steps=cfg.nt, grid_points=nzp * nxp,
                  record_samples=cfg.record_samples,
                  observed_bytes=int(observed.nbytes))
    forward = api.ensemble(
        shot_synthetic,
        over=[{"source_x": int(sx), "receiver_x": np.asarray(rx, np.int32),
               "cfg": cfg, "seed": seed} for sx, rx in geometry],
        name=f"sweep-forward-{seed}")
    misfits = forward.then(shot_misfit,
                           over=[{"observed": obs} for obs in observed],
                           name=f"sweep-misfit-{seed}")
    return _run_objective(misfits, f"sweep-total-{seed}", slots=slots,
                          devices=devices, fuse=True, chain=True,
                          shard=True, timeout=timeout)


def _run_objective(ens: api.Ensemble, name: str, *, slots: int, devices,
                   fuse: bool, chain: bool, shard: bool, timeout: float
                   ) -> Dict:
    """Σ of the misfit ensemble ``ens`` through ``api.run`` on a JaxRTS."""
    objective = api.gather(ens, total_misfit, name=name)
    holder: Dict[str, JaxRTS] = {}

    def make_rts() -> JaxRTS:
        holder["rts"] = JaxRTS(devices=devices, slot_oversubscribe=slots,
                               shard=shard)
        return holder["rts"]

    t0 = time.time()
    result = api.run(
        objective, resources=ResourceDescription(slots=slots),
        rts_factory=make_rts, chain=chain, shard=shard, timeout=timeout)
    elapsed = time.time() - t0
    out = {
        "n_events": len(ens.specs),
        "fused": fuse,
        "chained": chain,
        "all_done": result.all_done,
        "total_misfit": objective.out.result(),
        "misfits": [float(np.asarray(s.out.result())) for s in ens.specs],
        "wallclock_s": elapsed,
        "rts": holder.get("rts"),
    }
    result.close()
    return out


def run_misfit_ensemble(n_events: int, slots: int = 4, *, nx: int = 64,
                        nt: int = 120, seed: int = 0, dv: float = 0.0,
                        fuse: bool = True, shard: bool = True,
                        timeout: float = 600.0) -> Dict:
    """Evaluate the source-ensemble misfit on the fused JaxRTS path.

    ``fuse=False`` runs the identical description member-per-task — the
    scalar baseline the fusion benchmark and the parity tests compare
    against. ``shard=False`` keeps per-device micro-batches on
    multi-device inventories (a single-device run is unaffected).
    """
    ens = build_misfit_ensemble(n_events, nx=nx, nz=nx, nt=nt, seed=seed,
                                dv=dv, fuse=fuse)
    return _run_objective(ens, f"total-misfit-{seed}", slots=slots,
                          devices=None, fuse=fuse, chain=True, shard=shard,
                          timeout=timeout)


def build_forward_ensemble(n_events: int, *, nx: int = 96, nz: int = 96,
                           nt: int = 220, max_retries: int = 3) -> Pipeline:
    pipe = Pipeline("seismic-forward")
    st = Stage("forward-simulations")
    xs = np.linspace(8, nx - 9, n_events).astype(int)
    for i, sx in enumerate(xs):
        st.add_tasks(Task(
            name=f"eq{i:03d}", executable="reg://simulate_earthquake",
            kwargs={"source_x": int(sx), "nx": nx, "nz": nz, "nt": nt},
            max_retries=max_retries, duration_hint=1.0))
    pipe.add_stages(st)
    return pipe


def run_forward_ensemble(n_events: int, concurrency: int,
                         failure_rate: float = 0.0, seed: int = 0,
                         nx: int = 96, nt: int = 220,
                         timeout: float = 600.0):
    """Fig.-10 cell: ``n_events`` forward sims on ``concurrency`` slots.

    ``failure_rate``: probability a task attempt fails (models the
    high-concurrency filesystem-overload failures of the paper); EnTK
    resubmits within each task's retry budget.
    """
    rng = np.random.default_rng(seed)
    attempts: Dict[str, int] = {}

    def injector(task) -> bool:
        attempts[task.name] = attempts.get(task.name, 0) + 1
        return bool(rng.random() < failure_rate)

    amgr = AppManager(
        resources=ResourceDescription(slots=concurrency),
        rts_factory=lambda: LocalRTS(fault_injector=injector))
    amgr.workflow = [build_forward_ensemble(n_events, nx=nx, nz=nx, nt=nt)]
    t0 = time.time()
    amgr.run(timeout=timeout)
    elapsed = time.time() - t0
    total_attempts = sum(attempts.values())
    return {
        "n_events": n_events,
        "concurrency": concurrency,
        "failure_rate": failure_rate,
        "all_done": amgr.all_done,
        "task_execution_s": amgr.prof.totals().get("task_execution", 0.0),
        "wallclock_s": elapsed,
        "attempts": total_attempts,
    }
