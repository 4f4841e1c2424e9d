"""Seismic forward-ensemble workflow under EnTK (paper §IV-C.1, Fig. 10).

Each task forward-simulates one earthquake (one source position) on the
current velocity model. The scale experiment varies the *concurrency*
(pilot slots) for a fixed ensemble and injects failures at high concurrency
— reproducing the paper's observation that reducing concurrency eliminated
failures while EnTK's resubmission transparently completed the failed tasks
(157 attempted for 128 nominal at 2⁵ concurrency in the paper).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import numpy as np

from ... import api
from ...core import AppManager, Pipeline, Stage, Task, register_executable
from ...fusion import fusable, fusable_reduction
from ...rts.base import ResourceDescription
from ...rts.jax_rts import JaxRTS
from ...rts.local import LocalRTS
from .solver import SeismicConfig, forward_simulation, make_velocity_model, misfit

_CACHE: Dict[str, object] = {}


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
    values match the single-kernel sweep to float precision)."""
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
    objective = api.gather(ens, total_misfit, name=f"total-chain-{seed}")
    holder: Dict[str, JaxRTS] = {}

    def make_rts() -> JaxRTS:
        holder["rts"] = JaxRTS(slot_oversubscribe=slots, shard=shard)
        return holder["rts"]

    t0 = time.time()
    result = api.run(
        objective, resources=ResourceDescription(slots=slots),
        rts_factory=make_rts, chain=chain, shard=shard, timeout=timeout)
    elapsed = time.time() - t0
    out = {
        "n_events": n_events,
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


@fusable_reduction(kind="sum")
def total_misfit(values: List) -> float:
    """Gather: the ensemble objective Σ_sources misfit(source).

    ``@fusable_reduction(kind="sum")`` lets ``api.compile`` fold this
    fan-in into the sweep's ``_fusion_dag`` plan: the whole
    forward → misfit → Σ aggregation becomes one device-side dispatch
    (sharded sweeps reduce via ``psum`` across the mesh), while the scalar
    body keeps running unchanged everywhere fusion is off."""
    return float(np.sum([np.asarray(v) for v in values]))


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
    objective = api.gather(ens, total_misfit, name=f"total-misfit-{seed}")
    t0 = time.time()
    result = api.run(
        objective, resources=ResourceDescription(slots=slots),
        rts_factory=lambda: JaxRTS(slot_oversubscribe=slots, shard=shard),
        shard=shard, timeout=timeout)
    elapsed = time.time() - t0
    out = {
        "n_events": n_events,
        "fused": fuse,
        "all_done": result.all_done,
        "total_misfit": objective.out.result(),
        "misfits": [float(np.asarray(s.out.result())) for s in ens.specs],
        "wallclock_s": elapsed,
    }
    result.close()
    return out


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
