"""Use-case applications: physics/numerics sanity + EnTK integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.seismic.solver import (SeismicConfig, forward_simulation,
                                       make_velocity_model, misfit_and_grad)
from repro.apps.anen.anen import (AnEnConfig, compute_analogs,
                                  idw_interpolate, make_dataset, rmse)


CFG = SeismicConfig(nx=48, nz=48, nt=90, n_receivers=8)


def test_forward_produces_signal():
    vel = make_velocity_model(CFG, "true")
    seis = forward_simulation(vel, source_x=24, cfg=CFG)
    assert seis.shape == (CFG.nt, CFG.n_receivers)
    e = np.asarray(seis ** 2).sum()
    assert np.isfinite(e) and e > 0


def test_wavefield_stable_no_blowup():
    vel = make_velocity_model(CFG, "background")
    seis = forward_simulation(vel, source_x=10, cfg=CFG)
    assert float(jnp.abs(seis).max()) < 1e3  # CFL-stable, damped


def test_velocity_anomaly_changes_seismogram():
    v0 = make_velocity_model(CFG, "background")
    v1 = make_velocity_model(CFG, "true")
    s0 = forward_simulation(v0, source_x=24, cfg=CFG)
    s1 = forward_simulation(v1, source_x=24, cfg=CFG)
    assert float(jnp.abs(s0 - s1).max()) > 1e-6


def test_adjoint_gradient_reduces_misfit():
    """One gradient step on the velocity model must reduce the misfit
    (adjoint-state correctness end-to-end)."""
    v_true = make_velocity_model(CFG, "true")
    observed = forward_simulation(v_true, source_x=24, cfg=CFG)
    v0 = make_velocity_model(CFG, "background")
    m0, g = misfit_and_grad(v0, observed, 24, CFG)
    assert float(jnp.abs(g).max()) > 0
    # normalized-gradient steps of O(1 m/s) velocity perturbation
    d = g / jnp.abs(g).max()
    improved = False
    for eps in (1.0, 0.3, 0.1):
        m1, _ = misfit_and_grad(v0 - eps * d, observed, 24, CFG)
        if float(m1) < float(m0):
            improved = True
            break
    assert improved, "no step size along -grad reduced the misfit"


def test_seismic_ensemble_under_entk():
    from repro.apps.seismic.workflow import run_forward_ensemble
    r = run_forward_ensemble(n_events=4, concurrency=2, failure_rate=0.4,
                             nx=40, nt=60)
    assert r["all_done"]
    assert r["attempts"] >= 4


# ---- the survey: moving spreads, decimated records, the CFL check --------- #

# a streamer survey on a 5 m grid: shots 25 m (5 cells) apart, three
# receivers 25, 50 and 75 m behind the shot
SURVEY = SeismicConfig(nx=160, nz=40, nt=60, dx=5.0, dt=1e-3, c_max=2000.0,
                       n_receivers=3, first_shot_m=400.0,
                       shot_spacing_m=25.0,
                       receiver_offsets_m=(-25.0, -50.0, -75.0))


def _layered(cfg):
    """Laterally uniform: 1800 m/s over 2000 m/s."""
    z = np.arange(cfg.nz)[:, None] < 10
    return jnp.asarray(np.where(z, 1800.0, 2000.0)
                       * np.ones((1, cfg.nx)), jnp.float32)


def test_receivers_move_with_the_source():
    from repro.apps.seismic.solver import shot_geometry

    s0, r0 = shot_geometry(SURVEY, 3)
    s1, r1 = shot_geometry(SURVEY, 4)
    assert s1 - s0 == 5 and np.array_equal(r1[1:], r0[:-1])
    assert np.array_equal(r1 - r0, [5, 5, 5])
    # on a laterally uniform model, a shot 25 m over records the same
    # traces: the spread moved with it (the wave reaches no damped cell)
    vel = _layered(SURVEY)
    seis0 = forward_simulation(vel, s0, SURVEY, receiver_x=r0)
    seis1 = forward_simulation(vel, s1, SURVEY, receiver_x=r1)
    assert float(jnp.abs(seis0).max()) > 0
    assert np.array_equal(np.asarray(seis0), np.asarray(seis1))
    fixed = forward_simulation(vel, s1, SURVEY, receiver_x=r0)
    assert not np.array_equal(np.asarray(seis1), np.asarray(fixed))


def test_receivers_move_with_the_source_in_marmousi_geometry():
    """At the Marmousi's 4 m grid the shots' 25 m are 6.25 cells: the
    source moves 6 or 7 columns, and receiver i of a shot sits where
    receiver i − 1 of the shot before sat."""
    from repro.apps.seismic.solver import shot_geometry

    cfg = SeismicConfig(nx=2301, nz=751, nt=5800, dx=4.0, dt=5e-4,
                        n_receivers=96, record_every=8,
                        first_shot_m=3000.0, shot_spacing_m=25.0,
                        receiver_offsets_m=tuple(-200.0 - 25.0 * i
                                                 for i in range(96)))
    steps = set()
    for shot in range(239):
        s0, r0 = shot_geometry(cfg, shot)
        s1, r1 = shot_geometry(cfg, shot + 1)
        assert np.array_equal(r1[1:], r0[:-1])
        steps.add(s1 - s0)
    assert steps == {6, 7}
    assert shot_geometry(cfg, 0)[1].min() == 106       # 425 m
    assert shot_geometry(cfg, 239)[0] == 2244          # 8975 m


def test_decimated_record_is_every_kth_sample_of_the_full_one():
    import dataclasses
    from repro.apps.seismic.solver import shot_geometry

    decimated = dataclasses.replace(SURVEY, record_every=4)
    source, spread = shot_geometry(SURVEY, 2)
    vel = _layered(SURVEY)
    full = forward_simulation(vel, source, SURVEY, receiver_x=spread)
    every = forward_simulation(vel, source, decimated, receiver_x=spread)
    assert every.shape == (SURVEY.nt // 4, 3)
    assert np.array_equal(np.asarray(every), np.asarray(full)[::4])


def test_cfl_check_raises_above_the_leapfrog_limit():
    # 5500 m/s on a 4 m grid: dt ≤ 4 / (5500 √2) = 0.514 ms
    SeismicConfig(dx=4.0, dt=5e-4, c_max=5500.0)
    with pytest.raises(ValueError, match="CFL"):
        SeismicConfig(dx=4.0, dt=6e-4, c_max=5500.0)
    with pytest.raises(ValueError, match="record_every"):
        SeismicConfig(dx=4.0, dt=5e-4, nt=5801, record_every=8)


def test_marmousi_like_models_from_the_seed():
    cfg = SeismicConfig(nx=192, nz=64, dx=12.0, dt=1.5e-3, model="marmousi")
    true = np.asarray(make_velocity_model(cfg, "true", seed=5))
    start = np.asarray(make_velocity_model(cfg, "init", seed=5))
    assert true.min() == 1500.0 and true.max() == 5500.0
    assert np.all(true[:3] == 1500.0) and np.all(start[:3] == 1500.0)
    # the smoothing keeps the values inside the true model's range and
    # takes the sharp contrasts out
    assert 1500.0 <= start.min() and start.max() < 5500.0
    assert (np.abs(np.diff(start[3:], axis=0)).max()
            < np.abs(np.diff(true[3:], axis=0)).max() / 5)
    assert np.array_equal(
        true, np.asarray(make_velocity_model(cfg, "true", seed=5)))
    assert not np.array_equal(
        true, np.asarray(make_velocity_model(cfg, "true", seed=6)))


# --------------------------------------------------------------------------- #
# AnEn
# --------------------------------------------------------------------------- #

ACFG = AnEnConfig(ny=24, nx=24, n_hist=60, seed=3)


def test_analogs_beat_climatology():
    data = make_dataset(ACFG)
    locs = jnp.asarray([[y, x] for y in range(0, 24, 3)
                        for x in range(0, 24, 3)], jnp.int32)
    pred = compute_analogs(data, locs, ACFG.k)
    truth = data.truth[locs[:, 0], locs[:, 1]]
    clim = data.hist_obs.mean(0)[locs[:, 0], locs[:, 1]]
    err_anen = float(jnp.sqrt(jnp.mean((pred - truth) ** 2)))
    err_clim = float(jnp.sqrt(jnp.mean((clim - truth) ** 2)))
    assert err_anen < err_clim


def test_idw_exact_at_samples():
    locs = jnp.asarray([[2, 2], [10, 17], [20, 5]], jnp.int32)
    vals = jnp.asarray([1.0, -2.0, 5.0])
    est = idw_interpolate(locs, vals, 24, 24)
    for (y, x), v in zip(np.asarray(locs), np.asarray(vals)):
        assert abs(float(est[y, x]) - float(v)) < 1e-3


def test_denser_sampling_reduces_error():
    data = make_dataset(ACFG)
    rng = np.random.default_rng(0)

    def err_with(n):
        pts = rng.choice(24 * 24, size=n, replace=False)
        locs = jnp.asarray([[p // 24, p % 24] for p in pts], jnp.int32)
        vals = compute_analogs(data, locs, ACFG.k)
        est = idw_interpolate(locs, vals, 24, 24)
        return rmse(est, data.truth)

    assert err_with(200) < err_with(20)


def test_aua_workflow_completes_and_steers():
    from repro.apps.anen.workflow import run_adaptive
    r = run_adaptive(seed=1, ny=24, nx=24, n_hist=40, per_iter=20,
                     max_iters=3, n_tasks=2, slots=2)
    assert r["all_done"]
    assert len(r["errors"]) == 3
    assert r["n_locations"] == 60
    # error is (weakly) improving as locations accumulate
    assert r["errors"][-1] <= r["errors"][0] + 1e-6


# ---- the AUA proposal against the whole-grid greedy ----------------------- #


def _whole_grid_proposal(st, n):
    """The AUA proposal as a whole-grid greedy, every distance and priority
    recomputed over the grid after each pick: the oracle that the
    incremental proposal has to match pick for pick."""
    from repro.apps.anen import workflow as w
    n_explore = max(1, n // 4)
    n_exploit = n - n_explore
    explore = st._random_new(n_explore)
    ny, nx = st.cfg.ny, st.cfg.nx
    locs = jnp.asarray(st.locations, jnp.int32)
    vals = jnp.asarray(st.values, jnp.float32)
    est = idw_interpolate(locs, vals, ny, nx)
    grad = np.asarray(w.gradient_magnitude(est)).astype(np.float64)
    grad = grad + 0.5 * (np.roll(grad, 1, 0) + np.roll(grad, -1, 0)
                         + np.roll(grad, 1, 1) + np.roll(grad, -1, 1))
    yy, xx = np.mgrid[0:ny, 0:nx]
    all_pts = (np.asarray(st.locations + explore.tolist())
               if len(st.locations) else explore)
    d2 = np.full((ny, nx), np.inf)
    for (py, px) in all_pts:
        d2 = np.minimum(d2, (yy - py) ** 2 + (xx - px) ** 2)
    picks = []
    pri = grad * d2
    for _ in range(n_exploit):
        flat = int(np.argmax(pri))
        py, px = flat // nx, flat % nx
        picks.append([py, px])
        nd2 = (yy - py) ** 2 + (xx - px) ** 2
        d2 = np.minimum(d2, nd2)
        pri = grad * d2
    return np.concatenate([explore, np.asarray(picks, np.int32)], axis=0)


def _new_state(seed, ny, nx, per_iter, rounds=2, n_tasks=3, method="aua"):
    from repro.apps.anen.workflow import _SearchState
    return _SearchState(method, seed, AnEnConfig(ny=ny, nx=nx, n_hist=12,
                                                 seed=seed),
                        per_iter, max_iters=rounds + 1, n_tasks=n_tasks)


def _absorb_round(st, per_iter, n_tasks=3, drop=None):
    """One round placed by the state's own proposal, valued from the truth
    field and absorbed; the result of slice ``drop`` comes back None."""
    truth = np.asarray(st.data.truth)
    slices = [sl for sl in np.array_split(st.propose(per_iter), n_tasks)
              if len(sl)]
    st._round_slices = [sl.tolist() for sl in slices]
    st.absorb([None if i == drop else truth[sl[:, 0], sl[:, 1]]
               for i, sl in enumerate(slices)])


def _add_behind_absorb(st, n=5):
    """Samples that absorb's kept arrays do not cover."""
    ny, nx = st.cfg.ny, st.cfg.nx
    taken = set(map(tuple, st.locations))
    extra = [[y, x] for y in range(0, ny, 7) for x in range(0, nx, 9)
             if (y, x) not in taken][:n]
    st.locations.extend(extra)
    st.values.extend([0.5] * len(extra))


def _search_state(seed, ny, nx, per_iter, rounds=2, n_tasks=3, drop=None):
    """An AUA search state after ``rounds`` absorbed rounds, each placed by
    the state's own proposal and valued from the truth field; the result of
    slice ``drop = (round, slice)`` comes back None."""
    st = _new_state(seed, ny, nx, per_iter, rounds, n_tasks)
    for r in range(rounds):
        _absorb_round(st, per_iter, n_tasks,
                      drop=drop[1] if drop and drop[0] == r else None)
    return st


def _against_oracle(st, n):
    """The incremental proposal and the oracle's, from one state and one
    position of its random stream."""
    at = st.rng.bit_generator.state
    got = st._adaptive_new(n)
    st.rng.bit_generator.state = at
    return got, _whole_grid_proposal(st, n)


@pytest.mark.parametrize("seed,ny,nx,per_iter,variant", [
    (0, 24, 24, 20, "plain"), (1, 24, 24, 20, "plain"),
    (2, 24, 24, 20, "plain"), (0, 48, 64, 64, "plain"),
    (1, 48, 64, 64, "plain"), (2, 48, 64, 64, "plain"),
    (0, 24, 24, 20, "ties"), (1, 48, 64, 64, "none_slice"),
    (2, 48, 64, 64, "stale"), (0, 48, 64, 7, "plain"),
    (1, 24, 24, 13, "plain"), (2, 24, 24, 3, "plain"),
])
def test_aua_proposal_matches_whole_grid_greedy(monkeypatch, seed, ny, nx,
                                                per_iter, variant):
    from repro.apps.anen import workflow as w
    if variant == "ties":
        # a constant-gradient estimate: priority = squared distance, whose
        # exact ties only the row-major first-hit rule settles
        monkeypatch.setattr(w, "gradient_magnitude", jnp.ones_like)
    st = _search_state(seed, ny, nx, per_iter,
                       drop=(1, 1) if variant == "none_slice" else None)
    if variant == "none_slice":
        assert len(st.locations) < 2 * per_iter
        assert st._idw[0] == len(st.locations)
    if variant == "stale":
        _add_behind_absorb(st)
        assert st._idw[0] != len(st.locations)
    got, want = _against_oracle(st, per_iter)
    assert got.shape == (per_iter, 2)
    np.testing.assert_array_equal(got, want)


# ---- absorb's incremental IDW against the whole-grid program -------------- #


@pytest.mark.parametrize("method,seed,ny,nx,per_iter,variant", [
    ("aua", 0, 8, 8, 6, "ties"), ("random", 1, 8, 8, 6, "ties"),
    ("aua", 1, 24, 24, 20, "none_slice"),
    ("random", 2, 24, 24, 20, "none_slice"),
    ("aua", 2, 24, 24, 5, "first_under_8"),
    ("random", 0, 24, 24, 5, "first_under_8"),
    ("aua", 0, 48, 64, 64, "stale"), ("random", 1, 48, 64, 64, "stale"),
])
def test_absorb_merges_to_the_whole_grid_idw(method, seed, ny, nx, per_iter,
                                             variant):
    """After every round, the estimate and nearest distances that absorb
    merged round by round are those of the whole-grid IDW over every
    sample absorbed: the nearest distances exactly, the estimate to
    float32 rounding of the same sum."""
    from repro.apps.anen.anen import idw_interpolate_nearest
    st = _new_state(seed, ny, nx, per_iter, rounds=4, method=method)
    for r in range(4):
        if variant == "stale" and r == 2:
            _add_behind_absorb(st)
        before = len(st.locations)
        _absorb_round(st, per_iter,
                      drop=1 if variant == "none_slice" and r == 1 else None)
        assert st._idw[0] == len(st.locations) > before
        if variant == "first_under_8" and r == 0:
            assert len(st.locations) < 8
        est, near = idw_interpolate_nearest(
            jnp.asarray(st.locations, jnp.int32),
            jnp.asarray(st.values, jnp.float32), ny, nx)
        np.testing.assert_array_equal(np.asarray(st._idw[2]),
                                      np.asarray(near))
        np.testing.assert_allclose(np.asarray(st._idw[1]), np.asarray(est),
                                   rtol=1e-6, atol=0)
    if variant == "none_slice":
        assert len(st.locations) < 4 * per_iter


@pytest.mark.parametrize("method", ["aua", "random"])
def test_absorb_reports_merge_or_rebuild(method):
    """``anen.absorb`` says, each round, whether the IDW merged the new
    samples into the kept nearest (scoring only them over the grid) or
    rebuilt from every sample: round 1, and after samples were added
    behind absorb's back."""
    from repro import telemetry
    telemetry.enable()
    telemetry.TRACER.clear()
    try:
        st = _new_state(3, 24, 24, 20, rounds=4, method=method)
        totals = []
        for r in range(4):
            if r == 2:
                _add_behind_absorb(st)
            _absorb_round(st, 20)
            totals.append(len(st.locations))
        recs = telemetry.TRACER.snapshot()
    finally:
        telemetry.disable()
        telemetry.TRACER.clear()
    events = [e for e in recs if e["name"] == "anen.absorb"]
    assert all(e["ph"] == "i" for e in events)
    attrs = [e["attrs"] for e in events]
    assert [a["merged"] for a in attrs] == [0, 1, 0, 1]
    assert [a["new_samples"] for a in attrs] == [20, 20, 20, 20]
    # a merge scores the round's new samples; a rebuild, every sample
    assert [a["columns"] for a in attrs] == [20, 20, totals[2], 20]


@pytest.mark.parametrize("absorb", ["real", "replaced"])
def test_aua_proposal_reuses_absorbs_idw(monkeypatch, absorb):
    from repro.apps.anen import workflow as w
    if absorb == "replaced":
        def plain_absorb(self, results):
            # the samples folded in, no IDW arrays kept
            for slice_locs, r in zip(self._round_slices, results):
                self.locations.extend(slice_locs)
                self.values.extend(np.asarray(r).tolist())
            self.iteration += 1
        monkeypatch.setattr(w._SearchState, "absorb", plain_absorb)
    st = _search_state(0, 48, 64, 32)
    calls = []
    real = w.idw_interpolate_nearest

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(w, "idw_interpolate_nearest", counted)
    at = st.rng.bit_generator.state
    got = st._adaptive_new(32)
    assert len(calls) == (0 if absorb == "real" else 1)
    st.rng.bit_generator.state = at
    np.testing.assert_array_equal(got, _whole_grid_proposal(st, 32))


def test_aua_proposal_span_inside_the_loop_body():
    """The proposal's record inside the loop body's span is an instant
    event, so the body's self time still holds the proposal."""
    from repro import telemetry
    from repro.apps.anen.workflow import run_adaptive
    telemetry.enable()
    telemetry.TRACER.clear()
    try:
        r = run_adaptive(seed=2, ny=24, nx=24, n_hist=40, per_iter=20,
                         max_iters=3, n_tasks=2, slots=2)
        recs = telemetry.TRACER.snapshot()
    finally:
        telemetry.disable()
        telemetry.TRACER.clear()
    assert r["all_done"]
    bodies = [s for s in recs
              if s["ph"] == "X" and s["name"] == "api.loop.body"]
    proposals = [s for s in recs if s["name"] == "anen.propose"]
    assert [b["attrs"]["round"] for b in bodies] == [0, 1, 2]
    # an instant event, so the proposal's time stays the body's self time
    assert all(p["ph"] == "i" for p in proposals)

    def inside(s, b):
        return (s["tid"] == b["tid"] and s["depth"] == b["depth"] + 1
                and b["ts"] <= s["ts"] <= b["ts"] + b["dur"])

    # round 0 is a random draw; each later round, one proposal
    assert [sum(inside(p, b) for p in proposals) for b in bodies] == [0, 1, 1]
    for p in proposals:
        a = p["attrs"]
        assert set(a) == {"picks", "explore", "reach", "cells_updated",
                          "reused_estimate"}
        assert (a["picks"], a["explore"], a["reused_estimate"]) == (15, 5, 1)
        assert 0 < a["reach"] and 0 < a["cells_updated"] <= 20 * 24 * 24


@pytest.mark.parametrize("backend,nz,nx,pad,path", [
    ("tpu", 751, 2301, 50, "pallas"),   # the Marmousi survey
    ("tpu", 512, 512, 0, "pallas"),     # chip_smoke's sweep
    ("tpu", 4, 124, 2, "pallas"),       # padded to exactly one (8, 128) tile
    ("tpu", 96, 96, 0, "jnp"),          # simulate_earthquake: narrower
    ("tpu", 7, 200, 0, "jnp"),          # fewer rows than a tile
    ("cpu", 751, 2301, 50, "jnp"),
])
def test_the_forward_takes_the_stencil_kernel_on_a_tpu_for_a_tile(
        monkeypatch, backend, nz, nx, pad, path):
    from repro.apps.seismic import solver
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = SeismicConfig(nx=nx, nz=nz, dx=4.0, dt=5e-4, pad=pad,
                        damp_width=max(pad, 1))
    assert solver.stencil_path(cfg) == path


def test_sweep_event_names_the_stencil_path():
    """``run_sweep``'s ``seismic.sweep`` event says which step the
    campaign's forward took: the jnp step on the CPU."""
    from repro import telemetry
    from repro.apps.seismic.solver import shot_geometry, stencil_path
    from repro.apps.seismic.workflow import record_survey, run_sweep

    geometry = [shot_geometry(SURVEY, j) for j in (3, 4)]
    observed = record_survey(SURVEY, geometry, seed=1)
    telemetry.enable()
    telemetry.TRACER.clear()
    try:
        res = run_sweep(SURVEY, geometry, observed, seed=1, slots=2,
                        timeout=300.0)
        recs = telemetry.TRACER.snapshot()
    finally:
        telemetry.disable()
        telemetry.TRACER.clear()
    assert res["all_done"]
    events = [e for e in recs if e["name"] == "seismic.sweep"]
    assert len(events) == 1 and events[0]["ph"] == "i"
    assert events[0]["attrs"]["stencil"] == stencil_path(SURVEY) == "jnp"


def test_sweep_leaves_no_campaign_output_on_the_device():
    """After ``run_sweep`` returns, the campaign's fused outputs are freed
    at once: closing the workflow drops its tasks' results, so device
    memory does not wait for the cyclic collector and does not grow with
    the number of campaigns run."""
    import gc
    from repro.apps.seismic.solver import shot_geometry
    from repro.apps.seismic.workflow import record_survey, run_sweep

    geometry = [shot_geometry(SURVEY, j) for j in (3, 4)]
    observed = record_survey(SURVEY, geometry, seed=1)
    records = (len(geometry), SURVEY.record_samples, SURVEY.n_receivers)
    gc.collect()
    gc.disable()
    try:
        # on the CPU ``observed`` is a view of the recording's device buffer
        before = {id(a) for a in jax.live_arrays()}
        for _ in range(2):
            res = run_sweep(SURVEY, geometry, observed, seed=1, slots=2,
                            timeout=300.0)
            assert res["all_done"]
            assert not [a for a in jax.live_arrays()
                        if a.shape == records and id(a) not in before]
    finally:
        gc.enable()
