"""The main path's device programs compile for a TPU v5e at real widths.

Nothing here runs on a chip: the topology is *described* (``v5e:2x2``) and
each program is lowered and compiled for it, so the TPU compiler rejects
what interpret mode and the CPU backend accept — unaligned kernel tiles, a
kernel that needs too much fast memory, a program that does not fit.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

# the AnEn phase of chip_smoke.py: one round's 1024 locations over a year
# of daily history with the generator's 3 variables
ANEN_H, ANEN_V, ANEN_N = 365, 3, 1024
# the seismic phase: 512 x 512 grid, 2000 steps, 128 events
GRID, STEPS, EVENTS = 512, 2000, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _member_misfits(sources):
    """The seismic sweep's composed member program: forward → misfit,
    vmapped over the events."""
    from repro.apps.seismic.workflow import forward_trial, trial_misfit

    def member(sx):
        seis = forward_trial(sx, nx=GRID, nz=GRID, nt=STEPS)
        return trial_misfit(seis, source_x=sx, nx=GRID, nz=GRID, nt=STEPS)

    return jax.vmap(member)(sources)


def test_anen_distance_kernel_compiles_for_v5e(one_chip):
    from repro.kernels.anen_distance import anen_distance

    f_hist = jax.ShapeDtypeStruct((ANEN_H, ANEN_V, ANEN_N), jnp.float32,
                                  sharding=one_chip)
    f_now = jax.ShapeDtypeStruct((ANEN_V, ANEN_N), jnp.float32,
                                 sharding=one_chip)
    compiled = anen_distance.lower(f_hist, f_now, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_idw_with_nearest_distances_keeps_its_topk_on_v5e(one_chip):
    """The IDW, which also returns each cell's nearest distance, on the NAM
    grid at a campaign's last round compiles to a fused ``TopK``: the
    (grid × samples) distance matrix is never sorted or held whole."""
    from repro.apps.anen.anen import idw_interpolate_nearest

    n = 4 * ANEN_N
    locs = jax.ShapeDtypeStruct((n, 2), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = idw_interpolate_nearest.lower(locs, vals, 428, 614).compile()
    assert 'custom_call_target="TopK"' in compiled.as_text()
    assert " sort(" not in compiled.as_text()
    # scratch below a tenth of the float32 (grid × samples) matrix
    assert (compiled.memory_analysis().temp_size_in_bytes
            < 428 * 614 * n * 4 // 10)


def test_idw_merge_keeps_its_topk_and_reuses_the_kept_state_on_v5e(
        one_chip):
    """absorb's incremental IDW on the NAM grid: one round's samples scored
    through a fused ``TopK``, and the kept 8 nearest of every cell written
    back into the buffers they came in."""
    from repro.apps.anen.anen import idw_interpolate_merge

    g, k = 428 * 614, 8
    kept = tuple(jax.ShapeDtypeStruct((g, k), jnp.float32, sharding=one_chip)
                 for _ in range(2))
    locs = jax.ShapeDtypeStruct((ANEN_N, 2), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((ANEN_N,), jnp.float32, sharding=one_chip)
    compiled = idw_interpolate_merge.lower(kept, locs, vals, 428,
                                           614).compile()
    assert 'custom_call_target="TopK"' in compiled.as_text()
    # both kept arrays, the grid axis padded to the chip's tiles
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * g * k * 4


def test_seismic_member_step_compiles_for_v5e(one_chip):
    sources = jax.ShapeDtypeStruct((EVENTS,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(_member_misfits).lower(sources).compile()
    mem = compiled.memory_analysis()
    # the 128 members' wavefield pairs (2 x 1 MiB each, twice: the trial
    # and the observed forward) must fit one chip's 16 GB
    assert mem.temp_size_in_bytes < 16e9


def test_seismic_member_step_runs_the_stencil_kernel_on_v5e(one_chip,
                                                            monkeypatch):
    """chip_smoke's 512 × 512 sweep on a TPU: both forwards of a member
    (the trial and the observed) take the kernel over the 128 events, two
    steps a loop iteration as every step is recorded, and no wavefield in
    the kernel's 520 × 640 storage is copied."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sources = jax.ShapeDtypeStruct((EVENTS,), jnp.int32, sharding=one_chip)
    # a fresh function: the jnp path's trace of _member_misfits is cached
    text = jax.jit(lambda s: _member_misfits(s)).lower(
        sources).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert not re.search(r"f32\[128,520,640\]\{[^}]*\} copy\(", text)


def test_sharded_sweep_with_sum_reduction_compiles_for_v5e(topo):
    from repro.fusion.engine import _apply_reduction

    mesh = Mesh(np.array(topo.devices[:4]), ("m",))

    def shard(sources, mask):
        misfits = _member_misfits(sources)
        return misfits, _apply_reduction(misfits, mask, "sum", None,
                                         axis_name="m")

    program = jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(P("m"), P("m")),
        out_specs=(P("m"), P()), check_vma=False))
    member_axis = NamedSharding(mesh, P("m"))
    compiled = program.lower(
        jax.ShapeDtypeStruct((EVENTS,), jnp.int32, sharding=member_axis),
        jax.ShapeDtypeStruct((EVENTS,), jnp.bool_, sharding=member_axis),
    ).compile()
    assert "all-reduce" in compiled.as_text()


def _marmousi_sweep(one_chip):
    """The 8-shot Marmousi misfit sweep (2301 × 751 at 4 m, a 50-cell pad,
    5800 steps recorded every 8th at 96 moving receivers) as the fusion
    engine composes it, compiled for one v5e chip: forward → misfit
    vmapped over the shots, then the masked Σ, the model an argument."""
    from repro.apps.seismic.solver import SeismicConfig
    from repro.apps.seismic.workflow import shot_misfit, shot_synthetic
    from repro.fusion.engine import _apply_reduction

    shots, receivers = 8, 96
    cfg = SeismicConfig(
        nx=2301, nz=751, nt=5800, dx=4.0, dt=5e-4, n_receivers=receivers,
        damp_width=50, damp_strength=0.1, record_every=8, source_z=2,
        receiver_z=3, pad=50, model="marmousi",
        first_shot_m=3000.0, shot_spacing_m=25.0,
        receiver_offsets_m=tuple(-200.0 - 25.0 * i for i in range(96)))

    def sweep(sources, spreads, observed, velocity, mask):
        synthetic = jax.vmap(lambda s, r: shot_synthetic(
            s, r, cfg=cfg, velocity=velocity))(sources, spreads)
        misfits = jax.vmap(shot_misfit)(synthetic, observed)
        return misfits, _apply_reduction(misfits, mask, "sum", None)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return jax.jit(sweep).lower(
        arg((shots,), jnp.int32), arg((shots, receivers), jnp.int32),
        arg((shots, cfg.record_samples, receivers), jnp.float32),
        arg((cfg.nz, cfg.nx), jnp.float32), arg((shots,), jnp.bool_),
    ).compile()


def test_marmousi_sweep_compiles_for_v5e_with_the_model_an_argument(
        one_chip):
    """The sweep with the jnp step. The velocity model is an argument: a
    constant of the program would put its 1.7 M values in the HLO text."""
    compiled = _marmousi_sweep(one_chip)
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9
    assert len(compiled.as_text()) < 1 << 20


def test_marmousi_sweep_runs_the_stencil_kernel_on_v5e(one_chip,
                                                       monkeypatch):
    """On a TPU the sweep's steps are the Pallas kernel, one call over the
    8 shots a step: four calls in the loop bodies (a recorded step, a pair
    of steps, the block's last step). The wavefields, in the kernel's
    864 × 2432 storage, stay in HBM, so each step streams them once, and
    the loop carries them without a copy."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _marmousi_sweep(one_chip).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    fields = re.findall(r"f32\[8,864,2432\]\{[^}]*\}", text)
    assert fields and not any("S(1)" in f for f in fields)
    assert not re.search(r"f32\[8,864,2432\]\{[^}]*\} copy\(", text)
