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


def test_seismic_member_step_compiles_for_v5e(one_chip):
    sources = jax.ShapeDtypeStruct((EVENTS,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(_member_misfits).lower(sources).compile()
    mem = compiled.memory_analysis()
    # the 128 members' wavefield pairs (2 x 1 MiB each, twice: the trial
    # and the observed forward) must fit one chip's 16 GB
    assert mem.temp_size_in_bytes < 16e9


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
