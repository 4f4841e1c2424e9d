"""AnEn analog-similarity distance as a Pallas TPU kernel.

The analog search's hot loop is the similarity matrix

    d2[h, n] = Σ_v (f_hist[h, v, n] − f_now[v, n])²

over H historical forecasts × N query locations × V forecast variables —
the distance computation behind every AnEn member of the fused ensemble
(:mod:`repro.apps.anen`). V is tiny (≈3) while H·N is large, so the kernel
tiles (H, N) onto the VPU — blocks of (block_h, block_n) with the last
dimension lane-aligned to 128 — and unrolls the V reduction as a static
Python loop over (block_h, block_n) tiles: V separate fused
multiply-subtract-accumulate passes, no MXU involvement, no intermediate
(H, V, N) materialization in VMEM.

Both grid axes are ``parallel`` (every output tile is independent). The
wrapper zero-pads H to the f32 sublane multiple (8) and N to the lane
multiple (128) and slices the result back; padded columns cost dead VPU
lanes, never wrong values.

Validated on CPU with ``interpret=True`` against the jnp reference in
``tests/test_fusion.py``.

:func:`anen_distance_sharded` extends the grid across a device mesh: the H
axis (the member-folded axis in the fused AnEn workflow) is sharded over a
1-D mesh and each device invokes :func:`anen_distance` — the same Pallas
block tiling — on its local shard under ``shard_map``
(``check_vma=False``: pallas_call has no replication rule).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _distance_kernel(fh_ref, fn_ref, out_ref, *, n_vars: int):
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for v in range(n_vars):        # V is static and tiny: unrolled
        d = fh_ref[:, v, :] - fn_ref[v, :][None, :]
        acc += d * d
    out_ref[...] = acc


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("interpret", "block_h",
                                             "block_n"))
def anen_distance(f_hist: jnp.ndarray, f_now: jnp.ndarray,
                  interpret: bool = False, block_h: int = 64,
                  block_n: int = 128) -> jnp.ndarray:
    """``f_hist`` (H, V, N), ``f_now`` (V, N) → squared distances (H, N)."""
    H, V, N = f_hist.shape
    fh = _pad_to(_pad_to(f_hist.astype(jnp.float32), 0, 8), 2, 128)
    fn = _pad_to(f_now.astype(jnp.float32), 1, 128)
    Hp, _, Np = fh.shape
    block_h = min(block_h, Hp)
    block_n = min(block_n, Np)
    # pad once more so the grid divides exactly (tiny inputs on CPU tests)
    fh = _pad_to(fh, 0, block_h)
    fh = _pad_to(fh, 2, block_n)
    fn = _pad_to(fn, 1, block_n)
    Hp, _, Np = fh.shape
    kernel = functools.partial(_distance_kernel, n_vars=V)
    out = pl.pallas_call(
        kernel,
        grid=(Hp // block_h, Np // block_n),
        in_specs=[
            pl.BlockSpec((block_h, V, block_n), lambda i, j: (i, 0, j)),
            pl.BlockSpec((V, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_h, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Hp, Np), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(fh, fn)
    return out[:H, :N]


def anen_distance_sharded(f_hist: jnp.ndarray, f_now: jnp.ndarray,
                          devices=None, interpret: bool = False,
                          block_h: int = 64,
                          block_n: int = 128) -> jnp.ndarray:
    """:func:`anen_distance` with the H axis sharded across ``devices``.

    ``f_hist`` (H, V, N) is split into per-device blocks on axis 0 (padded
    by edge rows to divide evenly — padded rows are sliced off the result);
    ``f_now`` (V, N) replicates. Falls back to the single-device kernel for
    an empty/unit device list. One ``shard_map`` program spans the mesh;
    inside it each device runs the existing block-tiled Pallas grid on its
    own (H/D, V, N) shard.
    """
    devices = [d for d in (devices or []) if isinstance(d, jax.Device)]
    devices = list(dict.fromkeys(devices))
    if len(devices) < 2:
        return anen_distance(f_hist, f_now, interpret=interpret,
                             block_h=block_h, block_n=block_n)
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    H = f_hist.shape[0]
    n = len(devices)
    pad = (-H) % n
    fh = f_hist if pad == 0 else jnp.concatenate(
        [f_hist, jnp.repeat(f_hist[-1:], pad, axis=0)])
    mesh = Mesh(np.array(devices, dtype=object), ("h",))

    def shard(fh_, fn_):
        return anen_distance(fh_, fn_, interpret=interpret,
                             block_h=block_h, block_n=block_n)

    fn_sharded = jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(P("h"), P()), out_specs=P("h"),
        check_vma=False))
    fh = jax.device_put(fh, NamedSharding(mesh, P("h")))
    return fn_sharded(fh, jnp.asarray(f_now))[:H]
