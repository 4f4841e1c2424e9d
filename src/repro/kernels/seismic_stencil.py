"""One leapfrog step of the 2-D acoustic wave equation as a Pallas TPU kernel.

The seismic forward (:func:`repro.apps.seismic.solver.forward_simulation`)
advances every shot's wavefield by

    u_next = (2 − d)·u − (1 − d)·u_prev + c·lap(u),
    then u_next[sz, sx] += w

with the 5-point Laplacian ``−4u + u[z−1] + u[z+1] + u[x−1] + u[x+1]``,
its indices wrapping around the nz × nx grid. XLA runs that as several
passes over the grid (the Laplacian's shifted copies, the update, the
source scatter, the loop carry's copies). This kernel reads ``u`` and
``u_prev`` once, writes ``u_next`` once, into ``u_prev``'s buffer
(``input_output_aliases``), and forms the neighbours with rolls in VMEM.

**Layout.** The fields are stored in an aligned (rows, cols) array
(:class:`Layout`): the nz × nx grid at its top left, rows a multiple of
the row block and cols of 128, the rest zero except two ghost columns and
one ghost row. Column ``nx`` holds a copy of column 0 and column
``cols − 1`` one of column ``nx − 1``, so that plain lane rolls over the
aligned width give both wrapped x-neighbours; row ``rows − 1`` holds a
copy of row ``nz − 1``, so that the first row block's halo above, the
last 8 rows of the array, ends in that row. The kernel writes the ghosts
with every step. The wrap below row ``nz − 1``, row 0, comes from the
last row block's halo below, which is the array's first 8 rows. With
zero initial fields, zero ``c`` outside the grid and no source there, the
cells outside the grid other than the ghosts stay zero. :func:`embed` and
:func:`crop` convert between the grid and this layout.

**Grid.** ``(row blocks, shots)``, the shot axis innermost: the
coefficient block (one per row block, shared by the shots) keeps its
index across consecutive shots, so Pallas fetches it once per row block
and step. Each program reads its rows of ``u`` and ``u_prev``, the 8-row
tiles of ``u`` above and below them, and ``c``; it generates the damping
``d = max(d_z[z], d_x[x])`` from two profile vectors, which equals the
profile at the nearest edge's distance because the profile does not rise
with the distance. The per-shot source row, column and wavelet sample are
scalar-prefetch operands. Inside a program the block is computed in
strips of 8 rows.

**Batching.** :func:`leapfrog` is one shot's step. Under ``jax.vmap`` (the
fusion engine's vmap over a campaign's shots, or ``record_survey``'s)
``jax.custom_batching.custom_vmap`` turns it into one kernel over every
shot, nested vmaps folded into the one shot axis.

Validated on CPU with ``interpret=True`` against the jnp step in
``tests/test_kernels.py``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the largest row block, in bytes of one float32 field's block: VMEM
#: holds four such blocks twice (double-buffered)
BLOCK_BYTES = 3 << 20
SUBLANES, LANES = 8, 128


@dataclasses.dataclass(frozen=True)
class Layout:
    """The aligned storage of an nz × nx grid: ``rows`` × ``cols``, cut
    into row blocks of ``block`` rows."""
    nz: int
    nx: int
    rows: int
    cols: int
    block: int

    def __post_init__(self) -> None:
        blocks, rest = divmod(self.rows, self.block)
        if (rest or self.block % SUBLANES or self.block < 2 * SUBLANES
                or self.rows <= self.nz or (blocks - 1) * self.block >= self.nz
                or self.cols % LANES or self.cols < self.nx + 2):
            raise ValueError(f"no storage of a {self.nz} x {self.nx} grid: "
                             f"{self}")


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def fits(nz: int, nx: int) -> bool:
    """Whether the grid fills at least one (8, 128) tile."""
    return nz >= SUBLANES and nx >= LANES


@functools.lru_cache(maxsize=64)
def layout(nz: int, nx: int) -> Layout:
    """The aligned storage of the grid, with the fewest rows: a column for
    each ghost, a row for the ghost row, and row blocks of at least two
    8-row strips and at most ``BLOCK_BYTES``, the last holding row nz − 1."""
    if not fits(nz, nx):
        raise ValueError(f"a {nz} x {nx} grid is smaller than one tile")
    cols = _round_up(nx + 2, LANES)
    most = max(2 * SUBLANES,
               BLOCK_BYTES // (4 * cols) // SUBLANES * SUBLANES)
    best = None
    for blocks in range(1, nz + 2):
        block = _round_up(-(-(nz + 1) // blocks), SUBLANES)
        if block < 2 * SUBLANES:
            break
        if block > most or (blocks - 1) * block > nz - 1:
            continue
        if best is None or blocks * block < best.rows:
            best = Layout(nz, nx, blocks * block, cols, block)
    if best is None:   # one block of the whole grid, however large
        block = max(2 * SUBLANES, _round_up(nz + 1, SUBLANES))
        best = Layout(nz, nx, block, cols, block)
    return best


def embed(field: jnp.ndarray, lay: Layout) -> jnp.ndarray:
    """An nz × nx field in the aligned storage, its ghosts filled."""
    out = jnp.pad(field, ((0, lay.rows - lay.nz), (0, lay.cols - lay.nx)))
    out = out.at[:, lay.nx].set(out[:, 0])
    out = out.at[:, lay.cols - 1].set(out[:, lay.nx - 1])
    return out.at[lay.rows - 1].set(out[lay.nz - 1])


def crop(field: jnp.ndarray, lay: Layout) -> jnp.ndarray:
    """The nz × nx grid of an aligned field."""
    return field[..., :lay.nz, :lay.nx]


def _damping_vectors(lay: Layout, profile: Sequence[float]
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """``profile[min(i, w)]`` at i cells from the nearer end of each axis,
    as a (rows, 1) and a (1, cols) vector, zero outside the grid."""
    table = np.asarray(profile, np.float32)
    w = len(table) - 1

    def along(n, size):
        i = np.arange(size)
        edge = np.clip(np.minimum(i, n - 1 - i), 0, w)
        return np.where(i < n, table[edge], np.float32(0)).astype(np.float32)

    return along(lay.nz, lay.rows)[:, None], along(lay.nx, lay.cols)[None, :]


def _set_column(x: jnp.ndarray, col: int, value: jnp.ndarray) -> jnp.ndarray:
    """``x`` (8, cols) with column ``col`` set to ``value`` (8, 1), touching
    only the 128-lane tile that holds it."""
    t = col // LANES * LANES
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
    tile = jnp.where(lane == col - t, value, x[:, t:t + LANES])
    parts = [p for p in (x[:, :t], tile, x[:, t + LANES:]) if p.shape[1]]
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else tile


def _kernel(src_rows_ref, src_cols_ref, amp_ref, u_prev_ref, u_ref,
            above_ref, below_ref, c_ref, dz_ref, dx_ref, out_ref, *,
            lay: Layout):
    i, b = pl.program_id(0), pl.program_id(1)
    bz, cols, nx = lay.block, lay.cols, lay.nx
    strips = bz // SUBLANES
    last = lay.rows // bz - 1
    src_row = src_rows_ref[b] - i * bz      # the source's row in this block
    src_col = src_cols_ref[b]
    amp = amp_ref[b]
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, cols), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, cols), 1)
    dx = dx_ref[...]

    def at(s):
        start = s * SUBLANES
        if not isinstance(s, int):
            start = pl.multiple_of(start, SUBLANES)
        return pl.ds(start, SUBLANES)

    def strip(s, prev, cur, nxt, wrap=None):
        """u_next of the block's rows 8s .. 8s + 7, whose u is ``cur``, with
        ``prev`` and ``nxt`` the 8 rows of u above and below them; ``wrap``
        = (r, row) puts ``row`` below strip row r in place of nxt's."""
        up = jnp.where(sub == 0, pltpu.roll(prev, 1, 0), pltpu.roll(cur, 1, 0))
        down = jnp.where(sub == SUBLANES - 1,
                         pltpu.roll(nxt, SUBLANES - 1, 0),
                         pltpu.roll(cur, SUBLANES - 1, 0))
        if wrap is not None:
            down = jnp.where(sub == wrap[0], wrap[1], down)
        lap = (-4.0 * cur + up + down + pltpu.roll(cur, 1, 1)
               + pltpu.roll(cur, cols - 1, 1))
        d = jnp.maximum(dz_ref[at(s), :], dx)
        new = ((2.0 - d) * cur - (1.0 - d) * u_prev_ref[at(s), :]
               + c_ref[at(s), :] * lap)
        new = jnp.where((sub == src_row - s * SUBLANES) & (lane == src_col),
                        new + amp, new)
        new = _set_column(new, nx, new[:, 0:1])
        new = _set_column(new, cols - 1, new[:, nx - 1:nx])
        out_ref[at(s), :] = new
        return new

    def rows_of(s):
        return u_ref[at(s), :]

    strip(0, above_ref[...], rows_of(0), rows_of(1))

    def body(s, carry):
        strip(s, rows_of(s - 1), rows_of(s), rows_of(s + 1))
        return carry

    jax.lax.fori_loop(1, strips - 1, body, 0)
    strip(strips - 1, rows_of(strips - 2), rows_of(strips - 1),
          below_ref[...])

    # the last block holds row nz − 1, whose row below is row 0 (its halo
    # below, the array's first 8 rows), and the ghost row, its copy
    r = lay.nz - 1 - last * bz
    s = r // SUBLANES

    @pl.when(i == last)
    def _():
        prev = above_ref[...] if s == 0 else rows_of(s - 1)
        nxt = below_ref[...] if s == strips - 1 else rows_of(s + 1)
        row0 = jnp.broadcast_to(below_ref[0:1, :], (SUBLANES, cols))
        new = strip(s, prev, rows_of(s), nxt, wrap=(r % SUBLANES, row0))
        ghost = jnp.broadcast_to(new[r % SUBLANES:r % SUBLANES + 1, :],
                                 (SUBLANES, cols))
        end = at(strips - 1)
        out_ref[end, :] = jnp.where(sub == SUBLANES - 1, ghost,
                                    out_ref[end, :])


def _call(lay: Layout, profile: Tuple[float, ...], interpret: bool,
          u_prev, u, c, src_rows, src_cols, amp):
    """The kernel over ``shots`` = u.shape[0] shots: u_prev, u (shots,
    rows, cols); c (shots / rep, rows, cols), shot j using c[j // rep];
    src_rows, src_cols, amp (shots,)."""
    shots = u.shape[0]
    rep = shots // c.shape[0]
    bz, width = lay.block, lay.cols
    tiles, per_block = lay.rows // SUBLANES, bz // SUBLANES
    dz, dx = _damping_vectors(lay, profile)
    field = pl.BlockSpec((pl.Squeezed(), bz, width),
                         lambda i, b, *_: (b, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(lay.rows // bz, shots),
        in_specs=[
            field,                                          # u_prev
            field,                                          # u
            pl.BlockSpec((pl.Squeezed(), SUBLANES, width),  # u above
                         lambda i, b, *_: (
                             b, (i * per_block + tiles - 1) % tiles, 0)),
            pl.BlockSpec((pl.Squeezed(), SUBLANES, width),  # u below
                         lambda i, b, *_: (
                             b, ((i + 1) * per_block) % tiles, 0)),
            pl.BlockSpec((pl.Squeezed(), bz, width),        # c
                         lambda i, b, *_: (b // rep, i, 0)),
            pl.BlockSpec((bz, 1), lambda i, b, *_: (i, 0)),
            pl.BlockSpec((1, width), lambda i, b, *_: (0, 0)),
        ],
        out_specs=field,
    )
    block_bytes = 4 * bz * width
    vmem = 2 * (4 * block_bytes + 4 * SUBLANES * width * 4
                + bz * LANES * 4) + (16 << 20)
    out_shape = jax.ShapeDtypeStruct(u.shape, jnp.float32)
    if not interpret:
        # the fields stay in HBM, each step streaming them through VMEM
        # once: left to itself, XLA keeps one whole field in VMEM across
        # the time loop on v5e
        u_prev, u, c = (pltpu.with_memory_space_constraint(x, pltpu.HBM)
                        for x in (u_prev, u, c))
        out_shape = pltpu.HBM(u.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_kernel, lay=lay),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(src_rows, src_cols, amp, u_prev, u, u, u, c, jnp.asarray(dz),
      jnp.asarray(dx))


@functools.lru_cache(maxsize=64)
def _batched(lay: Layout, profile: Tuple[float, ...], interpret: bool):
    """The kernel over a leading shot axis, as a ``custom_vmap`` whose rule
    folds a vmapped axis into that shot axis."""

    @jax.custom_batching.custom_vmap
    def step(u_prev, u, c, src_rows, src_cols, amp):
        return _call(lay, profile, interpret, u_prev, u, c, src_rows,
                     src_cols, amp)

    @step.def_vmap
    def _rule(axis_size, in_batched, u_prev, u, c, src_rows, src_cols, amp):
        def fold(x, batched):
            if not batched:
                x = jnp.broadcast_to(x, (axis_size,) + x.shape)
            return x.reshape((-1,) + x.shape[2:])

        u_prev, u, src_rows, src_cols, amp = (
            fold(x, bt) for x, bt in zip(
                (u_prev, u, src_rows, src_cols, amp),
                (in_batched[0], in_batched[1], *in_batched[3:])))
        # an unbatched c shared by every shot stays one block
        if in_batched[2] or c.shape[0] > 1:
            c = fold(c, in_batched[2])
        out = step(u_prev, u, c, src_rows, src_cols, amp)
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return step


def leapfrog(u_prev: jnp.ndarray, u: jnp.ndarray, c: jnp.ndarray,
             src_row, src_col, amp, *, lay: Layout,
             profile: Tuple[float, ...]) -> jnp.ndarray:
    """One shot's step: u_prev, u and c (rows, cols) in the ``lay``
    storage (u_prev and u with their ghosts, :func:`embed`), the source at
    grid row ``src_row`` and column ``src_col`` with sample ``amp``, the
    damping ``profile[min(i, w)]`` at i cells from the nearest edge.
    Returns u_next in the same storage, ghosts filled. Runs in interpret
    mode on any backend but a TPU."""
    interpret = jax.default_backend() != "tpu"

    def one(x, dtype):
        return jnp.asarray(x, dtype).reshape(1)

    return _batched(lay, tuple(profile), interpret)(
        u_prev[None], u[None], c[None], one(src_row, jnp.int32),
        one(src_col, jnp.int32), one(amp, jnp.float32))[0]
