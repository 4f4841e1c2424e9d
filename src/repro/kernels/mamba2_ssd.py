"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

TPU-native formulation (DESIGN.md §4): the CUDA SSD implementation uses
warp-specialized chunk scans; here each (batch·head) runs a sequential grid
over sequence chunks with the (N, P) state in **VMEM scratch**. Within a
chunk everything is MXU matmuls: the (C·Bᵀ) score matrix, the decay-masked
intra-chunk contraction, the state readout and the rank-T_c state update —
cumulative decays again via triangular-ones matmul.

Grid: (B·H parallel, n_chunks arbitrary). Blocks: x (chunk, P), B/C
(chunk, N), dt (chunk, 1), A (1, 1); state scratch (N, P) fp32.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref,
                y_ref, sT_ref, state_ref, *, chunk: int):
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)        # (c, P)
    dt = dt_ref[0].astype(jnp.float32)      # (c, 1)
    A = a_ref[0].astype(jnp.float32)        # (1, 1)
    Bm = b_ref[0].astype(jnp.float32)       # (c, N)
    Cm = c_ref[0].astype(jnp.float32)       # (c, N)
    c = x.shape[0]

    loga = dt * A                           # (c, 1), ≤ 0
    tri_incl = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1), 1.0, 0.0)
    cum = jax.lax.dot_general(tri_incl, loga, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (c, 1)

    state = state_ref[...]                  # (N, P)
    # inter-chunk: y += (C ⊙ exp(cum)) @ state
    y = jax.lax.dot_general(Cm * jnp.exp(cum), state,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # intra-chunk: scores[t,s] = (C_t·B_s)·exp(cum_t−cum_s)·dt_s, s ≤ t
    sc = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    # difference clamped at 0: exact for s ≤ t, no overflow for s > t
    decay = jnp.exp(jnp.minimum(cum - cum.T, 0.0))      # (c_t, c_s)
    sc = sc * decay * dt.T
    sc = sc * tri_incl
    y = y + jax.lax.dot_general(sc, x, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: S' = exp(cum_c)·S + Σ_s exp(cum_c−cum_s)·dt_s·B_s ⊗ x_s
    last = jnp.exp(cum[-1:, :])             # (1, 1)
    w_s = jnp.exp(cum[-1:, :] - cum) * dt   # (c, 1)
    state_ref[...] = (state * last
                      + jax.lax.dot_general(
                          Bm * w_s, x, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))

    @pl.when(t == nt - 1)
    def _finish():
        sT_ref[0] = state_ref[...]


def ssd(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray, Bm: jnp.ndarray,
        Cm: jnp.ndarray, state0: jnp.ndarray, chunk: int = 64,
        interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B,T,H,P); dt: (B,T,H); A: (H,); Bm,Cm: (B,T,G,N);
    state0: (B,H,N,P). Returns (y (B,T,H,P), state_T fp32).
    """
    B, T, H, P = x.shape
    G = Bm.shape[2]
    hpg = H // G
    N = Bm.shape[3]
    c = min(chunk, T)
    while T % c:
        c -= 1
    nt = T // c

    xf = x.transpose(0, 2, 1, 3).reshape(B * H, T, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, T, 1)
    af = jnp.broadcast_to(A[None], (B, H)).reshape(B * H, 1, 1)
    Bh = jnp.repeat(Bm, hpg, axis=2).transpose(0, 2, 1, 3).reshape(
        B * H, T, N)
    Ch = jnp.repeat(Cm, hpg, axis=2).transpose(0, 2, 1, 3).reshape(
        B * H, T, N)
    s0 = state0.reshape(B * H, N, P)

    kernel = functools.partial(_ssd_kernel, chunk=c)
    y, sT = pl.pallas_call(
        kernel,
        grid=(B * H, nt),
        in_specs=[
            pl.BlockSpec((1, c, P), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, c, 1), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, c, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, c, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, N, P), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, P), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, N, P), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, P), x.dtype),
            jax.ShapeDtypeStruct((B * H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xf, dtf, af, Bh, Ch, s0)
    return (y.reshape(B, H, T, P).transpose(0, 2, 1, 3),
            sT.reshape(B, H, N, P))
