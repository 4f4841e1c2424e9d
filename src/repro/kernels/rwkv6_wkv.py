"""RWKV-6 WKV recurrence as a Pallas TPU kernel (chunked).

TPU-native formulation (DESIGN.md §4): the CUDA original runs one thread per
channel stepping token-by-token; on TPU the recurrence is *chunked* — within
a chunk of T_c tokens the data-dependent-decay recurrence is evaluated as
three MXU matmuls (intra-chunk score matrix, inter-chunk state readout,
rank-T_c state update), and the (N, N) per-head WKV state is carried across
chunks in **VMEM scratch** over a sequential grid axis. Cumulative decay
sums are computed with a lower-triangular ones matmul (MXU) rather than a
serial cumsum.

Grid: (B·H parallel, n_chunks arbitrary). Blocks: r/k/v/w (chunk, N);
state scratch (N, N) fp32. Numerics match the model-side chunked reference
(fp32 state, log-space decays clamped at −30).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                o_ref, sT_ref, state_ref, *, chunk: int):
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)        # (c, N)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)        # (1, N) broadcast row
    c = r.shape[0]

    logw = jnp.log(jnp.maximum(w, 1e-8))
    # cumulative log-decay via lower-triangular matmul (MXU, not serial scan)
    tri_incl = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1), 1.0, 0.0)
    cum = jax.lax.dot_general(tri_incl, logw, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    cum = jnp.maximum(cum, -30.0)

    state = state_ref[...]
    p_prev = jnp.exp(cum - logw)            # P_{t-1}
    r_dec = r * p_prev
    # inter-chunk readout
    out = jax.lax.dot_general(r_dec, state, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    # intra-chunk (strictly lower) + diagonal bonus
    k_over = k * jnp.exp(-cum)
    scores = jax.lax.dot_general(r_dec, k_over, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    strict = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        > jax.lax.broadcasted_iota(jnp.int32, (c, c), 1), 1.0, 0.0)
    scores = scores * strict
    out = out + jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)
    out = out + diag * v
    o_ref[0] = out.astype(o_ref.dtype)

    # state update: S' = diag(P_c) S + Σ_s (P_c/P_s) k_s ⊗ v_s
    p_last = jnp.exp(cum[-1:, :])           # (1, N)
    k_scaled = k * jnp.exp(cum[-1:, :] - cum)
    state_ref[...] = (state * p_last.T
                      + jax.lax.dot_general(
                          k_scaled, v, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))

    @pl.when(t == nt - 1)
    def _finish():
        sT_ref[0] = state_ref[...]


def wkv(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
        u: jnp.ndarray, state0: jnp.ndarray, chunk: int = 64,
        interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """r,k,v,w: (B, T, H, N); u: (H, N); state0: (B, H, N, N).

    Returns (out (B,T,H,N), state_T (B,H,N,N) fp32).
    """
    B, T, H, N = r.shape
    c = min(chunk, T)
    while T % c:
        c -= 1
    nt = T // c

    fold = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, T, N)  # noqa: E731
    rf, kf, vf, wf = (fold(a) for a in (r, k, v, w))
    uf = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, 1, N)
    s0 = state0.reshape(B * H, N, N)

    kernel = functools.partial(_wkv_kernel, chunk=c)
    out, sT = pl.pallas_call(
        kernel,
        grid=(B * H, nt),
        in_specs=[
            pl.BlockSpec((1, c, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, c, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, c, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, c, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, 1, N), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, N, N), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, N), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, N, N), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, N), r.dtype),
            jax.ShapeDtypeStruct((B * H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0)
    out = out.reshape(B, H, T, N).transpose(0, 2, 1, 3)
    return out, sT.reshape(B, H, N, N)
