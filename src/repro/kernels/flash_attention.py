"""Causal flash attention as a Pallas TPU kernel.

TPU-native formulation (DESIGN.md §4): the GPU original (warp-level online
softmax over SRAM tiles) maps onto a sequential grid over kv blocks with the
running (acc, m, l) state held in **VMEM scratch** across grid steps — the
TPU grid is executed in order on each core, so the reduction axis is
declared ``arbitrary`` and scratch carries the accumulator, while the
(batch·head, q-block) axes are ``parallel``.

Tiling: q/o blocks are (block_q, hd), k/v blocks (block_k, hd); block sizes
default to 128 (MXU-aligned: the s = q·kᵀ matmul runs 128×hd×128). Causal
masking is applied only on the diagonal block; strictly-upper blocks are
skipped with ``pl.when`` (no MXU issue for masked-out tiles).

The public wrapper carries a ``custom_vjp``: forward = this kernel,
backward = the FlashAttention-2 pairs-scan from
:mod:`repro.models.attention` (recompute-from-lse, O(S) residuals) — the
standard kernel-forward/XLA-backward split.

Validated in ``tests/test_kernels.py`` against :mod:`repro.kernels.ref`
(interpret=True executes this exact kernel body on CPU).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref,
                      *, scale: float, block_q: int, block_k: int,
                      causal: bool):
    i = pl.program_id(1)          # q block index
    j = pl.program_id(2)          # kv block index
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (j <= i) if causal else (j <= nk)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (block_q, hd)
        k = k_ref[0].astype(jnp.float32)          # (block_k, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]                        # (block_q, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] + jnp.log(l))[:, 0]


def _flash_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               causal: bool, block_q: int, block_k: int,
               interpret: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q,k,v: (BH, S, hd) → (out (BH,S,hd), lse (BH,S))."""
    BH, S, hd = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / np.sqrt(hd)

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, block_q=block_q,
        block_k=block_k, causal=causal)
    out, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q), lambda b, i, j: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
            jax.ShapeDtypeStruct((BH, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out, lse


# --------------------------------------------------------------------------- #
# Public API: kernel forward + FlashAttention-2 backward
# --------------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, interpret: bool = False,
                    block_q: int = 128, block_k: int = 128):
    """q,k,v: (B, S, H, hd) MHA (kv pre-repeated for GQA). → (B,S,H,hd)."""
    out, _ = _fwd_rule(q, k, v, causal, interpret, block_q, block_k)
    return out


def _fwd_rule(q, k, v, causal, interpret, block_q, block_k):
    B, S, H, hd = q.shape
    fold = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, S, hd)  # noqa: E731
    out_f, lse_f = _flash_fwd(fold(q), fold(k), fold(v), causal,
                              block_q, block_k, interpret)
    out = out_f.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    lse = lse_f.reshape(B, H, S).transpose(0, 2, 1)    # (B, S, H)
    return out, (q, k, v, out, lse)


def _bwd_rule(causal, interpret, block_q, block_k, res, dout):
    from ..models.attention import _flash_bwd_impl
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, out, lse, dout, chunk=block_q)
    return dq, dk, dv


flash_attention.defvjp(_fwd_rule, _bwd_rule)
