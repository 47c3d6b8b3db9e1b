"""Pallas TPU kernel: blocked flash attention (prefill), causal + optional
sliding window, GQA-aware.

Grid (B, H, nQ, nK) with the K axis innermost ("arbitrary" semantics):
each (b, h, iq) revisits its output block across K panels carrying the
online-softmax state (running max m, denominator l, fp32 accumulator) in
VMEM scratch. K/V panels for GQA are indexed at h // rep so query heads
sharing a KV head stream the same panels.

Block shapes are MXU-aligned; the (block_q, block_k) score tile and the
(block_q, dh) accumulator bound VMEM use.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, window, block_q, block_k, n_k):
    i_q = pl.program_id(2)
    i_k = pl.program_id(3)

    @pl.when(i_k == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (BQ, dh)
    k = k_ref[0, 0].astype(jnp.float32)            # (BK, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        qpos = i_q * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = i_k * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    corr = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(i_k == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: (B,S,H,dh); k/v: (B,S,Hk,dh); S divisible by blocks. -> (B,S,H,dh)."""
    b, s, h, dh = q.shape
    hk = k.shape[2]
    rep = h // hk
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    n_q, n_k = s // block_q, s // block_k
    qt = q.transpose(0, 2, 1, 3)                   # (B,H,S,dh)
    kt = k.transpose(0, 2, 1, 3)                   # (B,Hk,S,dh)
    vt = v.transpose(0, 2, 1, 3)
    scale = dh ** -0.5

    out = pl.pallas_call(
        partial(_flash_kernel, scale=scale, causal=causal, window=window,
                block_q=block_q, block_k=block_k, n_k=n_k),
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b_, h_, iq, ik, rep=rep: (b_, h_ // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b_, h_, iq, ik, rep=rep: (b_, h_ // rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
