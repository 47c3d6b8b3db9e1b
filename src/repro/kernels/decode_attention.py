"""Pallas TPU kernel: flash-decode — one query token vs a long KV cache.

Grid (B, H, nK): the KV cache is streamed through VMEM in (block_k, dh)
panels with online-softmax accumulators in scratch (running max, running
denominator, fp32 (dh,) accumulator). A per-sequence valid length masks
the unwritten cache tail. This mirrors the cross-"model"-axis
flash-decoding the sharded serving path gets from GSPMD, applied within a
single chip (DESIGN.md §5).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale, block_k, n_k):
    i_b = pl.program_id(0)
    i_k = pl.program_id(2)

    @pl.when(i_k == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)             # (1, dh) row
    k = k_ref[0, 0].astype(jnp.float32)             # (BK, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = i_k * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    valid = kpos < len_ref[i_b]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    corr = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])                  # (1, BK)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    v = v_ref[0, 0].astype(jnp.float32)              # (BK, dh)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(i_k == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, kv_len, *, block_k: int = 256,
                            interpret: bool = False):
    """q: (B,H,dh); k/v: (B,T,Hk,dh); kv_len: (B,) valid lengths.
    Returns (B,H,dh)."""
    b, h, dh = q.shape
    t, hk = k.shape[1], k.shape[2]
    rep = h // hk
    assert t % block_k == 0, (t, block_k)
    n_k = t // block_k
    qt = q[:, :, None, :]                            # (B,H,1,dh)
    kt = k.transpose(0, 2, 1, 3)                     # (B,Hk,T,dh)
    vt = v.transpose(0, 2, 1, 3)
    scale = dh ** -0.5

    out = pl.pallas_call(
        partial(_decode_kernel, scale=scale, block_k=block_k, n_k=n_k),
        grid=(b, h, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # kv_len, whole array
            pl.BlockSpec((1, 1, 1, dh), lambda b_, h_, ik: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b_, h_, ik, rep=rep: (b_, h_ // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b_, h_, ik, rep=rep: (b_, h_ // rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, dh), lambda b_, h_, ik: (b_, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qt, kt, vt)
    return out[:, :, 0, :]
