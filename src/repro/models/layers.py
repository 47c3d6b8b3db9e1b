"""Core transformer layers: norms, RoPE, attention (GQA / sliding / MLA), MLP.

All layers are pure functions ``f(params, x, ...) -> y`` over dict pytrees.
Compute runs in ``cfg.dtype`` (bf16 by default); params are stored in
``cfg.param_dtype`` and cast at use. Matmul-heavy ops use einsum so GSPMD
can partition them from the sharding constraints placed in transformer.py.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


def _dotf32(spec, a, b):
    """f32-accumulated matmul. On the TPU the operands stay in their
    dtype (bf16 on the MXU); XLA:CPU cannot execute every bf16 x bf16
    -> f32 dot (the MLA contractions fail in DotThunk), so other
    platforms upcast the operands first. The branch is chosen when the
    program is lowered for its platform."""
    return jax.lax.platform_dependent(
        a, b,
        tpu=lambda a, b: jnp.einsum(spec, a, b,
                                    preferred_element_type=jnp.float32),
        default=lambda a, b: jnp.einsum(spec, a.astype(jnp.float32),
                                        b.astype(jnp.float32)))


def _cast(p, dtype):
    return jax.tree.map(lambda a: a.astype(dtype) if a.dtype != dtype else a, p)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, rng, dim: Optional[int] = None):
    dim = dim or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((dim,), cfg.param_dtype)}
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((dim,), cfg.param_dtype),
                "bias": jnp.zeros((dim,), cfg.param_dtype)}
    if cfg.norm == "nonparam_ln":  # OLMo: LayerNorm without affine params
        return {}
    raise ValueError(cfg.norm)


def apply_norm(cfg: ModelConfig, params, x):
    eps = cfg.norm_eps
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + eps)
        return (y * params["scale"].astype(jnp.float32)).astype(dt)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    if cfg.norm == "layernorm":
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(dt)


def rms_norm_headwise(x, scale, eps: float = 1e-6):
    """qk_norm (qwen3): RMS-norm over the head_dim of (..., H, hd)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd) or (..., S, hd); positions: (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # (hd//2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd//2)
    if x.ndim == angles.ndim + 1:  # head axis present
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense (gated) MLP
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, rng, d_ff: Optional[int] = None):
    ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = cfg.d_model ** -0.5
    s_out = ff ** -0.5
    return {
        "w_gate": (jax.random.normal(k1, (cfg.d_model, ff)) * s_in).astype(cfg.param_dtype),
        "w_up": (jax.random.normal(k2, (cfg.d_model, ff)) * s_in).astype(cfg.param_dtype),
        "w_down": (jax.random.normal(k3, (ff, cfg.d_model)) * s_out).astype(cfg.param_dtype),
    }


def apply_mlp(cfg: ModelConfig, params, x):
    p = _cast(params, x.dtype)
    g = jnp.einsum("...d,df->...f", x, p["w_gate"])
    u = jnp.einsum("...d,df->...f", x, p["w_up"])
    h = jax.nn.silu(g) * u
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


# ---------------------------------------------------------------------------
# Attention (GQA, optional sliding window / qk_norm / cross-attention)
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, rng):
    hd = cfg.hd
    k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
    s = cfg.d_model ** -0.5
    p = {
        "wq": (jax.random.normal(k1, (cfg.d_model, cfg.n_heads, hd)) * s).astype(cfg.param_dtype),
        "wk": (jax.random.normal(k2, (cfg.d_model, cfg.n_kv_heads, hd)) * s).astype(cfg.param_dtype),
        "wv": (jax.random.normal(k3, (cfg.d_model, cfg.n_kv_heads, hd)) * s).astype(cfg.param_dtype),
        "wo": (jax.random.normal(k4, (cfg.n_heads, hd, cfg.d_model))
               * (cfg.n_heads * hd) ** -0.5).astype(cfg.param_dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), cfg.param_dtype)
        p["k_norm"] = jnp.ones((hd,), cfg.param_dtype)
    return p


NO_WINDOW = 1 << 30  # "disabled" sliding window (may be a traced per-layer value)
_Q_BLOCK = 512       # query-chunk size: caps score memory at (B,H,blk,T)


def _attend_block(q, k, v, q_pos, kv_pos, window, softcap, causal):
    """One query block. q: (B,S,H,hd)  k,v: (B,T,Hk,hd).

    Inputs stay in their storage dtype (bf16 on TPU); the MXU accumulates
    in f32 via preferred_element_type — materializing an f32 copy of a
    32k-long cache would dominate decode memory (measured: EXPERIMENTS
    §Perf-C iteration 3)."""
    b, s, h, hd = q.shape
    hk = k.shape[2]
    rep = h // hk
    qg = (q * q.dtype.type(hd ** -0.5)).reshape(b, s, hk, rep, hd)
    scores = _dotf32("bskrd,btkd->bkrst", qg, k)
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    if causal:
        m = (kv_pos[:, None, :] <= q_pos[:, :, None]) & \
            (kv_pos[:, None, :] > q_pos[:, :, None] - window)   # (B,S,T)
        scores = jnp.where(m[:, None, None], scores, jnp.float32(-1e30))
    w = jax.nn.softmax(scores, axis=-1)
    out = _dotf32("bkrst,btkd->bskrd", w.astype(v.dtype), v)
    return out.reshape(b, s, h, v.shape[-1]).astype(q.dtype)  # v dim may != q dim (MLA)


def attend(q, k, v, q_pos, kv_pos, *, window=NO_WINDOW, softcap=0.0,
           causal=True, q_block: int = _Q_BLOCK, constrain=None):
    """Query-chunked attention: peak score memory (B,H,q_block,T) instead of
    (B,H,S,T). The chunk loop is a lax.scan so the HLO stays compact and the
    backward pass naturally recomputes per-chunk (flash-like, XLA-level).

    `constrain(x, axes)` pins the sharding of the chunk inputs/outputs —
    without it GSPMD is free to pick per-chunk resharding strategies that
    put collectives INSIDE the (layers x chunks) loop nest (measured: the
    dominant collective source at baseline, see EXPERIMENTS §Perf-B)."""
    c = constrain or (lambda x, a: x)
    b, s, h, hd = q.shape
    if s <= q_block:
        return _attend_block(q, k, v, q_pos, kv_pos, window, softcap, causal)
    pad = (-s) % q_block
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1)
    nblk = q.shape[1] // q_block
    q_r = c(q.reshape(b, nblk, q_block, h, hd), 
            ("batch", "seq", "seq", "heads", "head_dim")).swapaxes(0, 1)
    p_r = q_pos.reshape(b, nblk, q_block).swapaxes(0, 1)

    def body(_, inp):
        qb, pb = inp
        ob = _attend_block(qb, k, v, pb, kv_pos, window, softcap, causal)
        return 0, c(ob, ("batch", "seq", "heads", "head_dim"))

    _, out = jax.lax.scan(body, 0, (q_r, p_r))
    out = out.swapaxes(0, 1).reshape(b, nblk * q_block, h, v.shape[-1])
    return c(out, ("batch", "seq", "heads", "head_dim"))[:, :s]


def apply_attention(cfg: ModelConfig, params, x, positions, *,
                    theta, window=NO_WINDOW, cache=None,
                    cache_index=None, kv_source=None, causal=True,
                    rope=True, precomputed_kv=None, constrain=None):
    """General attention.

    cache: None (train/prefill w/o cache) or dict(k,v:(B,T,Hk,hd)).
    cache_index: scalar write offset for decode/prefill-into-cache.
    kv_source: cross-attention source (B,T,d); non-causal, no rope; its
      computed K/V are returned as new_cache so prefill can store them.
    precomputed_kv: dict(k,v) — reuse cached cross K/V (decode).
    Returns (out, new_cache).
    """
    c = constrain or (lambda y, a: y)
    p = _cast(params, x.dtype)
    q = c(jnp.einsum("bsd,dhk->bshk", x, p["wq"]),
          ("batch", "seq", "heads", "head_dim"))
    if precomputed_kv is not None:
        k = precomputed_kv["k"].astype(q.dtype)
        v = precomputed_kv["v"].astype(q.dtype)
    else:
        src = kv_source if kv_source is not None else x
        k = jnp.einsum("btd,dhk->bthk", src, p["wk"])
        v = jnp.einsum("btd,dhk->bthk", src, p["wv"])
    if cfg.qk_norm:
        q = rms_norm_headwise(q, p["q_norm"])
        if precomputed_kv is None:
            k = rms_norm_headwise(k, p["k_norm"])
    cross = kv_source is not None or precomputed_kv is not None
    if rope and not cross:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)

    new_cache = None
    if cache is not None and not cross:
        if "pos" in cache:
            # Ring-buffer cache of size W for sliding-window layers: slot
            # p %% W holds position p; a stored `pos` array both masks
            # garbage slots (init -NO_WINDOW) and feeds attend()'s window
            # mask, so the newest W tokens are always addressable without
            # a full-length cache (EXPERIMENTS §Perf-D).
            w_sz = cache["k"].shape[1]
            if x.shape[1] >= w_sz:
                # prefill: attend over the full in-flight K/V, then STORE
                # only the last W tokens; with S %% W == 0 the slot layout
                # (p %% W) is exactly their order
                assert x.shape[1] % w_sz == 0, (x.shape[1], w_sz)
                out = attend(q, k, v, positions, positions, window=window,
                             softcap=0.0, causal=causal, constrain=constrain)
                ck = k[:, -w_sz:].astype(cache["k"].dtype)
                cv = v[:, -w_sz:].astype(cache["v"].dtype)
                cpos = positions[:, -w_sz:].astype(cache["pos"].dtype)
            else:
                slot = jnp.mod(jnp.asarray(cache_index, jnp.int32), w_sz)
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
                cpos = jax.lax.dynamic_update_slice_in_dim(
                    cache["pos"], positions.astype(cache["pos"].dtype),
                    slot, axis=1)
                out = attend(q, ck.astype(q.dtype), cv.astype(q.dtype),
                             positions, cpos, window=window, softcap=0.0,
                             causal=causal, constrain=constrain)
            new_cache = {"k": ck, "v": cv, "pos": cpos}
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1)
            t = ck.shape[1]
            kv_pos = jnp.broadcast_to(
                jnp.arange(t, dtype=positions.dtype)[None], (x.shape[0], t))
            out = attend(q, ck.astype(q.dtype), cv.astype(q.dtype),
                         positions, kv_pos, window=window, softcap=0.0,
                         causal=causal, constrain=constrain)
            new_cache = {"k": ck, "v": cv}
    else:
        kv_pos = (positions if (kv_source is None and precomputed_kv is None)
                  else jnp.broadcast_to(
                      jnp.arange(k.shape[1], dtype=positions.dtype)[None],
                      (x.shape[0], k.shape[1])))
        out = attend(q, k, v, positions, kv_pos, window=window,
                     softcap=0.0, causal=causal and not cross,
                     constrain=constrain)
        if kv_source is not None:
            # cross-attention prefill: hand K/V back for caching
            new_cache = {"k": k, "v": v}
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V3)
# ---------------------------------------------------------------------------
# Cache stores only the compressed latent c_kv (kv_lora_rank) and the shared
# rope key k_r (qk_rope_dim) per token. Prefill uses the expanded form;
# decode uses the absorbed form (W_uk folded into the query, W_uv into the
# output) so the 32k-long cache is never re-expanded per step.

def init_mla(cfg: ModelConfig, rng):
    """With q_lora_rank 0 (Moonlight) the query is one projection
    `w_q`; otherwise (DeepSeek-V3) it goes through the normed rank
    q_lora_rank: `w_dq`, `q_norm`, `w_uq`."""
    d, h = cfg.d_model, cfg.n_heads
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(rng, 8)
    s = d ** -0.5
    if r_q:
        p = {
            "w_dq": (jax.random.normal(ks[0], (d, r_q)) * s).astype(cfg.param_dtype),
            "q_norm": jnp.ones((r_q,), cfg.param_dtype),
            "w_uq": (jax.random.normal(ks[1], (r_q, h, dn + dr)) * r_q ** -0.5).astype(cfg.param_dtype),
        }
    else:
        p = {"w_q": (jax.random.normal(ks[0], (d, h, dn + dr)) * s).astype(cfg.param_dtype)}
    p.update({
        "w_dkv": (jax.random.normal(ks[2], (d, r_kv)) * s).astype(cfg.param_dtype),
        "kv_norm": jnp.ones((r_kv,), cfg.param_dtype),
        "w_kr": (jax.random.normal(ks[3], (d, dr)) * s).astype(cfg.param_dtype),
        "w_uk": (jax.random.normal(ks[4], (r_kv, h, dn)) * r_kv ** -0.5).astype(cfg.param_dtype),
        "w_uv": (jax.random.normal(ks[5], (r_kv, h, dv)) * r_kv ** -0.5).astype(cfg.param_dtype),
        "wo": (jax.random.normal(ks[6], (h, dv, d)) * (h * dv) ** -0.5).astype(cfg.param_dtype),
    })
    return p


def _mla_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def apply_mla(cfg: ModelConfig, params, x, positions, *, theta,
              cache=None, cache_index=None, constrain=None):
    """MLA attention. cache: dict(c_kv:(B,T,r_kv), k_rope:(B,T,dr)).

    Single-token decode uses the absorbed form (W_uk folded into the query,
    W_uv into the output) so the long latent cache is attended in rank
    r_kv space and never re-expanded. Multi-token paths expand K/V once and
    reuse the chunked ``attend``.
    """
    c = constrain or (lambda y, a: y)
    p = _cast(params, x.dtype)
    b, s, _ = x.shape
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    scale = (dn + dr) ** -0.5

    eps = cfg.norm_eps

    if cfg.q_lora_rank:
        cq = _mla_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dq"]), p["q_norm"],
                       eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"])
    q = c(q, ("batch", "seq", "heads", "head_dim"))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, theta)

    c_kv = _mla_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dkv"]), p["kv_norm"],
                     eps)
    k_r = apply_rope(jnp.einsum("bsd,dr->bsr", x, p["w_kr"]), positions, theta)

    new_cache = None
    if cache is not None:
        c_all = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), cache_index, axis=1)
        r_all = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_r.astype(cache["k_rope"].dtype), cache_index, axis=1)
        new_cache = {"c_kv": c_all, "k_rope": r_all}
        t = c_all.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(t, dtype=positions.dtype)[None], (b, t))
        c_use, r_use = c_all.astype(x.dtype), r_all.astype(x.dtype)
    else:
        kv_pos = positions
        c_use, r_use = c_kv, k_r

    if s == 1 and cache is not None:
        with jax.named_scope("mla.decode"):
            # Absorbed decode: scores in latent space, O(T * r_kv) per head set.
            q_lat = _dotf32("bshk,rhk->bshr", q_nope, p["w_uk"]).astype(x.dtype)
            scores = (_dotf32("bshr,btr->bhst", q_lat, c_use)
                      + _dotf32("bshk,btk->bhst", q_rope, r_use)) * scale
            mask = (kv_pos[:, None, :] <= positions[:, :, None])[:, None]  # (B,1,S,T)
            scores = jnp.where(mask, scores, jnp.float32(-1e30))
            w = jax.nn.softmax(scores, axis=-1)
            o_lat = _dotf32("bhst,btr->bshr", w.astype(x.dtype), c_use)
            out = _dotf32("bshr,rhv->bshv", o_lat.astype(x.dtype),
                          p["w_uv"]).astype(x.dtype)
    else:
        # Expanded form (train / prefill): chunked attention, MHA (rep=1).
        t = c_use.shape[1]
        hax = ("batch", "seq", "heads", "head_dim")
        k_nope = c(jnp.einsum("btr,rhk->bthk", c_use, p["w_uk"]), hax)
        vv = c(jnp.einsum("btr,rhv->bthv", c_use, p["w_uv"]), hax)
        k_full = c(jnp.concatenate(
            [k_nope, jnp.broadcast_to(r_use[:, :, None, :],
                                      (b, t, cfg.n_heads, dr))], axis=-1), hax)
        q_full = c(jnp.concatenate([q_nope, q_rope], axis=-1), hax)
        # attend() scales by q.hd^-0.5 = (dn+dr)^-0.5, which equals `scale`.
        out = attend(q_full, k_full, vv, positions, kv_pos,
                     constrain=constrain)
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
    return y, new_cache
