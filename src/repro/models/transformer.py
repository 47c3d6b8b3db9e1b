"""Model assembly: stacks, caches, and the train/prefill/decode entry points.

Layer stacks are ``lax.scan``-ed over stacked parameter pytrees so the HLO
stays one-layer-sized regardless of depth (critical for the 512-device
dry-run compiles). Heterogeneous architectures decompose into homogeneous
stacks:

  dense / vlm      embed -> blocks(L) -> norm -> head
  gemma3           same, with per-layer (window, theta) arrays as scan xs
  moe (phi/ds3)    dense_blocks(first_k) -> moe_blocks(L-k)
  ssm (mamba2)     ssm blocks(L)
  hybrid (zamba2)  scan over G groups of [(period-1) ssm blocks + one
                   weight-SHARED attention block], plus an ssm tail
  encdec (whisper) enc blocks(Le, bidirectional) -> dec blocks(L) with
                   cross-attention; conv/mel frontend is a stub upstream

Sharding is injected via the ``constrain(x, logical_axes)`` callback so the
model code stays mesh-agnostic; ``repro.sharding`` provides the real one.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.config import ModelConfig

Pytree = Any
NO_WINDOW = L.NO_WINDOW


def _noconstrain(x, axes):
    return x


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_attn_block(cfg: ModelConfig, rng, *, moe=False, cross=False):
    ks = jax.random.split(rng, 6)
    p = {"attn_norm": L.init_norm(cfg, ks[0]),
         "mlp_norm": L.init_norm(cfg, ks[1])}
    p["attn"] = (L.init_mla(cfg, ks[2]) if cfg.attn_kind == "mla"
                 else L.init_attention(cfg, ks[2]))
    if cross:
        p["cross_norm"] = L.init_norm(cfg, ks[3])
        p["cross"] = L.init_attention(cfg, ks[4])
    p["ffn"] = MOE.init_moe(cfg, ks[5]) if moe else L.init_mlp(cfg, ks[5])
    return p


def _init_ssm_block(cfg: ModelConfig, rng):
    k1, k2 = jax.random.split(rng)
    return {"norm": L.init_norm(cfg, k1), "mamba": SSM.init_mamba2(cfg, k2)}


def _stacked(init_fn, rng, n: int):
    if n == 0:
        return None
    return jax.vmap(init_fn)(jax.random.split(rng, n))


def init_params(cfg: ModelConfig, rng) -> Pytree:
    cfg.validate()
    ks = jax.random.split(rng, 10)
    p: Dict[str, Any] = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model))
                  * cfg.d_model ** -0.5).astype(cfg.param_dtype),
        "final_norm": L.init_norm(cfg, ks[1]),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(ks[2], (cfg.d_model, cfg.vocab))
                        * cfg.d_model ** -0.5).astype(cfg.param_dtype)

    at = cfg.arch_type
    if at in ("dense", "vlm"):
        p["blocks"] = _stacked(partial(_init_attn_block, cfg), ks[3], cfg.n_layers)
    elif at == "moe":
        k_d = cfg.first_k_dense
        p["dense_blocks"] = _stacked(partial(_init_attn_block, cfg, moe=False),
                                     ks[3], k_d)
        p["moe_blocks"] = _stacked(partial(_init_attn_block, cfg, moe=True),
                                   ks[4], cfg.n_layers - k_d)
    elif at == "ssm":
        p["blocks"] = _stacked(partial(_init_ssm_block, cfg), ks[3], cfg.n_layers)
    elif at == "hybrid":
        per = cfg.hybrid_period - 1
        n_groups = cfg.n_layers // cfg.hybrid_period
        tail = cfg.n_layers - n_groups * cfg.hybrid_period
        p["groups"] = jax.vmap(
            lambda k: _stacked(partial(_init_ssm_block, cfg), k, per)
        )(jax.random.split(ks[3], n_groups))
        p["shared_attn"] = _init_attn_block(cfg, ks[4])
        p["tail"] = _stacked(partial(_init_ssm_block, cfg), ks[5], tail)
    elif at == "encdec":
        p["enc_blocks"] = _stacked(partial(_init_attn_block, cfg), ks[3],
                                   cfg.n_enc_layers)
        p["enc_norm"] = L.init_norm(cfg, ks[6])
        p["blocks"] = _stacked(partial(_init_attn_block, cfg, cross=True),
                               ks[4], cfg.n_layers)
    else:
        raise ValueError(at)

    if cfg.mtp_depth:
        p["mtp"] = {
            "proj": (jax.random.normal(ks[7], (2 * cfg.d_model, cfg.d_model))
                     * (2 * cfg.d_model) ** -0.5).astype(cfg.param_dtype),
            "block": _init_attn_block(cfg, ks[8]),
            "norm_h": L.init_norm(cfg, ks[9]),
            "norm_e": L.init_norm(cfg, ks[9]),
        }
    return p


# ---------------------------------------------------------------------------
# Caches (decode state). Leading dim stacks layers for scanning.
# ---------------------------------------------------------------------------

def _kv_shape(cfg: ModelConfig, batch: int, max_len: int):
    if cfg.attn_kind == "mla":
        return {"c_kv": (batch, max_len, cfg.kv_lora_rank),
                "k_rope": (batch, max_len, cfg.qk_rope_dim)}
    return {"k": (batch, max_len, cfg.n_kv_heads, cfg.hd),
            "v": (batch, max_len, cfg.n_kv_heads, cfg.hd)}


def _zeros_tree(shapes, dtype, lead=()):
    return jax.tree.map(lambda s: jnp.zeros(lead + s, dtype), shapes,
                        is_leaf=lambda s: isinstance(s, tuple))


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Pytree:
    at = cfg.arch_type
    kv = _kv_shape(cfg, batch, max_len) if at != "ssm" else None
    gn = cfg.ssm_ngroups * cfg.ssm_state
    ssm_shapes = {
        "conv_x": (batch, cfg.ssm_conv - 1, cfg.d_inner),
        "conv_B": (batch, cfg.ssm_conv - 1, gn),
        "conv_C": (batch, cfg.ssm_conv - 1, gn),
        "ssm": (batch, cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state),
    } if at in ("ssm", "hybrid") else None

    if at in ("dense", "vlm"):
        if cfg.window_cache and cfg.local_global_ratio and cfg.sliding_window:
            period = cfg.local_global_ratio + 1
            assert cfg.n_layers % period == 0, (cfg.n_layers, period)
            g = cfg.n_layers // period
            r = period - 1
            w = min(cfg.sliding_window, max_len)
            kv_l = {"k": (batch, w, cfg.n_kv_heads, cfg.hd),
                    "v": (batch, w, cfg.n_kv_heads, cfg.hd)}
            local = _zeros_tree(kv_l, dtype, (g, r))
            local["pos"] = jnp.full((g, r, batch, w), -(1 << 30), jnp.int32)
            return {"kv_local": local,
                    "kv_global": _zeros_tree(kv, dtype, (g,))}
        return {"kv": _zeros_tree(kv, dtype, (cfg.n_layers,))}
    if at == "moe":
        k_d = cfg.first_k_dense
        c = {}
        if k_d:
            c["kv_dense"] = _zeros_tree(kv, dtype, (k_d,))
        c["kv_moe"] = _zeros_tree(kv, dtype, (cfg.n_layers - k_d,))
        # the expert layers' counters (moe.py), accumulated on the device
        # over every call through this cache
        c["moe_stats"] = MOE.zero_stats()
        return c
    if at == "ssm":
        return {"ssm": _zeros_tree(ssm_shapes, jnp.float32, (cfg.n_layers,))}
    if at == "hybrid":
        per = cfg.hybrid_period - 1
        g = cfg.n_layers // cfg.hybrid_period
        tail = cfg.n_layers - g * cfg.hybrid_period
        c = {"groups_ssm": _zeros_tree(ssm_shapes, jnp.float32, (g, per)),
             "attn": _zeros_tree(kv, dtype, (g,))}
        if tail:
            c["tail_ssm"] = _zeros_tree(ssm_shapes, jnp.float32, (tail,))
        return c
    if at == "encdec":
        f = cfg.n_audio_frames
        return {"kv": _zeros_tree(kv, dtype, (cfg.n_layers,)),
                "cross": _zeros_tree(
                    {"k": (batch, f, cfg.n_kv_heads, cfg.hd),
                     "v": (batch, f, cfg.n_kv_heads, cfg.hd)},
                    dtype, (cfg.n_layers,))}
    raise ValueError(at)


# ---------------------------------------------------------------------------
# Per-layer theta / window schedules (gemma3 local:global pattern)
# ---------------------------------------------------------------------------

def _layer_schedules(cfg: ModelConfig, kinds):
    theta = np.array([
        cfg.rope_theta_global if k == "global" and cfg.rope_theta_global
        else cfg.rope_theta for k in kinds], np.float32)
    window = np.array([
        cfg.sliding_window if (k == "local" and cfg.sliding_window)
        else NO_WINDOW for k in kinds], np.int32)
    return jnp.asarray(theta), jnp.asarray(window)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _attn_block(cfg, p, x, positions, theta, window, kv_cache, cache_index,
                *, moe, mesh, constrain, enc_out=None, cross_cache=None,
                rope=True, train=False):
    """Returns (x, new_kv, new_cross, aux); with moe, aux is (aux loss,
    the expert layer's counters)."""
    h = L.apply_norm(cfg, p["attn_norm"], x)
    if cfg.attn_kind == "mla":
        a, new_kv = L.apply_mla(cfg, p["attn"], h, positions, theta=theta,
                                cache=kv_cache, cache_index=cache_index,
                                constrain=constrain)
    else:
        a, new_kv = L.apply_attention(
            cfg, p["attn"], h, positions, theta=theta, window=window,
            cache=kv_cache, cache_index=cache_index, rope=rope,
            constrain=constrain)
    x = constrain(x + a, ("batch", "seq", "embed"))
    if enc_out is not None or cross_cache is not None:
        h = L.apply_norm(cfg, p["cross_norm"], x)
        c, new_cross = L.apply_attention(
            cfg, p["cross"], h, positions, theta=theta, kv_source=enc_out,
            causal=False, precomputed_kv=cross_cache, rope=False,
            constrain=constrain)
        x = x + c
    else:
        new_cross = None
    h = L.apply_norm(cfg, p["mlp_norm"], x)
    if moe:
        y, loss, stats = MOE.apply_moe(cfg, p["ffn"], h, mesh, train=train)
        aux = (loss, stats)
    else:
        y, aux = L.apply_mlp(cfg, p["ffn"], h), jnp.float32(0.0)
    x = constrain(x + y, ("batch", "seq", "embed"))
    return x, new_kv, new_cross, aux


def _ssm_block(cfg, p, x, ssm_cache, constrain):
    h = L.apply_norm(cfg, p["norm"], x)
    y, new_cache = SSM.apply_mamba2(cfg, p["mamba"], h, cache=ssm_cache)
    return constrain(x + y, ("batch", "seq", "embed")), new_cache


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------

def _maybe_ckpt(cfg, train, fn):
    return jax.checkpoint(fn) if (cfg.remat and train) else fn


def _run_attn_stack(cfg, blocks, x, positions, cache, cache_index, kinds,
                    mesh, constrain, train, *, moe=False, enc_out=None,
                    cross_cache=None, rope=True):
    """Scan a stacked homogeneous attention stack. cache may be None.
    Returns (x, aux, new_cache, counters); counters (moe.py) only for
    an expert stack, else None.

    Cross-attention: during encdec prefill (enc_out given) the per-layer
    cross K/V are collected into ys so the caller can cache them; during
    decode the existing cross_cache is read per-layer via scan xs.
    """
    theta_arr, window_arr = _layer_schedules(cfg, kinds)
    has_cache = cache is not None
    read_cross = cross_cache is not None
    emit_cross = has_cache and enc_out is not None

    def body(carry, xs):
        x, aux = carry[:2]
        p = xs[0]
        theta, window = xs[1], xs[2]
        idx = 3
        kv = None
        if has_cache:
            kv = xs[idx]; idx += 1
        cc = None
        if read_cross:
            cc = xs[idx]; idx += 1
        x, new_kv, new_cross, a = _attn_block(
            cfg, p, x, positions, theta, window, kv, cache_index,
            moe=moe, mesh=mesh, constrain=constrain, enc_out=enc_out,
            cross_cache=cc, rope=rope, train=train)
        if moe:
            carry = (x, aux + a[0], MOE.merge_stats(carry[2], a[1]))
        else:
            carry = (x, aux + a)
        if not has_cache:
            ys = 0
        elif emit_cross:
            ys = (new_kv, new_cross)
        else:
            ys = (new_kv,)
        return carry, ys

    xs = [blocks, theta_arr, window_arr]
    if has_cache:
        xs.append(cache)
    if read_cross:
        xs.append(cross_cache)
    carry = (x, jnp.float32(0.0)) + ((MOE.zero_stats(),) if moe else ())
    carry, ys = jax.lax.scan(_maybe_ckpt(cfg, train, body), carry, tuple(xs))
    new_cache = ys if has_cache else None
    return carry[0], carry[1], new_cache, (carry[2] if moe else None)


def _run_ssm_stack(cfg, blocks, x, cache, constrain, train):
    has_cache = cache is not None

    def body(x, xs):
        p = xs[0]
        c = xs[1] if has_cache else None
        x, nc = _ssm_block(cfg, p, x, c, constrain)
        return x, (nc if has_cache else 0)

    xs = (blocks, cache) if has_cache else (blocks,)
    x, ys = jax.lax.scan(_maybe_ckpt(cfg, train, body), x, xs)
    return x, (ys if has_cache else None)


def _run_hybrid(cfg, params, x, positions, cache, cache_index, mesh,
                constrain, train):
    has_cache = cache is not None
    shared = params["shared_attn"]

    def group_body(carry, xs):
        x, aux = carry
        gp = xs[0]
        g_ssm = xs[1] if has_cache else None
        g_kv = xs[2] if has_cache else None
        x, new_ssm = _run_ssm_stack(cfg, gp, x, g_ssm, constrain, train)
        x, new_kv, _, a = _attn_block(
            cfg, shared, x, positions, jnp.float32(cfg.rope_theta),
            NO_WINDOW, g_kv, cache_index, moe=False, mesh=mesh,
            constrain=constrain)
        ys = (new_ssm, new_kv) if has_cache else 0
        return (x, aux + a), ys

    xs = [params["groups"]]
    if has_cache:
        xs += [cache["groups_ssm"], cache["attn"]]
    (x, aux), ys = jax.lax.scan(_maybe_ckpt(cfg, train, group_body),
                                (x, jnp.float32(0.0)), tuple(xs))
    new_cache = None
    if has_cache:
        new_cache = {"groups_ssm": ys[0], "attn": ys[1]}
    if params.get("tail") is not None:
        t_cache = cache.get("tail_ssm") if has_cache else None
        x, new_tail = _run_ssm_stack(cfg, params["tail"], x, t_cache,
                                     constrain, train)
        if has_cache:
            new_cache["tail_ssm"] = new_tail
    return x, aux, new_cache


def _run_windowed_dense(cfg, params, x, positions, cache, cache_index,
                        mesh, constrain, train):
    """Serving path for local:global stacks with ring-buffer local caches.

    The homogeneous (L,) layer stack regroups into G groups of
    [(period-1) local layers + 1 global layer] so the two cache shapes
    ((B,W,...) ring vs (B,T,...) full) each live in their own scan."""
    period = cfg.local_global_ratio + 1
    g = cfg.n_layers // period
    r = period - 1
    resh = lambda a: a.reshape((g, period) + a.shape[1:])
    local_p = jax.tree.map(lambda a: resh(a)[:, :r], params["blocks"])
    glob_p = jax.tree.map(lambda a: resh(a)[:, r], params["blocks"])
    th_l = jnp.float32(cfg.rope_theta)
    th_g = jnp.float32(cfg.rope_theta_global or cfg.rope_theta)
    win = jnp.int32(cfg.sliding_window)

    def local_body(carry, xs):
        x, aux = carry
        p, kv = xs
        x, nkv, _, a = _attn_block(cfg, p, x, positions, th_l, win, kv,
                                   cache_index, moe=False, mesh=mesh,
                                   constrain=constrain)
        return (x, aux + a), (nkv,)

    def group_body(carry, xs):
        x, aux = carry
        lp, gp, lc, gc = xs
        (x, aux), lys = jax.lax.scan(_maybe_ckpt(cfg, train, local_body),
                                     (x, aux), (lp, lc))
        x, ngc, _, a = _attn_block(cfg, gp, x, positions, th_g, NO_WINDOW,
                                   gc, cache_index, moe=False, mesh=mesh,
                                   constrain=constrain)
        return (x, aux + a), (lys[0], ngc)

    (x, aux), ys = jax.lax.scan(
        _maybe_ckpt(cfg, train, group_body), (x, jnp.float32(0.0)),
        (local_p, glob_p, cache["kv_local"], cache["kv_global"]))
    return x, aux, {"kv_local": ys[0], "kv_global": ys[1]}


# ---------------------------------------------------------------------------
# Embedding / heads
# ---------------------------------------------------------------------------

def _embed(cfg, params, tokens, constrain):
    e = params["embed"].astype(jnp.dtype(cfg.dtype))
    x = jnp.take(e, tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, jnp.dtype(cfg.dtype))
    return constrain(x, ("batch", "seq", "embed"))


def _logits(cfg, params, x, constrain):
    if cfg.tie_embeddings:
        w = params["embed"].astype(x.dtype)
        logits = jnp.einsum("bsd,vd->bsv", x, w)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return constrain(logits, ("batch", "seq", "vocab"))


def _sinusoidal_pos(positions, d: int):
    """Absolute sinusoidal embedding computed from (B,S) positions —
    table-free so 32k+ contexts cost no memory (whisper has no rope)."""
    i = jnp.arange(d // 2, dtype=jnp.float32)
    ang = positions[..., None].astype(jnp.float32) / \
        jnp.power(10000.0, 2.0 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _encode(cfg, params, enc_embeds, mesh, constrain, train):
    """Whisper encoder over stub frame embeddings (B, F, d)."""
    x = enc_embeds.astype(jnp.dtype(cfg.dtype))
    b, f, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(f, dtype=jnp.int32)[None], (b, f))
    x = x + _sinusoidal_pos(positions, cfg.d_model).astype(x.dtype)
    x = constrain(x, ("batch", "seq", "embed"))

    def body(carry, p):
        x, aux = carry
        h = L.apply_norm(cfg, p["attn_norm"], x)
        a, _ = L.apply_attention(cfg, p["attn"], h, positions,
                                 theta=jnp.float32(cfg.rope_theta),
                                 causal=False, rope=False,
                                 constrain=constrain)
        x = x + a
        h = L.apply_norm(cfg, p["mlp_norm"], x)
        x = constrain(x + L.apply_mlp(cfg, p["ffn"], h),
                      ("batch", "seq", "embed"))
        return (x, aux), 0

    (x, _), _ = jax.lax.scan(_maybe_ckpt(cfg, train, body),
                             (x, jnp.float32(0.0)), params["enc_blocks"])
    return L.apply_norm(cfg, params["enc_norm"], x)


# ---------------------------------------------------------------------------
# Full forward
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params, batch, *, cache=None, cache_index=0,
            mesh=None, constrain: Callable = _noconstrain, train=False):
    """Returns (logits, aux_loss, new_cache, hidden).

    batch keys: tokens (B,S); positions (B,S) optional; enc_embeds (B,F,d)
    for encdec; img_embeds (B,N,d) for vlm.
    """
    at = cfg.arch_type
    tokens = batch["tokens"]
    b, s_tok = tokens.shape
    x = _embed(cfg, params, tokens, constrain)

    if at == "vlm" and batch.get("img_embeds") is not None:
        img = batch["img_embeds"].astype(x.dtype)
        x = jnp.concatenate([img, x], axis=1)
        x = constrain(x, ("batch", "seq", "embed"))
    s = x.shape[1]

    if "positions" in batch and batch["positions"] is not None:
        positions = batch["positions"]
        if at == "vlm" and s != s_tok:
            positions = jnp.concatenate(
                [jnp.broadcast_to(jnp.arange(s - s_tok, dtype=jnp.int32)[None],
                                  (b, s - s_tok)), positions], axis=1)
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    if at == "encdec":  # whisper: absolute positions, no rope
        x = x + _sinusoidal_pos(positions, cfg.d_model).astype(x.dtype)

    kinds = cfg.layer_kinds()
    aux = jnp.float32(0.0)
    new_cache: Optional[Dict[str, Any]] = None

    if at in ("dense", "vlm"):
        if cache and cfg.window_cache and cfg.local_global_ratio \
                and cfg.sliding_window:
            x, aux, new_cache = _run_windowed_dense(
                cfg, params, x, positions, cache, cache_index, mesh,
                constrain, train)
        else:
            kv = cache["kv"] if cache else None
            x, aux, nkv, _ = _run_attn_stack(
                cfg, params["blocks"], x, positions, kv, cache_index, kinds,
                mesh, constrain, train)
            if cache:
                new_cache = {"kv": nkv[0]}
    elif at == "moe":
        k_d = cfg.first_k_dense
        if k_d:
            kvd = cache["kv_dense"] if cache else None
            x, a1, nkvd, _ = _run_attn_stack(
                cfg, params["dense_blocks"], x, positions, kvd, cache_index,
                kinds[:k_d], mesh, constrain, train, moe=False)
            aux += a1
        kvm = cache["kv_moe"] if cache else None
        x, a2, nkvm, stats = _run_attn_stack(
            cfg, params["moe_blocks"], x, positions, kvm, cache_index,
            kinds[k_d:], mesh, constrain, train, moe=True)
        aux += a2
        if cache:
            new_cache = {"kv_moe": nkvm[0],
                         "moe_stats": MOE.merge_stats(cache["moe_stats"],
                                                      stats)}
            if k_d:
                new_cache["kv_dense"] = nkvd[0]
    elif at == "ssm":
        c = cache["ssm"] if cache else None
        x, nssm = _run_ssm_stack(cfg, params["blocks"], x, c, constrain, train)
        if cache:
            new_cache = {"ssm": nssm}
    elif at == "hybrid":
        x, aux, new_cache = _run_hybrid(cfg, params, x, positions, cache,
                                        cache_index, mesh, constrain, train)
    elif at == "encdec":
        if batch.get("enc_embeds") is not None:
            enc_out = _encode(cfg, params, batch["enc_embeds"], mesh,
                              constrain, train)
            cross_kv = None
        else:
            enc_out = None  # decode: use cached cross K/V
            cross_kv = cache["cross"]
        kv = cache["kv"] if cache else None
        x, aux, ys, _ = _run_attn_stack(
            cfg, params["blocks"], x, positions, kv, cache_index, kinds,
            mesh, constrain, train, enc_out=enc_out,
            cross_cache=cross_kv, rope=False)
        if cache:
            new_cache = {"kv": ys[0],
                         "cross": ys[1] if enc_out is not None else cross_kv}
    else:
        raise ValueError(at)

    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = _logits(cfg, params, x, constrain)
    return logits, aux, new_cache, x


# ---------------------------------------------------------------------------
# Losses / steps
# ---------------------------------------------------------------------------

def cross_entropy(logits, targets, mask):
    lse = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(lse, targets[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    m = mask.astype(jnp.float32)
    return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)


def loss_fn(cfg: ModelConfig, params, batch, *, mesh=None,
            constrain: Callable = _noconstrain):
    logits, aux, _, hidden = forward(cfg, params, batch, mesh=mesh,
                                     constrain=constrain, train=True)
    targets = batch["targets"]
    s_t = targets.shape[1]
    logits_t = logits[:, -s_t:]  # vlm: loss only over the text positions
    mask = targets >= 0
    loss = cross_entropy(logits_t, jnp.maximum(targets, 0), mask)
    metrics = {"ce": loss, "aux": aux}
    total = loss + cfg.aux_loss_coef * aux

    if cfg.mtp_depth and "mtp" in params:
        # DeepSeek-style multi-token prediction: predict t+2 from
        # (hidden_t, embed(token_{t+1})) through one extra block.
        mp = params["mtp"]
        h = L.apply_norm(cfg, mp["norm_h"], hidden[:, :-1])
        e_next = L.apply_norm(
            cfg, mp["norm_e"],
            _embed(cfg, params, batch["tokens"][:, 1:], constrain))
        hcat = jnp.concatenate([h, e_next], axis=-1)
        hm = jnp.einsum("bsd,dk->bsk", hcat, mp["proj"].astype(hcat.dtype))
        b2, s2, _ = hm.shape
        pos2 = jnp.broadcast_to(jnp.arange(s2, dtype=jnp.int32)[None], (b2, s2))
        hm, _, _, _ = _attn_block(cfg, mp["block"], hm, pos2,
                                  jnp.float32(cfg.rope_theta), NO_WINDOW,
                                  None, 0, moe=False, mesh=mesh,
                                  constrain=constrain)
        mtp_logits = _logits(cfg, params, hm, constrain)
        mtp_tgt = jnp.pad(targets[:, 1:], ((0, 0), (0, 0)))
        mtp_mask = mask[:, 1:]
        mtp_loss = cross_entropy(mtp_logits[:, -mtp_tgt.shape[1]:],
                                 jnp.maximum(mtp_tgt, 0), mtp_mask)
        metrics["mtp"] = mtp_loss
        total = total + 0.3 * mtp_loss
    metrics["total"] = total
    return total, metrics


def prefill(cfg: ModelConfig, params, batch, max_len: int, *, mesh=None,
            constrain: Callable = _noconstrain, cache_dtype=jnp.bfloat16):
    """Run the prompt through the model, filling a fresh cache of size
    max_len. Returns (last_logits (B,V), cache)."""
    b = batch["tokens"].shape[0]
    cache = init_cache(cfg, b, max_len, cache_dtype)
    logits, _, new_cache, _ = forward(cfg, params, batch, cache=cache,
                                      cache_index=0, mesh=mesh,
                                      constrain=constrain, train=False)
    return logits[:, -1], new_cache


def decode_step(cfg: ModelConfig, params, cache, tokens, index, *, mesh=None,
                constrain: Callable = _noconstrain):
    """One decode step. tokens: (B,1); index: scalar int32 position.
    Returns (logits (B,V), new_cache)."""
    b = tokens.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(index, jnp.int32)[None, None],
                                 (b, 1))
    batch = {"tokens": tokens, "positions": positions}
    logits, _, new_cache, _ = forward(cfg, params, batch, cache=cache,
                                      cache_index=jnp.asarray(index, jnp.int32),
                                      mesh=mesh, constrain=constrain,
                                      train=False)
    return logits[:, -1], new_cache
