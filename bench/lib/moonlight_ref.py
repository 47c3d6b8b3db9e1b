"""Plain reference of one chip's share of Moonlight-16B-A3B served
expert-parallel (arXiv:2502.16982; the DeepSeek-V3 block,
arXiv:2412.19437 §2.1). Full forward pass over whole sequences in
float32 at Precision.HIGHEST, no cache, no kernels, every held expert
computed for every token and weighted by its router weight (zero where
the token did not choose it). Imports nothing of the program.

The layer equations, with h the RMS-normed input of a sublayer:

- MLA without q-LoRA: q = h W_q (heads of 128 nope + 64 rope dims);
  c = RMSNorm(h W_dkv) (512 wide); k_r = RoPE(h W_kr) (64 wide, shared
  by all heads); k_nope = c W_uk; v = c W_uv; RoPE on q's rope part;
  causal softmax of (q_nope.k_nope + q_rope.k_r) / sqrt(192); o = W_o
  concat(heads).
- MoE (DeepSeek-V3 eqs. 12-16): s_i = sigmoid(h.e_i) over all
  n_experts; chosen: TopK_k(s_i + b_i), b the score-correction bias,
  among the best topk_group of n_group groups when n_group > 1; weights
  g_i = routed_scale * s_i / sum over the chosen s_j (the bias does not
  enter them); y = SwiGLU_shared(h) + sum over chosen held i of
  g_i SwiGLU_i(h). The held experts are [expert_offset, expert_offset +
  experts_held); the absent experts' part is left out, as the program
  leaves it out: that partial sum is what goes on to the next layer.
- Layers [0, first_k_dense) have a SwiGLU FFN of width d_ff instead.
  RMSNorm with eps norm_eps everywhere; the token embedding is not
  scaled; the head is untied, over the vocabulary slice.

The benchmark makes the served weights with init_params, in one jitted
call from the member's seed, and hands them to the program; the
reference makes them again from the same seed. Departures from the
published model, which the program shares: RoPE rotates the two halves
of the rope dims (the published checkpoint pairs interleaved columns: a
permutation of the rope columns of W_q and W_kr, immaterial for random
weights); the weights are random, normal with std fan_in^-0.5 (the
token embedding d_model^-0.5 times `embed_scale`), norm scales 1, the
score-correction bias normal with std `router_bias_scale`.

With fp8=True every matmul's operands are rounded to float8_e4m3 with a
per-tensor scale: the control, one precision below the served bfloat16.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def _mla(cfg, key):
    d, h = cfg["d_model"], cfg["n_heads"]
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_dim"],
                     cfg["qk_rope_dim"], cfg["v_head_dim"])
    k = jax.random.split(key, 6)
    return {"w_q": _normal(k[0], (d, h, dn + dr), d),
            "w_dkv": _normal(k[1], (d, r), d),
            "kv_norm": jnp.ones((r,), jnp.float32),
            "w_kr": _normal(k[2], (d, dr), d),
            "w_uk": _normal(k[3], (r, h, dn), r),
            "w_uv": _normal(k[4], (r, h, dv), r),
            "wo": _normal(k[5], (h, dv, d), h * dv)}


def _swiglu(key, d, ff, lead=()):
    k = jax.random.split(key, 3)
    return {"w_gate": _normal(k[0], lead + (d, ff), d),
            "w_up": _normal(k[1], lead + (d, ff), d),
            "w_down": _normal(k[2], lead + (ff, d), ff)}


def init_params(cfg: dict, seed: int):
    """float32 weights in the served model's tree, on the device."""
    d = cfg["d_model"]
    ones = jnp.ones((d,), jnp.float32)

    def block(key, moe):
        ka, kf, kr, kb, ks = jax.random.split(key, 5)
        if moe:
            ffn = _swiglu(kf, d, cfg["moe_d_ff"], (cfg["experts_held"],))
            ffn["router"] = _normal(kr, (d, cfg["n_experts"]), d)
            ffn["router_bias"] = (jax.random.normal(kb, (cfg["n_experts"],))
                                  * cfg["router_bias_scale"])
            ffn["shared"] = _swiglu(
                ks, d, cfg["moe_d_ff"] * cfg["n_shared_experts"])
        else:
            ffn = _swiglu(kf, d, cfg["d_ff"])
        return {"attn_norm": {"scale": ones}, "mlp_norm": {"scale": ones},
                "attn": _mla(cfg, ka), "ffn": ffn}

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 4)
        v, kd = cfg["vocab"], cfg["first_k_dense"]
        return {
            "embed": _normal(ks[0], (v, d), d) * cfg["embed_scale"],
            "final_norm": {"scale": ones},
            "lm_head": _normal(ks[1], (d, v), d),
            "dense_blocks": jax.vmap(partial(block, moe=False))(
                jax.random.split(ks[2], kd)),
            "moe_blocks": jax.vmap(partial(block, moe=True))(
                jax.random.split(ks[3], cfg["n_layers"] - kd)),
        }

    return make(jax.random.key(seed))


def _q8(x):
    """Round to float8_e4m3 with a per-tensor scale, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """x (B, S, [H,] dr): rotate the two halves of the last axis."""
    s, dr = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    shape = (1, s) + (1,) * (x.ndim - 3) + (dr // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _route(s, bias, k, n_group, topk_group, scale):
    """Chosen experts (N, k) and their weights (N, k) from the sigmoid
    scores s (N, E): chosen on s + bias, weighted by s."""
    choice = s + bias
    if n_group > 1:
        n, e = choice.shape
        g = choice.reshape(n, n_group, e // n_group)
        gs = jnp.sort(g, -1)[..., -2:].sum(-1)               # best two
        rank = jnp.argsort(jnp.argsort(-gs, -1), -1)        # 0 = best
        choice = jnp.where((rank < topk_group)[..., None], g,
                           -jnp.inf).reshape(n, e)
    _, idx = jax.lax.top_k(choice, k)
    w = jnp.take_along_axis(s, idx, -1)
    return idx, scale * w / jnp.sum(w, -1, keepdims=True)


def mla(a, h, *, dn, dr, theta, eps, q8=lambda x: x):
    """Causal multi-head latent attention of normed inputs h (B, S, d)
    with the weights `a` of one layer, expanded form."""
    mm = partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    s = h.shape[1]
    q = mm("bsd,dhk->bshk", q8(h), q8(a["w_q"]))
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], theta)
    c = _rms(mm("bsd,dr->bsr", q8(h), q8(a["w_dkv"])), a["kv_norm"], eps)
    k_r = _rope(mm("bsd,dr->bsr", q8(h), q8(a["w_kr"])), theta)
    k_nope = mm("bsr,rhk->bshk", q8(c), q8(a["w_uk"]))
    v = mm("bsr,rhk->bshk", q8(c), q8(a["w_uv"]))
    sc = (mm("bshk,bthk->bhst", q8(q_nope), q8(k_nope))
          + mm("bshk,btk->bhst", q8(q_rope), q8(k_r))) * (dn + dr) ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], sc, -1e30)
    o = mm("bhst,bthk->bshk", q8(jax.nn.softmax(sc, -1)), q8(v))
    return mm("bshk,hkd->bsd", q8(o), q8(a["wo"]))


def _forward(params, tokens, last, *, meta, fp8):
    """tokens (B, S) -> hidden states at positions [last, S) after the
    final norm, times the head: logits (B, S - last, V)."""
    dn, dr, k, offset, n_group, topk_group, scale, theta, eps = meta
    q8 = _q8 if fp8 else (lambda x: x)
    mm = partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)

    def swiglu(h, m, spec_in="bsd,df->bsf", spec_out="bsf,fd->bsd"):
        g = mm(spec_in, q8(h), q8(m["w_gate"]))
        u = mm(spec_in, q8(h), q8(m["w_up"]))
        return mm(spec_out, q8(jax.nn.silu(g) * u), q8(m["w_down"]))

    def attention(x, p):
        h = _rms(x, p["attn_norm"]["scale"], eps)
        return x + mla(p["attn"], h, dn=dn, dr=dr, theta=theta, eps=eps,
                       q8=q8)

    def dense(x, p):
        x = attention(x, p)
        return x + swiglu(_rms(x, p["mlp_norm"]["scale"], eps), p["ffn"]), None

    def moe(x, p):
        x = attention(x, p)
        f = p["ffn"]
        h = _rms(x, p["mlp_norm"]["scale"], eps)
        s_ = jax.nn.sigmoid(mm("bsd,de->bse", q8(h), q8(f["router"])))
        idx, w = _route(s_.reshape(b * s, -1), f["router_bias"], k,
                        n_group, topk_group, scale)
        held = f["w_gate"].shape[0]
        # (N, held): each held expert's weight for each token, 0 if unchosen
        comb = jnp.sum(jnp.where(idx[..., None] == offset + jnp.arange(held),
                                 w[..., None], 0.0), 1).reshape(b, s, held)
        y = swiglu(h, f, "bsd,edf->ebsf", "ebsf,efd->ebsd")
        x = x + mm("bse,ebsd->bsd", comb, y)
        return x + swiglu(h, f["shared"]), None

    x, _ = jax.lax.scan(dense, x, params["dense_blocks"])
    x, _ = jax.lax.scan(moe, x, params["moe_blocks"])
    x = _rms(x[:, last:], params["final_norm"]["scale"], eps)
    return mm("bsd,dv->bsv", q8(x), q8(params["lm_head"]))


forward = jax.jit(_forward, static_argnames=("last", "meta", "fp8"))


def _meta(cfg):
    return (cfg["qk_nope_dim"], cfg["qk_rope_dim"],
            cfg["experts_per_tok"], cfg["expert_offset"], cfg["n_group"],
            cfg["topk_group"], float(cfg["routed_scale"]),
            float(cfg["rope_theta"]), float(cfg["norm_eps"]))


def logits(cfg: dict, params, tokens, last: int = 0, fp8: bool = False):
    """Reference logits (B, S - last, V) at positions [last, S)."""
    with jax.default_matmul_precision("highest"):
        return forward(params, jnp.asarray(tokens, jnp.int32), last=last,
                       meta=_meta(cfg), fp8=fp8)


def served_token_gaps(cfg: dict, params, prompts, served, control=False):
    """For each sequence, teacher-forced on prompt + served tokens: how
    far each served token's reference logit lies below the reference's
    best at that position, averaged over the sequence's served tokens.
    With control=True the tokens judged are the ones the fp8 control
    puts first at each position instead. prompts (B, P) int, served
    (B, T) int (-1 pads). Returns the mean gap per sequence (B,).

    The mean, where the OLMo reference takes the widest gap: the
    router's top-k is discontinuous in its scores, so rounding the
    residual stream to bfloat16 moves a few tokens' choice between
    experts of near-equal biased score, and one such token's gap is of
    the order of the logits' spread. The widest gap then reads alike for
    bfloat16 and for fp8 (on a v5e, 0.33-1.73 over six seeds against
    the control's 1.09-1.90). The mean counts how often and how far the
    served tokens fall short; a fault in the mathematics (an expert left
    out, the bias in the weights) moves every position."""
    b, p = prompts.shape
    t = served.shape[1]
    seq = np.concatenate([prompts, np.maximum(served, 0)], 1)[:, :p + t - 1]
    # position p-1+i predicts served token i
    ref = np.asarray(logits(cfg, params, seq, last=p - 1), np.float64)
    if control:
        ctl = logits(cfg, params, seq, last=p - 1, fp8=True)
        tok = np.asarray(jnp.argmax(ctl, -1))
    else:
        tok = np.maximum(served, 0)
    got = np.take_along_axis(ref, tok[..., None], -1)[..., 0]
    gap = ref.max(-1) - got
    live = served >= 0
    return np.where(live, gap, 0.0).sum(1) / np.maximum(live.sum(1), 1)
