"""Plain reference of an OLMo-style dense decoder (arXiv:2402.00838):
non-parametric LayerNorm, rotary multi-head attention, SwiGLU MLP, tied
embeddings. Full forward pass over whole sequences in float32 at
Precision.HIGHEST, no cache, no kernels. Imports nothing of the program.

The benchmark makes the served model's weights with init_params, in
one jitted call from the member's seed, and hands them to the program;
the reference makes them again from the same seed. They follow the
program's own initialisation (per-layer keys, normal weights scaled by
the fan-in) in the served model's tree, except that the token embedding
is drawn at `embed_scale` times the program's scale: at the program's
scale the embedding, multiplied by sqrt(d_model) at the input and tied
to the head, makes a random model repeat its last input token by a wide
margin, and greedy tokens then cannot tell one precision from another.

Departures of the served model from the published OLMo, which the
reference follows so that it computes the same function: the token
embedding is scaled by sqrt(d_model), and the LayerNorm epsilon is 1e-6
(OLMo: 1e-5).

With fp8=True every matmul's operands are rounded to float8_e4m3 with a
per-tensor scale: the control, one precision below the served bfloat16.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def init_params(cfg: dict, seed: int):
    """float32 weights in the served model's tree, on the device."""
    d, h, hd = cfg["d_model"], cfg["n_heads"], cfg["head_dim"]
    hk, ff, v, n = cfg["n_kv_heads"], cfg["d_ff"], cfg["vocab"], \
        cfg["n_layers"]

    def block(rng):
        ks = jax.random.split(rng, 6)
        k1, k2, k3, k4, _ = jax.random.split(ks[2], 5)
        s = d ** -0.5
        attn = {"wq": jax.random.normal(k1, (d, h, hd)) * s,
                "wk": jax.random.normal(k2, (d, hk, hd)) * s,
                "wv": jax.random.normal(k3, (d, hk, hd)) * s,
                "wo": jax.random.normal(k4, (h, hd, d)) * (h * hd) ** -0.5}
        m1, m2, m3 = jax.random.split(ks[5], 3)
        mlp = {"w_gate": jax.random.normal(m1, (d, ff)) * s,
               "w_up": jax.random.normal(m2, (d, ff)) * s,
               "w_down": jax.random.normal(m3, (ff, d)) * ff ** -0.5}
        # the served model's tree: non-parametric norms hold nothing
        return {"attn_norm": {}, "mlp_norm": {}, "attn": attn, "ffn": mlp}

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 10)
        embed = jax.random.normal(ks[0], (v, d)) * (cfg["embed_scale"]
                                                    * d ** -0.5)
        blocks = jax.vmap(block)(jax.random.split(ks[3], n))
        return {"embed": embed.astype(jnp.float32), "final_norm": {},
                "blocks": jax.tree.map(lambda x: x.astype(jnp.float32),
                                       blocks)}

    return make(jax.random.key(seed))


def _q8(x):
    """Round to float8_e4m3 with a per-tensor scale, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ln(x, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, theta):
    """x (B, S, H, hd): rotate the two halves of each head."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("theta", "fp8"))
def forward(params, tokens, *, theta: float = 10000.0, fp8: bool = False):
    """tokens (B, S) -> logits (B, S, V) at every position."""
    q8 = _q8 if fp8 else (lambda x: x)
    mm = partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    emb = params["embed"]
    d = emb.shape[1]
    x = jnp.take(emb, tokens, axis=0) * jnp.float32(d ** 0.5)
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        a, m = p["attn"], p["ffn"]
        h = _ln(x)
        q = mm("bsd,dhk->bshk", q8(h), q8(a["wq"]))
        k = mm("bsd,dhk->bshk", q8(h), q8(a["wk"]))
        v = mm("bsd,dhk->bshk", q8(h), q8(a["wv"]))
        q, k = _rope(q, theta), _rope(k, theta)
        hd = q.shape[-1]
        sc = mm("bshk,bthk->bhst", q8(q * hd ** -0.5), q8(k))
        sc = jnp.where(causal[None, None], sc, -1e30)
        w = jax.nn.softmax(sc, axis=-1)
        o = mm("bhst,bthk->bshk", q8(w), q8(v))
        x = x + mm("bshk,hkd->bsd", q8(o), q8(a["wo"]))
        h = _ln(x)
        g = mm("bsd,df->bsf", q8(h), q8(m["w_gate"]))
        u = mm("bsd,df->bsf", q8(h), q8(m["w_up"]))
        x = x + mm("bsf,fd->bsd", q8(jax.nn.silu(g) * u), q8(m["w_down"]))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return mm("bsd,vd->bsv", q8(_ln(x)), q8(emb))


def served_token_gaps(cfg: dict, params, prompts, served, control=False):
    """For each sequence, teacher-forced on prompt + served tokens: how
    far each served token's reference logit lies below the reference's
    best at that position. With control=True the tokens judged are the
    ones the fp8 control puts first at each position instead.
    prompts (B, P) int, served (B, T) int (-1 pads). Returns the widest
    gap per sequence (B,)."""
    import numpy as np
    theta = cfg["rope_theta"]
    b, p = prompts.shape
    t = served.shape[1]
    seq = np.concatenate([prompts, np.maximum(served, 0)], 1)[:, :p + t - 1]
    logits = forward(params, jnp.asarray(seq, jnp.int32), theta=theta)
    # position p-1+i predicts served token i
    ref = np.asarray(logits[:, p - 1:p - 1 + t], np.float64)
    if control:
        ctl = forward(params, jnp.asarray(seq, jnp.int32), theta=theta,
                      fp8=True)
        tok = np.asarray(jnp.argmax(ctl[:, p - 1:p - 1 + t], -1))
    else:
        tok = np.maximum(served, 0)
    got = np.take_along_axis(ref, tok[..., None], -1)[..., 0]
    gap = ref.max(-1) - got
    gap = np.where(served >= 0, gap, 0.0)
    return gap.max(1)
