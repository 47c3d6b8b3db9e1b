"""Mixture-of-Experts FFN: the router, and the experts a device holds.

The router scores every one of the model's `n_experts` (softmax, as in
Phi-3.5-MoE, or sigmoid with a score-correction bias, as in the
DeepSeek-V3 family: arXiv:2412.19437 eqs. 12-16) and picks
`experts_per_tok` per token. A device holds only some experts' weights
(`experts_held` from `expert_offset`; all by default) and computes the
part of the result those experts give. With a "model" mesh axis each
model shard holds `held / model_size` of them and the parts are summed
with one psum over "model", the same collective a tensor-parallel dense
FFN needs; on one device there is no exchange.

Serving is dropless (`_held_experts`): every assignment of a token to a
held expert is computed. The assignments, at most T*k rows, are sorted
by expert and run through one grouped matmul (`jax.lax.ragged_dot`)
per projection. Training keeps the fixed-capacity buffer
(`_capacity_experts`, `capacity_factor`), whose memory does not grow
with the assignments other shards compute.

Each call also returns its counters, int32[3]: assignments routed to
held experts, of them dropped (0 when serving), and the busiest held
expert's rows (on a mesh whose tokens are batch-sharded, the busiest
of any one token shard).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.layers import _cast

_EXPERT_KEYS = ("w_gate", "w_up", "w_down")
#: spread of the drawn score-correction bias: a trained model's bias
#: moves the choice among experts of similar score, as this one does
ROUTER_BIAS_SCALE = 0.1


def init_moe(cfg: ModelConfig, rng):
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.expert_ff
    eh = cfg.held_experts
    ks = jax.random.split(rng, 5)
    s_in, s_out = d ** -0.5, ff ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * s_in).astype(cfg.param_dtype),
        "w_gate": (jax.random.normal(ks[1], (eh, d, ff)) * s_in).astype(cfg.param_dtype),
        "w_up": (jax.random.normal(ks[2], (eh, d, ff)) * s_in).astype(cfg.param_dtype),
        "w_down": (jax.random.normal(ks[3], (eh, ff, d)) * s_out).astype(cfg.param_dtype),
    }
    if cfg.router_score == "sigmoid":
        p["router_bias"] = (jax.random.normal(jax.random.fold_in(rng, 5), (e,))
                            * ROUTER_BIAS_SCALE).astype(cfg.param_dtype)
    if cfg.n_shared_experts:
        sff = ff * cfg.n_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": (jax.random.normal(k1, (d, sff)) * s_in).astype(cfg.param_dtype),
            "w_up": (jax.random.normal(k2, (d, sff)) * s_in).astype(cfg.param_dtype),
            "w_down": (jax.random.normal(k3, (sff, d)) * (sff ** -0.5)).astype(cfg.param_dtype),
        }
    return p


# ---------------------------------------------------------------------------
# Router
# ---------------------------------------------------------------------------

def _group_limited(cfg: ModelConfig, choice):
    """Keep only the experts of each token's best `topk_group` groups
    (a group scores the sum of its two best biased scores); the rest
    cannot be chosen."""
    t, e = choice.shape
    g = choice.reshape(t, cfg.n_group, e // cfg.n_group)
    top = jax.lax.top_k(g, min(2, g.shape[-1]))[0].sum(-1)      # (T, G)
    _, gi = jax.lax.top_k(top, cfg.topk_group)
    keep = jnp.zeros(top.shape, bool).at[jnp.arange(t)[:, None], gi].set(True)
    return jnp.where(keep[..., None], g, -jnp.inf).reshape(t, e)


def route(cfg: ModelConfig, params, xt):
    """xt (T, d) -> (weights (T, k) f32, experts (T, k) int32, aux).

    Scores are float32 over all n_experts. Experts are chosen on the
    scores plus the bias (sigmoid scores); their weights are the
    unbiased scores, normalised over the chosen k, times
    `routed_scale`. aux is the Switch-style balance loss over the
    scores (normalised per token for sigmoid scores)."""
    k = cfg.experts_per_tok
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            params["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        if cfg.router_score == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            probs = scores / jnp.sum(scores, -1, keepdims=True)
            choice = scores + params["router_bias"].astype(jnp.float32)
        else:
            scores = probs = choice = jax.nn.softmax(logits, axis=-1)
        if cfg.n_group > 1:
            choice = _group_limited(cfg, choice)
        _, top_e = jax.lax.top_k(choice, k)
        top_w = jnp.take_along_axis(scores, top_e, axis=1)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        if cfg.routed_scale != 1.0:
            top_w = top_w * cfg.routed_scale
        e = cfg.n_experts
        frac_tokens = jnp.mean(
            jax.nn.one_hot(top_e, e, dtype=jnp.float32).sum(1), axis=0)
        aux = e * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    return top_w, top_e, aux


# ---------------------------------------------------------------------------
# Experts
# ---------------------------------------------------------------------------

def zero_stats():
    return jnp.zeros((3,), jnp.int32)


def merge_stats(a, b):
    """Counters of two calls: sums, and the larger busiest load."""
    return jnp.concatenate([a[:2] + b[:2], jnp.maximum(a[2:], b[2:])])


def _stats(assigned, dropped, busiest):
    return jnp.stack([assigned, dropped, busiest]).astype(jnp.int32)


def _held_experts(params, xt, top_w, top_e, offset):
    """Dropless part of the held experts [offset, offset + E_held) for
    tokens xt (T, d): the T*k assignments sorted by expert (those to
    experts held elsewhere last, outside every group), one grouped
    matmul per projection, each row weighted by its router weight and
    summed back onto its token. Returns (y (T, d) f32, counters)."""
    t, _ = xt.shape
    k = top_e.shape[1]
    eh = params["w_gate"].shape[0]
    with jax.named_scope("moe.experts"):
        local = top_e.reshape(-1) - offset
        held = (local >= 0) & (local < eh)
        key = jnp.where(held, local, eh)
        order = jnp.argsort(key, stable=True)
        tok = order // k
        sizes = jnp.zeros((eh,), jnp.int32).at[key].add(1, mode="drop")
        w = _cast({n: params[n] for n in _EXPERT_KEYS}, xt.dtype)
        xs = xt[tok]
        g = jax.lax.ragged_dot(xs, w["w_gate"], sizes,
                               preferred_element_type=jnp.float32)
        u = jax.lax.ragged_dot(xs, w["w_up"], sizes,
                               preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(xt.dtype)
        y = jax.lax.ragged_dot(h, w["w_down"], sizes,
                               preferred_element_type=jnp.float32)
        row_w = top_w.reshape(-1)[order]
        y = jnp.where(held[order][:, None], y * row_w[:, None], 0.0)
        y = jax.ops.segment_sum(y, tok, num_segments=t)
        n_held = held.sum()
        stats = _stats(n_held, n_held - sizes.sum(), sizes.max())
    return y, stats


def _capacity_experts(cfg: ModelConfig, params, xt, top_w, top_e, offset):
    """Training form: the held experts' tokens gathered into a fixed
    (E_held, C, d) buffer, C = capacity_factor * k * T / n_experts;
    assignments past an expert's C are dropped. Returns (y (T, d),
    counters)."""
    t, d = xt.shape
    k = top_e.shape[1]
    eh = params["w_gate"].shape[0]
    capacity = int(max(k, cfg.capacity_factor * k * t / cfg.n_experts))

    flat_e = top_e.reshape(-1) - offset            # (T*k,)
    flat_w = top_w.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), k)
    is_local = (flat_e >= 0) & (flat_e < eh)
    loc_e = jnp.where(is_local, flat_e, eh)        # eh = drop bin

    # Position of each assignment within its expert (capacity slots).
    onehot = jax.nn.one_hot(loc_e, eh + 1, dtype=jnp.int32)  # (T*k, E+1)
    pos = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.take_along_axis(pos, loc_e[:, None], axis=1)[:, 0]
    keep = is_local & (slot < capacity)
    loc_e_c = jnp.where(keep, loc_e, eh)
    slot_c = jnp.where(keep, slot, 0)

    buf = jnp.zeros((eh + 1, capacity, d), xt.dtype)
    buf = buf.at[loc_e_c, slot_c].add(jnp.where(keep[:, None], xt[flat_t], 0))
    buf = buf[:eh]

    w = _cast({n: params[n] for n in _EXPERT_KEYS}, xt.dtype)
    g = jnp.einsum("ecd,edf->ecf", buf, w["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, w["w_up"])
    h = jax.nn.silu(g) * u
    y_buf = jnp.einsum("ecf,efd->ecd", h, w["w_down"])  # (E_held, C, d)

    y_tok = y_buf[jnp.minimum(loc_e_c, eh - 1), slot_c]  # (T*k, d)
    contrib = jnp.where(keep[:, None], y_tok * flat_w[:, None].astype(xt.dtype), 0)
    y = jnp.zeros_like(xt).at[flat_t].add(contrib)
    load = jnp.minimum(onehot[:, :eh].sum(0), capacity)
    stats = _stats(is_local.sum(), (is_local & ~keep).sum(), load.max())
    return y, stats


def _experts(cfg, params, xt, top_w, top_e, offset, train):
    if train:
        return _capacity_experts(cfg, params, xt, top_w, top_e, offset)
    return _held_experts(params, xt, top_w, top_e, offset)


def apply_moe(cfg: ModelConfig, params, x, mesh=None, constrain=None,
              train: bool = False
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss, counters). Routed over all
    n_experts; computed for the held ones, split over the "model" mesh
    axis when there is one (a psum sums the parts)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    top_w, top_e, aux = route(cfg, params, xt)
    experts = {n: params[n] for n in _EXPERT_KEYS}

    if mesh is not None and "model" in mesh.axis_names \
            and mesh.shape["model"] > 1:
        batch_axes = tuple(a for a in mesh.axis_names if a != "model")
        n_batch = 1
        for a in batch_axes:
            n_batch *= mesh.shape[a]
        # tokens batch-sharded where they divide, else replicated
        # (decode: a few rows, every model shard sees them all)
        tok = P(batch_axes, None) if (b * s) % n_batch == 0 else P(None, None)
        sharded = tok != P(None, None)

        def body(xt_l, w_l, e_l, p_l):
            off = cfg.expert_offset \
                + jax.lax.axis_index("model") * p_l["w_gate"].shape[0]
            y_l, st = _experts(cfg, p_l, xt_l, w_l, e_l, off, train)
            y_l = jax.lax.psum(y_l, "model")
            cnt = jax.lax.psum(st[:2], ("model",) + (batch_axes if sharded
                                                    else ()))
            top = jax.lax.pmax(st[2:], mesh.axis_names)
            return y_l, jnp.concatenate([cnt, top])

        y, stats = jax.shard_map(
            body, mesh=mesh,
            in_specs=(tok, tok, tok, {n: P("model", None, None)
                                      for n in _EXPERT_KEYS}),
            out_specs=(tok, P()), check_vma=False)(xt, top_w, top_e, experts)
    else:
        y, stats = _experts(cfg, experts, xt, top_w, top_e,
                            cfg.expert_offset, train)

    y = y.reshape(b, s, d).astype(x.dtype)
    if cfg.n_shared_experts:
        sp = _cast(params["shared"], x.dtype)
        g = jnp.einsum("bsd,df->bsf", x, sp["w_gate"])
        u = jnp.einsum("bsd,df->bsf", x, sp["w_up"])
        y = y + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, sp["w_down"])
    return y, aux.astype(jnp.float32), stats
