"""Moonlight-16B-A3B's expert-parallel share against the plain reference
(bench/lib/moonlight_ref.py) at a reduced size on the CPU, with seeded
random weights the reference makes.

The program runs here in float32 (`dtype="float32"`): these tests check
the mathematics, so the tolerances only absorb float32 summation order
(relative 1e-4 on O(1) logits). The served bfloat16 path is judged on
the chip against the same reference by the benchmark's `logit_gap`.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.lib import moonlight_ref as REF  # noqa: E402
from repro import obs as OBS  # noqa: E402
from repro.configs import get_config, get_reduced_config  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving.engine import FleetModel  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

#: float32 on both sides: only the order of summation differs
TOL = dict(rtol=1e-4, atol=1e-4)


def _share(**kw):
    """The reduced ep8 share (2 of 8 experts held, top-2, 1 dense + 1
    MoE layer) computed in float32, and its reference description."""
    cfg = dataclasses.replace(get_reduced_config("moonlight-16b-a3b-ep8"),
                              dtype="float32", **kw)
    ref = dict(n_layers=cfg.n_layers, first_k_dense=cfg.first_k_dense,
               d_model=cfg.d_model, n_heads=cfg.n_heads,
               kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope_dim,
               qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
               d_ff=cfg.d_ff, moe_d_ff=cfg.moe_d_ff, n_experts=cfg.n_experts,
               experts_per_tok=cfg.experts_per_tok,
               experts_held=cfg.held_experts, expert_offset=cfg.expert_offset,
               n_shared_experts=cfg.n_shared_experts,
               routed_scale=cfg.routed_scale, n_group=cfg.n_group,
               topk_group=cfg.topk_group, vocab=cfg.vocab,
               rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
               embed_scale=1.0, router_bias_scale=0.1)
    return cfg, ref


@pytest.fixture(scope="module")
def share():
    cfg, ref = _share(n_layers=3)
    params = REF.init_params(ref, 3)
    toks = np.random.default_rng(3).integers(0, cfg.vocab, (2, 12))
    return cfg, ref, params, toks


def test_reference_tree_is_the_programs(share):
    cfg, _, params, _ = share
    mine = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_prefill_logits_match_reference(share):
    cfg, ref, params, toks = share
    logits, _, _, _ = T.forward(cfg, params, {"tokens": jnp.asarray(toks)})
    want = REF.logits(ref, params, toks)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), **TOL)


def test_prefill_then_decode_matches_full_forward(share):
    """Prefill 8 tokens into the latent cache, then decode the other 4
    one at a time (absorbed MLA over the cache, dropless experts at
    T = batch): every step's logits are the reference's full pass."""
    cfg, ref, params, toks = share
    want = np.asarray(REF.logits(ref, params, toks))
    p = 8
    last, cache = T.prefill(cfg, params, {"tokens": jnp.asarray(toks[:, :p])},
                            16, cache_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(last), want[:, p - 1], **TOL)
    for i in range(p, toks.shape[1]):
        step, cache = T.decode_step(cfg, params, cache,
                                    jnp.asarray(toks[:, i:i + 1]), i)
        np.testing.assert_allclose(np.asarray(step), want[:, i], **TOL)


def test_mla_without_q_lora_matches_reference(share):
    cfg, _, params, _ = share
    assert cfg.q_lora_rank == 0
    a = jax.tree.map(lambda x: x[0], params["dense_blocks"]["attn"])
    assert set(a) == {"w_q", "w_dkv", "kv_norm", "w_kr", "w_uk", "w_uv", "wo"}
    h = jnp.asarray(np.random.default_rng(4).normal(size=(2, 9, cfg.d_model)),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(9)[None], (2, 9))
    got, _ = L.apply_mla(cfg, a, h, pos, theta=cfg.rope_theta)
    want = REF.mla(a, h, dn=cfg.qk_nope_dim, dr=cfg.qk_rope_dim,
                   theta=cfg.rope_theta, eps=cfg.norm_eps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_router_chooses_on_bias_but_weighs_without_it():
    """Bias lifts expert 7 (lowest score) into the top 2 and pushes
    expert 0 (highest score) out; the weights are the unbiased scores
    of the chosen two, normalised, times routed_scale."""
    cfg, _ = _share()
    e, d = cfg.n_experts, cfg.d_model
    logits = jnp.linspace(2.0, -2.0, e)                  # expert 0 best
    x = jnp.zeros((1, d)).at[0, 0].set(1.0)
    router = jnp.zeros((d, e)).at[0].set(logits)
    bias = jnp.zeros((e,)).at[7].set(5.0).at[0].set(-5.0)
    w, idx, _ = MOE.route(cfg, {"router": router, "router_bias": bias}, x)
    assert sorted(np.asarray(idx)[0].tolist()) == [1, 7]
    s = jax.nn.sigmoid(logits)
    chosen = np.asarray(idx)[0]
    want = cfg.routed_scale * s[chosen] / s[chosen].sum()
    np.testing.assert_allclose(np.asarray(w)[0], np.asarray(want), rtol=1e-6)
    ridx, rw = REF._route(s[None], bias, cfg.experts_per_tok, 1, 1,
                          cfg.routed_scale)
    np.testing.assert_array_equal(np.sort(np.asarray(ridx)), [[1, 7]])
    np.testing.assert_allclose(np.sort(np.asarray(rw)), np.sort(want)[None],
                               rtol=1e-6)


def test_dropless_when_every_token_picks_one_expert(share):
    """A bias that sends every token to held expert 0 (and 1): the served
    path computes all of them, as the reference does; the training
    form's capacity buffer drops most of them."""
    cfg, _, params, _ = share
    f = jax.tree.map(lambda x: x[0], params["moe_blocks"]["ffn"])
    f["router_bias"] = f["router_bias"].at[:2].set(100.0)
    t = 64
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, t, cfg.d_model)),
                    jnp.float32)
    y, _, stats = MOE.apply_moe(cfg, f, x)
    assert np.asarray(stats).tolist()[:2] == [2 * t, 0]
    assert int(stats[2]) == t
    s = jax.nn.sigmoid(x[0] @ f["router"])
    idx, w = REF._route(s, f["router_bias"], 2, 1, 1, cfg.routed_scale)
    assert (np.sort(np.asarray(idx), 1) == [0, 1]).all()

    def swiglu(m, h):
        return (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]
    want = swiglu(f["shared"], x[0])
    for j in range(2):
        e = jax.tree.map(lambda a: a[j], {n: f[n] for n in
                                          ("w_gate", "w_up", "w_down")})
        wj = jnp.sum(jnp.where(idx == j, w, 0.0), 1)
        want = want + wj[:, None] * swiglu(e, x[0])
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), **TOL)
    _, _, st_train = MOE.apply_moe(cfg, f, x, train=True)
    assert int(st_train[1]) > 0           # today's capacity drops


def test_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips of an EP8 layer, each holding 2 of 16 experts: their
    outputs, with the shared experts (computed alike on every chip)
    counted once, add up to the layer that holds all 16."""
    full, _ = _share(experts_held=0, n_experts=16)
    f = MOE.init_moe(full, jax.random.key(6))
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 5, full.d_model)),
                    jnp.float32)
    want, _, _ = MOE.apply_moe(full, f, x)
    shared = MOE.apply_moe(dataclasses.replace(full, n_shared_experts=0),
                           f, x)[0]
    shared = want - shared
    parts, assigned = [], 0
    for i in range(8):
        cfg = dataclasses.replace(full, experts_held=2, expert_offset=2 * i)
        p = dict(f, **{n: f[n][2 * i:2 * i + 2]
                       for n in ("w_gate", "w_up", "w_down")})
        y, _, st = MOE.apply_moe(cfg, p, x)
        parts.append(y - shared)
        assigned += int(st[0])
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(want), **TOL)
    assert assigned == x.shape[0] * x.shape[1] * full.experts_per_tok


def test_ep8_keeps_every_published_width():
    pub, ep8 = get_config("moonlight-16b-a3b"), \
        get_config("moonlight-16b-a3b-ep8")
    changed = {f.name for f in dataclasses.fields(pub)
               if getattr(pub, f.name) != getattr(ep8, f.name)}
    assert changed == {"name", "n_layers", "experts_held", "vocab"}
    assert (ep8.n_layers, ep8.first_k_dense, ep8.experts_held,
            ep8.expert_offset, ep8.vocab) == (9, 1, 8, 0, 163840 // 8)
    assert (pub.d_model, pub.d_ff, pub.moe_d_ff, pub.n_heads,
            pub.kv_lora_rank, pub.qk_nope_dim, pub.qk_rope_dim,
            pub.v_head_dim, pub.q_lora_rank) == (2048, 11264, 1408, 16, 512,
                                                 128, 64, 128, 0)
    assert (pub.n_experts, pub.experts_per_tok, pub.n_shared_experts,
            pub.routed_scale, pub.router_score, pub.norm_eps,
            pub.embed_scale, pub.tie_embeddings) == (
        64, 6, 2, 2.446, "sigmoid", 1e-5, False, False)
    assert abs(ep8.total_params() - 0.9698e9) < 1e6
    assert abs(pub.total_params() - 15.96e9) < 0.01e9


def test_served_member_counts_no_dropped_assignment():
    cfg = get_reduced_config("moonlight-16b-a3b-ep8")
    m = FleetModel(cfg, seed=1, max_len=24)
    m.obs = OBS.Observability(enabled=False)
    toks = np.random.default_rng(7).integers(0, cfg.vocab, (4, 8))
    out = m.generate(toks, 5)
    assert out.shape == (4, 5)
    r = m.obs.registry
    lab = dict(model=cfg.name)
    # the one MoE layer routes 4 rows x (8 + 4) positions x 2 slots
    total = 4 * 12 * cfg.experts_per_tok
    held = r.value("serve_moe_assignments_total", **lab)
    assert 0 < held <= total
    assert r.value("serve_moe_dropped_total", **lab) == 0
    assert 0 < r.value("serve_moe_max_expert_tokens", **lab) <= 4 * 8


def _group_limited_numpy(s, bias, k, n_group, topk_group):
    """DeepSeek-V3's noaux_tc selection, one token at a time."""
    out = []
    for row in s + bias:
        groups = row.reshape(n_group, -1)
        score = np.sort(groups, 1)[:, -2:].sum(1)
        best = np.argsort(-score, kind="stable")[:topk_group]
        masked = np.full_like(row, -np.inf)
        for g in best:
            lo = g * groups.shape[1]
            masked[lo:lo + groups.shape[1]] = groups[g]
        out.append(sorted(np.argsort(-masked, kind="stable")[:k].tolist()))
    return np.asarray(out)


def test_deepseek_v3_group_limited_selection():
    """DeepSeek-V3's published router: 256 experts in 8 groups, the best
    4 groups by their two best biased scores, top-8 among them."""
    cfg = get_config("deepseek-v3-671b")
    assert (cfg.router_score, cfg.n_group, cfg.topk_group,
            cfg.routed_scale) == ("sigmoid", 8, 4, 2.5)
    assert get_config("phi3.5-moe-42b-a6.6b").router_score == "softmax"
    rng = np.random.default_rng(8)
    e, d, t = cfg.n_experts, 16, 32
    x = rng.normal(size=(t, d)).astype(np.float32)
    router = (rng.normal(size=(d, e)) * d ** -0.5).astype(np.float32)
    bias = (rng.normal(size=(e,)) * 0.1).astype(np.float32)
    small = dataclasses.replace(cfg, d_model=d)
    w, idx, _ = MOE.route(small, {"router": jnp.asarray(router),
                                  "router_bias": jnp.asarray(bias)},
                          jnp.asarray(x))
    s = 1 / (1 + np.exp(-(x.astype(np.float64) @ router)))
    want = _group_limited_numpy(s, bias, cfg.experts_per_tok, 8, 4)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), 1), want)
    chosen = np.take_along_axis(s, want, 1)
    got_w = np.take_along_axis(np.asarray(w), np.argsort(np.asarray(idx), 1), 1)
    np.testing.assert_allclose(got_w, 2.5 * chosen / chosen.sum(1, keepdims=True),
                               rtol=1e-5)
    # every chosen expert lies in one of 4 groups
    assert all(len(set(r // 32)) <= 4 for r in want)
