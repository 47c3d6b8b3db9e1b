"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def similarity_ref(q, db):
    """Cosine-similarity score panel. q: (Q,D), db: (N,D) — both rows are
    L2-normalized by the kernel, so the oracle normalizes too. The dot
    is pinned at Precision.HIGHEST like the kernel's (DESIGN.md §13):
    XLA's default f32 dot on the TPU is a single bf16 pass."""
    qn = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-9)
    dn = db / (jnp.linalg.norm(db, axis=-1, keepdims=True) + 1e-9)
    return jnp.matmul(qn, dn.T, precision=jax.lax.Precision.HIGHEST)


def elo_scan_ref(ratings, a_idx, b_idx, outcome, valid, k=32.0):
    """Batched ELO replay. ratings: (Q,M); records: (Q,T)."""
    q, m = ratings.shape
    t = a_idx.shape[1]
    r = ratings.astype(jnp.float32)
    for i in range(t):
        a, b = a_idx[:, i], b_idx[:, i]
        r_a = jnp.take_along_axis(r, a[:, None], 1)[:, 0]
        r_b = jnp.take_along_axis(r, b[:, None], 1)[:, 0]
        e_a = 1.0 / (1.0 + 10.0 ** ((r_b - r_a) / 400.0))
        delta = k * (outcome[:, i] - e_a) * valid[:, i].astype(jnp.float32)
        one_a = jax.nn.one_hot(a, m, dtype=jnp.float32)
        one_b = jax.nn.one_hot(b, m, dtype=jnp.float32)
        r = r + delta[:, None] * (one_a - one_b)
    return r


def gather_records(model_a, model_b, outcome, valid, idx, hit):
    """Device-side neighbor-record gather: (Q,N) prompt rows -> flattened
    (Q, N*R) records, entirely in jnp (no host fancy-indexing).

    Replay order is FARTHEST neighbor first: ELO is recency-weighted
    (later updates dominate the final ratings), so the most similar
    prompts are replayed last to carry the most influence."""
    idx = jnp.flip(idx, axis=1)
    hit = jnp.flip(hit, axis=1)
    nq = idx.shape[0]
    a = jnp.take(model_a, idx, axis=0).reshape(nq, -1)
    b = jnp.take(model_b, idx, axis=0).reshape(nq, -1)
    s = jnp.take(outcome, idx, axis=0).reshape(nq, -1)
    v = (jnp.take(valid, idx, axis=0) & hit[..., None]).reshape(nq, -1)
    return a, b, s, v


def elo_replay_ref(ratings, a_idx, b_idx, outcome, valid, k=32.0):
    """lax.scan formulation of elo_scan_ref (identical math, O(1) trace
    size) — the replay stage of the fused retrieve_replay reference.

    Deliberately NOT delegated to core.elo.elo_scan: kernels/ is the
    leaf layer (core imports kernels, never the reverse), and this
    module is the self-contained ground truth the Pallas bodies are
    validated against. test_elo_scan_kernel_matches_core_scan pins the
    kernel to core's production scan, so the copies cannot drift
    unnoticed."""

    def step(r, rec):
        a, b, s, v = rec
        m = r.shape[-1]
        r_a = jnp.take_along_axis(r, a[:, None], 1)[:, 0]
        r_b = jnp.take_along_axis(r, b[:, None], 1)[:, 0]
        e_a = 1.0 / (1.0 + 10.0 ** ((r_b - r_a) / 400.0))
        delta = k * (s - e_a) * v.astype(jnp.float32)
        one_a = jax.nn.one_hot(a, m, dtype=jnp.float32)
        one_b = jax.nn.one_hot(b, m, dtype=jnp.float32)
        return r + delta[:, None] * (one_a - one_b), None

    out, _ = jax.lax.scan(step, ratings.astype(jnp.float32),
                          (a_idx.T, b_idx.T, outcome.T, valid.T))
    return out


def budget_select_ref(scores, costs, budgets):
    """Budget-selection epilogue: highest-scoring model with cost <=
    budget, cheapest-model fallback when nothing fits. Must stay
    choice-identical to core.state.select_within_budget (pinned by
    tests); lives here too because kernels/ is the leaf layer and the
    fused epilogue needs a copy the Pallas body is validated against.

    scores: (Q, M); costs: (M,); budgets: (Q,). Returns (Q,) int32."""
    feasible = costs[None, :] <= budgets[:, None]
    masked = jnp.where(feasible, scores, -jnp.inf)
    choice = jnp.argmax(masked, axis=-1)
    fallback = jnp.argmin(costs)
    return jnp.where(feasible.any(axis=-1), choice, fallback).astype(
        jnp.int32)


def retrieve_replay_pipeline(similarity_fn, replay_fn, q, emb, model_a,
                             model_b, outcome, valid, size, init_ratings,
                             *, n):
    """The fused retrieval chain — similarity panel -> live-row masked
    top-k -> farthest-first record gather -> replay from the broadcast
    prior — with the stage implementations injected, so the reference
    and Pallas backends share ONE copy of the glue and cannot drift.

    replay_fn may return either `local` or a `(local, *extras)` tuple
    (the fused budget-selection epilogue returns `(local, choices)`);
    extras are appended to the pipeline's return tuple."""
    scores = similarity_fn(q, emb)
    live = jnp.arange(emb.shape[0]) < size
    scores = jnp.where(live[None, :], scores, -jnp.inf)
    top_s, top_i = jax.lax.top_k(scores, n)
    hit = jnp.isfinite(top_s)
    a, b, s, v = gather_records(model_a, model_b, outcome, valid, top_i, hit)
    init = jnp.broadcast_to(init_ratings, (q.shape[0], init_ratings.shape[-1]))
    out = replay_fn(init, a, b, s, v)
    local, extras = (out[0], tuple(out[1:])) if isinstance(out, tuple) \
        else (out, ())
    return (local, top_i, top_s) + extras


def retrieve_replay_ref(q, emb, model_a, model_b, outcome, valid, size,
                        init_ratings, *, n, k=32.0):
    """Fused routing retrieval oracle: similarity panel -> masked top-k ->
    device gather -> batched ELO replay. Returns (local (Q,M), topk_idx,
    topk_scores)."""
    return retrieve_replay_pipeline(
        similarity_ref, partial(elo_replay_ref, k=k), q, emb, model_a,
        model_b, outcome, valid, size, init_ratings, n=n)


def retrieve_replay_select_ref(q, emb, model_a, model_b, outcome, valid,
                               size, init_ratings, global_ratings, costs,
                               budgets, *, n, k=32.0, p=0.5):
    """retrieve_replay with the budget-selection epilogue fused in: the
    replay stage also combines Score = p*Global + (1-p)*Local and picks
    the best affordable model, so the caller reads (Q,) choices without
    a second op over the (Q, M) scores. Returns (local (Q,M), topk_idx,
    topk_scores, choices (Q,))."""

    def replay_select(init, a, b, s, v):
        local = elo_replay_ref(init, a, b, s, v, k=k)
        combined = p * global_ratings[None, :] + (1.0 - p) * local
        return local, budget_select_ref(combined, costs, budgets)

    return retrieve_replay_pipeline(
        similarity_ref, replay_select, q, emb, model_a, model_b, outcome,
        valid, size, init_ratings, n=n)


def sharded_retrieve_replay_pipeline(similarity_fn, replay_fn, q, emb,
                                     model_a, model_b, outcome, valid,
                                     size, init_ratings, *, n,
                                     axis_name):
    """Per-shard body of the capacity-sharded retrieval chain, run
    under shard_map over `axis_name` (DESIGN.md §12): the DB panels
    arrive as this shard's CONTIGUOUS row range, the queries and the
    replay prior arrive replicated. Stages:

      local similarity panel -> global-row live mask -> local top
      min(n, C_local) -> local candidate-record gather ->
      cross-shard merge (all-gather + final top-n reduce, candidates'
      records carried by position) -> farthest-first flatten ->
      replicated replay + epilogue.

    Bit-identical to retrieve_replay_pipeline over the full panels:
    slicing the similarity matmul on the row dim leaves each score
    column's D-accumulation untouched, and the merge's (shard, local
    rank) pool order reproduces single-device top_k tie-breaking under
    the contiguous partition (see shard_merge_topk). Like the
    unsharded glue, both backends share this ONE copy."""
    from repro.kernels.similarity_topk import (shard_local_topk,
                                               shard_merge_topk)
    scores = similarity_fn(q, emb)
    c_local = emb.shape[0]
    offset = jax.lax.axis_index(axis_name) * c_local
    live = (jnp.arange(c_local) + offset) < size
    scores = jnp.where(live[None, :], scores, -jnp.inf)
    loc_s, loc_i = shard_local_topk(scores, n)
    records = tuple(jnp.take(x, loc_i, axis=0)
                    for x in (model_a, model_b, outcome, valid))
    top_s, top_i, (ca, cb, cs, cv) = shard_merge_topk(
        loc_s, loc_i + offset, records, n, axis_name)
    hit = jnp.isfinite(top_s)
    nq = q.shape[0]
    # farthest-first flatten of the MERGED candidates — gather_records'
    # replay-order contract, minus the row gather it already did
    a = jnp.flip(ca, axis=1).reshape(nq, -1)
    b = jnp.flip(cb, axis=1).reshape(nq, -1)
    s = jnp.flip(cs, axis=1).reshape(nq, -1)
    v = (jnp.flip(cv, axis=1)
         & jnp.flip(hit, axis=1)[..., None]).reshape(nq, -1)
    init = jnp.broadcast_to(init_ratings, (nq, init_ratings.shape[-1]))
    out = replay_fn(init, a, b, s, v)
    local, extras = (out[0], tuple(out[1:])) if isinstance(out, tuple) \
        else (out, ())
    return (local, top_i, top_s) + extras


def sharded_retrieve_replay_select_ref(q, emb, model_a, model_b, outcome,
                                       valid, size, init_ratings,
                                       global_ratings, costs, budgets, *,
                                       n, k=32.0, p=0.5,
                                       axis_name="db"):
    """Capacity-sharded retrieve_replay_select_ref: same fused replay +
    budget-selection epilogue, run on the merged cross-shard
    candidates. Returns (local (Q,M), topk_idx (Q,n) GLOBAL rows,
    topk_scores, choices (Q,))."""

    def replay_select(init, a, b, s, v):
        local = elo_replay_ref(init, a, b, s, v, k=k)
        combined = p * global_ratings[None, :] + (1.0 - p) * local
        return local, budget_select_ref(combined, costs, budgets)

    return sharded_retrieve_replay_pipeline(
        similarity_ref, replay_select, q, emb, model_a, model_b, outcome,
        valid, size, init_ratings, n=n, axis_name=axis_name)


def flash_attention_ref(q, k, v, *, causal=True, window=0):
    """q: (B,S,H,dh), k/v: (B,T,Hk,dh). fp32 softmax reference."""
    b, s, h, dh = q.shape
    t, hk = k.shape[1], k.shape[2]
    rep = h // hk
    kk = jnp.repeat(k, rep, axis=2)
    vv = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * dh ** -0.5
    if causal:
        qp = jnp.arange(s)[:, None]
        kp = jnp.arange(t)[None, :]
        mask = kp <= qp + (t - s)          # bottom-right aligned
        if window:
            mask &= kp > qp + (t - s) - window
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", w, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention_ref(q, k, v, kv_len):
    """Single-token decode. q: (B,H,dh); k/v: (B,T,Hk,dh); kv_len: (B,)
    number of valid cache entries per sequence."""
    b, h, dh = q.shape
    t, hk = k.shape[1], k.shape[2]
    rep = h // hk
    kk = jnp.repeat(k, rep, axis=2)
    vv = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * dh ** -0.5
    mask = jnp.arange(t)[None, :] < kv_len[:, None]
    scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bht,bthd->bhd", w, vv.astype(jnp.float32)).astype(q.dtype)
