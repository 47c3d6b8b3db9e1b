"""Jit'd dispatch wrappers over the Pallas kernels and their jnp oracles.

backend:
  "reference"        pure-jnp oracle (default — fast on CPU, used by the
                     serving/benchmark paths in this container)
  "pallas_interpret" the Pallas kernel body executed by the interpreter
                     (CPU-correctness validation of the TPU kernels)
  "pallas"           compiled Pallas (TPU target)

The routing chain is ONE op, retrieve_replay_select: the same wrapper
runs on one device and, given a mesh axis, as the per-shard body of a
capacity-sharded DB (DESIGN.md §12).
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.elo_scan import elo_scan_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.retrieve_replay import retrieve_replay_select_pallas
from repro.kernels.similarity_topk import similarity_pallas


def _dispatch(backend, ref_fn, pallas_fn, *args, **kw):
    if backend == "reference":
        return ref_fn(*args, **kw)
    if backend == "pallas_interpret":
        return pallas_fn(*args, interpret=True, **kw)
    if backend == "pallas":
        return pallas_fn(*args, **kw)
    raise ValueError(backend)


@partial(jax.jit, static_argnames=("backend",))
def similarity(q, db, *, backend: str = "reference"):
    """(Q,D) x (N,D) -> (Q,N) cosine scores."""
    return _dispatch(backend, ref.similarity_ref, similarity_pallas, q, db)


@partial(jax.jit, static_argnames=("backend", "n"))
def similarity_topk(q, db, n: int, *, backend: str = "reference"):
    """Fused retrieval: scores panel (kernel) + jax.lax.top_k reduce."""
    scores = similarity(q, db, backend=backend)
    return jax.lax.top_k(scores, n)


@partial(jax.jit, static_argnames=("backend", "k"))
def elo_scan(ratings, a_idx, b_idx, outcome, valid, *, k: float = 32.0,
             backend: str = "reference"):
    """Batched ELO replay: (Q,M) ratings x (Q,T) records -> (Q,M)."""
    return _dispatch(backend, partial(ref.elo_scan_ref, k=k),
                     partial(elo_scan_pallas, k=k),
                     ratings, a_idx, b_idx, outcome, valid)


@partial(jax.jit, static_argnames=("backend", "n", "k", "p", "axis_name"))
def retrieve_replay_select(q, emb, model_a, model_b, outcome, valid, size,
                           init_ratings, global_ratings, costs, budgets, *,
                           n: int, k: float = 32.0, p: float = 0.5,
                           backend: str = "reference", axis_name=None):
    """Fused routing retrieval: similarity panel + masked top-k + device
    record gather + batched ELO replay, one dispatch, no host transfers,
    with the budget-selection epilogue fused in: the replay stage also
    combines Score = p*Global + (1-p)*Local against `global_ratings`,
    masks models costing over `budgets`, and emits the per-query argmax
    (cheapest-model fallback) — the serving hot path reads (Q,) choices
    with no second op over the (Q, M) scores. A caller that wants only
    Eagle-Local takes `local`: the epilogue does not touch it.

    With `axis_name` this is the per-shard body of the capacity-sharded
    chain (DESIGN.md §12), run under the caller's shard_map: the DB
    panels arrive as this shard's contiguous row slice, and candidates
    merge across `axis_name` inside.

    Returns (local (Q,M), topk_idx (Q,n), topk_scores (Q,n),
    choices (Q,) int32)."""
    return _dispatch(
        backend,
        partial(ref.retrieve_replay_select_ref, n=n, k=k, p=p,
                axis_name=axis_name),
        partial(retrieve_replay_select_pallas, n=n, k=k, p=p,
                axis_name=axis_name),
        q, emb, model_a, model_b, outcome, valid, size, init_ratings,
        global_ratings, costs, budgets)


@partial(jax.jit, static_argnames=("backend", "causal", "window"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    backend: str = "reference"):
    return _dispatch(backend,
                     partial(ref.flash_attention_ref, causal=causal,
                             window=window),
                     partial(flash_attention_pallas, causal=causal,
                             window=window),
                     q, k, v)


@partial(jax.jit, static_argnames=("backend",))
def decode_attention(q, k, v, kv_len, *, backend: str = "reference"):
    return _dispatch(backend, ref.decode_attention_ref,
                     decode_attention_pallas, q, k, v, kv_len)
