"""Fused routing retrieval: the whole Eagle-Local hot path as one
device-resident chain (DESIGN.md §3).

Chains the similarity_topk and elo_scan Pallas kernels — similarity panel
(MXU) -> masked top-k -> neighbor-record gather (jnp.take, on device) ->
batched ELO replay (VPU one-hot masked adds) — without materializing any
intermediate on host. The only host interaction of a routing step is the
final (Q,) choice readout by the caller; everything between the query
embeddings and the model scores stays in HBM/VMEM.

The top-k + gather glue is ordinary jnp (data-dependent sorts and
gathers map poorly onto the VPU — see similarity_topk.py); under jit the
whole chain lowers into a single XLA computation between the two Pallas
calls, so "fused" here means one dispatch and zero host round-trips, not
one monolithic kernel body.

One wrapper serves one device and a capacity-sharded DB alike: given a
mesh axis it is the per-shard body of a shard_map, and the shared glue
adds the shard's row offset and the cross-shard merge (DESIGN.md §12).
"""
from __future__ import annotations

from functools import partial

import jax.numpy as jnp

from repro.kernels.elo_scan import elo_scan_select_pallas
from repro.kernels.ref import retrieve_replay_pipeline
from repro.kernels.similarity_topk import similarity_pallas


def retrieve_replay_select_pallas(q, emb, model_a, model_b, outcome, valid,
                                  size, init_ratings, global_ratings, costs,
                                  budgets, *, n, k: float = 32.0,
                                  p: float = 0.5, axis_name=None,
                                  interpret: bool = False):
    """q: (Q,D); emb: (C,D); records: (C,R); size: () live-row count;
    init_ratings: (M,) or (Q,M) replay starting point; global_ratings
    (M,) combine prior, costs (M,), budgets (Q,), p score weight
    (static).

    The budget-selection epilogue is fused into the ELO kernel body
    (elo_scan_select_pallas): the replay tile is combined with the
    global prior, budget-masked and argmax-reduced in VMEM, so the
    per-query choice leaves the kernel directly instead of
    materializing the (Q, M) scores through a second op. The
    top-k/gather glue, and with `axis_name` the cross-shard merge, is
    shared with the reference backend (retrieve_replay_pipeline). On a
    mesh the similarity kernel runs on this shard's row range: panel
    slicing leaves the kernel's per-column D-accumulation and its
    (128, 256) blocking untouched, so the scores — and everything
    downstream — stay bit-identical to one device.

    Returns (local_ratings (Q,M), topk_idx (Q,n), topk_scores (Q,n),
    choices (Q,) int32); topk rows past `size` score -inf (misses), and
    their records are masked out of the replay."""

    def replay_select(init, a, b, s, v):
        return elo_scan_select_pallas(
            init.astype(jnp.float32), a, b, s.astype(jnp.float32), v,
            global_ratings, costs, budgets, p=p, k=k, interpret=interpret)

    return retrieve_replay_pipeline(
        partial(similarity_pallas, interpret=interpret), replay_select, q,
        emb, model_a, model_b, outcome, valid, size, init_ratings, n=n,
        axis_name=axis_name)
