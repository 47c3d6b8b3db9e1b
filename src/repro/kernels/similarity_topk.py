"""Pallas TPU kernel: fused L2-normalize + cosine-similarity score panel.

The router retrieval hot spot (DESIGN.md §3): queries x vector-DB scores.
The DB is streamed HBM->VMEM in (block_n, D) panels; the query block stays
resident; the MXU computes the (block_q, D)x(D, block_n) panel with the
row normalization fused in VMEM. Top-k over the panel is left to
jax.lax.top_k (data-dependent sorts map poorly onto the VPU — see ops.py).

Blocks are MXU-aligned (multiples of 128 on the matmul dims); D is kept
whole per panel (1536 floats/row ~ 6 KiB: a 256-row panel is 1.5 MiB,
comfortably inside the ~16 MiB VMEM budget together with the query block).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sim_kernel(q_ref, db_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)
    db = db_ref[...].astype(jnp.float32)
    qn = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-18)
    dn = db * jax.lax.rsqrt(jnp.sum(db * db, axis=-1, keepdims=True) + 1e-18)
    out_ref[...] = jax.lax.dot_general(
        qn, dn, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def similarity_pallas(q, db, *, block_q: int = 128, block_n: int = 256,
                      interpret: bool = False):
    """q: (Q, D), db: (N, D) -> (Q, N) cosine scores (fp32)."""
    qn, d = q.shape
    n = db.shape[0]
    pq = (-qn) % block_q
    pn = (-n) % block_n
    qp = jnp.pad(q, ((0, pq), (0, 0))) if pq else q
    dbp = jnp.pad(db, ((0, pn), (0, 0))) if pn else db
    grid = ((qn + pq) // block_q, (n + pn) // block_n)
    out = pl.pallas_call(
        _sim_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn + pq, n + pn), jnp.float32),
        interpret=interpret,
        name="eagle_similarity",
    )(qp, dbp)
    return out[:qn, :n]


# ---------------------------------------------------------------------------
# capacity-sharded retrieval: local top-k + cross-shard merge (DESIGN.md §12)
# ---------------------------------------------------------------------------

def shard_local_topk(scores, n: int):
    """Per-shard candidate reduce over a LOCAL score panel (Q, C_l):
    keep min(n, C_l) candidates. That per-shard k is exact — any single
    shard can contribute at most min(n, C_l) rows of the global top-n,
    so the merged pool provably contains the true global top-n.
    Returns (top_scores (Q, kl), top_local_idx (Q, kl))."""
    return jax.lax.top_k(scores, min(n, scores.shape[-1]))


def shard_merge_topk(top_s, top_i, payloads, n: int, axis_name: str):
    """Cross-shard top-k merge: all-gather every shard's kl candidates
    (XLA lowers the gather as a ring/tree exchange), pool them per
    query, and take the final top-n reduce. `payloads` are per-shard
    candidate tensors (Q, kl, ...) carried through the merge by
    position, so the winners' records arrive with them and no second
    cross-shard gather of arbitrary rows is needed.

    Tie-breaking contract: the pool is ordered (shard asc, local rank
    asc). jax.lax.top_k breaks ties toward the lowest index, and local
    rank order is ascending-local-row among equal scores, so under the
    CONTIGUOUS capacity partition equal-score candidates appear in
    ascending GLOBAL row order — the final reduce is bit-identical to
    a single-device top_k over the full panel, dead (-inf) rows
    included. Returns (merged_s (Q,n), merged_i (Q,n), merged_payloads)."""
    gather = partial(jax.lax.all_gather, axis_name=axis_name)

    def pool(x):  # (S, Q, kl, ...) -> (Q, S*kl, ...)
        s, q, kl = x.shape[:3]
        return jnp.moveaxis(x, 0, 1).reshape((q, s * kl) + x.shape[3:])

    pool_s, pool_i = pool(gather(top_s)), pool(gather(top_i))
    merged_s, pos = jax.lax.top_k(pool_s, n)
    merged_i = jnp.take_along_axis(pool_i, pos, axis=1)
    merged_payloads = tuple(
        jnp.take_along_axis(
            pool(gather(p)),
            pos.reshape(pos.shape + (1,) * (p.ndim - 2)), axis=1)
        for p in payloads)
    return merged_s, merged_i, merged_payloads
