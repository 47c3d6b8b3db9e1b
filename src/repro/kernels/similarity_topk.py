"""Pallas TPU kernel: fused L2-normalize + cosine-similarity score panel,
and the exact top-k that reduces the panel (DESIGN.md §3).

The router retrieval hot spot: queries x vector-DB scores. The DB is
streamed HBM->VMEM in (block_n, D) panels; the query block stays
resident; the MXU computes the (block_q, D)x(D, block_n) panel with the
row normalization fused in VMEM. The kernel's result is the one f32
panel; the top-k over it is ordinary XLA (data-dependent sorts map
poorly onto the VPU — see ops.py).

Blocks are MXU-aligned (multiples of 128 on the matmul dims); D is kept
whole per panel (1536 floats/row ~ 6 KiB: a 256-row panel is 1.5 MiB,
comfortably inside the ~16 MiB VMEM budget together with the query block).

Top-k over the panel (`panel_topk`). `lax.top_k` over a (Q, C) panel
with C = 2^20 runs at about 15x the time of one read of the panel, so a
wide panel takes an exact two-stage top-k instead:

  1. chunk maxima: the max of each chunk of TOPK_CHUNK consecutive rows,
     the live-row mask fused into the reduce, so the panel is read once
     and no masked copy is written;
  2. chunk selection: `lax.top_k` over the (Q, C/B) maxima, the n chunk
     ids sorted ascending;
  3. candidate gather: the n chunks' B rows each, (Q, n·B), live-masked
     again from their row ids;
  4. final top-k: `lax.top_k` over the candidates, positions mapped back
     to row ids.

Exactness. Order (score, row) pairs by score descending, then row
ascending: `lax.top_k` returns the first n pairs of that order. A chunk
holding one of them has a best pair at least as high, and the chunk
ranking of stage 2 (max descending, then chunk id ascending) is that
order of the chunks' best pairs, because chunks are contiguous. At most
n chunks have a best pair at or above the n-th winner, each such pair
being a distinct winner; so the n best chunks hold all n winners, ties
and dead (-inf) rows included. The candidates are laid out in ascending
row order, so the final `lax.top_k` breaks ties toward the lowest row as
the one-stage top-k does: scores and row ids are bitwise equal to it.
Scores are assumed free of NaN.

Shape rule, static: the two-stage path runs when C % B == 0 and
C >= 4·n·B (10,240 rows at n = 20); below that the candidates are a
large share of the panel and one `lax.top_k` does as well.

Layout. The TPU tiles an f32 (Q, C) array in (8, 128) blocks, so its
bytes are laid out as (Q/8, C/128, 8, 128). Both panel-sized stages
index the panel through exactly that view (a bitcast there): the
chunk-max reduce runs over its lane axis and the gather takes whole
(1, 128) tile rows. A (Q, C/B, B) reshape is not a bitcast under that
tiling, and XLA would materialise a 1 GiB relayout copy for it.
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
# exact two-stage top-k over the score panel
# ---------------------------------------------------------------------------

#: rows per chunk of the two-stage top-k: one lane width, so a chunk is
#: one row of an (8, 128) tile. XLA reduces a tile row at a time either
#: way, so a wider chunk only adds a second reduce and candidates: on a
#: TPU v5e, (256, 2^20) panel, 128 took 2.32 ms and 256 took 2.52 ms
#: (one-stage lax.top_k 23.65 ms with its mask copy)
TOPK_CHUNK = 128
#: the two-stage path needs at least this many candidates' worth of rows
_MIN_CHUNKS_PER_N = 4


def two_stage_topk(c: int, n: int) -> bool:
    """Whether panel_topk reduces a (Q, c) panel to its top n in two
    stages. Both are static shapes, so the choice is made at trace
    time."""
    return c % TOPK_CHUNK == 0 and c >= _MIN_CHUNKS_PER_N * n * TOPK_CHUNK


def panel_topk(scores, n: int, size, offset=0):
    """Top n of each row of the (Q, C) score panel over the live rows:
    panel column j is row j + offset, live while below `size`; dead rows
    score -inf. Returns (top_scores (Q, n), top_rows (Q, n)) with rows
    local to the panel, bitwise equal to `lax.top_k` over the masked
    panel (module docstring: two stages when two_stage_topk(C, n))."""
    q, c = scores.shape
    if not two_stage_topk(c, n):
        live = (jnp.arange(c) + offset) < size
        return jax.lax.top_k(jnp.where(live[None, :], scores, -jnp.inf), n)
    b = TOPK_CHUNK
    sub = 8 if q % 8 == 0 else 1        # the tile's rows of queries
    # (Q/8, C/B, 8, B): the panel's bytes in the TPU's tiled order, a
    # chunk being one tile row
    tiles = scores.reshape(q // sub, sub, c // b, b).transpose(0, 2, 1, 3)
    row = (jax.lax.broadcasted_iota(jnp.int32, tiles.shape, 1) * b
           + jax.lax.broadcasted_iota(jnp.int32, tiles.shape, 3))
    maxima = jnp.where(row + offset < size, tiles, -jnp.inf).max(axis=3)
    maxima = maxima.transpose(0, 2, 1).reshape(q, c // b)
    _, chunk = jax.lax.top_k(maxima, n)
    chunk = jnp.sort(chunk, axis=1)     # candidates in ascending row order
    qi = jnp.arange(q)[:, None]
    cand = tiles[qi // sub, chunk, qi % sub].reshape(q, n * b)
    rows = (chunk[:, :, None] * b + jnp.arange(b)).reshape(q, n * b)
    cand = jnp.where(rows + offset < size, cand, -jnp.inf)
    top_s, pos = jax.lax.top_k(cand, n)
    return top_s, jnp.take_along_axis(rows, pos, axis=1)


# ---------------------------------------------------------------------------
# capacity-sharded retrieval: the cross-shard merge (DESIGN.md §12)
# ---------------------------------------------------------------------------

def shard_merge_topk(top_s, top_i, payloads, n: int, axis_name: str):
    """Cross-shard top-k merge: all-gather every shard's kl =
    min(n, C_local) candidates (XLA lowers the gather as a ring/tree
    exchange), pool them per query, and take the final top-n reduce.
    No shard holds more than kl of the global top-n, so the pool holds
    all of it. `payloads` are per-shard candidate tensors (Q, kl, ...)
    carried through the merge by position, so the winners' records
    arrive with them and no second cross-shard gather of arbitrary rows
    is needed.

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
