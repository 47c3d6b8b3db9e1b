"""Plain reference of the Eagle router (arXiv:2409.15518, section 2.2).

Imports nothing of the program. Retrieval is cosine similarity over the
DB rebuilt from the benchmark's own data; the exact ordering is settled
in float64 on the host, after a float32 search on the device at
Precision.HIGHEST narrows each query to a few candidates more than it
needs. ELO (Eq. 1-2) is replayed in float64 with numpy.

`dtype` / `control=True` computes the same in the nearest precision
below the one the configuration states: the panel and queries rounded
to bfloat16 with a three-pass (`high`) dot, and the ratings in bfloat16.
That is the control of the comparison that decides `correct`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16
#: search this many candidates beyond N before the float64 re-ranking
CAND_EXTRA = 12


def precision_dtype(control: bool):
    return BF16 if control else np.float64


# ---------------------------------------------------------------------------
# ELO
# ---------------------------------------------------------------------------

def elo_fold(ratings, a, b, o, k: float, dtype=np.float64):
    """Sequential ELO over records (a[i] vs b[i], outcome o[i] for a)."""
    r = np.asarray(ratings).astype(dtype).copy()
    kk, c400, ten, one = (dtype(k), dtype(400.0), dtype(10.0), dtype(1.0))
    for ai, bi, oi in zip(np.asarray(a).tolist(), np.asarray(b).tolist(),
                          np.asarray(o).tolist()):
        e = one / (one + ten ** ((r[bi] - r[ai]) / c400))
        d = kk * (dtype(oi) - e)
        r[ai] = r[ai] + d
        r[bi] = r[bi] - d
    return r


def replay_select(prior, a, b, o, v, costs, budgets, *, p: float, k: float,
                  dtype=np.float64):
    """Eagle-Local replay of (Q, T) records (already in replay order)
    from `prior` (M,), combined with the prior: Score = p*Global +
    (1-p)*Local. Returns (scores (Q, M), feasible (Q, M)).

    The combine uses `prior` as Global, as the program's route does."""
    q, t = a.shape
    m = prior.shape[0]
    r = np.broadcast_to(np.asarray(prior).astype(dtype), (q, m)).copy()
    rows = np.arange(q)
    kk, c400, ten, one = (dtype(k), dtype(400.0), dtype(10.0), dtype(1.0))
    for i in range(t):
        ai, bi = a[:, i], b[:, i]
        ra, rb = r[rows, ai], r[rows, bi]
        e = one / (one + ten ** ((rb - ra) / c400))
        d = kk * (o[:, i].astype(dtype) - e) * v[:, i].astype(dtype)
        r[rows, ai] = ra + d
        r[rows, bi] = r[rows, bi] - d
    g = np.asarray(prior).astype(dtype)
    scores = dtype(p) * g[None, :] + dtype(1.0 - p) * r
    feasible = np.asarray(costs)[None, :] <= np.asarray(budgets)[:, None]
    return scores.astype(np.float64), feasible


def choice_gaps(scores, feasible, costs, choices):
    """Per query: how far the given choice's score lies below the best
    affordable score (0 when it is the best). A choice that is not
    affordable while something is, or that is not the cheapest model
    when nothing is, reads +inf."""
    masked = np.where(feasible, scores, -np.inf)
    best = masked.max(axis=1)
    any_ok = feasible.any(axis=1)
    cheapest = int(np.argmin(costs))
    ch = np.asarray(choices)
    got = masked[np.arange(len(ch)), ch]
    ok = any_ok & np.isfinite(got)
    gap = np.full(len(ch), np.inf)
    gap[ok] = best[ok] - got[ok]
    gap[~any_ok & (ch == cheapest)] = 0.0
    return gap


# ---------------------------------------------------------------------------
# retrieval
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n", "control"))
def _search_block(panels, q, sizes, *, n: int, control: bool):
    """Top-n by cosine of q (B, D) over the concatenation of `panels`
    (each (rows_i, D), not normalized), rows at or past sizes[b]
    masked out."""
    qn = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    prec = jax.lax.Precision.HIGH if control else jax.lax.Precision.HIGHEST
    if control:
        qn = qn.astype(jnp.bfloat16).astype(jnp.float32)
    parts = []
    for p in panels:
        inv = jax.lax.rsqrt(jnp.sum(p * p, axis=-1))
        if control:
            pn = (p * inv[:, None]).astype(jnp.bfloat16)
            s = jnp.dot(qn.astype(jnp.bfloat16), pn.T,
                        preferred_element_type=jnp.float32, precision=prec)
        else:
            s = jnp.dot(qn, p.T, precision=prec) * inv[None, :]
        parts.append(s)
    s = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    live = jnp.arange(s.shape[1])[None, :] < sizes[:, None]
    s = jnp.where(live, s, -jnp.inf)
    return jax.lax.top_k(s, n)


def device_search(panels, queries, sizes, n: int, *, control: bool = False,
                  block: int = 256, block_rows=None, devices=None):
    """Top-n search on the device over the rows of `panels` (host arrays
    (rows_i, D), one panel in row order), in blocks of at most
    `block_rows` rows (default: all of them, one block). Block k is put
    on devices[k % len(devices)] (default: JAX's default device) and
    searched `block` queries at a time; its top-n keep their global row
    ids. A round puts one block on each device and reads its results
    back before the next, so no device holds two. Blocks' candidates
    are merged on the host, ties to the lower row: the order lax.top_k
    gives over the concatenation, so the result is that of one block.
    Returns host (scores, rows)."""
    total = sum(len(p) for p in panels)
    block_rows = block_rows or total
    devices = list(devices) if devices else [None]
    sizes = np.asarray(sizes)
    starts = list(range(0, total, block_rows))
    found = []
    for r0 in range(0, len(starts), len(devices)):
        pending = []
        for lo, dev in zip(starts[r0:r0 + len(devices)], devices):
            parts = tuple(jax.device_put(p, dev) for p in
                          _row_slices(panels, lo, lo + block_rows))
            pending.append((lo, _search_queries(
                parts, queries, sizes - lo, n, control, block, dev)))
            del parts
        for lo, runs in pending:
            s = np.concatenate([np.asarray(s)[:k] for s, _, k in runs])
            i = np.concatenate([np.asarray(i)[:k] for _, i, k in runs])
            found.append((s, i + lo))
    if len(found) == 1:
        return found[0]
    s = np.concatenate([f[0] for f in found], axis=1)
    i = np.concatenate([f[1] for f in found], axis=1)
    # a stable sort keeps equal scores in block order, and each block's
    # equal scores in row order
    order = np.argsort(-s, axis=1, kind="stable")[:, :n]
    return (np.take_along_axis(s, order, axis=1),
            np.take_along_axis(i, order, axis=1))


def _row_slices(panels, lo: int, hi: int):
    """Views of rows [lo, hi) of the panels taken as one, panel by
    panel."""
    out, at = [], 0
    for p in panels:
        a, b = max(lo - at, 0), min(hi - at, len(p))
        if a < b:
            out.append(p[a:b])
        at += len(p)
    return out


def _search_queries(parts, queries, sizes, n, control, block, dev):
    """_search_block over every `block` queries, enqueued on `dev`.
    Returns per query block (device scores, device local rows, number
    of queries kept)."""
    runs = []
    nq = len(queries)
    for lo in range(0, nq, block):
        q = queries[lo:lo + block]
        sz = sizes[lo:lo + block]
        pad = block - len(q)
        if pad:
            q = np.concatenate([q, np.repeat(q[:1], pad, 0)])
            sz = np.concatenate([sz, np.repeat(sz[:1], pad)])
        s, i = _search_block(parts,
                             jax.device_put(np.asarray(q, np.float32), dev),
                             jax.device_put(np.asarray(sz, np.int32), dev),
                             n=n, control=control)
        runs.append((s, i, block - pad))
    return runs


def exact_cosines(row_emb_fn, queries, rows):
    """float64 cosine of each query with each of its rows (Q, K)."""
    qn = queries.astype(np.float64)
    qn /= np.linalg.norm(qn, axis=-1, keepdims=True)
    e = row_emb_fn(rows.reshape(-1)).astype(np.float64)
    e /= np.linalg.norm(e, axis=-1, keepdims=True)
    e = e.reshape(rows.shape + (-1,))
    return np.einsum("qkd,qd->qk", e, qn)


def exact_topk(row_emb_fn, queries, cand_rows, n: int):
    """Re-rank candidate rows in float64; ties go to the lower row, as
    in a stable top-k. Returns (rows (Q, n), cosines (Q, n))."""
    cos = exact_cosines(row_emb_fn, queries, cand_rows)
    out_r = np.empty((len(queries), n), np.int64)
    out_c = np.empty((len(queries), n), np.float64)
    for i in range(len(queries)):
        order = np.lexsort((cand_rows[i], -cos[i]))[:n]
        out_r[i], out_c[i] = cand_rows[i][order], cos[i][order]
    return out_r, out_c


def topk_gaps(row_emb_fn, queries, ref_cos, got_rows, sizes):
    """Position-wise: how far the reference cosine of the row returned
    at position j lies below the reference's j-th best (0 when the same
    or an exact tie). A row that is not a live row reads +inf."""
    got = np.asarray(got_rows)
    bad = (got < 0) | (got >= np.asarray(sizes)[:, None])
    safe = np.where(bad, 0, got)
    cos = exact_cosines(row_emb_fn, queries, safe)
    gap = np.maximum(ref_cos - cos, 0.0)
    return np.where(bad, np.inf, gap).max(axis=1)
