"""Pallas TPU kernel: batched local-ELO replay.

Eagle-Local replays N neighbor feedback records per query. The replay is
sequential in T (a true scan) but embarrassingly parallel across queries.
GPU thinking assigns one thread per query; on TPU we keep a
(n_models, block_q) rating tile resident in VMEM and apply each of the T
updates as a one-hot masked add over the whole tile — pure VPU work with
no gather/scatter (DESIGN.md §3).

Layout: the callers' ratings (Q, M) and records (Q, T) are transposed
by the wrappers so queries run along lanes: ratings (M, Qp) fp32,
records (T, Qp) int32/fp32. Grid over Q blocks; T is walked with a
fori_loop that reads record row i of each (T, block_q) tile.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _replay(r0, a_ref, b_ref, s_ref, v_ref, k: float):
    """Replay the (T, BQ) record tiles into the (M, BQ) rating tile.
    Queries sit on lanes and models on sublanes, so record i of every
    query is one dynamic sublane row of each record ref — a ref load
    the TPU lowering supports, where a dynamic lane slice of a loaded
    value is not."""
    m = r0.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)

    def step(i, r):
        a = a_ref[pl.ds(i, 1), :]                              # (1, BQ)
        b = b_ref[pl.ds(i, 1), :]
        s = s_ref[pl.ds(i, 1), :]
        v = v_ref[pl.ds(i, 1), :]
        one_a = (iota == a).astype(jnp.float32)                # (M, BQ)
        one_b = (iota == b).astype(jnp.float32)
        r_a = jnp.sum(r * one_a, axis=0, keepdims=True)        # (1, BQ)
        r_b = jnp.sum(r * one_b, axis=0, keepdims=True)
        e_a = 1.0 / (1.0 + jnp.exp2(jnp.log2(10.0) * (r_b - r_a) / 400.0))
        delta = k * (s - e_a) * v
        return r + delta * (one_a - one_b)

    return jax.lax.fori_loop(0, a_ref.shape[0], step, r0)


def _elo_kernel(r_ref, a_ref, b_ref, s_ref, v_ref, out_ref, *, k: float):
    out_ref[...] = _replay(r_ref[...], a_ref, b_ref, s_ref, v_ref, k)


def _first_index_where(mask, iota, m):
    """Index of the first True along the model (sublane) axis (==
    jnp.argmax tie-breaking) as a masked min — no argmax/argmin
    primitives inside the kernel body."""
    return jnp.min(jnp.where(mask, iota, m), axis=0, keepdims=True)


def _elo_select_kernel(r_ref, a_ref, b_ref, s_ref, v_ref, g_ref, c_ref,
                       bud_ref, out_ref, ch_ref, *, k: float, p: float):
    r = _replay(r_ref[...], a_ref, b_ref, s_ref, v_ref, k)    # (M, BQ)
    out_ref[...] = r
    m = r.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)

    # budget-selection epilogue, straight out of VMEM: combine with the
    # global prior, mask by affordability, first-max argmax (matching
    # jnp.argmax tie-breaking), cheapest-model fallback.
    g = g_ref[...]                                # (M, 1)
    c = c_ref[...]                                # (M, 1)
    bud = bud_ref[...]                            # (1, BQ)
    combined = p * g + (1.0 - p) * r              # (M, BQ)
    feasible = c <= bud                           # (M, BQ)
    masked = jnp.where(feasible, combined, -jnp.inf)
    mx = jnp.max(masked, axis=0, keepdims=True)
    choice = _first_index_where(masked == mx, iota, m)       # (1, BQ)
    cmin = jnp.min(c, axis=0, keepdims=True)
    fallback = _first_index_where(c == cmin, iota, m)        # (1, 1)
    any_ok = jnp.max(feasible.astype(jnp.int32), axis=0, keepdims=True) > 0
    ch_ref[...] = jnp.where(any_ok, choice, fallback)


def _lanes_major(block_q, ratings, a_idx, b_idx, outcome, valid):
    """Pad the query axis to a block multiple and put it on lanes:
    ratings (Q, M) -> (M, Qp) f32, records (Q, T) -> (T, Qp)."""
    q = ratings.shape[0]
    pq = (-q) % block_q
    pad_t = lambda x: jnp.pad(x, ((0, pq), (0, 0))).T
    return (pad_t(ratings.astype(jnp.float32)), pad_t(a_idx),
            pad_t(b_idx), pad_t(outcome.astype(jnp.float32)),
            pad_t(valid.astype(jnp.float32)))


def elo_scan_select_pallas(ratings, a_idx, b_idx, outcome, valid,
                           global_ratings, costs, budgets, *,
                           p: float = 0.5, k: float = 32.0,
                           block_q: int = 128, interpret: bool = False):
    """Batched ELO replay with the budget-selection epilogue fused into
    the same kernel body: after the T-step replay the (M, block_q)
    rating tile is combined with the global prior
    (Score = p*Global + (1-p)*Local), budget-masked, and argmax-reduced
    while still resident in VMEM — choices never round-trip a second op
    through HBM.

    ratings: (Q, M) replay init; records (Q, T); global_ratings (M,);
    costs (M,); budgets (Q,). Returns (ratings (Q, M) f32,
    choices (Q,) int32)."""
    q, m = ratings.shape
    t = a_idx.shape[1]
    args = _lanes_major(block_q, ratings, a_idx, b_idx, outcome, valid)
    qp = args[0].shape[1]
    bud = jnp.pad(budgets.astype(jnp.float32), (0, qp - q))[None, :]
    col = lambda x: x.astype(jnp.float32)[:, None]
    out, choices = pl.pallas_call(
        partial(_elo_select_kernel, k=k, p=p),
        grid=(qp // block_q,),
        in_specs=[
            pl.BlockSpec((m, block_q), lambda i: (0, i)),
            pl.BlockSpec((t, block_q), lambda i: (0, i)),
            pl.BlockSpec((t, block_q), lambda i: (0, i)),
            pl.BlockSpec((t, block_q), lambda i: (0, i)),
            pl.BlockSpec((t, block_q), lambda i: (0, i)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, block_q), lambda i: (0, i)),
        ],
        out_specs=[pl.BlockSpec((m, block_q), lambda i: (0, i)),
                   pl.BlockSpec((1, block_q), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((m, qp), jnp.float32),
                   jax.ShapeDtypeStruct((1, qp), jnp.int32)],
        interpret=interpret,
        name="eagle_elo_replay_select",
    )(*args, col(global_ratings), col(costs), bud)
    return out[:, :q].T, choices[0, :q]


def elo_scan_pallas(ratings, a_idx, b_idx, outcome, valid, *, k: float = 32.0,
                    block_q: int = 128, interpret: bool = False):
    """ratings: (Q, M) initial; records (Q, T). Returns (Q, M) replayed."""
    q, m = ratings.shape
    t = a_idx.shape[1]
    args = _lanes_major(block_q, ratings, a_idx, b_idx, outcome, valid)
    qp = args[0].shape[1]
    out = pl.pallas_call(
        partial(_elo_kernel, k=k),
        grid=(qp // block_q,),
        in_specs=[pl.BlockSpec((m, block_q), lambda i: (0, i))]
        + [pl.BlockSpec((t, block_q), lambda i: (0, i))] * 4,
        out_specs=pl.BlockSpec((m, block_q), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, qp), jnp.float32),
        interpret=interpret,
        name="eagle_elo_replay",
    )(*args)
    return out[:, :q].T
