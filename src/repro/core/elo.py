"""ELO rating engine (Eq. 1-2 of the paper), as jittable JAX scans.

The paper's core mechanism: transform sparse pairwise feedback
(model_a, model_b, outcome) into a full per-model rating vector with

    E  = 1 / (1 + 10^((R_opp - R) / 400))        (expected score)
    R' = R + K * (S - E)                          (update, K=32)

Two operating modes:

  * global: one long scan over the entire feedback log (initialization),
    or over only the NEW records (incremental update) — this asymmetry is
    exactly the paper's efficiency claim: updating is O(new records),
    with no retraining.
  * local: a batched scan — Q queries each replay their N retrieved
    neighbor records starting from the global ratings (Eagle-Local).

Updates are formulated as one-hot masked adds on the whole rating vector
(VPU-friendly: no scatter), which is also how the Pallas kernel
(repro.kernels.elo_scan) lays it out in VMEM.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_RATING = 1000.0


def expected_score(r_a, r_b):
    """P(a beats b) under the ELO model."""
    return 1.0 / (1.0 + jnp.power(10.0, (r_b - r_a) / 400.0))


def elo_step(ratings, a_idx, b_idx, outcome, k, valid=True):
    """One pairwise update on a (..., M) rating tensor.

    a_idx/b_idx: int32 model indices (...,); outcome: S for model a
    (1 win / 0.5 draw / 0 loss); valid: mask, False leaves ratings as-is.
    """
    m = ratings.shape[-1]
    r_a = jnp.take_along_axis(ratings, a_idx[..., None], axis=-1)[..., 0]
    r_b = jnp.take_along_axis(ratings, b_idx[..., None], axis=-1)[..., 0]
    e_a = expected_score(r_a, r_b)
    delta = k * (outcome - e_a)
    v = jnp.asarray(valid, ratings.dtype)
    one_a = jax.nn.one_hot(a_idx, m, dtype=ratings.dtype)
    one_b = jax.nn.one_hot(b_idx, m, dtype=ratings.dtype)
    return ratings + (v * delta)[..., None] * (one_a - one_b)


@partial(jax.jit, static_argnames=("k",))
def elo_scan(ratings, a_idx, b_idx, outcome, valid=None, *, k: float = 32.0):
    """Replay T records in arrival order.

    ratings: (..., M) initial;  a_idx/b_idx/outcome/valid: (T, ...) —
    leading time axis, remaining axes broadcast against ratings' batch
    dims (use (T,) for global, (T, Q) for per-query local replays).
    """
    if valid is None:
        valid = jnp.ones(a_idx.shape, bool)

    def step(r, rec):
        a, b, s, v = rec
        return elo_step(r, a, b, s, k, v), None

    out, _ = jax.lax.scan(step, ratings, (a_idx, b_idx, outcome, valid))
    return out


def local_elo(global_ratings, nbr_a, nbr_b, nbr_outcome, nbr_valid,
              *, k: float = 32.0):
    """Eagle-Local: per-query replay of retrieved neighbor feedback.

    global_ratings: (M,) — the background knowledge each query starts from.
    nbr_*: (Q, N) neighbor records per query.
    Returns (Q, M) local ratings.
    """
    q, n = nbr_a.shape
    m = global_ratings.shape[-1]
    init = jnp.broadcast_to(global_ratings, (q, m))
    # scan over the N neighbor slots; batch over Q inside each step
    return elo_scan(init, nbr_a.T, nbr_b.T, nbr_outcome.T, nbr_valid.T, k=k)


def _pad_bucket(t: int, floor: int = 64) -> int:
    """Round a count up to a power-of-two bucket so the jitted consumer
    compiles once per bucket, not once per length — the online path must
    stay O(new records) wall-clock, not O(compiles). `floor` is the
    smallest bucket (64 for record scans; the query-side dispatch cache
    in core.dispatch uses a smaller floor for tiny batches)."""
    b = floor
    while b < t:
        b *= 2
    return b


def _scan_padded(ratings, a_idx, b_idx, outcome, k):
    """Pad the records to their bucket on the host: a device-side pad
    would compile once per distinct record count, on the serving path."""
    t = np.shape(a_idx)[0]
    tb = _pad_bucket(t)
    pad = tb - t
    a = np.pad(np.asarray(a_idx, np.int32), (0, pad))
    b = np.pad(np.asarray(b_idx, np.int32), (0, pad))
    s = np.pad(np.asarray(outcome, np.float32), (0, pad))
    v = np.arange(tb) < t
    return elo_scan(ratings, a, b, s, v, k=k)


def fit_global(n_models: int, a_idx, b_idx, outcome, *, k: float = 32.0,
               init: float = DEFAULT_RATING):
    """Eagle-Global initialization: one pass over the full history."""
    ratings = jnp.full((n_models,), init, jnp.float32)
    return _scan_padded(ratings, a_idx, b_idx, outcome, k)


def update_global(ratings, new_a, new_b, new_outcome, *, k: float = 32.0):
    """Incremental Eagle-Global update: scan only the NEW records."""
    return _scan_padded(ratings, new_a, new_b, new_outcome, k)
