"""The router's feedback history and its query traffic, made from the seed.

The history is made on the device in one jitted call: random unit
directions (as `data.routerbench.bulk_history` draws them) with 1 to R-1
pairwise records per prompt, so one slot stays free. Its host copy is
what the program's VectorDB is filled from, and the benchmark keeps it:
the reference rebuilds the DB from it and from the feedback the run
folded, never from the program's buffers.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.traffic import seed32


@partial(jax.jit, static_argnames=("n", "d", "m", "r"))
def make_history(key, *, n: int, d: int, m: int, r: int):
    k = jax.random.split(key, 5)
    emb = jax.random.normal(k[0], (n, d), jnp.float32)
    a = jax.random.randint(k[1], (n, r), 0, m, jnp.int32)
    b = (a + 1 + jax.random.randint(k[2], (n, r), 0, m - 1, jnp.int32)) % m
    o = jax.random.randint(k[3], (n, r), 0, 3).astype(jnp.float32) * 0.5
    n_rec = jax.random.randint(k[4], (n,), 1, r, jnp.int32)
    return emb, a, b, o, n_rec


@partial(jax.jit, static_argnames=("noise",))
def make_queries(key, emb, src, *, noise: float):
    """Stored prompts plus noise: normalize(emb[src]) + noise * g / sqrt(D),
    so a query's nearest stored row has cosine about 1/sqrt(1 + noise^2)."""
    rows = jnp.take(emb, src, axis=0)
    rows = rows / jnp.linalg.norm(rows, axis=-1, keepdims=True)
    g = jax.random.normal(key, rows.shape, jnp.float32)
    return rows + noise * g / np.sqrt(rows.shape[-1])


@dataclasses.dataclass
class History:
    """Host copy of the generated history. Rows [0, fit_rows) seed the
    global ratings through EagleRouter.fit with their first record
    only; rows [fit_rows, n) are bulk-loaded with all their records."""
    raw: np.ndarray        # (n, D) f32, not normalized
    a: np.ndarray          # (n, R) int32
    b: np.ndarray
    o: np.ndarray          # (n, R) f32 in {0, 0.5, 1}
    n_rec: np.ndarray      # (n,) int32 live records per row
    fit_rows: int
    key_seed: int

    @property
    def n(self) -> int:
        return self.raw.shape[0]

    def fit_records(self):
        f = self.fit_rows
        return self.a[:f, 0], self.b[:f, 0], self.o[:f, 0]

    def live_records(self, rows: np.ndarray):
        """(a, b, o, valid) of history rows as the DB holds them."""
        rows = np.asarray(rows)
        r = self.a.shape[1]
        n_live = np.where(rows < self.fit_rows, 1, self.n_rec[rows])
        valid = np.arange(r)[None, :] < n_live[:, None]
        return (np.where(valid, self.a[rows], 0),
                np.where(valid, self.b[rows], 0),
                np.where(valid, self.o[rows], 0.0).astype(np.float32), valid)


def build_history(seed: int, *, rows: int, dim: int, n_models: int,
                  records: int, fit_rows: int, n_queries: int,
                  noise: float):
    """Make the history and a pool of n_queries queries on the device,
    copy both to the host. Returns (History, queries (n_queries, D) f32)."""
    ks = seed32(seed, 1)
    dev = make_history(jax.random.key(ks), n=rows, d=dim, m=n_models,
                       r=records)
    src = np.random.default_rng([seed % (1 << 64), 2]).integers(
        0, rows, n_queries)
    q = make_queries(jax.random.key(seed32(seed, 3)), dev[0],
                     jnp.asarray(src, jnp.int32), noise=noise)
    host = [np.asarray(x) for x in dev]
    queries = np.asarray(q)
    del dev, q
    hist = History(*host, fit_rows=fit_rows, key_seed=ks)
    return hist, queries


def regenerate_raw(hist: History, dim: int, n_models: int):
    """The history's embeddings again on the device (same key, same
    program: the same bits), for the reference's search."""
    dev = make_history(jax.random.key(hist.key_seed), n=hist.n, d=dim,
                       m=n_models, r=hist.a.shape[1])
    return dev[0]


@dataclasses.dataclass
class FeedbackLog:
    """Every comparison the run folded, in order: the prompts it added
    to the DB (one new row each) and the records."""
    emb: List[np.ndarray] = dataclasses.field(default_factory=list)
    a: List[np.ndarray] = dataclasses.field(default_factory=list)
    b: List[np.ndarray] = dataclasses.field(default_factory=list)
    o: List[np.ndarray] = dataclasses.field(default_factory=list)
    count: int = 0

    def add(self, emb, a, b, o):
        self.emb.append(np.asarray(emb, np.float32))
        self.a.append(np.asarray(a, np.int32))
        self.b.append(np.asarray(b, np.int32))
        self.o.append(np.asarray(o, np.float32))
        self.count += len(a)

    def arrays(self, dim: int):
        if not self.a:
            return (np.zeros((0, dim), np.float32), np.zeros(0, np.int32),
                    np.zeros(0, np.int32), np.zeros(0, np.float32))
        return (np.concatenate(self.emb), np.concatenate(self.a),
                np.concatenate(self.b), np.concatenate(self.o))
