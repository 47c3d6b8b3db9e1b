"""The router's feedback history and its query traffic, made from the seed.

The history is random unit directions (as `data.routerbench.bulk_history`
draws them) with 1 to R-1 pairwise records per prompt, so one slot stays
free. An unsharded DB's history is made on the device in one jitted
call. A DB sharded over S devices may be larger than any one of them
holds, so its history is made one shard's block of rows at a time, each
on its shard's device from a key of its own, and copied to the host as
soon as it is made. The host copy is what the program's VectorDB is
filled from, and the benchmark keeps it: the reference rebuilds the DB
from it and from the feedback the run folded, never from the program's
buffers.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
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
    raw: np.ndarray        # (n, D) f32, not normalized (Rows if sharded)
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
                  noise: float, shards: int = 1, capacity: int = 0):
    """Make the history and a pool of n_queries queries, copy both to
    the host. With shards > 1 the history is made in blocks of the
    shard's capacity // shards rows (history_blocks). Returns (History,
    queries (n_queries, D) f32)."""
    ks = seed32(seed, 1)
    src = np.random.default_rng([seed % (1 << 64), 2]).integers(
        0, rows, n_queries)
    qkey = jax.random.key(seed32(seed, 3))
    if shards == 1:
        dev = make_history(jax.random.key(ks), n=rows, d=dim, m=n_models,
                           r=records)
        q = make_queries(qkey, dev[0], jnp.asarray(src, jnp.int32),
                         noise=noise)
        host = [np.asarray(x) for x in dev]
        del dev
    else:
        host = history_blocks(seed, rows=rows, dim=dim, n_models=n_models,
                              records=records,
                              block_rows=capacity // shards,
                              devices=jax.devices()[:shards])
        q = make_queries(qkey, jnp.asarray(host[0][src]),
                         jnp.arange(n_queries, dtype=jnp.int32), noise=noise)
    queries = np.asarray(q)
    del q
    hist = History(*host, fit_rows=fit_rows, key_seed=ks)
    return hist, queries


def history_blocks(seed: int, *, rows: int, dim: int, n_models: int,
                   records: int, block_rows: int, devices):
    """The history in blocks of block_rows rows (the last one shorter):
    block b is made from the key seed32(seed, 1, b) on
    devices[b % len(devices)] and copied to the host, one host thread a
    device, side by side (4 x 2^20 rows on a 4-chip v5e host: 33-47 s,
    against 51-54 s one block after another). Its embeddings stay on the
    host as they arrive (a Rows of the blocks: no second copy of the
    whole), and the device's copy is freed, so no device holds more than
    one block. Returns host (raw, a, b, o, n_rec)."""
    recs = [np.empty((rows, records), np.int32),
            np.empty((rows, records), np.int32),
            np.empty((rows, records), np.float32),
            np.empty((rows,), np.int32)]
    starts = list(range(0, rows, block_rows))

    def make(blk: int):
        lo = starts[blk]
        hi = min(lo + block_rows, rows)
        key = jax.device_put(jax.random.key(seed32(seed, 1, blk)),
                             devices[blk % len(devices)])
        emb, *rest = make_history(key, n=hi - lo, d=dim, m=n_models,
                                  r=records)
        for dst, x in zip(recs, rest):
            dst[lo:hi] = np.asarray(x)
        return np.asarray(emb)

    with ThreadPoolExecutor(len(devices)) as ex:
        raw = Rows(list(ex.map(make, range(len(starts)))))
    return [raw, *recs]


class Rows:
    """An (n, D) array held as host blocks in row order. Row slices
    inside one block are views of it; gathers and slices across blocks
    copy only the rows they take."""

    def __init__(self, blocks):
        self.blocks = blocks
        self.starts = np.cumsum([0] + [len(b) for b in blocks])
        self.shape = (int(self.starts[-1]),) + blocks[0].shape[1:]
        self.dtype = blocks[0].dtype

    def __len__(self) -> int:
        return self.shape[0]

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            lo, hi, step = idx.indices(len(self))
            if step != 1:
                raise IndexError("Rows takes row slices of step 1")
            parts = [b[max(lo - s, 0):hi - s]
                     for b, s in zip(self.blocks, self.starts)
                     if s < hi and lo < s + len(b)]
            if len(parts) == 1:
                return parts[0]
            if not parts:
                return np.empty((0,) + self.shape[1:], self.dtype)
            return np.concatenate(parts)
        idx = np.asarray(idx)
        blk = np.searchsorted(self.starts, idx, side="right") - 1
        out = np.empty(idx.shape + self.shape[1:], self.dtype)
        for k in np.unique(blk):
            at = blk == k
            out[at] = self.blocks[k][idx[at] - self.starts[k]]
        return out

    def __array__(self, dtype=None, copy=None):
        out = np.concatenate(self.blocks)
        return out if dtype is None else out.astype(dtype)


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
