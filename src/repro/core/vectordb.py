"""Vector database: host-side append buffer for prompt embeddings +
grouped pairwise feedback.

The retrieval unit is the PROMPT (paper §2.2: "retrieve the N nearest
neighbors ... using the prompt embedding vector"): each stored prompt
carries all pairwise feedback collected for it, and Eagle-Local replays
the FULL feedback of the N retrieved prompts.

Storage lives in host numpy (appends are the online hot path and must
cost microseconds, not device round-trips). Retrieval itself runs on
device against a RouterState (core/state.py): the buffer tracks which
rows were touched since the last sync and `state.commit()` scatters just
those rows into the device-resident state (donated buffers, O(new
records)). The `query`/`gather_feedback` methods below are the LEGACY
object-path retrieval — kept for equivalence tests against the fused
route_batch pipeline, no longer on the serving hot path.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as OBS
from repro.kernels import ops as KOPS


def _l2norm_np(x, eps=1e-9):
    return x / (np.linalg.norm(x, axis=-1, keepdims=True) + eps)


class VectorDB:
    def __init__(self, dim: int, capacity: int = 4096,
                 records_per_query: int = 8, backend: str = "reference"):
        self.dim = dim
        self.capacity = capacity
        self.rcap = records_per_query
        self.backend = backend
        self.size = 0                      # prompts stored
        self._alloc(capacity, records_per_query)
        self._row_of: Dict[int, int] = {}
        self._device: Optional[Tuple] = None  # cached device snapshot
        # rows touched since last commit, ONE ledger per device replica:
        # every registered consumer sees every touch until it drains, so
        # double-buffered states absorb rows landing between their turns
        self._dirty: Dict[str, set] = {"default": set()}

    def _alloc(self, cq, r):
        self.emb = np.zeros((cq, self.dim), np.float32)
        self.model_a = np.zeros((cq, r), np.int32)
        self.model_b = np.zeros((cq, r), np.int32)
        self.outcome = np.zeros((cq, r), np.float32)
        self.valid = np.zeros((cq, r), bool)
        self.n_rec = np.zeros((cq,), np.int32)

    def _grow(self, need_q: int = 0, need_r: int = 0):
        new_q = max(self.capacity, need_q,
                    self.capacity * 2 if need_q > self.capacity else self.capacity)
        new_r = max(self.rcap, need_r,
                    self.rcap * 2 if need_r > self.rcap else self.rcap)
        if (new_q, new_r) == (self.capacity, self.rcap):
            return
        # a grow is a shape change = full re-upload + recompiles
        # downstream; it should be RARE at steady state, so it is an
        # event worth logging, not just a counter bump
        o = OBS.get_obs(None)
        o.registry.counter(
            "vectordb_grow_total",
            "buffer reallocs (shape change -> full re-upload)").inc()
        o.emit({"kind": "db_grow", "from": [self.capacity, self.rcap],
                "to": [new_q, new_r], "size": self.size})
        emb = np.zeros((new_q, self.dim), np.float32)
        emb[:self.capacity] = self.emb
        self.emb = emb

        def grow2(a, dtype):
            out = np.zeros((new_q, new_r), dtype)
            out[:self.capacity, :self.rcap] = a
            return out

        self.model_a = grow2(self.model_a, np.int32)
        self.model_b = grow2(self.model_b, np.int32)
        self.outcome = grow2(self.outcome, np.float32)
        self.valid = grow2(self.valid, bool)
        n_rec = np.zeros((new_q,), np.int32)
        n_rec[:self.capacity] = self.n_rec
        self.n_rec = n_rec
        self.capacity, self.rcap = new_q, new_r

    def add(self, emb, model_a, model_b, outcome, query_id=None):
        """Append feedback records (host-side, O(batch)). emb: (B, D);
        query_id: (B,) — records sharing an id group under one prompt."""
        emb = np.atleast_2d(np.asarray(emb, np.float32))
        model_a = np.asarray(model_a, np.int32).reshape(-1)
        model_b = np.asarray(model_b, np.int32).reshape(-1)
        outcome = np.asarray(outcome, np.float32).reshape(-1)
        b = emb.shape[0]
        if query_id is None:
            base = -1 - len(self._row_of)
            query_id = np.arange(base, base - b, -1)
        query_id = np.asarray(query_id).reshape(-1)

        for i in range(b):
            qid = int(query_id[i])
            row = self._row_of.get(qid)
            if row is None:
                if self.size >= self.capacity:
                    self._grow(need_q=self.size + 1)
                row = self.size
                self._row_of[qid] = row
                self.size += 1
                self.emb[row] = _l2norm_np(emb[i])
            slot = self.n_rec[row]
            if slot >= self.rcap:
                self._grow(need_r=slot + 1)
            self.model_a[row, slot] = model_a[i]
            self.model_b[row, slot] = model_b[i]
            self.outcome[row, slot] = outcome[i]
            self.valid[row, slot] = True
            self.n_rec[row] += 1
            for ledger in self._dirty.values():
                ledger.add(row)
        self._appended(b)

    def add_rows(self, emb, model_a, model_b, outcome, n_records,
                 query_id=None):
        """Bulk append of whole prompt rows, vectorized (loading a
        history of 10^5-10^6 prompts through add() would be a Python
        loop per record). emb: (B, D); records (B, R) with row i's
        first n_records[i] slots live; query_id: (B,) optional ids that
        later add() calls can append feedback under."""
        emb = np.atleast_2d(np.asarray(emb, np.float32))
        n_records = np.asarray(n_records, np.int32).reshape(-1)
        b, r = emb.shape[0], np.shape(model_a)[1]
        if self.size + b > self.capacity:
            self._grow(need_q=self.size + b)
        if r > self.rcap:
            self._grow(need_r=r)
        rows = slice(self.size, self.size + b)
        live = np.arange(r)[None, :] < n_records[:, None]
        self.emb[rows] = _l2norm_np(emb)
        self.model_a[rows, :r] = np.where(live, model_a, 0)
        self.model_b[rows, :r] = np.where(live, model_b, 0)
        self.outcome[rows, :r] = np.where(live, outcome, 0.0)
        self.valid[rows, :r] = live
        self.n_rec[rows] = n_records
        if query_id is not None:
            self._row_of.update(zip(np.asarray(query_id).tolist(),
                                    range(rows.start, rows.stop)))
        for ledger in self._dirty.values():
            ledger.update(range(rows.start, rows.stop))
        self.size += b
        self._appended(int(n_records.sum()))

    def _appended(self, n_records: int):
        self._device = None  # invalidate the device snapshot
        o = OBS.get_obs(None)
        o.registry.counter("vectordb_records_total",
                           "feedback records appended").inc(n_records)
        o.registry.gauge("vectordb_size", "live prompt rows").set(self.size)
        o.registry.gauge("vectordb_capacity",
                         "allocated prompt rows").set(self.capacity)

    def register_consumer(self, name: str):
        """Open a dirty-row ledger for another device replica of this
        buffer (e.g. one half of a core.state.DoubleBuffer). The new
        ledger starts empty: the consumer is expected to take a full
        upload (commit with prev=None) as its first sync."""
        self._dirty.setdefault(name, set())

    def drain_dirty(self, consumer: str = "default") -> np.ndarray:
        """Rows touched since `consumer`'s last drain (sorted), then
        clear that ledger. The commit() path uploads exactly these rows;
        a buffer realloc (_grow) changes the array shapes, which
        commit() detects and answers with a full re-upload instead."""
        ledger = self._dirty.setdefault(consumer, set())
        rows = np.fromiter(sorted(ledger), np.int32, count=len(ledger))
        ledger.clear()
        return rows

    def drain_dirty_sharded(self, consumer: str = "default",
                            n_shards: int = 1) -> list:
        """Per-shard drain of `consumer`'s ledger: dirty rows grouped by
        OWNING shard under the contiguous capacity partition (shard s
        owns rows [s*C/S, (s+1)*C/S) — sharding.db_state_specs). The
        sharded commit scatters each group only to its shard. Stale
        rows at/past the live count are dropped here, same guard as the
        unsharded commit's."""
        rows = self.drain_dirty(consumer)
        rows = rows[rows < self.size]
        c_local = self.capacity // n_shards
        return [rows[(rows >= s * c_local) & (rows < (s + 1) * c_local)]
                for s in range(n_shards)]

    def next_capacity(self, need_q: Optional[int] = None) -> int:
        """The capacity _grow() will allocate when the buffer next
        overflows (doubling policy). The dispatch-ladder prebaker
        (core.dispatch.CapacityPrebaker) bakes executables for THIS
        shape before the grow trips on the hot path."""
        if need_q is None:
            need_q = self.capacity + 1
        if need_q <= self.capacity:
            return self.capacity
        return max(need_q, self.capacity * 2)

    def clear(self):
        """Roll the buffer back to empty without reallocating. Device
        states committed before the clear keep stale row contents, but
        `size` masks them; re-added rows are re-dirtied by add() and
        overwritten on the next commit. Stale entries left in a dirty
        ledger (e.g. marked between a drain and this clear) are guarded
        in commit() by the rows < size filter."""
        self.size = 0
        self._row_of.clear()
        self.n_rec[:] = 0
        self.valid[:] = False
        self._device = None
        for ledger in self._dirty.values():
            ledger.clear()

    def _snapshot(self):
        if self._device is None:
            self._device = (jnp.asarray(self.emb),)
        return self._device

    def query(self, q, n: int):
        """LEGACY object-path retrieval (see module docstring).
        Top-n prompts. Returns (idx (Q,n), scores (Q,n), hit (Q,n))."""
        (emb_dev,) = self._snapshot()
        q = jnp.atleast_2d(jnp.asarray(q, jnp.float32))
        q = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-9)
        scores = KOPS.similarity(q, emb_dev, backend=self.backend)
        mask = jnp.arange(self.capacity) < self.size
        scores = jnp.where(mask[None, :], scores, -jnp.inf)
        top_s, top_i = jax.lax.top_k(scores, min(n, self.capacity))
        return top_i, top_s, jnp.isfinite(top_s)

    def gather_feedback(self, idx, hit):
        """LEGACY host-side record gather (pulls top-k indices back to
        host numpy for fancy-indexing; the fused pipeline keeps this on
        device via kernels.ref.gather_records). idx: (Q,N) prompt rows
        -> flattened (Q, N*R) neighbor records (model_a, model_b,
        outcome, valid) for the local ELO replay.

        Replay order is FARTHEST neighbor first: ELO is recency-weighted
        (later updates dominate the final ratings), so the most similar
        prompts are replayed last to carry the most influence."""
        idx = np.asarray(idx)[:, ::-1]
        hit = np.asarray(hit)[:, ::-1]
        qn = idx.shape
        a = jnp.asarray(self.model_a[idx].reshape(qn[0], -1))
        b = jnp.asarray(self.model_b[idx].reshape(qn[0], -1))
        s = jnp.asarray(self.outcome[idx].reshape(qn[0], -1))
        v = jnp.asarray((self.valid[idx] & hit[..., None]).reshape(qn[0], -1))
        return a, b, s, v
