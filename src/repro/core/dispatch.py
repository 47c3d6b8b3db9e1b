"""Steady-state dispatch layer: query bucketing + a persistent
compiled-fn cache (DESIGN.md §8).

Online traffic is ragged — every distinct batch size would be a fresh
XLA compilation of route_batch, and at serving volume the compile queue,
not the kernels, becomes the latency floor. This layer makes the hot
path recompile-free at steady state:

  * ragged batches are padded to power-of-two BUCKETS (the same policy
    elo._pad_bucket applies to record scans, with a smaller floor), so
    the universe of compiled shapes is the bucket ladder, not the
    traffic;
  * each bucket's executable is AOT-compiled (jit.lower().compile())
    into an EVICTION-FREE cache keyed on
    (batch_bucket, capacity, records_per_query, mode, backend, mesh) —
    the full static signature of a dispatch. AOT executables bypass jit's
    tracing machinery entirely, so a cache hit is a direct XLA call and
    a compile can ONLY happen on a cache miss: `stats()` is an exact
    compile ledger, which the CI steady-state gate asserts over;
  * `warmup()` pre-bakes the ladder at engine startup, so the first
    request of any size is already a hit.

The cached executable is route_batch_choices — the lean variant whose
(Q, M) score panel never leaves the device (the budget selection is
fused into the replay kernel's epilogue). A dispatcher given a DB mesh
lowers the same function with that mesh (the capacity-sharded route,
DESIGN.md §12); the mesh is the one fork, and it is part of the cache
key.
"""
from __future__ import annotations

import dataclasses
import threading
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs as OBS
from repro import sharding as SHARD
from repro.core import elo
from repro.core import state as STATE
from repro.core.state import (RouterState, route_batch_choices,
                              state_shardings)

#: default bucket ladder bounds (powers of two, inclusive)
MIN_BUCKET = 8
MAX_BUCKET = 1024


# ---------------------------------------------------------------------------
# XLA compile counter (exact, process-wide)
# ---------------------------------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_count = 0
_counter_lock = threading.Lock()
_listener_registered = False


def _on_event(name: str, *_a, **_k):
    global _compile_count
    if name == _COMPILE_EVENT:
        with _counter_lock:
            _compile_count += 1


def _ensure_listener():
    """Register the jax.monitoring listener once per process (there is
    no unregister API; the listener is a counter bump, negligible)."""
    global _listener_registered
    with _counter_lock:
        if not _listener_registered:
            jax.monitoring.register_event_duration_secs_listener(_on_event)
            _listener_registered = True


def xla_compile_count() -> int:
    """Process-wide count of XLA backend compilations observed since the
    first CompileCounter/RouteDispatcher was created. Differences of
    this counter bound the compiles of any code region."""
    _ensure_listener()
    return _compile_count


class CompileCounter:
    """Compile-count delta reader: `with CompileCounter() as c: ...` or
    manual `c.delta()`. Backed by jax.monitoring's backend-compile
    event, so it sees EVERY compilation in the process — jit cache
    misses, AOT compiles, transfers' helper programs — not just the
    dispatch cache's own misses."""

    def __init__(self):
        _ensure_listener()
        self.start = xla_compile_count()
        self.count = 0

    def delta(self) -> int:
        self.count = xla_compile_count() - self.start
        return self.count

    def __enter__(self):
        self.start = xla_compile_count()
        return self

    def __exit__(self, *exc):
        self.delta()
        return False


# ---------------------------------------------------------------------------
# the dispatcher
# ---------------------------------------------------------------------------

def batch_bucket(n: int, min_bucket: int = MIN_BUCKET,
                 max_bucket: int = MAX_BUCKET) -> int:
    """Power-of-two bucket for a batch of n queries (elo._pad_bucket
    policy with a query-sized floor). Batches beyond max_bucket keep
    their exact padded size — they are rare enough to compile for."""
    b = elo._pad_bucket(max(1, n), floor=min_bucket)
    return b if b <= max_bucket else elo._pad_bucket(n, floor=max_bucket)


def bucket_ladder(min_bucket: int = MIN_BUCKET,
                  max_bucket: int = MAX_BUCKET) -> Tuple[int, ...]:
    """All buckets the dispatcher can produce up to max_bucket."""
    out = []
    b = min_bucket
    while b <= max_bucket:
        out.append(b)
        b *= 2
    return tuple(out)


def abstract_state(n_models: int, dim: int, capacity: int, records: int,
                   mesh: Optional[Mesh] = None) -> RouterState:
    """RouterState of ShapeDtypeStructs: the full shape signature of a
    dispatch with no arrays allocated — AOT lowering only reads
    avals/shardings, so this is what warmup_shapes()/the capacity
    prebaker feed the cache. With a DB mesh, leaves carry the
    capacity-partition NamedShardings so the baked executable accepts
    the concrete sharded states commits produce."""
    sh = state_shardings(mesh) if mesh is not None else None

    def sd(shape, dtype, field):
        return jax.ShapeDtypeStruct(
            shape, dtype,
            sharding=getattr(sh, field) if sh is not None else None)

    return RouterState(
        global_ratings=sd((n_models,), jnp.float32, "global_ratings"),
        emb=sd((capacity, dim), jnp.float32, "emb"),
        model_a=sd((capacity, records), jnp.int32, "model_a"),
        model_b=sd((capacity, records), jnp.int32, "model_b"),
        outcome=sd((capacity, records), jnp.float32, "outcome"),
        valid=sd((capacity, records), bool, "valid"),
        size=sd((), jnp.int32, "size"))


@dataclasses.dataclass
class DispatchStats:
    hits: int = 0
    misses: int = 0          # == compilations caused by this dispatcher
    warmed: int = 0          # misses taken by warmup(), not traffic
    compile_s: float = 0.0   # total seconds spent compiling

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


class RouteDispatcher:
    """Owns the serving hot path's compiled executables.

    One dispatcher per (routing config, costs) pair; states of any
    capacity/record width flow through it — the cache key carries the
    shape-defining axes. Thread-compat: routing itself is pure; the
    cache dict is guarded for concurrent warmers."""

    def __init__(self, costs, *, p_global: float = 0.5,
                 n_neighbors: int = 20, k: float = 32.0,
                 backend: str = "reference", mode: str = "combined",
                 init_rating: float = elo.DEFAULT_RATING,
                 min_bucket: int = MIN_BUCKET,
                 max_bucket: int = MAX_BUCKET,
                 mesh: Optional[Mesh] = None,
                 obs: Optional["OBS.Observability"] = None):
        # with a DB mesh the cached executables are the capacity-sharded
        # route (DESIGN.md §12); replicated operands (costs, queries,
        # budgets) are committed to the mesh so AOT calls see the exact
        # shardings they were lowered with
        self.mesh = mesh
        self._rep = None if mesh is None else NamedSharding(mesh, P())
        self.costs = jnp.asarray(costs, jnp.float32)
        if self._rep is not None:
            self.costs = jax.device_put(self.costs, self._rep)
        self.kw = dict(p_global=float(p_global),
                       n_neighbors=int(n_neighbors), k=float(k),
                       backend=backend, mode=mode,
                       init_rating=float(init_rating))
        self.min_bucket = min_bucket
        self.max_bucket = max_bucket
        self._cache: Dict[Tuple, jax.stages.Compiled] = {}
        self._lock = threading.Lock()
        self.stats = DispatchStats()
        _ensure_listener()
        # telemetry handles (metrics are always-on; spans are gated by
        # obs.enabled). pad-waste ratio and cache hit rate are derived
        # at scrape time from these raw counters.
        self.obs = OBS.get_obs(obs)
        r = self.obs.registry
        self._m_calls = r.counter(
            "dispatch_calls_total", "route() dispatches")
        self._m_rows = r.counter(
            "dispatch_rows_total", "real query rows routed")
        self._m_padded = r.counter(
            "dispatch_padded_rows_total",
            "bucket-padded rows dispatched (>= rows; waste = padded-rows)")
        self._m_hits = r.counter(
            "dispatch_cache_hits_total", "executable-cache hits")
        self._m_misses = r.counter(
            "dispatch_cache_misses_total",
            "executable-cache misses == compiles this dispatcher caused")
        self._m_compile_s = r.counter(
            "dispatch_compile_seconds_total", "time spent compiling")
        self._h_occupancy = r.histogram(
            "dispatch_bucket_occupancy", "rows/bucket fill per dispatch",
            bounds=[i / 16 for i in range(1, 17)])
        # point-in-time companion of the histogram: what the LAST
        # dispatch filled — the SLO engine's live occupancy signal
        # (the histogram mean averages over all time)
        self._g_occupancy = r.gauge(
            "dispatch_occupancy_last", "rows/bucket fill, last dispatch")
        self._bucket_counters: Dict[int, "OBS.Counter"] = {}
        r.gauge("xla_compiles_total",
                "process-wide XLA backend compiles (jax.monitoring)",
                fn=xla_compile_count)

    def _bucket_counter(self, qb: int):
        c = self._bucket_counters.get(qb)
        if c is None:
            c = self.obs.registry.counter(
                "dispatch_bucket_total", "dispatches per bucket size",
                bucket=str(qb))
            self._bucket_counters[qb] = c
        return c

    @classmethod
    def for_router(cls, router, **kw) -> "RouteDispatcher":
        """Build from an EagleRouter's config (costs, mode, backend...)."""
        c = router.cfg
        return cls(router.costs, p_global=c.p_global,
                   n_neighbors=c.n_neighbors, k=c.k_factor,
                   backend=c.backend, mode=router.mode,
                   init_rating=c.init_rating, **kw)

    # -- cache ---------------------------------------------------------------
    def bucket(self, n: int) -> int:
        return batch_bucket(n, self.min_bucket, self.max_bucket)

    def _key(self, state: RouterState, qb: int) -> Tuple:
        return (qb, state.capacity, state.records_per_query,
                self.kw["mode"], self.kw["backend"], self.mesh)

    def _compiled(self, state: RouterState, qb: int, warm: bool = False):
        key = self._key(state, qb)
        fn = self._cache.get(key)
        if fn is not None:
            if not warm:
                self.stats.hits += 1
                self._m_hits.inc()
            return fn
        with self._lock:
            fn = self._cache.get(key)
            if fn is None:
                import time
                t0 = time.perf_counter()
                with self.obs.span(f"dispatch.compile.q{qb}"):
                    q = jax.ShapeDtypeStruct((qb, state.dim), jnp.float32,
                                             sharding=self._rep)
                    b = jax.ShapeDtypeStruct((qb,), jnp.float32,
                                             sharding=self._rep)
                    c = jax.ShapeDtypeStruct(self.costs.shape,
                                             jnp.float32,
                                             sharding=self._rep)
                    fn = route_batch_choices.lower(
                        state, q, b, c, mesh=self.mesh, **self.kw).compile()
                self._cache[key] = fn
                self.stats.misses += 1
                self.stats.warmed += bool(warm)
                dt = time.perf_counter() - t0
                self.stats.compile_s += dt
                self._m_misses.inc()
                self._m_compile_s.inc(dt)
                self.obs.emit({"kind": "dispatch_compile", "bucket": qb,
                               "capacity": state.capacity,
                               "records": state.records_per_query,
                               "seconds": dt})
        return fn

    def warmup(self, state: RouterState,
               batch_sizes: Optional[Sequence[int]] = None) -> int:
        """Pre-bake the bucket ladder for `state`'s shape signature so
        steady-state traffic never compiles. Returns the number of
        executables compiled (0 if already warm)."""
        buckets = sorted({self.bucket(n) for n in batch_sizes}
                         if batch_sizes is not None
                         else bucket_ladder(self.min_bucket,
                                            self.max_bucket))
        before = self.stats.misses
        for qb in buckets:
            self._compiled(state, qb, warm=True)
        return self.stats.misses - before

    def warmup_shapes(self, capacity: int, records: int, dim: int,
                      batch_sizes: Optional[Sequence[int]] = None) -> int:
        """warmup() from a bare shape signature (no concrete state):
        AOT lowering needs only avals, so the ladder for a capacity the
        DB hasn't grown to YET can bake in the background — this is the
        CapacityPrebaker's entry point."""
        st = abstract_state(int(self.costs.shape[0]), dim, capacity,
                            records, self.mesh)
        return self.warmup(st, batch_sizes)

    def cache_stats(self) -> Dict:
        """Eviction-free readout: nothing is ever dropped, so misses is
        the exact number of executables this dispatcher ever built."""
        return {**self.stats.as_dict(), "entries": len(self._cache),
                "keys": sorted(self._cache)}

    def telemetry(self) -> Dict:
        """Derived serving-efficiency readout from the raw counters:
        pad-waste ratio (fraction of dispatched rows that were bucket
        padding), cache hit rate, and the exact compile ledger."""
        rows = self._m_rows.value
        padded = self._m_padded.value
        hits, misses = self._m_hits.value, self._m_misses.value
        # warmup()-induced compiles are deliberate pre-baking, not
        # traffic misses — the hit rate reads over traffic only
        traffic_misses = max(0, misses - self.stats.warmed)
        return {
            "calls": self._m_calls.value,
            "rows": rows,
            "padded_rows": padded,
            "pad_waste_ratio": (padded - rows) / padded if padded else 0.0,
            "cache_hit_rate": hits / (hits + traffic_misses)
                              if (hits + traffic_misses) else 1.0,
            "cache_hits": hits,
            "cache_misses": misses,
            "compile_seconds": self._m_compile_s.value,
            "xla_compiles_process": xla_compile_count(),
        }

    def _record_dispatch(self, nq: int, qb: int):
        self._m_calls.inc()
        self._m_rows.inc(nq)
        self._m_padded.inc(qb)
        self._h_occupancy.observe(nq / qb)
        self._g_occupancy.set(nq / qb)
        self._bucket_counter(qb).inc()

    # -- the hot path --------------------------------------------------------
    def _chunks(self, nq: int):
        """(lo, hi) spans of at most max_bucket rows. Routing is
        row-independent, so an oversized batch is dispatched as
        ladder-sized chunks — an off-ladder padded shape would silently
        miss the warmed cache and compile on the hot path."""
        return [(lo, min(lo + self.max_bucket, nq))
                for lo in range(0, nq, self.max_bucket)]

    def _launch(self, state: RouterState, q: np.ndarray, b: np.ndarray):
        """Pad to the bucket, place, and run the cached executable, as
        the spans `dispatch.put` and `dispatch.launch`. Returns the
        device result and the real row count."""
        nq = q.shape[0]
        qb = self.bucket(nq)
        self._record_dispatch(nq, qb)
        obs = self.obs
        with obs.span("dispatch.put"):
            if qb != nq:
                q = np.pad(q, ((0, qb - nq), (0, 0)))
                b = np.pad(b, (0, qb - nq))
            if self._rep is not None:
                q = jax.device_put(q, self._rep)
                b = jax.device_put(b, self._rep)
        with obs.span("dispatch.launch"):
            res = self._compiled(state, qb)(state, q, b, self.costs)
        return res, nq

    def _route_one(self, state: RouterState, q: np.ndarray,
                   b: np.ndarray) -> np.ndarray:
        with self.obs.span("dispatch.route"):
            res, nq = self._launch(state, q, b)
            with self.obs.span("dispatch.readout"):
                return np.asarray(res.choices)[:nq]

    def route(self, state: RouterState, query_embs, budgets) -> np.ndarray:
        """Bucket-pad, dispatch the cached executable, slice. Returns
        host (Q,) int32 choices — the single readout of a routing step.
        Batches beyond max_bucket are chunked into ladder-sized
        dispatches (never an off-ladder compile)."""
        q = np.atleast_2d(np.asarray(query_embs, np.float32))
        nq = q.shape[0]
        b = np.broadcast_to(np.asarray(budgets, np.float32),
                            (nq,)).astype(np.float32)
        if nq <= self.max_bucket:
            return self._route_one(state, q, b)
        return np.concatenate([self._route_one(state, q[lo:hi], b[lo:hi])
                               for lo, hi in self._chunks(nq)])

    def _route_result_one(self, state: RouterState, q: np.ndarray,
                          b: np.ndarray):
        with self.obs.span("dispatch.route_result"):
            res, nq = self._launch(state, q, b)
            with self.obs.span("dispatch.readout"):
                return (np.asarray(res.choices)[:nq],
                        np.asarray(res.topk_idx)[:nq])

    def route_result(self, state: RouterState, query_embs, budgets):
        """Bucketed dispatch returning (choices (Q,), topk_idx (Q, n))
        as host arrays, for callers that want the retrieval trace.
        Chunks oversized batches like route()."""
        q = np.atleast_2d(np.asarray(query_embs, np.float32))
        nq = q.shape[0]
        b = np.broadcast_to(np.asarray(budgets, np.float32),
                            (nq,)).astype(np.float32)
        if nq <= self.max_bucket:
            return self._route_result_one(state, q, b)
        parts = [self._route_result_one(state, q[lo:hi], b[lo:hi])
                 for lo, hi in self._chunks(nq)]
        return (np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]))


# ---------------------------------------------------------------------------
# capacity prebaker: grow the cache BEFORE the DB grows
# ---------------------------------------------------------------------------

class CapacityPrebaker:
    """Background pre-bake of the NEXT capacity bucket's executables.

    A VectorDB._grow() doubles the panel shapes, which invalidates every
    cached route executable AND the commit scatter's jit entry — without
    preparation the first post-grow dispatch eats the full ladder
    recompile on the hot path. poll() is a cheap post-commit hook: once
    the buffer fills past `watermark`, a daemon thread AOT-bakes the
    dispatch ladder for db.next_capacity() from abstract avals
    (warmup_shapes) and runs one dummy scatter at the new shapes so the
    commit path's jit cache is warm too. By the time _grow() trips, the
    shape change costs only the one-off full re-upload (transfers, zero
    compiles).

    join() is the determinism hook for tests/benches; serving loops just
    poll and let the thread finish in the background."""

    def __init__(self, dispatch: RouteDispatcher, db, *,
                 watermark: float = 0.75,
                 batch_sizes: Optional[Sequence[int]] = None,
                 warm_scatter: bool = True,
                 obs: Optional["OBS.Observability"] = None):
        self.dispatch = dispatch
        self.db = db
        self.watermark = watermark
        self.batch_sizes = batch_sizes
        self.warm_scatter = warm_scatter
        self._thread: Optional[threading.Thread] = None
        self._baked = {db.capacity}
        self.obs = OBS.get_obs(obs)
        self._m_bakes = self.obs.registry.counter(
            "dispatch_prebake_total", "background next-capacity bakes")
        self._m_bake_s = self.obs.registry.counter(
            "dispatch_prebake_seconds_total", "time spent prebaking")

    def poll(self) -> bool:
        """Post-commit hook: start a bake if the fill watermark is
        crossed and the next capacity isn't covered yet. Returns
        whether a bake was started."""
        if self._thread is not None and self._thread.is_alive():
            return False
        if self.db.size < self.watermark * self.db.capacity:
            return False
        nxt = self.db.next_capacity()
        if nxt in self._baked:
            return False
        self._baked.add(nxt)
        self._thread = threading.Thread(
            target=self._bake, args=(nxt, self.db.rcap, self.db.dim),
            name="capacity-prebake", daemon=True)
        self._thread.start()
        return True

    def join(self, timeout: Optional[float] = None):
        if self._thread is not None:
            self._thread.join(timeout)

    def _bake(self, capacity: int, records: int, dim: int):
        import time
        t0 = time.perf_counter()
        n = self.dispatch.warmup_shapes(capacity, records, dim,
                                        self.batch_sizes)
        if self.warm_scatter:
            self._warm_scatter(capacity, records, dim)
        dt = time.perf_counter() - t0
        self._m_bakes.inc()
        self._m_bake_s.inc(dt)
        self.obs.emit({"kind": "dispatch_prebake", "capacity": capacity,
                       "records": records, "executables": n,
                       "seconds": dt})

    def _warm_scatter(self, capacity: int, records: int, dim: int):
        """Execute one dummy commit scatter at the next-capacity shapes
        (the smallest row bucket — the common case), placed as the
        commit places them: on one device, or split over the DB mesh
        (one shard without a mesh). jit call caches key on shapes and
        shardings, so the later real scatter is a hit; the dummy
        buffers are donated and freed immediately."""
        mesh = self.dispatch.mesh
        shards = 1 if mesh is None else SHARD.db_shard_count(mesh)
        zeros = partial(jnp.zeros, device=None if mesh is None else
                        NamedSharding(mesh, P(SHARD.DB_AXIS)))
        rows = shards * elo._pad_bucket(1)
        STATE.scatter_for(mesh)(
            zeros((capacity, dim), jnp.float32),
            zeros((capacity, records), jnp.int32),
            zeros((capacity, records), jnp.int32),
            zeros((capacity, records), jnp.float32),
            zeros((capacity, records), bool),
            zeros((rows, STATE.packed_width(dim, records)), jnp.int32))
