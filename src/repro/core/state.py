"""Functional, device-resident routing core (DESIGN.md §2).

RouterState is an immutable pytree holding everything the routing hot
path needs on device: the standing global ELO ratings plus the vector-DB
panels (embeddings + grouped pairwise feedback). Per-batch routing is ONE
jitted dispatch over this state:

    route_batch(state, query_embs, budgets, costs)
      = similarity -> top-k -> record gather -> local ELO replay
        -> score combine -> budget masking

with zero host transfers between the similarity panel and the final model
selection (the legacy object path crossed the host/device boundary four
times per batch). The VectorDB stays a host-side append buffer — appends
must cost microseconds — and syncs into a RouterState via commit(), which
scatters only the rows touched since the last commit into the previous
state's DONATED device buffers (O(new records) upload, no realloc).

EagleRouter (core/router.py) is a thin stateful shell over these
functions; ServingEngine and the benchmarks call them directly.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs as OBS
from repro import sharding as SHARD
from repro.core import elo
from repro.kernels import ops as KOPS

#: route_batch scoring modes (paper Appendix B ablations).
MODES = ("combined", "global", "local")


# ---------------------------------------------------------------------------
# score combination + budget selection (pure functions, shared with the
# baseline routers)
# ---------------------------------------------------------------------------

def combine_scores(global_r, local_r, p: float):
    """Score(X) = P * Global(X) + (1-P) * Local(X).  global_r: (M,),
    local_r: (Q, M) -> (Q, M)."""
    return p * global_r[None, :] + (1.0 - p) * local_r


def select_within_budget(scores, costs, budget):
    """Highest-scoring model with cost <= budget; falls back to the
    cheapest model when nothing fits (never refuse service).

    scores: (Q, M); costs: (M,); budget: scalar or (Q,).
    Returns (choice (Q,), feasible (Q, M))."""
    budget = jnp.asarray(budget)
    if budget.ndim == 0:
        budget = budget[None]
    feasible = costs[None, :] <= budget[:, None]
    masked = jnp.where(feasible, scores, -jnp.inf)
    choice = jnp.argmax(masked, axis=-1)
    fallback = jnp.argmin(costs)
    any_ok = feasible.any(axis=-1)
    return jnp.where(any_ok, choice, fallback), feasible


# ---------------------------------------------------------------------------
# RouterState pytree
# ---------------------------------------------------------------------------

@partial(jax.tree_util.register_dataclass,
         data_fields=["global_ratings", "emb", "model_a", "model_b",
                      "outcome", "valid", "size"],
         meta_fields=[])
@dataclasses.dataclass(frozen=True)
class RouterState:
    """Immutable device snapshot of the router: passes through jit/vmap
    as a pytree; capacities are encoded in the array shapes."""
    global_ratings: jax.Array   # (M,)  standing Eagle-Global ratings
    emb: jax.Array              # (C, D) L2-normalized prompt embeddings
    model_a: jax.Array          # (C, R) int32 pairwise records
    model_b: jax.Array          # (C, R) int32
    outcome: jax.Array          # (C, R) float32 S for model_a
    valid: jax.Array            # (C, R) bool record mask
    size: jax.Array             # ()    int32 live prompt rows

    @property
    def n_models(self) -> int:
        return self.global_ratings.shape[-1]

    @property
    def capacity(self) -> int:
        return self.emb.shape[0]

    @property
    def dim(self) -> int:
        return self.emb.shape[1]

    @property
    def records_per_query(self) -> int:
        return self.model_a.shape[1]


def init_state(n_models: int, dim: int, capacity: int = 4096,
               records_per_query: int = 8,
               init_rating: float = elo.DEFAULT_RATING) -> RouterState:
    """Empty device state (no history)."""
    return RouterState(
        global_ratings=jnp.full((n_models,), init_rating, jnp.float32),
        emb=jnp.zeros((capacity, dim), jnp.float32),
        model_a=jnp.zeros((capacity, records_per_query), jnp.int32),
        model_b=jnp.zeros((capacity, records_per_query), jnp.int32),
        outcome=jnp.zeros((capacity, records_per_query), jnp.float32),
        valid=jnp.zeros((capacity, records_per_query), bool),
        size=jnp.int32(0))


def state_from_buffer(db, global_ratings,
                      shardings: Optional[RouterState] = None
                      ) -> RouterState:
    """Full upload of a host append buffer (VectorDB) to device. With
    `shardings` (state_shardings(mesh)) each panel goes from the host
    straight to its shards, never whole through one device."""
    host = RouterState(
        global_ratings=np.asarray(global_ratings, np.float32),
        emb=db.emb, model_a=db.model_a, model_b=db.model_b,
        outcome=db.outcome, valid=db.valid, size=np.int32(db.size))
    if shardings is None:
        return jax.tree.map(jnp.asarray, host)
    return jax.tree.map(jax.device_put, host, shardings)


def packed_width(dim: int, records: int) -> int:
    """32-bit words of one packed commit row (_pack_rows)."""
    return dim + 4 * records + 2


def _pack_rows(db, rows: np.ndarray, local: np.ndarray) -> np.ndarray:
    """The dirty rows of the host buffer as ONE int32 (len(rows), W)
    buffer, so a commit makes one transfer (per chip on a mesh). A row
    is `[emb (D) | model_a (R) | model_b (R) | outcome (R) | valid (R)
    | local row | live size]`: every field is 4 bytes wide, so the
    floats travel as bit views (no value is converted: -0.0, NaN
    payloads and denormals arrive as they left) and `valid` as 0/1.
    emb leads so its slice starts on a lane boundary. `rows` index the
    host buffer, `local` the target panel (the owning shard's on a
    mesh)."""
    d, r = db.emb.shape[1], db.model_a.shape[1]
    buf = np.empty((rows.size, packed_width(d, r)), np.int32)
    f32 = buf.view(np.float32)
    f32[:, :d] = db.emb[rows]
    buf[:, d:d + r] = db.model_a[rows]
    buf[:, d + r:d + 2 * r] = db.model_b[rows]
    f32[:, d + 2 * r:d + 3 * r] = db.outcome[rows]
    buf[:, d + 3 * r:d + 4 * r] = db.valid[rows]
    buf[:, -2] = local
    buf[:, -1] = db.size
    return buf


def _write_rows(emb, model_a, model_b, outcome, valid, packed):
    """Unpack a _pack_rows buffer on the device and write its rows into
    the panels; the live size rides along in the header."""
    d, r = emb.shape[1], model_a.shape[1]

    def f32(x):
        return jax.lax.bitcast_convert_type(x, jnp.float32)

    rows = packed[:, -2]
    with jax.named_scope("eagle.commit_scatter"):
        return (emb.at[rows].set(f32(packed[:, :d])),
                model_a.at[rows].set(packed[:, d:d + r]),
                model_b.at[rows].set(packed[:, d + r:d + 2 * r]),
                outcome.at[rows].set(f32(packed[:, d + 2 * r:d + 3 * r])),
                valid.at[rows].set(packed[:, d + 3 * r:d + 4 * r] != 0),
                packed[0, -1])


@partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def _scatter_rows(emb, model_a, model_b, outcome, valid, packed):
    """Write the packed dirty rows into the donated previous-state
    buffers; returns the five panels and the live size."""
    return _write_rows(emb, model_a, model_b, outcome, valid, packed)


# ---------------------------------------------------------------------------
# capacity-sharded state: placement, routing, commit (DESIGN.md §12)
# ---------------------------------------------------------------------------

def state_shardings(mesh: Mesh) -> RouterState:
    """RouterState-shaped tree of NamedShardings for the capacity
    partition (sharding.db_state_specs): DB panels split dim 0 over
    DB_AXIS, ratings/size replicate."""
    specs = SHARD.db_state_specs()
    return RouterState(**{f: NamedSharding(mesh, s)
                          for f, s in specs.items()})


def shard_state(state: RouterState, mesh: Mesh) -> RouterState:
    """Place a RouterState onto a DB mesh (contiguous capacity split)."""
    SHARD.check_db_mesh(mesh, state.capacity)
    return jax.tree.map(jax.device_put, state, state_shardings(mesh))


_SHARDED_SCATTER: Dict[Mesh, "jax.stages.Wrapped"] = {}


def _sharded_scatter(mesh: Mesh):
    """Jitted owner-scatter for a DB mesh, cached per mesh. The packed
    rows (_pack_rows) are a per-shard stack sharded over DB_AXIS —
    shard s receives ONLY the rows it owns, so each dirty row crosses
    the host boundary toward exactly one device, in one transfer a
    chip. Padding entries repeat a row the shard owns with that row's
    host content, which makes the duplicate writes idempotent (same
    guarantee the unsharded scatter's repeat-first-row padding relies
    on). The live size leaves replicated."""
    fn = _SHARDED_SCATTER.get(mesh)
    if fn is not None:
        return fn
    spec = P(SHARD.DB_AXIS)

    # the function's name is the compiled module's (`jit_body`), by
    # which bench/lib/readers.py finds the owner-scatter in a trace
    def body(emb, model_a, model_b, outcome, valid, packed):
        return _write_rows(emb, model_a, model_b, outcome, valid, packed)

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 6,
                               out_specs=(spec,) * 5 + (P(),),
                               check_vma=False),
                 donate_argnums=(0, 1, 2, 3, 4))
    _SHARDED_SCATTER[mesh] = fn
    return fn


def _commit_sharded(db, global_ratings, prev: Optional[RouterState],
                    consumer: str, mesh: Mesh, ob) -> Tuple[RouterState, str]:
    """Sharded _commit(): drain the ledger grouped by OWNING shard and
    scatter each group only to its shard (donated buffers), from one
    packed transfer a chip. Falls back to a full sharded upload on a
    shape change, like the unsharded path. The ratings are re-placed
    replicated on the mesh every commit so the state's shardings stay
    AOT-executable-stable."""
    shards = SHARD.check_db_mesh(mesh, db.capacity)
    per_shard = db.drain_dirty_sharded(consumer, shards)
    if (prev is None or prev.emb.shape != db.emb.shape
            or prev.model_a.shape != db.model_a.shape):
        return (state_from_buffer(db, global_ratings, state_shardings(mesh)),
                "full")
    rep = NamedSharding(mesh, P())
    g = jnp.asarray(global_ratings, jnp.float32)
    if not any(r.size for r in per_shard):
        return dataclasses.replace(
            prev, global_ratings=jax.device_put(g, rep),
            size=jax.device_put(jnp.int32(db.size), rep)), "ratings_only"
    c_local = db.capacity // shards
    with ob.span("state.commit.gather"):
        bucket = elo._pad_bucket(max(r.size for r in per_shard))
        rows = np.empty((shards, bucket), np.int32)   # GLOBAL row ids
        for s, r in enumerate(per_shard):
            pad = r[0] if r.size else s * c_local   # a row shard s owns
            rows[s, :r.size] = r
            rows[s, r.size:] = pad
        flat = rows.reshape(-1)
        host = _pack_rows(db, flat, flat % c_local)
    with ob.span("state.commit.upload"):
        packed = jax.device_put(host, NamedSharding(mesh, P(SHARD.DB_AXIS)))
        g = jax.device_put(g, rep)
    with ob.span("state.commit.scatter"):
        emb, a, b, o, v, size = _sharded_scatter(mesh)(
            prev.emb, prev.model_a, prev.model_b, prev.outcome,
            prev.valid, packed)
    return RouterState(global_ratings=g, emb=emb, model_a=a, model_b=b,
                       outcome=o, valid=v, size=size), "packed"


def commit(db, global_ratings, prev: Optional[RouterState] = None,
           consumer: str = "default",
           mesh: Optional[Mesh] = None,
           obs: Optional["OBS.Observability"] = None) -> RouterState:
    """Sync the host append buffer into a device RouterState.

    With a previous state of matching shape, only the rows touched since
    the last commit are uploaded and scattered into `prev`'s donated
    buffers (the 100-200x incremental-update claim depends on this being
    O(new records), not O(history)). `prev` MUST NOT be used after this
    call — its buffers are donated. Row counts are padded to power-of-two
    buckets so the scatter compiles once per bucket.

    `consumer` names the dirty-row ledger to drain: each device replica
    of the buffer (e.g. the two halves of a DoubleBuffer) drains its own
    ledger, so rows landing between two replicas' commits reach both.

    With a DB `mesh`, the returned state is capacity-sharded and every
    dirty row is scattered only to its owning shard (DESIGN.md §12).

    An incremental commit runs as three spans on `obs`:
    `state.commit.gather` (the dirty rows packed from the host buffer),
    `state.commit.upload` (their one transfer, one a chip on a mesh)
    and `state.commit.scatter` (the scatter's enqueue)."""
    return _commit(db, global_ratings, prev, consumer, mesh,
                   OBS.get_obs(obs))[0]


def _commit(db, global_ratings, prev, consumer, mesh, ob
            ) -> Tuple[RouterState, str]:
    """commit(), and which path it took: "full" (a whole upload, on
    the first commit or a shape change), "ratings_only" (no dirty row
    left) or "packed" (the dirty rows in one transfer)."""
    if mesh is not None:
        return _commit_sharded(db, global_ratings, prev, consumer, mesh, ob)
    rows = db.drain_dirty(consumer)
    if (prev is None or prev.emb.shape != db.emb.shape
            or prev.model_a.shape != db.model_a.shape):
        return state_from_buffer(db, global_ratings), "full"
    g = jnp.asarray(global_ratings, jnp.float32)
    if rows.size:
        # rollback/clear guard: a drained row at/past the live count is
        # stale (its content is masked by `size` anyway) — drop it
        # rather than scatter it, and never index rows[0] of what could
        # now be an empty set.
        rows = rows[rows < db.size]
    if rows.size == 0:
        return dataclasses.replace(prev, global_ratings=g,
                                   size=jnp.int32(db.size)), "ratings_only"
    with ob.span("state.commit.gather"):
        bucket = elo._pad_bucket(rows.size)
        # pad by repeating the first dirty row: duplicate scatter writes
        # of identical content are idempotent
        rows = np.concatenate([rows, np.full(bucket - rows.size, rows[0],
                                             rows.dtype)])
        host = _pack_rows(db, rows, rows)
    with ob.span("state.commit.upload"):
        packed = jnp.asarray(host)
    with ob.span("state.commit.scatter"):
        emb, a, b, o, v, size = _scatter_rows(
            prev.emb, prev.model_a, prev.model_b, prev.outcome,
            prev.valid, packed)
    return RouterState(global_ratings=g, emb=emb, model_a=a, model_b=b,
                       outcome=o, valid=v, size=size), "packed"


class DoubleBuffer:
    """Two device replicas of the router state over ONE host buffer, so
    feedback commits overlap in-flight routing (DESIGN.md §8).

    Protocol: `front` serves every route_batch dispatch; `commit()`
    drains the BACK replica's dirty-row ledger into its donated buffers
    and swaps, so the scatter never donates a buffer an in-flight
    dispatch may still be reading, and the host never blocks on it
    (async dispatch). Each replica keeps its own ledger (VectorDB
    consumers), so rows appended between a replica's commits reach it on
    its next turn."""

    def __init__(self, db, global_ratings, tags=("dbuf_a", "dbuf_b"),
                 obs: Optional["OBS.Observability"] = None,
                 mesh: Optional[Mesh] = None):
        self.db = db
        self.mesh = mesh   # capacity-sharded replicas when set (§12)
        db.register_consumer(tags[0])
        db.register_consumer(tags[1])
        self._front = (commit(db, global_ratings, None, consumer=tags[0],
                              mesh=mesh), tags[0])
        self._back = (commit(db, global_ratings, None, consumer=tags[1],
                             mesh=mesh), tags[1])
        self.obs = OBS.get_obs(obs)
        r = self.obs.registry
        self._m_swaps = r.counter(
            "dbuf_swaps_total", "double-buffer commit/swap cycles")
        self._g_backlog = r.gauge(
            "dbuf_dirty_backlog",
            "dirty rows pending in the back replica's ledger at commit")
        self._m_paths = {p: r.counter(
            "dbuf_commit_total", "double-buffer commits by path", path=p)
            for p in ("packed", "full", "ratings_only")}

    @property
    def front(self) -> RouterState:
        """The replica live dispatches read. Valid until the SECOND next
        commit() (one swap keeps it as back, the next donates it)."""
        return self._front[0]

    def commit(self, global_ratings) -> RouterState:
        """Absorb pending feedback into the back replica, swap, return
        the new front. Enqueued asynchronously: routing already in
        flight on the old front is never disturbed."""
        st, tag = self._back
        self._g_backlog.set(len(self.db._dirty.get(tag, ())))
        with self.obs.span("state.commit"):
            new, path = _commit(self.db, global_ratings, st, tag,
                                self.mesh, self.obs)
        self._back, self._front = self._front, (new, tag)
        self._m_swaps.inc()
        self._m_paths[path].inc()
        return self.front


# ---------------------------------------------------------------------------
# the fused routing pipeline
# ---------------------------------------------------------------------------

class RouteResult(NamedTuple):
    choices: jax.Array    # (Q,)   selected model per query
    scores: jax.Array     # (Q, M) combined quality scores
    topk_idx: jax.Array   # (Q, N) retrieved prompt rows (-1 in global mode)


class RouteChoices(NamedTuple):
    choices: jax.Array    # (Q,)   selected model per query
    topk_idx: jax.Array   # (Q, N) retrieved prompt rows (-1 in global mode)


def _scores(state: RouterState, q, p_global, n_neighbors, k, backend,
            mode, init_rating):
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    q = jnp.atleast_2d(jnp.asarray(q, jnp.float32))
    nq = q.shape[0]
    m = state.n_models
    n = min(n_neighbors, state.capacity)
    if mode == "global":
        # Eagle-Global ablation: no retrieval at all
        scores = jnp.broadcast_to(state.global_ratings, (nq, m))
        return scores, jnp.full((nq, n), -1, jnp.int32)
    if mode == "local":
        init = jnp.full((m,), jnp.float32(init_rating))  # flat prior
    else:
        init = state.global_ratings
    local, top_i, _ = KOPS.retrieve_replay(
        q, state.emb, state.model_a, state.model_b, state.outcome,
        state.valid, state.size, init, n=n, k=k, backend=backend)
    if mode == "local":
        return local, top_i
    return combine_scores(state.global_ratings, local, p_global), top_i


@partial(jax.jit,
         static_argnames=("n_neighbors", "k", "backend", "mode"))
def batch_scores(state: RouterState, query_embs, *, p_global: float = 0.5,
                 n_neighbors: int = 20, k: float = 32.0,
                 backend: str = "reference", mode: str = "combined",
                 init_rating: float = elo.DEFAULT_RATING):
    """(Q, M) combined quality scores, one jitted dispatch."""
    return _scores(state, query_embs, p_global, n_neighbors, k, backend,
                   mode, init_rating)[0]


def _route(state: RouterState, q, budgets, costs, p_global, n_neighbors,
           k, backend, mode, init_rating):
    """Shared body of route_batch/route_batch_choices: the retrieval +
    replay + budget-selection chain with the selection folded into the
    kernel epilogue (choices leave the replay tile directly; the
    standalone select_within_budget stays as the parity oracle)."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    q = jnp.atleast_2d(jnp.asarray(q, jnp.float32))
    nq = q.shape[0]
    m = state.n_models
    n = min(n_neighbors, state.capacity)
    costs = jnp.asarray(costs, jnp.float32)
    budgets = jnp.broadcast_to(jnp.asarray(budgets, jnp.float32), (nq,))
    if mode == "global":
        # Eagle-Global ablation: no retrieval, selection is the whole op
        scores = jnp.broadcast_to(state.global_ratings, (nq, m))
        choices, _ = select_within_budget(scores, costs, budgets)
        return choices, scores, jnp.full((nq, n), -1, jnp.int32)
    if mode == "local":
        init = jnp.full((m,), jnp.float32(init_rating))  # flat prior
        p = 0.0   # 0*Global + 1*Local == Local, bit-exact for finite r
    else:
        init = state.global_ratings
        p = p_global
    # named_scope tags the fused chain's HLO ops so device ops group
    # under one label in XLA profiles, next to the host-side
    # TraceAnnotation spans the tracer emits around the dispatch
    with jax.named_scope("eagle.retrieve_replay_select"):
        local, top_i, _, choices = KOPS.retrieve_replay_select(
            q, state.emb, state.model_a, state.model_b, state.outcome,
            state.valid, state.size, init, state.global_ratings, costs,
            budgets, n=n, k=k, p=p, backend=backend)
    scores = local if mode == "local" else \
        combine_scores(state.global_ratings, local, p_global)
    return choices, scores, top_i


@partial(jax.jit,
         static_argnames=("p_global", "n_neighbors", "k", "backend",
                          "mode", "init_rating"))
def route_batch(state: RouterState, query_embs, budgets, costs, *,
                p_global: float = 0.5, n_neighbors: int = 20,
                k: float = 32.0, backend: str = "reference",
                mode: str = "combined",
                init_rating: float = elo.DEFAULT_RATING) -> RouteResult:
    """Route a batch of queries under budgets: the entire hot path —
    similarity, top-k, feedback gather, local ELO replay, score
    combination, budget masking — fused into a single device dispatch,
    with the budget selection folded into the replay kernel's epilogue."""
    choices, scores, top_i = _route(state, query_embs, budgets, costs,
                                    p_global, n_neighbors, k, backend,
                                    mode, init_rating)
    return RouteResult(choices, scores, top_i)


@partial(jax.jit,
         static_argnames=("p_global", "n_neighbors", "k", "backend",
                          "mode", "init_rating"))
def route_batch_choices(state: RouterState, query_embs, budgets, costs, *,
                        p_global: float = 0.5, n_neighbors: int = 20,
                        k: float = 32.0, backend: str = "reference",
                        mode: str = "combined",
                        init_rating: float = elo.DEFAULT_RATING
                        ) -> RouteChoices:
    """Lean serving variant of route_batch: identical dataflow, but the
    (Q, M) score panel is never an output — only the fused-epilogue
    choices and the retrieval trace leave the device. This is what the
    dispatch cache (core/dispatch.py) pre-compiles per bucket."""
    choices, _, top_i = _route(state, query_embs, budgets, costs,
                               p_global, n_neighbors, k, backend, mode,
                               init_rating)
    return RouteChoices(choices, top_i)


@partial(jax.jit,
         static_argnames=("mesh", "p_global", "n_neighbors", "k",
                          "backend", "mode", "init_rating"))
def route_batch_choices_sharded(state: RouterState, query_embs, budgets,
                                costs, *, mesh: Mesh,
                                p_global: float = 0.5,
                                n_neighbors: int = 20, k: float = 32.0,
                                backend: str = "reference",
                                mode: str = "combined",
                                init_rating: float = elo.DEFAULT_RATING
                                ) -> RouteChoices:
    """route_batch_choices over a capacity-sharded RouterState
    (DESIGN.md §12): one jitted dispatch whose retrieval chain runs
    under shard_map over the DB axis — per-shard similarity + local
    top-k, cross-shard candidate merge, replicated replay/selection
    epilogue. Bit-identical choices/topk_idx to the single-device
    oracle; `mesh` is static so each DB mesh compiles its own
    executable (the dispatch cache keys on it)."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    q = jnp.atleast_2d(jnp.asarray(query_embs, jnp.float32))
    nq = q.shape[0]
    m = state.n_models
    n = min(n_neighbors, state.capacity)
    costs = jnp.asarray(costs, jnp.float32)
    budgets = jnp.broadcast_to(jnp.asarray(budgets, jnp.float32), (nq,))
    if mode == "global":
        # no retrieval: ratings/size replicate, so no shard_map either
        scores = jnp.broadcast_to(state.global_ratings, (nq, m))
        choices, _ = select_within_budget(scores, costs, budgets)
        return RouteChoices(choices, jnp.full((nq, n), -1, jnp.int32))
    if mode == "local":
        init = jnp.full((m,), jnp.float32(init_rating))  # flat prior
        p = 0.0   # 0*Global + 1*Local == Local, bit-exact for finite r
    else:
        init = state.global_ratings
        p = p_global
    axis = SHARD.DB_AXIS

    def body(gr, init_b, emb, model_a, model_b, outcome, valid, size,
             qq, bb, cc):
        _, top_i, _, choices = KOPS.retrieve_replay_select_sharded(
            qq, emb, model_a, model_b, outcome, valid, size, init_b, gr,
            cc, bb, n=n, k=k, p=p, backend=backend, axis_name=axis)
        return choices, top_i

    shd = P(axis)
    # check_vma=False: the merged epilogue output is replicated by
    # construction (every shard reduces the same gathered pool), which
    # shard_map's replication checker cannot prove through all_gather
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), P(), shd, shd, shd, shd, shd,
                                 P(), P(), P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    with jax.named_scope("eagle.retrieve_replay_select_sharded"):
        choices, top_i = fn(state.global_ratings, init, state.emb,
                            state.model_a, state.model_b, state.outcome,
                            state.valid, state.size, q, budgets, costs)
    return RouteChoices(choices, top_i)
