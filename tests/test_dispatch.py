"""Tests for the steady-state dispatch layer (core/dispatch.py) and the
fused budget-selection epilogue: bucket policy, bucket-padding
invariance of choices (raw vs dispatcher-padded, all modes, both
backends), no-recompile within a bucket, fused choices vs the
select_within_budget oracle, warmup precompilation, and DoubleBuffer
equivalence to a full upload."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dispatch import (MIN_BUCKET, CapacityPrebaker,
                                 CompileCounter, RouteDispatcher,
                                 batch_bucket, bucket_ladder,
                                 xla_compile_count)
from repro.core.router import (EagleConfig, EagleRouter, GlobalOnlyRouter,
                               LocalOnlyRouter, select_within_budget)
from repro.core.state import (DoubleBuffer, route_batch,
                              route_batch_choices, state_from_buffer)
from repro.kernels import ref as kref
from repro.kernels.similarity_topk import two_stage_topk

jax.config.update("jax_platform_name", "cpu")

ROUTERS = {"combined": EagleRouter, "global": GlobalOnlyRouter,
           "local": LocalOnlyRouter}


def _router(seed=0, n_models=5, dim=8, n_prompts=40, capacity=64,
            mode="combined", backend="reference"):
    rng = np.random.default_rng(seed)
    r = ROUTERS[mode]([f"m{i}" for i in range(n_models)],
                      np.arange(1, n_models + 1.0),
                      EagleConfig(embed_dim=dim, backend=backend),
                      db_capacity=capacity)
    emb = rng.normal(size=(n_prompts, dim)).astype(np.float32)
    a = rng.integers(0, n_models, n_prompts)
    b = (a + 1 + rng.integers(0, n_models - 1, n_prompts)) % n_models
    s = rng.choice([0.0, 0.5, 1.0], n_prompts)
    r.fit(emb, a, b, s, query_id=np.arange(n_prompts))
    return r, rng


# ---------------------------------------------------------------------------
# bucket policy
# ---------------------------------------------------------------------------

def test_batch_bucket_policy():
    assert batch_bucket(1) == MIN_BUCKET
    assert batch_bucket(MIN_BUCKET) == MIN_BUCKET
    assert batch_bucket(MIN_BUCKET + 1) == 2 * MIN_BUCKET
    assert batch_bucket(1000) == 1024
    # beyond max_bucket: still pow2-padded (rare, but never raises)
    assert batch_bucket(1025) == 2048
    assert bucket_ladder(8, 64) == (8, 16, 32, 64)
    for n in (1, 7, 9, 100, 500):
        assert batch_bucket(n) >= n


# ---------------------------------------------------------------------------
# bucket-padding invariance + oracle parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", list(ROUTERS))
@pytest.mark.parametrize("backend", ["reference", "pallas_interpret"])
def test_bucketed_choices_bit_identical_to_raw(mode, backend):
    """Dispatcher-padded routing must give exactly the raw route_batch
    choices: padded rows change nothing about live rows."""
    r, rng = _router(seed=1, mode=mode, backend=backend)
    d = RouteDispatcher.for_router(r)
    for nq in (1, 7, 8, 13):
        q = rng.normal(size=(nq, 8)).astype(np.float32)
        budgets = rng.uniform(0.5, 6.0, nq).astype(np.float32)
        raw = np.asarray(r.route(q, budgets))
        np.testing.assert_array_equal(d.route(r.state, q, budgets), raw)


@pytest.mark.parametrize("mode", list(ROUTERS))
@pytest.mark.parametrize("backend", ["reference", "pallas_interpret"])
def test_fused_epilogue_matches_budget_oracle(mode, backend):
    """The choices emitted by the kernel epilogue must be bit-identical
    to select_within_budget applied to the returned score panel (the
    standalone function is the parity oracle)."""
    r, rng = _router(seed=2, mode=mode, backend=backend)
    q = rng.normal(size=(9, 8)).astype(np.float32)
    # include infeasible budgets to exercise the cheapest-model fallback
    budgets = np.concatenate([
        rng.uniform(0.5, 6.0, 7), [0.0, 0.1]]).astype(np.float32)
    res = r.route_result(q, budgets)
    oracle, _ = select_within_budget(res.scores, r.costs, budgets)
    np.testing.assert_array_equal(np.asarray(res.choices),
                                  np.asarray(oracle))


def test_scalar_budget_broadcasts():
    r, rng = _router(seed=3)
    q = rng.normal(size=(5, 8)).astype(np.float32)
    per_q = np.full((5,), 3.0, np.float32)
    np.testing.assert_array_equal(np.asarray(r.route(q, 3.0)),
                                  np.asarray(r.route(q, per_q)))
    d = RouteDispatcher.for_router(r)
    np.testing.assert_array_equal(d.route(r.state, q, 3.0),
                                  np.asarray(r.route(q, per_q)))


# ---------------------------------------------------------------------------
# compile behavior: one executable per bucket, warmup pre-bakes
# ---------------------------------------------------------------------------

def test_same_bucket_no_second_compile():
    """Two batch sizes landing in the same bucket share one executable:
    cache stats record a single miss AND jax.monitoring observes zero
    backend compilations on the second call."""
    r, rng = _router(seed=4)
    d = RouteDispatcher.for_router(r)
    d.route(r.state, rng.normal(size=(9, 8)).astype(np.float32), 3.0)
    assert d.cache_stats()["misses"] == 1
    with CompileCounter() as c:
        d.route(r.state, rng.normal(size=(13, 8)).astype(np.float32), 3.0)
    assert c.delta() == 0
    stats = d.cache_stats()
    assert stats["misses"] == 1 and stats["hits"] == 1
    assert stats["entries"] == 1


def test_warmup_prebakes_ladder():
    r, rng = _router(seed=5)
    d = RouteDispatcher.for_router(r, max_bucket=32)
    n = d.warmup(r.state)
    assert n == len(bucket_ladder(d.min_bucket, 32)) == 3
    assert d.warmup(r.state) == 0  # idempotent
    with CompileCounter() as c:
        for nq in (1, 5, 8, 9, 16, 17, 31, 32):
            d.route(r.state, rng.normal(size=(nq, 8)).astype(np.float32),
                    2.5)
    assert c.delta() == 0
    stats = d.cache_stats()
    assert stats["misses"] == stats["warmed"] == 3


def test_oversized_batch_chunks_on_ladder():
    """A batch beyond max_bucket splits into ladder-sized dispatches:
    choices identical to one raw route_batch call over the full batch,
    zero fresh compiles after warmup, and route_result concatenating
    both of its outputs across the chunks."""
    r, rng = _router(seed=9)
    d = RouteDispatcher.for_router(r, min_bucket=8, max_bucket=16)
    d.warmup(r.state)
    q = rng.normal(size=(35, 8)).astype(np.float32)
    budgets = rng.uniform(0.5, 6.0, 35).astype(np.float32)
    want = np.asarray(route_batch(r.state, q, budgets, r.costs,
                                  **r._kw()).choices)
    with CompileCounter() as c:
        got = d.route(r.state, q, budgets)
        ch2, topk = d.route_result(r.state, q, budgets)
    assert c.delta() == 0                    # 16+16+8 all pre-warmed
    assert got.shape == (35,)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(ch2, want)
    assert topk.shape[0] == 35


def test_oversized_batch_scalar_budget():
    """Scalar budgets broadcast across chunk boundaries too."""
    r, rng = _router(seed=10)
    d = RouteDispatcher.for_router(r, max_bucket=MIN_BUCKET)
    q = rng.normal(size=(21, 8)).astype(np.float32)
    got = d.route(r.state, q, 2.5)
    np.testing.assert_array_equal(got, np.asarray(r.route(q, 2.5)))
    assert got.shape == (21,)


def test_cache_key_tracks_state_shape():
    """Growing the DB changes (capacity, records_per_query) — the cache
    key must see that as a new signature, not serve a stale executable."""
    rng = np.random.default_rng(6)
    r = EagleRouter(["a", "b", "c"], [1.0, 2.0, 3.0],
                    EagleConfig(embed_dim=4), db_capacity=4)
    r.fit(rng.normal(size=(3, 4)).astype(np.float32), [0, 1, 2],
          [1, 2, 0], [1.0, 0.5, 0.0], query_id=[0, 1, 2])
    d = RouteDispatcher.for_router(r)
    q = rng.normal(size=(2, 4)).astype(np.float32)
    d.route(r.state, q, 5.0)
    assert d.cache_stats()["entries"] == 1
    r.update(rng.normal(size=(7, 4)).astype(np.float32), [0] * 7, [1] * 7,
             [1.0] * 7, query_id=list(range(3, 10)))  # forces _grow
    ch = d.route(r.state, q, 5.0)
    assert d.cache_stats()["entries"] == 2
    np.testing.assert_array_equal(ch, np.asarray(r.route(q, 5.0)))


# ---------------------------------------------------------------------------
# two-stage top-k through the route (kernels/similarity_topk.py)
# ---------------------------------------------------------------------------

WIDE_CAPACITY = 1 << 14     # 128 chunks of 128 rows: two stages at N = 20


def _one_stage_topk(scores, n, size, offset=0):
    live = (jnp.arange(scores.shape[1]) + offset) < size
    return jax.lax.top_k(jnp.where(live[None, :], scores, -jnp.inf), n)


@contextlib.contextmanager
def _traced_with_one_stage_topk():
    """Route programs traced inside take lax.top_k over the masked
    panel in place of panel_topk: the oracle of the two-stage path."""
    jax.clear_caches()
    saved = kref.panel_topk
    kref.panel_topk = _one_stage_topk
    try:
        yield
    finally:
        kref.panel_topk = saved
        jax.clear_caches()


def _wide_router(backend):
    """12,001 live rows of 2^14 (the live edge inside a chunk); every
    tenth row repeats an earlier row's embedding, so scores tie, and
    five of the 13 queries are such rows."""
    rng = np.random.default_rng(11)
    n_models, n = 5, 12_001
    r = EagleRouter([f"m{i}" for i in range(n_models)],
                    np.arange(1, n_models + 1.0),
                    EagleConfig(embed_dim=8, backend=backend),
                    db_capacity=WIDE_CAPACITY)
    emb = rng.normal(size=(n, 8)).astype(np.float32)
    emb[10::10] = emb[:len(emb[10::10])]
    a = rng.integers(0, n_models, n)
    b = (a + 1 + rng.integers(0, n_models - 1, n)) % n_models
    r.fit(emb, a, b, rng.choice([0.0, 0.5, 1.0], n),
          query_id=np.arange(n))
    q = rng.normal(size=(13, 8)).astype(np.float32)
    q[:5] = emb[[0, 3, 7, 500, 1100]]
    return r, q, rng.uniform(0.5, 6.0, 13).astype(np.float32)


@pytest.mark.parametrize("backend", ["reference", "pallas_interpret"])
def test_two_stage_route_bit_identical_to_one_stage(backend):
    """At a capacity that takes the two-stage top-k, route_batch_choices
    and the dispatcher return the rows and choices of the same route
    traced with lax.top_k, bit for bit, ties included."""
    r, q, budgets = _wide_router(backend)
    assert two_stage_topk(r.state.capacity, r.cfg.n_neighbors)
    got = route_batch_choices(r.state, q, budgets, r.costs, **r._kw())
    got_c, got_i = RouteDispatcher.for_router(r).route_result(
        r.state, q, budgets)
    with _traced_with_one_stage_topk():
        want = route_batch_choices(r.state, q, budgets, r.costs, **r._kw())
    want_i, want_c = np.asarray(want.topk_idx), np.asarray(want.choices)
    np.testing.assert_array_equal(np.asarray(got.topk_idx), want_i)
    np.testing.assert_array_equal(np.asarray(got.choices), want_c)
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_array_equal(got_c, want_c)


@pytest.mark.parametrize("capacity,mode,two_stage", [
    (64, "combined", False), (WIDE_CAPACITY, "combined", True),
    (WIDE_CAPACITY, "global", False)])
def test_two_stage_counter_counts_its_dispatches(capacity, mode, two_stage):
    """dispatch_two_stage_topk_total counts every dispatch whose
    executable takes the two-stage top-k, and none other."""
    r, rng = _router(seed=12, capacity=capacity, mode=mode)
    d = RouteDispatcher.for_router(r, max_bucket=16)
    for nq in (3, 9, 30):                  # 30 rows: two dispatches
        d.route(r.state, rng.normal(size=(nq, 8)).astype(np.float32), 3.0)
    d.route_result(r.state, rng.normal(size=(5, 8)).astype(np.float32), 3.0)
    reg = d.obs.registry
    calls = reg.counter("dispatch_calls_total").value
    assert calls == 5
    assert reg.counter("dispatch_two_stage_topk_total").value == \
        (calls if two_stage else 0)


# ---------------------------------------------------------------------------
# DoubleBuffer: both replicas track the host buffer
# ---------------------------------------------------------------------------

def test_double_buffer_front_equals_full_upload():
    """After every commit the new front must equal a from-scratch upload
    of the host buffer: per-consumer ledgers deliver rows appended
    between a replica's turns."""
    r, rng = _router(seed=7)
    dbuf = DoubleBuffer(r.db, r.global_ratings)
    for round_ in range(4):
        emb = rng.normal(size=(3, 8)).astype(np.float32)
        r.update(emb, [0, 1, 2], [1, 2, 0], [1.0, 0.0, 0.5],
                 query_id=[100 + 3 * round_ + i for i in range(3)])
        front = dbuf.commit(r.global_ratings)
        full = state_from_buffer(r.db, r.global_ratings)
        for got, want in zip(jax.tree_util.tree_leaves(front),
                             jax.tree_util.tree_leaves(full)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_double_buffer_routing_equivalence():
    """Routing over the double-buffered front == routing over the
    router's own (single-buffer) state, across interleaved commits."""
    r, rng = _router(seed=8)
    dbuf = DoubleBuffer(r.db, r.global_ratings)
    d = RouteDispatcher.for_router(r)
    kw = r._kw()
    for round_ in range(3):
        q = rng.normal(size=(6, 8)).astype(np.float32)
        budgets = rng.uniform(0.5, 6.0, 6).astype(np.float32)
        got = d.route(dbuf.front, q, budgets)
        want = np.asarray(route_batch(
            state_from_buffer(r.db, r.global_ratings), q, budgets,
            r.costs, **kw).choices)
        np.testing.assert_array_equal(got, want)
        r.feedback(rng.normal(size=(2, 8)).astype(np.float32),
                   [0, 1], [2, 3], [1.0, 0.0])
        dbuf.commit(r.global_ratings)


# ---------------------------------------------------------------------------
# capacity prebaker: zero hot-path compiles across a DB growth boundary
# ---------------------------------------------------------------------------

def test_prebaker_poll_gating():
    """poll() is inert below the watermark, bakes once per capacity,
    and never double-starts."""
    r, _ = _router(capacity=64, n_prompts=40)
    d = RouteDispatcher.for_router(r)
    pb = CapacityPrebaker(d, r.db, watermark=0.75, batch_sizes=[4])
    assert r.db.size < 0.75 * r.db.capacity
    assert pb.poll() is False          # below watermark
    rng = np.random.default_rng(3)
    while r.db.size < 48:              # cross the watermark
        r.update(rng.normal(size=(1, 8)).astype(np.float32),
                 [0], [1], [1.0], query_id=[1000 + r.db.size])
    assert pb.poll() is True           # bake for next_capacity (128)
    pb.join()
    assert pb.poll() is False          # 128 already baked
    assert (d.bucket(4), 128, r.db.rcap, "combined", "reference",
            None) in d._cache


def test_prebaker_zero_hot_compiles_across_growth():
    """200-step serving loop (route + feedback + commit) that crosses a
    VectorDB growth boundary: with the prebaker polled after each
    commit, the hot path never compiles — the grown capacity's ladder
    and scatter are baked in the background before _grow() trips.
    Background bake compiles land outside the counted regions (join()
    runs between steps, where a serving loop would absorb them off the
    critical path)."""
    r, rng = _router(capacity=256, n_prompts=150, dim=8)
    d = RouteDispatcher.for_router(r)
    dbuf = DoubleBuffer(r.db, r.global_ratings)
    pb = CapacityPrebaker(d, r.db, watermark=0.75, batch_sizes=[8])
    q = rng.normal(size=(8, 8)).astype(np.float32)
    budgets = rng.uniform(0.5, 6.0, 8).astype(np.float32)
    # warmup at the CURRENT capacity: the ladder bucket plus two real
    # feedback+commit cycles (the scatter only compiles on the first
    # non-empty ledger — an empty-ledger commit would leave it cold)
    d.warmup(dbuf.front, batch_sizes=[8])
    next_row = 150
    for _ in range(2):
        r.update(rng.normal(size=(1, 8)).astype(np.float32),
                 [0], [1], [1.0], query_id=[next_row])
        next_row += 1
        dbuf.commit(r.global_ratings)
    d.route(dbuf.front, q, budgets)

    hot = 0
    start_capacity = r.db.capacity
    for step in range(200):
        c0 = xla_compile_count()
        d.route(dbuf.front, q, budgets)
        r.update(rng.normal(size=(1, 8)).astype(np.float32),
                 [step % 5], [(step + 1) % 5], [float(step % 2)],
                 query_id=[next_row])
        next_row += 1
        dbuf.commit(r.global_ratings)
        hot += xla_compile_count() - c0
        if pb.poll():
            pb.join()                  # bake compiles: NOT hot-path
    assert r.db.capacity > start_capacity, "loop never crossed a grow"
    assert r.db.size > start_capacity
    assert hot == 0, f"{hot} hot-path compiles across the growth"


def test_steady_packed_commit_compiles_nothing_keeps_shardings():
    """One device: after warm-up, windows of route + feedback + packed
    commit compile nothing, every commit takes the packed path, and each
    front's leaves keep the shardings of the state the dispatcher's
    executable was built on, so that one executable runs on every front.
    (The 1/2/4-shard meshes: tests/test_sharded_state.py.)"""
    from repro import obs as OBS
    r, rng = _router(seed=12, capacity=256, n_prompts=100)
    ob = OBS.Observability()
    dbuf = DoubleBuffer(r.db, r.global_ratings, obs=ob)
    d = RouteDispatcher.for_router(r)
    d.warmup(dbuf.front, batch_sizes=[8])
    q = rng.normal(size=(8, 8)).astype(np.float32)
    budgets = rng.uniform(0.5, 6.0, 8).astype(np.float32)
    want = [(x.sharding, x._committed)
            for x in jax.tree_util.tree_leaves(dbuf.front)]

    def window(k):
        d.route(dbuf.front, q, budgets)
        r.update(rng.normal(size=(2, 8)).astype(np.float32), [0, 1], [2, 3],
                 [1.0, 0.0], query_id=[1000 + 2 * k, 1001 + 2 * k])
        return dbuf.commit(r.global_ratings)

    for k in range(2):                 # the scatter's first compile
        window(k)
    with CompileCounter() as c:
        for k in range(2, 8):
            front = window(k)
            got = [(x.sharding, x._committed)
                   for x in jax.tree_util.tree_leaves(front)]
            assert got == want, k
        jax.block_until_ready(dbuf.front)
    assert c.delta() == 0
    assert d.cache_stats()["misses"] == 1
    assert ob.registry.value("dbuf_commit_total", path="packed") == 8
