"""Tests for the functional routing core: RouterState, commit(), and the
fused route_batch pipeline (equivalence vs the legacy object path,
incremental commit correctness, ref vs pallas_interpret parity, and
device-residency of the hot path)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _commit_oracle import (commit_steps, differing, host_differing,
                            per_field_commit)
from repro import obs as OBS
from repro.core import elo
from repro.core.router import (EagleConfig, EagleRouter, GlobalOnlyRouter,
                               LocalOnlyRouter, combine_scores,
                               select_within_budget)
from repro.core.state import (DoubleBuffer, RouterState, commit,
                              init_state, route_batch, state_from_buffer)
from repro.core.vectordb import VectorDB
from repro.kernels import ops

jax.config.update("jax_platform_name", "cpu")


def _random_router(seed=0, n_models=5, dim=8, n_prompts=40, capacity=64,
                   cls=EagleRouter):
    rng = np.random.default_rng(seed)
    r = cls([f"m{i}" for i in range(n_models)],
            np.arange(1, n_models + 1.0),
            EagleConfig(embed_dim=dim), db_capacity=capacity)
    emb = rng.normal(size=(n_prompts, dim)).astype(np.float32)
    a = rng.integers(0, n_models, n_prompts)
    b = (a + 1 + rng.integers(0, n_models - 1, n_prompts)) % n_models
    s = rng.choice([0.0, 0.5, 1.0], n_prompts)
    r.fit(emb, a, b, s, query_id=np.arange(n_prompts))
    return r, rng


def _legacy_scores(router, q):
    """The seed implementation's object path: host-hopping retrieval
    (VectorDB.query -> gather_feedback) + local replay + combine."""
    idx, _, hit = router.db.query(q, router.cfg.n_neighbors)
    a, b, s, v = router.db.gather_feedback(idx, hit)
    local = elo.local_elo(router.global_ratings, a, b, s, v,
                          k=router.cfg.k_factor)
    return combine_scores(router.global_ratings, local, router.cfg.p_global)


# ---------------------------------------------------------------------------
# equivalence: fused pipeline == legacy object path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_route_batch_matches_legacy_path(seed):
    router, rng = _random_router(seed=seed)
    q = rng.normal(size=(7, 8)).astype(np.float32)
    budgets = rng.uniform(0.5, 6.0, 7).astype(np.float32)

    want_scores = np.asarray(_legacy_scores(router, q))
    want_choice, _ = select_within_budget(jnp.asarray(want_scores),
                                          router.costs, budgets)

    res = router.route_result(q, budgets)
    np.testing.assert_allclose(np.asarray(res.scores), want_scores,
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(res.choices),
                                  np.asarray(want_choice))


def test_ablation_modes_match_legacy_semantics():
    g, rng = _random_router(seed=3, cls=GlobalOnlyRouter)
    q = rng.normal(size=(4, 8)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(g.scores(q)),
        np.tile(np.asarray(g.global_ratings), (4, 1)))

    l, rng = _random_router(seed=4, cls=LocalOnlyRouter)
    q = rng.normal(size=(4, 8)).astype(np.float32)
    idx, _, hit = l.db.query(q, l.cfg.n_neighbors)
    a, b, s, v = l.db.gather_feedback(idx, hit)
    flat = jnp.full((l.n_models,), l.cfg.init_rating, jnp.float32)
    want = elo.local_elo(flat, a, b, s, v, k=l.cfg.k_factor)
    np.testing.assert_allclose(np.asarray(l.scores(q)), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_empty_db_scores_equal_prior():
    r = EagleRouter(["a", "b"], [1.0, 2.0], EagleConfig(embed_dim=4),
                    db_capacity=8)
    q = np.ones((3, 4), np.float32)
    np.testing.assert_allclose(
        np.asarray(r.scores(q)),
        np.full((3, 2), r.cfg.init_rating))


# ---------------------------------------------------------------------------
# commit(): incremental sync + growth
# ---------------------------------------------------------------------------

def test_incremental_commit_equals_full_upload():
    router, rng = _random_router(seed=5)
    s1 = router.state
    emb2 = rng.normal(size=(5, 8)).astype(np.float32)
    router.update(emb2, [1, 2, 3, 4, 0], [0, 0, 0, 0, 1],
                  [1.0, 0.0, 0.5, 1.0, 0.0],
                  query_id=[100 + i for i in range(5)])
    s2 = router.state                     # incremental scatter into s1
    full = state_from_buffer(router.db, router.global_ratings)
    for got, want in zip(jax.tree_util.tree_leaves(s2),
                         jax.tree_util.tree_leaves(full)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_commit_after_db_growth():
    rng = np.random.default_rng(6)
    router = EagleRouter(["a", "b", "c"], [1.0, 2.0, 3.0],
                         EagleConfig(embed_dim=4), db_capacity=4)
    emb = rng.normal(size=(10, 4)).astype(np.float32)
    router.fit(emb[:3], [0, 1, 2], [1, 2, 0], [1.0, 0.5, 0.0],
               query_id=[0, 1, 2])
    s1 = router.state
    assert s1.capacity == 4
    # force both prompt-axis and record-axis growth
    router.update(emb[3:], [0] * 7, [1] * 7, [1.0] * 7,
                  query_id=list(range(3, 10)))
    for _ in range(10):  # record-axis growth on one prompt
        router.update(emb[:1], [1], [2], [0.0], query_id=[0])
    s2 = router.state
    assert s2.capacity >= 10 and s2.records_per_query >= 11
    assert int(s2.size) == router.db.size == 10
    full = state_from_buffer(router.db, router.global_ratings)
    for got, want in zip(jax.tree_util.tree_leaves(s2),
                         jax.tree_util.tree_leaves(full)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    # and the grown state still routes
    q = rng.normal(size=(2, 4)).astype(np.float32)
    res = route_batch(s2, q, 5.0, router.costs)
    assert np.asarray(res.choices).shape == (2,)


def test_commit_guards_stale_dirty_rows_after_clear():
    """Rollback race: rows marked dirty between a drain and a clear()
    leave the ledger pointing past the live count. commit() must drop
    them (rows < size) instead of scattering stale content — and must
    not index rows[0] of the then-empty set."""
    router, rng = _random_router(seed=11)
    s1 = router.state
    router.db.clear()
    # simulate the race: ledger refers to rows at/past db.size == 0
    router.db._dirty["default"].update({0, 3, 7})
    s2 = commit(router.db, router.global_ratings, s1)
    assert int(s2.size) == 0
    # empty DB: retrieval is fully masked, scores fall back to the prior
    q = rng.normal(size=(2, 8)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(route_batch(s2, q, jnp.inf, router.costs).scores),
        np.tile(np.asarray(s2.global_ratings), (2, 1)), rtol=1e-6)


def test_commit_mixed_live_and_stale_dirty_rows():
    """After clear()+re-add, only rows below the live count scatter;
    stale ledger entries beyond it are dropped, and the result matches
    a from-scratch upload."""
    router, rng = _random_router(seed=12)
    s1 = router.state
    db = router.db
    db.clear()
    emb = rng.normal(size=(2, 8)).astype(np.float32)
    router.update(emb, [0, 1], [1, 2], [1.0, 0.0], query_id=[0, 1])
    db._dirty["default"].add(30)          # stale row past size == 2
    router._state, router._stale = s1, True
    s2 = router.state                     # commit() with the guard
    assert int(s2.size) == 2
    full = state_from_buffer(db, router.global_ratings)
    np.testing.assert_allclose(np.asarray(s2.emb[:2]),
                               np.asarray(full.emb[:2]))
    np.testing.assert_array_equal(np.asarray(s2.valid[:2]),
                                  np.asarray(full.valid[:2]))


def test_vectordb_clear_resets_and_reuses():
    router, rng = _random_router(seed=13)
    db = router.db
    assert db.size > 0
    db.clear()
    assert db.size == 0 and not db.valid.any() and not db.n_rec.any()
    for ledger in db._dirty.values():
        assert not ledger
    # buffer is reusable in place: same shapes, fresh content
    emb = rng.normal(size=(3, 8)).astype(np.float32)
    db.add(emb, [0, 1, 2], [1, 2, 0], [1.0, 0.5, 0.0], query_id=[0, 1, 2])
    assert db.size == 3 and db.valid[:3, 0].all()


def test_commit_without_writes_refreshes_ratings_only():
    router, rng = _random_router(seed=7)
    s1 = router.state
    router.global_ratings = router.global_ratings + 10.0
    router._stale = True
    s2 = router.state
    np.testing.assert_allclose(np.asarray(s2.global_ratings),
                               np.asarray(s1.global_ratings) + 10.0)
    np.testing.assert_allclose(np.asarray(s2.emb), np.asarray(s1.emb))


def test_packed_commit_bitwise_equals_per_field_commit():
    """Over a sequence of commits (a padded bucket, rows rewritten with
    -0.0, NaN payloads, denormals and mixed `valid`, rolled-back rows
    past the live count, a commit with no dirty row, a wider spread),
    the packed one-transfer commit leaves every field bit for bit as
    the per-field commit it replaced, and the live rows as the host
    buffer holds them."""
    db = VectorDB(16, capacity=128, records_per_query=6)
    db.register_consumer("packed")
    db.register_consumer("oracle")
    rng = np.random.default_rng(21)
    g = jnp.full((4,), 1500.0, jnp.float32)
    got = want = None
    for k, step in enumerate(commit_steps(db, rng, 4)):
        g = g + 0.25 * k
        got = commit(db, g, got, consumer="packed")
        want = per_field_commit(db, g, want, "oracle")
        assert not differing(want, got), step
        assert not host_differing(db, got), step


def test_double_buffer_counts_commit_paths():
    """dbuf_commit_total{path}: incremental commits are `packed`, a
    commit after the buffer grew is `full`, one with nothing dirty is
    `ratings_only`."""
    router, rng = _random_router(seed=9, capacity=64, n_prompts=40)
    ob = OBS.Observability()
    dbuf = DoubleBuffer(router.db, router.global_ratings, obs=ob)

    def count(path):
        return ob.registry.value("dbuf_commit_total", path=path)

    def add(n):
        router.update(rng.normal(size=(n, 8)).astype(np.float32),
                      [0] * n, [1] * n, [1.0] * n,
                      query_id=[1000 + router.db.size + i for i in range(n)])

    for _ in range(3):
        add(2)
        dbuf.commit(router.global_ratings)
    assert (count("packed"), count("full"), count("ratings_only")) == \
        (3, 0, 0)
    dbuf.commit(router.global_ratings)   # the other replica's backlog
    dbuf.commit(router.global_ratings)   # nothing left for either
    assert (count("packed"), count("ratings_only")) == (4, 1)
    add(30)                              # past the 64-row capacity
    assert router.db.capacity > 64
    dbuf.commit(router.global_ratings)
    dbuf.commit(router.global_ratings)
    assert (count("packed"), count("full"), count("ratings_only")) == \
        (4, 2, 1)
    add(1)
    dbuf.commit(router.global_ratings)
    assert count("packed") == 5
    full = state_from_buffer(router.db, router.global_ratings)
    assert not differing(full, dbuf.front)


# ---------------------------------------------------------------------------
# fused retrieve_replay op: reference vs pallas_interpret parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nq,cap,rcap,d,m,n", [(4, 32, 4, 16, 6, 5),
                                               (1, 8, 2, 8, 3, 8),
                                               (9, 130, 3, 32, 10, 20)])
def test_retrieve_replay_backend_parity(nq, cap, rcap, d, m, n):
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(nq, d)), jnp.float32)
    emb = jnp.asarray(rng.normal(size=(cap, d)), jnp.float32)
    size = jnp.int32(cap - cap // 3)
    a = jnp.asarray(rng.integers(0, m, (cap, rcap)), jnp.int32)
    b = jnp.asarray((np.asarray(a) + 1) % m, jnp.int32)
    o = jnp.asarray(rng.choice([0.0, 0.5, 1.0], (cap, rcap)), jnp.float32)
    v = jnp.asarray(rng.random((cap, rcap)) > 0.3)
    init = jnp.asarray(1000 + 40 * rng.normal(size=(m,)), jnp.float32)
    costs = jnp.asarray(rng.uniform(1.0, 4.0, m), jnp.float32)
    budgets = jnp.asarray(rng.uniform(0.5, 4.5, nq), jnp.float32)
    n_eff = min(n, cap)
    ref_out, pal_out = (
        ops.retrieve_replay_select(q, emb, a, b, o, v, size, init, init,
                                   costs, budgets, n=n_eff, k=32.0,
                                   backend=bk)
        for bk in ("reference", "pallas_interpret"))
    np.testing.assert_array_equal(np.asarray(ref_out[1]),
                                  np.asarray(pal_out[1]))
    np.testing.assert_allclose(np.asarray(ref_out[0]),
                               np.asarray(pal_out[0]), rtol=1e-5, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(ref_out[3]),
                                  np.asarray(pal_out[3]))


# ---------------------------------------------------------------------------
# device residency: the hot path must tolerate tracing end-to-end
# ---------------------------------------------------------------------------

def test_route_batch_is_traceable_end_to_end():
    """route_batch under an outer jit: any host transfer between the
    similarity panel and model selection (np.asarray on a tracer) would
    raise TracerArrayConversionError here."""
    router, rng = _random_router(seed=9)
    q = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    budgets = jnp.asarray(rng.uniform(1.0, 5.0, 5), jnp.float32)

    @jax.jit
    def routed(state, q, budgets, costs):
        return route_batch(state, q, budgets, costs)

    res = routed(router.state, q, budgets, router.costs)
    assert isinstance(res.choices, jax.Array)
    np.testing.assert_array_equal(
        np.asarray(res.choices),
        np.asarray(router.route(q, budgets)))


def test_state_is_pytree():
    s = init_state(4, 8, capacity=16, records_per_query=2)
    leaves = jax.tree_util.tree_leaves(s)
    assert len(leaves) == 7
    s2 = jax.tree_util.tree_map(lambda x: x, s)
    assert isinstance(s2, RouterState)
    assert s2.n_models == 4 and s2.capacity == 16 and s2.dim == 8
