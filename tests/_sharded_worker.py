"""Subprocess worker for tests/test_sharded_state.py (DESIGN.md §12).

The forced-host-device XLA flag must be set before jax initializes, so
the sharded-equivalence checks cannot run in the pytest process: the
parent test spawns THIS script once with
`XLA_FLAGS=--xla_force_host_platform_device_count=4`, and it prints a
single JSON report line covering the whole matrix —

  * route_batch_choices_sharded vs the single-device oracle, bitwise,
    on {1,2,4}-shard meshes x all routing modes x both exercisable
    backends (reference, pallas_interpret);
  * tie-breaking stress: duplicate embeddings straddling every shard
    boundary, an empty DB (all -inf similarity), and flat ratings
    (budget-selector ties) — all must match the oracle bit for bit;
  * incremental sharded commit() vs the oracle commit, field by field,
    plus post-commit routing equality;
  * zero post-warmup XLA compiles per mesh shape across a
    route+feedback+commit steady-state loop (warmup includes REAL
    feedback+commit cycles: an empty-ledger commit never exercises the
    scatter, so counting before the first real cycle would charge its
    compile to the steady state);
  * the named device scopes of the 4-shard route (both backends) and
    of the owner-scatter, in their lowered HLO;
  * a DB wide enough that every shard's top-k takes two stages
    (kernels/similarity_topk.py panel_topk), through the dispatcher on
    each mesh and both backends: bitwise equal to one device, with
    dispatch_two_stage_topk_total counting every dispatch there and
    none at the small capacity above;
  * a seeded property-style table the parent replays through the
    hypothesis shim.
"""
import json
import sys

import numpy as np

M, D, CAP, RCAP = 4, 16, 128, 6
#: 2^14 rows a shard on 4 shards: two-stage top-k on every mesh
WIDE_CAP, WIDE_LIVE = 1 << 16, 50_001
MESHES = (1, 2, 4)
MODES = ("combined", "global", "local")
BACKENDS = ("reference", "pallas_interpret")


def _fill(db, n_rows, rng, dup_pairs=((15, 16), (31, 32), (63, 64))):
    """Seeded feedback: one prompt per row, 1..RCAP-1 records each.
    `dup_pairs` forces bit-identical embeddings on row pairs that
    straddle the shard boundaries of every mesh in MESHES — equal
    similarity scores whose tie-break must agree with the oracle."""
    emb = rng.normal(size=(n_rows, D)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    for a, b in dup_pairs:
        if b < n_rows:
            emb[b] = emb[a]
    for i in range(n_rows):
        k = int(rng.integers(1, RCAP))
        a = rng.integers(0, M, k).astype(np.int32)
        b = ((a + rng.integers(1, M, k)) % M).astype(np.int32)
        s = rng.random(k).astype(np.float32).round()
        db.add(np.repeat(emb[i:i + 1], k, axis=0), a, b, s,
               query_id=np.full(k, i))
    return emb


def main():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import elo, state as STATE
    from repro.core.dispatch import CompileCounter
    from repro.core.vectordb import VectorDB
    from repro.launch.mesh import make_db_mesh

    report = {"n_devices": jax.device_count()}
    rng = np.random.default_rng(0)
    costs = np.array([1.0, 2.0, 4.0, 8.0], np.float32)
    # tie between models 0 and 1: the budget selector must break it
    # identically on every mesh
    ratings = np.array([1500.0, 1500.0, 1520.0, 1480.0], np.float32)
    meshes = {s: make_db_mesh(s) for s in MESHES}

    def rep(mesh, x):
        return jax.device_put(x, NamedSharding(mesh, P()))

    def sharded_route(mesh, state, q, budgets, **kw):
        sstate = STATE.shard_state(state, mesh)
        return STATE.route_batch_choices_sharded(
            sstate, rep(mesh, q), rep(mesh, budgets), rep(mesh, costs),
            mesh=mesh, **kw)

    def equal(a, b):
        return bool(np.array_equal(np.asarray(jax.device_get(a)),
                                   np.asarray(jax.device_get(b))))

    def route_equal(mesh, state, q, budgets, **kw):
        want = STATE.route_batch_choices(state, q, budgets, costs, **kw)
        got = sharded_route(mesh, state, q, budgets, **kw)
        return equal(want.choices, got.choices) and \
            equal(want.topk_idx, got.topk_idx)

    # -- main matrix: meshes x modes x backends --------------------------
    db = VectorDB(D, capacity=CAP, records_per_query=RCAP)
    emb = _fill(db, 70, rng)
    state = STATE.state_from_buffer(db, ratings)
    q = rng.normal(size=(8, D)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    q[0], q[1] = emb[31], emb[63]     # land exactly on duplicated rows
    budgets = np.array([0.5, 1.0, 2.0, 4.0, 8.0, 3.0, 8.0, 2.0],
                       np.float32)    # infeasible -> full feasibility
    report["equiv"] = {
        str(s): {f"{mode}/{bk}": route_equal(meshes[s], state, q,
                                             budgets, mode=mode,
                                             backend=bk)
                 for mode in MODES for bk in BACKENDS}
        for s in MESHES}

    # -- tie stress: empty DB + flat ratings (budget-selector ties) ------
    db_e = VectorDB(D, capacity=CAP, records_per_query=RCAP)
    flat = np.full(M, 1500.0, np.float32)
    state_e = STATE.state_from_buffer(db_e, flat)
    report["ties"] = {
        str(s): {mode: route_equal(meshes[s], state_e, q, budgets,
                                   mode=mode)
                 for mode in ("combined", "local")}
        for s in MESHES}

    # -- incremental sharded commit vs oracle commit ---------------------
    report["commit"] = {}
    for s in MESHES:
        mesh = meshes[s]
        db2 = VectorDB(D, capacity=CAP, records_per_query=RCAP)
        db2.register_consumer("oracle")
        db2.register_consumer("mesh")
        rng2 = np.random.default_rng(100 + s)
        _fill(db2, 40, rng2)
        st_o = STATE.commit(db2, ratings, None, consumer="oracle")
        st_s = STATE.commit(db2, ratings, None, consumer="mesh",
                            mesh=mesh)
        # touch NEW rows and EXISTING rows (both sides of the ledger)
        e2 = rng2.normal(size=(12, D)).astype(np.float32)
        for i in range(12):
            db2.add(e2[i], [i % M], [(i + 1) % M], [1.0],
                    query_id=[40 + i])
        for row in (0, 17, 39):
            db2.add(db2.emb[row], [0], [1], [0.0], query_id=[row])
        st_o = STATE.commit(db2, ratings, st_o, consumer="oracle")
        st_s = STATE.commit(db2, ratings, st_s, consumer="mesh",
                            mesh=mesh)
        fields = {f: equal(getattr(st_o, f), getattr(st_s, f))
                  for f in ("global_ratings", "emb", "model_a",
                            "model_b", "outcome", "valid", "size")}
        want = STATE.route_batch_choices(st_o, q, budgets, costs)
        got = STATE.route_batch_choices_sharded(
            st_s, rep(mesh, q), rep(mesh, budgets), rep(mesh, costs),
            mesh=mesh)
        fields["route"] = equal(want.choices, got.choices) and \
            equal(want.topk_idx, got.topk_idx)
        report["commit"][str(s)] = fields

    # -- steady state: zero post-warmup compiles per mesh shape ----------
    report["hot_compiles"] = {}
    for s in MESHES:
        mesh = meshes[s]
        db3 = VectorDB(D, capacity=CAP, records_per_query=RCAP)
        rng3 = np.random.default_rng(200 + s)
        _fill(db3, 70, rng3)
        next_row = 70

        def feedback():
            nonlocal next_row
            for _ in range(2):
                e = rng3.normal(size=(1, D)).astype(np.float32)
                db3.add(e, [0], [1], [1.0], query_id=[next_row])
                next_row += 1

        st = STATE.commit(db3, ratings, None, mesh=mesh)
        qd, bd, cd = rep(mesh, q), rep(mesh, budgets), rep(mesh, costs)
        for _ in range(2):   # warmup MUST include real feedback+commit
            STATE.route_batch_choices_sharded(
                st, qd, bd, cd, mesh=mesh).choices.block_until_ready()
            feedback()
            st = STATE.commit(db3, ratings, st, mesh=mesh)
        with CompileCounter() as cc:
            for _ in range(6):
                STATE.route_batch_choices_sharded(
                    st, qd, bd, cd, mesh=mesh).choices.block_until_ready()
                feedback()
                st = STATE.commit(db3, ratings, st, mesh=mesh)
            jax.block_until_ready(st)
        report["hot_compiles"][str(s)] = cc.count

    # -- named device scopes in the 4-shard programs ---------------------
    mesh = meshes[4]
    sstate = STATE.shard_state(state, mesh)
    report["scopes"] = {}
    for bk in BACKENDS:
        txt = STATE.route_batch_choices_sharded.lower(
            sstate, rep(mesh, q), rep(mesh, budgets), rep(mesh, costs),
            mesh=mesh, backend=bk).as_text(debug_info=True)
        report["scopes"][bk] = sorted(
            sc for sc in ("eagle.similarity", "eagle.topk", "eagle.gather",
                          "eagle.merge", "eagle.replay") if sc in txt)
    rows = np.zeros((4, 2), np.int32)
    shr = NamedSharding(mesh, P(STATE.SHARD.DB_AXIS))
    put = lambda x: jax.device_put(x, shr)  # noqa: E731
    txt = STATE._sharded_scatter(mesh).lower(
        sstate.emb, sstate.model_a, sstate.model_b, sstate.outcome,
        sstate.valid, put(rows.reshape(-1)),
        put(np.zeros((8, D), np.float32)), put(np.zeros((8, RCAP), np.int32)),
        put(np.zeros((8, RCAP), np.int32)),
        put(np.zeros((8, RCAP), np.float32)),
        put(np.zeros((8, RCAP), bool))).as_text(debug_info=True)
    report["scopes"]["commit"] = "eagle.commit_scatter" in txt

    # -- wide DB: the two-stage top-k on every shard ----------------------
    from repro import obs as OBS
    from repro.core.dispatch import RouteDispatcher
    from repro.kernels.similarity_topk import two_stage_topk

    def counted(mesh, st, backend, queries, rounds=3):
        """The last of `rounds` dispatches, and the dispatcher's
        (dispatch count, two-stage count)."""
        d = RouteDispatcher(costs, backend=backend, mesh=mesh,
                            obs=OBS.Observability())
        out = [d.route_result(st, queries, budgets) for _ in range(rounds)]
        reg = d.obs.registry
        return out[-1], (reg.counter("dispatch_calls_total").value,
                         reg.counter("dispatch_two_stage_topk_total").value)

    rw = np.random.default_rng(300)
    db_w = VectorDB(D, capacity=WIDE_CAP, records_per_query=RCAP)
    emb_w = rw.normal(size=(WIDE_LIVE, D)).astype(np.float32)
    # ties: every shard's first rows repeat the previous shard's last
    for lo in range(1 << 14, WIDE_LIVE, 1 << 14):
        emb_w[lo:lo + 3] = emb_w[lo - 3:lo]
    rec = rw.integers(0, M, (WIDE_LIVE, RCAP)).astype(np.int32)
    db_w.add_rows(emb_w, rec, (rec + 1) % M,
                  rw.random((WIDE_LIVE, RCAP)).astype(np.float32).round(),
                  rw.integers(1, RCAP, WIDE_LIVE))
    state_w = STATE.state_from_buffer(db_w, ratings)
    q_w = q.copy()
    q_w[:2] = emb_w[(1 << 14) - 1], emb_w[(1 << 15) - 2]
    report["wide"] = {"two_stage_per_shard": {
        str(s): two_stage_topk(WIDE_CAP // s, 20) for s in MESHES}}
    for bk in BACKENDS:
        want = STATE.route_batch_choices(state_w, q_w, budgets, costs,
                                         backend=bk)
        for s in MESHES:
            (ch, topk), calls = counted(
                meshes[s], STATE.shard_state(state_w, meshes[s]), bk, q_w)
            report["wide"][f"{s}/{bk}"] = {
                "equal": bool(np.array_equal(ch, np.asarray(want.choices))
                              and np.array_equal(
                                  topk, np.asarray(want.topk_idx))),
                "calls": calls[0], "two_stage": calls[1]}
    _, calls = counted(meshes[4], STATE.shard_state(state, meshes[4]),
                       "reference", q)
    report["wide"]["narrow"] = {"calls": calls[0], "two_stage": calls[1]}

    # -- seeded property-style table (replayed via the shim) -------------
    report["seeded"] = {}
    for seed in range(8):
        r = np.random.default_rng(1000 + seed)
        nq = int(r.integers(1, 9))
        qq = r.normal(size=(nq, D)).astype(np.float32)
        qq /= np.linalg.norm(qq, axis=1, keepdims=True)
        bb = r.uniform(0.0, 10.0, nq).astype(np.float32)
        report["seeded"][str(seed)] = all(
            route_equal(meshes[s], state, qq, bb) for s in (2, 4))

    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
