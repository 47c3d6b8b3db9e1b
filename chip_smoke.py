"""On-chip smoke run of the routed serving path.

  python3 chip_smoke.py [--seed N]
  python3 chip_smoke.py --chips 4 [--seed N]

With no option it needs one TPU chip. It builds the paper's router
(PAPER_CONFIG: D=1536, N=20, K=32, P=0.5) on the compiled Pallas
kernels over a 2^18-row feedback DB made from --seed, in front of a
fleet whose cheapest member is OLMo-1B at its published width (the
other members are the reduced configs launch/serve.py builds). After
a warm-up it serves a few batches through ServingEngine.serve():
route dispatch, generate, feedback, commit. It checks that every
request gets max_new tokens, that OLMo-1B answered, and that the
Pallas choices and top-k rows agree with the reference backend on the
same state under the contract of DESIGN.md §13.

--chips 4 runs only the capacity-sharded phase (DESIGN.md §12): a
2^20-row DB on a 4-device mesh, routed through RouteDispatcher(mesh=),
one owner-scatter commit, routed again. Choices and topk_idx must equal
single-device route_batch_choices on chip 0, bitwise, on the same state.

Without a TPU, or outside a checkout of this repository, it exits
non-zero and prints no result. Otherwise the last line of stdout is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import zlib
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: DESIGN.md §13: position-wise cosine gap under which two top-k rows
#: count as a near-tie, and combined-score gap (rating points) under
#: which two choices do
TOPK_TOL = 1e-5
CHOICE_TOL = 1e-2


def log(msg: str):
    print(msg, flush=True)


def _oracle(emb, mi) -> float:
    """Simulated answer quality, deterministic in (prompt, model)."""
    return float(np.random.default_rng(
        [zlib.crc32(np.asarray(emb, np.float32).tobytes()), int(mi)]
    ).random())


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# pallas vs reference agreement (DESIGN.md §13)
# ---------------------------------------------------------------------------

def agreement(state, db_emb, q, budgets, costs, kw, backend):
    """Route one batch on `backend` and on the reference backend over
    the same state; classify every row. Returns counts: rows whose
    top-k rows are identical, near-tie top-k rows, equal choices,
    near-tie choices, and unexplained disagreements (must be 0)."""
    from repro.core.state import route_batch, route_batch_choices
    pal = route_batch_choices(state, q, budgets, costs,
                              **dict(kw, backend=backend))
    ref = route_batch(state, q, budgets, costs,
                      **dict(kw, backend="reference"))
    p_top, r_top = np.asarray(pal.topk_idx), np.asarray(ref.topk_idx)
    p_ch, r_ch = np.asarray(pal.choices), np.asarray(ref.choices)
    scores = np.asarray(ref.scores, np.float64)
    costs = np.asarray(costs)
    qn = q / np.linalg.norm(q.astype(np.float64), axis=1, keepdims=True)
    out = dict(rows=len(q), topk_equal=0, topk_near_tie=0,
               choices_equal=int((p_ch == r_ch).sum()), choice_near_tie=0,
               unexplained=0)
    for i in range(len(q)):
        if (p_top[i] == r_top[i]).all():
            out["topk_equal"] += 1
        else:
            # position-wise cosine of both retrieved sequences, in f64
            gap = np.abs(db_emb[p_top[i]].astype(np.float64) @ qn[i]
                         - db_emb[r_top[i]].astype(np.float64) @ qn[i])
            if gap.max() <= TOPK_TOL:
                out["topk_near_tie"] += 1
            else:
                out["unexplained"] += 1
            continue   # other neighbours may rightly change the choice
        if p_ch[i] == r_ch[i]:
            continue
        feasible = costs[p_ch[i]] <= budgets[i]
        if feasible and scores[i, p_ch[i]] >= scores[i, r_ch[i]] - CHOICE_TOL:
            out["choice_near_tie"] += 1
        else:
            out["unexplained"] += 1
    return out


# ---------------------------------------------------------------------------
# one chip: the served path
# ---------------------------------------------------------------------------

def serve_phase(seed: int = 0, *, dim: int = 1536, db_rows: int = 1 << 18,
                n_fleet: int = 4, full_width=("olmo-1b",),
                batch: int = 16, batches: int = 3, prompt_len: int = 16,
                max_new: int = 4, agree_q: int = 256,
                backend: str = "pallas") -> dict:
    """Build, warm up and serve; returns a report whose "ok" says
    whether every check passed. The defaults are the chip run; tests
    call it at a tiny size with backend="pallas_interpret"."""
    from repro.configs import ARCH_IDS, get_config, get_reduced_config
    from repro.configs.eagle import PAPER_CONFIG
    from repro.core.dispatch import CompileCounter
    from repro.core.router import EagleRouter
    from repro.data.routerbench import (bulk_history, make_corpus,
                                        pairwise_feedback)
    from repro.serving.engine import FleetModel, Request, ServingEngine

    rep = {}
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    # OLMo-1B first so it is the cheapest member: requests whose budget
    # is below every cost fall back to it (core.state.select_within_budget)
    names = ["olmo-1b"] + [a for a in ARCH_IDS if a != "olmo-1b"][:n_fleet - 1]
    costs = np.linspace(1.0, 8.0, n_fleet).astype(np.float32)
    corpus = make_corpus(seed=seed, n_per_dataset=60, dim=dim,
                         model_names=names, costs=costs)
    fb = pairwise_feedback(corpus, corpus.train_idx, seed=seed,
                           pairs_per_query=4)
    cfg = dataclasses.replace(PAPER_CONFIG, embed_dim=dim, backend=backend)
    router = EagleRouter(names, costs, cfg, db_capacity=db_rows)
    router.fit(fb["emb"], fb["model_a"], fb["model_b"], fb["outcome"],
               query_id=fb["query_idx"])
    # leave room for the prompts feedback adds: a full DB would regrow
    # (a new shape, so every executable would compile again)
    router.db.add_rows(*bulk_history(rng, db_rows - 1024 - router.db.size,
                                     dim, n_fleet, router.db.rcap))
    fleet = {}
    for i, n in enumerate(names):
        full = n in full_width
        fleet[n] = FleetModel(get_config(n) if full
                              else get_reduced_config(n),
                              seed=i, max_len=128 if full else 64)
    engine = ServingEngine(fleet, router, compare_rate=0.5, seed=seed,
                           quality_oracle=_oracle, gen_bucket=True,
                           gen_min_bucket=batch, gen_max_bucket=batch,
                           gen_pad_len=prompt_len)
    rep["setup_s"] = time.perf_counter() - t0
    log(f"setup: {rep['setup_s']:.2f} s (DB {router.db.size} rows x "
        f"D={dim}, fleet {names}, full width {list(full_width)})")

    test = corpus.test_idx

    def requests(k: int):
        out = []
        for j in range(batch):
            i = test[(k * batch + j) % len(test)]
            # the first rows of every batch can afford no model
            budget = 0.5 if j < 3 else float(rng.uniform(1.0, 8.5))
            n_tok = int(rng.integers(4, prompt_len + 1))
            out.append(Request(
                tokens=rng.integers(0, 100, n_tok).astype(np.int32),
                embedding=corpus.embeddings[i], budget=budget,
                max_new_tokens=max_new, rid=k * batch + j))
        return out

    t0 = time.perf_counter()
    rep["route_executables"] = engine.warmup([batch])
    engine.warmup_generate(prompt_len, [batch], max_new=max_new)
    # one served batch compiles the feedback fold and the commit scatter
    engine.serve(requests(0))
    rep["warmup_compile_s"] = time.perf_counter() - t0
    log(f"warmup + compile: {rep['warmup_compile_s']:.2f} s "
        f"({rep['route_executables']} route executables)")

    before = dict(engine.stats)
    t0 = time.perf_counter()
    responses = []
    with CompileCounter() as cc:
        for k in range(1, batches + 1):
            responses += engine.serve(requests(k))
    rep["serve_s"] = time.perf_counter() - t0
    rep["post_warmup_compiles"] = cc.count
    stats = engine.stats
    rep["served"] = stats["served"] - before["served"]
    rep["feedback"] = stats["feedback"] - before["feedback"]
    rep["commits"] = stats["commits"] - before["commits"]
    rep["per_model"] = {m: stats["per_model"][m] - before["per_model"][m]
                        for m in names}
    answered = [r for r in responses
                if r is not None and r.tokens.shape == (max_new,)]
    rep["answered"] = len(answered)
    log(f"serve: {batches} batches x {batch} requests in "
        f"{rep['serve_s']:.2f} s; answered {rep['answered']}/"
        f"{batches * batch}; per model {rep['per_model']}; feedback "
        f"{rep['feedback']}, commits {rep['commits']}")
    log(f"post-warmup compiles: {rep['post_warmup_compiles']}")

    # same front state, both backends, paper-size batch
    idx = test[np.arange(agree_q) % len(test)]
    q = corpus.embeddings[idx]
    budgets = rng.uniform(0.5, 8.5, agree_q).astype(np.float32)
    state = engine.dbuf.front
    rep["agreement"] = agreement(state, router.db.emb[:router.db.size], q,
                                 budgets, router.costs, router._kw(),
                                 backend)
    log(f"agreement ({backend} vs reference): {rep['agreement']}")

    checks = {
        "every request answered": rep["answered"] == batches * batch,
        "olmo-1b answered": rep["per_model"]["olmo-1b"] > 0,
        "feedback folded and committed": rep["feedback"] > 0
        and rep["commits"] > 0,
        "agreement contract": rep["agreement"]["unexplained"] == 0,
    }
    rep["failed"] = [k for k, ok in checks.items() if not ok]
    rep["ok"] = not rep["failed"]
    return rep


# ---------------------------------------------------------------------------
# four chips: the capacity-sharded DB
# ---------------------------------------------------------------------------

def sharded_phase(seed: int = 0, *, n_shards: int = 4, dim: int = 1536,
                  db_rows: int = 1 << 20, n_models: int = 10,
                  batch: int = 256, batches: int = 2,
                  backend: str = "pallas") -> dict:
    """Route, owner-scatter commit and route again on an n_shards DB
    mesh; every batch is compared bitwise with the single-device route
    on the default device over the same host buffer."""
    import jax
    from repro import sharding as SHARD
    from repro.configs.eagle import PAPER_CONFIG
    from repro.core import elo
    from repro.core.dispatch import CompileCounter, RouteDispatcher
    from repro.core.state import (commit, route_batch_choices,
                                  state_from_buffer)
    from repro.core.vectordb import VectorDB
    from repro.data.routerbench import bulk_history
    from repro.launch.mesh import make_db_mesh

    rep = {}
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    mesh = make_db_mesh(n_shards)
    costs = np.linspace(1.0, 8.0, n_models).astype(np.float32)
    db = VectorDB(dim, db_rows)
    n_fill = db_rows - 1024      # room for new prompts without a regrow
    emb, a, b, o, n_rec = bulk_history(rng, n_fill, dim, n_models, db.rcap)
    db.add_rows(emb, a, b, o, n_rec, query_id=np.arange(n_fill))
    del emb
    g = elo.fit_global(n_models, a[:4096, 0], b[:4096, 0], o[:4096, 0])
    state = commit(db, g, None, consumer="shards", mesh=mesh)
    c = PAPER_CONFIG
    kw = dict(p_global=c.p_global, n_neighbors=c.n_neighbors,
              k=c.k_factor, backend=backend, mode="combined",
              init_rating=c.init_rating)
    dispatch = RouteDispatcher(costs, mesh=mesh, **kw)
    rep["setup_s"] = time.perf_counter() - t0
    log(f"setup: {rep['setup_s']:.2f} s (DB {db.size} rows x D={dim} "
        f"over {n_shards} shards)")
    t0 = time.perf_counter()
    dispatch.warmup(state, [batch])
    rep["warmup_compile_s"] = time.perf_counter() - t0
    log(f"warmup + compile: {rep['warmup_compile_s']:.2f} s")

    def compare(tag):
        oracle = state_from_buffer(db, g)   # whole, on the default device
        rows = bad_c = bad_t = 0
        with CompileCounter() as cc:
            for _ in range(batches):
                q = rng.standard_normal((batch, dim), dtype=np.float32)
                bud = rng.uniform(0.5, 8.5, batch).astype(np.float32)
                got_c, got_t = dispatch.route_result(state, q, bud)
                want = route_batch_choices(oracle, q, bud, costs, **kw)
                rows += batch
                bad_c += int((got_c != np.asarray(want.choices)).sum())
                bad_t += int((got_t != np.asarray(want.topk_idx))
                             .any(axis=1).sum())
        del oracle
        rep[tag] = dict(rows=rows, choice_mismatch=bad_c,
                        topk_row_mismatch=bad_t)
        log(f"{tag}: {rep[tag]} (compiles incl. oracle: {cc.count})")

    compare("before_commit")
    # feedback on stored prompts spread over every shard, plus new ones
    touched = rng.choice(n_fill, 512, replace=False)
    new = rng.standard_normal((64, dim), dtype=np.float32)
    fa = rng.integers(0, n_models, 576).astype(np.int32)
    fb = ((fa + 1) % n_models).astype(np.int32)
    fo = rng.choice(np.asarray([0.0, 0.5, 1.0], np.float32), 576)
    db.add(db.emb[touched], fa[:512], fb[:512], fo[:512], query_id=touched)
    db.add(new, fa[512:], fb[512:], fo[512:])
    g = elo.update_global(g, fa, fb, fo)
    c_local = db_rows // n_shards
    dirty = np.concatenate([touched, np.arange(n_fill, n_fill + 64)])
    rep["dirty_rows_per_shard"] = np.bincount(
        dirty // c_local, minlength=n_shards).tolist()
    state = commit(db, g, state, consumer="shards", mesh=mesh)
    jax.block_until_ready(state)
    log(f"owner-scatter commit: dirty rows per shard "
        f"{rep['dirty_rows_per_shard']}")
    compare("after_commit")
    rep["shards"] = SHARD.db_shard_count(mesh)
    rep["failed"] = [t for t in ("before_commit", "after_commit")
                     if rep[t]["choice_mismatch"]
                     or rep[t]["topk_row_mismatch"]]
    if min(rep["dirty_rows_per_shard"]) == 0:
        rep["failed"].append("commit touched every shard")
    rep["ok"] = not rep["failed"]
    return rep


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the capacity-sharded phase")
    args = ap.parse_args(argv)

    import jax
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: this is not a checkout of the repository "
              f"({e})", file=sys.stderr)
        return 2
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 1
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {device}")
    log(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        rep = sharded_phase(args.seed)
    else:
        rep = serve_phase(args.seed)
    rep["peak_bytes_in_use"] = _peak_bytes()
    log(f"peak_bytes_in_use (device 0): {rep['peak_bytes_in_use']}")
    log("report: " + json.dumps(rep, default=str))
    if not rep["ok"]:
        print(f"chip_smoke: FAILED {rep['failed']}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
