"""The router half of a cell: builds the program's router over the
generated history, logs what the run folds, checks the committed device
state, and judges sampled routing decisions against the plain reference.

Shared by the route cells and the served cells.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import router_ref as REF
from bench.lib.history import FeedbackLog, build_history
from bench.lib.tracing import phase

#: rows the DB-state check reads back besides every row the window added
STATE_SAMPLE_ROWS = 2048
#: the +1e-9 of VectorDB's normalization, so the state check compares
#: like with like (a difference of 1e-9 relative is far under any limit)
_NORM_EPS = 1e-9


@dataclasses.dataclass
class RouteSample:
    """One routing decision batch taken from the timed path, with the
    state it was routed against (the DB's live rows and the number of
    comparisons folded into the global ratings by then)."""
    queries: np.ndarray
    budgets: np.ndarray
    choices: np.ndarray
    topk: Optional[np.ndarray]
    size: int
    folds: int


class RouterSide:
    def __init__(self, cfg: dict, seed: int, n_queries: int, noise: float,
                 log):
        self.cfg = cfg
        self.log = log
        r, db, fleet = cfg["router"], cfg["db"], cfg["fleet"]
        self.dim = r["embed_dim"]
        self.n_models = len(fleet["names"])
        self.costs = np.asarray(fleet["costs"], np.float32)
        self.capacity = db["capacity"]
        self.shards = db.get("shards", 1)
        self.records = db["records_per_prompt"]
        n_hist = self.capacity - db["headroom"]
        with phase(log, "history made and copied to the host"):
            self.hist, self.queries = build_history(
                seed, rows=n_hist, dim=self.dim, n_models=self.n_models,
                records=self.records, fit_rows=db["fit_prompts"],
                n_queries=n_queries, noise=noise, shards=self.shards,
                capacity=self.capacity)
        with phase(log, "router fitted and DB filled"):
            self._fill(cfg)
        self.fb = FeedbackLog()
        self.fold_sizes: List[int] = []     # comparisons per feedback call
        self._spy_feedback()

    def _fill(self, cfg):
        from repro.core.router import EagleConfig, EagleRouter
        r, fleet = cfg["router"], cfg["fleet"]
        self.router = EagleRouter(
            fleet["names"], self.costs,
            EagleConfig(p_global=r["p_global"], n_neighbors=r["n_neighbors"],
                        k_factor=r["k_factor"], init_rating=r["init_rating"],
                        embed_dim=self.dim, backend=r["backend"]),
            db_capacity=self.capacity)
        h = self.hist
        f = h.fit_rows
        self.router.fit(h.raw[:f], *h.fit_records(), query_id=np.arange(f))
        chunk = 1 << 16
        for lo in range(f, h.n, chunk):
            hi = min(lo + chunk, h.n)
            self.router.db.add_rows(h.raw[lo:hi], h.a[lo:hi], h.b[lo:hi],
                                    h.o[lo:hi], h.n_rec[lo:hi])

    # -- what the run folds ---------------------------------------------------
    def _spy_feedback(self):
        """Log every comparison the program folds, whoever calls
        EagleRouter.feedback (the route loop or ServingEngine.serve)."""
        router = self.router
        original = router.feedback

        def feedback(query_emb, chosen, opponent, outcome):
            self.fb.add(query_emb, chosen, opponent, outcome)
            self.fold_sizes.append(int(np.size(chosen)))
            return original(query_emb, chosen, opponent, outcome)

        router.feedback = feedback

    def mesh(self):
        if self.shards == 1:
            return None
        from repro.launch.mesh import make_db_mesh
        return make_db_mesh(self.shards)

    # -- the reference's view of the DB ------------------------------------------
    def _row_emb(self, fb_emb):
        """Raw embedding rows by DB row: history rows, then the run's
        feedback rows."""
        n = self.hist.n
        raw = self.hist.raw

        def rows(idx):
            idx = np.asarray(idx)
            out = np.empty((len(idx), self.dim), np.float32)
            old = idx < n
            out[old] = raw[idx[old]]
            if (~old).any():
                out[~old] = fb_emb[idx[~old] - n]
            return out
        return rows

    def _row_records(self, rows, fb):
        """(a, b, o, valid) (K, R) of reference rows."""
        fb_emb, fa, fbb, fo = fb
        rows = np.asarray(rows)
        n, r = self.hist.n, self.records
        a = np.zeros((len(rows), r), np.int32)
        b = np.zeros_like(a)
        o = np.zeros((len(rows), r), np.float32)
        v = np.zeros((len(rows), r), bool)
        old = rows < n
        if old.any():
            a[old], b[old], o[old], v[old] = self.hist.live_records(rows[old])
        new = ~old
        if new.any():
            j = rows[new] - n
            a[new, 0], b[new, 0], o[new, 0] = fa[j], fbb[j], fo[j]
            v[new, 0] = True
        return a, b, o, v

    # -- post-window checks ----------------------------------------------------
    def read_state(self, state, fb_start: int):
        """Read back from the program's front state every row the
        window added and a sample of the others (before it is freed)."""
        n_live = self.hist.n + self.fb.count
        rng = np.random.default_rng(self.hist.key_seed)
        sample = rng.choice(self.hist.n, min(STATE_SAMPLE_ROWS, self.hist.n),
                            replace=False)
        rows = np.unique(np.concatenate(
            [sample, np.arange(self.hist.n + fb_start, n_live)])).astype(
                np.int32)
        # pad to a power of two so the gather compiles once per bucket
        bucket = 1 << max(0, int(len(rows) - 1).bit_length())
        idx = jnp.asarray(np.pad(rows, (0, bucket - len(rows)), mode="edge"))
        got = [np.asarray(jnp.take(x, idx, axis=0))[:len(rows)] for x in
               (state.emb, state.model_a, state.model_b, state.outcome,
                state.valid)]
        return rows, got, int(np.asarray(state.size))

    def free_program(self):
        self.router = None
        gc.collect()

    def numbers(self, samples: List[RouteSample], g_program, state_read,
                *, control: bool = False):
        """The compared numbers of the router half.

        With control=True the reference itself, in the lower precision,
        stands in the program's place: its own top-k, choices, folded
        ratings and stored rows are judged by the same numbers."""
        rc = self.cfg["router"]
        k, p, n_nb = rc["k_factor"], rc["p_global"], rc["n_neighbors"]
        fb = self.fb.arrays(self.dim)
        row_emb = self._row_emb(fb[0])
        m = self.n_models
        dt = REF.precision_dtype(control)

        # global ratings after each fold, in float64 (and the control's)
        t_elo = time.perf_counter()
        g0 = np.full(m, rc["init_rating"])
        fa, fbb, fo = self.hist.fit_records()
        g_ref = [REF.elo_fold(g0, fa, fbb, fo, k)]
        g_ctl = [REF.elo_fold(g0, fa, fbb, fo, k, dt)] if control else None
        lo = 0
        for sz in self.fold_sizes:
            sl = slice(lo, lo + sz)
            g_ref.append(REF.elo_fold(g_ref[-1], fb[1][sl], fb[2][sl],
                                      fb[3][sl], k))
            if control:
                g_ctl.append(REF.elo_fold(g_ctl[-1], fb[1][sl], fb[2][sl],
                                          fb[3][sl], k, dt))
            lo += sz
        folds_at = np.cumsum([0] + self.fold_sizes)

        def g_at(seq, folds):
            return seq[int(np.searchsorted(folds_at, folds))]

        out = {}
        g_final = g_ctl[-1] if control else np.asarray(g_program)
        out["ratings_gap"] = float(np.max(np.abs(
            g_final.astype(np.float64) - g_ref[-1])))
        self.log(f"phase reference ELO ({self.fb.count} folded): "
                 f"{time.perf_counter() - t_elo:.2f} s")

        # retrieval: float32 search for candidates, float64 re-rank
        t_ret = time.perf_counter()
        q = np.concatenate([s.queries for s in samples])
        sizes = np.concatenate([np.full(len(s.queries), s.size)
                                for s in samples])
        # the feedback rows padded to the headroom: one search program
        # per configuration, whatever number of rows the run added
        head = self.capacity - self.hist.n
        if len(fb[0]) > head:
            raise ValueError("the run added more rows than the headroom")
        fb_panel = np.zeros((head, self.dim), np.float32)
        fb_panel[:len(fb[0])] = fb[0]
        # searched in blocks of one shard's rows, over the cell's devices
        search = dict(block_rows=self.capacity // self.shards,
                      devices=jax.devices()[:self.shards])
        panels = [self.hist.raw, fb_panel]
        _, cand = REF.device_search(panels, q, sizes, n_nb + REF.CAND_EXTRA,
                                    **search)
        if control:
            _, got_top = REF.device_search(panels, q, sizes, n_nb,
                                           control=True, **search)
        del panels, fb_panel
        ref_rows, ref_cos = REF.exact_topk(row_emb, q, cand, n_nb)
        have_topk = all(s.topk is not None for s in samples)
        if not control and have_topk:
            got_top = np.concatenate([s.topk for s in samples])
        if control or have_topk:
            out["topk_gap"] = float(REF.topk_gaps(row_emb, q, ref_cos,
                                                  got_top, sizes).max())
        self.log(f"phase reference retrieval ({len(q)} queries): "
                 f"{time.perf_counter() - t_ret:.2f} s")

        # replay + selection over the program's rows where it gives them
        # (retrieval is judged above), else over the reference's
        rows_used = got_top if (control or have_topk) else ref_rows
        rows_used = np.where((rows_used >= 0) & (rows_used < sizes[:, None]),
                             rows_used, 0)
        gaps = []
        at = 0
        for s in samples:
            nq = len(s.queries)
            rws = rows_used[at:at + nq][:, ::-1]          # farthest first
            a, b, o, v = self._row_records(rws.reshape(-1), fb)
            a, b, o, v = (x.reshape(nq, -1) for x in (a, b, o, v))
            scores, feas = REF.replay_select(g_at(g_ref, s.folds), a, b, o, v,
                                             self.costs, s.budgets, p=p, k=k)
            if control:
                cs, cf = REF.replay_select(g_at(g_ctl, s.folds), a, b, o, v,
                                           self.costs, s.budgets, p=p, k=k,
                                           dtype=dt)
                choices = np.where(cf.any(1), np.argmax(
                    np.where(cf, cs, -np.inf), 1), int(np.argmin(self.costs)))
            else:
                choices = s.choices
            gaps.append(REF.choice_gaps(scores, feas, self.costs, choices))
            at += nq
        out["choice_gap"] = float(np.concatenate(gaps).max())

        # committed device state against the rebuilt DB
        rows, got, size = state_read
        ref_e = row_emb(rows).astype(np.float64)
        ref_e /= (np.linalg.norm(ref_e, axis=-1, keepdims=True) + _NORM_EPS)
        ra, rb, ro, rv = self._row_records(rows, fb)
        if control:
            got = [ref_e.astype(np.float32).astype(REF.BF16), ra, rb, ro, rv]
            size = self.hist.n + self.fb.count
        emb_gap = float(np.max(np.abs(np.asarray(got[0], np.float64) - ref_e)))
        rec_ok = (np.array_equal(got[1], ra) and np.array_equal(got[2], rb)
                  and np.array_equal(np.asarray(got[3], np.float32), ro)
                  and np.array_equal(got[4], rv)
                  and size == self.hist.n + self.fb.count)
        out["state_gap"] = emb_gap if rec_ok else float("inf")
        return out
