"""Eagle router: Global + Local ELO, budget-constrained selection.

Implements the full workflow of Fig. 1 / §2.2 of the paper:

  1. a query arrives with its prompt embedding;
  2. Eagle-Local retrieves the N most similar historical queries from the
     vector DB (cosine similarity) and replays their pairwise feedback
     through ELO, starting from the global ratings;
  3. Eagle-Global is the standing rating vector over all history;
  4. Score(X) = P * Global(X) + (1-P) * Local(X);
  5. the highest-scoring model with cost <= budget is selected;
  6. (optional) a second model is sampled for comparison and the user's
     preference is appended to the DB + folded into Global — the
     training-free online update.

EagleRouter is a thin stateful shell over the functional core in
core/state.py: writes (fit/update/feedback) land in the host append
buffer + global ratings and lazily commit into a device-resident
RouterState; reads (scores/rank/route) are single jitted dispatches of
route_batch over that state.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as OBS
from repro.core import elo
from repro.core.state import (RouterState, RouteResult, combine_scores,
                              commit, route_batch, select_within_budget)
from repro.core.vectordb import VectorDB

__all__ = ["EagleConfig", "EagleRouter", "GlobalOnlyRouter",
           "LocalOnlyRouter", "combine_scores", "select_within_budget"]


@dataclasses.dataclass(frozen=True)
class EagleConfig:
    """Paper Appendix A.1 parameters."""
    p_global: float = 0.5   # P: weight of the global score
    n_neighbors: int = 20   # N: local retrieval size
    k_factor: float = 32.0  # K: ELO sensitivity
    init_rating: float = elo.DEFAULT_RATING
    embed_dim: int = 256
    backend: str = "reference"  # similarity/replay kernel backend


class EagleRouter:
    """Online router over a fleet of models."""

    #: route_batch scoring mode; the Appendix B ablation subclasses
    #: override this (see core.state.MODES).
    mode = "combined"

    #: telemetry scope; None -> the module default (repro.obs.DEFAULT).
    #: ServingEngine points this at its own scope.
    obs: Optional["OBS.Observability"] = None

    #: optional router-quality monitor (obs/quality.py): when attached,
    #: every feedback fold feeds it the comparison outcomes and the
    #: post-fold rating vector (trajectories + drift detection).
    quality = None

    def __init__(self, model_names: Sequence[str], costs,
                 cfg: EagleConfig = EagleConfig(), db_capacity: int = 4096):
        self.cfg = cfg
        self.model_names = list(model_names)
        self.n_models = len(model_names)
        self.costs = jnp.asarray(costs, jnp.float32)
        assert self.costs.shape == (self.n_models,)
        self.global_ratings = jnp.full((self.n_models,), cfg.init_rating,
                                       jnp.float32)
        self.db = VectorDB(cfg.embed_dim, db_capacity, backend=cfg.backend)
        self._state: Optional[RouterState] = None
        self._stale = True

    # -- device state --------------------------------------------------------
    @property
    def state(self) -> RouterState:
        """Device-resident snapshot of the router; recommitted lazily
        after writes (incremental: only dirty DB rows are uploaded).

        The snapshot is only valid until the next write (fit/update/
        feedback): the following commit DONATES its buffers. Re-read
        this property after every write instead of holding a reference
        across writes — on accelerator backends a held reference raises
        a deleted-buffer error."""
        if self._stale or self._state is None:
            self._state = commit(self.db, self.global_ratings, self._state)
            self._stale = False
        return self._state

    def _kw(self) -> Dict:
        c = self.cfg
        return dict(p_global=c.p_global, n_neighbors=c.n_neighbors,
                    k=c.k_factor, backend=c.backend, mode=self.mode,
                    init_rating=c.init_rating)

    # -- state building ------------------------------------------------------
    def fit(self, embeddings, model_a, model_b, outcome,
            query_id=None) -> float:
        """Initialize from a feedback history. Returns wall seconds (the
        paper's Table 3a 'training time' measurement)."""
        t0 = time.perf_counter()
        self.db.add(embeddings, model_a, model_b, outcome, query_id)
        self.global_ratings = elo.fit_global(
            self.n_models, model_a, model_b, outcome,
            k=self.cfg.k_factor, init=self.cfg.init_rating)
        self.global_ratings.block_until_ready()
        self._stale = True
        return time.perf_counter() - t0

    def update(self, embeddings, model_a, model_b, outcome,
               query_id=None) -> float:
        """Incremental online update: O(new records), no retraining.
        Spans: `router.feedback.add` (the DB append) and
        `router.feedback.fold` (the global fold, to its completion)."""
        o = OBS.get_obs(self.obs)
        t0 = time.perf_counter()
        with o.span("router.feedback.add"):
            self.db.add(embeddings, model_a, model_b, outcome, query_id)
        with o.span("router.feedback.fold"):
            self.global_ratings = elo.update_global(
                self.global_ratings, model_a, model_b, outcome,
                k=self.cfg.k_factor)
            self.global_ratings.block_until_ready()
        self._stale = True
        return time.perf_counter() - t0

    # -- scoring (single-dispatch reads over the committed state) ------------
    def scores(self, query_emb) -> jnp.ndarray:
        """(Q, M) combined quality scores (higher = better predicted)."""
        return route_batch(self.state, query_emb, jnp.inf, self.costs,
                           **self._kw()).scores

    def rank(self, query_emb) -> jnp.ndarray:
        """(Q, M) model indices, best first."""
        return jnp.argsort(-self.scores(query_emb), axis=-1)

    def route_result(self, query_emb, budget) -> RouteResult:
        """Full fused routing step: (choices, scores, topk_idx)."""
        return route_batch(self.state, query_emb, budget, self.costs,
                           **self._kw())

    def route(self, query_emb, budget) -> jnp.ndarray:
        """(Q,) selected model index per query under the budget."""
        return self.route_result(query_emb, budget).choices

    def local_ratings(self, query_emb) -> jnp.ndarray:
        """(Q, M) Eagle-Local ratings (replay from the global prior)."""
        from repro.kernels import ops as KOPS
        s = self.state
        q = jnp.atleast_2d(jnp.asarray(query_emb, jnp.float32))
        local, _, _, _ = KOPS.retrieve_replay_select(
            q, s.emb, s.model_a, s.model_b, s.outcome, s.valid, s.size,
            s.global_ratings, s.global_ratings, self.costs,
            jnp.full((q.shape[0],), jnp.inf, jnp.float32),
            n=min(self.cfg.n_neighbors, s.capacity), k=self.cfg.k_factor,
            backend=self.cfg.backend)
        return local

    # -- feedback loop (workflow step 5) ------------------------------------
    def feedback(self, query_emb, chosen, opponent, outcome):
        """Record a user comparison between two served responses.

        Instrumented: the ELO update magnitude (max |Δrating| of the
        global fold — how much this comparison actually moved the
        router) lands in a histogram, and the batch size in a counter.
        The magnitude math is host numpy on already-synced ratings, so
        the steady-state zero-compile guarantee is untouched."""
        o = OBS.get_obs(self.obs)
        before = np.asarray(self.global_ratings) if o.enabled else None
        with o.span("router.feedback"):
            dt = self.update(query_emb, chosen, opponent, outcome)
        n = np.asarray(chosen).reshape(-1).size
        o.registry.counter("router_feedback_total",
                           "pairwise comparisons folded online").inc(n)
        if before is not None:
            after = np.asarray(self.global_ratings)
            mag = float(np.max(np.abs(after - before)))
            o.registry.histogram(
                "router_elo_update_magnitude",
                "max |delta global rating| per feedback fold",
                bounds=OBS.geometric_bounds(1e-3, 100.0, 1.5)).observe(mag)
            if self.quality is not None:
                # the quality monitor rides the SAME host readout the
                # magnitude metric already paid for: win-rate
                # accounting plus the post-fold rating trajectory /
                # drift detection (obs/quality.py)
                self.quality.observe_feedback(chosen, opponent, outcome,
                                              ratings=after)
        return dt


# ---------------------------------------------------------------------------
# Ablation variants (paper Appendix B)
# ---------------------------------------------------------------------------

class GlobalOnlyRouter(EagleRouter):
    """Eagle-Global: ignores the local module (P=1, retrieval skipped)."""
    mode = "global"


class LocalOnlyRouter(EagleRouter):
    """Eagle-Local only: local replay from a FLAT prior (no global info)."""
    mode = "local"
