"""Serving engine: the Eagle router in front of the model fleet.

Workflow per Fig. 1 of the paper:
  ① requests arrive (prompt tokens + prompt embedding + budget)
  ②/③ Eagle ranks the fleet per request and picks the best model within
     the budget
  ④ requests are grouped per chosen model, batch-prefilled and greedily
     decoded
  ⑤ with probability `compare_rate` a second model also answers and a
     simulated user preference is appended to the DB + ELO (the online,
     training-free update)

Each fleet member is a FleetModel over any ModelConfig, at its published
widths or a reduced test size; the production-mesh versions of the same
step functions are what the dry-run lowers (launch/dryrun.py).
"""
from __future__ import annotations

import dataclasses
import math
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as OBS
from repro.core.dispatch import (CapacityPrebaker, RouteDispatcher,
                                 batch_bucket, bucket_ladder)
from repro.core.router import EagleRouter
from repro.core.state import DoubleBuffer
from repro.models import transformer as T
from repro.models.config import ModelConfig


@dataclasses.dataclass
class Request:
    tokens: np.ndarray            # (S,) int32 prompt
    embedding: np.ndarray         # (D,) prompt embedding
    budget: float
    max_new_tokens: int = 8
    rid: int = 0
    # admission metadata (serving/admission.py): stamped arrival time
    # (0 = unstamped -> the queue stamps at submit), end-to-end deadline
    # (the coalescing window flushes by min(deadline, max_wait)), and
    # priority class (higher flushes first)
    arrival_ns: int = 0
    deadline_ms: float = math.inf
    priority: int = 0


@dataclasses.dataclass
class Response:
    rid: int
    model: str
    tokens: np.ndarray
    latency_s: float


class FleetModel:
    """One servable model: jitted prefill + decode with greedy sampling."""

    #: telemetry scope; None -> the module default (repro.obs.DEFAULT).
    #: ServingEngine points this at its own scope.
    obs: Optional[OBS.Observability] = None

    def __init__(self, cfg: ModelConfig, seed: int = 0,
                 max_len: int = 128):
        self.cfg = cfg
        self.max_len = max_len
        self.params = T.init_params(cfg, jax.random.key(seed))

        # named, so the device trace's programs read jit_prefill and
        # jit_decode_step
        def prefill(p, b):
            return T.prefill(cfg, p, b, max_len, cache_dtype=jnp.float32)

        def decode_step(p, c, t, i):
            return T.decode_step(cfg, p, c, t, i)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode_step)

    def generate(self, tokens: np.ndarray, max_new: int) -> np.ndarray:
        """tokens: (B, S) -> (B, max_new) greedy continuation.

        Spans on `self.obs`, once per call: `serve.prefill.<model>` (the
        prompt's upload, prefill and its token on the host) and
        `serve.decode.<model>` (the decode loop). Each decode step is a
        ring-free marker
        `serve.decode_step.<model>` around the decode and
        `serve.readout.<model>`, the host sync of the step's token.

        A member with expert layers counts, on the device, over the
        prefill and every decode step, and the counts are read once at
        the end of `serve.decode.<model>` into `self.obs`'s registry:
        `serve_moe_assignments_total{model}` (assignments routed to the
        held experts), `serve_moe_dropped_total{model}` (of them not
        computed: 0, serving is dropless) and the gauge
        `serve_moe_max_expert_tokens{model}` (the busiest held expert's
        rows in one layer call, the largest seen)."""
        ob = OBS.get_obs(self.obs)
        name = self.cfg.name
        step_span, readout_span = (f"serve.decode_step.{name}",
                                   f"serve.readout.{name}")
        b, s = tokens.shape
        with ob.span(f"serve.prefill.{name}"):
            batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
            if self.cfg.arch_type == "encdec":
                batch["enc_embeds"] = jnp.zeros(
                    (b, self.cfg.n_audio_frames, self.cfg.d_model),
                    jnp.float32)
            if self.cfg.arch_type == "vlm":
                batch["img_embeds"] = jnp.zeros(
                    (b, self.cfg.n_image_tokens, self.cfg.d_model),
                    jnp.float32)
                s += self.cfg.n_image_tokens
            logits, cache = self._prefill(self.params, batch)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            outs = [np.asarray(tok)[:, 0]]
        with ob.span(f"serve.decode.{name}"):
            for i in range(max_new - 1):
                with ob.span(step_span, ring=False):
                    logits, cache = self._decode(self.params, cache, tok,
                                                 s + i)
                    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
                    with ob.span(readout_span, ring=False):
                        outs.append(np.asarray(tok)[:, 0])
            if "moe_stats" in cache:
                self._count_experts(ob, np.asarray(cache["moe_stats"]))
        return np.stack(outs, axis=1)

    def _count_experts(self, ob, stats):
        r, name = ob.registry, self.cfg.name
        r.counter("serve_moe_assignments_total",
                  "assignments routed to held experts",
                  model=name).inc(int(stats[0]))
        r.counter("serve_moe_dropped_total",
                  "assignments to held experts not computed",
                  model=name).inc(int(stats[1]))
        top = r.gauge("serve_moe_max_expert_tokens",
                      "busiest held expert's rows in one layer call",
                      model=name)
        top.set(max(top.value, int(stats[2])))


class ServingEngine:
    """Steady-state serving loop: routing runs through the bucketed
    dispatch cache (core/dispatch.py) over a double-buffered
    RouterState, so at steady state a serve() step triggers zero XLA
    compilations and feedback commits never stall in-flight routing."""

    def __init__(self, fleet: Dict[str, FleetModel], router: EagleRouter,
                 compare_rate: float = 0.2, seed: int = 0,
                 quality_oracle: Optional[Callable] = None,
                 dispatcher: Optional[RouteDispatcher] = None,
                 warmup_batch_sizes: Optional[Sequence[int]] = None,
                 obs: Optional[OBS.Observability] = None,
                 gen_bucket: bool = False, gen_min_bucket: int = 1,
                 gen_max_bucket: int = 64,
                 gen_pad_len: Optional[int] = None,
                 quality: Optional["RouterQualityMonitor"] = None,
                 now_ns: Callable[[], int] = time.time_ns,
                 mesh=None, prebake: bool = False):
        assert list(fleet) == router.model_names, "fleet/router order mismatch"
        self.fleet = fleet
        self.router = router
        self.compare_rate = compare_rate
        # generation-shape bucketing: pad each per-model group's rows to
        # the power-of-two ladder (padded rows are independent in the
        # batch dim, so real rows are untouched) and optionally floor
        # the token panel length, so prefill/decode executables come
        # from a finite shape universe warmup_generate() can pre-bake
        self.gen_bucket = gen_bucket
        self.gen_min_bucket = gen_min_bucket
        self.gen_max_bucket = gen_max_bucket
        self.gen_pad_len = gen_pad_len
        self.rng = np.random.default_rng(seed)
        self.quality_oracle = quality_oracle  # (emb, model_idx) -> quality
        # one telemetry scope threads through every layer the engine
        # owns: dispatcher spans/metrics, double-buffer commit stats,
        # router feedback magnitude, the fleet's prefill/decode spans,
        # and the engine's own serve spans
        self.obs = OBS.get_obs(obs)
        router.obs = self.obs
        for m in fleet.values():
            m.obs = self.obs
        # decision-log clock: injectable (matching AdmissionQueue's
        # now_ns) so traffic replays produce deterministic /decisions
        # output; defaults to wall time, which is what
        # arrivals_from_decision_log replays
        self.now_ns = now_ns
        # optional router-quality monitor (obs/quality.py): fed per
        # routed batch (regret, selection share) on the obs-enabled
        # path, and per feedback fold through router.feedback
        self.quality = quality
        if quality is not None:
            router.quality = quality
        # with a DB mesh (launch.mesh.make_db_mesh) the dispatcher's
        # executables and both buffer replicas are capacity-sharded
        # (DESIGN.md §12); everything downstream is mesh-agnostic
        self.mesh = mesh
        self.dispatch = dispatcher or RouteDispatcher.for_router(
            router, obs=self.obs, mesh=mesh)
        # two device replicas over the router's host buffer: route on
        # the front while commits scatter into the back, then swap
        self.dbuf = DoubleBuffer(router.db, router.global_ratings,
                                 obs=self.obs, mesh=mesh)
        # optional background next-capacity bake (polled after commits)
        # so a DB grow never recompiles on the hot path
        self.prebaker = CapacityPrebaker(
            self.dispatch, router.db, obs=self.obs) if prebake else None
        # typed serve metrics (the old ad-hoc `stats` dict, now a
        # registry; the `.stats` property keeps the legacy readout)
        r = self.obs.registry
        self._m_served = r.counter("serve_requests_total",
                                   "requests served")
        self._m_steps = r.counter("serve_steps_total", "serve() batches")
        self._m_feedback = r.counter("serve_feedback_total",
                                     "online comparisons collected")
        self._m_commits = r.counter("serve_commits_total",
                                    "router commits from the serve path")
        self._m_per_model = {
            m: r.counter("serve_model_requests_total",
                         "requests served per fleet model", model=m)
            for m in fleet}
        self._sorted_costs = np.sort(np.asarray(router.costs, np.float32))
        if warmup_batch_sizes is not None:
            self.warmup(warmup_batch_sizes)

    @property
    def stats(self) -> Dict:
        """Legacy readout of the typed metrics (kept for callers of the
        pre-registry ad-hoc dict; mutations are meaningless now)."""
        return {
            "served": int(self._m_served.value),
            "feedback": int(self._m_feedback.value),
            "commits": int(self._m_commits.value),
            "per_model": {m: int(c.value)
                          for m, c in self._m_per_model.items()},
        }

    def metrics_snapshot(self) -> Dict:
        """Full JSON snapshot of this engine's telemetry scope."""
        return self.obs.registry.json_snapshot()

    def warmup(self, batch_sizes: Optional[Sequence[int]] = None) -> int:
        """Pre-bake the dispatch cache's bucket ladder (and one commit
        cycle per buffer, so the scatter/ELO-fold executables are warm
        too). Call at startup; steady-state traffic then never
        compiles. Returns the number of route executables compiled."""
        n = self.dispatch.warmup(self.dbuf.front, batch_sizes)
        for _ in range(2):  # one commit per replica bakes the scatter
            self.dbuf.commit(self.router.global_ratings)
        return n

    def warmup_generate(self, prompt_len: int,
                        batch_sizes: Optional[Sequence[int]] = None,
                        max_new: int = 2) -> None:
        """Pre-trace every fleet model's prefill/decode executables for
        the generate-bucket ladder at a fixed padded prompt length, so
        bucketed generation at steady state never compiles. (Decode
        shapes depend only on the row bucket; prefill on (bucket,
        prompt_len) — callers must pad prompts to `prompt_len`, e.g.
        via `gen_pad_len`.)"""
        if batch_sizes is not None:
            buckets = sorted({batch_bucket(n, self.gen_min_bucket,
                                           self.gen_max_bucket)
                              for n in batch_sizes})
        else:
            buckets = list(bucket_ladder(self.gen_min_bucket,
                                         self.gen_max_bucket))
        for b in buckets:
            toks = np.zeros((b, prompt_len), np.int32)
            for m in self.fleet.values():
                m.generate(toks, max_new)

    def serve(self, requests: Sequence[Request]) -> List[Response]:
        if not len(requests):
            return []   # np.stack below rejects empty lists
        obs = self.obs
        self._m_steps.inc()
        with obs.span("serve.step"):
            t0 = time.perf_counter()
            embs = np.stack([r.embedding for r in requests])
            budgets = np.asarray([r.budget for r in requests], np.float32)
            # ②/③ the whole routing hot path (similarity -> replay ->
            # budget masking in the kernel epilogue) is ONE bucketed
            # dispatch of a pre-compiled executable over the FRONT
            # buffer; the single host readout is the per-request choice
            with obs.span("serve.route"):
                choices = self.dispatch.route(self.dbuf.front, embs,
                                              budgets)
            route_dt = time.perf_counter() - t0
            if obs.enabled:
                self._emit_decisions(requests, budgets, choices)
                if self.quality is not None:
                    self.quality.observe_batch(budgets, choices)

            # ④ group by chosen model, pad to a batch, generate. Each
            # group is timed separately: a request's latency is routing
            # + its OWN group's generation, not the sum of every
            # earlier group's.
            responses: List[Response] = [None] * len(requests)  # type: ignore
            for mi, name in enumerate(self.router.model_names):
                sel = np.nonzero(choices == mi)[0]
                if sel.size == 0:
                    continue
                max_s = max(len(requests[i].tokens) for i in sel)
                rows = int(sel.size)
                if self.gen_bucket:
                    rows = batch_bucket(rows, self.gen_min_bucket,
                                        self.gen_max_bucket)
                    if self.gen_pad_len is not None:
                        max_s = max(max_s, self.gen_pad_len)
                toks = np.zeros((rows, max_s), np.int32)
                for row, i in enumerate(sel):
                    t = requests[i].tokens
                    toks[row, :len(t)] = t
                max_new = max(requests[i].max_new_tokens for i in sel)
                tg = time.perf_counter()
                with obs.span(f"serve.generate.{name}"):
                    gen = self.fleet[name].generate(toks, max_new)
                dt = route_dt + time.perf_counter() - tg
                for row, i in enumerate(sel):
                    responses[i] = Response(
                        requests[i].rid, name,
                        gen[row, :requests[i].max_new_tokens], dt)
                self._m_per_model[name].inc(int(sel.size))
            self._m_served.inc(len(requests))

            # ⑤ optional second-model comparison -> online router
            # update. Feedback and commit are timed spans now — the
            # pre-telemetry serve() never measured this leg at all, so
            # the cost of the online update was invisible.
            if self.quality_oracle is not None and self.compare_rate > 0:
                cmp_sel = self.rng.random(len(requests)) < self.compare_rate
                idxs = np.nonzero(cmp_sel)[0]
                if idxs.size:
                    a = choices[idxs]
                    b = np.asarray([self.rng.choice(
                        [m for m in range(len(self.fleet)) if m != ai])
                        for ai in a], np.int32)
                    qa = np.asarray([self.quality_oracle(embs[i], int(ai))
                                     for i, ai in zip(idxs, a)])
                    qb = np.asarray([self.quality_oracle(embs[i], int(bi))
                                     for i, bi in zip(idxs, b)])
                    outcome = np.where(qa == qb, 0.5,
                                       (qa > qb).astype(np.float32))
                    with obs.span("serve.feedback"):
                        self.router.feedback(embs[idxs], a, b, outcome)
                    self._m_feedback.inc(int(idxs.size))
                    # absorb the new rows into the BACK buffer and swap
                    # — async, so it overlaps anything still in flight
                    # on the old front (double-buffered commit protocol)
                    with obs.span("serve.commit"):
                        self.dbuf.commit(self.router.global_ratings)
                    self._m_commits.inc()
                    if self.prebaker is not None:
                        self.prebaker.poll()
        return responses

    def _emit_decisions(self, requests: Sequence[Request], budgets,
                        choices):
        """One JSONL record per routed request: the offline AUC/cost
        analysis input (chosen model, budget, feasible-set size)."""
        # feasible-set size = #models with cost <= budget, via one
        # searchsorted over the pre-sorted cost vector (O(B log M))
        feas = np.searchsorted(self._sorted_costs, budgets, side="right")
        names = self.router.model_names
        nb = len(requests)
        idx = choices.tolist()
        self.obs.events.emit_columns(
            "route", nb,
            {"ts": self.now_ns() / 1e9, "batch": nb},
            {"rid": [r.rid for r in requests],
             "model": [names[c] for c in idx],
             "model_idx": idx,
             "budget": budgets.tolist(),
             "feasible": feas.tolist()})
