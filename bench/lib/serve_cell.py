"""Open-loop served cell: requests arrive on a stratified Poisson schedule
fixed by the seed, AdmissionQueue coalesces them into windows and
ServingEngine.serve routes, generates, folds feedback and commits. Each
request is timed from its due time until the serve call that answered
it returned its tokens to the host; a request that is rejected or
unfinished counts as missing.
"""
from __future__ import annotations

import dataclasses
import importlib
import time
import zlib

import numpy as np

from bench.lib import traffic as TR
from bench.lib.router_side import RouteSample, RouterSide
from bench.lib.tracing import phase, span

#: how long after the window the run waits for the window's requests
DRAIN_LIMIT_S = 60.0


def _oracle(emb, mi) -> float:
    """Simulated answer quality, deterministic in (prompt, model)."""
    return float(np.random.default_rng(
        [zlib.crc32(np.asarray(emb, np.float32).tobytes()), int(mi)]
    ).random())


def reference_module(reference: dict):
    """The plain reference of the checked member: the module under
    bench/lib/ that the configuration's `fleet.reference.module` names.
    It exposes init_params(reference, seed) and served_token_gaps(
    reference, params, prompts, served, control)."""
    return importlib.import_module(f"bench.lib.{reference['module']}")


class ServeCell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, log):
        self.cfg, self.traffic, self.seed, self.log = cfg, traffic, seed, log

    # -- set-up -------------------------------------------------------------------
    def setup(self, seconds: float):
        from repro import obs as OBS
        from repro.configs import get_config, get_reduced_config
        from repro.serving.admission import AdmissionQueue
        from repro.serving.engine import FleetModel, Request, ServingEngine

        t, f = self.traffic, self.cfg["fleet"]
        self.w = t["window"]
        n = max(1, int(round(t["rate_hz"] * seconds)))
        n_warm = t["warmup_windows"] * self.w
        self.side = RouterSide(self.cfg, self.seed, n + n_warm,
                               t["query_noise"], self.log)
        side = self.side
        rng = TR.rng_for(self.seed, 7)
        plen = t["prompt_len"]
        toks = rng.integers(0, t["token_ids_below"], (n + n_warm, plen),
                            dtype=np.int64).astype(np.int32)
        lo = t["output_lognormal"]
        new = TR.stratified_lognormal_ints(n, lo["median"], lo["sigma"],
                                           lo["min"], lo["max"], rng)
        bud = TR.budgets(n, t["budgets"], rng)
        gaps = TR.stratified_poisson_gaps(t["rate_hz"], n, rng)
        self.due_s = np.cumsum(gaps) - gaps[0]
        self.max_new = new
        self.n = n

        fleet = {}
        self.member_seeds = {}
        t_fleet = time.perf_counter()
        for i, name in enumerate(f["names"]):
            full = f["members"][name] == "published"
            ms = TR.seed32(self.seed, 10 + i)
            self.member_seeds[name] = ms
            fleet[name] = FleetModel(
                get_config(name) if full else get_reduced_config(name),
                seed=ms, max_len=f["max_len"])
        # the checked member serves the benchmark's weights, which its
        # reference can make again (bench/lib/olmo_ref.py says why)
        checked = f["checked"]
        self.ref = reference_module(f["reference"])
        fleet[checked].params = self.ref.init_params(
            f["reference"], self.member_seeds[checked])
        self.log(f"phase fleet initialised: "
                 f"{time.perf_counter() - t_fleet:.2f} s")
        self.obs = OBS.Observability(enabled=False)
        t_eng = time.perf_counter()
        engine = ServingEngine(
            fleet, side.router, compare_rate=t["compare_rate"],
            seed=TR.seed32(self.seed, 8), quality_oracle=_oracle,
            gen_bucket=True, gen_min_bucket=self.w, gen_max_bucket=self.w,
            gen_pad_len=plen, obs=self.obs, mesh=side.mesh())
        self.engine = engine
        self.log(f"phase engine built, DB replicas uploaded: "
                 f"{time.perf_counter() - t_eng:.2f} s")
        self.queue = AdmissionQueue.for_engine(
            engine, window_bucket=self.w, max_wait_ms=t["max_wait_ms"],
            shed_watermark=t["shed_watermark"], reject_cap=t["reject_cap"])
        self.samples = []
        self.serve_ns = 0
        self._sampling = False
        serve = engine.serve

        def serve_logged(reqs):
            size, folds = side.router.db.size, side.fb.count
            t_call = time.perf_counter_ns()
            with span("bench.serve"):
                out = serve(reqs)
            if self._sampling:
                self.serve_ns += time.perf_counter_ns() - t_call
                names = side.router.model_names
                self.samples.append(RouteSample(
                    np.stack([r.embedding for r in reqs]),
                    np.asarray([r.budget for r in reqs], np.float32),
                    np.asarray([names.index(o.model) for o in out]), None,
                    size, folds))
            return out

        self.queue.serve = serve_logged
        self.requests = [Request(tokens=toks[i], embedding=side.queries[i],
                                 budget=float(bud[i]),
                                 max_new_tokens=int(new[i]), rid=i)
                         for i in range(n)]

        # warm every shape the window uses: route buckets of a partial
        # and a full window, each member's prefill and decode at the
        # window bucket, the feedback fold and the commit scatter
        with phase(self.log, "route executables warmed"):
            engine.warmup([1, self.w])
        with phase(self.log, "prefill and decode warmed"):
            engine.warmup_generate(plen, [self.w], max_new=2)
        t_warm = time.perf_counter()
        k = n
        while engine.stats["commits"] < 2 or k < n + n_warm:
            batch = [Request(tokens=toks[j % len(toks)],
                             embedding=side.queries[j % len(toks)],
                             budget=float(bud[j % n]), max_new_tokens=2,
                             rid=-1 - j)
                     for j in range(k, k + self.w)]
            engine.serve(batch)
            k += self.w
        import jax
        jax.block_until_ready(engine.dbuf.front)
        self.log(f"phase warm-up serves: {time.perf_counter() - t_warm:.2f} s")
        self.log(f"setup: DB {side.router.db.size} rows, fleet "
                 f"{list(fleet)}, {n} requests at {t['rate_hz']} req/s")

    # -- the measured window ------------------------------------------------------
    def run_window(self, seconds: float, tracer=None):
        q = self.queue
        self.obs.enabled = self.obs.tracer.enabled = tracer is not None
        self._sampling = True
        t0 = time.perf_counter_ns()
        due_ns = t0 + (self.due_s * 1e9).astype(np.int64)
        end = np.zeros(self.n, np.int64)
        wait_us = np.full(self.n, np.nan)
        late_ns = np.zeros(self.n, np.int64)
        served = {}
        rejected = 0
        i = 0
        limit = t0 + int((seconds + DRAIN_LIMIT_S) * 1e9)
        while True:
            now = time.perf_counter_ns()
            if tracer is not None:
                tracer.poll((now - t0) / 1e9)
            while i < self.n and due_ns[i] <= now:
                r = dataclasses.replace(self.requests[i],
                                        arrival_ns=int(due_ns[i]))
                late_ns[i] = now - due_ns[i]
                if q.submit(r) is not None:
                    rejected += 1
                i += 1
            batch = q.flush_due(now)
            if batch:
                t_ret = time.perf_counter_ns()
                for c in batch:
                    end[c.rid] = t_ret
                    wait_us[c.rid] = c.wait_us
                    served[c.rid] = c.response
                continue
            if i >= self.n and q.depth == 0:
                break
            if now > limit:
                break
            nxt = [due_ns[i]] if i < self.n else []
            fl = q.next_flush_ns()
            if fl is not None:
                nxt.append(fl)
            if nxt:
                with span("bench.await_arrival"):
                    time.sleep(max(0.0, min(min(nxt) - now, 2_000_000) / 1e9))
        if tracer is not None:
            tracer.stop()
        self._sampling = False
        self.obs.enabled = self.obs.tracer.enabled = False
        self.t0, self.end, self.wait_us = t0, end, wait_us
        self.due_ns, self.served, self.rejected = due_ns, served, rejected
        self.t_give_up = time.perf_counter_ns()
        # the loop submits between serve calls: a request due while one
        # runs is submitted late, stamped with its due time
        self.log("generator lateness ms (p50/p95/max): " + "/".join(
            f"{x:.1f}" for x in np.percentile(late_ns[:i] / 1e6, [50, 95, 100])))

    def _ok(self):
        return np.asarray([
            self.end[i] > 0 and i in self.served
            and self.served[i].tokens.shape == (int(self.max_new[i]),)
            for i in range(self.n)])

    def failed(self) -> int:
        return int((~self._ok()).sum())

    def _e2e_ms(self, ok):
        """Each request's time from its due time to its last token on
        the host; one never answered in full counts until the give-up."""
        return np.where(ok, self.end - self.due_ns,
                        self.t_give_up - self.due_ns) / 1e6

    def end_to_end(self):
        ok = self._ok()
        e2e = self._e2e_ms(ok)
        t_last = self.end[ok].max() if ok.any() else self.t_give_up
        toks = float(self.max_new[ok].sum())
        # the manifest reports the token rate (completed tokens over the
        # window and its drain); the median is a per-layer reading
        # (bench/metrics/e2e_p50_ms.serve.py), the mean and tail printed
        return {"e2e_p50_ms": (float(np.percentile(e2e, 50)), "ms"),
                "e2e_mean_ms": (float(e2e.mean()), "ms"),
                "e2e_p95_ms": (float(np.percentile(e2e, 95)), "ms"),
                "out_tok_s": (toks / ((t_last - self.t0) / 1e9),
                              "tokens/s")}, self.n

    def counters(self, tracer=None):
        """What the per-layer readers of a served cell read: queue
        waits, the engine's spans, the dispatcher's row counters, and
        per flush the rows each member generated."""
        gen_rows = gen_padded = 0
        for smp in self.samples:
            per = np.bincount(smp.choices,
                              minlength=len(self.cfg["fleet"]["names"]))
            gen_rows += int(per.sum())
            gen_padded += int((per > 0).sum()) * self.w
        ok = self._ok()
        return {"wait_us": self.wait_us[~np.isnan(self.wait_us)],
                "spans": self.obs.tracer.spans(),
                "dispatch": self.engine.dispatch.telemetry(),
                "gen_rows": gen_rows, "gen_padded": gen_padded,
                "flush_sizes": [len(smp.choices) for smp in self.samples],
                "serve_s": self.serve_ns / 1e9,
                "served": self.served, "max_new": self.max_new,
                "e2e_ms": self._e2e_ms(ok),
                "t0_ns": self.t0,
                "t_last_ns": int(self.end[ok].max()) if ok.any() else
                self.t_give_up}

    # -- after the window -----------------------------------------------------------
    def finish(self):
        side = self.side
        self.g_program = np.asarray(side.router.global_ratings)
        self.state_read = side.read_state(self.engine.dbuf.front, 0)
        self.grew = side.router.db.capacity != side.capacity
        self.full_name = self.cfg["fleet"]["checked"]
        self.full_cfg = dict(self.cfg["fleet"]["reference"])
        self.full_seed = self.member_seeds[self.full_name]
        self.gen_sample = self._sample_generated()
        # the telemetry scope's gauges hold the queue, and through it
        # the engine and its weights: drop it with them
        self.engine = self.queue = self.obs = None
        side.free_program()

    def _sample_generated(self):
        """Requests the full-width member answered: the longest, and
        others drawn from the seed up to the sample size."""
        mine = [i for i, r in self.served.items()
                if r.model == self.full_name]
        k = self.traffic["check_requests"]
        if not mine:
            return []
        mine.sort()
        longest = max(mine, key=lambda i: (int(self.max_new[i]), -i))
        rest = [i for i in mine if i != longest]
        rng = TR.rng_for(self.seed, 9)
        pick = [longest] + list(rng.permutation(rest)[:k - 1])
        return [(self.requests[i].tokens, self.served[i].tokens)
                for i in pick]

    def numbers(self, control: bool = False):
        nums = self.side.numbers(self.samples, self.g_program,
                                 self.state_read, control=control)
        if self.grew:
            nums["state_gap"] = float("inf")
        if not self.gen_sample:
            nums["logit_gap"] = float("inf")
            return nums
        t_ref = time.perf_counter()
        params = self.ref.init_params(self.full_cfg, self.full_seed)
        t = max(len(s) for _, s in self.gen_sample)
        prompts = np.stack([p for p, _ in self.gen_sample])
        served = np.full((len(self.gen_sample), t), -1, np.int64)
        for j, (_, s) in enumerate(self.gen_sample):
            served[j, :len(s)] = s
        gaps = self.ref.served_token_gaps(self.full_cfg, params, prompts,
                                          served, control)
        nums["logit_gap"] = float(gaps.max())
        self.log(f"phase reference forward ({len(self.gen_sample)} "
                 f"requests): {time.perf_counter() - t_ref:.2f} s")
        return nums
