"""Closed-loop routing cell: back-to-back windows of W requests through
RouteDispatcher.route_result on DoubleBuffer.front; on a share of each
window's requests a comparison is folded through EagleRouter.feedback,
then one DoubleBuffer.commit. Each request is timed from the moment its
window is handed to the dispatcher until its choice is on the host.
"""
from __future__ import annotations

import time

import numpy as np

from bench.lib import traffic as TR
from bench.lib.router_side import RouteSample, RouterSide
from bench.lib.tracing import phase, span


class RouteCell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, log):
        self.cfg, self.traffic, self.seed, self.log = cfg, traffic, seed, log

    # -- set-up ---------------------------------------------------------------
    def setup(self, seconds: float = 0.0):
        import jax
        from repro.core.dispatch import RouteDispatcher
        from repro.core.state import DoubleBuffer

        t = self.traffic
        w, pool = t["window"], t["pool_windows"]
        self.w = w
        self.side = RouterSide(self.cfg, self.seed, w * pool, t["query_noise"],
                               self.log)
        side = self.side
        m = side.n_models
        rng = TR.rng_for(self.seed, 4)
        self.q_pool = side.queries.reshape(pool, w, side.dim)
        lo, hi = t["budget_uniform"]
        self.b_pool = TR.stratified_uniform(pool * w, lo, hi, rng).reshape(
            pool, w)
        self.n_fb = int(round(t["compare_rate"] * w))
        self.opp_pool = rng.integers(1, m, (pool, self.n_fb))
        self.out_pool = rng.choice(np.asarray([0.0, 0.5, 1.0], np.float32),
                                   (pool, self.n_fb))
        mesh = side.mesh()
        router = side.router
        with phase(self.log, "two DB replicas uploaded"):
            self.dbuf = DoubleBuffer(router.db, router.global_ratings,
                                     mesh=mesh)
            jax.block_until_ready(self.dbuf.front)
        with phase(self.log, "route executable warmed"):
            self.dispatch = RouteDispatcher.for_router(router, mesh=mesh)
            self.dispatch.warmup(self.dbuf.front, [w])
        self.k = 0
        with phase(self.log, "warm-up windows"):
            for _ in range(t["warmup_windows"]):
                self.step()
            jax.block_until_ready(self.dbuf.front)
        self.log(f"setup: DB {router.db.size} rows of capacity "
                 f"{router.db.capacity}, D={side.dim}, {m} models")

    # -- one window ------------------------------------------------------------
    def step(self, sample: bool = False):
        side, router = self.side, self.side.router
        j = self.k % self.q_pool.shape[0]
        q, bud = self.q_pool[j], self.b_pool[j]
        size, folds = router.db.size, side.fb.count
        t_hand = time.perf_counter()
        with span("bench.route"):
            choices, topk = self.dispatch.route_result(self.dbuf.front, q, bud)
        t_done = time.perf_counter()
        with span("bench.feedback"):
            nf = self.n_fb
            a = choices[:nf].astype(np.int32)
            b = ((a + self.opp_pool[j]) % side.n_models).astype(np.int32)
            router.feedback(q[:nf], a, b, self.out_pool[j])
        with span("bench.commit"):
            self.dbuf.commit(router.global_ratings)
        self.k += 1
        s = RouteSample(q, bud, choices, topk, size, folds) if sample else None
        return t_hand, t_done, time.perf_counter(), s

    # -- the measured window ----------------------------------------------------
    def run_window(self, seconds: float, tracer=None):
        """Windows back to back until `seconds` have passed. Returns the
        per-window timings and the sampled decisions."""
        rng = TR.rng_for(self.seed, 5)
        keep = self.traffic["check_windows"]
        samples = []
        rows = []
        self.fb_start = self.side.fb.count
        t0 = time.perf_counter()
        j = 0
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            if tracer is not None:
                tracer.poll(now - t0)
            # reservoir sample of `keep` windows, fixed by the seed
            r = j if j < keep else int(rng.integers(0, j + 1))
            t_hand, t_done, t_host, s = self.step(sample=r < keep)
            if r < keep:
                if j < keep:
                    samples.append(s)
                else:
                    samples[r] = s
            rows.append((t_hand, t_done, t_host))
            j += 1
        t_end = rows[-1][2] if rows else time.perf_counter()
        if tracer is not None:
            tracer.stop()
        self.samples = samples
        self.timing = np.asarray(rows)
        self.t0, self.t_end = t0, t_end
        return self.timing

    def end_to_end(self):
        tm = self.timing
        lat_ms = (tm[:, 1] - tm[:, 0]) * 1e3
        n_req = len(tm) * self.w
        dur = self.t_end - self.t0
        return {"route_rps": (n_req / dur, "req/s"),
                "route_p95_ms": (float(np.percentile(np.repeat(lat_ms, self.w),
                                                     95)), "ms")}, n_req

    def counters(self, tracer=None):
        """Per-window host timings; with a tracer, only the windows
        handed to the dispatcher inside the traced stretch."""
        tm = self.timing
        if tracer is not None and tracer.t_start is not None:
            inside = (tm[:, 0] >= tracer.t_start) & (tm[:, 2] <= tracer.t_stop)
            tm = tm[inside]
        return {"windows": len(tm), "window_rows": self.w,
                "route_ms": (tm[:, 1] - tm[:, 0]) * 1e3,
                "host_ms": (tm[:, 2] - tm[:, 1]) * 1e3}

    # -- after the window ---------------------------------------------------------
    def finish(self):
        """Read what the checks need from the program's state, then free
        it, so the reference runs with the chip to itself."""
        side = self.side
        self.g_program = np.asarray(side.router.global_ratings)
        self.state_read = side.read_state(self.dbuf.front, self.fb_start)
        self.grew = side.router.db.capacity != side.capacity
        self.dbuf = self.dispatch = None
        side.free_program()

    def numbers(self, control: bool = False):
        nums = self.side.numbers(self.samples, self.g_program,
                                 self.state_read, control=control)
        if self.grew:   # a regrown DB changed every shape: no sound run
            nums["state_gap"] = float("inf")
        return nums
