"""Knee sweep of a served cell: its traffic at each of a list of offered
rates, in one process, a fresh cell each, one JSON line per rate.

  python3 bench/knee_sweep.py --workload serve.olmo1b.poisson \
      --seed 4242 --seconds 30 --rates 6,7,8,9

Per rate it prints the requests due in the window, how many of them were
still unanswered when it closed (the backlog), the end-to-end median and
95th percentile over all of them, the mean flush and the mean serve call.
The knee is the lowest rate from which the backlog passes two windows and
grows with the rate. A served cell's traffic file fixes its rate_hz from
this by hand (0.8 of the knee); bench/run.py never searches for one.
Without a TPU it exits non-zero, as bench/run.py does; --rehearse runs a
tiny configuration on the CPU (with --data-dir bench/tests/data).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as RUN  # noqa: E402


def sweep_rate(cfg, traffic, rate, seed, seconds):
    import numpy as np
    from bench.lib.serve_cell import ServeCell
    cell = ServeCell(cfg, dict(traffic, rate_hz=rate), seed, RUN.log)
    cell.setup(seconds)
    cell.run_window(seconds)
    e2e, n = cell.end_to_end()
    closed = cell.t0 + int(seconds * 1e9)
    due = cell.due_ns < closed
    backlog = int(((cell.end == 0) | (cell.end > closed))[due].sum())
    c = cell.counters()
    flushes = c["flush_sizes"]
    rec = {"rate_hz": rate, "requests": n, "failed": cell.failed(),
           "backlog_at_close": backlog,
           **{k: v for k, (v, _) in e2e.items()},
           "flushes": len(flushes),
           "mean_flush": float(np.mean(flushes)) if flushes else 0.0,
           "mean_serve_s": c["serve_s"] / max(len(flushes), 1)}
    cell.engine = cell.queue = cell.obs = None
    cell.side.free_program()
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="offered rates in req/s, comma-separated")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--data-dir", default=None)
    args = ap.parse_args(argv)
    data_dir = Path(args.data_dir).resolve() if args.data_dir else ROOT
    _, _, cfg, traffic = RUN.load_cell(args.workload, data_dir,
                                       data_dir / "BENCHMARK.json")
    if traffic["kind"] != "serve":
        raise SystemExit(f"{args.workload} is not a served cell")
    if not args.rehearse:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(RUN.CACHE_DIR)
    import jax
    if not args.rehearse:
        if jax.devices()[0].platform != "tpu":
            RUN.log("knee_sweep: JAX found no TPU; nothing was run")
            return 1
        jax.config.update("jax_compilation_cache_dir", str(RUN.CACHE_DIR))
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    for rate in (float(x) for x in args.rates.split(",")):
        rec = sweep_rate(cfg, traffic, rate, args.seed, args.seconds)
        print(json.dumps(rec), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
