"""Queueing model of a served cell, on the CPU, for sizing its bound.

  python3 bench/queue_model.py --workload serve.olmo1b.poisson --seeds 40

Draws each seed's arrivals, output lengths and budgets exactly as
bench/lib/serve_cell.py does, then replays them through a model of the
open loop: requests due while a serve call runs wait for it; a flush
takes up to a window of the queue; a call lasts a fixed overhead plus,
for each fleet member that got requests, its longest output times the
member's time per decode step. Requests under every cost go to the
cheapest member, the rest to a member drawn from the seed (the router's
choice is not modelled). It prints the quartile spread over the median,
across seeds, of the end-to-end median, mean and 95th percentile, with
each seed's own order and with one fixed order. The step times are
readings of the chip (bench/run.py --trace 1), not measurements made here.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.lib import traffic as TR  # noqa: E402

#: seconds per decode step of the checked member and of a stand-in, and
#: the per-call overhead (route, prefill, feedback, commit), on a v5e
STEP_S, STANDIN_STEP_S, CALL_S = 0.0186, 0.005, 0.15


def latencies(traffic: dict, seed: int, order_seed: int, seconds: float,
              members: int) -> np.ndarray:
    t = traffic
    n = max(1, int(round(t["rate_hz"] * seconds)))
    rng = TR.rng_for(order_seed, 7)
    rng.integers(0, t["token_ids_below"], (n, t["prompt_len"]))
    lo = t["output_lognormal"]
    new = TR.stratified_lognormal_ints(n, lo["median"], lo["sigma"],
                                       lo["min"], lo["max"], rng)
    bud = TR.budgets(n, t["budgets"], rng)
    gaps = TR.stratified_poisson_gaps(t["rate_hz"], n, rng)
    due = np.cumsum(gaps) - gaps[0]
    below = bud < t["budgets"]["uniform"][0]
    member = np.where(below, 0,
                      TR.rng_for(seed, 99).integers(0, members, n))
    clock, i, end = 0.0, 0, np.zeros(n)
    while i < n:
        clock = max(clock, due[i])
        j = i
        while j < n and due[j] <= clock and j - i < t["window"]:
            j += 1
        call = CALL_S
        for m in range(members):
            lens = new[i:j][member[i:j] == m]
            if len(lens):
                call += lens.max() * (STEP_S if m == 0 else STANDIN_STEP_S)
        clock += call
        end[i:j] = clock
        i = j
    return (end - due) * 1e3


def spread(x) -> float:
    q = statistics.quantiles(x, n=4)
    return (q[2] - q[0]) / statistics.median(x)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=40)
    ap.add_argument("--seconds", type=float, default=50.0)
    args = ap.parse_args(argv)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in manifest["workloads"]}[args.workload]
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    members = len(json.loads((ROOT / conf["file"]).read_text())
                  ["fleet"]["names"])
    traffic = json.loads((ROOT / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    for label, fixed in (("each seed's order", False), ("one order", True)):
        stats = {"p50": [], "mean": [], "p95": []}
        for seed in range(1, args.seeds + 1):
            lat = latencies(traffic, seed, 0 if fixed else seed,
                            args.seconds, members)
            stats["p50"].append(np.percentile(lat, 50))
            stats["mean"].append(lat.mean())
            stats["p95"].append(np.percentile(lat, 95))
        print(json.dumps({"order": label, **{
            k: {"median_ms": round(statistics.median(v), 1),
                "spread_pct": round(100 * spread(v), 2)}
            for k, v in stats.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
