"""Readings that the limits of the correctness check are set from.

  python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 5

For each seed, in one process: make the cell from the seed, run a short
window at the cell's own load, and read every compared number twice:
once for the program's timed path, once for the control, which is the
plain reference computed one precision below the configuration's (the
bfloat16 panel with a three-pass dot and bfloat16 ratings; fp8 matmuls
for the served model) put in the program's place. A limit sits above
the program's largest reading and below the control's smallest.

The benchmark's own runs never run the control. Prints one JSON line per
seed, and appends them to the file --out names, if any.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run as RUN

    data_dir = Path(args.data_dir).resolve() if args.data_dir else ROOT
    _, cell, cfg, traffic = RUN.load_cell(args.workload, data_dir,
                                          data_dir / "BENCHMARK.json")
    import jax
    if not args.rehearse:
        if jax.devices()[0].platform != "tpu":
            RUN.log("calibrate: JAX found no TPU; nothing was run")
            return 1
        jax.config.update("jax_compilation_cache_dir", str(RUN.CACHE_DIR))
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    runner_cls = RUN.cell_runner(traffic["kind"])
    with open(args.out if args.out else os.devnull, "a") as f:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t0 = time.perf_counter()
            d = runner_cls(cfg, traffic, seed, RUN.log)
            d.setup(args.seconds)
            d.run_window(args.seconds)
            e2e, attempted = d.end_to_end()
            failed = d.failed() if hasattr(d, "failed") else 0
            d.finish()
            rec = {"workload": args.workload, "seed": seed,
                   "attempted": attempted, "failed": failed,
                   "e2e": {k: v[0] for k, v in e2e.items()},
                   "program": d.numbers(),
                   "control": d.numbers(control=True),
                   "limits": traffic["limits"],
                   "seconds": time.perf_counter() - t0}
            line = json.dumps(rec, default=lambda x: str(x))
            print(line, flush=True)
            f.write(line + "\n")
            del d
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
