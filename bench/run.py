"""Benchmark of the Eagle router on the chip.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
BENCHMARK.json, its configuration in bench/configs/<config>.json, its
traffic mix in bench/traffic/<traffic>.json (whose "kind" picks the
general runner: a closed routing loop or the open-loop served path), the
cell's limits of the correctness check in bench/workloads/<cell>.json,
and each per-layer metric in bench/metrics/<metric>.py.

A run makes its data and weights from --seed, warms up every shape the
cell uses (set-up), measures for --seconds, then checks what the timed
path produced against the plain reference and prints one JSON line. With
--trace 1 it records a steady stretch of the window with the profiler
and reports the per-layer metrics instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. --rehearse runs a tiny configuration on
the CPU for the tests; its readings are never device metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: compile cache inside the checkout, at a fixed path (part of the key)
CACHE_DIR = ROOT / ".jax_cache"
#: stand-in peaks for a CPU rehearsal (never reported as device metrics)
REHEARSAL_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                   "hbm_bytes": 1 << 34, "source": "rehearsal"}


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU run for the tests (no device metrics)")
    ap.add_argument("--data-dir", default=None,
                    help="where BENCHMARK.json, configs/ and workloads/ "
                         "are read (default: the checkout)")
    return ap.parse_args(argv)


# ---------------------------------------------------------------------------
# finding a cell's parts by name
# ---------------------------------------------------------------------------

def load_cell(workload: str, data_dir: Path, manifest_path: Path):
    manifest = json.loads(manifest_path.read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in {manifest_path}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    conf_entry = configs[cell["config"]]
    cfg = json.loads((data_dir / conf_entry["file"]).read_text())
    traffic = json.loads(
        (data_dir / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    traffic["limits"] = json.loads(
        (data_dir / "bench" / "workloads" / f"{workload}.json").read_text()
    )["limits"]
    return manifest, cell, cfg, traffic


def applies(entry: dict, workload: str, reported=()) -> bool:
    ws = entry.get("workloads")
    if ws is not None:
        return workload in ws
    return entry.get("moves") in reported if "moves" in entry else True


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_runner(kind: str):
    if kind == "route":
        from bench.lib.route_cell import RouteCell
        return RouteCell
    if kind == "serve":
        from bench.lib.serve_cell import ServeCell
        return ServeCell
    raise SystemExit(f"unknown traffic kind {kind!r}")


# ---------------------------------------------------------------------------

def _num(x):
    return "inf" if isinstance(x, float) and not math.isfinite(x) else x


def device_info(jax, chips: int):
    devs = jax.devices()[:max(chips, 1)]
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def main(argv=None) -> int:
    args = parse(argv)
    data_dir = Path(args.data_dir).resolve() if args.data_dir else ROOT
    manifest_path = data_dir / "BENCHMARK.json"
    if not (ROOT / "src" / "repro").is_dir():
        log("bench: the program (src/repro) is not in this checkout; "
            "nothing was run")
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    manifest, cell, cfg, traffic = load_cell(args.workload, data_dir,
                                             manifest_path)
    if not args.rehearse:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    devs = jax.devices()
    if not args.rehearse:
        if devs[0].platform != "tpu":
            log(f"bench: JAX found no TPU (platform {devs[0].platform!r}); "
                "nothing was run")
            return 1
        if len(devs) < cell["chips"]:
            log(f"bench: {args.workload} needs {cell['chips']} chips, "
                f"found {len(devs)}; nothing was run")
            return 1
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
    from bench.lib.peaks import UnknownDevice, peaks_for
    from bench.lib.tracing import host_memory
    try:
        peaks = peaks_for(devs[0].device_kind)
    except UnknownDevice:
        if not args.rehearse:
            raise
        peaks = REHEARSAL_PEAKS
    from repro.core.dispatch import CompileCounter

    hits = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: hits.append(name)
        if name == "/jax/compilation_cache/cache_hits" else None)
    cc_setup = CompileCounter()
    log(f"phase imports and device: {time.perf_counter() - T_START:.2f} s")
    runner = cell_runner(traffic["kind"])(cfg, traffic, args.seed, log)
    runner.setup(args.seconds)
    setup_s = time.perf_counter() - T_START
    log(f"setup_s {setup_s:.3f} ({cc_setup.delta()} compiles, "
        f"{len(hits)} of them from the persistent cache); {host_memory()}")

    tracer = None
    tmp = None
    if args.trace:
        from bench.lib.tracing import Tracer
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        length = min(traffic["trace_seconds"], args.seconds)
        tracer = Tracer(tmp, (args.seconds - length) / 2, length)
    with CompileCounter() as cc:
        runner.run_window(args.seconds, tracer)
    log(f"compiles inside the window: {cc.count}")
    e2e, attempted = runner.end_to_end()
    for name, (v, unit) in e2e.items():
        log(f"{name} {v} {unit}")
    # report the end-to-end metrics the manifest gives this cell
    e2e = {k: v for k, v in e2e.items()
           if any(e["name"] == k and applies(e, args.workload)
                  for e in manifest["end_to_end"])}
    failed = runner.failed() if hasattr(runner, "failed") else 0
    dev = device_info(jax, cell["chips"])
    log(f"memory_peak_bytes {dev['memory_peak_bytes']}")

    if args.trace:
        from bench.lib.tracing import Trace
        trace = Trace.load(tmp)
        shutil.rmtree(tmp, ignore_errors=True)
        ctx = dict(trace=trace, cfg=cfg, traffic=traffic, peaks=peaks,
                   counters=runner.counters(tracer))
        reported = set(e2e) | {"setup_s"}
        metrics = {}
        for entry in manifest["per_layer"]:
            if not applies(entry, args.workload, reported):
                continue
            v = load_reader(entry["name"]).read(ctx)
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
        dev["busy_s"] = trace.busy_s()
        dev["window_s"] = trace.window_s
        breakdown = {"device_ops": trace.top_ops(10),
                     "idle_gaps": trace.idle_gaps(10)}
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for name, (v, unit) in e2e.items():
            metrics[name] = {"value": v, "unit": unit}
        breakdown = None

    t_ref = time.perf_counter()
    runner.finish()
    nums = runner.numbers()
    limits = traffic["limits"]
    checks = {k: {"value": _num(float(nums[k])), "limit": limits[k]}
              for k in limits}
    checks["failed"] = {"value": int(failed), "limit": 0}
    correct = failed == 0 and all(nums[k] <= limits[k] for k in limits)
    log(f"reference and checks took {time.perf_counter() - t_ref:.2f} s; "
        f"{host_memory()}")
    rehearsal_trace = None
    if args.rehearse:
        rehearsal_trace = {k: dev.pop(k) for k in ("busy_s", "window_s")
                           if k in dev}
        dev.pop("memory_peak_bytes")
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed)}
    out["metrics" if not args.rehearse else "rehearsal_readings"] = metrics
    out["device"] = dev
    if breakdown is not None:
        out["breakdown"] = breakdown
    if rehearsal_trace:
        out["rehearsal_trace"] = rehearsal_trace
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
