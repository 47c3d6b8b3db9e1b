"""End-to-end rehearsals of bench/run.py at a tiny size on the CPU: the
cells named in bench/tests/data run through the same runners, the
checks pass, the control and every planted fault fail them, a 4-shard
configuration runs on 4 host devices from data alone, and without a
TPU the real command refuses to run."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "bench" / "tests" / "data"
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
ENV4 = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")


def _run(args, env=ENV, cwd=ROOT, script="bench/run.py", timeout=600):
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _rehearse(workload, *extra, env=ENV, script="bench/run.py", seed=11,
              seconds=2):
    p = _run(["--workload", workload, "--seed", str(seed), "--seconds",
              str(seconds), "--rehearse", "--data-dir", str(DATA), *extra],
             env=env, script=script)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_without_a_tpu_nothing_is_reported():
    p = _run(["--workload", "route.paper1m.w256", "--seed", "3",
              "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", "route.paper1m.w256", "--seed", "3",
              "--seconds", "1", "--trace", "0"], cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload,env", [
    ("tiny.route", ENV), ("tiny.route4", ENV4), ("tiny.serve", ENV)])
def test_rehearsal_is_correct(workload, env):
    r = _rehearse(workload, env=env, seconds=3 if "serve" in workload else 2)
    assert r["correct"] is True, r
    assert r["failed"] == 0 and r["attempted"] > 0
    assert "metrics" not in r            # CPU readings are never metrics
    assert list(r)[-1] == "checks"
    for name, c in r["checks"].items():
        assert c["value"] <= c["limit"], name
    if workload == "tiny.route4":
        assert r["device"]["count"] == 4


def test_traced_rehearsal_reduces_its_trace():
    r = _rehearse("tiny.route", "--trace", "1")
    assert r["correct"] is True
    assert "busy_s" not in r["device"]   # not a device reading on the CPU
    assert r["rehearsal_trace"]["busy_s"] > 0
    assert r["rehearsal_trace"]["window_s"] > 0
    assert r["breakdown"]["device_ops"]
    names = [n for n, _ in r["breakdown"]["idle_gaps"]]
    assert any(n.startswith("bench.") for n in names), names


def test_traced_sharded_rehearsal_reduces_its_trace():
    """On 4 host devices the sharded cell's history and reference search
    take the block path, and its traced run reduces all 4 devices."""
    r = _rehearse("tiny.route4", "--trace", "1", env=ENV4)
    assert r["correct"] is True, r["checks"]
    assert r["device"]["count"] == 4
    assert r["rehearsal_trace"]["busy_s"] > 0
    assert r["breakdown"]["device_ops"]
    for name in ("dispatch_idle_ms.route", "commit_idle_ms.route",
                 "commit_device_ms.shard"):
        assert name in r["rehearsal_readings"], r["rehearsal_readings"]


@pytest.mark.parametrize("workload,seconds", [("tiny.route", 1),
                                              ("tiny.serve", 3)])
def test_control_fails_where_the_program_passes(workload, seconds):
    p = _run(["--workload", workload, "--seeds", "21", "--seconds",
              str(seconds), "--rehearse", "--data-dir", str(DATA),
              "--out", os.devnull], script="bench/calibrate.py")
    assert p.returncode == 0, p.stderr[-3000:]
    rec = json.loads(p.stdout.strip().splitlines()[-1])
    lim = rec["limits"]
    assert all(rec["program"][k] <= v for k, v in lim.items()), rec
    assert any(rec["control"][k] > v for k, v in lim.items()), rec


@pytest.mark.parametrize("fault,workload,env", [
    ("state_unchanged", "tiny.route", ENV),
    ("half_batch", "tiny.route", ENV),
    ("answer_altered", "tiny.route", ENV),
    ("no_exchange", "tiny.route4", ENV4),
    ("half_batch", "tiny.serve", ENV),
    ("token_altered", "tiny.serve", ENV),
])
def test_planted_fault_is_not_correct(fault, workload, env):
    p = _run([fault, "--workload", workload, "--seed", "5", "--seconds",
              "3" if "serve" in workload else "1", "--rehearse",
              "--data-dir", str(DATA)], env=env,
             script="bench/tests/_fault_run.py")
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is False, r["checks"]
