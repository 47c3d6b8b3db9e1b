"""chip_smoke.py on the CPU: it must refuse to report without a TPU,
and its two phases are rehearsed here at a tiny size with the Pallas
kernels interpreted (the chip runs them compiled at full size). The
persistent compilation cache of the entry points is checked here too:
its fixed in-checkout path is read back by a second run."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

TINY = dict(dim=64, db_rows=2048, n_fleet=2, full_width=(), batch=8,
            batches=2, prompt_len=8, max_new=2, agree_q=32)


def _python(*args, cwd=REPO, env_extra=None, drop=()):
    """Run python on the CPU in a fresh process (host-device counts and
    cache settings are fixed per process)."""
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"),
               **(env_extra or {}))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def _no_result(r):
    return r.returncode != 0 and '"ok"' not in r.stdout


def test_refuses_without_tpu():
    assert _no_result(_python(str(REPO / "chip_smoke.py")))


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    r = _python(str(tmp_path / "chip_smoke.py"), cwd=tmp_path,
                drop=("PYTHONPATH",))
    assert _no_result(r)


def test_serve_phase_rehearsal():
    """route -> generate -> feedback -> commit through ServingEngine,
    interpreted kernels vs the reference backend under the DESIGN.md
    §13 contract, and nothing compiles after warm-up."""
    rep = chip_smoke.serve_phase(0, backend="pallas_interpret", **TINY)
    assert rep["ok"], rep["failed"]
    assert rep["answered"] == 16 and rep["per_model"]["olmo-1b"] >= 6
    assert rep["post_warmup_compiles"] == 0
    assert rep["agreement"]["unexplained"] == 0


def test_sharded_phase_rehearsal():
    """The --chips 4 phase on four host devices: sharded choices and
    topk_idx bitwise equal to the single-device route before and after
    an owner-scatter commit that touched every shard."""
    r = _python("-c", "import jax, json, sys\n"
                "jax.config.update('jax_num_cpu_devices', 4)\n"
                f"sys.path.insert(0, {str(REPO)!r})\n"
                "import chip_smoke\n"
                "rep = chip_smoke.sharded_phase(0, dim=32, db_rows=4096, "
                "batch=16, batches=2, backend='pallas_interpret')\n"
                "print(json.dumps(rep))")
    assert r.returncode == 0, r.stderr[-3000:]
    rep = json.loads(r.stdout.splitlines()[-1])
    assert rep["ok"], rep
    assert rep["before_commit"]["rows"] == rep["after_commit"]["rows"] == 32


_CACHE_RUN = (
    "import jax, json, sys\n"
    f"sys.path.insert(0, {str(REPO)!r})\n"
    "hits = []\n"
    "jax.monitoring.register_event_listener(lambda e, **k: hits.append(e)"
    " if e == '/jax/compilation_cache/cache_hits' else None)\n"
    "from repro.launch.compile_cache import enable_compile_cache\n"
    "path = enable_compile_cache()\n"
    "import chip_smoke\n"
    f"chip_smoke.serve_phase(0, backend='reference', **{TINY!r})\n"
    "print(json.dumps({'path': path, 'hits': len(hits), 'dir': "
    "jax.config.jax_compilation_cache_dir}))")


def test_second_run_reads_the_in_checkout_cache():
    runs = []
    for _ in range(2):
        r = _python("-c", _CACHE_RUN, drop=("JAX_COMPILATION_CACHE_DIR",))
        assert r.returncode == 0, r.stderr[-3000:]
        runs.append(json.loads(r.stdout.splitlines()[-1]))
    assert runs[1]["path"] == runs[1]["dir"] == str(REPO / ".jax_cache")
    assert runs[1]["hits"] > 0


def test_env_cache_dir_is_the_only_path(tmp_path):
    r = _python("-c", "import jax, json\n"
                "from repro.launch.compile_cache import enable_compile_cache\n"
                "path = enable_compile_cache()\n"
                "jax.jit(lambda x: x * 2)(1.0)\n"
                "print(json.dumps([path, jax.config.jax_compilation_cache_dir"
                "]))", env_extra={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr[-3000:]
    assert json.loads(r.stdout.splitlines()[-1]) == [str(tmp_path)] * 2
    assert any(tmp_path.iterdir())
